"""Layer: kernels.  Device trace, first device: time per step in the
operations under `flash.fwd`, the flash attention's forward kernel, in the
forward pass and again where the backward pass recomputes a layer's inside
(`flash_remat_ms` is that part), whoever calls it, in ms."""
import pass_scopes


def read(run):
    return pass_scopes.scope_ms(run["trace"], (pass_scopes.FLASH_FWD,))
