"""Layer: kernels.  Device trace, first device: time per step in the
operations under `flash.dq`, the flash attention's backward kernel that
walks a query block's key blocks and writes dq, whoever calls it, in ms."""
import pass_scopes


def read(run):
    return pass_scopes.scope_ms(run["trace"], (pass_scopes.FLASH_DQ,))
