"""Layer: kernels.  Device trace, first device: time per step in the
operations under `flash.fwd` whose op path also holds the component
`rematted_computation`: the forward kernels that the backward pass runs
again under a layer's checkpoint (a part of `flash_fwd_ms`; half of it where
every layer is recomputed once), in ms.  A program that recomputes nothing
reports nothing."""
import pass_scopes


def read(run):
    return pass_scopes.scope_ms(run["trace"], (pass_scopes.FLASH_FWD,),
                                within=(pass_scopes.REMAT,))
