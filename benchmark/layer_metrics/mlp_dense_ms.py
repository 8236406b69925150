"""Layer: compiled step.  Device trace, first device: time per step in the
operations under `mlp.dense` and not under `moe.shared`: the gated MLP of
the layers that have no experts, forward, backward and recomputed, in ms.
(The same block as an expert layer's shared expert lies under `moe.shared`
and is the expert layer's.)"""
import decoder_scopes
import pass_scopes


def read(run):
    return pass_scopes.scope_ms(run["trace"], (pass_scopes.MLP_DENSE,),
                                outside=(decoder_scopes.MOE_SHARED,))
