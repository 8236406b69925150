"""Layer: attention dispatch.  Device trace, first device: time per step in
the operations under `mla.attend`: the flash kernels or the dense passes,
with the head layout and the rotary turn around them, forward, backward
and recomputed, in ms."""
import decoder_scopes


def read(run):
    return decoder_scopes.scope_ms(run["trace"],
                                   (decoder_scopes.MLA_ATTEND,))
