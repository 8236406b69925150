"""Layer: attention dispatch.  Device trace, first device: time per step in
the operations under `attn.full`, the layers whose queries see the whole
causal past (and carry no positions): the flash kernels (or the dense
passes) and the head layout around them, forward, backward and recomputed,
in ms.  Beside `attn_window_ms` over the number of layers of each kind it
says what the window saves."""
import attention_scopes


def read(run):
    return attention_scopes.scope_ms(run["trace"],
                                     (attention_scopes.ATTN_FULL,))
