"""Layer: attention dispatch.  Device trace, first device: time per step in
the operations under `attn.window`, the layers whose queries see a window of
the past: the flash kernels (or the dense passes), the head layout and the
rotary turn around them, forward, backward and recomputed, in ms."""
import attention_scopes


def read(run):
    return attention_scopes.scope_ms(run["trace"],
                                     (attention_scopes.ATTN_WINDOW,))
