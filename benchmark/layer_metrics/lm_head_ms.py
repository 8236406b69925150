"""Layer: compiled step.  Device trace, first device: time per step in the
operations under `lm_head`: the final norm's product with the head, the
softmax and the loss (in chunks of positions where the model computes them
so), forward, backward and recomputed, in ms."""
import attention_scopes
import decoder_scopes


def read(run):
    return attention_scopes.scope_ms(run["trace"], (decoder_scopes.LM_HEAD,))
