"""Layer: attention dispatch.  Device trace, first device: time per step in
the operations under `attn.gate`: the per-head gate's projection (hidden ->
one scalar a query head), its sigmoid and its multiply into the heads'
outputs before the output projection, in every layer that has a gate,
forward, backward and recomputed, in ms."""
import attention_scopes
import gate_scopes


def read(run):
    return attention_scopes.scope_ms(run["trace"], (gate_scopes.ATTN_GATE,))
