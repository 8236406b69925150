"""The per-head attention gate's own name in a trace, for the readers under
layer_metrics/ that ISSUE 34 brought.

tpu_mx/models/decoder.py's GroupedQueryAttention, where it has a gate, puts
the gate's projection, its sigmoid and its multiply into the heads' outputs
under the jax.named_scope `attn.gate` (SCOPES below, as a literal: the
yardstick must not import what it measures; tests/test_gated_mixed_decoder.py
holds it equal to the program's ATTENTION_GATE_SCOPES), beside the three
names of attention_scopes.py, whose matcher and reduction serve here too.

A program without the scope (the parent of the PR that brought it, or a
model without a gate) reads as None, never as 0.
"""
SCOPES = ("attn.gate",)
ATTN_GATE, = SCOPES
