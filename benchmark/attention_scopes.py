"""The grouped-query attention block's own names in a trace, for the readers
under layer_metrics/ that ISSUE 31 brought.

tpu_mx/models/decoder.py's GroupedQueryAttention names its parts with
jax.named_scope (SCOPES below, as literals: the yardstick must not import
what it measures; tests/test_windowed_gqa_decoder.py holds them equal to the
program's ATTENTION_SCOPES): `attn.project` around the four projections,
and around the head layout, the rotary turn and the attention itself
`attn.window` in a layer with a window, `attn.full` in one without.  They
are entered inside the differentiated function, so an op path holds them
wrapped, as decoder_scopes.py says of the decoder's other names; its
matcher and its step_ops() serve here too.  The flash kernels are the
operations whose op path ends in `pallas_call`.

  scope_ms(trace, scopes, kernels=False)   device time per step of the first
                device's operations under the scopes (ms), forward, backward
                and recomputed; only the Pallas kernels where asked

No trace, or a program in which no operation lies under the scopes (the
parent of the PR that brought them): None, never 0.
"""
import decoder_scopes

SCOPES = ("attn.project", "attn.window", "attn.full")
ATTN_PROJECT, ATTN_WINDOW, ATTN_FULL = SCOPES
KERNEL = "pallas_call"


def scope_ms(trace, scopes, kernels=False):
    steps, ops = decoder_scopes.step_ops(trace)
    took = [d for _, path, _, d in ops if decoder_scopes.under(path, scopes)
            and (KERNEL in path or not kernels)]
    return sum(took) / len(steps) / 1e6 if took else None
