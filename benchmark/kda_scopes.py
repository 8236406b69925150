"""Kimi Delta Attention's own names in a trace, for the readers under
layer_metrics/ that read Kimi Delta Attention.

tpu_mx/models/decoder.py's KimiDeltaAttention puts its five projections,
its two low-rank paths, its output norm and gate and W_o under the
jax.named_scope `kda.project`, the three short filters with SiLU and the L2
norms under `kda.conv`, and the decays, beta and the chunked delta rule
under `kda.scan` (SCOPES below, as literals: the yardstick must not import
what it measures; tests/test_kda_decoder.py holds them equal to the
program's KDA_SCOPES).  They are entered inside the differentiated
function, as the decoder's other names are, so pass_scopes.scope_ms
(decoder_scopes' matcher) reads them forward, backward and recomputed.
They are not among pass_scopes.OWNERS (ROADMAP B1).

A program without the scopes (the parent of the PR that brought them, or a
model without the mixer) reads as None, never as 0.
"""
SCOPES = ("kda.project", "kda.conv", "kda.scan")
KDA_PROJECT, KDA_CONV, KDA_SCAN = SCOPES
