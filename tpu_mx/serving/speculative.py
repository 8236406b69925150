"""Speculative multi-token decode: draft cheap, verify in one window.

Classic speculative decoding (ISSUE 16): a cheap **proposer** guesses
the next ``K-1`` tokens, the real model verifies the whole guessed
window in ONE batched ``(B, K, H, D)`` attention call against the paged
pool (the widened kernels/paged_attention.py query axis), and the
agreeing prefix is accepted.  Greedy verification makes the scheme
lossless BY CONSTRUCTION: every emitted token is the verify model's own
argmax given the accepted prefix — exactly the token one-at-a-time
decode would have produced — so greedy streams are provably
bit-identical speculative on/off (tests/test_serving.py pins it; the
CI serve tier gates it in both decode arms).  Speculation only changes
how many verify-model STEPS a stream costs: an accepted draft token is
a decode step the engine never ran.

The draft window rides the normal cache machinery: ``reserve_window``
grabs the K slots, the verify forward writes every drafted position's
K/V, and rejection truncates the unaccepted tail
(``PagedKVCache.truncate``) — so a restart mid-draft loses nothing the
server's committed-stream replay doesn't already cover.

Knob (resolved once per engine generation, recorded on the
``serve.decode_path`` event's ``spec_window`` field):

- ``TPUMX_SPECULATIVE`` unset/``0``/``off`` — window 1 (speculation
  off: one token per step, the classic decode loop).
- ``1``/``on`` — the default window (:data:`DEFAULT_WINDOW`).
- an integer ``>= 2`` — that window width.  Anything else raises (the
  loud-config discipline every serving knob follows).
"""
from __future__ import annotations

import os

import numpy as np

__all__ = ["DEFAULT_WINDOW", "resolve_spec_window", "SiblingProposer",
           "accept_prefix"]

_SPEC_ENV = "TPUMX_SPECULATIVE"

# The widened kernel shares the block walk over the Tq rows of a window,
# so the window wants to be as wide as the accept rate sustains; 4 is
# where the toy proposer's acceptance still paid for the extra verify
# rows (a count on a CPU host, not a speed; no cell measures it yet).
DEFAULT_WINDOW = 4


def resolve_spec_window():
    """The draft-window width ``TPUMX_SPECULATIVE`` requests; 1 means
    speculation off (see module docstring)."""
    v = os.environ.get(_SPEC_ENV, "0").strip().lower()
    if v in ("", "0", "off", "no"):
        return 1
    if v in ("1", "on", "yes", "auto"):
        return DEFAULT_WINDOW
    try:
        n = int(v)
    except ValueError:
        raise ValueError(
            f"{_SPEC_ENV}={v!r} is not a recognized speculative setting "
            "— use 0 (off), 1 (default window) or an integer window "
            "width >= 2") from None
    if n < 2:
        raise ValueError(
            f"{_SPEC_ENV}={v!r}: an explicit window must be >= 2 "
            "(1-token windows are just decode; use 0/1 to toggle)")
    return n


class SiblingProposer:
    """The verify model's own weights, evaluated context-FREE: each
    draft step embeds only (token, position) and collapses every
    layer's attention to its own value row (a single-key causal softmax
    is the identity on ``v``), so drafting costs a handful of ``(B, E)``
    matmuls — no cache reads, no O(context) anything.  It is exactly
    the verify model minus context, which is what makes it a sibling:
    same embeddings, same projections, deterministic, free to disagree.

    Acceptance is therefore workload-dependent by design — the engine
    REPORTS the measured ratio (``serve.spec_accept_ratio``) rather
    than assuming one; correctness never depends on it (module
    docstring: greedy verification is lossless at any accept rate)."""

    def __init__(self, model):
        self.model = model

    def draft(self, last_tokens, positions, n):
        """``n`` greedy draft tokens per row: ``last_tokens`` ``(B,)``
        are the stream heads, ``positions`` ``(B,)`` their absolute
        positions.  Returns int64 ``(B, n)`` — draft ``j`` chained from
        draft ``j-1`` (the window the verify step will judge)."""
        m = self.model
        cur = np.asarray(last_tokens, np.int64)
        pos = np.asarray(positions, np.int64)
        out = np.empty((cur.shape[0], n), np.int64)
        for j in range(n):
            p = np.minimum(pos + j, m.max_positions - 1)
            h = m.tok_emb[cur % m.vocab_size] + m.pos_emb[p]
            for i in range(m.num_layers):
                _, _, v = m.layer_qkv(i, h)
                h = m.layer_combine(i, h, v)
            cur = np.argmax(m.logits(h), axis=-1)
            out[:, j] = cur
        return out


def accept_prefix(draft_row, out_row):
    """How many DRAFTED tokens the verify step confirmed: the longest
    ``j`` run where ``draft_row[j] == out_row[j-1]`` for ``j = 1..K-1``
    (``draft_row[0]`` is the stream head, never judged; ``out_row[j]``
    is the verify model's argmax after consuming ``draft_row[:j+1]``).
    The emitted tokens are ``out_row[:accepted+1]`` — the confirmed
    drafts plus the verify model's one free next token."""
    a = 0
    for j in range(1, len(draft_row)):
        if int(draft_row[j]) != int(out_row[j - 1]):
            break
        a += 1
    return a
