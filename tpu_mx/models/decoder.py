"""Causal decoder blocks, named by mechanism and not by model (ROADMAP M1):
rotary positions, a gated MLP, latent attention, grouped-query attention
with an optional window, optional positions and optional q/k norms, a
gated short convolution, Kimi Delta Attention (a gated delta rule with a
decay per key channel, in chunks), a pre-norm layer, and a causal language
model with its next-token loss inside the forward (whole, or in chunks of
positions), an optional tied head and an optional multi-token-prediction
module.
The expert layer is `parallel.DroplessMoE`.

The equations are those of the DeepSeek-V3 family (arXiv:2412.19437,
sections 2.1 and 2.2); tests/references/latent_moe_decoder.py is their plain
float32 form, and tests/test_latent_moe_decoder.py holds the two together.
The grouped-query layers, the router fed from before the attention and the
chunked head (ISSUE 31) follow tests/references/windowed_gqa_decoder.py,
held together by tests/test_windowed_gqa_decoder.py; the per-head gate,
the rotary turn over a part of the head and YaRN's frequencies (ISSUE 34)
follow tests/references/gated_mixed_decoder.py
(tests/test_gated_mixed_decoder.py); the third token mixer, the gated short
convolution, with the q/k norms and the tied head (ISSUE 39), follows
tests/references/short_conv_decoder.py (tests/test_short_conv_decoder.py);
the fourth, Kimi Delta Attention, with latent attention without a query
rank or positions, follows tests/references/kda_decoder.py
(tests/test_kda_decoder.py).
Training form only: nothing is absorbed, there is no cache (serving is
ROADMAP M4-M8).  No dropout anywhere, and no bias but Kimi Delta
Attention's decay offset and output-gate bias.

bf16-friendly like models/bert.py: norm and softmax statistics, the
router's scores and the logits are f32 whatever the model's type.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray import ops
from ..parallel import DroplessMoE, attention as _attention
from ..parallel.moe import MOE_SCOPES
from ..parallel.ring_attention import dispatch_counts
from ..random import host_rng

__all__ = ["rotary", "yarn_inv_freq", "GatedMLP", "LatentAttention",
           "GroupedQueryAttention", "short_conv_mix", "ShortConv",
           "kda_chunked", "KimiDeltaAttention", "DecoderLayer", "CausalLM",
           "DECODER_SCOPES", "ATTENTION_SCOPES", "ATTENTION_GATE_SCOPES",
           "CONV_SCOPES", "KDA_SCOPES", "OWNER_SCOPES"]

# jax.named_scope names inside these blocks (HLO metadata only), beside
# train_step.STEP_SCOPES; benchmark/decoder_scopes.py holds them as literals
DECODER_SCOPES = ("mla.project", "mla.attend") + MOE_SCOPES \
    + ("mtp", "lm_head")
_PROJECT, _ATTEND = DECODER_SCOPES[:2]
_MTP, _LM_HEAD = DECODER_SCOPES[-2:]
# GroupedQueryAttention's own (benchmark/attention_scopes.py holds them as
# literals): its projections, and its attention by the layer's kind
ATTENTION_SCOPES = ("attn.project", "attn.window", "attn.full")
_GQ_PROJECT, _GQ_WINDOW, _GQ_FULL = ATTENTION_SCOPES
# and its per-head gate's: projection, sigmoid and multiply
# (benchmark/gate_scopes.py holds the literal)
ATTENTION_GATE_SCOPES = ("attn.gate",)
_GQ_GATE, = ATTENTION_GATE_SCOPES
# every name a block here gives a part of the step: the three tuples above
# and GatedMLP's own (a dense layer; as DroplessMoE's shared expert it lies
# under `moe.shared`, and a reader takes the outer name).  The flash kernels'
# names are kernels/flash_attention.py's FLASH_SCOPES;
# benchmark/pass_scopes.py holds both as literals
_MLP_DENSE = "mlp.dense"
OWNER_SCOPES = DECODER_SCOPES + ATTENTION_SCOPES + ATTENTION_GATE_SCOPES \
    + (_MLP_DENSE,)
# ShortConv's own (benchmark/conv_scopes.py holds them as literals): its two
# projections, and the gates with the causal filter between them.  Not in
# OWNER_SCOPES, which tests/test_named_passes.py holds equal to the
# benchmark's accepted owners (ROADMAP B1)
CONV_SCOPES = ("conv.project", "conv.mix")
_CONV_PROJECT, _CONV_MIX = CONV_SCOPES
# KimiDeltaAttention's own (benchmark/kda_scopes.py holds them as literals):
# its projections with the output norm and gate, the short convolutions with
# the L2 norms, and the decays with the chunked rule; not in OWNER_SCOPES
# either
KDA_SCOPES = ("kda.project", "kda.conv", "kda.scan")
_KDA_PROJECT, _KDA_CONV, _KDA_SCAN = KDA_SCOPES
# inside `kda.scan`, the Pallas calls that form the within-sub-chunk pairs
# (no reader of the benchmark takes this name yet; `kda_scan_ms` holds it)
KDA_PAIRS = "kda.pairs"


def yarn_inv_freq(theta, dim, factor, original_length, beta_fast=32.0,
                  beta_slow=1.0):
    """YaRN's inverse frequencies (Peng et al., arXiv:2309.00071, "NTK by
    parts") for `dim` rotary dimensions, d/2 numbers in float64: pair i of
    plain frequency f_i = theta^(-2i/dim) keeps it where it turns more than
    beta_fast times over the original length, takes f_i / factor where it
    turns less than beta_slow times, and a linear blend of the two by its
    index between.  c(n) = dim · ln(original_length / (2 pi n)) / (2 ln
    theta) is the index of the pair that turns n times."""
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    turns = lambda n: dim * np.log(original_length / (2 * np.pi * n)) \
        / (2 * np.log(theta))
    low = max(np.floor(turns(beta_fast)), 0)
    high = min(np.ceil(turns(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return f / factor * ramp + f * (1 - ramp)


def rotary(x, theta, pairs="interleaved", inv_freq=None, factor=1.0):
    """Rotary positions (Su et al., arXiv:2104.09864) over the whole last
    axis of x (..., T, d): pair i turns by position · theta^(-2i/d), or by
    position · inv_freq[i] where the d/2 frequencies are given (`theta` is
    then not read); cos and sin are multiplied by `factor` (YaRN's
    attention factor).
    `pairs` "interleaved": pair i is (x[2i], x[2i+1]); "halves": (x[i],
    x[i + d/2]), the `rotate_half` convention.  Angles and the turn in
    f32."""
    t, d = x.shape[-2], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d) \
        if inv_freq is None else jnp.asarray(inv_freq, jnp.float32)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    xf = x.astype(jnp.float32)
    if pairs == "halves":
        a, b = xf[..., :d // 2], xf[..., d // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                               axis=-1).astype(x.dtype)
    if pairs != "interleaved":
        raise ValueError(f"rotary pairs {pairs!r}: interleaved or halves")
    a, b = xf[..., 0::2], xf[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def _linear(F, x, weight):
    return F.FullyConnected(x, weight, no_bias=True, flatten=False)


class GatedMLP(HybridBlock):
    """SwiGLU: down(silu(gate · x) * (up · x)) (Shazeer, arXiv:2002.05202)."""

    def __init__(self, units, hidden_size, **kwargs):
        super().__init__(**kwargs)
        self.gate_proj_weight = self.params.get(
            "gate_proj_weight", shape=(hidden_size, units))
        self.up_proj_weight = self.params.get(
            "up_proj_weight", shape=(hidden_size, units))
        self.down_proj_weight = self.params.get(
            "down_proj_weight", shape=(units, hidden_size))

    def hybrid_forward(self, F, x, gate_proj_weight, up_proj_weight,
                       down_proj_weight):
        with jax.named_scope(_MLP_DENSE):
            h = ops._apply(lambda g, u: jax.nn.silu(g) * u,
                           [_linear(F, x, gate_proj_weight),
                            _linear(F, x, up_proj_weight)], "swiglu")
            return _linear(F, h, down_proj_weight)


class LatentAttention(HybridBlock):
    """Multi-head latent attention, training form: queries and keys/values
    through low-rank paths with their own RMS norms; each head's query and
    key are a part without positions (`nope_dim`) and a rotary part
    (`rope_dim`), the key's rotary part one vector a token shared by all
    heads.  Scores over nope_dim + rope_dim, causal, through
    `parallel.attention` (so the dispatch chooses dense or the flash kernel
    as for every caller); values of `v_dim` (at most the key's size: the
    kernel takes one head size, so smaller values ride zero-padded).
    `q_rank` None: the query is x W_q, with no low-rank path and no norm;
    `rotary` False: neither part of q or k turns (the "rotary" part is then
    only the key's shared part)."""

    def __init__(self, units, num_heads, q_rank, kv_rank, nope_dim, rope_dim,
                 v_dim, rope_theta=10000.0, epsilon=1e-6, mesh=None,
                 rotary=True, **kwargs):
        super().__init__(**kwargs)
        if v_dim > nope_dim + rope_dim:
            raise ValueError(f"v_dim {v_dim} exceeds the key size "
                             f"{nope_dim + rope_dim}")
        self._h, self._dn, self._dr, self._dv = \
            num_heads, nope_dim, rope_dim, v_dim
        self._rkv, self._theta, self._mesh = kv_rank, float(rope_theta), mesh
        self._rotary = rotary
        if q_rank is None:
            self.q_weight = self.params.get(
                "q_weight", shape=(num_heads * (nope_dim + rope_dim), units))
        else:
            self.q_a_weight = self.params.get("q_a_weight",
                                              shape=(q_rank, units))
            self.q_a_norm = nn.RMSNorm(epsilon=epsilon, in_channels=q_rank)
            self.q_b_weight = self.params.get(
                "q_b_weight", shape=(num_heads * (nope_dim + rope_dim),
                                     q_rank))
        self.kv_a_weight = self.params.get(
            "kv_a_weight", shape=(kv_rank + rope_dim, units))
        self.kv_a_norm = nn.RMSNorm(epsilon=epsilon, in_channels=kv_rank)
        self.kv_b_weight = self.params.get(
            "kv_b_weight", shape=(num_heads * (nope_dim + v_dim), kv_rank))
        self.o_weight = self.params.get(
            "o_weight", shape=(units, num_heads * v_dim))

    def _heads(self, q, k_r, kv):
        """(B, T, ...) projections to (B, H, T, D) q, k, v: split, turn the
        rotary parts, give every head the shared rotary key."""
        b, t = q.shape[:2]
        h, dn, dr, dv = self._h, self._dn, self._dr, self._dv
        q = q.reshape(b, t, h, dn + dr).transpose(0, 2, 1, 3)
        kv = kv.reshape(b, t, h, dn + dv).transpose(0, 2, 1, 3)
        if self._rotary:
            q = jnp.concatenate(
                [q[..., :dn], rotary(q[..., dn:], self._theta)], axis=-1)
            k_r = rotary(k_r[:, None], self._theta)           # (B, 1, T, dr)
        else:
            k_r = k_r[:, None]
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (b, h, t, dr))], axis=-1)
        v = jnp.pad(kv[..., dn:], [(0, 0)] * 3 + [(0, dn + dr - dv)])
        return q, k, v

    def hybrid_forward(self, F, x, kv_a_weight, kv_b_weight, o_weight,
                       q_a_weight=None, q_b_weight=None, q_weight=None):
        b, t = x.shape[:2]
        rkv = self._rkv
        with jax.named_scope(_PROJECT):
            q = _linear(F, x, q_weight) if q_weight is not None else \
                _linear(F, self.q_a_norm(_linear(F, x, q_a_weight)),
                        q_b_weight)
            kv_a = _linear(F, x, kv_a_weight)
            c_kv = self.kv_a_norm(
                F.slice_axis(kv_a, axis=-1, begin=0, end=rkv))
            k_r = F.slice_axis(kv_a, axis=-1, begin=rkv, end=rkv + self._dr)
            kv = _linear(F, c_kv, kv_b_weight)
        # the kernels or the dense passes, with the head layout (and the
        # rotary turn that XLA fuses into it) around them
        with jax.named_scope(_ATTEND):
            q, k, v = ops._apply(self._heads, [q, k_r, kv], "latent_heads")
            out = ops._apply(
                lambda qq, kk, vv: _attention(qq, kk, vv, mesh=self._mesh,
                                              causal=True),
                [q, k, v], "RingAttention")                   # (B, H, T, D)
            out = ops._apply(
                lambda o: o[..., :self._dv].transpose(0, 2, 1, 3).reshape(
                    b, t, self._h * self._dv), [out], "merge_heads")
        with jax.named_scope(_PROJECT):
            return _linear(F, out, o_weight)


class GroupedQueryAttention(HybridBlock):
    """Causal attention with `num_heads` query heads over `num_kv_heads`
    key/value heads of `head_dim` (query head h reads key/value head
    h // (num_heads / num_kv_heads)), no bias.  `rope_theta` None: no
    positions at all; else q and k turn by rotary positions
    (`rotary_pairs` as `rotary` takes them) over the first `rotary_dim`
    dimensions of a head, the whole head by default, the rest passing
    unturned; `yarn` (factor, original_length, beta_fast, beta_slow,
    attention_factor) blends the frequencies as `yarn_inv_freq` does and
    scales cos and sin by the attention factor.  `window` None: query i
    sees every key j <= i; else the last `window` of them, i - window < j
    <= i.  `gate`: each head's output is multiplied by a scalar of its own
    a token, sigmoid(x W_g), before the output projection (the head-wise
    form of Qiu et al., arXiv:2505.06708).  `qk_norm`: each head of q and
    of k passes an RMS norm over its `head_dim` (a weight of its own for q
    and for k, `epsilon`) before the rotary turn.  Through
    `parallel.attention`, which hands k and v to the flash kernel with the
    heads they have."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 rope_theta=None, window=None, rotary_pairs="halves",
                 rotary_dim=None, yarn=None, gate=False, qk_norm=False,
                 epsilon=1e-6, mesh=None, **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads over {num_kv_heads} "
                             "key/value heads")
        self._h, self._hkv, self._d = num_heads, num_kv_heads, head_dim
        self._theta = None if rope_theta is None else float(rope_theta)
        self._window, self._pairs, self._mesh = window, rotary_pairs, mesh
        self._rot = head_dim if rotary_dim is None else int(rotary_dim)
        if not 0 < self._rot <= head_dim or self._rot % 2:
            raise ValueError(f"rotary_dim {rotary_dim} of a head of "
                             f"{head_dim}")
        self._inv_freq, self._factor = None, 1.0
        if yarn is not None:
            yarn = dict(yarn)
            self._factor = float(yarn.pop("attention_factor", 1.0))
            self._inv_freq = yarn_inv_freq(self._theta, self._rot, **yarn)
        self.q_weight = self.params.get(
            "q_weight", shape=(num_heads * head_dim, units))
        self.k_weight = self.params.get(
            "k_weight", shape=(num_kv_heads * head_dim, units))
        self.v_weight = self.params.get(
            "v_weight", shape=(num_kv_heads * head_dim, units))
        self.o_weight = self.params.get(
            "o_weight", shape=(units, num_heads * head_dim))
        if gate:
            self.gate_weight = self.params.get(
                "gate_weight", shape=(num_heads, units))
        self._qk_norm = qk_norm
        if qk_norm:
            self.q_norm = nn.RMSNorm(epsilon=epsilon, in_channels=head_dim)
            self.k_norm = nn.RMSNorm(epsilon=epsilon, in_channels=head_dim)

    def _heads(self, x, n, turn):
        b, t = x.shape[:2]
        x = x.reshape(b, t, n, self._d).transpose(0, 2, 1, 3)
        if not turn:
            return x
        turned = lambda a: rotary(a, self._theta, self._pairs,
                                  self._inv_freq, self._factor)
        return turned(x) if self._rot == self._d else jnp.concatenate(
            [turned(x[..., :self._rot]), x[..., self._rot:]], axis=-1)

    def _gated(self, out, z):
        """out (B, T, H·d) times sigmoid(z) (B, T, H), a head at a time;
        the sigmoid in f32."""
        g = jax.nn.sigmoid(z.astype(jnp.float32)).astype(out.dtype)
        return (out.reshape(*z.shape, self._d) * g[..., None]).reshape(
            out.shape)

    def hybrid_forward(self, F, x, q_weight, k_weight, v_weight, o_weight,
                       gate_weight=None):
        b, t = x.shape[:2]
        turn = self._theta is not None
        with jax.named_scope(_GQ_PROJECT):
            q, k, v = (_linear(F, x, w)
                       for w in (q_weight, k_weight, v_weight))
        with jax.named_scope(_GQ_FULL if self._window is None
                             else _GQ_WINDOW):
            if self._qk_norm:
                q = self.q_norm(ops._apply(
                    lambda a: a.reshape(b, t, self._h, self._d), [q],
                    "split_heads"))
                k = self.k_norm(ops._apply(
                    lambda a: a.reshape(b, t, self._hkv, self._d), [k],
                    "split_heads"))
            q = ops._apply(lambda a: self._heads(a, self._h, turn), [q],
                           "query_heads")
            k = ops._apply(lambda a: self._heads(a, self._hkv, turn), [k],
                           "key_heads")
            v = ops._apply(lambda a: self._heads(a, self._hkv, False), [v],
                           "value_heads")
            out = ops._apply(
                lambda qq, kk, vv: _attention(
                    qq, kk, vv, mesh=self._mesh, causal=True,
                    window=self._window), [q, k, v], "RingAttention")
            out = ops._apply(
                lambda o: o.transpose(0, 2, 1, 3).reshape(
                    b, t, self._h * self._d), [out], "merge_heads")
        if gate_weight is not None:
            with jax.named_scope(_GQ_GATE):
                out = ops._apply(self._gated,
                                 [out, _linear(F, x, gate_weight)],
                                 "head_gate")
        with jax.named_scope(_GQ_PROJECT):
            return _linear(F, out, o_weight)


def short_conv_mix(bcx, weight):
    """The middle of the gated short convolution: bcx (..., T, 3H) is
    [B | C | x~] as the input projection gives it, weight (H, taps) the
    depthwise filter, whose tap i reads position t - (taps - 1) + i (the
    layout of a Conv1d weight (H, 1, taps) over a sequence padded on the
    left); returns C * conv(B * x~) (..., T, H) in bcx's type.  Three
    shifted multiply-adds over the padded sequence, the products and their
    sum in f32.  (lax.conv_general_dilated with H feature groups was faster
    on the chip because XLA:TPU ran it in bf16 passes, which the cell's
    comparison refused: PERF.md section 6, PR 39.)"""
    b, c, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    return (c * _causal_filter(b * x, weight)).astype(bcx.dtype)


def _causal_filter(v, weight):
    """The causal depthwise filter over v (..., T, C) f32, weight (C, taps)
    whose tap i reads position t - (taps - 1) + i: shifted multiply-adds
    over the sequence padded on the left, in f32."""
    taps, t = weight.shape[-1], v.shape[-2]
    v = jnp.pad(v, [(0, 0)] * (v.ndim - 2) + [(taps - 1, 0), (0, 0)])
    w = weight.astype(jnp.float32)
    return sum(v[..., i:i + t, :] * w[:, i] for i in range(taps))


class ShortConv(HybridBlock):
    """Gated short convolution, the token mixer of LFM2's `conv` layers:
    [B | C | x~] = x W_in (W_in: units -> 3 units), y = (C * conv(B * x~))
    W_out, conv a causal depthwise filter of `taps` positions a channel
    (`short_conv_mix`); no bias."""

    def __init__(self, units, taps, **kwargs):
        super().__init__(**kwargs)
        self.in_proj_weight = self.params.get(
            "in_proj_weight", shape=(3 * units, units))
        self.conv_weight = self.params.get("conv_weight", shape=(units, taps))
        self.out_proj_weight = self.params.get(
            "out_proj_weight", shape=(units, units))

    def hybrid_forward(self, F, x, in_proj_weight, conv_weight,
                       out_proj_weight):
        with jax.named_scope(_CONV_PROJECT):
            bcx = _linear(F, x, in_proj_weight)
        with jax.named_scope(_CONV_MIX):
            y = ops._apply(short_conv_mix, [bcx, conv_weight], "short_conv")
        with jax.named_scope(_CONV_PROJECT):
            return _linear(F, y, out_proj_weight)


# the chunked rule's sub-chunk: pairs within one are decayed pairwise, pairs
# across sub-chunks through the later one's first position
_KDA_SUB = 16
# chunks whose products that need no state are formed at once: compiled for
# a TPU v5e, a layer's backward over 2 sequences of 8,192 in 32 heads of 128
# takes some 1.46 GiB of temporaries at groups of 8 chunks of 64, and 2.03
# GiB at 16
_KDA_GROUP = 8
# chunks a program of the inverse's Pallas call takes.  Mosaic unrolls the
# substitution over the block's chunks, so its code grows with the block:
# compiled for a TPU v5e, 0.40 MiB at 8 chunks of 64 and 2.95 MiB at 64, an
# executable holding twelve of them
_KDA_INVERSE_BLOCK = 8


# sub-chunks a program of the pairs' Pallas calls takes, on the lanes
_KDA_PAIRS_BLOCK = 128
# key channels the pairs' kernels take a step: one register's sublanes
_KDA_PAIRS_ROWS = 8
# the gradient's double-buffered blocks and its scratch take 16.4 MiB at
# heads of 128, past the 16 MiB a TPU v5e kernel is given by default
_KDA_PAIRS_VMEM = 32 << 20


def _pairs_kernel(q_ref, k_ref, g_ref, a_ref, p_ref, qt, kt, gt, at, pt):
    """The within-sub-chunk pairs of a block of sub-chunks: q, k, g (block
    s, d) a position a row, s rows a sub-chunk; a (block, s s) = sum_d k_i
    k_j exp(g_i - g_j) for j < i, p the same of q_i and k_j for j <= i, 0
    elsewhere, a sub-chunk a row.  The inputs are turned so that the
    sub-chunks lie on the lanes and the channels on the sublanes (qt, kt,
    gt (s, d, block)); for each i the pairs (i, j < i) take one exp
    between them and sum over the channels eight at a time, and the
    diagonal of p is q_i k_i."""
    s, d, block = qt.shape
    rows = _KDA_PAIRS_ROWS
    for ref, turned in ((q_ref, qt), (k_ref, kt), (g_ref, gt)):
        for i in range(s):
            turned[i] = ref[pl.ds(i, block, stride=s)].T
    at[...] = jnp.zeros(at.shape, at.dtype)
    pt[...] = jnp.zeros(pt.shape, pt.dtype)
    for i in range(s):
        def channels(c, acc, i=i):
            ch = pl.ds(pl.multiple_of(c * rows, rows), rows)
            qi, ki = qt[i, ch], kt[i, ch]
            if not i:
                return acc[0] + qi * ki,
            ke = kt[:i, ch] * jnp.exp(gt[i, ch][None] - gt[:i, ch])
            return (acc[0] + qi * ki, acc[1] + ki[None] * ke,
                    acc[2] + qi[None] * ke)
        pairs = (jnp.zeros((i, rows, block), jnp.float32),) * 2 if i else ()
        acc = jax.lax.fori_loop(0, d // rows, channels,
                                (jnp.zeros((rows, block), jnp.float32),)
                                + pairs)
        pt[i, i:i + 1] = jnp.sum(acc[0], axis=0, keepdims=True)
        if i:
            at[i, :i] = jnp.sum(acc[1], axis=1)
            pt[i, :i] = jnp.sum(acc[2], axis=1)
    a_ref[...] = at[...].reshape(s * s, block).T
    p_ref[...] = pt[...].reshape(s * s, block).T


def _pairs_grad_kernel(q_ref, k_ref, g_ref, da_ref, dp_ref, dq_ref, dk_ref,
                       dg_ref, qt, kt, gt, dat, dpt, dkb, dqi, dkai):
    """The gradient of `_pairs_kernel`'s a and p by q, k and g, the same
    blocks and layouts, from the pairs (i, j < i) with E_ij = exp(g_i -
    g_j): dq_i = sum_j dp_ij k_j E_ij; dk as a_i, sum_j da_ij k_j E_ij; dk
    as b_j, sum_i (da_ij k_i + dp_ij q_i) E_ij (`dkb`); and since dW_ij /
    dg_i = a_i b_j E_ij = -dW_ij / dg_j, dg = q dq + k (dk as a_i - dk as
    b_j).  The diagonal of p adds dp_ii k_i to dq_i and dp_ii q_i to dk_i
    and nothing to dg.  Rows i from the last, so that dkb_i is whole once
    i is done."""
    s, d, block = qt.shape
    rows = _KDA_PAIRS_ROWS
    for ref, turned in ((q_ref, qt), (k_ref, kt), (g_ref, gt)):
        for i in range(s):
            turned[i] = ref[pl.ds(i, block, stride=s)].T
    dat[...] = da_ref[...].T.reshape(s, s, block)
    dpt[...] = dp_ref[...].T.reshape(s, s, block)
    dkb[...] = jnp.zeros(dkb.shape, dkb.dtype)
    for i in reversed(range(s)):
        def channels(c, carry, i=i):
            ch = pl.ds(pl.multiple_of(c * rows, rows), rows)
            da, dp = carry
            e = jnp.exp(gt[i, ch][None] - gt[:i, ch])
            ke = kt[:i, ch] * e
            dqi[ch] = jnp.sum(dp * ke, axis=0)
            dkai[ch] = jnp.sum(da * ke, axis=0)
            dkb[:i, ch] += (da * kt[i, ch][None] + dp * qt[i, ch][None]) * e
            return carry
        if i:
            spread = lambda x: jnp.broadcast_to(x[:, None, :],
                                                (i, rows, block))
            jax.lax.fori_loop(0, d // rows, channels,
                              (spread(dat[i, :i]), spread(dpt[i, :i])))
            dq, dka = dqi[...], dkai[...]
        else:
            dq = dka = jnp.zeros((d, block), jnp.float32)
        q, k, diagonal, dkb_i = qt[i], kt[i], dpt[i, i:i + 1], dkb[i]
        position = pl.ds(i, block, stride=s)
        dq_ref[position] = (dq + diagonal * k).T
        dk_ref[position] = (dka + dkb_i + diagonal * q).T
        dg_ref[position] = (q * dq + k * (dka - dkb_i)).T


def _pairs_call(kernel, n, ins, outs, scratch):
    """One Pallas call over blocks of `_KDA_PAIRS_BLOCK` of n sub-chunks:
    ins (n r, w), r rows a sub-chunk, padded with zeros to whole blocks;
    outs the (r, w) of each output, (n r, w) f32; scratch the shapes of the
    kernel's f32 scratch less their last axis, the block."""
    block = _KDA_PAIRS_BLOCK
    padded = -(-n // block) * block
    spec = lambda r, w: pl.BlockSpec((block * r, w), lambda b: (b, 0))
    shapes = [(x.shape[0] // n, x.shape[1]) for x in ins]
    ins = [jnp.pad(x, [(0, (padded - n) * r), (0, 0)])
           for x, (r, _) in zip(ins, shapes)]
    ys = pl.pallas_call(
        kernel, grid=(padded // block,),
        in_specs=[spec(*shape) for shape in shapes],
        out_specs=[spec(*shape) for shape in outs],
        out_shape=[jax.ShapeDtypeStruct((padded * r, w), jnp.float32)
                   for r, w in outs],
        scratch_shapes=[pltpu.VMEM(shape + (block,), jnp.float32)
                        for shape in scratch],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_KDA_PAIRS_VMEM),
        interpret=_interpret())(*ins)
    return [y[:n * r] for y, (r, _) in zip(ys, outs)]


@jax.custom_vjp
def _sub_chunk_pairs(q, k, cum):
    """(A, P) within each sub-chunk: q, k, cum (..., n, s, d) f32, cum not
    increasing along s; A[i, j] = sum_d k_i k_j exp(cum_i - cum_j) for
    j < i and P[i, j] = sum_d q_i k_j exp(cum_i - cum_j) for j <= i
    (..., n, s, s), 0 elsewhere.  One Pallas call forms both from one exp
    a pair, every exponent at most 0, f32 throughout; the gradient is one
    more."""
    *lead, s, d = q.shape
    if d % _KDA_PAIRS_ROWS:
        raise ValueError(f"head_dim {d}: a multiple of {_KDA_PAIRS_ROWS}")
    with jax.named_scope(KDA_PAIRS):
        a, p = _pairs_call(_pairs_kernel, q.size // (s * d),
                           [x.reshape(-1, d) for x in (q, k, cum)],
                           [(1, s * s)] * 2, [(s, d)] * 3 + [(s, s)] * 2)
        return a.reshape(*lead, s, s), p.reshape(*lead, s, s)


def _sub_chunk_pairs_fwd(q, k, cum):
    return _sub_chunk_pairs(q, k, cum), (q, k, cum)


def _sub_chunk_pairs_bwd(res, cts):
    *lead, s, d = res[0].shape
    with jax.named_scope(KDA_PAIRS):
        grads = _pairs_call(
            _pairs_grad_kernel, res[0].size // (s * d),
            [x.reshape(-1, d) for x in res]
            + [x.reshape(-1, s * s) for x in cts], [(s, d)] * 3,
            [(s, d)] * 3 + [(s, s)] * 2 + [(s, d), (d,), (d,)])
        return tuple(x.reshape(*lead, s, d) for x in grads)


_sub_chunk_pairs.defvjp(_sub_chunk_pairs_fwd, _sub_chunk_pairs_bwd)


def _decayed_products(q, k, cum, sub):
    """(A, P) over a chunk's C positions: A[i, j] = sum_d k[i, d] k[j, d]
    exp(cum[i, d] - cum[j, d]) for j < i, P[i, j] the same of q_i and k_j
    for j <= i, else 0: q, k, cum (..., C, d) f32, cum not increasing
    along C.  No exponent exceeds 0, so nothing overflows however strong
    the decay: across sub-chunks of `sub` positions the decay passes
    through the first position r of i's sub-chunk, exp(cum_i - cum_r)
    exp(cum_r - cum_j), a product on the MXU; within one it is taken pair
    by pair (`_sub_chunk_pairs`)."""
    *lead, c, d = k.shape
    n = c // sub
    at = lambda x: x.reshape(*lead, n, sub, d)
    # each sub-chunk's first position; a strided slice here lowers on
    # XLA:TPU to a gather, and in the gradient to a scatter
    ref = at(cum)[..., 0, :]                                  # (..., n, d)
    before = jnp.arange(c)[None, :] < (jnp.arange(n) * sub)[:, None]
    right = k[..., None, :, :] * jnp.exp(jnp.where(
        before[:, :, None], ref[..., :, None, :] - cum[..., None, :, :],
        -jnp.inf))                                            # (..., n, C, d)
    eye = jnp.eye(n)[:, None, :, None]

    def whole(a, within):
        left = at(a) * jnp.exp(at(cum) - ref[..., None, :])
        across = jnp.einsum("...asd,...acd->...asc", left, right,
                            precision=jax.lax.Precision.HIGHEST)
        out = across.reshape(*lead, n, sub, n, sub) \
            + within[..., :, :, None, :] * eye
        return out.reshape(*lead, c, c)
    within_a, within_p = _sub_chunk_pairs(at(q), at(k), at(cum))
    return whole(k, within_a), whole(q, within_p)


def _interpret():
    return jax.default_backend() != "tpu"


def _inverse_kernel(a_ref, t_ref):
    """(I + A)^-1 for a block of chunks, A (block, C, C) strictly lower:
    forward substitution a column at a time, every chunk of the block at
    once.  Once row j of T is final, every later row takes its share of
    it; row j is 0 past column j, so a step touches the columns up to the
    next multiple of 8."""
    c = t_ref.shape[-1]
    rows = jax.lax.broadcasted_iota(jnp.int32, t_ref.shape, 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, t_ref.shape, 2)
    t_ref[...] = (rows == cols).astype(t_ref.dtype)
    for j in range(c - 1):
        width = min(c, -(-(j + 1) // 8) * 8)
        t_ref[:, j + 1:, :width] -= (a_ref[:, j + 1:, j:j + 1]
                                     * t_ref[:, j:j + 1, :width])


def _unit_lower_inverse(a):
    """(I + a)^-1 for a (..., C, C) f32 strictly lower triangular, in one
    Pallas call over blocks of `_KDA_INVERSE_BLOCK` chunks, f32 throughout."""
    *lead, c, _ = a.shape
    x = a.reshape(-1, c, c)
    n = x.shape[0]
    block = min(_KDA_INVERSE_BLOCK, n)
    x = jnp.pad(x, [(0, -n % block), (0, 0), (0, 0)])
    spec = pl.BlockSpec((block, c, c), lambda i: (i, 0, 0))
    t = pl.pallas_call(
        _inverse_kernel, grid=(x.shape[0] // block,), in_specs=[spec],
        out_specs=spec, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=_interpret())(x)
    return t[:n].reshape(*lead, c, c)


def _product(t, rhs):
    return jnp.einsum("...ij,...je->...ie", t, rhs,
                      precision=jax.lax.Precision.HIGHEST)


@jax.custom_vjp
def _unit_lower_solve(a, rhs):
    """(I + a)^-1 rhs, a (..., C, C) f32 strictly lower triangular: one
    product with the inverse.  Its gradient is two more products with it,
    as a triangular solve's is with a transposed solve: d rhs = T^T dx,
    d a = -(T^T dx) x^T below the diagonal."""
    return _product(_unit_lower_inverse(a), rhs)


def _unit_lower_solve_fwd(a, rhs):
    t = _unit_lower_inverse(a)
    x = _product(t, rhs)
    return x, (t, x)


def _unit_lower_solve_bwd(res, dx):
    t, x = res
    d_rhs = _product(jnp.swapaxes(t, -1, -2), dx)
    d_a = -_product(d_rhs, jnp.swapaxes(x, -1, -2))
    below = jnp.tri(t.shape[-1], k=-1, dtype=bool)
    return jnp.where(below, d_a, 0.0), d_rhs


_unit_lower_solve.defvjp(_unit_lower_solve_fwd, _unit_lower_solve_bwd)


def kda_chunked(q, k, v, g, beta, chunk=64):
    """Kimi Delta Attention's recurrence, the gated delta rule with a decay
    for each key channel (arXiv:2510.26692), a head at a time:

        S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_(t-1) + beta_t k_t v_t^T
        o_t = S_t^T q_t,   S_0 = 0, S (dk, dv) in f32

    q, k (B, T, H, dk), v (B, T, H, dv), g (B, T, H, dk) the log of each
    channel's decay (at most 0), beta (B, T, H); returns o (B, T, H, dv) in
    v's type.  In chunks of `chunk` positions, with G the decays summed
    from a chunk's start and S the state the chunk is handed; T is padded
    up to a whole number of groups of `_KDA_GROUP` chunks (fewer where T
    has fewer).  A `lax.scan` over groups, its body under `jax.checkpoint`
    (the backward pass keeps one state a group and recomputes the rest),
    first forms what needs no S for the group's chunks at once: the WY
    form, (I + A) [u0 | w] = [beta v | beta k exp G] with A[i, j] = beta_i
    sum_d k_i k_j exp(G_i - G_j) for j < i, solved as one product with
    (I + A)^-1 (`_unit_lower_solve`), and P, the same products of q and k
    for j <= i; every exponent is at most 0 (`_decayed_products`; the pairs
    within a sub-chunk of both from one Pallas call, `_sub_chunk_pairs`).
    An inner `lax.scan` then carries S from chunk to chunk with four
    products a chunk: u = u0 - w S, o = (q exp G) S + P u, and the state
    it leaves, exp(G_C) S + (k exp(G_C - G))^T u.  Products at the
    precision "highest", for the state is f32."""
    dispatch_counts["kda_chunked"] += 1
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    sub = min(_KDA_SUB, chunk)
    if chunk % sub:
        raise ValueError(f"chunk {chunk}: a multiple of {sub}")
    group = min(_KDA_GROUP, -(-t // chunk))
    pad = -t % (chunk * group)
    n = (t + pad) // (chunk * group)
    mm = lambda spec, x, y: jnp.einsum(spec, x, y,
                                       precision=jax.lax.Precision.HIGHEST)

    def groups(x):
        """(B, T, H, ...) -> (N, group, B, H, C, ...)."""
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        return jnp.moveaxis(x.reshape(b, n, group, chunk, h, *x.shape[3:]),
                            (1, 2, 4), (0, 1, 3))

    def across(state, parts):
        u0, w, p, qd, kd, decay = parts
        u = u0 - mm("...cd,...de->...ce", w, state)
        out = mm("...cd,...de->...ce", qd, state) \
            + mm("...ij,...je->...ie", p, u)
        state = decay[..., None] * state + mm("...cd,...ce->...de", kd, u)
        return state, out.astype(v.dtype)

    def one(state, xs):
        qc, kc, vc, gc, bc = (x.astype(jnp.float32) for x in xs)
        cum = jnp.cumsum(gc, axis=-2)
        last, bc = cum[..., -1:, :], bc[..., None]
        a, p = _decayed_products(qc, kc, cum, sub)
        solved = _unit_lower_solve(
            bc * a,
            jnp.concatenate([bc * vc, bc * kc * jnp.exp(cum)], axis=-1))
        return jax.lax.scan(across, state, (
            solved[..., :dv], solved[..., dv:],
            p, qc * jnp.exp(cum),
            kc * jnp.exp(last - cum), jnp.exp(last[..., 0, :])))

    state = jnp.zeros((b, h, dk, dv), jnp.float32)
    _, out = jax.lax.scan(jax.checkpoint(one), state,
                          tuple(groups(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out, (0, 1, 3), (1, 2, 4)).reshape(
        b, n * group * chunk, h, dv)[:, :t]


def _l2_normalised(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def _drawn(fn):
    """An initializer that draws a parameter's values from the host's
    generator, fn(rng, shape), whatever the parameter's name."""
    from .. import initializer

    class Drawn(initializer.Initializer):
        def __call__(self, name, shape, dtype="float32"):
            return np.asarray(fn(host_rng(), shape), dtype)
    return Drawn()


def _a_log(rng, shape):
    """log A, A ~ U(1, 16) a head: decay rates exp(A_log) from 1 to 16."""
    return np.log(rng.uniform(1.0, 16.0, size=shape))


def _dt_bias(rng, shape, dt_min=1e-3, dt_max=0.1, floor=1e-4):
    """softplus^(-1) of dt, dt log-uniform in [dt_min, dt_max] (at least
    `floor`) a channel: the Mamba family's initial step sizes."""
    dt = np.maximum(np.exp(rng.uniform(np.log(dt_min), np.log(dt_max),
                                       size=shape)), floor)
    return dt + np.log(-np.expm1(-dt))


class KimiDeltaAttention(HybridBlock):
    """Kimi Delta Attention (arXiv:2510.26692), the token mixer of
    Kimi-Linear's `kda_layers`, per layer input x (B, T, units) and head:
    q, k, v = x W_q, x W_k, x W_v (units -> heads·head_dim each), each
    through a causal depthwise filter of `taps` positions and SiLU; q and k
    L2-normalised a head (eps 1e-6), q scaled by head_dim^-1/2; decays g =
    -exp(A_log) softplus(x W_f_down W_f_up + dt_bias) a key channel (rank
    head_dim; A_log a head, dt_bias a channel); beta = sigmoid(x W_b) a
    head; o from `kda_chunked`; y = W_o [RMS(o) * sigmoid(x W_g_down W_g_up +
    b_g)], the norm's weight of head_dim shared by the heads (`epsilon`)
    and the gate's path of rank head_dim with a bias on its up-projection.
    No other bias."""

    def __init__(self, units, num_heads=32, head_dim=128, taps=4,
                 epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        self._h, self._d = num_heads, head_dim
        width = num_heads * head_dim
        for name in ("q", "k", "v"):
            setattr(self, name + "_weight", self.params.get(
                name + "_weight", shape=(width, units)))
        for name in ("q", "k", "v"):
            setattr(self, name + "_conv_weight", self.params.get(
                name + "_conv_weight", shape=(width, taps)))
        self.f_a_weight = self.params.get("f_a_weight",
                                          shape=(head_dim, units))
        self.f_b_weight = self.params.get("f_b_weight",
                                          shape=(width, head_dim))
        self.A_log = self.params.get("A_log", shape=(num_heads,),
                                     init=_drawn(_a_log))
        self.dt_bias = self.params.get("dt_bias", shape=(width,),
                                       init=_drawn(_dt_bias))
        self.b_weight = self.params.get("b_weight", shape=(num_heads, units))
        self.g_a_weight = self.params.get("g_a_weight",
                                          shape=(head_dim, units))
        self.g_b_weight = self.params.get("g_b_weight",
                                          shape=(width, head_dim))
        self.g_b_bias = self.params.get("g_b_bias", shape=(width,),
                                        init="zeros")
        self.o_norm = nn.RMSNorm(epsilon=epsilon, in_channels=head_dim)
        self.o_weight = self.params.get("o_weight", shape=(units, width))

    def _conv(self, q, k, v, q_taps, k_taps, v_taps):
        """The three filters with SiLU, heads split, q and k normalised and
        q scaled; in the input's type."""
        (b, t), dtype = q.shape[:2], q.dtype
        heads = lambda a: a.reshape(b, t, self._h, self._d)
        q, k, v = (heads(jax.nn.silu(_causal_filter(a.astype(jnp.float32),
                                                    w)))
                   for a, w in ((q, q_taps), (k, k_taps), (v, v_taps)))
        return tuple(a.astype(dtype) for a in (
            _l2_normalised(q) * self._d ** -0.5, _l2_normalised(k), v))

    def _scan(self, q, k, v, f, a_log, dt_bias, z_beta):
        """The decays, beta and the chunked rule: o (B, T, H, head_dim)."""
        f32 = jnp.float32
        b, t = f.shape[:2]
        g = -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(
            (f.astype(f32) + dt_bias.astype(f32)).reshape(b, t, self._h,
                                                          self._d))
        return kda_chunked(q, k, v, g, jax.nn.sigmoid(z_beta.astype(f32)))

    @staticmethod
    def _gate(o, z, bias):
        """RMS(o) (B, T, H, d) times sigmoid(z + b_g) (B, T, H·d), in f32,
        heads merged."""
        b, t = z.shape[:2]
        gate = jax.nn.sigmoid(z.astype(jnp.float32)
                              + bias.astype(jnp.float32))
        return (o.reshape(b, t, -1).astype(jnp.float32) * gate).astype(
            o.dtype)

    def hybrid_forward(self, F, x, q_weight, k_weight, v_weight,
                       q_conv_weight, k_conv_weight, v_conv_weight,
                       f_a_weight, f_b_weight, A_log, dt_bias, b_weight,
                       g_a_weight, g_b_weight, g_b_bias, o_weight):
        with jax.named_scope(_KDA_PROJECT):
            q, k, v = (_linear(F, x, w)
                       for w in (q_weight, k_weight, v_weight))
            f = _linear(F, _linear(F, x, f_a_weight), f_b_weight)
            z_beta = _linear(F, x, b_weight)
            z = _linear(F, _linear(F, x, g_a_weight), g_b_weight)
        with jax.named_scope(_KDA_CONV):
            q, k, v = ops._apply(self._conv, [q, k, v, q_conv_weight,
                                              k_conv_weight, v_conv_weight],
                                 "kda_conv")
        with jax.named_scope(_KDA_SCAN):
            o = ops._apply(self._scan, [q, k, v, f, A_log, dt_bias, z_beta],
                           "kda_scan")
        with jax.named_scope(_KDA_PROJECT):
            o = ops._apply(self._gate, [self.o_norm(o), z, g_b_bias],
                           "kda_gate")
            return _linear(F, o, o_weight)


class DecoderLayer(HybridBlock):
    """Pre-norm residual layer: x + attention(RMS(x)), then x + ffn(RMS(x));
    `attention` and `ffn` are the blocks given.  With
    `router_before_attention` the ffn (an expert layer) is also handed the
    layer's raw input, from which it takes its router's scores."""

    def __init__(self, units, attention, ffn, epsilon=1e-6,
                 router_before_attention=False, **kwargs):
        super().__init__(**kwargs)
        self.ln1 = nn.RMSNorm(epsilon=epsilon, in_channels=units)
        self.attention = attention
        self.ln2 = nn.RMSNorm(epsilon=epsilon, in_channels=units)
        self.ffn = ffn
        self._router_first = router_before_attention

    def hybrid_forward(self, F, x):
        y = x + self.attention(self.ln1(x))
        if self._router_first:
            return y + self.ffn(self.ln2(y), x)
        return y + self.ffn(self.ln2(y))


class _MultiTokenModule(HybridBlock):
    """One multi-token-prediction module (DeepSeek-V3, section 2.2): joins
    the main model's last hidden state with the embedding of the token
    after, passes one decoder layer, and ends in its own norm.  The
    embedding and the head are the main model's, shared."""

    def __init__(self, units, layer, epsilon, **kwargs):
        super().__init__(**kwargs)
        self.hnorm = nn.RMSNorm(epsilon=epsilon, in_channels=units)
        self.enorm = nn.RMSNorm(epsilon=epsilon, in_channels=units)
        self.eh_proj_weight = self.params.get(
            "eh_proj_weight", shape=(units, 2 * units))
        self.layer = layer
        self.final_norm = nn.RMSNorm(epsilon=epsilon, in_channels=units)

    def hybrid_forward(self, F, h, next_embed, eh_proj_weight):
        joined = F.concat(self.hnorm(h), self.enorm(next_embed), dim=-1)
        return self.final_norm(self.layer(_linear(F, joined, eh_proj_weight)))


def _logits(hidden, head):
    """f32: the MXU's accumulator, as for BERTModel's head."""
    return jnp.einsum("btu,vu->btv", hidden, head,
                      preferred_element_type=jnp.float32)


def _nll(logits, labels):
    return -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                labels[..., None], axis=-1)[..., 0]


def _next_token_loss(hidden, head, tokens, shift):
    """(logits (B, T, V) f32, mean cross-entropy of position i against
    token i + shift over the T - shift positions that have one)."""
    logits = _logits(hidden, head)
    labels = jnp.roll(tokens.astype(jnp.int32), -shift, axis=1)
    nll = _nll(logits, labels)
    n = tokens.shape[1] - shift
    valid = jnp.arange(tokens.shape[1]) < n
    return logits, jnp.sum(jnp.where(valid, nll, 0.0)) / (tokens.shape[0] * n)


def _chunked_next_token_loss(hidden, head, tokens, shift, chunk, stride):
    """_next_token_loss without the (B, T, V) logits: the loss is summed
    over chunks of `chunk` positions (it divides T) by a `lax.map` whose
    body is under `jax.checkpoint`, so that forward and backward each hold
    one chunk's logits at a time; the logits returned are those of every
    `stride`-th position only, from a product of their own (a step that
    does not read them does not compute them).  A chunk's positions of
    every sequence are one axis of rows before the product: with a batch
    above 1, XLA:TPU lowers the log-softmax's max over a (B, chunk, V)
    block into a reduce-window as wide as twice the vocabulary (16,383 at
    V 8,192), quadratic in V."""
    b, t = tokens.shape
    if t % chunk:
        raise ValueError(f"loss_chunk {chunk} does not divide T {t}")
    labels = jnp.roll(tokens.astype(jnp.int32), -shift, axis=1)
    valid = jnp.arange(t) < t - shift

    @jax.checkpoint
    def one(h, l, ok, w):
        logits = jnp.einsum("nu,vu->nv", h.reshape(-1, h.shape[-1]), w,
                            preferred_element_type=jnp.float32)
        ok = jnp.broadcast_to(ok, l.shape).reshape(-1)
        return jnp.sum(jnp.where(ok, _nll(logits, l.reshape(-1)), 0.0))
    split = lambda a: jnp.moveaxis(
        a.reshape(b, t // chunk, chunk, *a.shape[2:]), 1, 0)
    sums = jax.lax.map(lambda a: one(*a, head),
                       (split(hidden), split(labels),
                        valid.reshape(t // chunk, chunk)))
    return _logits(hidden[:, ::stride], head), \
        jnp.sum(sums) / (b * (t - shift))


class CausalLM(HybridBlock):
    """Causal language model: embedding, `num_dense_layers` leading layers
    with a GatedMLP, the rest with DroplessMoE (and a shared expert where
    `shared_hidden` is given), a final RMS norm, a head of its own or, with
    `tie_embeddings`, the embedding matrix itself; forward(tokens (B, T))
    returns (loss, logits) or, with `mtp_depth` 1, (loss, logits, mtp_logits):
    the objective is computed in the forward (train with
    `CompiledTrainStep(net, gluon.loss.PassThrough(), ...)`), mean
    next-token cross-entropy plus `mtp_weight` times the multi-token
    module's (position i against token i + 2).

    config: vocab_size, units, num_layers, num_dense_layers, dense_hidden,
    epsilon, attention (the attention block's arguments after units: one
    dict for every layer alike, or a list with one dict a layer, the
    multi-token module's last; `kind` "latent", the default, for
    LatentAttention, "grouped_query" for GroupedQueryAttention,
    "short_conv" for ShortConv, "kda" for KimiDeltaAttention),
    tie_embeddings, moe
    (hidden_size, num_experts, top_k,
    held_experts (lo, hi), scaling, shared_hidden, scoring, activation,
    router_before_attention), mtp_depth (0 or 1), mtp_weight, loss_chunk
    (the head's loss in chunks of so many positions; the logits returned
    are then those of every `logits_stride`-th position)."""

    def __init__(self, config, mesh=None, dtype="float32", remat=False,
                 **kwargs):
        super().__init__(**kwargs)
        cfg = self._cfg = dict(config)
        if cfg.get("mtp_depth", 0) not in (0, 1):
            raise ValueError("mtp_depth must be 0 or 1")
        units, eps = cfg["units"], cfg.get("epsilon", 1e-6)
        self.embed_weight = self.params.get(
            "embed_weight", shape=(cfg["vocab_size"], units))
        if not cfg.get("tie_embeddings"):
            self.head_weight = self.params.get(
                "head_weight", shape=(cfg["vocab_size"], units))
        self.final_norm = nn.RMSNorm(epsilon=eps, in_channels=units)

        def attention(i):
            att = cfg["attention"]
            att = dict(att[i] if isinstance(att, (list, tuple)) else att)
            kind = att.pop("kind", "latent")
            if kind == "latent":
                return LatentAttention(units, epsilon=eps, mesh=mesh, **att)
            if kind == "short_conv":
                return ShortConv(units, **att)
            if kind == "kda":
                return KimiDeltaAttention(units, epsilon=eps, **att)
            if kind != "grouped_query":
                raise ValueError(f"attention kind {kind!r}: latent, "
                                 "grouped_query, short_conv or kda")
            return GroupedQueryAttention(units, epsilon=eps, mesh=mesh,
                                         **att)

        def layer(sparse, i):
            moe = cfg["moe"] if sparse else {}
            if sparse:
                ffn = DroplessMoE(
                    units, moe["hidden_size"], moe["num_experts"],
                    moe["top_k"], held_experts=range(*moe["held_experts"]),
                    scaling=moe.get("scaling", 1.0),
                    shared=GatedMLP(units, moe["shared_hidden"])
                    if moe.get("shared_hidden") else None,
                    scoring=moe.get("scoring", "sigmoid"),
                    activation=moe.get("activation", "silu"))
            else:
                ffn = GatedMLP(units, cfg["dense_hidden"])
            return DecoderLayer(
                units, attention(i), ffn, epsilon=eps,
                router_before_attention=moe.get("router_before_attention",
                                                False))
        self.layers = nn.HybridSequential()
        for i in range(cfg["num_layers"]):
            self.layers.add(layer(i >= cfg.get("num_dense_layers", 0), i))
        if cfg.get("mtp_depth", 0):
            # the module's layer is one more (a list names it last)
            self.mtp = _MultiTokenModule(
                units, layer(True, cfg["num_layers"]), eps)
        if remat:
            # one checkpoint a layer, as BERTModel's: layer inputs stay,
            # the inside is recomputed in the backward pass
            for block in self.decoder_layers():
                block.remat()
        if dtype and str(dtype) != "float32":
            self.cast(dtype)

    def decoder_layers(self):
        """Every DecoderLayer, the multi-token module's last."""
        layers = list(self.layers._children.values())
        return layers + ([self.mtp.layer] if "mtp" in self._children else [])

    def hybrid_forward(self, F, tokens, embed_weight, head_weight=None):
        if head_weight is None:     # tied: the gradient sums both uses
            head_weight = embed_weight
        x = F.Embedding(tokens, embed_weight)
        for block in self.layers._children.values():
            x = block(x)
        chunk, stride = (self._cfg.get(k) for k in ("loss_chunk",
                                                    "logits_stride"))
        with jax.named_scope(_LM_HEAD):
            logits, loss = ops._apply(
                (lambda h, w, t: _next_token_loss(h, w, t, 1))
                if chunk is None else
                (lambda h, w, t: _chunked_next_token_loss(
                    h, w, t, 1, chunk, stride or 1)),
                [self.final_norm(x), head_weight, tokens], "next_token_loss")
        if "mtp" not in self._children:
            return loss, logits
        with jax.named_scope(_MTP):
            nxt = F.Embedding(
                ops._apply(lambda t: jnp.roll(t, -1, axis=1), [tokens],
                           "next_tokens", nondiff=True), embed_weight)
            mtp_logits, mtp_loss = ops._apply(
                lambda h, w, t: _next_token_loss(h, w, t, 2),
                [self.mtp(x, nxt), head_weight, tokens], "next_token_loss")
        return loss + self._cfg.get("mtp_weight", 0.3) * mtp_loss, logits, \
            mtp_logits
