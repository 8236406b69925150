"""Causal decoder blocks, named by mechanism and not by model (ROADMAP M1):
rotary positions, a gated MLP, latent attention, grouped-query attention
with an optional window, optional positions and optional q/k norms, a
gated short convolution, a pre-norm layer, and a causal language model
with its next-token loss inside the forward (whole, or in chunks of
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
tests/references/short_conv_decoder.py (tests/test_short_conv_decoder.py).
Training form only: nothing is absorbed, there is no cache (serving is
ROADMAP M4-M8).  No bias and no dropout anywhere.

bf16-friendly like models/bert.py: norm and softmax statistics, the
router's scores and the logits are f32 whatever the model's type.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray import ops
from ..parallel import DroplessMoE, attention as _attention
from ..parallel.moe import MOE_SCOPES

__all__ = ["rotary", "yarn_inv_freq", "GatedMLP", "LatentAttention",
           "GroupedQueryAttention", "short_conv_mix", "ShortConv",
           "DecoderLayer", "CausalLM", "DECODER_SCOPES", "ATTENTION_SCOPES",
           "ATTENTION_GATE_SCOPES", "CONV_SCOPES", "OWNER_SCOPES"]

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
    kernel takes one head size, so smaller values ride zero-padded)."""

    def __init__(self, units, num_heads, q_rank, kv_rank, nope_dim, rope_dim,
                 v_dim, rope_theta=10000.0, epsilon=1e-6, mesh=None,
                 **kwargs):
        super().__init__(**kwargs)
        if v_dim > nope_dim + rope_dim:
            raise ValueError(f"v_dim {v_dim} exceeds the key size "
                             f"{nope_dim + rope_dim}")
        self._h, self._dn, self._dr, self._dv = \
            num_heads, nope_dim, rope_dim, v_dim
        self._rkv, self._theta, self._mesh = kv_rank, float(rope_theta), mesh
        self.q_a_weight = self.params.get("q_a_weight", shape=(q_rank, units))
        self.q_a_norm = nn.RMSNorm(epsilon=epsilon, in_channels=q_rank)
        self.q_b_weight = self.params.get(
            "q_b_weight", shape=(num_heads * (nope_dim + rope_dim), q_rank))
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
        q = jnp.concatenate([q[..., :dn], rotary(q[..., dn:], self._theta)],
                            axis=-1)
        k_r = rotary(k_r[:, None], self._theta)               # (B, 1, T, dr)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (b, h, t, dr))], axis=-1)
        v = jnp.pad(kv[..., dn:], [(0, 0)] * 3 + [(0, dn + dr - dv)])
        return q, k, v

    def hybrid_forward(self, F, x, q_a_weight, q_b_weight, kv_a_weight,
                       kv_b_weight, o_weight):
        b, t = x.shape[:2]
        rkv = self._rkv
        with jax.named_scope(_PROJECT):
            q = _linear(F, self.q_a_norm(_linear(F, x, q_a_weight)),
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
    taps, t = weight.shape[-1], bcx.shape[-2]
    b, c, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    v = jnp.pad(b * x, [(0, 0)] * (bcx.ndim - 2) + [(taps - 1, 0), (0, 0)])
    w = weight.astype(jnp.float32)
    z = sum(v[..., i:i + t, :] * w[:, i] for i in range(taps))
    return (c * z).astype(bcx.dtype)


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
    "short_conv" for ShortConv), tie_embeddings, moe
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
            if kind != "grouped_query":
                raise ValueError(f"attention kind {kind!r}: latent, "
                                 "grouped_query or short_conv")
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
