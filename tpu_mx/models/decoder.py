"""Causal decoder blocks, named by mechanism and not by model (ROADMAP M1):
rotary positions, a gated MLP, latent attention, a pre-norm layer, and a
causal language model with its next-token loss inside the forward and an
optional multi-token-prediction module.  The expert layer is
`parallel.DroplessMoE`.

The equations are those of the DeepSeek-V3 family (arXiv:2412.19437,
sections 2.1 and 2.2); tests/references/latent_moe_decoder.py is their plain
float32 form, and tests/test_latent_moe_decoder.py holds the two together.
Training form only: nothing is absorbed, there is no cache (serving is
ROADMAP M4-M8).  No bias and no dropout anywhere.

bf16-friendly like models/bert.py: norm and softmax statistics, the
router's scores and the logits are f32 whatever the model's type.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray import ops
from ..parallel import DroplessMoE, attention as _attention
from ..parallel.moe import MOE_SCOPES

__all__ = ["rotary", "GatedMLP", "LatentAttention", "DecoderLayer",
           "CausalLM", "DECODER_SCOPES"]

# jax.named_scope names inside these blocks (HLO metadata only), beside
# train_step.STEP_SCOPES; benchmark/decoder_scopes.py holds them as literals
DECODER_SCOPES = ("mla.project", "mla.attend") + MOE_SCOPES \
    + ("mtp", "lm_head")
_PROJECT, _ATTEND = DECODER_SCOPES[:2]
_MTP, _LM_HEAD = DECODER_SCOPES[-2:]


def rotary(x, theta):
    """Rotary positions (Su et al., arXiv:2104.09864) over the whole last
    axis of x (..., T, d), pairs interleaved: (x[2i], x[2i+1]) turns by
    position · theta^(-2i/d).  Angles and the turn in f32."""
    t, d = x.shape[-2], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
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


class DecoderLayer(HybridBlock):
    """Pre-norm residual layer: x + attention(RMS(x)), then x + ffn(RMS(x));
    `attention` and `ffn` are the blocks given."""

    def __init__(self, units, attention, ffn, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        self.ln1 = nn.RMSNorm(epsilon=epsilon, in_channels=units)
        self.attention = attention
        self.ln2 = nn.RMSNorm(epsilon=epsilon, in_channels=units)
        self.ffn = ffn

    def hybrid_forward(self, F, x):
        x = x + self.attention(self.ln1(x))
        return x + self.ffn(self.ln2(x))


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


def _next_token_loss(hidden, head, tokens, shift):
    """(logits (B, T, V) f32, mean cross-entropy of position i against
    token i + shift over the T - shift positions that have one).  The f32
    is the MXU's accumulator, as for BERTModel's head."""
    logits = jnp.einsum("btu,vu->btv", hidden, head,
                        preferred_element_type=jnp.float32)
    labels = jnp.roll(tokens.astype(jnp.int32), -shift, axis=1)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                               labels[..., None], axis=-1)[..., 0]
    n = tokens.shape[1] - shift
    valid = jnp.arange(tokens.shape[1]) < n
    return logits, jnp.sum(jnp.where(valid, nll, 0.0)) / (tokens.shape[0] * n)


class CausalLM(HybridBlock):
    """Causal language model: embedding, `num_dense_layers` leading layers
    with a GatedMLP, the rest with DroplessMoE (and a shared expert), a
    final RMS norm, an untied head; forward(tokens (B, T)) returns
    (loss, logits) or, with `mtp_depth` 1, (loss, logits, mtp_logits):
    the objective is computed in the forward (train with
    `CompiledTrainStep(net, gluon.loss.PassThrough(), ...)`), mean
    next-token cross-entropy plus `mtp_weight` times the multi-token
    module's (position i against token i + 2).

    config: vocab_size, units, num_layers, num_dense_layers, dense_hidden,
    epsilon, attention (LatentAttention's arguments after units), moe
    (hidden_size, num_experts, top_k, held_experts (lo, hi), scaling,
    shared_hidden), mtp_depth (0 or 1), mtp_weight."""

    def __init__(self, config, mesh=None, dtype="float32", remat=False,
                 **kwargs):
        super().__init__(**kwargs)
        cfg = self._cfg = dict(config)
        if cfg.get("mtp_depth", 0) not in (0, 1):
            raise ValueError("mtp_depth must be 0 or 1")
        units, eps = cfg["units"], cfg.get("epsilon", 1e-6)
        self.embed_weight = self.params.get(
            "embed_weight", shape=(cfg["vocab_size"], units))
        self.head_weight = self.params.get(
            "head_weight", shape=(cfg["vocab_size"], units))
        self.final_norm = nn.RMSNorm(epsilon=eps, in_channels=units)

        def layer(sparse):
            if sparse:
                moe = cfg["moe"]
                ffn = DroplessMoE(
                    units, moe["hidden_size"], moe["num_experts"],
                    moe["top_k"], held_experts=range(*moe["held_experts"]),
                    scaling=moe.get("scaling", 1.0),
                    shared=GatedMLP(units, moe["shared_hidden"])
                    if moe.get("shared_hidden") else None)
            else:
                ffn = GatedMLP(units, cfg["dense_hidden"])
            return DecoderLayer(
                units, LatentAttention(units, epsilon=eps, mesh=mesh,
                                       **cfg["attention"]), ffn, epsilon=eps)
        self.layers = nn.HybridSequential()
        for i in range(cfg["num_layers"]):
            self.layers.add(layer(i >= cfg.get("num_dense_layers", 0)))
        if cfg.get("mtp_depth", 0):
            self.mtp = _MultiTokenModule(units, layer(True), eps)
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

    def hybrid_forward(self, F, tokens, embed_weight, head_weight):
        x = F.Embedding(tokens, embed_weight)
        for block in self.layers._children.values():
            x = block(x)
        with jax.named_scope(_LM_HEAD):
            logits, loss = ops._apply(
                lambda h, w, t: _next_token_loss(h, w, t, 1),
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
