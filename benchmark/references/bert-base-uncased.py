"""Plain reference: BERT encoder with the masked-language-model head
(Devlin et al., arXiv:1810.04805, section 3 and appendix A.2; the block is
Vaswani et al.'s post-LayerNorm encoder layer).

Straightforward jax.numpy in float32 under matmul precision "highest": no
kernels, no fused attention, no dropout (the comparison runs with dropout
off).  Independent of tpu_mx: it is handed the system's seeded weights as a
plain nested dict (see configs/bert-base-uncased.py `weights`).

Departures from the published model, taken from the system so that the same
function is compared: LayerNorm epsilon 1e-5 (the published config.json has
1e-12); no next-sentence head and no pooler; the vocabulary head runs on the
masked positions only, which changes no logit that the loss reads.

`wrong` selects a deliberately wrong variant, used only to place the
tolerance: "unscaled_scores" (no 1/sqrt(d)), "pre_ln" (LayerNorm before the
sublayer instead of after the residual).  (The tanh approximation of GELU
is no use for that: it moves the logits by less than bf16 rounding does.)
"""
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["gamma"] + p["beta"]


def dense(x, w, b):
    """Weights are (out, in), as the system keeps them."""
    return x @ w.T + b


def attention(x, p, heads, scale_scores=True):
    b, t, u = x.shape
    d = u // heads
    qkv = dense(x, p["qkv_weight"], p["qkv_bias"]).reshape(b, t, 3, heads, d)
    q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
    scores = q @ k.transpose(0, 1, 3, 2)
    if scale_scores:
        scores = scores / math.sqrt(d)
    out = jax.nn.softmax(scores, axis=-1) @ v
    out = out.transpose(0, 2, 1, 3).reshape(b, t, u)
    return dense(out, p["out_weight"], p["out_bias"])


def encoder_layer(x, p, heads, wrong):
    scale = wrong != "unscaled_scores"

    def ffn(h):
        h = jax.nn.gelu(dense(h, p["ffn1_weight"], p["ffn1_bias"]),
                        approximate=False)
        return dense(h, p["ffn2_weight"], p["ffn2_bias"])
    if wrong == "pre_ln":
        x = x + attention(layer_norm(x, p["ln1"]), p, heads, scale)
        return x + ffn(layer_norm(x, p["ln2"]))
    x = layer_norm(x + attention(x, p, heads, scale), p["ln1"])
    return layer_norm(x + ffn(x), p["ln2"])


def forward(weights, tokens, token_types, masked_positions, heads,
            wrong=None):
    """Logits (batch, masked, vocab) at the masked positions.  `weights`
    may come in the system's type: they are taken to float32 here."""
    weights = jax.tree.map(lambda w: w.astype(jnp.float32), weights)
    with jax.default_matmul_precision("highest"):
        t = tokens.shape[1]
        x = (weights["word_embed"][tokens] + weights["type_embed"][token_types]
             + weights["pos_embed"][:t][None])
        x = layer_norm(x, weights["embed_ln"])
        for p in weights["layers"]:
            x = encoder_layer(x, p, heads, wrong)
        x = jnp.take_along_axis(x, masked_positions[..., None], axis=1)
        h = jax.nn.gelu(dense(x, weights["mlm_dense_weight"],
                              weights["mlm_dense_bias"]), approximate=False)
        h = layer_norm(h, weights["mlm_ln"])
        return h @ weights["word_embed"].T + weights["mlm_bias"]
