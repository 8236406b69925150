"""Plain reference: a causal decoder with latent attention, sparse experts
that drop no token, and a depth-1 multi-token-prediction module, with its
loss and the gradients of it.  The equations are those of the DeepSeek-V3
family (arXiv:2412.19437, sections 2.1 and 2.2), which `glm4_moe_lite`
(GLM-4.7-Flash) follows: pre-norm layers with RMS norm, low-rank query and
key/value paths with a rotary part that all heads share, `noaux_tc` sigmoid
routing with a selection bias that enters the choice only, a shared expert,
one leading dense layer.

Straightforward jax.numpy in float32 under matmul precision "highest": no
kernel, no sort, no grouped product (every held expert applied to every
token under a dense mask, as one einsum over the held experts: the Python
loop it replaces cost a minute more of XLA's time in every run), no cache.
Independent of tpu_mx: it is handed the system's seeded weights as a plain
nested dict, in the system's layouts (dense weights (out, in); stacked
expert weights (held, in, out)).  The one concession to
memory is `jax.checkpoint` around a layer, so that a 4096-token sequence's
score tensors are held for one layer at a time; it changes no number.

On a chip that holds the experts `held = (lo, hi)` of `n_experts`, the sum
over a token's chosen experts runs over chosen ∩ held, **with the weights
normalised over all chosen**; what the absent experts would add is left
out, and the partial result goes on to the next layer.

`hp` (hyper-parameters, static): heads, nope, rope, v_dim, theta, eps,
top_k, scaling, n_experts, mtp_lambda.

`forced`, one (S, k) array of expert ids for each expert layer in order
(the multi-token module's last), takes the place of the top-k choice: the
choice is a step function of the scores, so a program in bfloat16 moves a
few tokens in a hundred across its boundary, and an error made of such
flips says nothing of the mathematics.  A comparison hands the system's own
choice in here and holds the choice itself to `route()` on the system's own
layer inputs.

`wrong` selects a deliberately wrong variant, used only to place the
tolerances (a name; or, so that one compiled program serves them all, a
traced index into WRONG, -1 for none): "bias_in_weight" (the selection
bias also enters the weights), "no_scaling" (routed_scaling_factor left
out), "softmax_gate" (softmax scores in place of sigmoid), "capacity_1" (an
expert drops what exceeds the mean load), "no_rope" (no rotary positions),
"norm_over_held" (weights normalised over chosen ∩ held).  `low` is the honest path in a lower
precision than stated, which the comparison must refuse: "softmax"
(attention's softmax in bfloat16), "router" (the router's scores from a
bfloat16 product), "all" (weights, activations, statistics and logits all
bfloat16, products at the default precision).
"""
import math

import jax
import jax.numpy as jnp

WRONG = ("bias_in_weight", "no_scaling", "softmax_gate", "capacity_1",
         "no_rope", "norm_over_held")
LOW = ("softmax", "router", "all")


def _is(wrong, name):
    """Whether the variant `name` is on: a Python bool for a name or None,
    a traced one for a traced index into WRONG."""
    if wrong is None or isinstance(wrong, str):
        return wrong == name
    return wrong == WRONG.index(name)


def rms_norm(x, g, eps):
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + eps) * g).astype(x.dtype)


def dense(x, w):
    """Weights are (out, in), as the system keeps them; no bias anywhere."""
    return x @ w.T


def rope(x, theta, off=False):
    """Rotary positions over the whole last axis of x (..., T, d), pairs
    interleaved: (x[2i], x[2i+1]) turns by position * theta**(-2i/d); by
    nothing where `off`."""
    t, d = x.shape[-2], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    ang = jnp.where(off, 0.0, ang)
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def latent_attention(x, p, hp, wrong=None, low=None):
    b, t, _ = x.shape
    h, dn, dr, dv = hp["heads"], hp["nope"], hp["rope"], hp["v_dim"]
    rkv = p["kv_a_norm"].shape[0]
    c_q = rms_norm(dense(x, p["q_a"]), p["q_a_norm"], hp["eps"])
    q = dense(c_q, p["q_b"]).reshape(b, t, h, dn + dr).transpose(0, 2, 1, 3)
    kv_a = dense(x, p["kv_a"])
    c_kv = rms_norm(kv_a[..., :rkv], p["kv_a_norm"], hp["eps"])
    k_r = kv_a[..., rkv:][:, None]                          # (b, 1, t, dr)
    kv = dense(c_kv, p["kv_b"]).reshape(b, t, h, dn + dv).transpose(0, 2, 1, 3)
    k_n, v = kv[..., :dn], kv[..., dn:]
    q_n, q_r = q[..., :dn], q[..., dn:]
    off = _is(wrong, "no_rope")
    q_r, k_r = rope(q_r, hp["theta"], off), rope(k_r, hp["theta"], off)
    q = jnp.concatenate([q_n, q_r], -1)
    k = jnp.concatenate([k_n, jnp.broadcast_to(k_r, (b, h, t, dr))], -1)
    scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(dn + dr)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    if low == "softmax":
        probs = jax.nn.softmax(scores.astype(jnp.bfloat16),
                               axis=-1).astype(jnp.float32)
    else:
        probs = jax.nn.softmax(scores, axis=-1)
    out = (probs @ v).transpose(0, 2, 1, 3).reshape(b, t, h * dv)
    return dense(out, p["o"])


def swiglu(x, gate, up, down):
    """(in, out) matrices: one expert's slice of the stacked weights."""
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(x, p, hp, held, wrong=None, low=None, forced=None):
    """(chosen (S, k) expert ids, their weights (S, k)), over all experts;
    `forced` (S, k) takes the place of the choice."""
    if low == "router":
        logits = (x.astype(jnp.bfloat16)
                  @ p["router"].T.astype(jnp.bfloat16)).astype(jnp.float32)
    else:
        logits = x @ p["router"].T
    s = jnp.where(_is(wrong, "softmax_gate"), jax.nn.softmax(logits, -1),
                  jax.nn.sigmoid(logits))
    _, chosen = jax.lax.top_k(s + p["bias"], hp["top_k"])
    if forced is not None:
        chosen = forced
    picked = jnp.take_along_axis(
        s + jnp.where(_is(wrong, "bias_in_weight"), p["bias"], 0), chosen, -1)
    here = (chosen >= held[0]) & (chosen < held[1])
    absent = _is(wrong, "norm_over_held") & ~here
    picked = jnp.where(absent, 0.0, picked)
    weights = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    weights = weights * jnp.where(_is(wrong, "no_scaling"), 1.0,
                                  hp["scaling"])
    return chosen, weights


def expert_layer(x, p, hp, held, wrong=None, low=None, forced=None):
    """The routed experts held here, each applied to every token under a
    dense mask (w is 0 where a token did not choose the expert), plus the
    shared expert that every token passes."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    chosen, weights = route(x, p, hp, held, wrong, low, forced)
    capacity = math.ceil(x.shape[0] * hp["top_k"] / hp["n_experts"])
    ids = jnp.arange(held[0], held[1])
    hit = chosen[:, :, None] == ids[None, None, :]            # (S, k, held)
    w = jnp.sum(jnp.where(hit, weights[:, :, None], 0.0), 1)  # (S, held)
    over = jnp.cumsum(jnp.any(hit, 1), 0) > capacity
    w = jnp.where(_is(wrong, "capacity_1") & over, 0.0, w)
    act = jax.nn.silu(jnp.einsum("su,eui->sei", x, p["w1"])) \
        * jnp.einsum("su,eui->sei", x, p["w3"])
    y = jnp.einsum("sei,eiu,se->su", act, p["w2"], w)
    sh = p["shared"]
    y = y + swiglu(x, sh["gate"].T, sh["up"].T, sh["down"].T)
    return y.reshape(shape)


def layer(x, p, hp, held, wrong=None, low=None, forced=None):
    x = x + latent_attention(rms_norm(x, p["ln1"], hp["eps"]), p["attn"],
                             hp, wrong, low)
    h = rms_norm(x, p["ln2"], hp["eps"])
    if "moe" in p:
        return x + expert_layer(h, p["moe"], hp, held, wrong, low, forced)
    m = p["mlp"]
    return x + swiglu(h, m["gate"].T, m["up"].T, m["down"].T)


def cross_entropy(logits, labels, n_valid):
    """Mean over the first n_valid positions of each sequence."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    nll = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    valid = jnp.arange(logits.shape[1]) < n_valid
    return jnp.sum(jnp.where(valid, nll, 0.0)) / (logits.shape[0] * n_valid)


def forward(weights, tokens, hp, held, wrong=None, low=None, forced=None):
    """{"logits", "mtp_logits" (b, t, vocab), "loss_main", "loss_mtp",
    "loss"}.  The multi-token module runs over all t positions, position i
    fed the embedding of token i+1 (the last position wraps round and is
    masked out of its loss; the causal mask keeps it from the others)."""
    dtype = jnp.bfloat16 if low == "all" else jnp.float32
    weights = jax.tree.map(lambda w: w.astype(dtype), weights)
    hp = dict(hp)
    choices = iter(forced or ())
    layer_ = jax.checkpoint(
        lambda x, p, choice: layer(x, p, hp, held, wrong, low, choice))

    def one(x, p):
        return layer_(x, p, next(choices, None) if "moe" in p else None)
    with jax.default_matmul_precision(
            "default" if low == "all" else "highest"):
        t = tokens.shape[1]
        x = weights["embed"][tokens]
        for p in weights["layers"]:
            x = one(x, p)
        logits = dense(rms_norm(x, weights["final_norm"], hp["eps"]),
                       weights["head"])
        out = {"logits": logits,
               "loss_main": cross_entropy(logits, jnp.roll(tokens, -1, 1),
                                          t - 1)}
        out["loss"] = out["loss_main"]
        m = weights.get("mtp")
        if m is not None:
            nxt = weights["embed"][jnp.roll(tokens, -1, 1)]
            h = dense(jnp.concatenate(
                [rms_norm(x, m["hnorm"], hp["eps"]),
                 rms_norm(nxt, m["enorm"], hp["eps"])], -1), m["eh_proj"])
            h = one(h, m["layer"])
            mtp_logits = dense(rms_norm(h, m["final_norm"], hp["eps"]),
                               weights["head"])
            out["mtp_logits"] = mtp_logits
            out["loss_mtp"] = cross_entropy(
                mtp_logits, jnp.roll(tokens, -2, 1), t - 2)
            out["loss"] = out["loss_main"] + hp["mtp_lambda"] * out["loss_mtp"]
        return out


def loss_and_grads(weights, tokens, hp, held, wrong=None, low=None,
                   forced=None):
    """(forward's outputs, d loss / d weights as the same nested dict)."""
    def f(w):
        out = forward(w, tokens, hp, held, wrong, low, forced)
        return out["loss"], out
    weights = jax.tree.map(lambda w: w.astype(jnp.float32), weights)
    (_, out), grads = jax.value_and_grad(f, has_aux=True)(weights)
    return out, grads
