"""Plain reference: a causal decoder whose layers mix full and windowed
grouped-query attention with a different number of query heads by kind, a
sigmoid gate on every head's output, rotary positions over half a head with
YaRN's frequencies in the full layers and over the whole head in the
windowed ones, a leading dense layer, and then sparse SwiGLU experts behind
a sigmoid router beside a shared expert; with its loss and the gradients of
it.  The equations are ISSUE 34's, for `Laguna-S-2.1` (poolside;
config.json's `layer_types`, `num_attention_heads_per_layer`, `gating`,
`rope_parameters`, `sliding_window`, `mlp_only_layers`, `norm_topk_prob`,
`moe_routed_scaling_factor`).  For layer l with H_l query heads over
`kv_heads` key/value heads of d, x the stream entering it:

    h = RMS_1(x);  q = h W_q (H_l·d), k = h W_k, v = h W_v (kv_heads·d), no
        bias;  g = sigmoid(h W_g), W_g: U -> H_l, one scalar a head a token
        [ASSUMED: the gate's function is the sigmoid and it multiplies the
        head's output before W_o, the head-wise form of arXiv:2505.06708;
        the config names the granularity only ("per-head")]
        [ASSUMED: no norm on q or k: no key names one]
    positions, pairs by halves of the turned part (x[i], x[i + r/2]):
      a full layer turns the first r = d/2 dimensions of a head
        (partial_rotary_factor 0.5) by YaRN's frequencies: for i in 0 ..
        r/2 - 1, f_i = theta^(-2i/r); c(n) = r ln(L / (2 pi n)) / (2 ln
        theta) clipped to [0, r - 1]; low = floor(c(beta_fast)), high =
        ceil(c(beta_slow)); ramp_i = clip((i - low) / (high - low), 0, 1);
        inv_freq_i = (f_i / factor) ramp_i + f_i (1 - ramp_i); cos and sin
        are multiplied by `attention_factor`; the other d - r dimensions
        pass unturned and unscaled;
      a windowed layer turns the whole head by theta_w^(-2i/d), unscaled.
    scores softmax(q k^T / sqrt(d)) over keys j <= i (full) or i - window <
        j <= i (windowed); query head h reads key/value head
        h // (H_l / kv_heads);  o_h = g_h (p v)_h;  y = x + concat(o) W_o
    u = RMS_2(y);  the leading layer: out = y + SwiGLU(u) of `dense` width
    else  s = sigmoid(u W_r) over all experts in f32
        [ASSUMED: sigmoid scores with a selection bias held at zero, the
        convention `norm_topk_prob` with a routed scaling of 2.5 comes from
        (arXiv:2412.19437); the config has no `scoring_func`]
        chosen = the top_k largest of s;  w = s / sum over chosen of s
        (norm_topk_prob) x scaling, applied to the experts' outputs
        out = y + sum over chosen and held e of w_e SwiGLU_e(u)
                + SwiGLU_shared(u)           (the shared expert unweighted)
    loss = mean next-token cross-entropy after RMS_f and an untied head
        [ASSUMED: the initializer is the caller's: this file is handed
        weights and draws none]

Straightforward jax.numpy in float32 under matmul precision "highest": no
kernel, no sort, no grouped product (every held expert applied to every
token under a dense mask), no cache.  Independent of tpu_mx: it is handed
the system's seeded weights as a plain nested dict, in the system's layouts
(dense weights (out, in); stacked expert weights (held, in, out)).  Its
concessions to memory change no number: `jax.checkpoint` around a layer,
attention in blocks of `BLOCK_Q` queries against all keys (8,192 x 8,192
scores of 72 heads never exist at once), the experts in blocks of
`BLOCK_ROWS` tokens and the head in blocks of `BLOCK_ROWS` positions, each
block a `lax.map` step under `jax.checkpoint`; the logits it returns are
those of every `hp["logit_stride"]`-th position.

The chip's share is an argument.  Experts: on a chip that holds the experts
`held = (lo, hi)` of `n_experts`, the sum over a token's chosen experts runs
over chosen and held, **with the weights normalised over all chosen**; what
the absent experts would add is left out, and the partial result goes on to
the next layer.  Vocabulary: the rows of `embed` and `head` that are handed
in are the vocabulary (a slice is a smaller vocabulary).  Heads: every
layer's heads are as published (ISSUE 34's rule left them whole).

`hp` (hyper-parameters, static): heads (one count a layer), kv_heads,
head_dim, sliding (a bool a layer), window, rope {"full", "sliding"} each
{theta, rotary_dim, yarn: None or {factor, original_length, beta_fast,
beta_slow, attention_factor}}, eps, top_k, scaling, n_experts,
logit_stride.  A layer is dense where its weights hold "mlp", sparse where
they hold "moe".

`forced`, one (S, k) array of expert ids for each expert layer in order,
takes the place of the top-k choice (a step function of the scores: a
comparison hands the system's own choice in here and holds the choice
itself to `route()` on the system's own layer inputs).

`wrong` selects a deliberately wrong variant, used only to place the
tolerances (a name; or, so that one compiled program serves them all, a
traced index into WRONG, -1 for none): "gate_off" (g = 1),
"gate_after_output_projection" (W_o's output times the mean of the heads'
gates: a scalar a head cannot act after the projection otherwise, W_o being
linear in each head), "rotary_whole_head_in_full_layers" (r = d there),
"yarn_off" (the full layers' plain theta^(-2i/r)), "attention_factor_off"
(cos and sin unscaled), "thetas_swapped" (each kind of layer takes the
other's theta), "window_off_by_one" (window + 1 keys), "softmax_scores"
(softmax over all experts for the sigmoid), "scaling_off" (no routed
scaling), "chosen_not_normalised" (w = s x scaling), "shared_expert_off".
`low` is the honest path in a lower precision than stated, which the
comparison must refuse: "router" (the router's scores from a bfloat16
product), "all" (weights, activations, statistics and logits all bfloat16,
products at the default precision).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

WRONG = ("gate_off", "gate_after_output_projection",
         "rotary_whole_head_in_full_layers", "yarn_off",
         "attention_factor_off", "thetas_swapped", "window_off_by_one",
         "softmax_scores", "scaling_off", "chosen_not_normalised",
         "shared_expert_off")
LOW = ("router", "all")
BLOCK_Q = 256
BLOCK_ROWS = 2048


def _is(wrong, name):
    """Whether the variant `name` is on: a Python bool for a name or None,
    a traced one for a traced index into WRONG."""
    if wrong is None or isinstance(wrong, str):
        return wrong == name
    return wrong == WRONG.index(name)


def _blocks(fn, n, size, *arrays):
    """fn over blocks of `size` along the leading axis (length n) of every
    array, one block at a time, and the results joined again; whole where
    `size` does not divide n (the small tests)."""
    if n <= size or n % size:
        return fn(*arrays)
    out = jax.lax.map(
        lambda a: jax.checkpoint(fn)(*a),
        tuple(a.reshape(n // size, size, *a.shape[1:]) for a in arrays))
    return jax.tree.map(lambda o: o.reshape(n, *o.shape[2:]), out)


def rms_norm(x, g, eps):
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + eps) * g).astype(x.dtype)


def dense(x, w):
    """Weights are (out, in), as the system keeps them; no bias anywhere."""
    return x @ w.T


def swiglu(x, p):
    return dense(jax.nn.silu(dense(x, p["gate"])) * dense(x, p["up"]),
                 p["down"])


def inv_freq(theta, r, yarn=None):
    """The r/2 inverse frequencies of a turned part of r dimensions, as the
    equations at the head of this file have them; float64 numpy."""
    f = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    if yarn is None:
        return f
    c = lambda n: float(np.clip(
        r * math.log(yarn["original_length"] / (2 * math.pi * n))
        / (2 * math.log(theta)), 0, r - 1))
    low, high = math.floor(c(yarn["beta_fast"])), \
        math.ceil(c(yarn["beta_slow"]))
    ramp = np.clip((np.arange(r // 2) - low) / max(high - low, 1e-3), 0, 1)
    return f / yarn["factor"] * ramp + f * (1 - ramp)


def rope(x, hp, sliding, wrong=None):
    """x (..., T, d) with the first r dimensions of the head turned by the
    layer kind's positions, the rest as they are."""
    d = x.shape[-1]
    mine = hp["rope"]["sliding" if sliding else "full"]
    other = hp["rope"]["full" if sliding else "sliding"]
    yarn = mine["yarn"]

    def turn(r):
        freq = jnp.where(
            _is(wrong, "thetas_swapped"), inv_freq(other["theta"], r, yarn),
            jnp.where(_is(wrong, "yarn_off"), inv_freq(mine["theta"], r),
                      inv_freq(mine["theta"], r, yarn))).astype(jnp.float32)
        ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freq
        factor = 1.0 if yarn is None else jnp.where(
            _is(wrong, "attention_factor_off"), 1.0,
            yarn["attention_factor"])
        cos, sin = (jnp.cos(ang) * factor).astype(x.dtype), \
            (jnp.sin(ang) * factor).astype(x.dtype)
        a, b = x[..., :r // 2], x[..., r // 2:r]
        return jnp.concatenate(
            [a * cos - b * sin, a * sin + b * cos, x[..., r:]], -1)
    honest = turn(mine["rotary_dim"])
    if sliding or mine["rotary_dim"] == d:
        return honest
    return jnp.where(_is(wrong, "rotary_whole_head_in_full_layers"),
                     turn(d), honest)


def heads(x, p, hp, n_heads, sliding, wrong=None):
    """q (b, n_heads, t, d), k and v (b, kv_heads, t, d) of one layer, q and
    k turned."""
    b, t, _ = x.shape
    split = lambda a, n: a.reshape(b, t, n, hp["head_dim"]).transpose(
        0, 2, 1, 3)
    q, k, v = split(dense(x, p["q"]), n_heads), \
        split(dense(x, p["k"]), hp["kv_heads"]), \
        split(dense(x, p["v"]), hp["kv_heads"])
    return rope(q, hp, sliding, wrong), rope(k, hp, sliding, wrong), v


def attend(q, k, v, hp, sliding, wrong=None):
    """softmax(q k^T / sqrt(d) under the layer's mask) v, (b, heads, t, d):
    query head h reads key/value head h // (heads / kv_heads); a block of
    queries at a time against all keys."""
    h, hk, (t, d) = q.shape[1], k.shape[1], q.shape[2:]
    reads = jnp.arange(h) // (h // hk)
    k, v = k[:, reads], v[:, reads]                          # (b, h, t, d)
    # how many keys a query sees, itself among them: t and more is all
    width = hp["window"] + jnp.int32(_is(wrong, "window_off_by_one")) \
        if sliding else t + 1
    kt = k.transpose(0, 1, 3, 2)

    def block(qb, at):
        """qb (bq, b, h, d) queries at positions `at` (bq,), all keys."""
        s = jnp.einsum("qbhd,bhdk->bhqk", qb, kt) / math.sqrt(d)
        behind = at[:, None] - jnp.arange(t)[None, :]
        s = jnp.where((behind >= 0) & (behind < width), s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->qbhd", jax.nn.softmax(s, -1), v)
    return _blocks(block, t, BLOCK_Q, q.transpose(2, 0, 1, 3),
                   jnp.arange(t)).transpose(1, 2, 0, 3)


def attention(x, p, hp, n_heads, sliding, wrong=None):
    """Head-gated grouped-query attention of one layer."""
    b, t, _ = x.shape
    out = attend(*heads(x, p, hp, n_heads, sliding, wrong), hp, sliding,
                 wrong)                                      # (b, h, t, d)
    g = jnp.where(_is(wrong, "gate_off"), 1.0,
                  jax.nn.sigmoid(dense(x, p["g"]))).astype(x.dtype)
    late = _is(wrong, "gate_after_output_projection")
    each = jnp.where(late, jnp.ones_like(g), g)              # (b, t, h)
    out = out.transpose(0, 2, 1, 3) * each[..., None]
    y = dense(out.reshape(b, t, -1), p["o"])
    return y * jnp.where(late, jnp.mean(g, -1, keepdims=True), 1.0).astype(
        x.dtype)


def route(x, p, hp, held, wrong=None, low=None, forced=None):
    """(chosen (S, k) expert ids, their weights (S, k)), over all experts,
    from the rows x that the router reads; `forced` (S, k) takes the place
    of the choice."""
    if low == "router":
        logits = (x.astype(jnp.bfloat16)
                  @ p["router"].T.astype(jnp.bfloat16)).astype(jnp.float32)
    else:
        logits = x @ p["router"].T
    s = jnp.where(_is(wrong, "softmax_scores"), jax.nn.softmax(logits, -1),
                  jax.nn.sigmoid(logits))
    _, chosen = jax.lax.top_k(s + p["bias"], hp["top_k"])
    if forced is not None:
        chosen = forced
    picked = jnp.take_along_axis(s, chosen, -1)
    total = jnp.where(_is(wrong, "chosen_not_normalised"), 1.0,
                      jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return chosen, picked / total * jnp.where(_is(wrong, "scaling_off"), 1.0,
                                              hp["scaling"])


def experts(u, chosen, weights, p, held):
    """The held experts' part for rows u (S, U): each applied to every row
    under a dense mask (w is 0 where a row did not choose the expert)."""
    ids = jnp.arange(held[0], held[1])

    def block(ub, cb, wb):
        hit = cb[:, :, None] == ids[None, None, :]            # (s, k, held)
        w = jnp.sum(jnp.where(hit, wb[:, :, None], 0.0), 1)   # (s, held)
        act = jax.nn.silu(jnp.einsum("su,eui->sei", ub, p["w1"])) \
            * jnp.einsum("su,eui->sei", ub, p["w3"])
        return jnp.einsum("sei,eiu,se->su", act, p["w2"], w.astype(ub.dtype))
    return _blocks(block, u.shape[0], BLOCK_ROWS, u, chosen, weights)


def expert_layer(u, p, hp, held, wrong=None, low=None, forced=None):
    """The routed experts held here plus the shared expert, which every
    token passes unweighted."""
    flat = u.reshape(-1, u.shape[-1])
    chosen, weights = route(flat, p, hp, held, wrong, low, forced)
    shared = _blocks(lambda ub: swiglu(ub, p["shared"]), flat.shape[0],
                     BLOCK_ROWS, flat)
    out = experts(flat, chosen, weights, p, held) \
        + jnp.where(_is(wrong, "shared_expert_off"), 0.0, shared)
    return out.reshape(u.shape)


def layer(x, p, hp, held, n_heads, sliding, wrong=None, low=None,
          forced=None):
    y = x + attention(rms_norm(x, p["ln1"], hp["eps"]), p["attn"], hp,
                      n_heads, sliding, wrong)
    u = rms_norm(y, p["ln2"], hp["eps"])
    if "moe" in p:
        return y + expert_layer(u, p["moe"], hp, held, wrong, low, forced)
    flat = u.reshape(-1, u.shape[-1])
    return y + _blocks(lambda ub: swiglu(ub, p["mlp"]), flat.shape[0],
                       BLOCK_ROWS, flat).reshape(u.shape)


def head_loss(hidden, head, labels, n_valid):
    """Mean cross-entropy over the first n_valid positions of each sequence,
    the logits a block of positions at a time."""
    b, t, _ = hidden.shape

    def block(hb, lb, ok):
        logp = jax.nn.log_softmax(dense(hb, head).astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(logp, lb[..., None], -1)[..., 0]
        return jnp.where(ok[:, None], nll, 0.0)
    nll = _blocks(block, t, BLOCK_ROWS, hidden.transpose(1, 0, 2),
                  labels.T, jnp.arange(t) < n_valid)
    return jnp.sum(nll) / (b * n_valid)


def forward(weights, tokens, hp, held, wrong=None, low=None, forced=None):
    """{"logits" (b, t / logit_stride, vocab): those of every
    logit_stride-th position, "loss"}."""
    dtype = jnp.bfloat16 if low == "all" else jnp.float32
    weights = jax.tree.map(lambda w: w.astype(dtype), weights)
    hp = dict(hp)
    choices = iter(forced or ())
    with jax.default_matmul_precision(
            "default" if low == "all" else "highest"):
        t = tokens.shape[1]
        x = weights["embed"][tokens]
        for i, p in enumerate(weights["layers"]):
            choice = next(choices, None) if "moe" in p else None
            x = jax.checkpoint(
                lambda x, p, choice, i=i: layer(
                    x, p, hp, held, hp["heads"][i], hp["sliding"][i], wrong,
                    low, choice))(x, p, choice)
        hidden = rms_norm(x, weights["final_norm"], hp["eps"])
        return {"logits": dense(hidden[:, ::hp.get("logit_stride", 1)],
                                weights["head"]),
                "loss": head_loss(hidden, weights["head"],
                                  jnp.roll(tokens, -1, 1), t - 1)}


def loss_and_grads(weights, tokens, hp, held, wrong=None, low=None,
                   forced=None):
    """(forward's outputs, d loss / d weights as the same nested dict)."""
    def f(w):
        out = forward(w, tokens, hp, held, wrong, low, forced)
        return out["loss"].astype(jnp.float32), out
    weights = jax.tree.map(lambda w: w.astype(jnp.float32), weights)
    (_, out), grads = jax.value_and_grad(f, has_aux=True)(weights)
    return out, grads
