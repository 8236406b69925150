"""Plain reference: a causal decoder whose every layer is grouped-query
attention (global without positions, or over a window with rotary
positions) and sparse ReGLU experts behind a softmax router that is fed
from BEFORE the attention, with its loss and the gradients of it.  The
equations are ISSUE 31's, section 1, for `SmallThinker-21BA3B-Instruct`
(PowerInfer; config.json's `rope_layout`, `sliding_window_layout`,
`moe_primary_router_apply_softmax`, `norm_topk_prob`); with x the stream
entering a layer:

    r = x W_router                     (raw x: no norm, before the attention)
    y = x + Attn(RMS_1(x)) W_o         q of `heads`, k and v of `kv_heads`
                                       heads of `head_dim`; query head h reads
                                       key/value head h // (heads / kv_heads);
                                       scores q k^T / sqrt(head_dim), softmax
    layout 0: no rotary turn, query i sees keys j <= i
    layout 1: q and k turned by rotary positions (theta, pairs by halves:
              (x[i], x[i + d/2])), query i sees keys i - window < j <= i
    chosen = the top_k largest of r; w = softmax over the chosen's r
    out = y + sum over chosen and held e of w_e W_down,e (relu(W_gate,e u)
                                       * W_up,e u),  u = RMS_2(y)
    loss = mean next-token cross-entropy after RMS_f and an untied head

Straightforward jax.numpy in float32 under matmul precision "highest": no
kernel, no sort, no grouped product (every held expert applied to every
token under a dense mask), no cache.  Independent of tpu_mx: it is handed
the system's seeded weights as a plain nested dict, in the system's layouts
(dense weights (out, in); stacked expert weights (held, in, out)).  Its
concessions to memory change no number: `jax.checkpoint` around a layer,
attention in blocks of `BLOCK_Q` queries against all keys (16,384 x 16,384
scores never exist at once), the experts in blocks of `BLOCK_ROWS` tokens
and the head in blocks of `BLOCK_ROWS` positions, each block a `lax.map`
step under `jax.checkpoint`; the logits it returns are those of every
`hp["logit_stride"]`-th position.

On a chip that holds the experts `held = (lo, hi)` of `n_experts`, the sum
over a token's chosen experts runs over chosen and held, **with the weights
normalised over all chosen**; what the absent experts would add is left
out, and the partial result goes on to the next layer.

`hp` (hyper-parameters, static): heads, kv_heads, head_dim, theta, window,
rope_layout, window_layout (a 0 or 1 a layer), eps, top_k, n_experts,
logit_stride.

`forced`, one (S, k) array of expert ids for each layer in order, takes the
place of the top-k choice (a step function of the scores: a comparison
hands the system's own choice in here and holds the choice itself to
`route()` on the system's own layer inputs).

`wrong` selects a deliberately wrong variant, used only to place the
tolerances (a name; or, so that one compiled program serves them all, a
traced index into WRONG, -1 for none): "no_window" (window layers see the
whole past), "window_off_by_one" (window + 1 keys), "rope_on_global" (the
global layers turn too), "no_rope" (no layer turns), "router_after_attention"
(scores from RMS_2(y)), "sigmoid_gate" (sigmoid scores normalised over the
chosen, for the softmax), "silu_experts" (SwiGLU for ReGLU),
"kv_heads_interleaved" (query head h reads key/value head h % kv_heads),
"norm_over_held" (weights normalised over chosen and held).  `low` is the
honest path in a lower precision than stated, which the comparison must
refuse: "router" (the router's scores from a bfloat16 product), "all"
(weights, activations, statistics and logits all bfloat16, products at the
default precision).
"""
import math

import jax
import jax.numpy as jnp

WRONG = ("no_window", "window_off_by_one", "rope_on_global", "no_rope",
         "router_after_attention", "sigmoid_gate", "silu_experts",
         "kv_heads_interleaved", "norm_over_held")
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


def rope(x, theta, on):
    """Rotary positions over the whole last axis of x (..., T, d), pairs by
    halves: (x[i], x[i + d/2]) turns by position * theta**(-2i/d); by
    nothing where not `on`."""
    t, d = x.shape[-2], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    ang = jnp.where(on, ang, 0.0)
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def heads(x, p, hp, turned, wrong=None):
    """q (b, heads, t, d), k and v (b, kv_heads, t, d) of one layer, q and
    k turned where the layer's `turned` says so."""
    b, t, _ = x.shape
    split = lambda a, n: a.reshape(b, t, n, hp["head_dim"]).transpose(
        0, 2, 1, 3)
    q, k, v = split(dense(x, p["q"]), hp["heads"]), \
        split(dense(x, p["k"]), hp["kv_heads"]), \
        split(dense(x, p["v"]), hp["kv_heads"])
    on = jnp.logical_and(
        jnp.logical_or(bool(turned), _is(wrong, "rope_on_global")),
        jnp.logical_not(_is(wrong, "no_rope")))
    return rope(q, hp["theta"], on), rope(k, hp["theta"], on), v


def attend(q, k, v, hp, windowed, wrong=None):
    """softmax(q k^T / sqrt(d) under the layer's mask) v, (b, heads, t, d):
    query head h reads key/value head h // (heads / kv_heads); a block of
    queries at a time against all keys."""
    h, hk, (t, d) = q.shape[1], k.shape[1], q.shape[2:]
    reads = jnp.where(_is(wrong, "kv_heads_interleaved"),
                      jnp.arange(h) % hk, jnp.arange(h) // (h // hk))
    k, v = k[:, reads], v[:, reads]                          # (b, h, t, d)
    # how many keys a query sees, itself among them: t and more is all
    width = jnp.where(
        jnp.logical_and(bool(windowed),
                        jnp.logical_not(_is(wrong, "no_window"))),
        hp["window"] + jnp.int32(_is(wrong, "window_off_by_one")), t + 1)
    kt = k.transpose(0, 1, 3, 2)

    def block(qb, at):
        """qb (bq, b, h, d) queries at positions `at` (bq,), all keys."""
        s = jnp.einsum("qbhd,bhdk->bhqk", qb, kt) / math.sqrt(d)
        behind = at[:, None] - jnp.arange(t)[None, :]
        s = jnp.where((behind >= 0) & (behind < width), s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->qbhd", jax.nn.softmax(s, -1), v)
    return _blocks(block, t, BLOCK_Q, q.transpose(2, 0, 1, 3),
                   jnp.arange(t)).transpose(1, 2, 0, 3)


def attention(x, p, hp, turned, windowed, wrong=None, low=None):
    """Grouped-query attention of one layer; `turned` and `windowed` are
    the layer's entries in the two layouts."""
    b, t, _ = x.shape
    out = attend(*heads(x, p, hp, turned, wrong), hp, windowed, wrong)
    return dense(out.transpose(0, 2, 1, 3).reshape(b, t, -1), p["o"])


def route(x, p, hp, held, wrong=None, low=None, forced=None):
    """(chosen (S, k) expert ids, their weights (S, k)), over all experts,
    from the rows x that the router reads; `forced` (S, k) takes the place
    of the choice."""
    if low == "router":
        logits = (x.astype(jnp.bfloat16)
                  @ p["router"].T.astype(jnp.bfloat16)).astype(jnp.float32)
    else:
        logits = x @ p["router"].T
    _, chosen = jax.lax.top_k(logits + p["bias"], hp["top_k"])
    if forced is not None:
        chosen = forced
    picked = jnp.take_along_axis(logits, chosen, -1)
    here = (chosen >= held[0]) & (chosen < held[1])
    absent = jnp.logical_and(_is(wrong, "norm_over_held"),
                             jnp.logical_not(here))
    # the softmax over the chosen, written out so that the variant can take
    # the absent out of its sum (1e-30: a token none of whose chosen are
    # held then has no weight at all, and not 0 / 0)
    soft = jnp.where(absent, 0.0, jnp.exp(
        picked - jnp.max(picked, -1, keepdims=True)))
    soft = soft / (jnp.sum(soft, -1, keepdims=True) + 1e-30)
    sig = jnp.where(absent, 0.0, jax.nn.sigmoid(picked))
    sig = sig / (jnp.sum(sig, -1, keepdims=True) + 1e-20)
    return chosen, jnp.where(_is(wrong, "sigmoid_gate"), sig, soft)


def experts(u, chosen, weights, p, held, wrong=None):
    """The held experts' part for rows u (S, U): each applied to every row
    under a dense mask (w is 0 where a row did not choose the expert)."""
    ids = jnp.arange(held[0], held[1])

    def block(ub, cb, wb):
        hit = cb[:, :, None] == ids[None, None, :]            # (s, k, held)
        w = jnp.sum(jnp.where(hit, wb[:, :, None], 0.0), 1)   # (s, held)
        gate = jnp.einsum("su,eui->sei", ub, p["w1"])
        act = jnp.where(_is(wrong, "silu_experts"), jax.nn.silu(gate),
                        jax.nn.relu(gate)) \
            * jnp.einsum("su,eui->sei", ub, p["w3"])
        return jnp.einsum("sei,eiu,se->su", act, p["w2"], w.astype(ub.dtype))
    return _blocks(block, u.shape[0], BLOCK_ROWS, u, chosen, weights)


def layer(x, p, hp, held, turned, windowed, wrong=None, low=None,
          forced=None):
    y = x + attention(rms_norm(x, p["ln1"], hp["eps"]), p["attn"], hp,
                      turned, windowed, wrong, low)
    u = rms_norm(y, p["ln2"], hp["eps"])
    read = jnp.where(_is(wrong, "router_after_attention"), u, x)
    flat = lambda a: a.reshape(-1, a.shape[-1])
    chosen, weights = route(flat(read), p["moe"], hp, held, wrong, low,
                            forced)
    return y + experts(flat(u), chosen, weights, p["moe"], held,
                       wrong).reshape(y.shape)


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
    choices = list(forced) if forced is not None else \
        [None] * len(weights["layers"])
    with jax.default_matmul_precision(
            "default" if low == "all" else "highest"):
        t = tokens.shape[1]
        x = weights["embed"][tokens]
        for i, (p, choice) in enumerate(zip(weights["layers"], choices)):
            x = jax.checkpoint(
                lambda x, p, choice, i=i: layer(
                    x, p, hp, held, hp["rope_layout"][i],
                    hp["window_layout"][i], wrong, low, choice))(x, p, choice)
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
