"""Plain reference: a causal decoder whose layers are Kimi Delta Attention
(a gated delta rule with a decay for each key channel) three in four and
latent attention without positions the fourth, a leading dense layer, then
sparse SwiGLU experts behind a sigmoid router beside a shared expert, and a
head of its own; with its loss and the gradients of it.  The equations are
those of `Kimi-Linear-48B-A3B-Instruct` (moonshotai; config.json's
`linear_attn_config`, `mla_use_nope`, `q_lora_rank` null, `kv_lora_rank`,
`qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`,
`first_k_dense_replace`, `moe_router_activation_func`, `moe_renormalize`,
`routed_scaling_factor`, `num_shared_experts`, `rms_norm_eps`; the KDA
paper, arXiv:2510.26692).  For a layer, x the stream entering it and
RMS(a) = a / sqrt(mean(a^2) + eps) * w:

    h = x + Mixer(RMS_op(x));  out = h + FFN(RMS_ffn(h))
    a `kda` layer's mixer, u = RMS_op(x), per head of d (head_dim):
      q, k, v = u W_q, u W_k, u W_v (U -> heads·d each), no bias
      each passes a causal depthwise filter of `taps` taps a channel, then
        SiLU: c_t = sum over j = 0 .. taps - 1 of w[:, taps - 1 - j]
        a_(t - j), a before the first position 0
        [ASSUMED: the filter's layout (a Conv1d's (C, 1, taps) over a
        sequence padded on the left) and conv-then-SiLU, from the family's
        fla `KimiDeltaAttention` code, named and not fetched]
      q and k L2-normalised a head, q / sqrt(q_t . q_t + 1e-6), q then
        times d^-1/2
        [ASSUMED: the L2 norm's 1e-6 and the q scale, from the same code]
      g = -exp(A_log) softplus(u W_fa W_fb + dt_bias): the log of a decay
        for each key channel (W_fa: U -> d, W_fb: d -> heads·d; A_log one
        a head, dt_bias one a channel)
      beta = sigmoid(u W_b), one a head
      S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_(t-1) + beta_t k_t v_t^T,
        S_0 = 0 (d x d a head);  o_t = S_t^T q_t
        -- the per-token recurrence itself, a position at a time
      y = (RMS_o(o) * sigmoid(u W_ga W_gb + b_g)) W_o: RMS_o over a head's
        d with one weight of d for all heads (eps `rms_norm_eps`), the
        gate's path of rank d with a bias on its up-projection
        [ASSUMED: the output norm and gate's form, from the same code]
    a `latent` layer's mixer (MLA, `q_lora_rank` null, `mla_use_nope`):
      q = u W_q (heads·(nope + rope)), no low-rank path and no q norm
      [c | k_r] = u W_kva; c = RMS_kv(c) (kv_rank); [k_n | v] = c W_kvb
        per head (nope + v_dim)
      k = [k_n | k_r], k_r (rope) one a token shared by all heads; NO
        rotary turn on any part (`mla_use_nope`)
      softmax(q k^T / sqrt(nope + rope)) over keys j <= i, times v; W_o
    FFN, u = RMS_ffn(h):  the first `first_k_dense_replace` layers: a
        SwiGLU of `intermediate_size`
      else  s = sigmoid(u W_r) over all experts in f32
        [ASSUMED: the selection bias (the family's e_score_correction_bias)
        held at zero; one expert group, so the grouped top-k is the top-k]
        chosen = the top_k largest of s + bias (the bias chooses only);
        w = s / sum over chosen of s (moe_renormalize) x scaling
        out = sum over chosen and held e of w_e SwiGLU_e(u)
              + SwiGLU_shared(u)       (one shared expert, unweighted)
    loss = mean next-token cross-entropy of RMS_f(x) W_head^T, a head of its
        own (tie_word_embeddings false)
        [ASSUMED: the initializer is the caller's: this file is handed
        weights and draws none]

Straightforward jax.numpy in float32 under matmul precision "highest": no
kernel, no chunked form of the rule, no grouped product (every held expert
applied to every token under a dense mask), no cache.  Independent of
tpu_mx: it is handed the system's seeded weights as a plain nested dict, in
the system's layouts (dense weights (out, in); stacked expert weights
(held, in, out); filters (C, taps)).  Its concessions to memory change no
number: `jax.checkpoint` around a layer, the recurrence's positions in
blocks of `BLOCK_T` under `jax.checkpoint` (so its gradient keeps one state
a block), attention in blocks of `BLOCK_Q` queries against all keys, the
experts, the dense layer and the head in blocks of `BLOCK_ROWS` rows, each
block a `lax.map` step under `jax.checkpoint`; the logits it returns are
those of every `hp["logit_stride"]`-th position.

The chip's share is an argument.  Experts: on a chip that holds the experts
`held = (lo, hi)` of `n_experts`, the sum over a token's chosen experts runs
over chosen and held, **with the weights normalised over all chosen**; what
the absent experts would add is left out, the shared expert is added whole,
and the partial result goes on to the next layer.  Vocabulary: the rows of
`embed` and `head` that are handed in are the vocabulary.  Depth: the
layers handed in are the model.

`hp` (hyper-parameters, static): kinds (one a layer, "kda" or "latent"),
kda (heads, head_dim, taps), mla (heads, nope, rope, v, kv_rank, theta),
eps, top_k, scaling, n_experts, logit_stride.  A layer is dense where its
weights hold "mlp", sparse where they hold "moe"; its mixer is "kda" ({"q",
"k", "v", "q_taps", "k_taps", "v_taps", "f_a", "f_b", "A_log", "dt_bias",
"b", "g_a", "g_b", "g_bias", "o_norm", "o"}) or "mla" ({"q", "kv_a",
"kv_a_norm", "kv_b", "o"}).

`forced`, one (S, k) array of expert ids for each expert layer in order,
takes the place of the top-k choice (a step function of the scores: a
comparison hands the system's own choice in here and holds the choice
itself to `route()` on the system's own layer inputs).

`wrong` selects a deliberately wrong variant, used only to place the
tolerances (a name; or, so that one compiled program serves them all, a
traced index into WRONG, -1 for none): "decay_per_head" (each head's decay
the mean of its channels'), "no_delta" (S_t = Diag(exp g_t) S_(t-1) +
beta_t k_t v_t^T), "qk_norm_off" (q and k not L2-normalised), "taps_reversed"
(the filters turned round in time), "chunk_state_dropped" (the state set to
0 at every 64th position: every chunk from zero), "mla_rotary" (q's and
k's rope parts turned by rotary positions, theta `rope_theta`, pairs
interleaved), "scaling_one" (w = s / sum x 1.0), "no_shared_expert".  `low`
"all" is the honest path in a lower precision than stated, which the
comparison must refuse: weights, activations, statistics, logits and the
recurrence's state all bfloat16, products at the default precision.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

WRONG = ("decay_per_head", "no_delta", "qk_norm_off", "taps_reversed",
         "chunk_state_dropped", "mla_rotary", "scaling_one",
         "no_shared_expert")
LOW = ("all",)
BLOCK_T = 64
BLOCK_Q = 256
BLOCK_ROWS = 2048
CHUNK = 64          # the chunk whose incoming state "chunk_state_dropped" drops


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


def l2_norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def dense(x, w):
    """Weights are (out, in), as the system keeps them; no bias anywhere
    but the output gate's."""
    return x @ w.T


def swiglu(x, p):
    return dense(jax.nn.silu(dense(x, p["gate"])) * dense(x, p["up"]),
                 p["down"])


def causal_conv(a, weight, wrong=None):
    """The causal depthwise filter over a (b, t, C), weight (C, taps): c_t =
    sum_j w[:, taps - 1 - j] a_(t - j), a tap at a time; the variant turns
    the filter round in time."""
    t, taps = a.shape[1], weight.shape[-1]
    w = jnp.where(_is(wrong, "taps_reversed"), weight, weight[:, ::-1])
    out = jnp.zeros_like(a)
    for j in range(taps):
        behind = jnp.pad(a, ((0, 0), (j, 0), (0, 0)))[:, :t]   # a_(t - j)
        out = out + behind * w[:, j]
    return out


def recurrence(q, k, v, g, beta, wrong=None):
    """o (b, t, heads, dv) of the gated delta rule, a position at a time:
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_(t-1) + beta_t k_t v_t^T,
    o_t = S_t^T q_t, S_0 = 0 in q's type; q, k, g (b, t, heads, dk), v (b,
    t, heads, dv), beta (b, t, heads).  Positions in blocks of BLOCK_T under
    jax.checkpoint where the block divides t."""
    b, t, h, dk = k.shape
    g = jnp.where(_is(wrong, "decay_per_head"),
                  jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape),
                  g)
    delta = jnp.where(_is(wrong, "no_delta"), 0.0, 1.0).astype(q.dtype)

    def step(state, x):
        qt, kt, vt, gt, bt, at = x
        state = jnp.where(_is(wrong, "chunk_state_dropped") & (at % CHUNK
                                                              == 0),
                          jnp.zeros_like(state), state)
        state = jnp.exp(gt)[..., None].astype(state.dtype) * state
        old = delta * jnp.einsum("bhd,bhde->bhe", kt, state)
        state = state + (bt[..., None, None] * kt[..., :, None]
                         * (vt - old)[..., None, :]).astype(state.dtype)
        return state, jnp.einsum("bhd,bhde->bhe", qt, state)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)) \
        + (jnp.arange(t),)
    state = jnp.zeros((b, h, dk, v.shape[-1]), q.dtype)
    if t > BLOCK_T and t % BLOCK_T == 0:
        blocked = tuple(a.reshape(t // BLOCK_T, BLOCK_T, *a.shape[1:])
                        for a in xs)
        _, o = jax.lax.scan(
            jax.checkpoint(lambda s, x: jax.lax.scan(step, s, x)), state,
            blocked)
        o = o.reshape(t, *o.shape[2:])
    else:
        _, o = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(o, 0, 1)


def kda(x, p, hp, wrong=None):
    """Kimi Delta Attention of one layer."""
    b, t, _ = x.shape
    heads, d = hp["kda"]["heads"], hp["kda"]["head_dim"]
    split = lambda a: a.reshape(b, t, heads, d)
    q, k, v = (split(jax.nn.silu(causal_conv(dense(x, p[n]), p[n + "_taps"],
                                              wrong)))
               for n in ("q", "k", "v"))
    off = _is(wrong, "qk_norm_off")
    q = jnp.where(off, q, l2_norm(q)) * d ** -0.5
    k = jnp.where(off, k, l2_norm(k))
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        split(dense(dense(x, p["f_a"]), p["f_b"]) + p["dt_bias"]))
    beta = jax.nn.sigmoid(dense(x, p["b"]))
    o = rms_norm(recurrence(q, k, v, g, beta, wrong), p["o_norm"], hp["eps"])
    gate = jax.nn.sigmoid(dense(dense(x, p["g_a"]), p["g_b"]) + p["g_bias"])
    return dense(o.reshape(b, t, -1) * gate, p["o"])


def rope_interleaved(x, theta):
    """x (..., T, d) turned by rotary positions, pairs (x[2i], x[2i+1])."""
    d = x.shape[-1]
    freq = jnp.asarray(theta ** (-np.arange(0, d, 2, dtype=np.float64) / d),
                       jnp.float32)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


def attend(q, k, v):
    """softmax(q k^T / sqrt(dq), causal) v, q and k (b, heads, t, dq), v (b,
    heads, t, dv); a block of queries at a time against all keys."""
    t, d = q.shape[2:]
    kt = k.transpose(0, 1, 3, 2)

    def block(qb, at):
        """qb (bq, b, h, d) queries at positions `at` (bq,), all keys."""
        s = jnp.einsum("qbhd,bhdk->bhqk", qb, kt) / math.sqrt(d)
        s = jnp.where(jnp.arange(t)[None, :] <= at[:, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->qbhd", jax.nn.softmax(s, -1), v)
    return _blocks(block, t, BLOCK_Q, q.transpose(2, 0, 1, 3),
                   jnp.arange(t)).transpose(1, 2, 0, 3)


def mla(x, p, hp, wrong=None):
    """Latent attention of one layer: no q rank, no positions."""
    b, t, _ = x.shape
    m = hp["mla"]
    h, dn, dr, dv = m["heads"], m["nope"], m["rope"], m["v"]
    q = dense(x, p["q"]).reshape(b, t, h, dn + dr).transpose(0, 2, 1, 3)
    kv_a = dense(x, p["kv_a"])
    c = rms_norm(kv_a[..., :m["kv_rank"]], p["kv_a_norm"], hp["eps"])
    k_r = kv_a[..., m["kv_rank"]:][:, None]                  # (b, 1, t, dr)
    kv = dense(c, p["kv_b"]).reshape(b, t, h, dn + dv).transpose(0, 2, 1, 3)
    turn = _is(wrong, "mla_rotary")
    q = jnp.concatenate([q[..., :dn], jnp.where(
        turn, rope_interleaved(q[..., dn:], m["theta"]), q[..., dn:])], -1)
    k_r = jnp.where(turn, rope_interleaved(k_r, m["theta"]), k_r)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (b, h, t, dr))],
                        -1)
    out = attend(q, k, kv[..., dn:])                         # (b, h, t, dv)
    return dense(out.transpose(0, 2, 1, 3).reshape(b, t, -1), p["o"])


def route(x, p, hp, held, wrong=None, low=None, forced=None):
    """(chosen (S, k) expert ids, their weights (S, k)), over all experts,
    from the rows x that the router reads; `forced` (S, k) takes the place
    of the choice."""
    s = jax.nn.sigmoid(x @ p["router"].T)
    _, chosen = jax.lax.top_k(s + p["bias"], hp["top_k"])
    if forced is not None:
        chosen = forced
    picked = jnp.take_along_axis(s, chosen, -1)
    scaling = jnp.where(_is(wrong, "scaling_one"), 1.0, hp["scaling"])
    return chosen, picked / jnp.sum(picked, -1, keepdims=True) * scaling


def experts(u, chosen, weights, p, held, wrong=None):
    """The held experts' part for rows u (S, U), each applied to every row
    under a dense mask (w is 0 where a row did not choose the expert), and
    the shared expert's."""
    ids = jnp.arange(held[0], held[1])

    def block(ub, cb, wb):
        hit = cb[:, :, None] == ids[None, None, :]            # (s, k, held)
        w = jnp.sum(jnp.where(hit, wb[:, :, None], 0.0), 1)   # (s, held)
        act = jax.nn.silu(jnp.einsum("su,eui->sei", ub, p["w1"])) \
            * jnp.einsum("su,eui->sei", ub, p["w3"])
        routed = jnp.einsum("sei,eiu,se->su", act, p["w2"],
                            w.astype(ub.dtype))
        shared = swiglu(ub, p["shared"])
        return routed + jnp.where(_is(wrong, "no_shared_expert"), 0.0,
                                  shared).astype(ub.dtype)
    return _blocks(block, u.shape[0], BLOCK_ROWS, u, chosen, weights)


def layer(x, p, hp, held, wrong=None, low=None, forced=None):
    u = rms_norm(x, p["ln1"], hp["eps"])
    h = x + (kda(u, p["kda"], hp, wrong) if "kda" in p
             else mla(u, p["mla"], hp, wrong))
    flat = rms_norm(h, p["ln2"], hp["eps"]).reshape(-1, h.shape[-1])
    if "moe" in p:
        chosen, weights = route(flat, p["moe"], hp, held, wrong, low, forced)
        out = experts(flat, chosen, weights, p["moe"], held, wrong)
    else:
        out = _blocks(lambda ub: swiglu(ub, p["mlp"]), flat.shape[0],
                      BLOCK_ROWS, flat)
    return h + out.reshape(h.shape)


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
    choices = iter(forced or ())
    with jax.default_matmul_precision(
            "default" if low == "all" else "highest"):
        t = tokens.shape[1]
        x = weights["embed"][tokens]
        for p in weights["layers"]:
            choice = next(choices, None) if "moe" in p else None
            x = jax.checkpoint(
                lambda x, p, choice: layer(x, p, hp, held, wrong, low,
                                           choice))(x, p, choice)
        hidden = rms_norm(x, weights["final_norm"], hp["eps"])
        head = weights["head"]
        return {"logits": dense(hidden[:, ::hp.get("logit_stride", 1)], head),
                "loss": head_loss(hidden, head, jnp.roll(tokens, -1, 1),
                                  t - 1)}


def loss_and_grads(weights, tokens, hp, held, wrong=None, low=None,
                   forced=None):
    """(forward's outputs, d loss / d weights as the same nested dict)."""
    def f(w):
        out = forward(w, tokens, hp, held, wrong, low, forced)
        return out["loss"].astype(jnp.float32), out
    weights = jax.tree.map(lambda w: w.astype(jnp.float32), weights)
    (_, out), grads = jax.value_and_grad(f, has_aux=True)(weights)
    return out, grads


def scan(q, k, v, g, beta, wrong=None, low=None):
    """The recurrence alone on given inputs, for the comparison of the
    system's chunked form: f32 (or all bfloat16 under `low`)."""
    dtype = jnp.bfloat16 if low == "all" else jnp.float32
    with jax.default_matmul_precision(
            "default" if low == "all" else "highest"):
        return recurrence(*(a.astype(dtype) for a in (q, k, v, g, beta)),
                          wrong)
