"""Mixture-of-Experts FFN with expert parallelism (above-parity: the
reference has no MoE — SURVEY §2.3 listed ep out of scope — but the
driver's multi-chip contract names ep shardings, and sparse scaling is
table stakes for a modern TPU framework).

TPU-first design (GShard/Switch einsum formulation, all static shapes):
  - gating, top-k selection, and capacity-limited dispatch are dense
    einsums over a (S, E, C) one-hot dispatch tensor — no gather/scatter,
    no dynamic shapes, everything tiles onto the MXU;
  - expert weights are STACKED on a leading E axis ((E, H, U) / (E, U, H))
    so expert parallelism is nothing but a PartitionSpec("ep", ...) on
    that axis: under a mesh with an `ep` axis, GSPMD partitions the
    per-expert compute and inserts the token-exchange collectives itself
    (the scaling-book recipe — annotate shardings, let XLA insert
    collectives).  `moe_sharding_rules()` returns the rules for
    CompiledTrainStep;
  - gate math runs in f32 whatever the model dtype (softmax over E and
    the load-balance statistics are precision-sensitive); expert matmuls
    run in x.dtype.

Capacity: each expert processes at most C = ceil(capacity_factor·S·k/E)
tokens; overflow tokens are DROPPED from the MoE path (their combine
weight is zero — the residual connection around the layer carries them),
the standard Switch trade-off that keeps shapes static.

forward(x) -> (y, aux_loss): aux_loss is the Switch load-balance term
(E · Σ_e fraction_tokens_e · mean_prob_e, ≥ 1 at perfect balance); add
`aux_loss_weight * aux_loss` to the training objective.

`DroplessMoE` is the second expert layer (ISSUE 27; merging the two is
design debt, ROADMAP): it is TOLD which experts it holds
(`held_experts=range(lo, hi)` of `num_experts`), routes over all of them,
drops nothing, and runs grouped matrix products (`jax.lax.ragged_dot`) over
its own: one chip's part of an expert-parallel layer, without the exchange.
Shapes stay static: the S·k (token, choice) pairs are sorted by held expert
and the sorted order is walked in slabs of C rows, twice the mean that the
held experts draw (head_rows()), for as many slabs as hold rows routed here:
one traced slab under a loop whose trip count is read from the router's
choice, forward and, by the layer's own rule, backward.  The grouped
products are handed the real group sizes, so their cost follows the rows
that are real, and everything around them follows the slabs that ran: C
while a router stays within twice the mean, one more C for each C of rows
past it; nothing is dropped at any load.  Each layer counts the rows every
expert was chosen for, step by step, into a non-trainable buffer of the
last LOAD_HISTORY training steps, written through the path BatchNorm's
running statistics take, and
`load_census(net)` reads it back when asked.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import autograd
from .. import telemetry as _telemetry
from ..gluon.block import HybridBlock
from ..ndarray import ops

__all__ = ["MoEFFN", "DroplessMoE", "moe_sharding_rules", "dropless_route",
           "load_census", "head_rows", "MOE_SCOPES", "LOAD_HISTORY"]

# training steps of expert load a DroplessMoE keeps: a router that learns
# moves its load from step to step, and a trace is of some steps ago
LOAD_HISTORY = 64

# jax.named_scope names inside DroplessMoE (HLO metadata only), read from a
# device trace by benchmark/decoder_scopes.py, which holds them as literals
MOE_SCOPES = ("moe.route", "moe.experts", "moe.shared", "moe.combine")
_ROUTE, _EXPERTS, _SHARED, _COMBINE = MOE_SCOPES


def moe_sharding_rules():
    """Expert-parallel rules: the stacked expert axis shards over `ep`;
    the gate is replicated.  Compose with bert_sharding_rules()-style tp
    rules for the dense sublayers of a surrounding model."""
    return [
        (r"expert_w1$", P("ep", None, None)),
        (r"expert_b1$", P("ep", None)),
        (r"expert_w2$", P("ep", None, None)),
        (r"expert_b2$", P("ep", None)),
        (r"expert_w3$", P("ep", None, None)),
        (r"gate_weight$", P(None, None)),
    ]


def _moe_forward(x, gw, w1, b1, w2, b2, *, top_k, capacity, act):
    """Core routing + expert compute on flattened tokens (S, U)."""
    S, U = x.shape
    E = w1.shape[0]
    xf32 = x.astype(jnp.float32)
    logits = xf32 @ gw.astype(jnp.float32).T                  # (S, E)
    probs = jax.nn.softmax(logits, axis=-1)

    combine = jnp.zeros((S, E, capacity), jnp.float32)
    dispatch = jnp.zeros((S, E, capacity), jnp.bool_)
    masked = probs
    gates, masks = [], []
    for _ in range(top_k):
        idx = jnp.argmax(masked, axis=-1)                     # (S,)
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)    # (S, E)
        gates.append(jnp.sum(probs * onehot, axis=-1))        # (S,)
        masks.append(onehot)
        masked = masked * (1.0 - onehot)
    if top_k > 1:
        # renormalize the selected gates (the GShard top-2 convention)
        denom = sum(gates) + 1e-9
        gates = [g / denom for g in gates]
    # top-1 keeps the RAW router prob (Switch): y = p_i · expert_i(x) is
    # exactly what makes the router differentiable through the task loss
    # — renormalizing would pin the weight at ~1 and starve the gate of
    # gradient

    # positions within each expert: cumulative count over the token axis,
    # later selections queue after ALL first-choice tokens (priority to
    # the k=0 picks, the Switch/GShard behavior).  int32 counts: an f32
    # cumsum silently merges slots once an expert has seen > 2^24 tokens
    # (pod-scale global batches get there)
    prev = jnp.zeros((E,), jnp.int32)
    for g, m in zip(gates, masks):
        mi = m.astype(jnp.int32)
        pos = jnp.cumsum(mi, axis=0) - mi + prev[None, :]     # (S, E)
        within = (pos < capacity) & (mi > 0)
        posi = jnp.clip(pos, 0, capacity - 1)
        oh_c = jax.nn.one_hot(posi, capacity, dtype=jnp.float32)
        sel = within[..., None] * oh_c                        # (S, E, C)
        combine = combine + g[:, None, None] * sel
        dispatch = dispatch | (sel > 0)
        prev = prev + jnp.sum(mi, axis=0)

    dspf = dispatch.astype(x.dtype)
    expert_in = jnp.einsum("sec,su->ecu", dspf, x)            # (E, C, U)
    h = jnp.einsum("ecu,ehu->ech", expert_in, w1) + \
        b1[:, None, :].astype(x.dtype)
    h = act(h)
    eo = jnp.einsum("ech,euh->ecu", h, w2) + \
        b2[:, None, :].astype(x.dtype)
    y = jnp.einsum("sec,ecu->su", combine.astype(x.dtype), eo)

    # Switch load-balance auxiliary: fraction of tokens routed to each
    # expert (first choice) x mean gate prob, scaled by E
    frac = jnp.mean(masks[0], axis=0)                         # (E,)
    mean_prob = jnp.mean(probs, axis=0)                       # (E,)
    aux = E * jnp.sum(frac * mean_prob)
    return y.astype(x.dtype), aux.astype(jnp.float32)


class MoEFFN(HybridBlock):
    """Sparse FFN: top-k gated mixture of `num_experts` two-layer MLPs.

    forward(x: (..., units)) -> (y: (..., units), aux_loss: scalar).
    Under a mesh with an `ep` axis (CompiledTrainStep with
    moe_sharding_rules()), experts shard across devices."""

    def __init__(self, units, hidden_size, num_experts, top_k=2,
                 capacity_factor=1.25, activation="gelu", **kwargs):
        super().__init__(**kwargs)
        if top_k not in (1, 2):
            raise ValueError("top_k must be 1 (Switch) or 2 (GShard)")
        self._units = units
        self._hidden = hidden_size
        self._E = num_experts
        self._k = top_k
        self._cf = float(capacity_factor)
        self._act_name = activation
        self.gate_weight = self.params.get(
            "gate_weight", shape=(num_experts, units))
        self.expert_w1 = self.params.get(
            "expert_w1", shape=(num_experts, hidden_size, units))
        self.expert_b1 = self.params.get(
            "expert_b1", shape=(num_experts, hidden_size),
            init="zeros")
        self.expert_w2 = self.params.get(
            "expert_w2", shape=(num_experts, units, hidden_size))
        self.expert_b2 = self.params.get(
            "expert_b2", shape=(num_experts, units), init="zeros")

    def hybrid_forward(self, F, x, gate_weight, expert_w1, expert_b1,
                       expert_w2, expert_b2):
        import math
        shape = x.shape
        S = 1
        for d in shape[:-1]:
            S *= d
        capacity = max(1, math.ceil(self._cf * S * self._k / self._E))
        if self._act_name == "gelu":
            # match F.gelu (exact erf; jax.nn.gelu defaults to the tanh
            # approximation, which is the separate gelu_tanh op here)
            act = lambda v: jax.nn.gelu(v, approximate=False)
        else:
            act = getattr(jax.nn, self._act_name)

        def fn(xa, gw, w1, b1, w2, b2):
            flat = xa.reshape((S, shape[-1]))
            y, aux = _moe_forward(flat, gw, w1, b1, w2, b2,
                                  top_k=self._k, capacity=capacity,
                                  act=act)
            return y.reshape(shape), aux

        return ops._apply(fn, [x, gate_weight, expert_w1, expert_b1,
                               expert_w2, expert_b2], "MoEFFN")

    def __repr__(self):
        return (f"MoEFFN(units={self._units}, hidden={self._hidden}, "
                f"experts={self._E}, top_k={self._k}, "
                f"capacity_factor={self._cf})")


# -- the dropless layer ---------------------------------------------------------
# rows of one tile of XLA:TPU's grouped product: a slab is a whole number of
# them
_ROW_TILE = 512


def head_rows(rows, held, experts):
    """C, the rows of one slab of the sorted order: twice the mean that
    `held` of `experts` experts draw from `rows` (token, choice) pairs, in
    whole tiles, and never more than `rows`."""
    twice = -(-2 * rows * held // experts)
    return min(rows, -(-twice // _ROW_TILE) * _ROW_TILE)


def dropless_route(x, gate_weight, select_bias, top_k, scaling=1.0,
                   scoring="sigmoid"):
    """The choice and its weights, over all E experts, for tokens (S, U):
    (chosen (S, k) expert ids, weights (S, k) f32).  Scores come from
    x · gate in f32 whatever the model's type (x and the gate hold bf16
    values at most, whose products an f32 accumulator takes exactly).
    `scoring` "sigmoid": scores are sigmoid(x · gate); the k largest of
    score + bias are chosen, the bias for the choice only (noaux_tc: no
    gradient, no weight); weights are the chosen scores normalised over all
    chosen, times `scaling`.  "softmax": the k largest of x · gate + bias
    are chosen, and the weights are the softmax over the chosen k of x ·
    gate (the softmax over all E, normalised over the chosen, is the same
    number), times `scaling`."""
    logits = jnp.einsum(
        "su,eu->se", x.astype(jnp.float32), gate_weight.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax":
        _, chosen = jax.lax.top_k(logits + select_bias.astype(jnp.float32),
                                  top_k)
        return chosen, jax.nn.softmax(
            jnp.take_along_axis(logits, chosen, axis=-1), axis=-1) * scaling
    if scoring != "sigmoid":
        raise ValueError(f"scoring {scoring!r}: sigmoid or softmax")
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores + select_bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20) \
        * scaling


_GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _over_slabs(order, sizes, rows, body, carry):
    """body(j, carry) for the slabs j of `rows` rows of the sorted order
    that hold rows routed here, one at least: a loop with a dynamic trip
    count, and no loop where the order is one slab."""
    if order.shape[0] == rows:
        return body(0, carry)
    n = jnp.maximum(1, -(-jnp.sum(sizes) // rows))
    return jax.lax.while_loop(
        lambda c: c[0] < n, lambda c: (c[0] + 1, body(c[0], c[1])),
        (jnp.int32(0), carry))[1]


def _slab_rows(j, x, k, order, sizes, rows):
    """Slab j of the sorted order of (token, choice) pairs, k a token: (its
    pairs, their tokens, the held experts' group sizes clipped to its edges
    (a group may straddle one), which of its rows a group owns (they come
    first), x's rows for them)."""
    with jax.named_scope(_ROUTE):
        start = j * rows
        pairs = jax.lax.dynamic_slice(order, (start,), (rows,))
        ends = jnp.clip(jnp.cumsum(sizes) - start, 0, rows)
        tok = pairs // k
        real = (jnp.arange(rows) < ends[-1])[:, None]
        return pairs, tok, jnp.diff(ends, prepend=0), real, x[tok]


def _slab_experts(xs, w1, w3, w2, sizes, real, activation):
    """A slab's rows through their experts, (rows, U)."""
    with jax.named_scope(_EXPERTS):
        def grouped(a, w):
            # XLA:TPU's kernel leaves the rows that no group owns UNWRITTEN,
            # whatever the buffer held before (seen on the chip, PR 27: NaN
            # by the third step of a toy decoder); they are cleared before
            # anything reads them, forward and, by where()'s transpose,
            # backward.  The select fuses into the gated activation that
            # follows.
            return jnp.where(real, jax.lax.ragged_dot(a, w, sizes), 0)
        h = _GATES[activation](grouped(xs, w1)) * grouped(xs, w3)
        return grouped(h, w2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_experts(rows, activation, x, weights, w1, w3, w2, order, sizes):
    """What the held experts add to every token's result, (S, U): each slab's
    rows times their pairs' weights, added into their tokens in f32.  The
    backward rule walks the same slabs again; it keeps the operands and
    nothing of a slab's inside.  `order` is a whole number of slabs."""
    def slab(j, y):
        pairs, tok, slab_sizes, real, xs = _slab_rows(
            j, x, weights.shape[1], order, sizes, rows)
        ys = _slab_experts(xs, w1, w3, w2, slab_sizes, real, activation)
        with jax.named_scope(_COMBINE):
            scale = weights.reshape(-1)[pairs]
            return y.at[tok].add(scale[:, None] * ys.astype(jnp.float32))
    return _over_slabs(order, sizes, rows, slab,
                       jnp.zeros(x.shape, jnp.float32)).astype(x.dtype)


def _held_experts_fwd(rows, activation, *operands):
    return _held_experts(rows, activation, *operands), operands


def _held_experts_bwd(rows, activation, operands, g):
    x, weights, w1, w3, w2, order, sizes = operands

    def slab(j, grads):
        dx, dweights, dws = grads
        pairs, tok, slab_sizes, real, xs = _slab_rows(
            j, x, weights.shape[1], order, sizes, rows)
        ys, back = jax.vjp(
            lambda *a: _slab_experts(*a, slab_sizes, real, activation),
            xs, w1, w3, w2)
        with jax.named_scope(_COMBINE):
            gs = g[tok].astype(jnp.float32)
            # a row no group owns holds zeros, and adds them
            dweights = dweights.at[pairs].add(
                jnp.sum(gs * ys.astype(jnp.float32), axis=-1))
            gys = (weights.reshape(-1)[pairs][:, None] * gs).astype(ys.dtype)
        gxs, *gws = back(gys)
        with jax.named_scope(_ROUTE):
            # the grouped products' transposes leave the rows that no group
            # owns unwritten too
            dx = dx.at[tok].add(jnp.where(real, gxs, 0).astype(jnp.float32))
        return dx, dweights, [d + g for d, g in zip(dws, gws)]
    # x's and the pair weights' gradients add up in f32; the expert weights'
    # in their own type, as a sum of per-slab gradients would: f32 sums of
    # them cost glm-4.7-flash.pretrain4k 0.69 GiB of the step's temporaries
    # (PERF.md section 6, PR 33)
    dx, dweights, dws = _over_slabs(order, sizes, rows, slab, (
        jnp.zeros(x.shape, jnp.float32), jnp.zeros(weights.size, jnp.float32),
        [jnp.zeros_like(w) for w in (w1, w3, w2)]))
    return (dx.astype(x.dtype), dweights.reshape(weights.shape), *dws,
            None, None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def _dropless_forward(x, router_x, gw, bias, w1, w3, w2, *, top_k, lo,
                      scaling, scoring="sigmoid", activation="silu"):
    """Routing over all E experts (on `router_x`, the rows the router reads:
    x itself unless the model feeds its router from elsewhere) and the held
    experts' part of the result, on flattened tokens (S, U).  Returns
    (y (S, U), expert_load (E,) f32)."""
    S, k = x.shape[0], top_k
    E, H = gw.shape[0], w1.shape[0]
    with jax.named_scope(_ROUTE):
        chosen, weights = dropless_route(router_x, gw, bias, k, scaling,
                                         scoring)
        flat = chosen.reshape(-1)
        load = jnp.sum(flat[:, None] == jnp.arange(E)[None, :], axis=0)
        here = (chosen >= lo) & (chosen < lo + H)             # (S, k)
        # the pairs routed here first, in expert order, the others after
        key = jnp.where(here, chosen - lo, H).reshape(-1)
        order = jnp.argsort(key, stable=True)
        sizes = load[lo:lo + H].astype(jnp.int32)
        # in whole slabs: the padding's pairs come after all that a group owns
        rows = head_rows(S * k, H, E)
        order = jnp.pad(order, (0, -(S * k) % rows))
    y = _held_experts(rows, activation, x, weights, w1, w3, w2, order, sizes)
    return y, load.astype(jnp.float32)


class DroplessMoE(HybridBlock):
    """Sparse gated experts (SwiGLU, or ReGLU with `activation` "relu")
    without dropped tokens, for one holder of an expert-parallel layer.

    forward(x: (..., units)[, router_x]) -> y: (..., units), the sum over
    each token's chosen experts THAT ARE HELD HERE of `w_i · E_i(x)`, plus
    `shared(x)` if a shared block is given.  Scores are sigmoid(x · gate)
    in f32 over all `num_experts`; the `top_k` largest of score +
    `select_bias` are chosen (the bias is a buffer without gradient, for
    the choice only); weights are the chosen scores normalised over ALL
    chosen (held or not), times `scaling` (`scoring` "softmax":
    dropless_route says how).  `router_x`, where given, is what the router
    reads in place of x (a router placed before the attention reads the
    layer's own input).  What the absent experts would add is left out;
    nothing stands in for them or their exchange.

    Expert weights are stacked (held, in, out), the layout
    `jax.lax.ragged_dot` takes; `moe_sharding_rules()` shards their leading
    axis over `ep`.  The buffers `expert_load` (rows each of the num_experts
    experts was chosen for in each of the last LOAD_HISTORY training steps,
    a ring) and `steps_counted` are read by `load_census(net)`."""

    def __init__(self, units, hidden_size, num_experts, top_k,
                 held_experts=None, scaling=1.0, shared=None,
                 scoring="sigmoid", activation="silu", **kwargs):
        super().__init__(**kwargs)
        if scoring not in ("sigmoid", "softmax") or activation not in _GATES:
            raise ValueError(f"scoring {scoring!r} (sigmoid or softmax), "
                             f"activation {activation!r} ({list(_GATES)})")
        self._scoring, self._activation = scoring, activation
        held = range(num_experts) if held_experts is None else held_experts
        if list(held) != list(range(held.start, held.stop)) or not \
                0 <= held.start < held.stop <= num_experts:
            raise ValueError(f"held_experts must be a contiguous range "
                             f"within {num_experts} experts, got {held!r}")
        if not 1 <= top_k <= num_experts:
            raise ValueError(f"top_k {top_k} of {num_experts} experts")
        self._units, self._hidden, self._E, self._k = \
            units, hidden_size, num_experts, top_k
        self._held, self._scaling = held, float(scaling)
        n = len(held)
        self.gate_weight = self.params.get(
            "gate_weight", shape=(num_experts, units))
        self.select_bias = self.params.get(
            "select_bias", shape=(num_experts,), init="zeros",
            grad_req="null")
        self.expert_w1 = self.params.get(
            "expert_w1", shape=(n, units, hidden_size))
        self.expert_w3 = self.params.get(
            "expert_w3", shape=(n, units, hidden_size))
        self.expert_w2 = self.params.get(
            "expert_w2", shape=(n, hidden_size, units))
        self.expert_load = self.params.get(
            "expert_load", shape=(LOAD_HISTORY, num_experts), init="zeros",
            grad_req="null")
        self.steps_counted = self.params.get(
            "steps_counted", shape=(1,), init="zeros", grad_req="null")
        if shared is not None:
            self.shared = shared

    @property
    def held_experts(self):
        return self._held

    def cast(self, dtype):
        """The choice's bias and the counters stay f32: a count of 32,768
        rows is not a bf16 number."""
        super().cast(dtype)
        for p in (self.select_bias, self.expert_load, self.steps_counted):
            p.cast("float32")

    def hybrid_forward(self, F, x, router_x=None, *, gate_weight,
                       select_bias, expert_w1, expert_w3, expert_w2,
                       expert_load, steps_counted):
        shape = x.shape
        routed = router_x is not None

        def fn(xa, *rest):      # rest: [router_x,] then the five parameters
            rows = xa.reshape((-1, shape[-1]))
            y, load = _dropless_forward(
                rows, rest[0].reshape(rows.shape) if routed else rows,
                *rest[routed:], top_k=self._k, lo=self._held.start,
                scaling=self._scaling, scoring=self._scoring,
                activation=self._activation)
            return y.reshape(shape), load

        y, load = ops._apply(
            fn, [x] + [router_x] * routed
            + [gate_weight, select_bias, expert_w1, expert_w3, expert_w2],
            "DroplessMoE")
        if autograd.is_training():
            with autograd.pause():
                history, count, load = (getattr(a, "_data", a) for a in
                                        (expert_load, steps_counted, load))
                slot = count[0].astype(jnp.int32) % LOAD_HISTORY
                self.expert_load._register_mutation(
                    history.at[slot].set(load))
                self.steps_counted._register_mutation(count + 1)
        if "shared" in self._children:
            with jax.named_scope(_SHARED):
                y = y + self.shared(x)
        return y

    def __repr__(self):
        return (f"DroplessMoE(units={self._units}, hidden={self._hidden}, "
                f"experts={self._E}, held={self._held}, top_k={self._k})")


def load_census(net):
    """What every DroplessMoE under `net` counted in its last training
    steps, fetched from the device now (and only now: the steps themselves
    never wait for it): [{"layer", "held": (lo, hi), "expert_load": [E
    floats] of the step last run, "rows_routed_here", "max_expert_load",
    "rows_routed_here_history": the last LOAD_HISTORY steps' at most, the
    oldest first}] in the order the blocks were added.  A net that a
    CompiledTrainStep trains holds the step's values after
    `step.sync_to_net()`.  "head_rows" is the layer's C for the (token,
    choice) pairs of the step last run (head_rows(); 0 before any step): a
    step of the history whose rows exceeded it ran more than one slab, one
    for each C its rows reached.  Also sets the gauges moe.rows_routed_here,
    moe.max_expert_load, moe.head_rows and moe.tail_steps (such steps in
    the history), a layer each."""
    layers = []
    net.apply_fn(lambda b: isinstance(b, DroplessMoE) and layers.append(b))
    out = []
    for layer in layers:
        ring = jax.device_get(layer.expert_load.data()._data)
        count = int(jax.device_get(layer.steps_counted.data()._data)[0])
        kept = min(count, LOAD_HISTORY)
        history = ring[[(count - kept + i) % LOAD_HISTORY
                        for i in range(kept)]]
        held = layer.held_experts
        load = (history[-1] if kept else ring[0]).tolist()
        mine = load[held.start:held.stop]
        entry = {"layer": layer.name, "held": (held.start, held.stop),
                 "expert_load": load, "rows_routed_here": sum(mine),
                 "max_expert_load": max(mine),
                 "head_rows": head_rows(int(sum(load)), len(held),
                                        len(load)),
                 "rows_routed_here_history":
                     history[:, held.start:held.stop].sum(axis=1).tolist()}
        _telemetry.gauge("moe.rows_routed_here", layer=layer.name).set(
            entry["rows_routed_here"])
        _telemetry.gauge("moe.max_expert_load", layer=layer.name).set(
            entry["max_expert_load"])
        _telemetry.gauge("moe.head_rows", layer=layer.name).set(
            entry["head_rows"])
        _telemetry.gauge("moe.tail_steps", layer=layer.name).set(
            sum(rows > entry["head_rows"]
                for rows in entry["rows_routed_here_history"]))
        out.append(entry)
    return out
