"""The causal decoder family (ISSUE 27) at toy sizes on the CPU: latent
attention, the dropless expert layer, the multi-token head, against the
plain reference tests/references/latent_moe_decoder.py, through the
configuration module the benchmark uses (benchmark/configs/glm-4.7-flash.py:
its `weights`, `compare` and `hyper` are what decide `correct` on the chip).
"""
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpu_mx as mx
from tpu_mx import autograd, nd
from tpu_mx.models.decoder import DECODER_SCOPES, CausalLM, GatedMLP
from tpu_mx.parallel import DroplessMoE, dropless_route, load_census
from tpu_mx.parallel.moe import MOE_SCOPES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
NAME = "glm-4.7-flash"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load(os.path.join(ROOT, "tests", "references",
                               "latent_moe_decoder.py"), "latent_moe_ref")
config_mod = _load(os.path.join(BENCH, "configs", NAME + ".py"),
                   "glm_config_mod")


def toy_cfg(held=(0, 2)):
    """The configuration file at its rehearsal sizes, f32 so that the
    comparison is of the equations and not of bf16's rounding."""
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearse"])
    cfg["deployment"] = dict(cfg["deployment"], held_experts=list(held))
    cfg["n_routed_experts"] = held[1] - held[0]
    # at a sixteenth of the width, five times the published initializer's
    # spread gives attention and the router scores of the published size
    cfg["system"] = dict(cfg["system"], dtype="float32", init_sigma=0.1)
    cfg["reference_comparison"] = dict(cfg["reference_comparison"],
                                       logit_stride=1)
    return cfg


MIX = {"batch": 2, "seq_len": 32, "block_steps": 2}


@pytest.fixture(scope="module")
def compared():
    """One honest comparison and one per wrong variant, on one toy net."""
    cfg = toy_cfg()
    net, _ = config_mod.build(cfg, MIX, seed=7)
    batch = config_mod.make_batch(cfg, MIX, seed=7)
    out = {None: config_mod.compare(reference, net, batch, 2)}
    for wrong in reference.WRONG:
        out[wrong] = config_mod.compare(reference, net, batch, 2, wrong=wrong)
    return out


def rel(a, b):
    return float(np.sqrt(np.mean(np.square(a - b)))
                 / np.sqrt(np.mean(np.square(b))))


OUTPUTS = ["logits", "mtp_logits", "loss", "grad_router", "grad_expert_down",
           "grad_kv_a", "grad_embed", "route_choice", "route_weights"]
TOLERANCE = 2e-3    # f32 against f32 "highest"; the toy's honest error is 1e-5


@pytest.mark.parametrize("key", OUTPUTS)
def test_system_matches_reference(compared, key):
    got, want = compared[None]
    assert got[key].shape == want[key].shape
    assert rel(got[key], want[key]) < TOLERANCE, key


@pytest.mark.parametrize("wrong", reference.WRONG)
def test_each_wrong_variant_fails_the_tolerance(compared, wrong):
    got, want = compared[wrong]
    errors = {k: rel(got[k], want[k]) for k in OUTPUTS}
    assert max(errors.values()) > 10 * TOLERANCE, errors


@pytest.fixture(scope="module")
def toy_sides():
    """The system's side once, and a function for the reference's side
    that keeps its compiled programs, as a readings script would."""
    cfg = toy_cfg()
    net, _ = config_mod.build(cfg, MIX, seed=7)
    batch = config_mod.make_batch(cfg, MIX, seed=7)
    got, aux = config_mod.system_outputs(net, batch, 2)
    programs = {}

    def want(**kw):
        return config_mod.reference_outputs(reference, net, aux,
                                            programs=programs, **kw)
    return got, aux, want, programs, net


def test_one_compiled_program_serves_every_wrong_variant(compared, toy_sides):
    """`wrong` as a traced index gives what the name gives, from one
    program; -1 is the honest path."""
    got, _, want, programs, _ = toy_sides
    for wrong in (None,) + reference.WRONG:
        mine, named = want(wrong=wrong), compared[wrong][1]
        for key in OUTPUTS:
            assert np.allclose(mine[key], named[key], rtol=1e-5, atol=1e-8), \
                (wrong, key)
    assert len(programs) == 1
    hp, held = config_mod.hyper(toy_cfg())
    w = config_mod.weights(config_mod.build(toy_cfg(), MIX, seed=7)[0])
    tokens = np.zeros((1, 8), np.int32)
    by_name = reference.forward(w, tokens, hp, held, wrong="no_rope")
    by_index = jax.jit(lambda i: reference.forward(
        w, tokens, hp, held, wrong=i))(reference.WRONG.index("no_rope"))
    assert rel(np.asarray(by_index["logits"]),
               np.asarray(by_name["logits"])) < 1e-6


def test_the_systems_choice_is_what_the_reference_is_handed(toy_sides):
    """The reference takes the system's choice in place of its own, layer
    by layer in the order it walks them, and the choice itself is compared:
    a reference handed another choice reads far off."""
    got, aux, want, _, net = toy_sides
    assert len(aux["chosen"]) == len(aux["inputs"]) == 3
    tokens = MIX["batch"] * MIX["seq_len"]
    assert all(c.shape == (tokens, 2) for c in aux["chosen"])
    honest = want()
    assert rel(got["route_choice"], honest["route_choice"]) == 0.0
    assert rel(got["route_weights"], honest["route_weights"]) < 1e-5
    turned = dict(aux, chosen=[(c + 1) % 8 for c in aux["chosen"]])
    other = config_mod.reference_outputs(reference, net, turned)
    assert rel(got["logits"], other["logits"]) > 10 * TOLERANCE
    # the reference's free choice does not follow what it was handed
    assert rel(other["route_choice"], honest["route_choice"]) == 0.0


def test_a_bfloat16_router_fails_the_routings_own_tolerance(toy_sides):
    """The islands the configuration states (f32 router scores) are seen
    on identical inputs: the system's routing agrees with the f32
    reference's to rounding, and the reference with a bfloat16 router
    (`low`, alone or with everything else) does not."""
    got, _, want, _, _ = toy_sides
    honest = rel(got["route_weights"], want()["route_weights"])
    for low in ("router", "all"):
        lowered = rel(got["route_weights"], want(low=low)["route_weights"])
        assert lowered > 5e-4 > 50 * honest, (low, lowered, honest)


def test_dropless_route_is_the_references_route():
    layer = _layer(held=range(0, 8), shared=False)
    layer.select_bias.set_data(
        np.random.RandomState(3).randn(8).astype(np.float32))
    x = np.random.RandomState(2).randn(64, 32).astype(np.float32)
    chosen, weights = dropless_route(
        jnp.asarray(x), layer.gate_weight.data()._data,
        layer.select_bias.data()._data, 2, 1.8)
    with jax.default_matmul_precision("highest"):
        want_chosen, want_weights = reference.route(
            jnp.asarray(x), _ref_weights_no_shared(layer), HP, (0, 8))
    assert (np.asarray(chosen) == np.asarray(want_chosen)).all()
    assert rel(np.asarray(weights), np.asarray(want_weights)) < 1e-6


def test_both_losses_match(compared):
    """The objective's two terms, read from the reference's forward."""
    cfg = toy_cfg()
    net, _ = config_mod.build(cfg, MIX, seed=7)
    tokens = np.asarray(config_mod.make_batch(cfg, MIX, seed=7)[0])
    hp, held = config_mod.hyper(cfg)
    ref = reference.forward(config_mod.weights(net), tokens, hp, held)
    with autograd.predict_mode():
        loss, logits, mtp_logits = net(nd.array(tokens, dtype="int32"))
    assert float(loss.asscalar()) == pytest.approx(float(ref["loss"]),
                                                   rel=1e-4)
    assert float(ref["loss"]) == pytest.approx(
        float(ref["loss_main"]) + 0.3 * float(ref["loss_mtp"]), rel=1e-6)
    # the multi-token loss reads position i against token i + 2
    logp = jax.nn.log_softmax(jnp.asarray(mtp_logits._data), -1)
    nll = -np.take_along_axis(np.asarray(logp)[:, :-2],
                              tokens[:, 2:, None], -1)
    assert float(nll.mean()) == pytest.approx(float(ref["loss_mtp"]),
                                              rel=1e-4)


def _layer(units=32, hidden=16, experts=8, k=2, held=range(0, 8),
           shared=True, seed=5):
    mx.random.seed(seed)
    layer = DroplessMoE(units, hidden, experts, k, held_experts=held,
                        scaling=1.8,
                        shared=GatedMLP(units, hidden) if shared else None)
    layer.initialize(mx.init.Normal(0.3))
    return layer


def _ref_weights(layer, lo=0, hi=None):
    """The reference's `moe` group from a whole (all experts held) layer,
    cut to the experts [lo, hi)."""
    def raw(p):
        return np.asarray(p.data()._data)
    hi = layer._E if hi is None else hi
    return {"router": raw(layer.gate_weight), "bias": raw(layer.select_bias),
            "w1": raw(layer.expert_w1)[lo:hi],
            "w3": raw(layer.expert_w3)[lo:hi],
            "w2": raw(layer.expert_w2)[lo:hi],
            "shared": {"gate": raw(layer.shared.gate_proj_weight),
                       "up": raw(layer.shared.up_proj_weight),
                       "down": raw(layer.shared.down_proj_weight)}}


HP = dict(top_k=2, scaling=1.8, n_experts=8)


def test_the_shares_add_up():
    """8 experts in 4 shares of 2: the routed parts of the four shares plus
    the shared expert counted once equal the uncut reference's layer."""
    whole = _layer()
    x = np.random.RandomState(0).randn(24, 32).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(reference.expert_layer(
            jnp.asarray(x), _ref_weights(whole), HP, (0, 8)))
        # no expert held: the shared expert alone
        shared_once = np.asarray(reference.expert_layer(
            jnp.asarray(x), _ref_weights(whole, 0, 0), HP, (0, 0)))
    total = shared_once.copy()
    for lo in range(0, 8, 2):
        share = _layer(held=range(lo, lo + 2), shared=False)
        share.gate_weight.set_data(whole.gate_weight.data())
        for name in ("expert_w1", "expert_w3", "expert_w2"):
            getattr(share, name).set_data(
                getattr(whole, name).data()[lo:lo + 2])
        with autograd.predict_mode():
            total += share(nd.array(x)).asnumpy()
    assert rel(total, uncut) < 1e-4
    # and the system's whole layer is the reference's whole layer
    with autograd.predict_mode():
        assert rel(whole(nd.array(x)).asnumpy(), uncut) < 1e-4


def test_no_token_dropped_when_every_token_chooses_the_same_expert():
    layer = _layer(k=1, held=range(2, 4), shared=False)
    bias = np.zeros(8, np.float32)
    bias[3] = 100.0                     # every token's one choice: expert 3
    layer.select_bias.set_data(bias)
    x = np.random.RandomState(1).randn(40, 32).astype(np.float32)
    with autograd.record():
        y = layer(nd.array(x))
    with jax.default_matmul_precision("highest"):
        want = 1.8 * np.asarray(reference.swiglu(
            jnp.asarray(x), layer.expert_w1.data()._data[1],
            layer.expert_w3.data()._data[1], layer.expert_w2.data()._data[1]))
    assert rel(y.asnumpy(), want) < 1e-4      # weight 1 (normalised) x 1.8
    census, = load_census(layer)
    assert census["expert_load"][3] == 40 == census["max_expert_load"]
    assert census["rows_routed_here"] == 40


# -- slabs of the sorted order under a loop (ISSUEs 32 and 33) ---------------
def _plain_layer(x, router_x, gw, bias, w1, w3, w2, *, top_k, lo, scaling,
                 scoring, activation):
    """The held experts' part, every held expert applied to every token
    under a dense mask, as reference.expert_layer does it; with the file's
    reference's route where that has the scoring."""
    logits = router_x @ gw.T
    if scoring == "softmax":
        _, chosen = jax.lax.top_k(logits + bias, top_k)
        weights = jax.nn.softmax(
            jnp.take_along_axis(logits, chosen, -1), -1) * scaling
    else:
        chosen, weights = reference.route(
            router_x, {"router": gw, "bias": bias},
            dict(top_k=top_k, scaling=scaling), (lo, lo + w1.shape[0]))
    hit = chosen[:, :, None] == lo + jnp.arange(w1.shape[0])[None, None, :]
    w = jnp.sum(jnp.where(hit, weights[:, :, None], 0.0), 1)  # (S, held)
    gate = {"silu": jax.nn.silu, "relu": jax.nn.relu}[activation]
    act = gate(jnp.einsum("su,eui->sei", x, w1)) \
        * jnp.einsum("su,eui->sei", x, w3)
    return jnp.einsum("sei,eiu,se->su", act, w2, w)


# S 384, k 2 over 8 experts: 768 (token, choice) pairs; 2 held experts draw
# 192 of them on average, so a slab is one tile of 512 rows and the sorted
# order has two (the second padded from 256 pairs).  S 1024 over 16 experts:
# 2,048 pairs, 2 held draw 256, four slabs of 512
HEAD_CASES = {
    # name: ((S, E), held, bias on the held experts, scoring, activation,
    #        router_x, the slabs that the rows routed here reach)
    "rows_under_the_head": ((384, 8), (2, 4), (0.0, 0.0), "sigmoid", "silu",
                            False, 1),
    "the_cut_inside_a_group": ((384, 8), (2, 4), (100.0, 0.3), "sigmoid",
                               "silu", False, 2),
    "every_token_here": ((384, 8), (2, 4), (100.0, 100.0), "sigmoid", "silu",
                         False, 2),
    "every_expert_held": ((384, 8), (0, 8), (0.0,) * 8, "sigmoid", "silu",
                          False, 1),
    "softmax_relu_forced": ((384, 8), (2, 4), (100.0, 100.0), "softmax",
                            "relu", False, 2),
    "softmax_silu": ((384, 8), (0, 2), (0.0, 0.0), "softmax", "silu", False,
                     1),
    "sigmoid_relu_over_the_head": ((384, 8), (2, 4), (100.0, 0.3), "sigmoid",
                                   "relu", False, 2),
    "router_fed_from_elsewhere": ((384, 8), (2, 4), (100.0, 100.0), "softmax",
                                  "relu", True, 2),
    "four_slabs_rows_under_the_head": ((1024, 16), (2, 4), (0.0, 0.0),
                                       "sigmoid", "silu", False, 1),
    "four_slabs_the_last_not_reached": ((1024, 16), (2, 4), (100.0, 0.15),
                                        "sigmoid", "silu", False, 3),
    "four_slabs_every_token_here": ((1024, 16), (2, 4), (100.0, 100.0),
                                    "softmax", "relu", True, 4),
}


@pytest.mark.parametrize("case", list(HEAD_CASES))
def test_the_slabs_are_the_plain_layer_and_drop_no_row(case):
    """Output, the five gradients (and the router input's, where it has one)
    and the load, for rows within the first slab, past it with a cut inside
    an expert's group, with slabs that run and slabs that do not, and with
    every token routed here."""
    from tpu_mx.parallel import moe
    (S, E), (lo, hi), held_bias, scoring, activation, routed, slabs_run = \
        HEAD_CASES[case]
    U, F, k = 32, 16, 2
    keys = jax.random.split(jax.random.key(32), 7)
    x, router_x, r = (jax.random.normal(q, (S, U)) for q in keys[:3])
    gw = 0.3 * jax.random.normal(keys[3], (E, U))
    w1, w3 = (0.3 * jax.random.normal(q, (hi - lo, U, F)) for q in keys[4:6])
    w2 = 0.3 * jax.random.normal(keys[6], (hi - lo, F, U))
    bias = jnp.zeros(E).at[lo:hi].set(jnp.asarray(held_bias))
    kw = dict(top_k=k, lo=lo, scaling=1.8, scoring=scoring,
              activation=activation)

    def system(x, router_x, gw, w1, w3, w2):
        y, load = moe._dropless_forward(
            x, router_x if routed else x, gw, bias, w1, w3, w2, **kw)
        return jnp.sum(y * r), (y, load)

    def plain(x, router_x, gw, w1, w3, w2):
        y = _plain_layer(x, router_x if routed else x, gw, bias, w1, w3, w2,
                         **kw)
        return jnp.sum(y * r), y
    args = (x, router_x, gw, w1, w3, w2)
    wrt = (0, 1, 2, 3, 4, 5) if routed else (0, 2, 3, 4, 5)
    with jax.default_matmul_precision("highest"):
        (_, (y, load)), grads = jax.jit(jax.value_and_grad(
            system, wrt, has_aux=True))(*args)
        (_, want), want_grads = jax.jit(jax.value_and_grad(
            plain, wrt, has_aux=True))(*args)
    assert rel(np.asarray(y), np.asarray(want)) < 1e-4
    for i, g, h in zip(wrt, grads, want_grads):
        assert rel(np.asarray(g), np.asarray(h)) < 1e-4, i
    # no row dropped: every (token, choice) pair is counted, and the rows
    # here are what the case says
    load = np.asarray(load)
    assert load.sum() == S * k
    head = moe.head_rows(S * k, hi - lo, E)
    rows, ends = load[lo:hi].sum(), np.cumsum(load[lo:hi])
    jaxpr = str(jax.make_jaxpr(jax.grad(lambda *a: system(*a)[0]))(*args))
    # a layer that holds every expert has one slab and no loop, forward or
    # backward; no form stands behind a condition
    assert ("while" in jaxpr) == (head < S * k) == (hi - lo < E)
    assert "cond[" not in jaxpr
    assert rows > 0 and -(-rows // head) == slabs_run, rows
    if max(held_bias) == 100.0:
        # a forced expert draws every token: a cut lies inside its group,
        # and with both forced every pair is here
        assert any(c not in ends for c in range(head, int(rows), head))
        assert (rows == S * k) == (min(held_bias) == 100.0)


def _grouped_products(jaxpr):
    """`ragged_dot` equations in a jaxpr and in every jaxpr inside it (a
    loop's body, a rule's, a checkpoint's)."""
    count = 0
    for eqn in jaxpr.eqns:
        count += eqn.primitive.name.startswith("ragged_dot")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            count += _grouped_products(sub)
    return count


def test_the_program_does_not_grow_with_the_slabs():
    """The gradient of a layer under a checkpoint holds the same grouped
    products whether the sorted order has 2 slabs or 4, and at most 15:
    3 forward, 3 rematerialised, 3 computed again by the rule and 6
    transposed.  One slab traced, however many run."""
    from tpu_mx.parallel import moe
    S, E, U, F, k = 512, 64, 16, 8, 4
    counts = {}
    for held in (8, 16):
        shapes = [(S, U), (E, U), (held, U, F), (held, U, F), (held, F, U)]
        args = [jnp.ones(s, jnp.float32) for s in shapes]

        @jax.checkpoint
        def layer(x, gw, w1, w3, w2):
            return moe._dropless_forward(
                x, x, gw, jnp.zeros(E), w1, w3, w2, top_k=k, lo=0,
                scaling=1.0)[0]
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(layer(*a)), argnums=(0, 1, 2, 3, 4)))(*args)
        slabs = S * k // moe.head_rows(S * k, held, E)
        counts[slabs] = _grouped_products(jaxpr.jaxpr)
    assert sorted(counts) == [2, 4]
    assert counts[2] == counts[4] <= 15, counts


def test_the_census_reports_the_head_and_the_history_gives_the_tails_share():
    """`head_rows` beside the rows routed here: the share of the history's
    steps whose rows exceeded it is 0 for a balanced layer and 1 for one
    whose bias sends every token here."""
    from tpu_mx.parallel import moe
    x = nd.array(np.random.RandomState(4).randn(384, 32).astype(np.float32))
    bias = np.zeros(8, np.float32)
    for on_held, steps, share in ((0.0, 2, 0.0), (100.0, 3, 1.0)):
        bias[2:4] = on_held
        layer = _layer(held=range(2, 4), shared=False)
        layer.select_bias.set_data(bias)
        for _ in range(steps):
            with autograd.record():
                layer(x)
        census, = load_census(layer)
        assert census["head_rows"] == moe.head_rows(768, 2, 8) == 512
        history = census["rows_routed_here_history"]
        assert sum(h > 512 for h in history) / len(history) == share
        assert mx.telemetry.gauge("moe.head_rows",
                                  layer=layer.name).value == 512
        assert mx.telemetry.gauge("moe.tail_steps",
                                  layer=layer.name).value == share * steps
    assert {"moe.head_rows", "moe.tail_steps"} <= mx.telemetry.KNOWN_METRICS


def test_selection_bias_changes_the_choice_and_not_the_weights():
    layer = _layer(held=range(0, 8), shared=False)
    x = np.random.RandomState(2).randn(16, 32).astype(np.float32)

    def run(bias):
        layer.select_bias.set_data(bias)
        with jax.default_matmul_precision("highest"):
            chosen, weights = reference.route(
                jnp.asarray(x), _ref_weights_no_shared(layer), HP, (0, 8))
        with autograd.predict_mode():
            return np.asarray(chosen), np.asarray(weights), \
                layer(nd.array(x)).asnumpy()
    chosen0, w0, y0 = run(np.zeros(8, np.float32))
    # a bias that keeps the order keeps everything: it is not in the weights
    chosen1, w1, y1 = run(np.full(8, 0.5, np.float32))
    assert (chosen0 == chosen1).all() and rel(w1, w0) < 1e-6
    assert rel(y1, y0) < 1e-6
    # a bias on one expert changes who is chosen
    bias = np.zeros(8, np.float32)
    bias[5] = 10.0
    chosen2, _, y2 = run(bias)
    assert (chosen2 == 5).any(axis=1).all() and rel(y2, y0) > 1e-3


def _ref_weights_no_shared(layer):
    def raw(p):
        return np.asarray(p.data()._data)
    return {"router": raw(layer.gate_weight), "bias": raw(layer.select_bias)}


def test_expert_load_sums_to_tokens_times_k_through_the_train_step():
    """The counter rides the compiled step's non-gradient update path, under
    recomputation, and the census finds it in the net once the step's
    values are written back."""
    cfg = toy_cfg()
    net, make_step = config_mod.build(cfg, MIX, seed=3)
    batch = config_mod.make_batch(cfg, MIX, seed=3)
    step = make_step()
    losses = [float(step.step(*batch).asscalar()) for _ in range(4)]
    assert losses[-1] < losses[0]
    step.sync_to_net()
    census = load_census(net)
    experts = config_mod.expert_layers(net)
    # two expert layers and the module's, in the order the reference walks
    assert [c["layer"] for c in census] == [m.name for m in experts]
    assert len(census) == 3
    tokens = MIX["batch"] * MIX["seq_len"]
    for c in census:
        assert sum(c["expert_load"]) == tokens * cfg["num_experts_per_tok"]
        assert 0 < c["rows_routed_here"] <= tokens * cfg["num_experts_per_tok"]
        # one entry a step, the newest last
        assert len(c["rows_routed_here_history"]) == 4
        assert c["rows_routed_here_history"][-1] == c["rows_routed_here"]
    assert mx.telemetry.gauge("moe.rows_routed_here",
                              layer=experts[0].name).value == \
        census[0]["rows_routed_here"]
    assert {"moe.rows_routed_here", "moe.max_expert_load"} \
        <= mx.telemetry.KNOWN_METRICS
    # a reader of the benchmark is handed the configuration alone: the
    # configuration's make_step() left the net and the step in it
    sys.path.insert(0, BENCH)
    try:
        import decoder_scopes
    finally:
        sys.path.remove(BENCH)
    step.step(*batch)
    assert decoder_scopes.census({"cfg": {}}) is None
    counted = decoder_scopes.census({"cfg": cfg})
    assert [c["layer"] for c in counted] == [m.name for m in experts]
    assert counted == load_census(net) != census
    ratio = _load(os.path.join(BENCH, "layer_metrics",
                               "moe_max_load_ratio.py"), "ratio_reader")
    assert ratio.read({"cfg": cfg}) == max(
        c["max_expert_load"] * 2 / c["rows_routed_here"] for c in counted)


def test_the_load_history_is_a_ring_of_the_last_training_steps(monkeypatch):
    """Each training-mode pass writes its load into the next slot; the
    census hands the steps back oldest first, the newest as `expert_load`;
    a pass that does not train writes nothing."""
    from tpu_mx.parallel import moe
    monkeypatch.setattr(moe, "LOAD_HISTORY", 4)
    layer = _layer(k=1, held=range(0, 8), shared=False)
    assert layer.expert_load.shape == (4, 8)
    rows = []
    for step in range(6):
        bias = np.zeros(8, np.float32)
        bias[step] = 100.0              # step i: every token to expert i
        layer.select_bias.set_data(bias)
        x = np.random.RandomState(step).randn(10 + step, 32)
        with autograd.record():
            layer(nd.array(x.astype(np.float32)))
        rows.append(10.0 + step)
        with autograd.predict_mode():
            layer(nd.array(x.astype(np.float32)))
        census, = load_census(layer)
        assert census["rows_routed_here_history"] == rows[-4:]
        assert census["expert_load"][step] == rows[-1]
        assert census["rows_routed_here"] == census["max_expert_load"] \
            == rows[-1]
    assert float(layer.steps_counted.data().asnumpy()[0]) == 6


def test_counters_stay_f32_in_a_bf16_model():
    cfg = toy_cfg()
    cfg["system"]["dtype"] = "bfloat16"
    net, _ = config_mod.build(cfg, MIX, seed=3)
    moe = config_mod.expert_layers(net)[0]
    assert str(moe.expert_w1.data().dtype) == "bfloat16"
    for p in (moe.select_bias, moe.expert_load, moe.steps_counted):
        assert str(p.data().dtype) == "float32"


def test_the_two_reference_files_are_byte_equal():
    with open(os.path.join(ROOT, "tests", "references",
                           "latent_moe_decoder.py"), "rb") as a, \
            open(os.path.join(BENCH, "references", NAME + ".py"), "rb") as b:
        assert a.read() == b.read()


def test_scope_names_equal_the_benchmarks_literals():
    sys.path.insert(0, BENCH)
    try:
        import decoder_scopes
    finally:
        sys.path.remove(BENCH)
    assert decoder_scopes.SCOPES == DECODER_SCOPES
    assert set(MOE_SCOPES) <= set(DECODER_SCOPES)


def test_scopes_reach_the_compiled_steps_op_paths():
    """Every scope names operations of the compiled train step, forward
    and backward, in the forms benchmark/decoder_scopes.py matches."""
    sys.path.insert(0, BENCH)
    try:
        import decoder_scopes
    finally:
        sys.path.remove(BENCH)
    cfg = toy_cfg()
    net, make_step = config_mod.build(cfg, MIX, seed=3)
    batch = config_mod.make_batch(cfg, MIX, seed=3)
    hlo = make_step().aot_compiled(*batch).as_text()
    import re
    paths = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in DECODER_SCOPES:
        hits = [p for p in paths if decoder_scopes.under(p, (scope,))]
        assert hits, scope
        assert any(decoder_scopes.is_backward(p) for p in hits), scope
    assert not decoder_scopes.under("jit(f)/train_step.grad/jvp(mtpx)/add",
                                    ("mtp",))


def test_flops_per_sample_is_the_issues_reckoning():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", "pretrain4k.json")) as f:
        mix = json.load(f)
    per_token_forward = config_mod.flops_per_sample(cfg, mix) \
        / 3 / mix["seq_len"]
    assert per_token_forward / 1e6 == pytest.approx(956.8, abs=0.6)
    n_params = sum(int(np.prod(s)) for s in _shapes(cfg))
    assert n_params / 1e6 == pytest.approx(706.5, abs=0.2)


def _shapes(cfg):
    net = CausalLM(config_mod.model_config(cfg))
    return [p.shape for p in net.collect_params().values()
            if p.grad_req != "null"]
