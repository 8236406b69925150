"""The gated short-convolution decoder (ISSUE 39) at toy sizes on the CPU:
ShortConv against the plain reference tests/references/short_conv_decoder.py
forward and in its three weights' gradients, its causality tap by tap, the
whole model (a conv-dense layer, an attention layer whose heads of q and k
are normalised, three conv expert layers, sigmoid-routed experts with no
shared expert, a head tied to the embedding) against the reference through
the whole and the chunked loss, through the configuration module the
benchmark uses (benchmark/configs/lfm2-24b-a2b.py: its `weights`, `compare`
and `hyper` are what decide `correct` on the chip), the expert shares
adding up to the uncut layer, the scope names, and the three decoder cells
that were there keeping their parameters.
"""
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_mx import nd
from tpu_mx.models import decoder
from tpu_mx.models.decoder import (CONV_SCOPES, OWNER_SCOPES, ShortConv,
                                   short_conv_mix)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
NAME = "lfm2-24b-a2b"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load(os.path.join(ROOT, "tests", "references",
                               "short_conv_decoder.py"), "short_conv_ref")
config_mod = _load(os.path.join(BENCH, "configs", NAME + ".py"),
                   "lfm2_config_mod")
TOLERANCE = 2e-3    # f32 against f32 "highest"; the toy's honest error is 1e-6


def rel(a, b):
    return float(np.sqrt(np.mean(np.square(a - b)))
                 / np.sqrt(np.mean(np.square(b))))


# -- the mixer -----------------------------------------------------------------------
def conv_block(units=16, taps=3, seed=0):
    block = ShortConv(units, taps)
    block.initialize()
    keys = iter(jax.random.split(jax.random.key(seed), 3))
    for p in block.collect_params().values():
        p.set_data(np.asarray(0.3 * jax.random.normal(next(keys), p.shape)))
    params = {k: p.data()._data for k, p in block.collect_params().items()}
    by = lambda end: next(v for k, v in params.items() if k.endswith(end))
    plain = {"in": by("in_proj_weight"), "taps": by("conv_weight"),
             "out": by("out_proj_weight")}
    return block, params, plain


def test_the_mixer_is_the_reference_forward_and_in_its_gradients():
    block, params, plain = conv_block()
    x = jax.random.normal(jax.random.key(1), (2, 12, 16))
    system = lambda pm, xx: block._functional_call(
        pm, jax.random.PRNGKey(0), True, (xx,))[0]
    names = {"in": "in_proj_weight", "taps": "conv_weight",
             "out": "out_proj_weight"}
    with jax.default_matmul_precision("highest"):
        got, want = system(params, x), reference.short_conv(x, plain)
        assert got.shape == (2, 12, 16)
        assert rel(np.asarray(got), np.asarray(want)) < 1e-5
        grads = jax.grad(lambda pm: jnp.sum(jnp.sin(system(pm, x))))(params)
        ref_grads = jax.grad(lambda p: jnp.sum(jnp.sin(
            reference.short_conv(x, p))))(plain)
    for k, v in names.items():
        g = next(g for n, g in grads.items() if n.endswith(v))
        assert g.shape == ref_grads[k].shape
        assert rel(np.asarray(g), np.asarray(ref_grads[k])) < 1e-5, k


@pytest.mark.parametrize("at", [0, 5, 11])
def test_the_output_at_t_moves_with_inputs_t_minus_2_to_t_only(at):
    """A change of the input at position s moves the output at s, s + 1 and
    s + 2 (three taps), and nowhere else: nothing before it, nothing past
    the filter's reach."""
    block, params, _ = conv_block()
    x = jax.random.normal(jax.random.key(2), (1, 12, 16))
    run = lambda xx: np.asarray(block._functional_call(
        params, jax.random.PRNGKey(0), True, (xx,))[0])
    moved = np.abs(run(x.at[0, at].add(1.0)) - run(x)).max(-1)[0]
    reach = set(range(at, min(at + 3, 12)))
    assert all(moved[t] > 1e-4 for t in reach), moved
    assert all(moved[t] == 0 for t in range(12) if t not in reach), moved


def test_the_filter_sums_the_taps_in_float32():
    """bf16 in and out, the products and their sum in f32: the result is
    the f32 filter's, rounded once to bf16, bit for bit; tap i reads
    position t - 2 + i, nothing before the first position."""
    bcx = jax.random.normal(jax.random.key(3), (2, 9, 3 * 8), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(4), (8, 3), jnp.bfloat16)
    got = short_conv_mix(bcx, w)
    assert got.dtype == jnp.bfloat16 and got.shape == (2, 9, 8)
    f = np.asarray(bcx, np.float32)
    b, c, x = f[..., :8], f[..., 8:16], f[..., 16:]
    v, k = b * x, np.asarray(w, np.float32)
    z = np.zeros_like(v)
    for t in range(9):
        for i in range(3):
            if t - 2 + i >= 0:
                z[:, t] += k[:, i] * v[:, t - 2 + i]
    want = (c * z).astype(jnp.bfloat16).astype(np.float32)
    assert np.array_equal(np.asarray(got, np.float32), want)


def test_the_filter_lies_under_its_scope_in_the_program():
    block, params, _ = conv_block()
    text = jax.jit(lambda pm, xx: block._functional_call(
        pm, jax.random.PRNGKey(0), True, (xx,))[0]).lower(
            params, jnp.ones((2, 12, 16))).as_text(debug_info=True)
    assert re.search(r"conv\.mix/.*mul", text)
    assert re.search(r"conv\.mix/.*(pad|slice)", text)
    assert re.search(r"conv\.project/.*dot_general", text)


def test_scope_names_are_the_benchmarks_literals(monkeypatch):
    monkeypatch.syspath_prepend(BENCH)
    literals = _load(os.path.join(BENCH, "conv_scopes.py"),
                     "conv_scopes_literals")
    assert literals.SCOPES == CONV_SCOPES == ("conv.project", "conv.mix")
    # kept out of the owners, which test_named_passes.py holds equal to the
    # benchmark's accepted literals (ROADMAP B1)
    assert not set(CONV_SCOPES) & set(OWNER_SCOPES)


# -- the model against the reference ---------------------------------------------------
def toy_cfg(chunk):
    """The configuration file at its rehearsal sizes, f32 so that the
    comparison is of the equations and not of bf16's rounding; the head's
    loss whole (chunk None: every position's logits) or in chunks."""
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearse"])
    # at a thirtieth of the width, five times the published initializer's
    # spread gives the filter, attention and the routers values of a size
    cfg["system"] = dict(cfg["system"], dtype="float32", init_sigma=0.1,
                         loss_chunk=chunk)
    cfg["reference_comparison"] = dict(cfg["reference_comparison"],
                                       logit_stride=4 if chunk else 1)
    return cfg


MIX = {"batch": 2, "seq_len": 64, "block_steps": 2}
OUTPUTS = ["logits", "loss", "route_choice", "route_weights", "conv_mix"] \
    + [f"{kind}_{leaf}" for leaf in config_mod.LEAVES
       for kind in ("grad", "update")]


@pytest.fixture(scope="module", params=[32, None], ids=["chunked", "whole"])
def sides(request):
    """The system's side once, and a function for the reference's side
    that keeps its compiled programs, as the readings tool does.  The
    timed step takes its first step on a twin of the net, drawn from the
    same seed: a step donates the weights that the reference reads."""
    cfg = toy_cfg(request.param)
    net, _ = config_mod.build(cfg, MIX, seed=7)
    batch = config_mod.make_batch(cfg, MIX, seed=7)
    got, aux = config_mod.system_forward(net, batch, 2)
    programs = {}

    def want(**kw):
        return config_mod.reference_outputs(reference, net, aux,
                                            programs=programs, **kw)
    twin, _ = config_mod.build(cfg, MIX, seed=7)
    assert all(np.array_equal(a.data()._data, b.data()._data) for a, b in zip(
        net.collect_params().values(), twin.collect_params().values()))
    got.update(config_mod.system_step(twin, batch, aux))
    return got, aux, want, programs, net, twin


@pytest.mark.parametrize("key", OUTPUTS)
def test_system_matches_reference(sides, key):
    got, _, want, _, _, _ = sides
    honest = want()
    assert set(got) == set(honest) == set(OUTPUTS)
    assert got[key].shape == honest[key].shape
    assert rel(got[key], honest[key]) < TOLERANCE, key


def test_every_gradient_matches_the_reference(sides):
    """Beyond the six the cell compares: every trainable parameter's
    gradient, as the timed step's first moment holds it, against the
    reference's gradient tree over the whole batch with the system's own
    choice of experts; the tied embedding's through both its uses."""
    _, aux, _, _, net, twin = sides
    step = twin._bench_make_step()
    beta1 = net._bench_cfg["system"]["beta1"]
    # the twin's parameter of the same place in the net
    twins = dict(zip(net.collect_params(), twin.collect_params()))

    def grad(param):
        return np.asarray(step.opt_states[twins[param.name]][0]) / (1 - beta1)
    hp, held = config_mod.hyper(net._bench_cfg)
    _, grads = jax.jit(lambda w, t: reference.loss_and_grads(
        w, t, hp=hp, held=held, forced=aux["chosen"]))(
            config_mod.weights(net), aux["tokens"])
    mixers = {"conv": {"in": "in_proj_weight", "taps": "conv_weight",
                       "out": "out_proj_weight"},
              "attn": {"q": "q_weight", "k": "k_weight", "v": "v_weight",
                       "o": "o_weight"}}
    ffns = {"moe": {"router": "gate_weight", "w1": "expert_w1",
                    "w3": "expert_w3", "w2": "expert_w2"},
            "mlp": {"gate": "gate_proj_weight", "up": "up_proj_weight",
                    "down": "down_proj_weight"}}
    pairs = [(net.embed_weight, grads["embed"]),
             (net.final_norm.gamma, grads["final_norm"])]
    for layer, want in zip(net.layers._children.values(), grads["layers"]):
        pairs += [(layer.ln1.gamma, want["ln1"]), (layer.ln2.gamma,
                                                   want["ln2"])]
        for group, names in dict(mixers, **ffns).items():
            block = layer.attention if group in mixers else layer.ffn
            pairs += [(getattr(block, v), want[group][k])
                      for k, v in names.items() if group in want]
        if "attn" in want:
            pairs += [(layer.attention.q_norm.gamma, want["attn"]["q_norm"]),
                      (layer.attention.k_norm.gamma, want["attn"]["k_norm"])]
    trainable = [p for p in net.collect_params().values()
                 if p.grad_req != "null"]
    assert len(pairs) == len(trainable) == 2 + 5 * 2 + 4 * 3 + 4 + 2 + 3 \
        + 4 * 4
    assert "head_weight" not in net._reg_params
    for param, ref in pairs:
        assert rel(grad(param), np.asarray(ref)) < TOLERANCE, param.name


@pytest.mark.parametrize("wrong", reference.WRONG)
def test_each_wrong_variant_fails_the_tolerance(sides, wrong):
    got, _, want, programs, _, _ = sides
    other = want(wrong=wrong)
    errors = {k: rel(got[k], other[k]) for k in OUTPUTS}
    assert max(errors.values()) > 10 * TOLERANCE, errors
    assert len(programs) == 1       # a traced index: one program for all


def test_the_filter_alone_shows_only_what_touches_it(sides):
    """The filter alone, on seeded B, C, x~ and taps: only the variants of
    the filter read off there."""
    got, aux, want, _, _, _ = sides
    assert got["conv_mix"].shape == (1, MIX["seq_len"], 64)
    for wrong in reference.WRONG:
        error = rel(got["conv_mix"], want(wrong=wrong)["conv_mix"])
        if wrong in ("filter_reversed", "fourth_tap", "b_c_exchanged"):
            assert error > 10 * TOLERANCE, wrong
        else:
            assert error < TOLERANCE, wrong


def test_the_model_holds_what_the_configuration_says(sides):
    got, aux, _, _, net, _ = sides
    layers = list(net.layers._children.values())
    assert [type(l.attention).__name__ for l in layers] == [
        "ShortConv", "GroupedQueryAttention", "ShortConv", "ShortConv",
        "ShortConv"]
    assert [l.attention.conv_weight.shape for l in layers
            if isinstance(l.attention, ShortConv)] == [(64, 3)] * 4
    attention = layers[1].attention
    assert attention._qk_norm and attention.q_norm.gamma.shape == (16,)
    assert (attention._h, attention._hkv, attention._d, attention._rot) \
        == (4, 2, 16, 16)
    assert not hasattr(layers[0].ffn, "expert_w1")
    assert not any("shared" in l.ffn._children for l in layers[1:])
    assert len(aux["chosen"]) == len(aux["inputs"]) == 4
    assert all(c.shape == (2 * MIX["seq_len"], 2) for c in aux["chosen"])


def test_a_bfloat16_router_fails_the_routings_own_tolerance(sides):
    got, _, want, _, _, _ = sides
    honest = rel(got["route_weights"], want()["route_weights"])
    for low in ("router", "all"):
        lowered = rel(got["route_weights"], want(low=low)["route_weights"])
        assert lowered > 2e-4 > 50 * honest, (low, lowered, honest)


def test_the_tests_reference_is_the_benchmarks():
    """One copy: tests/references/short_conv_decoder.py loads the
    benchmark's reference by path."""
    assert os.path.samefile(reference.PATH, os.path.join(
        BENCH, "references", NAME + ".py"))
    assert reference.mix.__module__ == "lfm2_24b_a2b_reference"


# -- the shares add up -------------------------------------------------------------------
def test_the_shares_parts_add_up_to_the_uncut_references_layer():
    """A chip of four holds experts [0, 4) of 16.  Each share computes the
    whole layer but for the absent experts: the residual and the mixer
    alike on every chip (there is no shared expert), its own experts' part
    besides.  Summed over the four shares, with what every chip computes
    alike counted once, that is the uncut reference's layer, whose experts
    are all held."""
    units, hidden, experts, top_k, t = 32, 16, 16, 4, 24
    hp = dict(eps=1e-5, top_k=top_k, scaling=1.0, n_experts=experts)
    keys = iter(jax.random.split(jax.random.key(11), 12))
    draw = lambda *shape: 0.3 * jax.random.normal(next(keys), shape)
    x = jax.random.normal(next(keys), (1, t, units))
    p = {"ln1": jnp.ones(units), "ln2": jnp.ones(units),
         "conv": {"in": draw(3 * units, units), "taps": draw(units, 3),
                  "out": draw(units, units)},
         "moe": {"router": draw(experts, units), "bias": jnp.zeros(experts),
                 "w1": draw(experts, units, hidden),
                 "w3": draw(experts, units, hidden),
                 "w2": draw(experts, hidden, units)}}

    def share(lo, hi):
        """The system's layer on the chip that holds experts [lo, hi)."""
        layer = decoder.DecoderLayer(
            units, ShortConv(units, 3),
            decoder.DroplessMoE(units, hidden, experts, top_k,
                                held_experts=range(lo, hi), scaling=1.0,
                                scoring="sigmoid"), epsilon=1e-5)
        layer.initialize()
        conv, ffn, m = layer.attention, layer.ffn, p["moe"]
        for block, name, value in (
                [(conv, "in_proj_weight", p["conv"]["in"]),
                 (conv, "conv_weight", p["conv"]["taps"]),
                 (conv, "out_proj_weight", p["conv"]["out"]),
                 (ffn, "gate_weight", m["router"])]
                + [(ffn, "expert_" + n, m[n][lo:hi])
                   for n in ("w1", "w3", "w2")]):
            getattr(block, name).set_data(np.asarray(value))
        return np.asarray(layer(nd.array(np.asarray(x)))._data)

    def plain(lo, hi):
        """The reference's layer handed the share [lo, hi)."""
        held = dict(p["moe"], **{n: p["moe"][n][lo:hi]
                                 for n in ("w1", "w3", "w2")})
        with jax.default_matmul_precision("highest"):
            return np.asarray(reference.layer(x, dict(p, moe=held), hp,
                                              (lo, hi)))
    with jax.default_matmul_precision("highest"):
        shares = [share(lo, lo + 4) for lo in range(0, experts, 4)]
    # what every chip computes alike: the reference's layer holding no
    # expert at all is residual + mixer
    whole, alike = plain(0, experts), plain(0, 0)
    parts = [s - alike for s in shares]
    assert all(np.abs(part).max() > 1e-3 for part in parts)
    assert np.allclose(alike + sum(parts), whole, atol=2e-5)
    for lo, got in zip(range(0, experts, 4), shares):
        assert np.allclose(got, plain(lo, lo + 4), atol=2e-5)


# -- what was there keeps its parameters -------------------------------------------------
# The three decoder cells' rehearsal nets as the parent of ISSUE 39 built
# them, and lfm2-24b-a2b's as it was before Kimi Delta Attention came: every
# parameter by its path in the block tree, and its shape.  (Their lowered
# train steps were also checked byte for byte at parent and change when
# each of those two changes was made, CHANGES.md.)
PARENT_PARAMS = {
    "glm-4.7-flash": """
embed_weight:512x128 head_weight:512x128 final_norm.gamma:128
layers.0.ln1.gamma:128 layers.0.attention.q_a_weight:32x128
layers.0.attention.q_b_weight:48x32 layers.0.attention.kv_a_weight:40x128
layers.0.attention.kv_b_weight:80x32 layers.0.attention.o_weight:128x48
layers.0.attention.q_a_norm.gamma:32 layers.0.attention.kv_a_norm.gamma:32
layers.0.ln2.gamma:128 layers.0.ffn.gate_proj_weight:256x128
layers.0.ffn.up_proj_weight:256x128 layers.0.ffn.down_proj_weight:128x256
layers.1.ln1.gamma:128 layers.1.attention.q_a_weight:32x128
layers.1.attention.q_b_weight:48x32 layers.1.attention.kv_a_weight:40x128
layers.1.attention.kv_b_weight:80x32 layers.1.attention.o_weight:128x48
layers.1.attention.q_a_norm.gamma:32 layers.1.attention.kv_a_norm.gamma:32
layers.1.ln2.gamma:128 layers.1.ffn.gate_weight:8x128
layers.1.ffn.select_bias:8 layers.1.ffn.expert_w1:2x128x64
layers.1.ffn.expert_w3:2x128x64 layers.1.ffn.expert_w2:2x64x128
layers.1.ffn.expert_load:64x8 layers.1.ffn.steps_counted:1
layers.1.ffn.shared.gate_proj_weight:64x128
layers.1.ffn.shared.up_proj_weight:64x128
layers.1.ffn.shared.down_proj_weight:128x64 layers.2.ln1.gamma:128
layers.2.attention.q_a_weight:32x128 layers.2.attention.q_b_weight:48x32
layers.2.attention.kv_a_weight:40x128 layers.2.attention.kv_b_weight:80x32
layers.2.attention.o_weight:128x48 layers.2.attention.q_a_norm.gamma:32
layers.2.attention.kv_a_norm.gamma:32 layers.2.ln2.gamma:128
layers.2.ffn.gate_weight:8x128 layers.2.ffn.select_bias:8
layers.2.ffn.expert_w1:2x128x64 layers.2.ffn.expert_w3:2x128x64
layers.2.ffn.expert_w2:2x64x128 layers.2.ffn.expert_load:64x8
layers.2.ffn.steps_counted:1 layers.2.ffn.shared.gate_proj_weight:64x128
layers.2.ffn.shared.up_proj_weight:64x128
layers.2.ffn.shared.down_proj_weight:128x64 mtp.eh_proj_weight:128x256
mtp.hnorm.gamma:128 mtp.enorm.gamma:128 mtp.layer.ln1.gamma:128
mtp.layer.attention.q_a_weight:32x128 mtp.layer.attention.q_b_weight:48x32
mtp.layer.attention.kv_a_weight:40x128
mtp.layer.attention.kv_b_weight:80x32 mtp.layer.attention.o_weight:128x48
mtp.layer.attention.q_a_norm.gamma:32
mtp.layer.attention.kv_a_norm.gamma:32 mtp.layer.ln2.gamma:128
mtp.layer.ffn.gate_weight:8x128 mtp.layer.ffn.select_bias:8
mtp.layer.ffn.expert_w1:2x128x64 mtp.layer.ffn.expert_w3:2x128x64
mtp.layer.ffn.expert_w2:2x64x128 mtp.layer.ffn.expert_load:64x8
mtp.layer.ffn.steps_counted:1 mtp.layer.ffn.shared.gate_proj_weight:64x128
mtp.layer.ffn.shared.up_proj_weight:64x128
mtp.layer.ffn.shared.down_proj_weight:128x64 mtp.final_norm.gamma:128
""",
    "smallthinker-21ba3b": """
embed_weight:512x112 head_weight:512x112 final_norm.gamma:112
layers.0.ln1.gamma:112 layers.0.attention.q_weight:224x112
layers.0.attention.k_weight:32x112 layers.0.attention.v_weight:32x112
layers.0.attention.o_weight:112x224 layers.0.ln2.gamma:112
layers.0.ffn.gate_weight:8x112 layers.0.ffn.select_bias:8
layers.0.ffn.expert_w1:2x112x32 layers.0.ffn.expert_w3:2x112x32
layers.0.ffn.expert_w2:2x32x112 layers.0.ffn.expert_load:64x8
layers.0.ffn.steps_counted:1 layers.1.ln1.gamma:112
layers.1.attention.q_weight:224x112 layers.1.attention.k_weight:32x112
layers.1.attention.v_weight:32x112 layers.1.attention.o_weight:112x224
layers.1.ln2.gamma:112 layers.1.ffn.gate_weight:8x112
layers.1.ffn.select_bias:8 layers.1.ffn.expert_w1:2x112x32
layers.1.ffn.expert_w3:2x112x32 layers.1.ffn.expert_w2:2x32x112
layers.1.ffn.expert_load:64x8 layers.1.ffn.steps_counted:1
layers.2.ln1.gamma:112 layers.2.attention.q_weight:224x112
layers.2.attention.k_weight:32x112 layers.2.attention.v_weight:32x112
layers.2.attention.o_weight:112x224 layers.2.ln2.gamma:112
layers.2.ffn.gate_weight:8x112 layers.2.ffn.select_bias:8
layers.2.ffn.expert_w1:2x112x32 layers.2.ffn.expert_w3:2x112x32
layers.2.ffn.expert_w2:2x32x112 layers.2.ffn.expert_load:64x8
layers.2.ffn.steps_counted:1 layers.3.ln1.gamma:112
layers.3.attention.q_weight:224x112 layers.3.attention.k_weight:32x112
layers.3.attention.v_weight:32x112 layers.3.attention.o_weight:112x224
layers.3.ln2.gamma:112 layers.3.ffn.gate_weight:8x112
layers.3.ffn.select_bias:8 layers.3.ffn.expert_w1:2x112x32
layers.3.ffn.expert_w3:2x112x32 layers.3.ffn.expert_w2:2x32x112
layers.3.ffn.expert_load:64x8 layers.3.ffn.steps_counted:1
""",
    "laguna-s-2.1": """
embed_weight:512x96 head_weight:512x96 final_norm.gamma:96
layers.0.ln1.gamma:96 layers.0.attention.q_weight:192x96
layers.0.attention.k_weight:32x96 layers.0.attention.v_weight:32x96
layers.0.attention.o_weight:96x192 layers.0.attention.gate_weight:12x96
layers.0.ln2.gamma:96 layers.0.ffn.gate_proj_weight:192x96
layers.0.ffn.up_proj_weight:192x96 layers.0.ffn.down_proj_weight:96x192
layers.1.ln1.gamma:96 layers.1.attention.q_weight:288x96
layers.1.attention.k_weight:32x96 layers.1.attention.v_weight:32x96
layers.1.attention.o_weight:96x288 layers.1.attention.gate_weight:18x96
layers.1.ln2.gamma:96 layers.1.ffn.gate_weight:16x96
layers.1.ffn.select_bias:16 layers.1.ffn.expert_w1:2x96x32
layers.1.ffn.expert_w3:2x96x32 layers.1.ffn.expert_w2:2x32x96
layers.1.ffn.expert_load:64x16 layers.1.ffn.steps_counted:1
layers.1.ffn.shared.gate_proj_weight:32x96
layers.1.ffn.shared.up_proj_weight:32x96
layers.1.ffn.shared.down_proj_weight:96x32 layers.2.ln1.gamma:96
layers.2.attention.q_weight:288x96 layers.2.attention.k_weight:32x96
layers.2.attention.v_weight:32x96 layers.2.attention.o_weight:96x288
layers.2.attention.gate_weight:18x96 layers.2.ln2.gamma:96
layers.2.ffn.gate_weight:16x96 layers.2.ffn.select_bias:16
layers.2.ffn.expert_w1:2x96x32 layers.2.ffn.expert_w3:2x96x32
layers.2.ffn.expert_w2:2x32x96 layers.2.ffn.expert_load:64x16
layers.2.ffn.steps_counted:1 layers.2.ffn.shared.gate_proj_weight:32x96
layers.2.ffn.shared.up_proj_weight:32x96
layers.2.ffn.shared.down_proj_weight:96x32 layers.3.ln1.gamma:96
layers.3.attention.q_weight:288x96 layers.3.attention.k_weight:32x96
layers.3.attention.v_weight:32x96 layers.3.attention.o_weight:96x288
layers.3.attention.gate_weight:18x96 layers.3.ln2.gamma:96
layers.3.ffn.gate_weight:16x96 layers.3.ffn.select_bias:16
layers.3.ffn.expert_w1:2x96x32 layers.3.ffn.expert_w3:2x96x32
layers.3.ffn.expert_w2:2x32x96 layers.3.ffn.expert_load:64x16
layers.3.ffn.steps_counted:1 layers.3.ffn.shared.gate_proj_weight:32x96
layers.3.ffn.shared.up_proj_weight:32x96
layers.3.ffn.shared.down_proj_weight:96x32 layers.4.ln1.gamma:96
layers.4.attention.q_weight:192x96 layers.4.attention.k_weight:32x96
layers.4.attention.v_weight:32x96 layers.4.attention.o_weight:96x192
layers.4.attention.gate_weight:12x96 layers.4.ln2.gamma:96
layers.4.ffn.gate_weight:16x96 layers.4.ffn.select_bias:16
layers.4.ffn.expert_w1:2x96x32 layers.4.ffn.expert_w3:2x96x32
layers.4.ffn.expert_w2:2x32x96 layers.4.ffn.expert_load:64x16
layers.4.ffn.steps_counted:1 layers.4.ffn.shared.gate_proj_weight:32x96
layers.4.ffn.shared.up_proj_weight:32x96
layers.4.ffn.shared.down_proj_weight:96x32
""",
    "lfm2-24b-a2b": """
embed_weight:512x64 final_norm.gamma:64 layers.0.ln1.gamma:64
layers.0.attention.in_proj_weight:192x64 layers.0.attention.conv_weight:64x3
layers.0.attention.out_proj_weight:64x64 layers.0.ln2.gamma:64
layers.0.ffn.gate_proj_weight:128x64 layers.0.ffn.up_proj_weight:128x64
layers.0.ffn.down_proj_weight:64x128 layers.1.ln1.gamma:64
layers.1.attention.q_weight:64x64 layers.1.attention.k_weight:32x64
layers.1.attention.v_weight:32x64 layers.1.attention.o_weight:64x64
layers.1.attention.q_norm.gamma:16 layers.1.attention.k_norm.gamma:16
layers.1.ln2.gamma:64 layers.1.ffn.gate_weight:8x64 layers.1.ffn.select_bias:8
layers.1.ffn.expert_w1:2x64x32 layers.1.ffn.expert_w3:2x64x32
layers.1.ffn.expert_w2:2x32x64 layers.1.ffn.expert_load:64x8
layers.1.ffn.steps_counted:1 layers.2.ln1.gamma:64
layers.2.attention.in_proj_weight:192x64 layers.2.attention.conv_weight:64x3
layers.2.attention.out_proj_weight:64x64 layers.2.ln2.gamma:64
layers.2.ffn.gate_weight:8x64 layers.2.ffn.select_bias:8
layers.2.ffn.expert_w1:2x64x32 layers.2.ffn.expert_w3:2x64x32
layers.2.ffn.expert_w2:2x32x64 layers.2.ffn.expert_load:64x8
layers.2.ffn.steps_counted:1 layers.3.ln1.gamma:64
layers.3.attention.in_proj_weight:192x64 layers.3.attention.conv_weight:64x3
layers.3.attention.out_proj_weight:64x64 layers.3.ln2.gamma:64
layers.3.ffn.gate_weight:8x64 layers.3.ffn.select_bias:8
layers.3.ffn.expert_w1:2x64x32 layers.3.ffn.expert_w3:2x64x32
layers.3.ffn.expert_w2:2x32x64 layers.3.ffn.expert_load:64x8
layers.3.ffn.steps_counted:1 layers.4.ln1.gamma:64
layers.4.attention.in_proj_weight:192x64 layers.4.attention.conv_weight:64x3
layers.4.attention.out_proj_weight:64x64 layers.4.ln2.gamma:64
layers.4.ffn.gate_weight:8x64 layers.4.ffn.select_bias:8
layers.4.ffn.expert_w1:2x64x32 layers.4.ffn.expert_w3:2x64x32
layers.4.ffn.expert_w2:2x32x64 layers.4.ffn.expert_load:64x8
layers.4.ffn.steps_counted:1
"""
,
}


def block_tree(block, prefix=""):
    out = [f"{prefix}{n}:{'x'.join(map(str, p.shape))}"
           for n, p in block._reg_params.items()]
    for n, child in block._children.items():
        out += block_tree(child, prefix + n + ".")
    return out


@pytest.mark.parametrize("name,traffic", [
    ("glm-4.7-flash", "pretrain4k"), ("smallthinker-21ba3b", "extend16k"),
    ("laguna-s-2.1", "pretrain8k"), ("lfm2-24b-a2b", "pretrain4k-b8")])
def test_the_decoder_cells_that_were_there_keep_their_parameters(name,
                                                                 traffic):
    """With `tie_embeddings`, `qk_norm` and the conv kind absent, each net
    has the parent's parameters, names and shapes, in the parent's order:
    an untied head, no q/k norm, no conv weight; and with `q_rank` given,
    `rotary` and the kda kind absent, the same again."""
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", traffic + ".json")) as f:
        mix = json.load(f)
    cfg.update(cfg["rehearse"])
    mix.update(mix["rehearse"])
    module = _load(os.path.join(BENCH, "configs", name + ".py"),
                   "parent_params_" + name.replace("-", "_").replace(".", "_"))
    net, _ = module.build(cfg, mix, 1)
    assert block_tree(net) == PARENT_PARAMS[name].split()
