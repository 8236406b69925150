"""The head-gated mixed decoder (ISSUE 34) at toy sizes on the CPU: the
flash kernel's window narrower than a key block and its groups of 6 and 9 in
interpret mode against the dense arm, YaRN's frequencies against numbers
worked by hand, GroupedQueryAttention's gate and its turn over a part of the
head, a leading dense layer before sigmoid-routed experts with a shared one,
against the plain reference tests/references/gated_mixed_decoder.py, through
the configuration module the benchmark uses
(benchmark/configs/laguna-s-2.1.py: its `weights`, `compare` and `hyper` are
what decide `correct` on the chip).
"""
import importlib
import importlib.util
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_mx import nd
from tpu_mx.kernels import flash_attention as fa
from tpu_mx.models import decoder
from tpu_mx.models.decoder import (ATTENTION_GATE_SCOPES, ATTENTION_SCOPES,
                                   DECODER_SCOPES, GroupedQueryAttention,
                                   rotary, yarn_inv_freq)

dispatch = importlib.import_module("tpu_mx.parallel.ring_attention")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
NAME = "laguna-s-2.1"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load(os.path.join(ROOT, "tests", "references",
                               "gated_mixed_decoder.py"), "gated_mixed_ref")
config_mod = _load(os.path.join(BENCH, "configs", NAME + ".py"),
                   "laguna_config_mod")


def rel(a, b):
    return float(np.sqrt(np.mean(np.square(a - b)))
                 / np.sqrt(np.mean(np.square(b))))


# -- the kernel against the dense arm ----------------------------------------------
T, BLOCK_Q, BLOCK_K = 256, 64, 128


def qkv(group, t=T, kv_heads=2, d=64, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    shape = lambda h: (1, h, t, d)
    return (jax.random.normal(keys[0], shape(kv_heads * group)),
            jax.random.normal(keys[1], shape(kv_heads)),
            jax.random.normal(keys[2], shape(kv_heads)))


def flash(q, k, v, window):
    return fa.mha_flash_attention(q, k, v, causal=True, window=window,
                                  block_q=BLOCK_Q, block_k=BLOCK_K)


def dense(q, k, v, window):
    return dispatch.local_flash_attention(q, k, v, causal=True, window=window)


def value_and_grads(fn, q, k, v, window):
    with jax.default_matmul_precision("highest"):
        return fn(q, k, v, window), jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a, window))), (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("group", [6, 9])
@pytest.mark.parametrize("window", [48, 64, 128, 200], ids=[
    "narrower_than_both_blocks", "a_q_block_and_half_a_k_block",
    "a_k_block", "a_multiple_of_neither"])
def test_the_kernel_is_the_dense_arm_under_a_narrow_window(group, window):
    """Interpret mode, causal, blocks of 64 x 128 (512 x 1,024, the cell's
    until ISSUE 35 gave it 512 x 512, in small): a window narrower than a
    key block, as wide as one, and a multiple of neither block, with 6 and
    with 9 query heads a key/value head; the output and all three
    gradients, dk and dv summed over the group's query heads inside the
    dk/dv kernel."""
    q, k, v = qkv(group)
    out, grads = value_and_grads(flash, q, k, v, window)
    want, want_grads = value_and_grads(dense, q, k, v, window)
    assert out.shape == q.shape and grads[1].shape == k.shape
    assert float(jnp.max(jnp.abs(out - want))) < 2e-5
    for got, ref, name in zip(grads, want_grads, "qkv"):
        assert float(jnp.max(jnp.abs(got - ref))) < 3e-4, name


def brute_force_blocks(t, bq, bk, window):
    """The blocks of the (q block, k block) grid that hold a pair the mask
    lets through, pair by pair."""
    q, k = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = (k <= q) & (q - k < (window or t))
    return int(seen.reshape(t // bq, bq, t // bk, bk).any((1, 3)).sum())


@pytest.mark.parametrize("t,bq,bk,window", [
    (256, 64, 128, 48), (256, 64, 128, 64), (256, 64, 128, 128),
    (256, 64, 128, 200), (512, 128, 64, 30), (1024, 128, 256, 128),
    (2048, 512, 1024, 512), (8192, 512, 1024, 512)])
def test_blocks_run_is_a_count_by_brute_force(t, bq, bk, window):
    assert fa.blocks_run(t, t, True, window, bq, bk) \
        == ((t // bq) * (t // bk), brute_force_blocks(t, bq, bk, window))
    if t == 8192:
        # the cell's window layers at the blocks flash_attention() takes by
        # itself, 512 x 512 since ISSUE 35 (512 x 1,024 before: 23 of 128):
        # 31 of 256 run (12.11%) for 6.06% of the square's pairs
        assert fa.blocks_run(t, t, True, window) == (256, 31)
        pairs = config_mod.window_pairs(t, window)
        assert 100 * pairs / t ** 2 == pytest.approx(6.06, abs=0.01)


def test_the_dispatch_counts_the_narrow_windows_blocks(monkeypatch):
    """What a TPU process dispatches, the kernel in interpret mode: 9 query
    heads a key/value head go to the kernel as they are, and the windowed
    call's blocks are counted as it is traced."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "_interpret", lambda: True)
    q, k, v = qkv(9, t=2048, kv_heads=1)
    before = dict(dispatch.dispatch_counts), dict(dispatch.window_blocks)
    out = dispatch.attention(q, k, v, causal=True, window=512)
    assert dispatch.dispatch_counts["pallas_flash"] \
        == before[0]["pallas_flash"] + 1
    grid, run = fa.blocks_run(2048, 2048, True, 512)
    assert (grid, run) == (16, 7)
    assert dispatch.window_blocks["grid"] == before[1]["grid"] + grid
    assert dispatch.window_blocks["run"] == before[1]["run"] + run
    # the band: two key blocks a query block, one step of the eight idle
    assert dispatch.window_blocks["walked"] == before[1]["walked"] + 8
    monkeypatch.undo()
    want = dispatch.attention(q, k, v, causal=True, window=512)
    assert float(jnp.max(jnp.abs(out - want))) < 1e-4


# -- positions ---------------------------------------------------------------------
def test_yarn_frequencies_are_the_numbers_worked_by_hand():
    """theta 500,000, 64 turned dimensions, factor 128 over 8,192 positions,
    beta_fast 32, beta_slow 1.  ln theta = 13.12236; the pair that turns n
    times in 8,192 positions has index c(n) = 64 ln(8192 / (2 pi n)) / (2 ln
    theta): c(32) = 64 x 3.70733 / 26.24473 = 9.0406, c(1) = 64 x 7.17306 /
    26.24473 = 17.4921, so low = 9 and high = 18: pairs 0..9 keep f_i =
    theta^(-i/32), pairs 18..31 take f_i / 128, pair 9 + m blends with
    ramp m / 9."""
    got = yarn_inv_freq(500000.0, 64, 128, 8192, 32, 1)
    f = lambda i: math.exp(-i / 32 * math.log(500000.0))
    assert got.shape == (32,)
    assert got[0] == 1.0 and got[9] == pytest.approx(f(9))
    assert got[9] == pytest.approx(0.0249554, rel=1e-5)      # e^(-3.690665)
    assert got[18] == pytest.approx(f(18) / 128)
    assert got[18] == pytest.approx(4.86541e-06, rel=1e-5)   # e^(-7.381329)/128
    assert got[31] == pytest.approx(f(31) / 128)
    # pair 12: ramp 3/9; f_12 = e^(-4.920886) = 0.00729266
    assert got[12] == pytest.approx(
        0.00729266 * (1 / 3 / 128 + 2 / 3), rel=1e-5)
    assert got[12] == pytest.approx(0.00488077, rel=1e-5)
    for i in range(32):
        ramp = min(max((i - 9) / 9, 0), 1)
        assert got[i] == pytest.approx(f(i) * (ramp / 128 + 1 - ramp))
    # the reference writes the same equations out again, on its own
    assert np.allclose(got, reference.inv_freq(
        500000.0, 64, dict(factor=128, original_length=8192, beta_fast=32,
                           beta_slow=1)), rtol=1e-12)
    # the published attention factor is 0.1 ln(factor) + 1
    assert 0.1 * math.log(128) + 1 == pytest.approx(1.4852030263919618)


def test_rotary_takes_given_frequencies_and_a_factor():
    x = jax.random.normal(jax.random.key(3), (2, 5, 8))
    freq = np.array([1.0, 0.3, 0.02, 0.001])
    ang = np.arange(5)[:, None] * freq[None, :]
    cos, sin = 1.5 * np.cos(np.tile(ang, 2)), 1.5 * np.sin(np.tile(ang, 2))
    turned = np.concatenate([-x[..., 4:], x[..., :4]], -1)
    assert np.allclose(rotary(x, None, "halves", freq, 1.5),
                       x * cos + turned * sin, atol=1e-5)
    # the plain frequencies handed in are the plain turn
    plain = 1e4 ** (-np.arange(0, 8, 2) / 8)
    assert np.allclose(rotary(x, None, "halves", plain), rotary(x, 1e4,
                                                                "halves"),
                       atol=1e-6)


# -- the block -----------------------------------------------------------------------
class ParentGroupedQueryAttention(GroupedQueryAttention):
    """GroupedQueryAttention's forward as the parent commit of ISSUE 34 had
    it, copied: no gate, the whole head turned by the plain frequencies."""

    def _heads(self, x, n, turn):
        b, t = x.shape[:2]
        x = x.reshape(b, t, n, self._d).transpose(0, 2, 1, 3)
        return rotary(x, self._theta, self._pairs) if turn else x

    def hybrid_forward(self, F, x, q_weight, k_weight, v_weight, o_weight):
        from tpu_mx.ndarray import ops
        from tpu_mx.parallel import attention
        b, t = x.shape[:2]
        turn = self._theta is not None
        with jax.named_scope("attn.project"):
            q, k, v = (decoder._linear(F, x, w)
                       for w in (q_weight, k_weight, v_weight))
        with jax.named_scope("attn.full" if self._window is None
                             else "attn.window"):
            q = ops._apply(lambda a: self._heads(a, self._h, turn), [q],
                           "query_heads")
            k = ops._apply(lambda a: self._heads(a, self._hkv, turn), [k],
                           "key_heads")
            v = ops._apply(lambda a: self._heads(a, self._hkv, False), [v],
                           "value_heads")
            out = ops._apply(
                lambda qq, kk, vv: attention(
                    qq, kk, vv, mesh=self._mesh, causal=True,
                    window=self._window), [q, k, v], "RingAttention")
            out = ops._apply(
                lambda o: o.transpose(0, 2, 1, 3).reshape(
                    b, t, self._h * self._d), [out], "merge_heads")
        with jax.named_scope("attn.project"):
            return decoder._linear(F, out, o_weight)


@pytest.mark.parametrize("kw", [dict(rope_theta=1e4, window=8),
                                dict(rope_theta=None),
                                dict(rope_theta=1.5e6, window=None)],
                         ids=["window", "no_positions", "full"])
def test_an_ungated_whole_head_layer_traces_to_the_parents_jaxpr(kw):
    """Without `gate`, with `rotary_dim` the head size and no `yarn`, the
    block's gradient program is the parent's, equation for equation: the
    two decoder cells that were there run the programs they ran."""
    def jaxpr(cls):
        block = cls(32, 6, 2, 8, **kw)
        block.initialize()
        params = {k: p.data()._data
                  for k, p in block.collect_params().items()}
        x = jnp.ones((2, 16, 32), jnp.float32)
        return str(jax.make_jaxpr(jax.grad(
            lambda pm, xx: jnp.sum(block._functional_call(
                pm, jax.random.PRNGKey(0), True, (xx,))[0][0]),
            argnums=(0, 1)))(params, x))
    mine, parents = jaxpr(GroupedQueryAttention), \
        jaxpr(ParentGroupedQueryAttention)
    assert mine == parents
    assert "logistic" not in mine
    gated = GroupedQueryAttention(32, 6, 2, 8, gate=True, **kw)
    assert gated.gate_weight.shape == (6, 32)


def test_rotary_dim_is_checked():
    with pytest.raises(ValueError, match="rotary_dim"):
        GroupedQueryAttention(32, 6, 2, 8, rope_theta=1e4, rotary_dim=12)
    with pytest.raises(ValueError, match="rotary_dim"):
        GroupedQueryAttention(32, 6, 2, 8, rope_theta=1e4, rotary_dim=3)


# -- the model against the reference ---------------------------------------------------
def toy_cfg():
    """The configuration file at its rehearsal sizes, f32 so that the
    comparison is of the equations and not of bf16's rounding."""
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearse"])
    # at a thirtieth of the width, five times the published initializer's
    # spread gives attention, the gates and the routers scores of a size
    cfg["system"] = dict(cfg["system"], dtype="float32", init_sigma=0.1,
                         loss_chunk=32)
    cfg["sliding_window"] = 24
    cfg["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = 32
    cfg["reference_comparison"] = dict(cfg["reference_comparison"],
                                       logit_stride=4)
    return cfg


MIX = {"batch": 2, "seq_len": 64, "block_steps": 2}
OUTPUTS = ["logits", "loss", "grad_router", "grad_expert_down", "grad_q",
           "grad_gate", "grad_embed", "route_choice", "route_weights",
           "attend_window"]
TOLERANCE = 2e-3    # f32 against f32 "highest"; the toy's honest error is 1e-6


@pytest.fixture(scope="module")
def sides():
    """The system's side once, and a function for the reference's side
    that keeps its one compiled program, as the readings tool does."""
    cfg = toy_cfg()
    net, _ = config_mod.build(cfg, MIX, seed=7)
    batch = config_mod.make_batch(cfg, MIX, seed=7)
    got, aux = config_mod.system_outputs(net, batch, 2)
    programs = {}

    def want(**kw):
        return config_mod.reference_outputs(reference, net, aux,
                                            programs=programs, **kw)
    return got, aux, want, programs, net


@pytest.mark.parametrize("key", OUTPUTS)
def test_system_matches_reference(sides, key):
    got, _, want, _, _ = sides
    honest = want()
    assert set(got) == set(honest) == set(OUTPUTS)
    assert got[key].shape == honest[key].shape
    assert rel(got[key], honest[key]) < TOLERANCE, key


def test_every_kind_of_gradient_matches_the_reference(sides):
    """Beyond the five the cell compares: every parameter's gradient, W_g's
    of every layer among them, against the reference's gradient tree with
    the system's own choice of experts."""
    _, aux, _, _, net = sides
    hp, held = config_mod.hyper(net._bench_cfg)
    _, grads = jax.jit(lambda w, t: reference.loss_and_grads(
        w, t, hp=hp, held=held, forced=aux["chosen"]))(
            config_mod.weights(net), aux["tokens"])
    names = {"q": "q_weight", "k": "k_weight", "v": "v_weight",
             "o": "o_weight", "g": "gate_weight"}
    mlp = {"gate": "gate_proj_weight", "up": "up_proj_weight",
           "down": "down_proj_weight"}
    checked = 0
    for layer, want in zip(net.layers._children.values(), grads["layers"]):
        pairs = [(getattr(layer.attention, v).grad, want["attn"][k])
                 for k, v in names.items()]
        pairs += [(layer.ln1.gamma.grad, want["ln1"]),
                  (layer.ln2.gamma.grad, want["ln2"])]
        if "moe" in want:
            moe = want["moe"]
            pairs += [(layer.ffn.gate_weight.grad, moe["router"])]
            pairs += [(getattr(layer.ffn, "expert_" + k).grad, moe[k])
                      for k in ("w1", "w3", "w2")]
            pairs += [(getattr(layer.ffn.shared, v).grad, moe["shared"][k])
                      for k, v in mlp.items()]
        else:
            pairs += [(getattr(layer.ffn, v).grad, want["mlp"][k])
                      for k, v in mlp.items()]
        for got, ref in pairs:
            assert rel(np.asarray(got._data), np.asarray(ref)) < TOLERANCE
            checked += 1
    assert checked == 5 * 7 + 3 + 4 * 7
    for got, ref in ((net.head_weight.grad, grads["head"]),
                     (net.final_norm.gamma.grad, grads["final_norm"])):
        assert rel(np.asarray(got._data), np.asarray(ref)) < TOLERANCE


@pytest.mark.parametrize("wrong", reference.WRONG)
def test_each_wrong_variant_fails_the_tolerance(sides, wrong):
    got, _, want, programs, _ = sides
    other = want(wrong=wrong)
    errors = {k: rel(got[k], other[k]) for k in OUTPUTS
              if np.any(other[k])}         # gate_off leaves W_g no gradient
    assert max(errors.values()) > 10 * TOLERANCE, errors
    assert len(programs) == 1       # a traced index: one program for all


def test_the_window_layers_attention_alone_shows_a_key_too_many(sides):
    """The attention call alone, on seeded q, k, v of a window layer's
    shapes (18 query heads over 2 here, 72 over 8 in the cell): only the
    variant that touches the mask reads off there."""
    got, aux, want, _, _ = sides
    assert got["attend_window"].shape == (1, 18, MIX["seq_len"], 16)
    assert [a.shape[1] for a in aux["window_qkv"]] == [18, 2, 2]
    for wrong in reference.WRONG:
        error = rel(got["attend_window"], want(wrong=wrong)["attend_window"])
        if wrong == "window_off_by_one":
            assert error > 10 * TOLERANCE
        else:
            assert error < TOLERANCE, wrong


def test_the_model_holds_what_the_configuration_says(sides):
    got, aux, _, _, net = sides
    assert got["logits"].shape == (2, MIX["seq_len"] // 4, 512)
    # four expert layers behind the leading dense one
    assert len(aux["chosen"]) == len(aux["inputs"]) == 4
    assert all(c.shape == (2 * MIX["seq_len"], 3) for c in aux["chosen"])
    layers = list(net.layers._children.values())
    assert [l.attention.gate_weight.shape[0] for l in layers] \
        == [12, 18, 18, 18, 12]
    assert [l.attention._window for l in layers] == [None, 24, 24, 24, None]
    assert [l.attention._rot for l in layers] == [8, 16, 16, 16, 8]
    assert [l.attention._factor for l in layers] \
        == [pytest.approx(1.2079441541679836), 1.0, 1.0, 1.0,
            pytest.approx(1.2079441541679836)]
    assert not hasattr(layers[0].ffn, "expert_w1")
    assert all(l.ffn.shared is not None for l in layers[1:])


def test_a_bfloat16_router_fails_the_routings_own_tolerance(sides):
    got, _, want, _, _ = sides
    honest = rel(got["route_weights"], want()["route_weights"])
    for low in ("router", "all"):
        lowered = rel(got["route_weights"], want(low=low)["route_weights"])
        assert lowered > 2e-4 > 50 * honest, (low, lowered, honest)


def test_the_two_reference_files_are_byte_equal():
    with open(os.path.join(BENCH, "references", NAME + ".py"), "rb") as a, \
            open(os.path.join(ROOT, "tests", "references",
                              "gated_mixed_decoder.py"), "rb") as b:
        assert a.read() == b.read()


def test_scope_names_are_the_benchmarks_literals(monkeypatch):
    monkeypatch.syspath_prepend(BENCH)
    gate = _load(os.path.join(BENCH, "gate_scopes.py"),
                 "gate_scopes_literals")
    attention = _load(os.path.join(BENCH, "attention_scopes.py"),
                      "attention_scopes_literals_34")
    assert gate.SCOPES == ATTENTION_GATE_SCOPES == ("attn.gate",)
    assert attention.SCOPES == ATTENTION_SCOPES     # still the three names
    assert not set(ATTENTION_GATE_SCOPES) \
        & (set(ATTENTION_SCOPES) | set(DECODER_SCOPES))


def test_the_gate_lies_under_its_scope_in_the_program():
    block = GroupedQueryAttention(32, 6, 2, 8, rope_theta=1e4, gate=True)
    block.initialize()
    params = {k: p.data()._data for k, p in block.collect_params().items()}
    text = jax.jit(lambda pm, xx: block._functional_call(
        pm, jax.random.PRNGKey(0), True, (xx,))[0][0]).lower(
            params, jnp.ones((2, 16, 32))).as_text(debug_info=True)
    assert re.search(r"attn\.gate/.*logistic", text)


# -- the shares add up -------------------------------------------------------------------
def test_the_shares_parts_add_up_to_the_uncut_references_layer():
    """A chip of four holds experts [0, 4) of 16.  Each share computes the
    whole layer but for the absent experts: the residual, the attention and
    the shared expert alike on every chip, its own experts' part besides.
    Summed over the four shares, with what every chip computes alike
    counted once, that is the uncut reference's layer, whose experts are
    all held."""
    units, hidden, experts, top_k, t = 32, 16, 16, 5, 24
    hp = dict(heads=(6,), kv_heads=2, head_dim=8, sliding=(True,), window=7,
              rope={"sliding": dict(theta=1e4, rotary_dim=8, yarn=None),
                    "full": dict(theta=5e5, rotary_dim=4, yarn=None)},
              eps=1e-6, top_k=top_k, scaling=2.5, n_experts=experts)
    keys = iter(jax.random.split(jax.random.key(11), 20))
    draw = lambda *shape: 0.3 * jax.random.normal(next(keys), shape)
    x = jax.random.normal(next(keys), (1, t, units))
    p = {"ln1": jnp.ones(units), "ln2": jnp.ones(units),
         "attn": {"q": draw(48, units), "k": draw(16, units),
                  "v": draw(16, units), "o": draw(units, 48),
                  "g": draw(6, units)},
         "moe": {"router": draw(experts, units), "bias": jnp.zeros(experts),
                 "w1": draw(experts, units, hidden),
                 "w3": draw(experts, units, hidden),
                 "w2": draw(experts, hidden, units),
                 "shared": {"gate": draw(hidden, units),
                            "up": draw(hidden, units),
                            "down": draw(units, hidden)}}}

    def share(lo, hi):
        """The system's layer on the chip that holds experts [lo, hi)."""
        layer = decoder.DecoderLayer(
            units, GroupedQueryAttention(units, 6, 2, 8, rope_theta=1e4,
                                         window=7, gate=True),
            decoder.DroplessMoE(units, hidden, experts, top_k,
                                held_experts=range(lo, hi), scaling=2.5,
                                shared=decoder.GatedMLP(units, hidden),
                                scoring="sigmoid"))
        layer.initialize()
        att, ffn, m = layer.attention, layer.ffn, p["moe"]
        for block, name, value in (
                [(att, n + "_weight", p["attn"][n[0]])
                 for n in ("q", "k", "v", "o")]
                + [(att, "gate_weight", p["attn"]["g"]),
                   (ffn, "gate_weight", m["router"])]
                + [(ffn, "expert_" + n, m[n][lo:hi])
                   for n in ("w1", "w3", "w2")]
                + [(ffn.shared, n + "_proj_weight", m["shared"][n])
                   for n in ("gate", "up", "down")]):
            getattr(block, name).set_data(np.asarray(value))
        return np.asarray(layer(nd.array(np.asarray(x)))._data)

    def plain(lo, hi):
        """The reference's layer handed the share [lo, hi)."""
        held = dict(p["moe"], **{n: p["moe"][n][lo:hi]
                                 for n in ("w1", "w3", "w2")})
        with jax.default_matmul_precision("highest"):
            return np.asarray(reference.layer(
                x, dict(p, moe=held), hp, (lo, hi), 6, True))
    with jax.default_matmul_precision("highest"):
        shares = [share(lo, lo + 4) for lo in range(0, experts, 4)]
    # what every chip computes alike: the reference's layer holding no
    # expert at all is residual + attention + shared expert
    whole, alike = plain(0, experts), plain(0, 0)
    parts = [s - alike for s in shares]
    assert all(np.abs(part).max() > 1e-3 for part in parts)
    assert np.allclose(alike + sum(parts), whole, atol=2e-5)
    # and each share is the reference handed the same share
    for lo, got in zip(range(0, experts, 4), shares):
        assert np.allclose(got, plain(lo, lo + 4), atol=2e-5)


# -- the cell's arithmetic -------------------------------------------------------------------
def test_flops_per_sample_is_the_issues_reckoning():
    """ISSUE 34: 30.00 T a sample, its parts as reckoned there; the
    parameters this chip holds, 811.1 M."""
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", "pretrain8k.json")) as f:
        mix = json.load(f)
    t = mix["seq_len"]
    assert config_mod.window_pairs(t, None) == 33558528
    assert config_mod.window_pairs(t, 512) == 4063488
    parts = config_mod.flops_parts(cfg, mix)
    total = config_mod.flops_per_sample(cfg, mix)
    assert total == sum(parts.values())
    assert total / 1e12 == pytest.approx(30.00, abs=0.005)
    share = lambda *names: 100 * sum(parts[n] for n in names) / total
    assert parts["scores_window"] == 12 * 4063488 * 128 * 72 * 3
    assert parts["scores_full"] == 12 * 33558528 * 128 * 48 * 2
    assert share("project", "scores_full", "scores_window") \
        == pytest.approx(66.5, abs=0.05)
    assert share("scores_full", "scores_window") \
        == pytest.approx(21.0, abs=0.05)
    assert share("scores_window") == pytest.approx(4.5, abs=0.05)
    assert share("routed") == pytest.approx(1.9, abs=0.05)
    assert share("shared") == pytest.approx(6.2, abs=0.05)
    assert share("dense_mlp") == pytest.approx(18.6, abs=0.05)
    assert share("head") == pytest.approx(6.3, abs=0.05)
    assert config_mod.loss_center(cfg, mix) == pytest.approx(
        math.log(12544) + 0.6144)
    attention = lambda h: 3072 * 128 * (2 * h + 16) + 3072 * h
    expert = 3 * 3072 * 1024
    sparse = lambda h: attention(h) + 3072 * 256 + 256 + 9 * expert
    held = attention(48) + 3 * 3072 * 12288 + 3 * sparse(72) + sparse(48) \
        + 2 * 12544 * 3072 + 5 * 2 * 3072 + 3072
    assert held == 811018240
    # the compile-only reading's count of the step's values: with each
    # expert layer's ring of 64 steps' loads and its step counter
    assert held + 4 * (64 * 256 + 1) == 811083780
    net_cfg = config_mod.model_config(cfg)
    assert [(a["num_heads"], a["window"], a["rotary_dim"], a["rope_theta"],
             a["yarn"] is not None) for a in net_cfg["attention"]] \
        == [(48, None, 64, 5e5, True)] + [(72, 512, 128, 1e4, False)] * 3 \
        + [(48, None, 64, 5e5, True)]
    assert net_cfg["num_dense_layers"] == 1 \
        and net_cfg["dense_hidden"] == 12288
    assert net_cfg["moe"] == dict(
        hidden_size=1024, num_experts=256, top_k=10, held_experts=(0, 8),
        scaling=2.5, shared_hidden=1024, scoring="sigmoid")
