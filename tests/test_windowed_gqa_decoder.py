"""The windowed grouped-query decoder (ISSUE 31) at toy sizes on the CPU:
the flash kernel's window and grouped heads in interpret mode against the
dense arm, the dispatch, GroupedQueryAttention, the router fed from before
the attention, ReGLU experts with softmax weights, the chunked head, against
the plain reference tests/references/windowed_gqa_decoder.py, through the
configuration module the benchmark uses
(benchmark/configs/smallthinker-21ba3b.py: its `weights`, `compare` and
`hyper` are what decide `correct` on the chip).
"""
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_mx import nd
from tpu_mx.kernels import flash_attention as fa
from tpu_mx.models import decoder
from tpu_mx.models.decoder import ATTENTION_SCOPES, DECODER_SCOPES, rotary
from tpu_mx.parallel import DroplessMoE, attention, make_mesh
from tpu_mx.parallel import ring_attention as ring_attention_fn
from tpu_mx.parallel.ulysses import ulysses_attention

dispatch = importlib.import_module("tpu_mx.parallel.ring_attention")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
NAME = "smallthinker-21ba3b"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load(os.path.join(ROOT, "tests", "references",
                               "windowed_gqa_decoder.py"), "windowed_gqa_ref")
config_mod = _load(os.path.join(BENCH, "configs", NAME + ".py"),
                   "smallthinker_config_mod")


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
    """The dispatch's own CPU arm: _dense_mask with the window, k and v
    repeated to the query heads."""
    return dispatch.local_flash_attention(q, k, v, causal=True, window=window)


def value_and_grads(fn, q, k, v, window):
    with jax.default_matmul_precision("highest"):
        return fn(q, k, v, window), jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a, window))), (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("group", [1, 7])
@pytest.mark.parametrize("window", [None, 40, 128, 1000],
                         ids=["none", "inside_a_block", "a_block_multiple",
                              "beyond_t"])
def test_the_kernel_is_the_dense_arm(group, window):
    """Interpret mode, causal: the output and all three gradients, dk and dv
    summed over the query heads of a group inside the dk/dv kernel."""
    q, k, v = qkv(group)
    out, grads = value_and_grads(flash, q, k, v, window)
    want, want_grads = value_and_grads(dense, q, k, v, window)
    assert out.shape == q.shape and grads[1].shape == k.shape
    assert float(jnp.max(jnp.abs(out - want))) < 2e-5
    for got, ref, name in zip(grads, want_grads, "qkv"):
        assert float(jnp.max(jnp.abs(got - ref))) < 2e-4, name


def test_a_window_that_hides_nothing_changes_no_bit():
    """window None with as many key as query heads builds the kernels the
    parent built (their jaxprs were held equal to the parent's when ISSUE 31
    was built: PERF.md, section 6); a window beyond T runs the same blocks
    and masks nothing, so not a bit of output or gradient may differ."""
    q, k, v = qkv(1)
    plain, plain_grads = value_and_grads(flash, q, k, v, None)
    wide, wide_grads = value_and_grads(flash, q, k, v, T)
    assert np.array_equal(plain, wide)
    for a, b in zip(plain_grads, wide_grads):
        assert np.array_equal(a, b)
    assert fa.blocks_run(T, T, True, T, BLOCK_Q, BLOCK_K) \
        == fa.blocks_run(T, T, True, None, BLOCK_Q, BLOCK_K)


@pytest.mark.parametrize("t,bq,bk,window", [
    (256, 64, 128, None), (256, 64, 128, 1), (256, 64, 128, 40),
    (256, 64, 128, 128), (256, 64, 128, 129), (256, 64, 128, 1000),
    (512, 128, 64, 100), (16384, 512, 1024, 4096)])
def test_the_blocks_run_are_those_a_plain_loop_counts(t, bq, bk, window):
    """A block runs iff it holds a pair the mask lets through; counted by
    the kernels' own condition and, here, pair by pair (by block corners:
    the visible pairs of a block, if any, include one of q's first row with
    k's last column that is at or before it, or the diagonal's)."""
    def visible(qi, ki):
        q0, k0 = qi * bq, ki * bk
        for q_ in (q0, q0 + bq - 1):
            lo = q_ - (window or t) + 1
            if max(lo, k0) <= min(q_, k0 + bk - 1):
                return True
        return False
    loop = sum(visible(qi, ki) for qi in range(t // bq)
               for ki in range(t // bk))
    assert fa.blocks_run(t, t, True, window, bq, bk) \
        == ((t // bq) * (t // bk), loop)
    if t == 16384:      # the sizes flash_attention() takes by itself
        assert fa.blocks_run(t, t, True, window) == (512, loop)
    if t == 16384:      # the cell's window layers: what its counter reads
        assert loop == 140 and window == 4096


def test_supported_learns_the_window_and_the_groups():
    shape = (1, 28, 1024, 128)
    assert fa.supported(shape, jnp.bfloat16, kv_heads=4, window=256)
    assert not fa.supported(shape, jnp.bfloat16, kv_heads=5)
    assert not fa.supported(shape, jnp.bfloat16, kv_heads=4, plain=False)
    assert not fa.supported(shape, jnp.bfloat16, window=256, causal=False)
    assert fa.supported(shape, jnp.bfloat16, kv_heads=28, plain=False)
    q, k, v = qkv(2)
    with pytest.raises(ValueError, match="window"):
        fa.mha_flash_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="grouped heads"):
        fa.mha_flash_attention(q, k, v, valid_length=jnp.array([T]))


# -- the dispatch --------------------------------------------------------------------
def test_the_flash_arm_takes_window_and_groups_and_counts_its_blocks(
        monkeypatch):
    """What a TPU process dispatches, with the kernel in interpret mode: k
    and v go to the kernel with the heads they have, the call counts once,
    and its window's blocks are counted as it is traced."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "_interpret", lambda: True)
    q, k, v = qkv(2, t=1024)
    before = dict(dispatch.dispatch_counts), dict(dispatch.window_blocks)
    out = attention(q, k, v, causal=True, window=300)
    assert dispatch.dispatch_counts["pallas_flash"] \
        == before[0]["pallas_flash"] + 1
    assert dispatch.dispatch_counts["xla_dense"] == before[0]["xla_dense"]
    grid, run = fa.blocks_run(1024, 1024, True, 300)
    # key blocks of 256, no wider than the window (ISSUE 35): 6 of the
    # square's 2 x 4 run, and the band walks all 8 (4 key blocks a row)
    assert (grid, run) == (8, 6)
    assert dispatch.window_blocks["grid"] == before[1]["grid"] + grid
    assert dispatch.window_blocks["run"] == before[1]["run"] + run
    assert dispatch.window_blocks["walked"] == before[1]["walked"] + 8
    monkeypatch.undo()
    want = attention(q, k, v, causal=True, window=300)
    assert float(jnp.max(jnp.abs(out - want))) < 1e-4


@pytest.mark.parametrize("arm", ["ring", "ulysses", "dispatch"])
def test_the_sequence_parallel_arms_refuse_a_window_by_name(arm):
    mesh = make_mesh({"sp": 2}, devices=jax.devices()[:2])
    q, k, v = qkv(1, t=64, d=16)
    call = {"ring": lambda: ring_attention_fn(q, k, v, mesh, causal=True,
                                              window=8),
            "ulysses": lambda: ulysses_attention(q, k, v, mesh, causal=True,
                                                 window=8),
            "dispatch": lambda: attention(q, k, v, mesh=mesh, causal=True,
                                          window=8)}[arm]
    with pytest.raises(ValueError, match="window=8"):
        call()


def test_rotary_by_halves_is_rotate_half():
    x = jax.random.normal(jax.random.key(3), (2, 5, 8))
    t, d = 5, 8
    freq = 1e4 ** (-np.arange(0, d, 2) / d)
    ang = np.arange(t)[:, None] * freq[None, :]
    cos, sin = np.cos(np.tile(ang, 2)), np.sin(np.tile(ang, 2))
    turned = np.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    assert np.allclose(rotary(x, 1e4, "halves"), x * cos + turned * sin,
                       atol=1e-5)
    assert not np.allclose(rotary(x, 1e4, "halves"), rotary(x, 1e4))
    with pytest.raises(ValueError, match="pairs"):
        rotary(x, 1e4, "thirds")


# -- the model against the reference ---------------------------------------------------
def toy_cfg():
    """The configuration file at its rehearsal sizes, f32 so that the
    comparison is of the equations and not of bf16's rounding."""
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearse"])
    # at a twentieth of the width, five times the published initializer's
    # spread gives attention and the router scores of the published size
    cfg["system"] = dict(cfg["system"], dtype="float32", init_sigma=0.1,
                         init_sigma_residual=0.05, init_sigma_embedding=0.1,
                         loss_chunk=32)
    cfg["sliding_window_size"] = 24
    cfg["reference_comparison"] = dict(cfg["reference_comparison"],
                                       logit_stride=4)
    return cfg


MIX = {"batch": 2, "seq_len": 64, "block_steps": 2}
OUTPUTS = ["logits", "loss", "grad_router", "grad_expert_down", "grad_k",
           "grad_q", "grad_embed", "route_choice", "route_weights",
           "attend_window"]
TOLERANCE = 2e-3    # f32 against f32 "highest"; the toy's honest error is 1e-5


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
    return got, aux, want, programs


@pytest.mark.parametrize("key", OUTPUTS)
def test_system_matches_reference(sides, key):
    got, _, want, _ = sides
    honest = want()
    assert set(got) == set(honest) == set(OUTPUTS)
    assert got[key].shape == honest[key].shape
    assert rel(got[key], honest[key]) < TOLERANCE, key


@pytest.mark.parametrize("wrong", reference.WRONG)
def test_each_wrong_variant_fails_the_tolerance(sides, wrong):
    got, _, want, programs = sides
    other = want(wrong=wrong)
    errors = {k: rel(got[k], other[k]) for k in OUTPUTS}
    assert max(errors.values()) > 10 * TOLERANCE, errors
    assert len(programs) == 1       # a traced index: one program for all


def test_the_window_layers_attention_alone_shows_a_key_too_many(sides):
    """The attention call alone, on seeded q, k, v of a window layer's
    shapes: the variants that touch the mask or the heads read far off
    there, the others not at all (at the published sizes it is the one
    output that shows one key too many in a window of 4,096)."""
    got, aux, want, _ = sides
    assert got["attend_window"].shape == (1, 14, MIX["seq_len"], 16)
    assert [a.shape[1] for a in aux["window_qkv"]] == [14, 2, 2]
    for wrong in reference.WRONG:
        error = rel(got["attend_window"], want(wrong=wrong)["attend_window"])
        if wrong in ("no_window", "window_off_by_one",
                     "kv_heads_interleaved"):
            assert error > 10 * TOLERANCE, wrong
        else:
            assert error < TOLERANCE, wrong


def test_the_model_returns_the_strided_logits_only(sides):
    got, aux, _, _ = sides
    assert got["logits"].shape == (2, MIX["seq_len"] // 4, 512)
    assert len(aux["chosen"]) == len(aux["inputs"]) == 4
    assert all(c.shape == (2 * MIX["seq_len"], 2) for c in aux["chosen"])


def test_a_bfloat16_router_fails_the_routings_own_tolerance(sides):
    got, _, want, _ = sides
    honest = rel(got["route_weights"], want()["route_weights"])
    for low in ("router", "all"):
        lowered = rel(got["route_weights"], want(low=low)["route_weights"])
        assert lowered > 5e-4 > 50 * honest, (low, lowered, honest)


def test_the_two_reference_files_are_byte_equal():
    with open(os.path.join(BENCH, "references", NAME + ".py"), "rb") as a, \
            open(os.path.join(ROOT, "tests", "references",
                              "windowed_gqa_decoder.py"), "rb") as b:
        assert a.read() == b.read()


def test_scope_names_are_the_benchmarks_literals(monkeypatch):
    monkeypatch.syspath_prepend(BENCH)      # it imports decoder_scopes
    scopes = _load(os.path.join(BENCH, "attention_scopes.py"),
                   "attention_scopes_literals")
    assert scopes.SCOPES == ATTENTION_SCOPES
    assert not set(ATTENTION_SCOPES) & set(DECODER_SCOPES)
    assert len(DECODER_SCOPES) == 8     # benchmark/decoder_scopes.py's


# -- the shares add up -------------------------------------------------------------------
def test_the_four_shares_expert_parts_add_up_to_the_uncut_layers():
    """One chip of four holds experts [0, 4) of 16; the parts that the four
    shares compute, summed, are what a layer holding all 16 computes, and
    what the reference's layer gives for the whole range: the softmax
    weights are over all chosen whoever holds them."""
    units, hidden, experts, top_k, rows = 32, 16, 16, 6, 48
    keys = jax.random.split(jax.random.key(11), 6)
    x, read = (jax.random.normal(k, (1, rows, units)) for k in keys[:2])
    gate = jax.random.normal(keys[2], (experts, units))
    w1, w3 = (0.3 * jax.random.normal(k, (experts, units, hidden))
              for k in keys[3:5])
    w2 = 0.3 * jax.random.normal(keys[5], (experts, hidden, units))

    def part(lo, hi):
        moe = DroplessMoE(units, hidden, experts, top_k,
                          held_experts=range(lo, hi), scoring="softmax",
                          activation="relu")
        moe.initialize()
        for name, value in (("gate_weight", gate), ("expert_w1", w1[lo:hi]),
                            ("expert_w3", w3[lo:hi]),
                            ("expert_w2", w2[lo:hi])):
            getattr(moe, name).set_data(np.asarray(value))
        return np.asarray(moe(nd.array(np.asarray(x)),
                              nd.array(np.asarray(read)))._data)
    whole = part(0, experts)
    shares = [part(lo, lo + 4) for lo in range(0, experts, 4)]
    assert np.allclose(sum(shares), whole, atol=1e-5)
    assert all(np.abs(s).max() > 1e-3 for s in shares)
    p = {"router": gate, "bias": jnp.zeros(experts), "w1": w1, "w3": w3,
         "w2": w2}
    hp = {"top_k": top_k}
    with jax.default_matmul_precision("highest"):
        chosen, weights = reference.route(read[0], p, hp, (0, experts))
        want = reference.experts(x[0], chosen, weights, p, (0, experts))
    assert np.allclose(whole[0], want, atol=1e-4)


# -- the head in chunks --------------------------------------------------------------------
@pytest.mark.parametrize("batch,shift", [(2, 1), (3, 2)])
def test_the_chunked_heads_loss_and_gradients_are_the_whole_heads(batch,
                                                                   shift):
    """At batch 3 and a shift of 2 the mask of the last positions reaches
    a chunk's rows of every sequence."""
    keys = jax.random.split(jax.random.key(5), 3)
    hidden = jax.random.normal(keys[0], (batch, 64, 16))
    head = jax.random.normal(keys[1], (40, 16))
    tokens = jax.random.randint(keys[2], (batch, 64), 0, 40)

    def whole(h, w):
        return decoder._next_token_loss(h, w, tokens, shift)

    def chunked(h, w):
        return decoder._chunked_next_token_loss(h, w, tokens, shift, 16, 4)
    (logits, loss), (strided, chunk_loss) = whole(hidden, head), \
        chunked(hidden, head)
    assert strided.shape == (batch, 16, 40)
    assert np.allclose(strided, logits[:, ::4], atol=1e-6)
    assert float(abs(loss - chunk_loss)) < 1e-6
    grads = jax.grad(lambda h, w: whole(h, w)[1], (0, 1))(hidden, head)
    chunk_grads = jax.grad(lambda h, w: chunked(h, w)[1], (0, 1))(hidden,
                                                                  head)
    for a, b in zip(grads, chunk_grads):
        assert np.allclose(a, b, atol=1e-6)
    with pytest.raises(ValueError, match="loss_chunk"):
        decoder._chunked_next_token_loss(hidden, head, tokens, 1, 24, 1)


# -- the cell's arithmetic -------------------------------------------------------------------
def test_flops_per_sample_is_the_issues_reckoning():
    """ISSUE 31, section 6: 34.7 T a sample, its parts as reckoned there."""
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", "extend16k.json")) as f:
        mix = json.load(f)
    t, tera = mix["seq_len"], 1e12
    assert config_mod.window_pairs(t, None) == 134225920
    assert config_mod.window_pairs(t, 4096) == 58722304
    scores = 12 * 128 * 28 * (134225920 + 3 * 58722304)
    project = 4 * 20971520 * 6 * t
    router = 4 * 2560 * 64 * 6 * t
    experts = 4 * 24576 * 3 * 2560 * 768 * 6
    head = t * 37984 * 2560 * 6
    assert scores / tera == pytest.approx(13.35, abs=0.01)
    assert project / tera == pytest.approx(8.246, abs=0.01)
    assert experts / tera == pytest.approx(3.479, abs=0.01)
    assert head / tera == pytest.approx(9.560, abs=0.01)
    total = config_mod.flops_per_sample(cfg, mix)
    assert total == scores + project + router + experts + head
    assert total / tera == pytest.approx(34.7, abs=0.05)
    assert config_mod.loss_center(cfg, mix) == pytest.approx(
        math.log(37984) + 0.512)
    # the parameters this chip holds: 656.5 M
    held = 4 * (20971520 + 2560 * 64 + 2 * 2560 + 16 * 3 * 2560 * 768) \
        + 2 * 37984 * 2560 + 2560
    assert held / 1e6 == pytest.approx(656.5, abs=0.1)
    net_cfg = config_mod.model_config(cfg)
    assert [(a["rope_theta"], a["window"]) for a in net_cfg["attention"]] \
        == [(None, None)] + [(1.5e6, 4096)] * 3
