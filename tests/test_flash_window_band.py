"""The flash kernels' grid under a window (ISSUE 35): the band the window
allows at key blocks no wider than the window, in interpret mode against the
dense arm at the tolerances of tests/test_windowed_gqa_decoder.py and
tests/test_gated_mixed_decoder.py; the band's extents and the steps walked
against a pair-by-pair brute force; the grids a call lowers to; the block
rule; the dispatch's `walked` count and the benchmark's reader of it.
"""
import importlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_mx.kernels import flash_attention as fa

dispatch = importlib.import_module("tpu_mx.parallel.ring_attention")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
D = 64


def qkv(group, t, tk=None, kv_heads=2, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    shape = lambda h, n: (1, h, n, D)
    return (jax.random.normal(keys[0], shape(kv_heads * group, t)),
            jax.random.normal(keys[1], shape(kv_heads, tk or t)),
            jax.random.normal(keys[2], shape(kv_heads, tk or t)))


def dense(q, k, v, window, valid_length=None):
    return dispatch.local_flash_attention(q, k, v, causal=True, window=window,
                                          valid_length=valid_length)


def value_and_grads(fn, q, k, v):
    with jax.default_matmul_precision("highest"):
        return fn(q, k, v), jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), (0, 1, 2))(q, k, v)


def agree(got, want, grad_limit=3e-4):
    (out, grads), (ref, ref_grads) = got, want
    assert out.shape == ref.shape
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5
    for g, r, name in zip(grads, ref_grads, "qkv"):
        assert g.shape == r.shape
        assert float(jnp.max(jnp.abs(g - r))) < grad_limit, name


# -- the banded kernels against the dense arm ---------------------------------
@pytest.mark.parametrize("group,window,bq,bk", [
    (1, 48, 64, 128), (6, 128, 64, 128), (7, 200, 64, 128),
    (9, 300, 128, 64), (1, 129, 128, 128), (6, 33, 64, 64),
    (7, 512, 64, 128), (9, 1000, 64, 128)],
    ids=["below_the_key_block", "the_key_block", "above_it_and_no_multiple",
         "above_it_q_blocks_the_wider", "one_past_a_block", "not_of_128",
         "the_whole_sequence", "beyond_the_sequence"])
def test_the_band_is_the_dense_arm(group, window, bq, bk):
    """Output, dq, dk and dv (dk and dv summed over the group's query heads
    in the dk/dv kernel, whose innermost axis is G * nbq) for a window
    below, at and above the key block, no multiple of 128, and at or beyond
    T, where the band is the whole triangle; groups of 1, 6, 7 and 9."""
    q, k, v = qkv(group, 512)
    flash = lambda q, k, v: fa.mha_flash_attention(
        q, k, v, causal=True, window=window, block_q=bq, block_k=bk)
    agree(value_and_grads(flash, q, k, v),
          value_and_grads(lambda *a: dense(*a, window), q, k, v))


@pytest.mark.parametrize("t,tk,window", [(256, 512, 100), (512, 256, 100),
                                         (512, 256, 300)],
                         ids=["keys_beyond_the_queries",
                              "queries_that_see_no_key",
                              "queries_beyond_the_keys"])
def test_the_band_with_other_keys_than_queries(t, tk, window):
    """T != Tk: key blocks no query sees into (the dk/dv band's first block
    past the last query block) and query blocks whose windows lie past the
    last key (the forward band's): skipped, their outputs and gradients
    zero as on the square grid."""
    q, k, v = qkv(7, t, tk, kv_heads=1)
    flash = lambda q, k, v: fa.mha_flash_attention(
        q, k, v, causal=True, window=window, block_q=64, block_k=128)
    # query i sees a key iff i - window < Tk; a q block none of whose rows
    # sees one is never run and written as zeros (the dense arm's softmax
    # of nothing is no reference)
    rows = slice(0, min(t, tk + window - 1))
    got, want = (value_and_grads(
        lambda *a: fn(*a)[:, :, rows], q, k, v) for fn in (
            flash, lambda *a: dense(*a, window)))
    agree(got, want)
    unseen = -(-rows.stop // 64) * 64
    assert not np.any(np.asarray(flash(q, k, v)[:, :, unseen:]))


@pytest.mark.parametrize("window", [48, 200])
def test_a_padding_mask_under_the_band(window):
    """G 1 with valid lengths: the band's key block index, not its step,
    decides what lies beyond the valid keys."""
    q, k, v = qkv(1, 512, kv_heads=3)
    valid = jnp.array([300])
    flash = lambda q, k, v: fa.mha_flash_attention(
        q, k, v, causal=True, window=window, valid_length=valid,
        block_q=64, block_k=128)
    rows = slice(0, 300)        # a query past the valid keys sees none
    got, want = (value_and_grads(
        lambda *a: fn(*a)[:, :, rows], q, k, v) for fn in (
            flash, lambda *a: dense(*a, window, valid)))
    agree(got, want)


def test_the_default_blocks_under_a_window_are_the_dense_arm():
    """No block sizes given: T 1,024 under W 256 runs in 512 x 256, the band
    two key blocks of a row's four."""
    q, k, v = qkv(6, 1024, kv_heads=1)
    assert fa._blocks(1024, 1024, window=256) == (512, 256)
    flash = lambda q, k, v: fa.mha_flash_attention(q, k, v, causal=True,
                                                   window=256)
    agree(value_and_grads(flash, q, k, v),
          value_and_grads(lambda *a: dense(*a, 256), q, k, v))


# -- the band's extents by brute force ----------------------------------------
def brute_force_band(t, tk, bq, bk, window):
    """(nb, nbq, blocks that run) pair by pair: the blocks of the square
    that hold a pair the mask lets through, the widest stretch of them in a
    row from the first key block that holds a key the row's FIRST query
    could see (where the band starts), and the tallest in a column from the
    first query block at or below the diagonal."""
    qpos, kpos = np.arange(t)[:, None], np.arange(tk)[None, :]
    seen = (kpos <= qpos) & (qpos - kpos < window)
    blocks = seen.reshape(t // bq, bq, tk // bk, bk).any((1, 3))
    nb = nbq = 1
    for i, row in enumerate(blocks):
        if row.any():
            first = max(i * bq - window + 1, 0) // bk
            nb = max(nb, int(np.flatnonzero(row)[-1]) - first + 1)
    for j, col in enumerate(blocks.T):
        if col.any():
            nbq = max(nbq, int(np.flatnonzero(col)[-1]) - (j * bk) // bq + 1)
    return nb, nbq, int(blocks.sum())


@pytest.mark.parametrize("t,tk,bq,bk,window", [
    (256, 256, 64, 128, 48), (256, 256, 64, 128, 128),
    (256, 256, 64, 128, 200), (512, 512, 128, 64, 30),
    (512, 512, 128, 64, 300), (1024, 1024, 128, 256, 129),
    (256, 512, 64, 128, 100), (512, 256, 64, 128, 100),
    (512, 512, 64, 64, 512), (512, 512, 64, 128, 5000),
    (8192, 8192, 512, 1024, 512), (8192, 8192, 512, 512, 512),
    (8192, 8192, 256, 256, 512), (16384, 16384, 512, 1024, 4096)])
def test_the_bands_extents_are_those_a_plain_count_gives(t, tk, bq, bk,
                                                         window):
    nb, nbq, run = brute_force_band(t, tk, bq, bk, window)
    assert fa._band_steps(t, tk, window, bq, bk) == (nb, nbq)
    assert fa.steps_walked(t, tk, window, bq, bk) == (t // bq) * nb
    assert fa.blocks_run(t, tk, True, window, bq, bk) \
        == ((t // bq) * (tk // bk), run)
    # every block that runs lies on the band of its row and of its column
    assert run <= (t // bq) * nb and run <= (tk // bk) * nbq


def test_the_issues_table_of_steps():
    """ISSUE 35's arithmetic: steps walked by forward/dq and by dk/dv a
    query head, and the blocks that run."""
    def walked(t, w, bq, bk):
        nb, nbq = fa._band_steps(t, t, w, bq, bk)
        return (t // bq) * nb, (t // bk) * nbq, \
            fa.blocks_run(t, t, True, w, bq, bk)[1]
    assert walked(8192, 512, 512, 1024) == (32, 24, 23)
    assert walked(8192, 512, 512, 512) == (32, 32, 31)
    assert walked(8192, 512, 256, 256) == (96, 96, 93)
    assert walked(16384, 4096, 512, 1024) == (160, 160, 140)
    # a bias keeps the square under a window; so does no window at all
    assert fa.steps_walked(8192, 8192, 512, biased=True) == 256
    assert fa.steps_walked(8192, 8192) == 16 * 8


# -- the grids a call lowers to -----------------------------------------------
def grids(window, group=1, t=512, tk=None, bias=False, **blocks):
    """The grids of the pallas_calls in the jaxpr of a call's gradient, in
    the order forward, dq, dk/dv."""
    q, k, v = qkv(group, t, tk)
    b = jnp.zeros((1, q.shape[1], t, tk or t)) if bias else None

    def loss(q, k, v):
        return fa.mha_flash_attention(q, k, v, causal=True, window=window,
                                      bias=b, **blocks).sum()
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v).jaxpr)
    return found


@pytest.mark.parametrize("group", [1, 6])
def test_a_call_without_a_window_keeps_the_squares_grids(group):
    """(BH, T/bq, Tk/bk) twice and (BH_kv, Tk/bk, G * T/bq), as before."""
    assert grids(None, group, block_q=64, block_k=128) == [
        (2 * group, 8, 4), (2 * group, 8, 4), (2, 4, group * 8)]


def test_a_windowed_call_walks_the_band_and_a_biased_one_the_square():
    assert fa._band_steps(512, 512, 100, 64, 128) == (2, 4)
    assert grids(100, 6, block_q=64, block_k=128) == [
        (12, 8, 2), (12, 8, 2), (2, 4, 6 * 4)]
    assert grids(100, 1, bias=True, block_q=64, block_k=128) == [
        (2, 8, 4), (2, 8, 4), (2, 4, 8)]
    # T != Tk: the extents follow both lengths
    assert grids(100, 7, t=256, tk=512, block_q=64, block_k=128) == [
        (14, 4, 2), (14, 4, 2), (2, 4, 7 * 4)]


# -- the block rule -----------------------------------------------------------
@pytest.mark.parametrize("t,window,blocks", [
    (8192, 512, (512, 512)), (16384, 4096, (512, 1024)),
    (8192, None, (512, 1024)), (8192, 1024, (512, 1024)),
    (8192, 1000, (512, 512)), (8192, 300, (512, 256)),
    (8192, 128, (512, 128)), (8192, 1, (512, 128)),
    (640, 512, (128, 128)), (128, 64, (128, 128))])
def test_the_key_block_is_no_wider_than_the_window(t, window, blocks):
    """The largest power of two <= min(1,024, W) that divides Tk, never
    under 128; the query block as without a window; the caller's sizes
    before either."""
    assert fa._blocks(t, t, window=window) == blocks
    assert fa._blocks(t, t, 256, 1024, window=window) == (
        min(256, t), min(1024, t))


# -- the counter and its reader -----------------------------------------------
def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name,
        os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_readers_share_of_the_grid_that_runs(monkeypatch):
    share = reader("attn_grid_run_share")
    monkeypatch.setattr(dispatch, "window_blocks",
                        {"grid": 0, "run": 0, "walked": 0})
    assert share.read({}) is None           # no windowed flash call
    # the two cells' window layers, three of them each
    monkeypatch.setattr(dispatch, "window_blocks",
                        {"grid": 768, "run": 93, "walked": 96})
    assert share.read({}) == pytest.approx(96.875)
    assert reader("attn_blocks_run_share").read({}) \
        == pytest.approx(12.11, abs=0.005)
    monkeypatch.setattr(dispatch, "window_blocks",
                        {"grid": 1536, "run": 420, "walked": 480})
    assert share.read({}) == pytest.approx(87.5)
    assert reader("attn_blocks_run_share").read({}) \
        == pytest.approx(27.34375)
    # the parent's program: a counter without the kind, or none at all
    monkeypatch.setattr(dispatch, "window_blocks", {"grid": 128, "run": 23})
    assert share.read({}) is None
    monkeypatch.delattr(dispatch, "window_blocks")
    assert share.read({}) is None


def test_the_readers_entry_names_the_two_window_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert next(m for m in bench["per_layer"]
                if m["name"] == "attn_grid_run_share") == {
        "name": "attn_grid_run_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "attention dispatch",
        "moves": "samples_per_s",
        "workloads": ["smallthinker-21ba3b.extend16k",
                      "laguna-s-2.1.pretrain8k"]}


def test_the_dispatch_counts_the_steps_the_cells_window_layers_walk(
        monkeypatch):
    """What a TPU process counts as it traces the 8k cell's window layer (9
    query heads a key/value head, one key/value head here, nothing run):
    grid and run at the blocks the call takes, and the band."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "_interpret", lambda: True)
    q = jax.ShapeDtypeStruct((1, 9, 8192, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 1, 8192, 128), jnp.bfloat16)
    before = dict(dispatch.window_blocks)
    jax.eval_shape(lambda q, k, v: dispatch.attention(
        q, k, v, causal=True, window=512), q, k, k)
    assert {kind: n - before[kind]
            for kind, n in dispatch.window_blocks.items()} \
        == {"grid": 256, "run": 31, "walked": 32}
