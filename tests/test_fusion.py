"""Lazy pointwise-fusion engine tests (tpu_mx/fusion.py + engine.bulk).

Equivalence contract: a fused segment executes the same primitive
sequence as eager dispatch, compiled as one XLA program.  Forward AND
backward are asserted BIT-IDENTICAL with eager for every covered chain
here, but for the one documented numerics divergence: XLA contracts a
multiply that feeds an add into an FMA inside a fused loop (excess
precision, the more accurate result).  Where a chain or its derivative
holds one (jax 0.9 writes tanh's as 1 - y*y), the fused result is held
bit for bit to the same chain under ONE jax.jit, which is what a segment
is, and to eager within a unit in the last place of the gradient's scale.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpu_mx as mx
from tpu_mx import autograd, engine, fusion, nd


@pytest.fixture(autouse=True)
def _fusion_stats():
    fusion.reset_stats()
    yield
    # no segment may leak past a test: every barrier design guarantees a
    # flush before observable reads, and tests end with reads
    assert fusion.pending_ops() == 0


def _x(shape=(8, 8), lo=-2.0, hi=2.0):
    n = int(np.prod(shape))
    return nd.array(np.linspace(lo, hi, n).reshape(shape), dtype="float32")


# chains with no multiply->add adjacency: bit-identical under fusion
CHAINS = {
    "unary": lambda v: nd.tanh(nd.sin(nd.exp(v * 0.25))),
    "scalar_mix": lambda v: (nd.sqrt(nd.abs(v / 1.7)) * 3).clip(0.05, 1.5),
    "broadcast": lambda v: nd.cos(
        v * nd.array(np.linspace(0.1, 1.1, 8), dtype="float32")),
    "cast": lambda v: nd.cast(nd.cast(nd.relu(v), "float16"), "float32"),
    "compare_where": lambda v: nd.where(v > 0.0, nd.sigmoid(v), -v) / 2.0,
    "reduce_tail": lambda v: nd.square(v).mean(axis=1) / 1.3,
    "softmax": lambda v: nd.log_softmax(v * 0.5, axis=-1),
    "sum_all": lambda v: (nd.exp(v * 0.1) / 2.5).sum(),
}


@pytest.mark.parametrize("name", sorted(CHAINS))
def test_fused_forward_bit_identical(name):
    chain = CHAINS[name]
    ref = chain(_x()).asnumpy()
    with engine.bulk(64):
        out = chain(_x()).asnumpy()
    np.testing.assert_array_equal(ref, out)
    assert engine.bulk_stats()["segments_flushed"] >= 1


# the chains whose DERIVATIVE holds a multiply->add, as jax.numpy
# composites of (x, scalars): fusion passes scalars as weak-typed arguments
ONE_JIT_TWINS = {
    "unary": (lambda v, s: jnp.tanh(jnp.sin(jnp.exp(v * s))), (0.25,)),
}


def _assert_backward_of_one_program(fused, eager, composite, scalars):
    """`fused` is the gradient a flushed segment gave: bit for bit what ONE
    jitted pullback of the same composite gives (fusion.flush's `pullback`),
    and per-op eager's within one unit in the last place of its scale."""
    def pullback(xv, *s):
        out, vjp = jax.vjp(lambda v: composite(v, *s), xv)
        return vjp(jnp.ones_like(out))[0]

    truth = jax.jit(pullback)(_x()._data, *map(jnp.asarray, scalars))
    np.testing.assert_array_equal(fused, np.asarray(truth))
    np.testing.assert_allclose(
        eager, fused, rtol=0,
        atol=np.finfo(np.float32).eps * max(1.0, np.abs(fused).max()))


@pytest.mark.parametrize("name", sorted(CHAINS))
def test_fused_backward_bit_identical(name):
    chain = CHAINS[name]
    xe, xf = _x(), _x()
    xe.attach_grad()
    xf.attach_grad()
    with autograd.record():
        le = chain(xe).sum()
    le.backward()
    with autograd.record():
        with engine.bulk(64):
            lf = chain(xf).sum()
    lf.backward()
    if name in ONE_JIT_TWINS:
        _assert_backward_of_one_program(
            xf.grad.asnumpy(), xe.grad.asnumpy(), *ONE_JIT_TWINS[name])
    else:
        np.testing.assert_array_equal(xe.grad.asnumpy(), xf.grad.asnumpy())


def test_fma_chain_matches_jit_ground_truth():
    """multiply->add chains: XLA contracts into FMA inside a fused loop.
    The fused result must equal jax.jit of the same composite exactly
    (one-program semantics, same as hybridize) and eager to ~1 ulp."""
    x = _x((16, 16))
    b = nd.array(np.linspace(0.1, 1.1, 16), dtype="float32")

    def chain(v):
        y = v
        for _ in range(3):
            y = y * 1.0009 + b
            y = nd.tanh(y)
        return y

    eager = chain(x).asnumpy()
    with engine.bulk(64):
        fused = chain(x).asnumpy()

    scal = jnp.asarray(1.0009)  # fusion passes scalars as weak-typed args

    def composite(xv, bv, s):
        y = xv
        for _ in range(3):
            y = jnp.tanh(y * s + bv)
        return y

    truth = np.asarray(jax.jit(composite)(x._data, b._data, scal))
    np.testing.assert_array_equal(fused, truth)
    # 1-ulp-per-contraction-site excess precision, compounded through the
    # tanh chain; atol covers the zero-crossing cells
    np.testing.assert_allclose(eager, fused, rtol=1e-5, atol=1e-6)


def test_cache_hit_on_second_call():
    x = _x()
    with engine.bulk(64):
        a = nd.tanh(nd.sin(x) * 0.5).asnumpy()
    misses = fusion.stats["cache_misses"]
    with engine.bulk(64):
        b = nd.tanh(nd.sin(x) * 0.5).asnumpy()
    assert fusion.stats["cache_misses"] == misses
    assert fusion.stats["cache_hits"] >= 1
    np.testing.assert_array_equal(a, b)


def test_cache_shared_across_scalar_values():
    """Scalars ride as runtime args, so a schedule-style changing scalar
    reuses ONE compiled program (and stays bit-identical to eager)."""
    x = _x()
    with engine.bulk(64):
        nd.sin(x * 0.5).asnumpy()
    misses = fusion.stats["cache_misses"]
    with engine.bulk(64):
        out = nd.sin(x * 0.25).asnumpy()
    assert fusion.stats["cache_misses"] == misses
    np.testing.assert_array_equal(out, nd.sin(x * 0.25).asnumpy())


def test_flush_barrier_asnumpy():
    x = _x()
    with engine.bulk(64):
        y = nd.exp(x)
        assert y._lazy is not None and fusion.pending_ops() == 1
        val = y.asnumpy()             # read barrier
        assert y._lazy is None and fusion.pending_ops() == 0
    np.testing.assert_array_equal(val, nd.exp(x).asnumpy())


def test_flush_barrier_wait_to_read():
    x = _x()
    with engine.bulk(64):
        y = nd.sqrt(nd.abs(x))
        assert y._lazy is not None
        y.wait_to_read()
        assert y._lazy is None


def test_flush_barrier_nonfusible_consumer():
    x = _x()
    with engine.bulk(64):
        y = nd.relu(x)
        assert y._lazy is not None
        z = nd.dot(y, y)              # matmul is not in the fusible table
        assert y._lazy is None        # consumer realized the input
    ref = nd.dot(nd.relu(x), nd.relu(x))
    np.testing.assert_array_equal(z.asnumpy(), ref.asnumpy())


def test_flush_barrier_scope_exit():
    x = _x()
    with engine.bulk(64):
        y = nd.sin(x)
        assert y._lazy is not None
    assert y._lazy is None            # scope exit flushed
    assert fusion.stats["flush_reasons"].get("scope_exit", 0) >= 1
    np.testing.assert_array_equal(y.asnumpy(), nd.sin(x).asnumpy())


def test_flush_barrier_bulk_size():
    x = _x()
    with engine.bulk(4):
        y = x
        for _ in range(12):
            y = nd.sin(y)
        out = y.asnumpy()
    assert fusion.stats["flush_reasons"].get("bulk_size", 0) >= 3
    ref = x
    for _ in range(12):
        ref = nd.sin(ref)
    np.testing.assert_array_equal(out, ref.asnumpy())


def test_flush_barrier_backward():
    x = _x()
    x.attach_grad()
    with autograd.record():
        with engine.bulk(64):
            y = nd.tanh(x) * 2.0
            y.backward()              # backward() flushes the segment
    xe = _x()
    xe.attach_grad()
    with autograd.record():
        ye = nd.tanh(xe) * 2.0
    ye.backward()
    _assert_backward_of_one_program(
        x.grad.asnumpy(), xe.grad.asnumpy(),
        lambda v, s: jnp.tanh(v) * s, (2.0,))


def test_lazy_metadata_does_not_flush():
    x = _x()
    with engine.bulk(64):
        y = nd.sin(x).sum(axis=0)
        assert y.shape == (8,)
        assert y.dtype == np.float32
        assert y.ndim == 1 and y.size == 8
        assert y._lazy is not None    # shape/dtype answered from avals
        y.asnumpy()


def test_mixed_fused_and_eager_autograd():
    """A fused segment in the middle of an eagerly-taped graph: gradients
    route through the segment's single tape node bit-identically."""
    def run(bulked):
        x = _x()
        x.attach_grad()
        with autograd.record():
            h = nd.dot(x, x)          # eager (non-fusible) producer
            if bulked:
                with engine.bulk(64):
                    h = nd.tanh(h * 0.01)
                    h = h + 0.5
            else:
                h = nd.tanh(h * 0.01)
                h = h + 0.5
            loss = nd.dot(h, h).sum() # eager consumer
        loss.backward()
        return x.grad.asnumpy()

    np.testing.assert_array_equal(run(False), run(True))


def test_grad_req_add_accumulates():
    def run(bulked):
        x = _x()
        x.attach_grad(grad_req="add")
        for _ in range(2):
            with autograd.record():
                if bulked:
                    with engine.bulk(64):
                        loss = (nd.sigmoid(x) * 3.0).sum()
                else:
                    loss = (nd.sigmoid(x) * 3.0).sum()
            loss.backward()
        return x.grad.asnumpy()

    np.testing.assert_array_equal(run(False), run(True))


def test_blockgrad_inside_segment():
    def run(bulked):
        x = _x()
        x.attach_grad()
        with autograd.record():
            if bulked:
                with engine.bulk(64):
                    loss = (nd.BlockGrad(nd.exp(x)) * nd.sin(x)).sum()
            else:
                loss = (nd.BlockGrad(nd.exp(x)) * nd.sin(x)).sum()
        loss.backward()
        return x.grad.asnumpy()

    np.testing.assert_array_equal(run(False), run(True))


def test_integer_chain_not_taped():
    x = _x()
    x.attach_grad()
    with autograd.record():
        with engine.bulk(64):
            idx = nd.cast(nd.abs(x) * 2.0, "int32")
            s = nd.sin(x).sum()
    assert idx._tape_node is None     # all-int output: unrecorded, eager parity
    assert idx.dtype == np.int32
    s.backward()
    np.testing.assert_array_equal(
        x.grad.asnumpy(), nd.cos(_x()).asnumpy())


def test_dead_intermediates_never_materialize():
    """Only live handles become program outputs; a fully-dead segment is
    dropped without executing."""
    x = _x()
    with engine.bulk(64):
        nd.exp(x)                     # result discarded immediately
        nd.sin(x)
    assert fusion.stats["segments_dead"] >= 1
    assert fusion.stats["segments_flushed"] == 0


def test_inplace_rebind_is_barrier():
    """Augmented assignment keeps strict eager rebind semantics (the
    in-place target realizes immediately) and stays correct in a scope."""
    x = _x()
    ref = x.copy()
    ref += 2.0
    ref = nd.sin(ref).asnumpy()
    with engine.bulk(64):
        y = x.copy()
        y += 2.0
        assert y._lazy is None
        out = nd.sin(y).asnumpy()
    np.testing.assert_array_equal(ref, out)


def test_out_kwarg_realizes():
    x = _x()
    with engine.bulk(64):
        tgt = nd.zeros((8, 8))
        res = nd.exp(x, out=tgt)
        assert res is tgt and tgt._lazy is None
    np.testing.assert_array_equal(tgt.asnumpy(), nd.exp(x).asnumpy())


def test_waitall_flushes():
    x = _x()
    with engine.bulk(64):
        y = nd.sin(x)
        assert y._lazy is not None
        nd.waitall()
        assert y._lazy is None


def test_env_fusion_off_restores_eager(monkeypatch):
    monkeypatch.setenv("TPUMX_FUSION", "0")
    x = _x()
    with engine.bulk(64):
        y = nd.sin(x)
        assert y._lazy is None        # eager exactly: no laziness at all
        assert fusion.stats["ops_fused"] == 0
    np.testing.assert_array_equal(y.asnumpy(), nd.sin(x).asnumpy())


def test_env_fusion_always_on(monkeypatch):
    monkeypatch.setenv("TPUMX_FUSION", "1")
    x = _x()
    y = nd.tanh(nd.sin(x))            # no bulk scope needed
    assert y._lazy is not None
    out = y.asnumpy()
    monkeypatch.delenv("TPUMX_FUSION")
    np.testing.assert_array_equal(out, nd.tanh(nd.sin(x)).asnumpy())


def test_bulk_size_one_disables():
    x = _x()
    with engine.bulk(1):
        y = nd.sin(x)
        assert y._lazy is None


def test_bulk_size_one_overrides_always_on(monkeypatch):
    """bulk(size<=1) is the reference's op-by-op escape hatch; it must
    win over TPUMX_FUSION=1 (review finding r6)."""
    monkeypatch.setenv("TPUMX_FUSION", "1")
    x = _x()
    with engine.bulk(1):
        y = nd.sin(x)
        assert y._lazy is None
    z = nd.sin(x)
    assert z._lazy is not None        # always-on resumes outside
    z.asnumpy()


def test_nondiff_op_blocks_gradients_like_eager():
    """A nondiff op (sgd_update, zeros_like...) inside a fused segment
    must stay a gradient DEAD END exactly as eager leaves it unrecorded
    (review finding r6: the segment vjp used to differentiate through)."""
    def run(bulked):
        w = _x()
        w.attach_grad()
        with autograd.record():
            if bulked:
                with engine.bulk(64):
                    new_w = nd.sgd_update(w, w * 0.1, lr=0.5)
                    loss = (new_w * nd.sin(w)).sum()
            else:
                new_w = nd.sgd_update(w, w * 0.1, lr=0.5)
                loss = (new_w * nd.sin(w)).sum()
        loss.backward()
        return w.grad.asnumpy()

    np.testing.assert_array_equal(run(False), run(True))


def test_nondiff_head_does_not_zero_leaf_grads():
    """backward() from a head that reaches a tracked leaf only through a
    nondiff fused node must leave the leaf's grad untouched (eager finds
    no tape path; a taped nondiff output would overwrite with zeros)."""
    def run(bulked):
        x = _x()
        x.attach_grad()
        with autograd.record():
            seed_loss = nd.sin(x).sum()
        seed_loss.backward()          # populate x.grad
        with autograd.record():
            if bulked:
                with engine.bulk(64):
                    head = nd.zeros_like(nd.exp(x)).sum()
            else:
                head = nd.zeros_like(nd.exp(x)).sum()
        head.backward()
        return x.grad.asnumpy()

    np.testing.assert_array_equal(run(False), run(True))
    assert np.abs(run(True)).max() > 0  # the seeded grad survived


def test_shared_buffer_handles_get_separate_grads():
    """detach() shares the underlying jax.Array; both handles must still
    receive their own cotangents through a fused segment (review finding
    r6: buffer-id dedup starved the second handle)."""
    def run(bulked):
        a = _x()
        d = a.detach()                # same jax.Array underneath
        a.attach_grad()
        d.attach_grad()
        with autograd.record():
            if bulked:
                with engine.bulk(64):
                    loss = (nd.sin(a) * nd.exp(d)).sum()
            else:
                loss = (nd.sin(a) * nd.exp(d)).sum()
        loss.backward()
        return a.grad.asnumpy(), d.grad.asnumpy()

    ea, ed = run(False)
    fa, fd = run(True)
    np.testing.assert_array_equal(ea, fa)
    np.testing.assert_array_equal(ed, fd)


def test_bulk_restores_size():
    prev = engine.set_bulk_size(7)
    try:
        with engine.bulk(31):
            pass
        assert engine.set_bulk_size(7) == 7
    finally:
        engine.set_bulk_size(prev)


def test_deferred_error_names_segment():
    x = _x((4, 4))
    b = nd.array(np.zeros((5,), np.float32))
    with pytest.raises(Exception, match="fused op segment"):
        with engine.bulk(64):
            y = nd.sin(x) + b         # invalid broadcast, surfaces at flush
            y.asnumpy()


def test_record_scope_is_tape_boundary():
    """Ops issued outside record() must not be taped even when their
    segment would otherwise flush inside the recording scope."""
    x = _x()
    x.attach_grad()
    with engine.bulk(64):
        pre = nd.sin(x)               # issued while NOT recording
        with autograd.record():       # boundary flushes the segment
            assert pre._lazy is None
            loss = (pre * nd.exp(x)).sum()
        loss.backward()
    xe = _x()
    xe.attach_grad()
    pre_e = nd.sin(xe)
    with autograd.record():
        loss_e = (pre_e * nd.exp(xe)).sum()
    loss_e.backward()
    np.testing.assert_array_equal(x.grad.asnumpy(), xe.grad.asnumpy())


def test_sgd_update_fuses_parameter_sweep():
    """The imperative optimizer path: a bulk() around a parameter-update
    sweep bulks the fusible sgd_update chains.  The update core is an
    FMA-bearing chain (wd*w feeds an add), so the contract is the
    contraction tolerance, not bit-identity."""
    rng = np.random.RandomState(0)
    ws = [nd.array(rng.rand(4, 4).astype(np.float32)) for _ in range(3)]
    gs = [nd.array(rng.rand(4, 4).astype(np.float32)) for _ in range(3)]
    refs = [mx.nd.sgd_update(w.copy(), g, lr=0.1, wd=0.01).asnumpy()
            for w, g in zip(ws, gs)]
    with engine.bulk(64):
        outs = [mx.nd.sgd_update(w.copy(), g, lr=0.1, wd=0.01)
                for w, g in zip(ws, gs)]
        assert fusion.stats["ops_fused"] >= 3
        outs = [o.asnumpy() for o in outs]
    for r, o in zip(refs, outs):
        np.testing.assert_allclose(r, o, rtol=1e-6, atol=1e-7)


@pytest.mark.slow
def test_fused_speedup_on_pointwise_chain():
    """Acceptance bar: >= 1.5x on a >= 32-op elementwise chain after
    cache warm-up (dispatch-overhead regime), on whatever host runs the
    tests: a tripwire at a lower threshold so host noise can't flake it,
    not a measurement (nothing has measured fusion on the chip)."""
    import time
    x = nd.array(np.random.RandomState(0).rand(64, 64).astype(np.float32))

    def chain32(v):
        y = v
        for _ in range(8):
            y = nd.sin(y)
            y = y * 1.0009
            y = y + 0.1
            y = nd.tanh(y)
        return y

    chain32(x).wait_to_read()
    with engine.bulk(64):
        chain32(x).wait_to_read()     # warm the fusion cache
    n = 30
    best_e = min(_timed(chain32, x, n, None) for _ in range(3))
    best_f = min(_timed(chain32, x, n, 64) for _ in range(3))
    assert best_e / best_f >= 1.3, \
        f"fused {best_f:.4f}s not faster than eager {best_e:.4f}s"


def _timed(chain, x, n, bulk_size):
    import time
    t0 = time.perf_counter()
    for _ in range(n):
        if bulk_size:
            with engine.bulk(bulk_size):
                chain(x).wait_to_read()
        else:
            chain(x).wait_to_read()
    return time.perf_counter() - t0


def test_scalar_spelling_does_not_collide_in_chain_cache():
    """clip(x, 0, 1) and clip(x, 0.0, 1.0) compare equal as Python values
    but bake DIFFERENT trace constants (int vs weak-float promotion) —
    the chain cache must key them apart, or the float-spelled call
    replays the int program and returns the wrong dtype vs eager."""
    xi = nd.array(np.arange(-2, 3, dtype=np.int32))
    with engine.bulk(4):
        a = nd.clip(xi, 0, 1)
        a.wait_to_read()
    with engine.bulk(4):
        b = nd.clip(xi, 0.0, 1.0)
        b.wait_to_read()
    eager_int = nd.clip(xi, 0, 1)      # no bulk scope: plain eager
    eager_float = nd.clip(xi, 0.0, 1.0)
    assert a.dtype == eager_int.dtype, (a.dtype, eager_int.dtype)
    assert b.dtype == eager_float.dtype, (b.dtype, eager_float.dtype)
    np.testing.assert_array_equal(a.asnumpy(), eager_int.asnumpy())
    np.testing.assert_array_equal(b.asnumpy(), eager_float.asnumpy())
