"""The flash kernels keep a row's statistics as columns of the tile (ISSUE 38).

Running maximum, running sum and the rescaling factor are (block_q, 128)
tiles with every lane alike from the scratch and back to it; `lse` and
`delta` enter the backward kernels as (block_q, 1) columns.  No kernel body
forms a (block_q,) vector: on the chip each such value was broadcast over
the lanes again through the cross-lane unit, 256 permutes a forward step at
512 query rows (docs/performance.md, "The rows' layout").

(a) guards the bodies' jaxprs, (b) holds forward and gradients to the dense
arm where the lane handling differs (head of 64 / 128 / 256, key blocks of
128 / 512 / 1,024 and under 128), (c) holds every shape that decides memory.
All in interpret mode; the chip's side is chip_smoke.py phase D.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_mx.kernels import flash_attention as fa


def dense_attention(q, k, v, causal, window=None):
    """(BH, T, D) reference; k, v may have BH / G rows."""
    group = q.shape[0] // k.shape[0]
    k, v = (jnp.repeat(x, group, axis=0) for x in (k, v))
    s = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(q.shape[-1])
    i, j = jnp.arange(q.shape[1])[:, None], jnp.arange(k.shape[1])[None, :]
    if causal:
        s = jnp.where(i >= j, s, -jnp.inf)
    if window is not None:
        s = jnp.where(i - j < window, s, -jnp.inf)
    return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), v)


def qkv(bh, bh_kv, t, d, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(key, (rows, t, d), jnp.float32)
            for key, rows in zip(keys, (bh, bh_kv, bh_kv))]


# ---------------------------------------------------------------------------
# (a) no rank-1 floating-point value in a kernel body
# ---------------------------------------------------------------------------
def _subjaxprs(eqn):
    for value in eqn.params.values():
        for item in value if isinstance(value, (list, tuple)) else (value,):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                yield inner


def kernel_bodies(jaxpr, found=None):
    """name -> jaxpr of every pallas_call's kernel body under `jaxpr`."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            body = eqn.params["jaxpr"]
            found[body.debug_info.func_name] = body
        else:
            for inner in _subjaxprs(eqn):
                kernel_bodies(inner, found)
    return found


def row_vectors(jaxpr):
    """Every rank-1 floating-point value of a kernel body, the branches of
    its `pl.when`s included, as strings.  jax spells a reduction that keeps
    its dimension as `reduce_*` followed by `broadcast_in_dim` to (rows, 1):
    that one intermediate, read by nothing else, is the reduction itself
    (Mosaic gives it the column's layout) and is not reported."""
    users = {}
    for eqn in jaxpr.eqns:
        for var in eqn.invars:
            if hasattr(var, "count"):
                users.setdefault(var, []).append(eqn)
    bad = []
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            aval = var.aval
            if getattr(aval, "ndim", 0) != 1 or \
                    not jnp.issubdtype(aval.dtype, jnp.floating):
                continue
            readers = users.get(var, ())
            kept = bool(readers) and \
                eqn.primitive.name.startswith("reduce_") and all(
                    reader.primitive.name == "broadcast_in_dim"
                    and reader.params["shape"] == (aval.shape[0], 1)
                    for reader in readers)
            if not kept:
                bad.append(f"{eqn.primitive.name} -> {aval.str_short()}")
        for inner in _subjaxprs(eqn):
            bad += row_vectors(inner)
    return bad


def traced_kernels(d, causal=True, window=None, group=1, masked=False,
                   biased=False, t=256, block=128):
    bh = 2 * group
    q, k, v = (jax.ShapeDtypeStruct((rows, t, d), jnp.bfloat16)
               for rows in (bh, 2, 2))
    valid = jax.ShapeDtypeStruct((bh,), jnp.int32) if masked else None
    bias = jax.ShapeDtypeStruct((bh, t, t), jnp.float32) if biased else None
    out, lse = (jax.ShapeDtypeStruct(s, dt) for s, dt in (
        ((bh, t, d), jnp.bfloat16), ((bh, t, 1), jnp.float32)))

    def both(q, k, v, valid, bias, out, lse, do):
        fwd = fa._fwd(q, k, v, valid, None, bias, 0.125, causal, 0.0, block,
                      block, True, window)
        bwd = fa._bwd_call(0.125, causal, 0.0, block, block, True,
                           (q, k, v, valid, None, bias, out, lse), do, window)
        return fwd, bwd
    return kernel_bodies(
        jax.make_jaxpr(both)(q, k, v, valid, bias, out, lse, out).jaxpr)


VARIANTS = {
    "causal": dict(),
    "window": dict(window=100),
    "grouped": dict(window=128, group=3),
    "padding_mask": dict(causal=False, masked=True),
    "bias": dict(biased=True),
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("d", [64, 128, 256])
def test_no_kernel_body_holds_a_row_vector(d, variant):
    bodies = traced_kernels(d, **VARIANTS[variant])
    assert sorted(bodies) == ["_bwd_dkv_kernel", "_bwd_dq_kernel",
                              "_fwd_kernel"]
    for name, body in bodies.items():
        assert row_vectors(body) == [], name
        # the (128,) block of valid lengths in SMEM is the one rank-1 value a
        # body may hold
        rank1 = [v.aval for v in body.invars
                 if len(getattr(v.aval, "shape", ())) == 1]
        assert all(a.dtype == jnp.int32 and a.shape in ((128,), (1,))
                   for a in rank1), (name, rank1)


def test_the_guard_sees_the_parents_form():
    """`m_scr[:, 0]`, a maximum over rank-1 values and `[:, None]` are what
    the guard is there to catch."""
    def body(x, m):
        m_prev = m[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(x, axis=1))
        return jnp.exp(x - m_cur[:, None])
    x = jax.ShapeDtypeStruct((64, 256), jnp.float32)
    m = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    found = row_vectors(jax.make_jaxpr(body)(x, m).jaxpr)
    assert any(f.startswith("max") for f in found), found
    assert any(f.startswith("reduce_max") for f in found), found

    def kept(x, m):
        return jnp.exp(x[:, :128] - jnp.maximum(
            m, jnp.max(x, axis=1, keepdims=True)))
    assert row_vectors(jax.make_jaxpr(kept)(x, m).jaxpr) == []


# ---------------------------------------------------------------------------
# (b) the dense arm, where the lanes are handled differently
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("t,block_k", [(64, None), (256, 128), (1024, 512),
                                       (2048, 1024)])
@pytest.mark.parametrize("d", [64, 128, 256])
def test_forward_and_gradients_match_the_dense_arm(d, t, block_k):
    """block_k // 128 repeats of the statistics' lanes over the scores (the
    leading 64 lanes at T 64), d // 128 over the accumulator (the leading 64
    at D 64); several key blocks a row but at T 64, so the rescaling runs."""
    window = t // 2
    q, k, v = qkv(4, 2, t, d, seed=d + t)
    block_q = min(256, t)
    assert fa._blocks(t, t, block_q, block_k, window)[1] == (block_k or t)
    weights = jnp.cos(jnp.arange(4 * t * d, dtype=jnp.float32)
                      ).reshape(4, t, d)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window,
                                  block_q=block_q, block_k=block_k)

    def dense(q, k, v):
        return dense_attention(q, k, v, True, window)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(dense(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    got, want = (jax.grad(lambda q, k, v: jnp.sum(f(q, k, v) * weights),
                          argnums=(0, 1, 2))(q, k, v) for f in (flash, dense))
    for a, b, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4, err_msg=f"d{name}")


def test_lanes_repeats_whole_tiles_and_takes_leading_lanes():
    x = jnp.broadcast_to(jnp.arange(16, dtype=jnp.float32)[:, None],
                         (16, 128))
    for n in (8, 64, 128, 256, 1024, 200):
        got = fa._lanes(x, n)
        assert got.shape == (16, n)
        np.testing.assert_array_equal(
            np.asarray(got), np.broadcast_to(np.arange(16.0)[:, None],
                                             (16, n)))
    assert fa._lanes(x, 128) is x


# ---------------------------------------------------------------------------
# (c) nothing that decides memory moved
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bh,bh_kv,t,d,block_q,block_k,window", [
    (28, 4, 16384, 128, 512, 1024, 4096),     # smallthinker-21ba3b.extend16k
    (72, 8, 8192, 128, 512, 512, 512),        # laguna-s-2.1.pretrain8k
    (40, 40, 4096, 256, 512, 1024, None),     # glm-4.7-flash.pretrain4k
    (576, 576, 512, 64, 512, 512, None),      # bert-base.mlm512
])
def test_shapes_are_the_parents(bh, bh_kv, t, d, block_q, block_k, window):
    """`lse` stays (BH, T, 1) f32 in HBM, the outputs keep their shapes and
    dtypes, and the forward's scratch is two (block_q, 128) f32 statistics
    and the (block_q, D) accumulator: `peak_hbm_gib` and `step_temp_gib`
    cannot have moved."""
    q, k = (jax.ShapeDtypeStruct((rows, t, d), jnp.bfloat16)
            for rows in (bh, bh_kv))
    lse = jax.ShapeDtypeStruct((bh, t, 1), jnp.float32)

    def fwd(q, k, v):
        return fa._fwd(q, k, v, None, None, None, 0.1, True, 0.0, block_q,
                       block_k, True, window)

    def bwd(q, k, v, out, lse, do):
        return fa._bwd_call(0.1, True, 0.0, block_q, block_k, True,
                            (q, k, v, None, None, None, out, lse), do, window)
    got = jax.eval_shape(fwd, q, k, k)
    assert [(x.shape, x.dtype) for x in got] == [
        ((bh, t, d), jnp.bfloat16), ((bh, t, 1), jnp.float32)]
    dq, dk, dv, *rest = jax.eval_shape(bwd, q, k, k, q, lse, q)
    assert [(x.shape, x.dtype) for x in (dq, dk, dv)] == [
        ((bh, t, d), jnp.bfloat16)] + [((bh_kv, t, d), jnp.bfloat16)] * 2
    assert rest == [None, None, None]
    body = kernel_bodies(jax.make_jaxpr(fwd)(q, k, k).jaxpr)["_fwd_kernel"]
    scratch = [(v.aval.shape, v.aval.dtype) for v in body.invars[-3:]]
    assert scratch == [((block_q, 128), jnp.float32)] * 2 + [
        ((block_q, d), jnp.float32)]
