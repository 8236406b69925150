"""Pallas kernel tests — run in interpret mode on CPU, real Mosaic on TPU.

Oracle: dense jnp attention (the check_consistency pattern from the
reference's test strategy, SURVEY §4)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_mx.kernels.flash_attention import (flash_attention,
                                            mha_flash_attention)


def dense_attention(q, k, v, causal=False):
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("btd,bsd->bts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        t, tk = s.shape[-2:]
        mask = np.arange(t)[:, None] >= np.arange(tk)[None, :]
        s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bts,bsd->btd", p, v.astype(jnp.float32))


def make_qkv(bh=2, t=256, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (bh, t, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_dense(causal):
    q, k, v = make_qkv()
    out = flash_attention(q, k, v, 1.0 / math.sqrt(q.shape[-1]), causal)
    ref = dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_forward_bf16():
    q, k, v = make_qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, 1.0 / math.sqrt(q.shape[-1]), False)
    ref = dense_attention(q, k, v, False)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), rtol=0.05, atol=0.05)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.slow
def test_flash_backward_matches_dense(causal):
    q, k, v = make_qkv(bh=1, t=256, d=64)
    scale = 1.0 / math.sqrt(q.shape[-1])

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, scale, causal) ** 2).sum()

    def loss_dense(q, k, v):
        return (dense_attention(q, k, v, causal) ** 2).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name} mismatch")


def test_flash_multiblock():
    # several q and k blocks: exercises the online-softmax carry
    q, k, v = make_qkv(bh=1, t=512, d=64, seed=3)
    out = flash_attention(q, k, v, 1.0 / math.sqrt(64), False,
                          block_q=128, block_k=128)
    ref = dense_attention(q, k, v, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_mha_wrapper_layout():
    b, h, t, d = 2, 4, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (b, h, t, d)) for kk in ks)
    out = mha_flash_attention(q, k, v)
    ref = dense_attention(q.reshape(b * h, t, d), k.reshape(b * h, t, d),
                          v.reshape(b * h, t, d)).reshape(b, h, t, d)
    assert out.shape == (b, h, t, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_under_jit():
    q, k, v = make_qkv(bh=1, t=128)
    fn = jax.jit(lambda a, b, c: flash_attention(a, b, c, 0.125, True))
    out = fn(q, k, v)
    ref = dense_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_default_scale():
    q, k, v = make_qkv(bh=1, t=128)
    out = flash_attention(q, k, v)  # no explicit scale
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_rejects_partial_kv_blocks():
    from tpu_mx.kernels.flash_attention import supported
    assert not supported((1, 256, 64), jnp.float32, kv_len=300)
    assert supported((1, 256, 64), jnp.float32, kv_len=512)


def test_flash_cross_attention_lengths():
    # Tq != Tkv but both tile-aligned
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (2, 128, 64))
    k = jax.random.normal(ks[1], (2, 384, 64))
    v = jax.random.normal(ks[2], (2, 384, 64))
    out = flash_attention(q, k, v)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_multiblock(causal):
    # explicit 128-blocks over t=256: exercises cross-block dq/dk/dv
    # accumulation and the causal skip predicates in the backward kernels
    q, k, v = make_qkv(bh=1, t=256, d=64, seed=11)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, None, causal,
                                block_q=128, block_k=128) ** 2).sum()

    def loss_dense(q, k, v):
        return (dense_attention(q, k, v, causal) ** 2).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name} mismatch")


def dense_attention_masked(q, k, v, valid, causal=False):
    """Oracle with a key-padding mask: columns >= valid[b] excluded."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("btd,bsd->bts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    tk = k.shape[1]
    s = jnp.where(jnp.arange(tk)[None, None, :] < valid[:, None, None],
                  s, -1e30)
    if causal:
        t = q.shape[1]
        mask = np.arange(t)[:, None] >= np.arange(tk)[None, :]
        s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bts,bsd->btd", p, v.astype(jnp.float32))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_padding_mask_matches_dense(causal):
    # ragged valid lengths incl. block-interior (200), block-boundary (128),
    # full (256) and minimal (1) — VERDICT r2 missing#2
    q, k, v = make_qkv(bh=4, t=256, d=64, seed=5)
    valid = jnp.asarray([200, 128, 256, 1], jnp.int32)
    out = flash_attention(q, k, v, causal=causal, kv_valid=valid,
                          block_q=128, block_k=128)
    ref = dense_attention_masked(q, k, v, valid, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_padding_mask_backward(causal):
    q, k, v = make_qkv(bh=3, t=256, d=64, seed=9)
    valid = jnp.asarray([130, 256, 7], jnp.int32)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal=causal, kv_valid=valid,
            block_q=128, block_k=128)))

    def loss_dense(q, k, v):
        return jnp.sum(jnp.sin(dense_attention_masked(q, k, v, valid,
                                                      causal)))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name} mismatch")
    # padded keys (beyond valid) must receive exactly zero dk/dv
    dk = np.asarray(g_flash[1])
    assert np.all(dk[0, 130:] == 0.0) and np.all(dk[2, 7:] == 0.0)


def test_mha_valid_length_broadcasts_heads():
    # (B,) valid_length must apply identically to every head
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    B, H, T, D = 2, 2, 128, 64
    q, k, v = (jax.random.normal(kk, (B, H, T, D)) for kk in ks)
    valid = jnp.asarray([100, 37], jnp.int32)
    out = mha_flash_attention(q, k, v, valid_length=valid)
    flat = lambda x: x.reshape(B * H, T, D)
    ref = dense_attention_masked(flat(q), flat(k), flat(v),
                                 jnp.repeat(valid, H))
    np.testing.assert_allclose(np.asarray(flat(out)), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t", [96, 130, 320, 384, 640, 1000, 1536])
def test_pick_block_guard_odd_lengths(t):
    """Any T either runs correctly (vs dense oracle) or raises a clean
    ValueError — never a silent O(T^2)-VMEM single block (VERDICT r2
    weak#6/ask#9)."""
    from tpu_mx.kernels.flash_attention import MAX_BLOCK_ELEMS, _pick_block
    ks = jax.random.split(jax.random.PRNGKey(t), 3)
    q, k, v = (jax.random.normal(kk, (1, t, 64)) for kk in ks)
    bq = min(_pick_block(t, 512), t)
    bk = min(_pick_block(t, 1024), t)
    if t % bq or t % bk or bq * bk > MAX_BLOCK_ELEMS:
        with pytest.raises(ValueError):
            flash_attention(q, k, v)
    else:
        out = flash_attention(q, k, v)
        ref = dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_flash_dropout_rejected_off_tpu():
    # the in-kernel PRNG has no interpret lowering; a clear error (and a
    # supported()=False gate) beats a crash deep inside Mosaic
    from tpu_mx.kernels.flash_attention import supported
    q, k, v = make_qkv(bh=1, t=128, d=64)
    if jax.default_backend() != "tpu":
        assert not supported(q.shape, q.dtype, dropout_rate=0.1)
        with pytest.raises(ValueError, match="dropout"):
            flash_attention(q, k, v, dropout_rate=0.1,
                            dropout_seed=jnp.zeros((1,), jnp.int32))


class TestFlashBias:
    """In-kernel additive attention bias (ALiBi/relative-position):
    fwd + all four grads vs the dense reference, every broadcast layout."""

    def _dense(self, q, k, v, bias, causal):
        import jax
        import jax.numpy as jnp
        d = q.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
        s = s + bias
        if causal:
            t, tk = q.shape[2], k.shape[2]
            m = jnp.arange(t)[:, None] >= jnp.arange(tk)[None, :]
            s = jnp.where(m[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    @pytest.mark.parametrize("bias_shape,causal", [
        ((2, 4, 128, 128), False), ((1, 4, 128, 128), False),
        ((1, 1, 128, 128), False), ((2, 4, 128, 128), True),
    ])
    @pytest.mark.slow
    def test_bias_fwd_bwd_vs_dense(self, bias_shape, causal):
        import jax
        import jax.numpy as jnp
        from tpu_mx.kernels.flash_attention import mha_flash_attention
        rng = np.random.RandomState(0)
        B, H, T, D = 2, 4, 128, 64
        q, k, v = (jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
                   for _ in range(3))
        bias = jnp.asarray(rng.randn(*bias_shape).astype(np.float32))

        def loss_flash(q, k, v, bias):
            return jnp.sum(jnp.sin(mha_flash_attention(
                q, k, v, causal=causal, bias=bias,
                block_q=64, block_k=64)))

        def loss_dense(q, k, v, bias):
            return jnp.sum(jnp.sin(self._dense(q, k, v, bias, causal)))

        out_f = mha_flash_attention(q, k, v, causal=causal, bias=bias,
                                    block_q=64, block_k=64)
        out_d = self._dense(q, k, v, bias, causal)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                                   rtol=2e-4, atol=2e-5)
        gf = jax.grad(loss_flash, argnums=(0, 1, 2, 3))(q, k, v, bias)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2, 3))(q, k, v, bias)
        for a, b, name in zip(gf, gd, "qkvb"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-4, atol=3e-5,
                                       err_msg=f"d{name} {bias_shape}")

    @pytest.mark.slow
    def test_bias_with_padding_mask(self):
        import jax.numpy as jnp
        from tpu_mx.kernels.flash_attention import mha_flash_attention
        rng = np.random.RandomState(1)
        B, H, T, D = 2, 2, 128, 32
        q, k, v = (jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
                   for _ in range(3))
        bias = jnp.asarray(rng.randn(1, H, T, T).astype(np.float32))
        vl = np.array([128, 64])
        out = mha_flash_attention(q, k, v, valid_length=vl, bias=bias,
                                  block_q=64, block_k=64)
        # dense reference with key-padding mask
        import jax
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D) + bias
        km = (jnp.arange(T)[None, None, None, :] <
              jnp.asarray(vl)[:, None, None, None])
        s = jnp.where(km, s, -jnp.inf)
        ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_bias_shape_validation(self):
        import jax.numpy as jnp
        from tpu_mx.kernels.flash_attention import flash_attention
        q = jnp.ones((4, 128, 32), jnp.float32)
        with pytest.raises(ValueError, match="bias shape"):
            flash_attention(q, q, q, bias=jnp.ones((3, 128, 128)))


def test_flash_bias_singleton_dims_and_ambiguity():
    """(1,H,1,T) ALiBi-layout biases broadcast correctly through the
    kernel path, and bare-divisor leading dims are rejected without
    bias_groups."""
    import jax.numpy as jnp
    from tpu_mx.kernels.flash_attention import (flash_attention,
                                               mha_flash_attention)
    rng = np.random.RandomState(0)
    B, H, T, D = 2, 4, 128, 32
    q, k, v = (jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
               for _ in range(3))
    bias_row = jnp.asarray(rng.randn(1, H, 1, T).astype(np.float32))
    out = mha_flash_attention(q, k, v, bias=bias_row, block_q=64,
                              block_k=64)
    import jax
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D) + bias_row
    ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    # divisor-without-groups is ambiguous -> rejected
    qf = q.reshape(B * H, T, D)
    with pytest.raises(ValueError, match="ambiguous"):
        flash_attention(qf, qf, qf, bias=jnp.ones((2, T, T)))
    # ...but explicit bias_groups makes it legal
    out2 = flash_attention(qf, qf, qf, bias=jnp.zeros((2, T, T)),
                           bias_groups=2, block_q=64, block_k=64)
    assert out2.shape == qf.shape


def test_attention_env_knob(monkeypatch):
    """TPUMX_ATTENTION measurement knob: bad values rejected, 'dense'
    always runs the XLA dense path."""
    import numpy as np
    import jax.numpy as jnp
    from tpu_mx.parallel.ring_attention import local_flash_attention
    q = jnp.asarray(np.random.RandomState(0).rand(1, 2, 128, 64),
                    jnp.float32)
    monkeypatch.setenv("TPUMX_ATTENTION", "bogus")
    with pytest.raises(ValueError, match="TPUMX_ATTENTION"):
        local_flash_attention(q, q, q)
    monkeypatch.setenv("TPUMX_ATTENTION", "dense")
    out = local_flash_attention(q, q, q)
    assert out.shape == q.shape


@pytest.mark.parametrize("on_tpu", [True, False], ids=["tpu", "cpu"])
@pytest.mark.parametrize("dropped", [True, False],
                         ids=["dropout", "no_dropout"])
@pytest.mark.parametrize("kv_len", [128, 256, 384, 512, 1024])
def test_auto_dispatch_rule(kv_len, dropped, on_tpu):
    """'auto' as a pure function of what the call shows (PERF.md section 6,
    PR 26 holds the two-arm table the crossover was taken from): on a TPU
    the Pallas kernel from kv 256 where attention dropout is active and
    from kv 512 where it is not, dense below and off the TPU; a query
    shorter than the crossover keeps the call dense, and beyond kv 512 the
    kernel is the choice whatever the query."""
    from tpu_mx.parallel.ring_attention import _auto_prefers_flash
    assert _auto_prefers_flash(kv_len, kv_len, dropped, on_tpu) is \
        (on_tpu and kv_len >= (256 if dropped else 512))
    assert _auto_prefers_flash(128, kv_len, dropped, on_tpu) is \
        (on_tpu and kv_len > 512)


def test_auto_dispatch_ignores_dense_max_kv(monkeypatch):
    """TPUMX_DENSE_MAX_KV is gone: set either way it moves no call.  The
    process is made to look like a TPU one (with the kernel in interpret
    mode), so that 'auto' really chooses."""
    import jax
    import jax.numpy as jnp
    from tpu_mx.kernels import flash_attention as fa
    from tpu_mx.parallel.ring_attention import (dispatch_counts,
                                                local_flash_attention)
    monkeypatch.delenv("TPUMX_ATTENTION", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "_interpret", lambda: True)

    def arm_of(t, max_kv):
        # a head count no other test uses: the counter is once a signature
        monkeypatch.setenv("TPUMX_DENSE_MAX_KV", str(max_kv))
        q = jnp.ones((1, 5, t, 64), jnp.float32)
        before = dict(dispatch_counts)
        assert local_flash_attention(q, q, q).shape == q.shape
        return [k for k in dispatch_counts
                if dispatch_counts[k] != before[k]]

    assert arm_of(128, max_kv=0) == ["xla_dense"]
    assert arm_of(1024, max_kv=4096) == ["pallas_flash"]


# ---------------------------------------------------------------------------
# paged-attention decode kernel (ISSUE 9) — interpret mode on CPU
# ---------------------------------------------------------------------------
def _paged_numpy_ref(q, k_pool, v_pool, tables, lengths):
    """Per-sequence dense truth: resolve each block table by hand."""
    b, h, d = q.shape
    bs = k_pool.shape[1]
    out = np.zeros_like(q)
    for i in range(b):
        length = int(lengths[i])
        nb = -(-length // bs)
        k = k_pool[tables[i, :nb]].reshape(-1, h, d)[:length]
        v = v_pool[tables[i, :nb]].reshape(-1, h, d)[:length]
        s = np.einsum("hd,khd->hk", q[i].astype(np.float64),
                      k.astype(np.float64)) / math.sqrt(d)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[i] = np.einsum("hk,khd->hd", p, v.astype(np.float64))
    return out


def _paged_case(seed=0, nblocks=24, bs=4, h=2, d=8, specs=((10, (7, 2, 9)),
                                                          (3, (5,)),
                                                          (16, (11, 1, 4, 8)))):
    """Fragmented tables, ragged lengths, rows 0-padded to a shared NB."""
    rng = np.random.RandomState(seed)
    kp = rng.randn(nblocks, bs, h, d).astype(np.float32)
    vp = rng.randn(nblocks, bs, h, d).astype(np.float32)
    b = len(specs)
    nb = max(len(t) for _, t in specs)
    tables = np.zeros((b, nb), np.int32)
    lens = np.zeros(b, np.int32)
    for i, (length, tab) in enumerate(specs):
        tables[i, :len(tab)] = tab
        lens[i] = length
    q = rng.randn(b, h, d).astype(np.float32)
    return q, kp, vp, tables, lens


@pytest.mark.parametrize("arm", ["kernel", "xla"])
def test_paged_attention_matches_reference(arm):
    from tpu_mx.kernels.paged_attention import (paged_attention,
                                                paged_attention_reference)
    q, kp, vp, tables, lens = _paged_case()
    fn = paged_attention if arm == "kernel" else paged_attention_reference
    out = np.asarray(fn(q, kp, vp, tables, lens))
    ref = _paged_numpy_ref(q, kp, vp, tables, lens)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_paged_attention_padding_blocks_cannot_leak():
    """Entries past a row's real blocks (0-padding) and slots past
    `lengths` inside the last block must be EXACTLY invisible: poison
    them and the output may not move a single bit."""
    from tpu_mx.kernels.paged_attention import paged_attention
    q, kp, vp, tables, lens = _paged_case()
    base = np.asarray(paged_attention(q, kp, vp, tables, lens))
    kp2, vp2 = kp.copy(), vp.copy()
    kp2[0] = 1e9          # block 0 backs every padded table entry
    vp2[0] = -1e9
    kp2[9, 2:] = 1e9      # row 0: length 10 ends 2 slots into block 9
    vp2[9, 2:] = -1e9
    kp2[5, 3:] = 1e9      # row 1: length 3 ends inside block 5
    vp2[5, 3:] = -1e9
    again = np.asarray(paged_attention(q, kp2, vp2, tables, lens))
    np.testing.assert_array_equal(base, again)


def test_paged_attention_accepts_single_token_axis():
    from tpu_mx.kernels.paged_attention import paged_attention
    q, kp, vp, tables, lens = _paged_case()
    out3 = np.asarray(paged_attention(q, kp, vp, tables, lens))
    out4 = np.asarray(paged_attention(q[:, None], kp, vp, tables, lens))
    assert out4.shape == (q.shape[0], 1) + q.shape[1:]
    np.testing.assert_array_equal(out4[:, 0], out3)


def _paged_numpy_window_ref(q, k_pool, v_pool, tables, lengths):
    """Window truth by reduction: row ``t`` of a ``Tq`` window is the
    single-token case at length ``lengths - (Tq-1-t)``."""
    b, tq, h, d = q.shape
    out = np.zeros((b, tq, h, d), np.float64)
    for t in range(tq):
        lens_t = (lengths - (tq - 1 - t)).astype(np.int32)
        out[:, t] = _paged_numpy_ref(q[:, t], k_pool, v_pool,
                                     tables, lens_t)
    return out


@pytest.mark.parametrize("arm", ["kernel", "walk", "xla"])
def test_paged_attention_window_matches_reference(arm):
    """The widened ``(B, Tq, H, D)`` query axis — the speculative verify
    call — must match the per-row single-token truth on every arm."""
    from tpu_mx.kernels import paged_attention as pk
    q1, kp, vp, tables, lens = _paged_case()
    rng = np.random.RandomState(7)
    tq = 3                                  # min length is 3 in the case
    q = rng.randn(len(lens), tq, q1.shape[-2],
                  q1.shape[-1]).astype(np.float32)
    scale = 1.0 / math.sqrt(q1.shape[-1])
    fn = {"kernel": pk.paged_attention,
          "walk": lambda *a: pk.window_walk(*a, scale),
          "xla": pk.paged_attention_reference}[arm]
    out = np.asarray(fn(q, kp, vp, tables, lens))
    ref = _paged_numpy_window_ref(q, kp, vp, tables, lens)
    assert out.shape == q.shape
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_paged_window_rows_are_causally_staggered():
    """Row ``t`` of the window sits at absolute position
    ``length - Tq + t``: poisoning the LAST occupied slot may move only
    the last row — earlier rows must not see their successors' keys."""
    from tpu_mx.kernels.paged_attention import paged_attention
    q1, kp, vp, tables, lens = _paged_case()
    rng = np.random.RandomState(8)
    tq = 3
    q = rng.randn(len(lens), tq, q1.shape[-2],
                  q1.shape[-1]).astype(np.float32)
    base = np.asarray(paged_attention(q, kp, vp, tables, lens))
    kp2, vp2 = kp.copy(), vp.copy()
    bs = kp.shape[1]
    for i in range(len(lens)):
        last = int(lens[i]) - 1             # final key slot of row i
        blk = int(tables[i, last // bs])
        kp2[blk, last % bs] = 1e6
        vp2[blk, last % bs] = -1e6
    again = np.asarray(paged_attention(q, kp2, vp2, tables, lens))
    np.testing.assert_array_equal(base[:, :-1], again[:, :-1])
    assert not np.array_equal(base[:, -1], again[:, -1])


def test_paged_attention_bf16_pool():
    import jax.numpy as jnp
    from tpu_mx.kernels.paged_attention import paged_attention
    q, kp, vp, tables, lens = _paged_case()
    out = np.asarray(paged_attention(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(kp, jnp.bfloat16),
        jnp.asarray(vp, jnp.bfloat16), tables, lens), np.float32)
    ref = _paged_numpy_ref(q, kp, vp, tables, lens)
    np.testing.assert_allclose(out, ref, rtol=0.05, atol=0.05)


def test_paged_attention_rejects_mismatched_operands():
    from tpu_mx.kernels.paged_attention import paged_attention
    q, kp, vp, tables, lens = _paged_case()
    with pytest.raises(ValueError, match="pool heads/dim"):
        paged_attention(q[:, :1], kp, vp, tables, lens)
    with pytest.raises(ValueError, match="block_tables"):
        paged_attention(q, kp, vp, tables[:2], lens)
    with pytest.raises(ValueError, match="lengths"):
        paged_attention(q, kp, vp, tables, lens[:2])


def test_paged_supported_gate():
    """Interpret mode accepts anything (correctness-only); the real-TPU
    constraints are shape/dtype gates the dispatcher consults."""
    import jax
    from tpu_mx.kernels import paged_attention as pk
    if jax.default_backend() != "tpu":
        assert pk.supported(8, np.float32)
    else:
        assert pk.supported(64, np.float32, 16)
        assert not pk.supported(8, np.float32, 16)
