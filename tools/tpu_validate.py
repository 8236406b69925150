"""Real-chip validation sweep for the Pallas/kernel tail (VERDICT r3 ask#2).

Interpret-mode green is not chip green: real Mosaic enforces limits the
CPU interpreter does not (PRNG seed arity, SMEM layouts), and the MXU's
default precision is not the CPU's.  This runner executes each kernel
path on `jax.devices()[0]` of a real TPU backend and prints a per-check
pass/fail record of what this run executed (to --out, else to stdout).

One process, holding the chip:
    python tools/tpu_validate.py [--out PATH] [--skip-bert]
or the same checks as pytest nodes:
    TPUMX_TEST_TPU=1 python -m pytest tests/test_tpu_chip.py -m tpu

Each check is isolated: one Mosaic rejection must not mask the others.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def log(msg):
    print(f"[validate {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def _dense_ref(q, k, v, causal=False, valid_length=None, bias=None):
    """O(T²) reference attention in f32 — the oracle for every kernel
    check (same contract as kernels.flash_attention)."""
    import jax
    import jax.numpy as jnp
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    if bias is not None:
        s = s + jnp.broadcast_to(bias, s.shape).astype(jnp.float32)
    t, tk = s.shape[-2], s.shape[-1]
    if causal:
        s = jnp.where(jnp.arange(t)[:, None] >= jnp.arange(tk)[None, :],
                      s, -1e30)
    if valid_length is not None:
        km = jnp.arange(tk)[None, None, None, :] < \
            jnp.asarray(valid_length)[:, None, None, None]
        s = jnp.where(km, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vf).astype(q.dtype)


def _max_err(a, b):
    import numpy as np
    return float(np.max(np.abs(np.asarray(a, np.float32) -
                               np.asarray(b, np.float32))))


def _highest_precision(fn):
    """Run an f32-oracle check under jax.default_matmul_precision('highest').

    On real TPU the DEFAULT matmul precision truncates f32 operands to
    single-pass bf16 on the MXU — both in the Pallas kernels' in-kernel
    dots (precision resolves from the jax config at trace time) and in the
    jnp oracle — so an exact-f32 comparison at tol 2e-3 fails with
    ~3-6e-3 truncation noise (the r4 first on-chip sweep failed exactly
    this way; CPU interpret mode computes true f32 and never showed it).
    Correctness checks compare true-f32 to true-f32; the bf16 checks and
    the benches keep DEFAULT, which is the production path."""
    import functools

    @functools.wraps(fn)
    def wrapped():
        import jax
        with jax.default_matmul_precision("highest"):
            return fn()
    return wrapped


def check_flash_fwd_bwd_vs_dense():
    """Flash kernel fwd+bwd vs dense oracle, f32 and bf16, causal and
    not.  The f32 legs run under matmul precision 'highest' (see
    _highest_precision); the bf16 legs DELIBERATELY keep DEFAULT — that
    is the production bench path, and wrapping them too would hide any
    DEFAULT-precision-only numeric bug."""
    import contextlib
    import jax
    import jax.numpy as jnp
    from tpu_mx.kernels.flash_attention import mha_flash_attention
    b, h, t, d = 2, 4, 512, 64
    key = jax.random.PRNGKey(0)
    qk, kk, vk = jax.random.split(key, 3)
    results = {}
    for dtype, tol in ((jnp.float32, 2e-3), (jnp.bfloat16, 4e-2)):
      with (jax.default_matmul_precision("highest")
            if dtype == jnp.float32 else contextlib.nullcontext()):
        q = jax.random.normal(qk, (b, h, t, d), dtype)
        k = jax.random.normal(kk, (b, h, t, d), dtype)
        v = jax.random.normal(vk, (b, h, t, d), dtype)
        for causal in (False, True):
            f = lambda q, k, v: mha_flash_attention(
                q, k, v, causal=causal).astype(jnp.float32).sum()
            g = lambda q, k, v: _dense_ref(
                q, k, v, causal=causal).astype(jnp.float32).sum()
            out = mha_flash_attention(q, k, v, causal=causal)
            ref = _dense_ref(q, k, v, causal=causal)
            e_out = _max_err(out, ref)
            gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
            gd = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
            e_grad = max(_max_err(a, b) for a, b in zip(gf, gd))
            tag = f"{jnp.dtype(dtype).name}_causal={causal}"
            results[tag] = {"out_err": e_out, "grad_err": e_grad}
            # grad tolerance is looser: sum-of-T cotangents accumulate
            if e_out > tol or e_grad > tol * 20:
                raise AssertionError(f"{tag}: out_err={e_out} "
                                     f"grad_err={e_grad} tol={tol}")
    return results


def check_attention_auto_dispatch():
    """What `auto` chooses on the chip (PERF.md section 6, PR 26 has the
    two-arm table): BERT-base's SelfAttention, forward and backward with
    attention dropout 0.1, takes the Pallas kernel at T 512 and XLA dense
    at T 128; and with dropout off the two pinned arms agree in output and
    q/k/v gradients within flash_fwd_bwd_vs_dense's bf16 tolerance."""
    from unittest import mock
    import numpy as np
    import jax
    import jax.numpy as jnp
    from tpu_mx import autograd, nd
    from tpu_mx.models.bert import SelfAttention
    from tpu_mx.parallel.ring_attention import (dispatch_counts,
                                                local_flash_attention)
    b, heads, dim = 2, 12, 64
    units = heads * dim
    results = {}
    with mock.patch.dict(os.environ):
        os.environ.pop("TPUMX_ATTENTION", None)
        for t, want, other in ((512, "pallas_flash", "xla_dense"),
                               (128, "xla_dense", "pallas_flash")):
            attn = SelfAttention(units=units, num_heads=heads, dropout=0.1)
            attn.initialize()
            attn.cast("bfloat16")
            x = nd.array(np.random.RandomState(t).randn(b, t, units)
                         .astype(np.float32)).astype("bfloat16")
            x.attach_grad()
            before = dict(dispatch_counts)
            with autograd.record():
                loss = attn(x).astype("float32").sum()
            loss.backward()
            took = {k: dispatch_counts[k] - before[k]
                    for k in (want, other)}
            finite = bool(np.isfinite(x.grad.asnumpy().astype(np.float32))
                          .all())
            results[f"T{t}"] = {"dispatch": took, "grad_finite": finite}
            if took[want] < 1 or took[other] or not finite:
                raise AssertionError(f"auto at T={t}: wanted {want}, counted "
                                     f"{took}, finite grads {finite}")

    tol = 4e-2                      # flash_fwd_bwd_vs_dense's bf16 leg
    q, k, v = (jax.random.normal(key, (b, heads, 512, dim), jnp.bfloat16)
               for key in jax.random.split(jax.random.PRNGKey(26), 3))
    f = lambda q, k, v: local_flash_attention(q, k, v).astype(
        jnp.float32).sum()
    arms = {}
    for arm in ("dense", "flash"):
        with mock.patch.dict(os.environ, TPUMX_ATTENTION=arm):
            arms[arm] = (local_flash_attention(q, k, v),
                         jax.grad(f, argnums=(0, 1, 2))(q, k, v))
    e_out = _max_err(arms["dense"][0], arms["flash"][0])
    e_grad = max(_max_err(a, c)
                 for a, c in zip(arms["dense"][1], arms["flash"][1]))
    results["arms_agree"] = {"out_err": e_out, "grad_err": e_grad}
    if e_out > tol or e_grad > tol * 20:
        raise AssertionError(f"pinned arms disagree: out_err={e_out} "
                             f"grad_err={e_grad} tol={tol}")
    return results


@_highest_precision
def check_flash_bias_layouts():
    """All broadcast layouts of the additive attention bias (r3 commit
    f1c476b, never chip-run): per-batch-head, shared-batch (G=H cycling),
    fully shared, and singleton-T broadcast.  fwd vs dense + d_bias."""
    import jax
    import jax.numpy as jnp
    from tpu_mx.kernels.flash_attention import mha_flash_attention
    b, h, t, d = 2, 4, 256, 64
    key = jax.random.PRNGKey(1)
    qk, kk, vk, bk = jax.random.split(key, 4)
    q = jax.random.normal(qk, (b, h, t, d), jnp.float32)
    k = jax.random.normal(kk, (b, h, t, d), jnp.float32)
    v = jax.random.normal(vk, (b, h, t, d), jnp.float32)
    results = {}
    for shape in ((b, h, t, t), (1, h, t, t), (1, 1, t, t), (b, 1, 1, t)):
        bias = jax.random.normal(bk, shape, jnp.float32)
        out = mha_flash_attention(q, k, v, bias=bias)
        ref = _dense_ref(q, k, v, bias=bias)
        e_out = _max_err(out, ref)
        f = lambda bb: mha_flash_attention(q, k, v, bias=bb).sum()
        g = lambda bb: _dense_ref(q, k, v, bias=bb).sum()
        db_f = jax.grad(f)(bias)
        db_d = jax.grad(g)(bias)
        e_db = _max_err(db_f, db_d)
        results[str(shape)] = {"out_err": e_out, "dbias_err": e_db}
        if e_out > 2e-3 or e_db > 2e-2:
            raise AssertionError(f"bias {shape}: out_err={e_out} "
                                 f"dbias_err={e_db}")
    return results


@_highest_precision
def check_flash_dropout():
    """In-kernel attention-prob dropout (TPU PRNG; r3 seed-fold fix,
    never chip-run): determinism under the same seed, divergence across
    seeds, keep-rate sanity, finite grads, and fwd/bwd mask agreement via
    directional-derivative consistency."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from tpu_mx.kernels.flash_attention import mha_flash_attention
    b, h, t, d = 2, 4, 256, 64
    rate = 0.25
    key = jax.random.PRNGKey(2)
    qk, kk, vk = jax.random.split(key, 3)
    q = jax.random.normal(qk, (b, h, t, d), jnp.float32)
    k = jax.random.normal(kk, (b, h, t, d), jnp.float32)
    v = jax.random.normal(vk, (b, h, t, d), jnp.float32)
    seed = jnp.array([1234], jnp.int32)
    run = lambda s: mha_flash_attention(q, k, v, dropout_rate=rate,
                                        dropout_seed=s)
    o1, o2 = run(seed), run(seed)
    if _max_err(o1, o2) != 0.0:
        raise AssertionError("same seed produced different outputs")
    o3 = run(jnp.array([999], jnp.int32))
    if _max_err(o1, o3) == 0.0:
        raise AssertionError("different seeds produced identical outputs")
    # fwd/bwd mask agreement via directional derivative in v: with the
    # mask and probs fixed, the output is LINEAR in v, so f = mean(O²) is
    # quadratic and the central difference is exact up to rounding — any
    # mismatch means the backward regenerated a different dropout mask
    u = jax.random.normal(jax.random.PRNGKey(7), v.shape, jnp.float32)
    f = lambda vv: (mha_flash_attention(q, k, vv, dropout_rate=rate,
                                        dropout_seed=seed) ** 2).mean()
    gv = jax.grad(f)(v)
    if not bool(jnp.isfinite(gv).all()):
        raise AssertionError("non-finite dropout grads")
    eps = 3e-3
    analytic = float((gv * u).sum())
    numeric = float((f(v + eps * u) - f(v - eps * u)) / (2 * eps))
    rel = abs(analytic - numeric) / max(abs(numeric), 1e-6)
    if rel > 5e-2:
        raise AssertionError(
            f"fwd/bwd dropout masks disagree: directional derivative "
            f"analytic={analytic:.6f} numeric={numeric:.6f} rel={rel:.4f}")
    # keep-rate sanity: ratio of dropped-softmax mass ≈ keep probability
    dense = _dense_ref(q, k, v)
    ratio = float(np.mean(np.asarray(o1) != np.asarray(dense)))
    return {"determinism": "ok", "grad_finite": True,
            "dir_deriv_rel_err": rel, "fraction_changed": ratio}


def check_dropout_mask_generator():
    """Dropout masks on the chip (ISSUE 28: bits from rng_bit_generator,
    drawn again in the backward pass, or held where the site says so or
    is the latest, as the one site of each program here is): at
    BERT-base's two mask shapes,
    under jit, the keep share, forward and backward on one mask (the
    gradient is nonzero exactly where the output is), run-to-run equality
    for one key and a different mask for its split's other half."""
    import jax
    import jax.numpy as jnp
    from tpu_mx import random as R
    rate, out = 0.1, {}
    left, right = jax.random.split(jax.random.PRNGKey(28))
    for name, shape, dtype in (("hidden", (192, 128, 768), jnp.bfloat16),
                               ("attention", (192, 12, 128, 128),
                                jnp.float32)):
        x = jnp.ones(shape, dtype)
        step = jax.jit(jax.value_and_grad(
            lambda x, key: R.dropout(x, key, rate).astype(jnp.float32).sum()))
        (_, g1), (_, g2) = step(x, left), step(x, left)
        y = jax.jit(lambda x, key: R.dropout(x, key, rate))(x, left)
        if not bool((g1 == g2).all()):
            raise AssertionError(f"{name}: one key, two masks")
        if not bool(((g1 != 0) == (y != 0)).all()):
            raise AssertionError(f"{name}: forward and backward masks differ")
        share = float((y != 0).mean())
        if abs(share - (1 - rate)) > 0.002:
            raise AssertionError(f"{name}: keep share {share}")
        other = jax.jit(lambda x, key: R.dropout(x, key, rate))(x, right)
        agree = float(((y != 0) == (other != 0)).mean())
        if abs(agree - 0.82) > 0.01:
            raise AssertionError(f"{name}: a split's halves agree on {agree}")
        out[name] = {"keep_share": share, "halves_agree": agree}
    return out


@_highest_precision
def check_flash_kv_valid():
    """Ragged key-padding masks (kv_valid) vs dense mask oracle."""
    import jax
    import jax.numpy as jnp
    from tpu_mx.kernels.flash_attention import mha_flash_attention
    b, h, t, d = 4, 2, 512, 64
    key = jax.random.PRNGKey(3)
    qk, kk, vk = jax.random.split(key, 3)
    q = jax.random.normal(qk, (b, h, t, d), jnp.float32)
    k = jax.random.normal(kk, (b, h, t, d), jnp.float32)
    v = jax.random.normal(vk, (b, h, t, d), jnp.float32)
    vl = jnp.array([512, 300, 128, 17], jnp.int32)
    out = mha_flash_attention(q, k, v, valid_length=vl)
    ref = _dense_ref(q, k, v, valid_length=vl)
    e = _max_err(out, ref)
    if e > 2e-3:
        raise AssertionError(f"kv_valid out_err={e}")
    return {"out_err": e}


def check_flash_t2048():
    """T=2048 blockwise path (the long-context tile) fwd+bwd, bf16."""
    import jax
    import jax.numpy as jnp
    from tpu_mx.kernels.flash_attention import mha_flash_attention
    b, h, t, d = 1, 4, 2048, 64
    key = jax.random.PRNGKey(4)
    qk, kk, vk = jax.random.split(key, 3)
    q = jax.random.normal(qk, (b, h, t, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, h, t, d), jnp.bfloat16)
    v = jax.random.normal(vk, (b, h, t, d), jnp.bfloat16)
    out = mha_flash_attention(q, k, v, causal=True)
    ref = _dense_ref(q, k, v, causal=True)
    e = _max_err(out, ref)
    g = jax.grad(lambda q, k, v: mha_flash_attention(
        q, k, v, causal=True).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)
    finite = all(bool(jnp.isfinite(x.astype(jnp.float32)).all()) for x in g)
    if e > 6e-2 or not finite:
        raise AssertionError(f"T=2048: out_err={e} grads_finite={finite}")
    return {"out_err": e, "grads_finite": finite}


@_highest_precision
def check_ring_inner_chunking():
    """Ring attention with O(T/n·C) inner chunking (r3 commit 75dab47,
    never chip-run) at T=2048 on an sp=1 single-chip mesh: the full
    shard_map ring body — scan, ppermute, chunked local attention —
    compiles and matches dense numerics on real silicon."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    import numpy as np
    from tpu_mx.parallel.ring_attention import ring_attention
    b, h, t, d = 1, 4, 2048, 64
    key = jax.random.PRNGKey(5)
    qk, kk, vk = jax.random.split(key, 3)
    q = jax.random.normal(qk, (b, h, t, d), jnp.float32)
    k = jax.random.normal(kk, (b, h, t, d), jnp.float32)
    v = jax.random.normal(vk, (b, h, t, d), jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    out = ring_attention(q, k, v, mesh, causal=True, step_chunk=512)
    ref = _dense_ref(q, k, v, causal=True)
    e = _max_err(out, ref)
    if e > 2e-3:
        raise AssertionError(f"ring sp=1 T=2048 out_err={e}")
    return {"out_err": e, "step_chunk": 512}


@_highest_precision
def check_paged_attention_ragged():
    """The paged decode kernel vs its XLA twin (paged_attention_reference,
    the same block-table walk as one jitted program) on ragged lengths and
    shuffled block tables: one-token decode and a Tq=4 draft window, an
    f32 pool (H=16, D=64) and a bf16 pool (H=16, D=128)."""
    import numpy as np
    import jax.numpy as jnp
    from tpu_mx.kernels.paged_attention import (paged_attention,
                                                paged_attention_reference)
    num_blocks, block_size, nb, heads = 64, 16, 8, 16
    lengths = np.array([5, 17, 64, 121], np.int32)
    rng = np.random.RandomState(6)
    # each row owns a disjoint shuffled run of pool blocks, 0-padded past
    # its real length (the cache's contract)
    perm = rng.permutation(num_blocks)[:len(lengths) * nb].reshape(-1, nb)
    used = -(-lengths // block_size)
    tables = np.where(np.arange(nb)[None, :] < used[:, None], perm,
                      0).astype(np.int32)
    results = {}
    for dtype, dim, tol in ((jnp.float32, 64, 1e-4), (jnp.bfloat16, 128,
                                                      2e-2)):
        pool_shape = (num_blocks, block_size, heads, dim)
        kp = jnp.asarray(rng.standard_normal(pool_shape), dtype)
        vp = jnp.asarray(rng.standard_normal(pool_shape), dtype)
        for tq in (1, 4):
            q = jnp.asarray(
                rng.standard_normal((len(lengths), tq, heads, dim)), dtype)
            out = paged_attention(q, kp, vp, tables, lengths)
            ref = paged_attention_reference(q, kp, vp, tables, lengths)
            err = _max_err(out, ref)
            tag = f"{jnp.dtype(dtype).name}_d{dim}_tq{tq}"
            results[tag] = {"out_err": err}
            if not err <= tol:
                raise AssertionError(f"{tag}: out_err={err} tol={tol}")
    return results


def check_bert_remat_batch512():
    """The full BERT-base remat train step at batch 512 — the exact config
    that OOM'd pre-remat in round 3 (27 GB > 16 GB HBM).  Runs 3 steps and
    records rough seq/s (the bench owns the official number)."""
    import numpy as np
    import tpu_mx as mx
    from tpu_mx import gluon, nd
    from tpu_mx.models.bert import BERTModel, bert_base_config
    from tpu_mx.parallel import CompiledTrainStep
    batch, seq_len = 512, 128
    cfg = bert_base_config(max_len=seq_len)
    net = BERTModel(cfg, dtype="bfloat16", remat=True)
    net.initialize()
    rng = np.random.RandomState(0)
    tokens = rng.randint(4, cfg["vocab_size"], (batch, seq_len)).astype(
        np.int32)
    types = np.zeros((batch, seq_len), np.int32)
    n_masked = max(1, int(0.15 * seq_len))
    positions = np.stack([rng.choice(seq_len, n_masked, replace=False)
                          for _ in range(batch)]).astype(np.int32)
    labels = np.take_along_axis(tokens, positions, axis=1)
    net(nd.array(tokens[:1]), nd.array(types[:1]), None,
        nd.array(positions[:1]))

    class MLMLoss(gluon.loss.Loss):
        def __init__(self, **kw):
            super().__init__(weight=None, batch_axis=0, **kw)
            self._ce = gluon.loss.SoftmaxCrossEntropyLoss()

        def hybrid_forward(self, F, logits, labels):
            vocab = logits.shape[-1]
            return F.mean(self._ce(F.reshape(logits, shape=(-1, vocab)),
                                   F.reshape(labels, shape=(-1,))))

    opt = mx.optimizer.create("lamb", learning_rate=1e-4,
                              multi_precision=True)
    step = CompiledTrainStep(net, MLMLoss(), opt)
    args = (nd.array(tokens), nd.array(types), None, nd.array(positions),
            nd.array(labels))
    fetch = lambda l: float(np.asarray(l._data).ravel()[0])
    loss = step.step(*args)
    first = fetch(loss)
    t0 = time.perf_counter()
    n = 3
    for _ in range(n):
        loss = step.step(*args)
    last = fetch(loss)
    dt = time.perf_counter() - t0
    if not np.isfinite(first) or not np.isfinite(last):
        raise AssertionError(f"non-finite loss: first={first} last={last}")
    return {"batch": batch, "seq_len": seq_len, "remat": True,
            "rough_seqs_per_sec": round(batch * n / dt, 1),
            "loss_first": first, "loss_last": last}


def check_async_checkpoint():
    """Async sharded checkpoint on silicon: save with
    block=False while training keeps stepping (donated buffers are
    overwritten under the in-flight save), then restore into a FRESH step
    and verify the resumed trajectory is numerically identical to the
    original — proof the async machinery snapshotted device state at save
    time, not whatever the buffers held when tensorstore committed."""
    import shutil
    import tempfile
    import numpy as np
    import tpu_mx as mx
    from tpu_mx import gluon, nd
    from tpu_mx.gluon import nn
    from tpu_mx.parallel import CompiledTrainStep

    def build():
        mx.random.seed(42)
        # explicit prefixes: both build() calls must produce identical
        # parameter names (the auto-name counter is process-global)
        net = nn.HybridSequential(prefix="ckptnet_")
        net.add(nn.Dense(256, activation="relu", prefix="fc1_"),
                nn.Dense(10, prefix="fc2_"))
        net.initialize(init="xavier")
        net(nd.zeros((2, 64)))
        opt = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9)
        return CompiledTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                 opt)

    rng = np.random.RandomState(0)
    xs = [nd.array(rng.rand(32, 64).astype(np.float32)) for _ in range(5)]
    ys = [nd.array(rng.randint(0, 10, (32,)).astype(np.float32))
          for _ in range(5)]
    fetch = lambda l: float(np.asarray(l._data).ravel()[0])

    path = tempfile.mkdtemp(prefix="tmx_ckpt_")
    ckpt_dir = os.path.join(path, "step")
    try:
        a = build()
        for i in range(2):
            a.step(xs[i], ys[i])
        a.save_checkpoint(ckpt_dir, block=False)
        # keep training THROUGH the in-flight save: with donate=True these
        # steps overwrite the very buffers being checkpointed
        ref_losses = [fetch(a.step(xs[i], ys[i])) for i in range(2, 5)]
        a.wait_for_checkpoint()

        b = build()
        b.load_checkpoint(ckpt_dir)
        res_losses = [fetch(b.step(xs[i], ys[i])) for i in range(2, 5)]
        err = max(abs(r - s) for r, s in zip(ref_losses, res_losses))
        if err != 0.0:
            raise AssertionError(
                f"resumed trajectory diverged: ref={ref_losses} "
                f"restored={res_losses} max_abs_err={err}")
        return {"ref_losses": ref_losses, "restored_losses": res_losses,
                "bitwise_identical": True}
    finally:
        shutil.rmtree(path, ignore_errors=True)


def check_quantized_inference_jit():
    """INT8 inference through the wrapper's own jax.jit on silicon (the
    r4 16→146 img/s fix): a quantized conv+dense net must match its
    float reference within int8 tolerance AND run as ONE compiled
    program (the jit cache populates), not per-op eager dispatch."""
    import numpy as np
    from tpu_mx import gluon, nd
    from tpu_mx.contrib import quantization as q
    from tpu_mx.gluon import nn

    rng = np.random.RandomState(0)
    net = nn.HybridSequential(prefix="qchipnet_")
    net.add(nn.Conv2D(8, kernel_size=3, padding=1, activation="relu",
                      prefix="c1_"),
            nn.MaxPool2D(pool_size=2),
            nn.Conv2D(16, kernel_size=3, padding=1, activation="relu",
                      prefix="c2_"),
            nn.Dense(32, activation="relu", prefix="d1_"),
            nn.Dense(4, prefix="d2_"))
    net.initialize(init="xavier")
    calib = nd.array(rng.rand(16, 1, 12, 12).astype(np.float32))
    net(calib)
    qnet = q.quantize_net(net, calib_data=calib)
    x = nd.array(rng.rand(8, 1, 12, 12).astype(np.float32))
    ref = net(x).asnumpy()
    out = qnet(x).asnumpy()
    if qnet._jit is None:
        raise AssertionError("quantized net did not take the jit path "
                             "(TPUMX_QUANT_JIT unset should default on)")
    scale = float(np.abs(ref).max()) + 1e-8
    rel = float(np.abs(out - ref).max()) / scale
    if rel > 0.12:
        raise AssertionError(f"int8 divergence {rel:.4f} > 0.12")
    return {"rel_err": rel, "jit_path": True}


def check_device_prefetch_feed():
    """The TPU-grade input feed on silicon: uint8/NHWC batches through
    DevicePrefetchIter(normalize=) must arrive on device as bf16 with
    (x-mean)/std applied in f32 BEFORE the cast, and feed a train step."""
    import numpy as np
    import tpu_mx as mx
    from tpu_mx import gluon, io, nd
    from tpu_mx.gluon import nn
    from tpu_mx.parallel import CompiledTrainStep

    rng = np.random.RandomState(1)
    n, h, w, c = 32, 8, 8, 3
    data = rng.randint(0, 256, (n, h, w, c)).astype(np.uint8)
    labels = rng.randint(0, 4, (n,)).astype(np.float32)
    mean, std = 127.0, 64.0
    base = io.NDArrayIter(data, labels, batch_size=8)
    it = io.DevicePrefetchIter(base, cast_data="bfloat16",
                               normalize=(mean, std))
    batch = next(iter(it))
    xb = batch.data[0]
    if str(xb.dtype) != "bfloat16":
        raise AssertionError(f"feed dtype {xb.dtype}, want bfloat16")
    want = ((data[:8].astype(np.float32) - mean) / std)
    got = xb.asnumpy().astype(np.float32)
    err = float(np.abs(got - want).max())
    if err > 0.02:  # bf16 quantization of a ~[-2, 2] range
        raise AssertionError(f"normalize-before-cast violated: err={err}")

    net = nn.HybridSequential(prefix="feednet_")
    net.add(nn.Dense(16, activation="relu", prefix="f1_"),
            nn.Dense(4, prefix="f2_"))
    net.initialize(init="xavier")
    net(nd.zeros((2, h * w * c)))
    step = CompiledTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.create("sgd", learning_rate=0.1))
    flat = nd.reshape(xb, shape=(8, -1))
    loss = step.step(flat, batch.label[0])
    lval = float(loss.asnumpy().ravel()[0])
    if not np.isfinite(lval):
        raise AssertionError(f"non-finite loss {lval}")
    return {"feed_dtype": "bfloat16", "normalize_err": err,
            "step_loss": lval}


def _consistency_compute(out_path):
    """Shared body of the cross-backend oracle: eager conv+relu+pool+dense
    forward/backward on WHATEVER backend this process has, saved to npz.
    Ends in logits (NOT softmax — sum-of-softmax is constant 1, which
    would zero every gradient and make the comparison vacuous)."""
    import numpy as np
    from tpu_mx import autograd, nd

    rng = np.random.RandomState(3)
    x = rng.rand(4, 6, 6, 3).astype(np.float32)
    w = (rng.rand(8, 3, 3, 3).astype(np.float32) - 0.5) * 0.5
    dw = (rng.rand(10, 8).astype(np.float32) - 0.5) * 0.5
    nds = [nd.array(a) for a in (x, w, dw)]
    for a in nds:
        a.attach_grad()
    with autograd.record():
        xx, ww, dd = nds
        y = nd.Convolution(xx, ww, num_filter=8, kernel=(3, 3),
                           pad=(1, 1), no_bias=True, layout="NHWC")
        y = nd.Activation(y, act_type="relu")
        y = nd.Pooling(y, kernel=(6, 6), pool_type="avg", global_pool=True,
                       layout="NHWC")
        logits = nd.FullyConnected(nd.flatten(y), dd, None, no_bias=True,
                                   num_hidden=10)
        # non-constant scalar: quadratic in the logits, grads exercise
        # every input's backward
        loss = (logits * logits).sum()
    loss.backward()
    np.savez(out_path, out=logits.asnumpy(),
             **{f"g{i}": a.grad.asnumpy() for i, a in enumerate(nds)})


@_highest_precision
def check_cpu_tpu_consistency():
    """SURVEY §4's check_consistency oracle on silicon: the same eager
    conv+relu+pool+dense forward/backward on XLA:CPU and the real chip
    must agree (the reference's [cpu, gpu] cross-backend check, TPU
    edition).  The CPU leg runs in a SUBPROCESS with JAX_PLATFORMS=cpu:
    this process holds the chip, and a CPU-only child is the one kind of
    child it may start (a child that needed the chip would fail or
    hang).  It also keeps the reference leg free of this process's
    default device."""
    import os
    import subprocess
    import sys
    import tempfile
    import numpy as np

    import jax
    if jax.devices()[0].platform != "tpu":
        raise AssertionError("not on a TPU backend")

    with tempfile.TemporaryDirectory(prefix="tmx_consist_") as td:
        cpu_npz = os.path.join(td, "cpu.npz")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        # no trailing empty entry: "REPO:" would make Python treat the
        # CWD as a path entry and risk module shadowing
        extra = env.get("PYTHONPATH")
        env["PYTHONPATH"] = REPO + (os.pathsep + extra if extra else "")
        script = (
            "import sys; sys.path.insert(0, %r); "
            "import tpu_validate; "
            "import jax; assert jax.devices()[0].platform == 'cpu'; "
            "tpu_validate._consistency_compute(%r)"
            % (os.path.dirname(os.path.abspath(__file__)), cpu_npz))
        proc = subprocess.run([sys.executable, "-c", script], env=env,
                              capture_output=True, text=True, timeout=600)
        if proc.returncode != 0:
            # the child's stderr is the only diagnostic there is — fold
            # its tail into the artifact instead of a bare exit status
            raise AssertionError(
                "cpu reference subprocess failed (rc=%d): %s"
                % (proc.returncode, (proc.stderr or "")[-800:]))
        ref = np.load(cpu_npz)

        tpu_npz = os.path.join(td, "tpu.npz")
        _consistency_compute(tpu_npz)      # this process: the real chip
        got = np.load(tpu_npz)

        errs = {}
        for key in ref.files:
            scale = float(np.abs(ref[key]).max()) + 1e-8
            rel = float(np.abs(got[key] - ref[key]).max()) / scale
            errs[key] = rel
            if rel > 2e-3:
                raise AssertionError(
                    f"cpu-vs-tpu mismatch on {key}: rel={rel:.5f}")
    return {"ctxs": ["cpu (subprocess)", "tpu"], "rel_errs": errs}


CHECKS = [
    ("flash_fwd_bwd_vs_dense", check_flash_fwd_bwd_vs_dense),
    ("attention_auto_dispatch", check_attention_auto_dispatch),
    ("flash_bias_layouts", check_flash_bias_layouts),
    ("flash_dropout_inkernel", check_flash_dropout),
    ("dropout_mask_generator", check_dropout_mask_generator),
    ("flash_kv_valid", check_flash_kv_valid),
    ("flash_t2048", check_flash_t2048),
    ("ring_inner_chunking_t2048", check_ring_inner_chunking),
    ("paged_attention_ragged", check_paged_attention_ragged),
    ("bert_remat_batch512", check_bert_remat_batch512),
    ("async_checkpoint_under_training", check_async_checkpoint),
    ("quantized_inference_jit", check_quantized_inference_jit),
    ("device_prefetch_feed", check_device_prefetch_feed),
    ("cpu_tpu_consistency", check_cpu_tpu_consistency),
]


def _write(out, record):
    from tpu_mx.checkpoint import atomic_write
    with atomic_write(out, "w") as f:
        json.dump(record, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="write the record here, after every check; "
                         "without it the record goes to stdout")
    ap.add_argument("--skip-bert", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated check names")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (writes a skip record; "
                         "for checking the runner's control flow)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - {name for name, _ in CHECKS}
        if unknown:
            log(f"unknown --only check(s): {sorted(unknown)}; "
                f"valid: {[n for n, _ in CHECKS]}")
            return 2

    global jax
    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    else:
        # a rerun on the same machine skips straight to execution
        from tpu_mx.runtime import enable_shared_compilation_cache
        enable_shared_compilation_cache()
    devs = jax.devices()
    platform = devs[0].platform
    # the record holds what THIS run executed and nothing else: a row
    # from an earlier run in this run's place would be a green nobody
    # measured
    record = {"ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
              "platform": platform, "n_devices": len(devs), "checks": {}}
    if platform != "tpu":
        record["skipped"] = True
        record["reason"] = f"platform is {platform}, not tpu"
        log(f"not a TPU backend ({platform}); writing skip record")
    else:
        record["skipped"] = False
        for name, fn in CHECKS:
            if only and name not in only:
                continue
            if args.skip_bert and name == "bert_remat_batch512":
                record["checks"][name] = {"ok": None, "skipped": True}
                continue
            log(f"running {name}...")
            t0 = time.perf_counter()
            try:
                detail = fn()
                record["checks"][name] = {
                    "ok": True, "seconds": round(time.perf_counter() - t0, 1),
                    "detail": detail}
                log(f"  {name}: OK ({record['checks'][name]['seconds']}s)")
            except Exception as e:
                record["checks"][name] = {
                    "ok": False, "seconds": round(time.perf_counter() - t0, 1),
                    "error": f"{type(e).__name__}: {e}"[:500],
                    "traceback": traceback.format_exc()[-1500:]}
                log(f"  {name}: FAIL {type(e).__name__}: {e}")
            # after every check: a later hang must not lose earlier rows
            if args.out:
                _write(args.out, record)
    if args.out:
        _write(args.out, record)
    else:
        print(json.dumps(record, indent=1))
    # 0 iff the suite ran on a TPU and every check it executed passed;
    # what --only and --skip-bert left out is left out by request and
    # is absent from (or marked skipped in) the record
    ran = [r for r in record["checks"].values() if not r.get("skipped")]
    ok = not record["skipped"] and all(r["ok"] is True for r in ran)
    log(f"done: {args.out or 'stdout'} (ran={len(ran)} ok={ok})")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
