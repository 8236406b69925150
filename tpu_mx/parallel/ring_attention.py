"""Ring attention: sequence/context parallelism over the ICI ring
(SURVEY §5.7 — a NEW capability, absent in the reference, whose max sequence
length was bounded by one device's memory).

Design: the sequence axis is sharded over mesh axis `sp`.  Each device holds a
(T/n)-length Q block and streams K/V blocks around the ring with
`lax.ppermute`, accumulating flash-attention style online-softmax statistics
(running max m, denominator l, numerator o) so the full T×T attention is
computed in n steps with O(T/n) memory per device and compute/communication
overlap on ICI.  Causal masking uses the rotating K-block index, and
key-padding masks (`valid_length`, the reference-era GluonNLP BERT contract)
ride the same index: each rotating K block masks its own global positions.

The same blockwise kernel with n=1 is the local attention path, so models can
call `attention()` unconditionally and get ring behavior exactly when the
mesh has an `sp` axis.

Attention-prob dropout: on the ring and dense paths the keep-mask is drawn
per (device, ring-step) from a folded key (`random.dropped`: XLA's
rng_bit_generator, drawn once and held for the backward pass, in the room
of the row maximum's tie mask, which `_block_attn` no longer holds); on the
local TPU path it runs inside the Pallas kernel's PRNG
(kernels.flash_attention).  The softmax normalizer always uses the
un-dropped probabilities.
"""
from __future__ import annotations

import functools
import logging
import math
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import random as _random
from .. import telemetry as _telemetry

__all__ = ["ring_attention", "attention", "local_flash_attention",
           "dispatch_counts", "window_blocks"]

_logger = logging.getLogger(__name__)

# Which attention path each distinct call signature took (and, counted by
# models/decoder.py's kda_chunked as it is traced, how often the chunked
# delta rule was).  Deduplicated by
# (path, detail): under jit this is once per compilation; on the eager path
# it is once per new shape/dtype — so a shape regression that silently drops
# the Pallas kernel shows up exactly once, not once per step (VERDICT r1
# weak#6).  Mirrored into profiler counters.
dispatch_counts = {"ring": 0, "ulysses": 0, "pallas_flash": 0,
                   "xla_dense": 0, "kda_chunked": 0}
# For every flash call with a window, as it is traced, a head: the (q block,
# k block) pairs of the square at the blocks the call takes ("grid"), those
# of them that run ("run": the rest lie above the diagonal or wholly before
# every query's window), and the steps the forward kernel's grid walks
# ("walked": the band the window allows, so all but a few of them run).
# Mirrored into the telemetry counter attention.window_blocks{kind=...}.
window_blocks = {"grid": 0, "run": 0, "walked": 0}


def _auto_prefers_flash(q_len, kv_len, dropped, on_tpu):
    """The arm 'auto' takes, from what the call shows: True for the Pallas
    flash kernel, False for XLA dense.  The crossover is one recorded
    measurement (PERF.md section 6, PR 26): both arms pinned in BERT-base's
    bf16 train step on a v5e (H 12, D 64, 24,576 tokens a step, attention
    dropout 0.1), samples/s dense -> flash: T 128 895.7 -> 774.2 (-13.6%),
    T 256 370.5 -> 404.5 (+9.2%), T 512 135.9 -> 203.7 (+49.9%).  Dense
    writes the (q_len, kv_len) scores of every head to HBM, keeps them for
    the backward pass and drew their dropout mask twice (threefry then,
    76 of its 166 ms at T 512; XLA's rng_bit_generator since PR 28, which
    cost this site about the same: PERF.md section 6, PR 28; one draw
    since PR 30, and the crossover was not read again: section 7); the
    kernel holds one head's block in VMEM and
    pays a fixed cost a grid step instead, which is what loses at T 128.
    With that dropout off, ms a step dense against flash: T 256 219.1
    against 235.3, T 512 274.8 against 230.2.
    Only square shapes at D 64 were measured for the crossover, so the
    shorter of the two lengths is held to it; beyond kv 512 the kernel
    always was the choice, for the memory.  Far beyond it, D 128 and T
    16,384 with 28 query heads over 4 key/value heads (PERF.md section 6,
    PR 31, smallthinker-21ba3b.extend16k's traced step, ms a layer): the
    causal kernel forward 22.8 and its backward pair 54.0 (43% and 36% of
    the MXU's peak for the pairs under the mask), with a window of 4,096
    forward 11.9 and backward 27.7, 0.52 of the whole past's for 0.515 of
    its blocks; dense has no arm there (its scores alone would be 28 GiB
    a layer in f32).  The rule itself did not change."""
    if not on_tpu:
        return False
    return kv_len > 512 or min(q_len, kv_len) >= (256 if dropped else 512)


_seen_signatures = set()


def _count(path, detail="", warn=False):
    sig = (path, detail)
    if sig in _seen_signatures:
        return
    _seen_signatures.add(sig)
    dispatch_counts[path] += 1
    try:
        from .. import profiler
        profiler.Counter(f"attention_dispatch_{path}",
                         domain="tpu_mx").increment()
    except Exception:
        pass
    if warn:
        # dense fallback on a TPU backend is a perf bug worth shouting about
        _logger.warning("attention: dense O(T^2) XLA fallback (%s)", detail)
    else:
        _logger.info("attention dispatch: %s %s", path, detail)


def _block_attn(q, k, v, bias=None, mask=None, scale=1.0,
                dropout_rate=0.0, dropout_key=None):
    """One q-block × k-block attention: returns (scores-exp sum stats).
    q: (B, H, Tq, D), k/v: (B, H, Tk, D).  mask: bool, True = attend.
    Dropout hits only the V-accumulation; the denominator l stays
    un-dropped (standard inverted dropout on softmax probs)."""
    # scores and softmax statistics in f32 regardless of input dtype
    # (bf16 exp/max over T keys loses ~3 decimal digits; the MXU
    # accumulates f32 internally anyway, preferred_element_type just
    # keeps it).  Callers cast the normalized output back to q.dtype.
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1)                                   # (B,H,Tq)
    # guard fully-masked rows: exp(-inf - -inf) -> use max(m, finite floor).
    # The maximum is a shift that cancels in o / l (here and through
    # _merge), so its gradient is zero in exact arithmetic; autodiff would
    # pay for it with jnp.max's tie indicator, a (B,H,Tq,Tk) tensor held
    # from forward to backward.  The dropout site below holds its keep
    # mask in that room.
    m_safe = lax.stop_gradient(jnp.maximum(m, -1e30))
    p = jnp.exp(s - m_safe[..., None])                        # (B,H,Tq,Tk)
    l = jnp.sum(p, axis=-1)                                   # (B,H,Tq)
    # probs cast to v.dtype for the AV matmul (flash-kernel numerics: the
    # softmax stats m/l stay f32, only the normalized weights round).  On
    # the dense path p is a materialized (B,H,Tq,Tk) HBM tensor and the
    # default MXU precision truncates f32 dot operands to bf16 anyway —
    # keeping p f32 paid double the HBM bytes for no extra matmul
    # precision; f32 accumulation is preserved via preferred_element_type.
    def weighted(p, v):
        return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32)  # (B,H,Tq,D)
    if dropout_rate > 0.0 and dropout_key is not None:
        # mask and product as one site: alone, the masked probabilities
        # would be held for the product's backward pass.  One draw: the
        # site holds its mask, in the room the row maximum gave up above
        o = _random.dropped(
            lambda keep, p, v: weighted(
                _random.scaled(keep, p, dropout_rate), v),
            dropout_key, dropout_rate, p.shape, p, v, hold=True)
    else:
        o = weighted(p, v)
    return m_safe, l, o


def _merge(m1, l1, o1, m2, l2, o2):
    """Merge two online-softmax partial results."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    l = l1 * a1 + l2 * a2
    o = o1 * a1[..., None] + o2 * a2[..., None]
    return m, l, o


def _ring_chunk(tb, prefer=1024):
    """Static inner-chunk size for one ring step: the per-step score block
    is (Tb, C), NOT (Tb, Tb) — this is what keeps device memory O(T/n·C)
    at long context instead of O((T/n)²).  `prefer` is overridable per
    call (ring_attention(step_chunk=...)); any value that doesn't divide
    Tb falls down the power-of-two ladder."""
    if tb <= prefer:
        return tb
    if tb % prefer == 0:
        return prefer
    for c in (512, 256, 128):
        if c <= prefer and tb % c == 0:
            return c
    return tb


def _ring_body(q, k, v, valid, seed, bias, *, axis_name, causal, scale,
               rate, masked, dropped, biased, key_axes=(),
               step_chunk=None):
    """Runs inside shard_map: q/k/v are LOCAL blocks (B, H, Tb, D);
    valid (B,) global key counts (replicated over the ring) or a dummy;
    seed (1,) int32 or a dummy — staticness comes from masked/dropped;
    bias is this device's (B|1, H|1, Tb, T_global) row-slice of the
    attention bias (ALiBi, relative position, …): each ring step slices
    the columns belonging to the K block it currently holds.
    key_axes: every mesh axis the q spec shards over — each device's
    dropout key folds in ALL its coordinates, so shards that differ only
    in dp/tp draw independent masks (not the same mask on different data)."""
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    B, H, Tb, D = q.shape
    # f32 carries: _block_attn emits f32 stats/partials (see its score
    # comment); the final normalize casts back to q.dtype
    neg = jnp.full((B, H, Tb), -1e30, jnp.float32)
    zero_l = jnp.zeros((B, H, Tb), jnp.float32)
    zero_o = jnp.zeros(q.shape, jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    base_key = None
    if dropped:
        # tpumx-lint: disable=determinism -- key is a pure function of the
        # caller-provided seed input (traced), not a hidden fresh stream
        base_key = jax.random.PRNGKey(seed[0])
        for ax in key_axes:
            base_key = jax.random.fold_in(base_key, lax.axis_index(ax))

    C = _ring_chunk(Tb, step_chunk) if step_chunk else _ring_chunk(Tb)
    nchunks = Tb // C
    qpos = my_idx * Tb + jnp.arange(Tb)

    def _sub_attn(m, l, o, k_idx, i, ci, k_sub, v_sub):
        """One (Tb, C) sub-block of the current ring step: masks/bias/
        dropout keys all derive from the GLOBAL key position of the
        chunk, so chunking changes memory, not math (dropout draws are
        keyed per (step, chunk) instead of per step — an equally valid
        stream, noted in the docstring)."""
        kpos = k_idx * Tb + ci * C + jnp.arange(C)
        mask = None
        if causal:
            mask = (qpos[:, None] >= kpos[None, :])[None, None]
        if masked:
            km = kpos[None, None, None, :] < valid[:, None, None, None]
            mask = km if mask is None else jnp.logical_and(mask, km)
        b_blk = None
        if biased:
            b_blk = lax.dynamic_slice_in_dim(bias, k_idx * Tb + ci * C, C,
                                             axis=3)
        key_i = (jax.random.fold_in(base_key, i * nchunks + ci)
                 if dropped else None)
        bm, bl, bo = _block_attn(q, k_sub, v_sub, bias=b_blk, mask=mask,
                                 scale=scale,
                                 dropout_rate=rate if dropped else 0.0,
                                 dropout_key=key_i)
        return _merge(m, l, o, bm, bl, bo)

    def step(carry, i):
        m, l, o, k_cur, v_cur = carry
        k_idx = (my_idx - i) % n  # whose K block we currently hold
        if nchunks == 1:
            m, l, o = _sub_attn(m, l, o, k_idx, i, 0, k_cur, v_cur)
        else:
            def kchunk(c2, ci):
                m2, l2, o2 = c2
                k_sub = lax.dynamic_slice_in_dim(k_cur, ci * C, C, axis=2)
                v_sub = lax.dynamic_slice_in_dim(v_cur, ci * C, C, axis=2)
                return _sub_attn(m2, l2, o2, k_idx, i, ci, k_sub,
                                 v_sub), None

            (m, l, o), _ = lax.scan(kchunk, (m, l, o),
                                    jnp.arange(nchunks))
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (m, l, o, k_nxt, v_nxt), None

    (m, l, o, _, _), _ = lax.scan(
        step, (neg, zero_l, zero_o, k, v), jnp.arange(n))
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def ring_attention(q, k, v, mesh, axis_name="sp", causal=False,
                   q_spec=None, valid_length=None, dropout_rate=0.0,
                   dropout_key=None, bias=None, batch_axes=("dp", "tp"),
                   step_chunk=None, window=None):
    """Sequence-parallel attention.  q/k/v: GLOBAL (B, H, T, D) arrays whose
    T axis is sharded over `axis_name`.  Returns attention output with the
    same sharding.  `q_spec` overrides the default
    P(batch_axes[0], batch_axes[1], axis_name, None) layout (axes absent
    from the mesh are dropped automatically; pass `batch_axes` to rename
    the batch/heads mesh axes without a full spec).
    valid_length: (B,) int32 valid key counts (global positions).
    dropout_rate/dropout_key: attention-prob dropout, drawn per ring step.
    bias: (B|1, H|1, T, T) additive attention bias (ALiBi, relative
    position, …) — rows shard with q over `axis_name`, columns stay whole
    and are sliced per ring step to match the rotating K block.
    window: refused by name (ValueError), never dropped."""
    _refuse_window(window, "ring_attention")

    def present(ax):
        return ax in mesh.axis_names

    bax, hax = (tuple(batch_axes) + (None, None))[:2]
    spec = q_spec or P(bax if bax and present(bax) else None,
                       hax if hax and present(hax) else None,
                       axis_name if present(axis_name) else None,
                       None)
    scale = 1.0 / math.sqrt(q.shape[-1])
    dropped = dropout_rate > 0.0 and dropout_key is not None
    if not present(axis_name):
        # no sequence axis: plain (flash-style blockwise on one device)
        mask = _dense_mask(q.shape[2], k.shape[2], causal, valid_length)
        m, l, o = _block_attn(q, k, v, bias=bias, mask=mask, scale=scale,
                              dropout_rate=dropout_rate if dropped else 0.0,
                              dropout_key=dropout_key)
        return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)

    _count("ring", f"sp={mesh.shape[axis_name]} shape={q.shape}")
    masked = valid_length is not None
    biased = bias is not None
    valid, seed, vspec = _sp_valid_seed(q, masked, dropped, valid_length,
                                        dropout_key, spec)
    bias_arr = bias if biased else jnp.zeros((1, 1, q.shape[2], 1), q.dtype)
    # valid is per-batch → shard like q's batch axis; seed replicated;
    # bias rows follow the q sharding (batch/head axes only when the bias
    # actually carries them), columns replicated
    bspec = P(spec[0] if biased and bias_arr.shape[0] > 1 else None,
              spec[1] if biased and bias_arr.shape[1] > 1 else None,
              spec[2], None)
    key_axes = tuple(ax for ax in spec if ax is not None)
    fn = jax.shard_map(
        functools.partial(_ring_body, axis_name=axis_name, causal=causal,
                          scale=scale, rate=float(dropout_rate),
                          masked=masked, dropped=dropped, biased=biased,
                          key_axes=key_axes, step_chunk=step_chunk),
        mesh=mesh, in_specs=(spec, spec, spec, vspec, P(None), bspec),
        out_specs=spec, check_vma=False)
    return fn(q, k, v, valid, seed, bias_arr)


def _sp_valid_seed(q, masked, dropped, valid_length, dropout_key, spec):
    """Shared shard_map prologue for the sp strategies (ring, ulysses):
    the (B,) valid-key counts, the scalar dropout seed, and the valid
    spec.  Dummies keep the jitted signature static when a feature is
    off."""
    B = q.shape[0]
    valid = (jnp.asarray(valid_length, jnp.int32) if masked
             else jnp.zeros((B,), jnp.int32))
    seed = (jax.random.randint(dropout_key, (1,), 0, 2 ** 31 - 1, jnp.int32)
            if dropped else jnp.zeros((1,), jnp.int32))
    vspec = P(spec[0]) if masked else P(None)
    return valid, seed, vspec


def _refuse_window(window, arm):
    if window is not None:
        raise ValueError(
            f"{arm}: window={window} is not implemented on the sequence-"
            "parallel arms (a window reaches over at most two ring steps); "
            "run windowed layers without an `sp` axis")


def _dense_mask(t, tk, causal, valid_length, window=None):
    """Combined causal (with its window: query i sees keys i - window < j
    <= i) + key-padding mask, or None.  True = attend."""
    mask = None
    if window is not None and not causal:
        raise ValueError(f"window={window} needs causal=True")
    if causal:
        mask = (jnp.arange(t)[:, None] >= jnp.arange(tk)[None, :])[None, None]
        if window is not None:
            mask &= (jnp.arange(t)[:, None] - jnp.arange(tk)[None, :]
                     < window)[None, None]
    if valid_length is not None:
        km = (jnp.arange(tk)[None, None, None, :] <
              jnp.asarray(valid_length, jnp.int32)[:, None, None, None])
        mask = km if mask is None else jnp.logical_and(mask, km)
    return mask


def local_flash_attention(q, k, v, causal=False, valid_length=None,
                          dropout_rate=0.0, dropout_key=None, bias=None,
                          window=None):
    """Single-device attention with the same numerics as the ring kernel.
    On TPU with tile-friendly shapes this runs the Pallas flash kernel
    (tpu_mx.kernels.flash_attention: blockwise online softmax, O(T) memory,
    in-kernel padding mask, prob dropout, and additive bias — ALiBi/
    relative-position tensors stream block-by-block with a differentiable
    d_bias); otherwise the XLA dense path.  `window` (with causal): query i
    sees keys i - window < j <= i.  k and v may have fewer heads than q
    (grouped queries: head h reads key/value head h // (H / H_kv)); the
    kernel reads them where they lie, the dense path repeats them."""
    from ..kernels import flash_attention as fa
    on_tpu = jax.default_backend() == "tpu"
    dropped = dropout_rate > 0.0 and dropout_key is not None
    rate = float(dropout_rate) if dropped else 0.0
    # TPUMX_ATTENTION=dense|flash|auto (default auto): 'auto' follows
    # _auto_prefers_flash's measured crossover; 'flash'/'dense' pin the arm,
    # which is how both are read on the chip ('flash' only where
    # supported() holds; 'dense' always works).
    mode = os.environ.get("TPUMX_ATTENTION", "auto")
    if mode not in ("auto", "dense", "flash"):
        raise ValueError(f"TPUMX_ATTENTION must be auto|dense|flash, "
                         f"got {mode!r}")
    want_flash = (mode == "flash" and on_tpu) or (
        mode == "auto" and _auto_prefers_flash(q.shape[2], k.shape[2],
                                               dropped, on_tpu))
    grouped = k.shape[1] != q.shape[1]
    # what only the new calls show, so that the old ones count as they did
    new = (f" kv_heads={k.shape[1]}" if grouped else "") + \
        (f" window={window}" if window is not None else "")
    if want_flash and fa.supported(
            q.shape, q.dtype, kv_len=k.shape[2], dropout_rate=rate,
            kv_heads=k.shape[1], window=window, causal=causal,
            plain=valid_length is None and bias is None):
        _count("pallas_flash", f"shape={q.shape}{new}")
        if window is not None:
            grid, run = fa.blocks_run(q.shape[2], k.shape[2], causal, window)
            walked = fa.steps_walked(q.shape[2], k.shape[2], window,
                                     biased=bias is not None)
            for kind, n in (("grid", grid), ("run", run), ("walked", walked)):
                window_blocks[kind] += n
                _telemetry.counter("attention.window_blocks",
                                   kind=kind).inc(n)
        seed = (jax.random.randint(dropout_key, (1,), 0, 2 ** 31 - 1,
                                   jnp.int32) if dropped else None)
        return fa.mha_flash_attention(q, k, v, causal=causal,
                                      valid_length=valid_length,
                                      dropout_rate=rate, dropout_seed=seed,
                                      bias=bias, window=window)
    # CPU dense is expected, and a DELIBERATE dense choice (the A/B pin,
    # or auto's measured short-T preference) must not fire the
    # perf-regression warning — it exists for wanted-but-unsupported flash
    _count("xla_dense",
           f"shape={q.shape} dtype={q.dtype} kv_len={k.shape[2]}{new}",
           warn=want_flash)
    scale = 1.0 / math.sqrt(q.shape[-1])
    mask = _dense_mask(q.shape[2], k.shape[2], causal, valid_length, window)
    if grouped:
        if q.shape[1] % k.shape[1]:
            raise ValueError(f"{q.shape[1]} query heads over {k.shape[1]} "
                             "key/value heads")
        k, v = (jnp.repeat(a, q.shape[1] // k.shape[1], axis=1)
                for a in (k, v))
    m, l, o = _block_attn(q, k, v, bias=bias, mask=mask, scale=scale,
                          dropout_rate=rate, dropout_key=dropout_key)
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def attention(q, k, v, mesh=None, causal=False, valid_length=None,
              dropout_rate=0.0, dropout_key=None, bias=None,
              sp_strategy=None, window=None):
    """Dispatch: sequence-parallel attention when a mesh with an `sp` axis
    is active (strategy 'ring' or 'ulysses' — per-call `sp_strategy`, else
    the module default set via `parallel.set_sp_strategy`; ulysses needs
    H % sp == 0 and quietly falls back to ring otherwise), local flash
    when not.  valid_length (B,) masks padded keys; dropout is
    attention-prob dropout (pass a key only in training mode); bias is an
    additive (B|1, H|1, Tq, Tk) attention bias (ALiBi, relative pos);
    window and grouped key/value heads as local_flash_attention says (the
    sequence-parallel arms refuse a window by name)."""
    if sp_strategy is not None and sp_strategy not in ("ring", "ulysses"):
        # validate on EVERY call, not just sp>1 meshes — a typo must not
        # silently select the local path
        raise ValueError(
            f"unknown sp_strategy {sp_strategy!r}; use 'ring' or "
            "'ulysses'")
    if mesh is not None and "sp" in mesh.axis_names and \
            mesh.shape["sp"] > 1:
        from .ulysses import get_sp_strategy, ulysses_attention
        strategy = sp_strategy or get_sp_strategy()
        # ulysses preconditions: heads divide sp, and no REAL head-axis
        # sharding (size-1 tp is fine) — otherwise quiet ring fallback
        if strategy == "ulysses" and q.shape[1] % mesh.shape["sp"] == 0 \
                and mesh.shape.get("tp", 1) == 1:
            return ulysses_attention(q, k, v, mesh, causal=causal,
                                     valid_length=valid_length,
                                     dropout_rate=dropout_rate,
                                     dropout_key=dropout_key, bias=bias,
                                     window=window)
        return ring_attention(q, k, v, mesh, causal=causal,
                              valid_length=valid_length,
                              dropout_rate=dropout_rate,
                              dropout_key=dropout_key, bias=bias,
                              window=window)
    return local_flash_attention(q, k, v, causal=causal,
                                 valid_length=valid_length,
                                 dropout_rate=dropout_rate,
                                 dropout_key=dropout_key, bias=bias,
                                 window=window)
