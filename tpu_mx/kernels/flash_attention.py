"""Flash attention as a Pallas TPU kernel.

TPU-native replacement for the reference's attention compute (the reference
has no fused attention at all — MXNet 1.x predates it; BERT-era GluonNLP
composed it from batch_dot + softmax, materializing the full T×T score
matrix).  This kernel computes attention blockwise with online softmax:
O(T) memory per core instead of O(T²), MXU-shaped (Bq×D)·(D×Bk) matmuls,
fp32 accumulation regardless of input dtype.

Layout: q/k/v are (BH, T, D) — batch*heads collapsed.  Grid is
(BH, T/Bq, T/Bk) with the K dimension innermost; VMEM scratch carries the
running (m, l, acc) statistics across K steps, and the output block is
written on the last K step (the standard sequential-grid accumulation
pattern).  The backward pass is two more Pallas kernels (dq and dk/dv),
using the saved logsumexp — the flash attention recompute trick.

Key-padding masks: `kv_valid` (BH,) int32 gives each row's number of valid
keys; key columns ≥ valid are masked to -inf and K blocks entirely beyond
valid are skipped (ragged batches pay only for their real length).  The
reference-era GluonNLP BERT consumed the same information as `valid_length`.

Attention-prob dropout runs INSIDE the kernel via the TPU PRNG
(`pltpu.prng_seed` / `prng_random_bits`), seeded per (seed, bh, qblk, kblk)
so the backward kernels regenerate bit-identical masks — no T×T mask is
ever materialized.  The softmax normalizer uses the un-dropped
probabilities (standard inverted dropout on the probs).  The TPU PRNG has
no CPU/interpret lowering, so dropout>0 requires a real TPU; callers gate
via `supported()`.

Window and grouped heads (ISSUE 31; the band, ISSUE 35).  With `causal` and
`window=W`, query i sees keys i - W < j <= i, and the grid is the band the
window allows and not the square: the innermost axis of the forward and dq
grids has `nb` steps, the most key blocks any query block's windows touch
(`_band_steps`), and step j of query block i is key block lo(i) + j, lo(i)
the first block that holds a key the block's first query sees (`_k_band`);
the dk/dv grid walks, for each of a key block's query heads, the `nbq` query
blocks from the first that sees into it (`_q_band`).  At T 8,192 under W 512
that is 32 steps a head and kernel of which 31 run, where the square of 512 x
1,024 blocks walked 128 for 23.  The few steps of the band that still lie
outside the mask (past the diagonal, or past the sequence's end) are skipped,
and the index maps clamp them to the nearest block that runs, so that their
K/V (or, in the dk/dv kernel, q/do) tiles are not copied in again; edge blocks
are masked.  The blocks that run, their order and their arithmetic are the
square grid's: at equal blocks not a bit of output or gradient differs.  The
key block is no wider than the window (`_blocks`: the largest power of two <=
min(1024, W) that divides Tk, at least 128; W 512 -> 512 x 512, W 4,096 ->
512 x 1,024): what an edge block holds beyond the window is computed and
masked away, and the arms read on the chip are in docs/performance.md.  A
bias keeps the square under a window (its blocks, and its gradient's, are
streamed by grid position).  With k and v of BH / G rows (`G` query heads
share a key/value head, row b reads row b // G), K and V are never repeated
in HBM: the block maps send the query row to its key/value row, and the
dk/dv kernel walks the G query heads of its row on its innermost grid axis
and sums them in its scratch.  Without a window the three kernels keep the
grids (BH, T/Bq, Tk/Bk) and (BH_kv, Tk/Bk, G·T/Bq) and the index maps they
had.

The rows' layout (ISSUE 38).  A row statistic is a column of the tile from
the moment it is read to the moment it is stored, and no kernel body forms a
(block_q,) vector.  In the forward kernel the running maximum, the running
sum and the factor exp(m_prev - m_cur) are (block_q, 128) values with every
lane alike, as the scratch holds them: the row reductions keep their
dimension (their results come out of the cross-lane unit on every lane),
`_lanes` repeats whole registers over the key block and over a head wider
than 128 and takes the leading lanes of a narrower one, and at D 128 the
accumulator is rescaled element by element.  `lse` is written from one lane
column and stays (BH, T, 1) in HBM; the backward kernels read it and `delta`
as (block_q, 1) columns.  The arithmetic, its order and its dtypes are what
they were with (block_q,) vectors, bit for bit; what went is the broadcast of
each such vector over the lanes again, 256 cross-lane permutes a grid step at
512 query rows (docs/performance.md, "The rows' layout").
tests/test_flash_row_layout.py guards the bodies' jaxprs.

Falls back to interpret mode off-TPU so tests run anywhere.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "mha_flash_attention", "supported",
           "blocks_run", "steps_walked", "FLASH_SCOPES"]

# jax.named_scope names around the three kernel calls (HLO metadata only):
# an operation's op path ends `.../jit(_bwd_call)/flash.dq/pallas_call`,
# whoever calls; benchmark/pass_scopes.py holds them as literals
FLASH_SCOPES = ("flash.fwd", "flash.dq", "flash.dkv")
_FWD_SCOPE, _DQ_SCOPE, _DKV_SCOPE = FLASH_SCOPES

NEG_INF = -1e30
# Largest (Bq × Bk) f32 score block we let the kernel materialize in VMEM:
# 512×1024×4B = 2 MiB, the tuned default product.  _pick_block's single-block
# fall-through for awkward T is allowed only under this bound (VERDICT r2
# weak#6: T with no power-of-two divisor silently ran block=T at any size).
MAX_BLOCK_ELEMS = 512 * 1024
# lanes of a vector register: the width of the statistics' scratch
_LANES = 128


def _cdiv(a, b):
    return (a + b - 1) // b


def _interpret():
    return jax.default_backend() != "tpu"


def _keep_mask(seed_ref, b, qi, ki, rate, block_q, block_k):
    """Regenerable dropout keep-mask for score block (qi, ki) of batch b.
    Seeding immediately before the draw makes the bits a pure function of
    (seed, b, qi, ki), so fwd / dq / dkv kernels all see the same mask.
    Mosaic on some TPUs caps prng_seed at two scalar values, so the tuple
    is folded injectively into two int32 lanes: (seed ⊕ b·φ, qi·2¹⁶+ki)
    with φ = 0x9E3779B9 (odd ⇒ b·φ bijective mod 2³²) — distinct
    (b, qi, ki) give distinct lanes for a fixed seed, needing qi < 2¹⁶
    AND ki < 2¹⁶ (both hold for any T the VMEM guard admits).  The
    multiply-XOR (rather than seed+b) keeps arithmetically related seeds
    across calls — counters, seed+layer schemes — from aligning whole
    rows' masks."""
    pltpu.prng_seed(seed_ref[0] ^ (b * -1640531527), qi * 65536 + ki)
    bits = pltpu.prng_random_bits((block_q, block_k))
    bits = pltpu.bitcast(bits, jnp.uint32)
    thresh = jnp.uint32(min(int(rate * (2 ** 32)), 2 ** 32 - 1))
    return bits >= thresh


def _lanes(x, n):
    """A row statistic x, (rows, 128) with every lane alike, at n lanes: for
    the score tile (n the key block) and the accumulator (n the head size).
    Whole tiles are repeated and under 128 the leading lanes are taken, so
    no row vector is formed and no lane is broadcast; only a width over 128
    that is no multiple of it (a single key block over an odd length, a head
    of 192) broadcasts one lane column."""
    if n == _LANES:
        return x
    if n < _LANES:
        return x[:, :n]
    if n % _LANES:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
    return jnp.tile(x, (1, n // _LANES))


def _score_mask(s, valid, causal, qi, ki, block_q, block_k, window=None):
    """Apply causal (with its window, if any) and/or key-padding masks to a
    score block.  A row may lose every key of an edge block: NEG_INF is
    finite, so what such a row accumulates there is wiped by the factor
    exp(NEG_INF - m) = 0 once a block with a key it sees (its diagonal
    block at the latest) sets its maximum."""
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    if causal:
        qpos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        s = jnp.where(qpos >= kpos, s, NEG_INF)
        if window is not None:
            s = jnp.where(qpos - kpos < window, s, NEG_INF)
    if valid is not None:
        s = jnp.where(kpos < valid, s, NEG_INF)
    return s


def _run_cond(causal, valid, qi, ki, block_q, block_k, window=None):
    """Whether block (qi, ki) can contribute at all: on/below the causal
    diagonal, holding a key inside some query's window, AND not entirely
    beyond the valid key length."""
    cond = None
    if causal:
        cond = qi * block_q + block_q - 1 >= ki * block_k
        if window is not None:
            # `&`: blocks_run() asks with numpy indices, inside a trace too
            cond = cond & (ki * block_k + block_k - 1 > qi * block_q - window)
    if valid is not None:
        c = ki * block_k < valid
        cond = c if cond is None else jnp.logical_and(cond, c)
    return True if cond is None else cond


# ----------------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------------
def _fwd_kernel(*refs, scale, causal, masked, rate, biased, block_q,
                block_k, window=None, band=None):
    (q_ref, k_ref, v_ref), bias_ref, valid_ref, seed_ref, tail = \
        _split_refs(refs, 3, masked, rate, biased)
    o_ref, lse_ref, m_scr, l_scr, acc_scr = tail

    b = pl.program_id(0)
    qi = pl.program_id(1)
    step = pl.program_id(2)
    nsteps = pl.num_programs(2)
    ki = step if band is None else \
        _k_band(qi, window, block_q, block_k, band)[0] + step
    valid = valid_ref[jax.lax.rem(b, _VALID_BLOCK)] if masked else None

    @pl.when(step == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute():
        # dots run in the INPUT dtype with f32 accumulation: on the MXU a
        # dot with f32 operands is emulated in multiple bf16 passes, so
        # upcasting bf16 q/k/v before the dot tripled the matmul cost for
        # precision the softmax stats (kept f32 throughout) never needed
        q = q_ref[0]                                          # (Bq, D)
        k = k_ref[0]                                          # (Bk, D)
        v = v_ref[0]                                          # (Bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if biased:
            s = s + bias_ref[0].astype(jnp.float32)           # (Bq, Bk)
        s = _score_mask(s, valid, causal, qi, ki, block_q, block_k, window)
        # a row's statistics stay columns of the tile, (Bq, 128) with every
        # lane alike, from the scratch and back to it (module docstring)
        m_prev = m_scr[...]                                   # (Bq, 128)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - _lanes(m_cur, block_k))               # (Bq, Bk)
        # normalizer uses the un-dropped probs; only the V-accumulation is
        # dropped (inverted dropout on softmax(s))
        l_cur = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if rate > 0.0:
            keep = _keep_mask(seed_ref, b, qi, ki, rate, block_q, block_k)
            p_acc = jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)
        else:
            p_acc = p
        acc_scr[:] = acc_scr[:] * _lanes(alpha, acc_scr.shape[1]) + \
            jax.lax.dot_general(
                p_acc.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        m_scr[...] = m_cur
        l_scr[...] = l_cur

    run = _run_cond(causal, valid, qi, ki, block_q, block_k, window)
    if band is not None:
        run = run & (ki < band)
    if run is True:
        _compute()
    else:
        pl.when(run)(_compute)

    @pl.when(step == nsteps - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[...], 1e-30)               # (Bq, 128)
        o_ref[0] = (acc_scr[:] / _lanes(l_safe, acc_scr.shape[1])).astype(
            o_ref.dtype)
        lse_ref[0] = (m_scr[:, :1] + jnp.log(l_safe[:, :1])).astype(
            jnp.float32)


def _split_refs(refs, n_fixed, masked, rate, biased=False):
    """Unpack a kernel's ref list: (fixed input refs, bias_ref, valid_ref,
    seed_ref, outputs+scratch tail).  The optional bias VMEM block comes
    right after the fixed inputs; the optional SMEM scalars follow, in
    (valid, seed) order."""
    i = n_fixed
    bias_ref = None
    if biased:
        bias_ref = refs[i]
        i += 1
    valid_ref = None
    if masked:
        valid_ref = refs[i]
        i += 1
    seed_ref = None
    if rate > 0.0:
        seed_ref = refs[i]
        i += 1
    return refs[:n_fixed], bias_ref, valid_ref, seed_ref, refs[i:]


def _bias_spec(bias, bh, bq, bk, swap=False):
    """BlockSpec for the (BHB, T, Tk) bias: BHB may be BH (per-row), H
    (shared across batch; picked via b %% H) or 1 (fully shared).  With
    swap=True the grid is (b, kblk, qblk) — the dkv kernel's order."""
    bhb = bias.shape[0]
    if bhb == bh:
        row = lambda b: b
    elif bhb == 1:
        row = lambda b: 0
    else:  # per-head, shared over batch: fold index b = batch*H + h
        h = bhb
        row = lambda b: jax.lax.rem(b, h)
    if swap:
        return pl.BlockSpec((1, bq, bk), lambda b, j, i: (row(b), i, j),
                            memory_space=pltpu.VMEM)
    return pl.BlockSpec((1, bq, bk), lambda b, i, j: (row(b), i, j),
                        memory_space=pltpu.VMEM)


# SMEM block length for the per-batch valid-key vector.  Real Mosaic
# requires rank-1 blocks to be the whole array or a multiple of the
# 128-lane tiling (interpret mode accepts (1,) blocks, the r4 chip did
# not) — so the (BH,) vector is padded to a 128 multiple, streamed in
# (128,) blocks selected by b // 128, and indexed b % 128 in-kernel.
_VALID_BLOCK = 128


def _pad_valid(kv_valid):
    bh = kv_valid.shape[0]
    padded = _cdiv(bh, _VALID_BLOCK) * _VALID_BLOCK
    if padded != bh:
        kv_valid = jnp.pad(kv_valid, (0, padded - bh))
    return kv_valid


def _extra_specs_and_args(kv_valid, seed):
    """(in_specs tail, args tail) for the optional valid/seed SMEM scalars.
    Index maps ignore the grid position except the leading batch axis."""
    specs, args = [], []
    if kv_valid is not None:
        specs.append(pl.BlockSpec((_VALID_BLOCK,),
                                  lambda b, i, j: (b // _VALID_BLOCK,),
                                  memory_space=pltpu.SMEM))
        args.append(_pad_valid(kv_valid))
    if seed is not None:
        specs.append(pl.BlockSpec((1,), lambda b, i, j: (0,),
                                  memory_space=pltpu.SMEM))
        args.append(seed)
    return specs, args


def _k_band(qi, window, bq, bk, nk, xp=jnp):
    """(first, last) of the key blocks that hold a key some query of block
    qi sees under the window; first > last where there is none (T > Tk)."""
    return (xp.maximum(qi * bq - window + 1, 0) // bk,
            xp.minimum((qi * bq + bq - 1) // bk, nk - 1))


def _q_band(ki, window, bq, bk, nq, xp=jnp):
    """(first, last) of the query blocks that hold a query which sees a key
    of block ki under the window; first > last where there is none."""
    return ((ki * bk) // bq,
            xp.minimum((ki * bk + bk + window - 2) // bq, nq - 1))


def _banded(window, biased):
    """Whether a call's innermost grid axis walks the window's band and not
    the whole square: a bias keeps the square, because its blocks, and its
    gradient's, are streamed by grid position and every one has to be
    written."""
    return window is not None and not biased


def _band_steps(t, tk, window, bq, bk):
    """(nb, nbq): the most key blocks a query block's windows touch, and the
    most query blocks that see into one key block: the innermost extents of
    the forward and dq grids and, a query head, of the dk/dv grid."""
    nq, nk = _cdiv(t, bq), _cdiv(tk, bk)
    lo, hi = _k_band(np.arange(nq), window, bq, bk, nk, np)
    qlo, qhi = _q_band(np.arange(nk), window, bq, bk, nq, np)
    return (max(int(np.max(hi - lo)) + 1, 1),
            max(int(np.max(qhi - qlo)) + 1, 1))


def _kv_index(group, window, bq, bk, nk, banded=False):
    """Index map (b, qblk, step) -> K/V block, for the forward and dq grids:
    query row b reads key/value row b // group.  With a window, step is the
    key block itself on the square grid and counts from the band's first
    block on the banded one; either way a step that is skipped stays on the
    nearest block that runs, and a block that does not change is not copied
    in again."""
    if group == 1 and window is None:
        return lambda b, i, j: (b, j, 0)

    def index(b, i, j):
        if window is not None:
            lo, hi = _k_band(i, window, bq, bk, nk)
            j = jnp.clip(lo + j if banded else j, lo, hi)
        return (b // group, j, 0)
    return index


def _q_index(group, window, bq, bk, nq, nbq=None):
    """Index map (b, kblk, step) -> block of q, do, lse or delta, for the
    dk/dv grid: key/value row b is read by the query rows b·group …
    b·group + group - 1, of each of which the innermost axis walks the q
    blocks in turn: all nq of them on the square grid, on the banded one
    nbq, from the band's first; the window's clamp as in _kv_index."""
    if group == 1 and window is None:
        return lambda b, j, i: (b, i, 0)
    per_head = nbq or nq

    def index(b, j, step):
        i = step
        if group > 1:
            b, i = b * group + step // per_head, jax.lax.rem(step, per_head)
        if window is not None:
            lo, hi = _q_band(j, window, bq, bk, nq)
            i = jnp.clip(lo + i if nbq else i, lo, hi)
        return (b, i, 0)
    return index


def _blocks(t, tk, block_q=None, block_k=None, window=None):
    """The (q, k) block sizes a call runs in: the caller's, or the tuned
    defaults, never beyond the sequence.  Under a window the key block is no
    wider than the window (down to 128): what an edge block holds beyond
    the window is computed and masked away (docs/performance.md has the
    arms read on the chip)."""
    prefer_k = 1024
    if window is not None:
        prefer_k = min(1024, max(128, 1 << (int(window).bit_length() - 1)))
    return (min(block_q or _pick_block(t, 512), t),
            min(block_k or _pick_block(tk, prefer_k), tk))


def blocks_run(t, tk, causal=True, window=None, block_q=None, block_k=None):
    """(blocks in the (q block, k block) square of one head, blocks of it
    that run), by the kernels' own _run_cond on the whole square at once; at
    the block sizes flash_attention() would take."""
    bq, bk = _blocks(t, tk, block_q, block_k, window)
    qi, ki = np.meshgrid(np.arange(_cdiv(t, bq)), np.arange(_cdiv(tk, bk)),
                         indexing="ij")
    run = _run_cond(causal, None, qi, ki, bq, bk, window)
    return qi.size, qi.size if run is True else int(np.sum(run))


def steps_walked(t, tk, window=None, block_q=None, block_k=None,
                 biased=False):
    """The steps the forward grid walks a head, at the block sizes
    flash_attention() would take: under a window the band, T/bq rows of the
    most key blocks a row's windows touch; without one, or with a bias
    (whose blocks are streamed by grid position), the whole square."""
    bq, bk = _blocks(t, tk, block_q, block_k, window)
    nb = _band_steps(t, tk, window, bq, bk)[0] \
        if _banded(window, biased) else _cdiv(tk, bk)
    return _cdiv(t, bq) * nb


# The kernel calls are jitted with `interpret` among the static arguments: a
# model's layers then share one traced and lowered copy of each kernel
# (twelve BERT layers lowered 36 pallas_calls one by one: a second a step
# program and 300 KB of module text, paid in every process, compile-cache
# hit or not), and a trace made in interpret mode is never taken for a
# Mosaic one.
@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11, 12))
def _fwd(q, k, v, kv_valid, seed, bias, scale, causal, rate, block_q,
         block_k, interpret, window=None):
    bh, t, d = q.shape
    tk = k.shape[1]
    block_q = min(block_q, t)
    block_k = min(block_k, tk)
    nq, nk = _cdiv(t, block_q), _cdiv(tk, block_k)
    masked = kv_valid is not None
    biased = bias is not None
    banded = _banded(window, biased)
    grid = (bh, nq, _band_steps(t, tk, window, block_q, block_k)[0]
            if banded else nk)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               masked=masked, rate=rate, biased=biased,
                               block_q=block_q, block_k=block_k,
                               window=window, band=nk if banded else None)
    kv_index = _kv_index(bh // k.shape[0], window, block_q, block_k, nk,
                         banded)
    bias_specs, bias_args = ([], [])
    if biased:
        bias_specs = [_bias_spec(bias, bh, block_q, block_k)]
        bias_args = [bias]
    extra_specs, extra_args = _extra_specs_and_args(
        kv_valid, seed if rate > 0.0 else None)
    extra_specs = bias_specs + extra_specs
    extra_args = bias_args + extra_args
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kv_index, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kv_index, memory_space=pltpu.VMEM),
        ] + extra_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            # lse rides as (BH, T, 1): TPU block rules need the last two
            # block dims divisible by (8, 128) or equal to the array dims
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running denom
            pltpu.VMEM((block_q, d), jnp.float32),        # output accumulator
        ],
        interpret=interpret,
    )
    with jax.named_scope(_FWD_SCOPE):
        out, lse = call(q, k, v, *extra_args)
    return out, lse


# ----------------------------------------------------------------------------
# backward: dq kernel (grid k-innermost, accumulate dq over k blocks)
# ----------------------------------------------------------------------------
def _bwd_dq_kernel(*refs, scale, causal, masked, rate, biased, block_q,
                   block_k, window=None, band=None):
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), bias_ref, \
        valid_ref, seed_ref, tail = _split_refs(refs, 6, masked, rate,
                                                biased)
    if biased:
        dq_ref, db_ref, dq_scr = tail
    else:
        dq_ref, dq_scr = tail
        db_ref = None

    b = pl.program_id(0)
    qi = pl.program_id(1)
    step = pl.program_id(2)
    nsteps = pl.num_programs(2)
    ki = step if band is None else \
        _k_band(qi, window, block_q, block_k, band)[0] + step
    valid = valid_ref[jax.lax.rem(b, _VALID_BLOCK)] if masked else None

    @pl.when(step == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    if biased:
        # every (qi, ki) block of d_bias must be DEFINED even when the
        # compute is skipped (causal/padding): zero first, overwrite below
        db_ref[0] = jnp.zeros_like(db_ref[0])

    def _compute():
        # native-dtype dot operands, f32 stats/accumulators (see fwd)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]                                       # (Bq, 1)
        delta = delta_ref[0]                                   # (Bq, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if biased:
            s = s + bias_ref[0].astype(jnp.float32)
        s = _score_mask(s, valid, causal, qi, ki, block_q, block_k, window)
        p = jnp.exp(s - lse)                                   # (Bq, Bk)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if rate > 0.0:
            # ds = p ∘ (z/(1-r)·dp̃ − δ): δ already equals Σ p̃·dp̃ because
            # it is computed from the dropped forward output
            keep = _keep_mask(seed_ref, b, qi, ki, rate, block_q, block_k)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - rate)), 0.0)
        ds_raw = p * (dp - delta)
        if biased:
            # bias enters AFTER the qk scale: d_bias = p ∘ (dp − δ)
            db_ref[0] = ds_raw.astype(db_ref.dtype)
        ds = ds_raw * scale
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    run = _run_cond(causal, valid, qi, ki, block_q, block_k, window)
    if band is not None:
        run = run & (ki < band)
    if run is True:
        _compute()
    else:
        pl.when(run)(_compute)

    @pl.when(step == nsteps - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


# ----------------------------------------------------------------------------
# backward: dk/dv kernel (grid q-innermost, accumulate dk,dv over q blocks)
# ----------------------------------------------------------------------------
def _bwd_dkv_kernel(*refs, scale, causal, masked, rate, biased, block_q,
                    block_k, window=None, group=1, band=None):
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), bias_ref, \
        valid_ref, seed_ref, tail = _split_refs(refs, 6, masked, rate,
                                                biased)
    dk_ref, dv_ref, dk_scr, dv_scr = tail

    b = pl.program_id(0)
    ki = pl.program_id(1)
    # the innermost axis walks the q blocks of each of the `group` query
    # heads that read this key/value row, one head after the other
    step = pl.program_id(2)
    nsteps = pl.num_programs(2)
    qi = step if group == 1 else jax.lax.rem(step, nsteps // group)
    if band is not None:
        qi = _q_band(ki, window, block_q, block_k, band)[0] + qi
    valid = valid_ref[jax.lax.rem(b, _VALID_BLOCK)] if masked else None

    @pl.when(step == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute():
        # native-dtype dot operands, f32 stats/accumulators (see fwd)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]                                       # (Bq, 1)
        delta = delta_ref[0]                                   # (Bq, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if biased:
            s = s + bias_ref[0].astype(jnp.float32)
        s = _score_mask(s, valid, causal, qi, ki, block_q, block_k, window)
        p = jnp.exp(s - lse)                                   # (Bq, Bk)
        if rate > 0.0:
            # same (seed, b, qi, ki) triple as fwd/dq → identical bits
            keep = _keep_mask(seed_ref, b, qi, ki, rate, block_q, block_k)
            inv = 1.0 / (1.0 - rate)
            p_drop = jnp.where(keep, p * inv, 0.0)
        else:
            keep = None
            p_drop = p
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p_drop.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if keep is not None:
            dp = jnp.where(keep, dp * (1.0 / (1.0 - rate)), 0.0)
        ds = p * (dp - delta) * scale
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    run = _run_cond(causal, valid, qi, ki, block_q, block_k, window)
    if band is not None:
        run = run & (qi < band)
    if run is True:
        _compute()
    else:
        pl.when(run)(_compute)

    @pl.when(step == nsteps - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 8))
def _bwd_call(scale, causal, rate, block_q, block_k, interpret, res, do,
              window=None):
    q, k, v, kv_valid, seed, bias, out, lse = res
    bh, t, d = q.shape
    bhk, tk = k.shape[:2]
    group = bh // bhk
    bq = min(block_q, t)
    bk = min(block_k, tk)
    nq, nk = _cdiv(t, bq), _cdiv(tk, bk)
    masked = kv_valid is not None
    biased = bias is not None
    # the band: dq walks its key blocks as _fwd does, dk/dv its query blocks
    banded = _banded(window, biased)
    nb, nbq = _band_steps(t, tk, window, bq, bk) if banded else (nk, None)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[..., None]                        # (BH, T, 1)
    extra_specs, extra_args = _extra_specs_and_args(
        kv_valid, seed if rate > 0.0 else None)
    bias_specs = [_bias_spec(bias, bh, bq, bk)] if biased else []
    bias_args = [bias] if biased else []

    qspec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, bk, d),
                         _kv_index(group, window, bq, bk, nk, banded),
                         memory_space=pltpu.VMEM)
    rowq = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0),
                        memory_space=pltpu.VMEM)
    # d_bias is emitted PER (b, qblk, kblk) at full (BH, T, Tk) and reduced
    # to the caller's broadcast shape afterwards — the gradient of a
    # materialized bias is inherently O(T²), same as the bias itself
    out_specs = qspec
    out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    if biased:
        dbspec = pl.BlockSpec((1, bq, bk), lambda b, i, j: (b, i, j),
                              memory_space=pltpu.VMEM)
        out_specs = [qspec, dbspec]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((bh, t, tk), jnp.float32)]
    dq_call = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          masked=masked, rate=rate, biased=biased,
                          block_q=bq, block_k=bk, window=window,
                          band=nk if banded else None),
        grid=(bh, nq, nb),
        in_specs=[qspec, kspec, kspec, qspec, rowq, rowq] + bias_specs
        + extra_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )
    with jax.named_scope(_DQ_SCOPE):
        dq_out = dq_call(q, k, v, do, lse, delta, *bias_args, *extra_args)
    if biased:
        dq, db_full = dq_out
        bhb = bias.shape[0]
        if bhb == bh:
            db = db_full
        elif bhb == 1:
            db = jnp.sum(db_full, axis=0, keepdims=True)
        else:  # per-head bias shared over batch: sum the batch groups
            db = jnp.sum(db_full.reshape(bh // bhb, bhb, t, tk), axis=0)
        db = db.astype(bias.dtype)
    else:
        dq = dq_out
        db = None

    # dk/dv: swap grid so q is innermost; index maps take (b, kblk, qblk),
    # b the key/value row, whose `group` query heads the innermost axis
    # walks one after the other: dk and dv come out summed over them
    q_index = _q_index(group, window, bq, bk, nq, nbq)
    qspec2 = pl.BlockSpec((1, bq, d), q_index, memory_space=pltpu.VMEM)
    kspec2 = pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0),
                          memory_space=pltpu.VMEM)
    rowq2 = pl.BlockSpec((1, bq, 1), q_index, memory_space=pltpu.VMEM)
    bias_specs2 = [_bias_spec(bias, bh, bq, bk, swap=True)] if biased else []
    dkv_call = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          masked=masked, rate=rate, biased=biased,
                          block_q=bq, block_k=bk, window=window, group=group,
                          band=nq if banded else None),
        grid=(bhk, nk, group * (nbq or nq)),
        # the SMEM scalar index maps only use the leading batch axis, so the
        # same specs serve both backward grids
        in_specs=[qspec2, kspec2, kspec2, qspec2, rowq2, rowq2]
        + bias_specs2 + extra_specs,
        out_specs=[kspec2, kspec2],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
    )
    with jax.named_scope(_DKV_SCOPE):
        dk, dv = dkv_call(q, k, v, do, lse, delta, *bias_args, *extra_args)
    return dq, dk, dv, None, None, db


# ----------------------------------------------------------------------------
# public entry
# ----------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _flash_core(q, k, v, kv_valid, seed, bias, scale, causal, rate,
                block_q, block_k, window):
    out, _ = _fwd(q, k, v, kv_valid, seed, bias, scale, causal, rate,
                  block_q, block_k, _interpret(), window)
    return out


def _flash_fwd_rule(q, k, v, kv_valid, seed, bias, scale, causal, rate,
                    block_q, block_k, window):
    out, lse = _fwd(q, k, v, kv_valid, seed, bias, scale, causal, rate,
                    block_q, block_k, _interpret(), window)
    return out, (q, k, v, kv_valid, seed, bias, out, lse)


def _bwd(scale, causal, rate, block_q, block_k, window, res, do):
    return _bwd_call(scale, causal, rate, block_q, block_k, _interpret(),
                     res, do, window)


_flash_core.defvjp(_flash_fwd_rule, _bwd)


def flash_attention(q, k, v, scale=None, causal=False, kv_valid=None,
                    dropout_rate=0.0, dropout_seed=None, bias=None,
                    bias_groups=None, block_q=None, block_k=None,
                    window=None):
    """softmax(q·kᵀ·scale [+causal/padding mask])·v, blockwise.
    q/k/v: (BH, T, D).  scale defaults to 1/sqrt(D); blocks default to the
    tuned sizes.  T (for both q and k/v) must tile exactly by the chosen
    blocks — partial K blocks would feed padded garbage into the softmax.

    window: with `causal`, query i sees only the keys i - window < j <= i
    (itself among them); the grid walks the band of blocks the window
    allows, at a default key block no wider than the window.
    Grouped heads: k/v may have BH / G rows, query row b then reads
    key/value row b // G, and dk, dv come out summed over the G query rows
    (no padding mask, dropout or bias with G > 1).

    kv_valid: optional (BH,) int32, number of valid keys per row (≥1); key
    columns beyond it are masked out and whole K blocks beyond it skipped.
    dropout_rate/dropout_seed: attention-prob dropout inside the kernel
    (TPU only — the TPU PRNG has no interpret lowering); seed is a (1,)
    int32 array, the mask is a pure function of it so fwd/bwd agree.
    bias: optional additive attention bias (ALiBi, relative position) of
    shape (BH, T, Tk), (1, T, Tk) fully shared, or (G, T, Tk) cycling
    with period G — G MUST then be passed as bias_groups (the mha wrapper
    passes H; a bare divisor would be ambiguous between per-head and
    per-batch).  Streamed block-by-block.  The backward materializes a
    (BH, T, Tk) f32 d_bias before reducing to the bias shape — the same
    footprint the DENSE path pays for its probability matrix in the
    forward (and keeps into backward), so the kernel path is never the
    worse choice; it is simply the inherent cost of a materialized
    O(T²) bias."""
    t, tk = q.shape[1], k.shape[1]
    scale = 1.0 / math.sqrt(q.shape[-1]) if scale is None else scale
    if window is not None and (not causal or window < 1):
        raise ValueError(f"flash_attention: window {window!r} needs "
                         "causal=True and at least one key")
    if k.shape != v.shape or q.shape[0] % k.shape[0]:
        raise ValueError(
            f"flash_attention: k {k.shape} and v {v.shape} must agree, and "
            f"their rows divide q's {q.shape[0]}")
    if q.shape[0] != k.shape[0] and (
            kv_valid is not None or bias is not None or dropout_rate > 0.0):
        raise ValueError(
            "flash_attention: grouped heads take no padding mask, dropout "
            "or bias; gate callers with supported(..., kv_heads=...)")
    bq, bk = _blocks(t, tk, block_q, block_k, window)
    if t % bq or tk % bk:
        raise ValueError(
            f"flash_attention: seq lens (q={t}, kv={tk}) must be divisible "
            f"by the block sizes ({bq}, {bk}); gate callers with "
            "kernels.flash_attention.supported()")
    if bq * bk > MAX_BLOCK_ELEMS:
        raise ValueError(
            f"flash_attention: block ({bq}×{bk}) exceeds the VMEM-sane "
            f"bound ({MAX_BLOCK_ELEMS} elems) — likely a seq len with no "
            "power-of-two divisor fell through to a single full-T block. "
            "Pass explicit block_q/block_k or gate with supported()")
    if dropout_rate < 0.0 or dropout_rate >= 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1): {dropout_rate}")
    if dropout_rate > 0.0:
        if _interpret():
            raise ValueError(
                "flash_attention: in-kernel dropout needs the TPU PRNG, "
                "which has no interpret-mode lowering; use the dense path "
                "off-TPU (parallel.attention dispatches this automatically)")
        if dropout_seed is None:
            raise ValueError("dropout_rate > 0 requires dropout_seed")
        dropout_seed = jnp.asarray(dropout_seed, jnp.int32).reshape((1,))
    else:
        dropout_seed = None
    if kv_valid is not None:
        kv_valid = jnp.asarray(kv_valid, jnp.int32).reshape((q.shape[0],))
    if bias is not None:
        bh = q.shape[0]
        ok_lead = (bias.shape[0] in (bh, 1) or
                   (bias_groups is not None and
                    bias.shape[0] == bias_groups and bh % bias_groups == 0))
        if bias.ndim != 3 or bias.shape[1:] != (t, tk) or not ok_lead:
            raise ValueError(
                f"bias shape {bias.shape} must be (BH, {t}, {tk}), "
                f"(1, {t}, {tk}), or (G, {t}, {tk}) with G passed as "
                f"bias_groups and dividing BH={bh} — a bare divisor is "
                "ambiguous between per-head and per-batch")
    return _flash_core(q, k, v, kv_valid, dropout_seed, bias, scale,
                       causal, float(dropout_rate), bq, bk,
                       None if window is None else int(window))



def _pick_block(t, prefer):
    """Largest power-of-two block ≤ prefer that divides t, so blocks tile T
    exactly — partial K blocks would feed garbage columns into the softmax.
    t ≤ the smallest candidate is returned as-is (single block); larger T
    with no aligned divisor also falls through to a single block, which
    flash_attention() rejects when it exceeds MAX_BLOCK_ELEMS."""
    if t <= 128:
        return t
    for b in (prefer, 1024, 512, 256, 128):
        if b <= prefer and t % b == 0:
            return b
    return t  # no aligned divisor: single block covering T (size-guarded)


def mha_flash_attention(q, k, v, causal=False, valid_length=None,
                        dropout_rate=0.0, dropout_seed=None, bias=None,
                        block_q=None, block_k=None, window=None):
    """Multi-head wrapper: q is (B, H, T, D), k/v (B, H_kv, T, D) with H a
    multiple of H_kv (query head h reads key/value head h // (H / H_kv));
    collapses batch*heads, runs the Pallas kernel, restores the layout.
    valid_length is per-batch (B,) and is broadcast across heads.  Default
    blocks tuned on v5e-class hardware: large K blocks amortize the scratch
    carry."""
    b, h, t, d = q.shape
    fold = lambda x: x.reshape(b * x.shape[1], x.shape[2], d)
    kv_valid = None
    if valid_length is not None:
        kv_valid = jnp.repeat(jnp.asarray(valid_length, jnp.int32), h)
    kbias = None
    bias_groups = None
    if bias is not None:
        # (B|1, H|1, Tq|1, Tk|1) -> kernel layout; singleton T dims are
        # broadcast up front (the kernel streams full (T, Tk) planes)
        tk = k.shape[2]
        bb, bhh = bias.shape[0], bias.shape[1]
        full_t = bias.shape[2:] == (t, tk)
        if bb == b and bhh == h and full_t:
            kbias = bias.reshape(b * h, t, tk)
        elif bb == 1 and bhh == h and full_t:
            kbias = bias.reshape(h, t, tk)
            bias_groups = h
        elif bb == 1 and bhh == 1 and full_t:
            kbias = bias.reshape(1, t, tk)
        else:
            # singleton T/Tk dims or per-batch shared-head layouts:
            # materialize the full fold (differentiable broadcast)
            kbias = jnp.broadcast_to(bias, (b, h, t, tk)).reshape(
                b * h, t, tk)
    out = flash_attention(fold(q), fold(k), fold(v), None, causal,
                          kv_valid, dropout_rate, dropout_seed, kbias,
                          bias_groups, block_q, block_k, window)
    return out.reshape(b, h, t, d)


def supported(q_shape, dtype, kv_len=None, dropout_rate=0.0, kv_heads=None,
              window=None, causal=True, plain=True):
    """Whether the Pallas path handles this problem: head dim a multiple of
    the VPU lane half-count (dense MXU tiles), BOTH sequence lengths
    multiples of the smallest block so K blocks tile exactly, and — when
    attention dropout is active — a real TPU backend (the kernel PRNG has
    no interpret lowering).  A window needs `causal`; `kv_heads` other than
    q's own (q_shape (B, H, T, D)) have to divide them and need a `plain`
    call: no padding mask, no dropout, no bias."""
    d = q_shape[-1]
    t = q_shape[-2]
    kv_len = t if kv_len is None else kv_len
    if dropout_rate > 0.0 and _interpret():
        return False
    if window is not None and (not causal or window < 1):
        return False
    if kv_heads is not None and kv_heads != q_shape[-3] and (
            q_shape[-3] % kv_heads or dropout_rate > 0.0 or not plain):
        return False
    return d % 64 == 0 and t % 128 == 0 and kv_len % 128 == 0 and \
        jnp.dtype(dtype).name in ("float32", "bfloat16")
