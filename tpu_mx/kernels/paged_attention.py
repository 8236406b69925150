"""Paged-attention decode as a Pallas TPU kernel.

The serving runtime's decode step is one new-token query per sequence
against a **paged** KV cache: each sequence's keys/values live scattered
across fixed-size blocks of one shared pool, addressed by a per-sequence
block table (tpu_mx/serving/kv_cache.py).  Until this kernel, decode
resolved those tables on the HOST — a padded dense `(B, Lmax, H, D)`
gather per step per layer, O(total context) of memcpy with the pool
living in host memory (docs/DIVERGENCES.md #27).

This module is the native path: the flash kernel's online-softmax loop
over KV blocks (tpu_mx/kernels/flash_attention.py), re-gridded so each
program walks ONE sequence's block table with the pool resident in HBM.
The block table and the true lengths ride as **scalar-prefetch** operands
(`pltpu.PrefetchScalarGridSpec`): they are available before the kernel
body runs, so the K/V BlockSpec index maps dereference `table[b, i]`
directly and the DMA engine fetches exactly the blocks each sequence
owns — per-token decode cost becomes O(blocks-visited), and the cache
never round-trips through the host.

Shape contract (decode-specific, deliberately different from flash's
`(BH, T, D)` training layout):

- `q`: `(B, H, D)`, `(B, 1, H, D)` or `(B, Tq, H, D)` — each sequence's
  new-token queries.  `Tq == 1` is classic one-token decode; a small
  `Tq > 1` is the speculative **draft window** (ISSUE 16): the queries
  are the last `Tq` positions of the sequence (query `t` sits at
  absolute position `lengths[b] - Tq + t`) and the causal mask is
  applied per row, so one batched `(B, Tq, H, D)` call verifies a whole
  drafted token window against the same paged pool.
- `k_pool`/`v_pool`: `(num_blocks, block_size, H, D)` — ONE layer's
  shared block pool.  The last two dims are full-dim blocks, so Mosaic's
  (sublane, lane) tiling sees `(H, D)` exactly.
- `block_tables`: `(B, NB)` int32.  Row `b`'s first
  `ceil(lengths[b]/block_size)` entries are the sequence's block ids in
  position order; every entry PAST that must still be a valid pool index
  (the cache pads with block 0) — the padded fetches are finite garbage
  the length mask excludes exactly, never an out-of-bounds DMA.
- `lengths`: `(B,)` int32 true context lengths (>= 1), the new token's
  slot included.

Two arms share the math:

- :func:`paged_attention` — the Pallas kernel.  Grid `(B, NB)`, KV-block
  index innermost; VMEM scratch carries the running `(m, l, acc)` f32
  statistics across a sequence's blocks (flash's sequential-grid
  accumulation), blocks entirely past `lengths[b]` are skipped via
  `pl.when`, and the output row is written on the last block step.
  Falls back to interpret mode off-TPU — the CPU tier-1 suite exercises
  the real code path (the flash kernel's established pattern).
- :func:`paged_attention_reference` — the same block-table algorithm as
  ONE jitted XLA program (gather-by-table + masked softmax fused by the
  compiler).  Off-TPU this is the production paged arm: it keeps the
  pool device-resident and reads each pool byte once where the per-step
  host dense-gather copies the padded context out and reads it again (on
  a CPU host; not measured on the chip), while the interpret-mode kernel
  stays a correctness-only tool.

No backward pass: decode is inference — there is nothing to
differentiate, and keeping the kernel forward-only is what lets the
grid stay `(B, NB)` with no logsumexp output.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention", "paged_attention_reference", "window_walk",
           "supported", "DEFAULT_BLOCK_SIZE"]

NEG_INF = -1e30

# Serving KV block size (tokens per pool block).  Swept once on a CPU
# host, not on the chip: 8 doubles the block walk's iteration count for
# the same bytes; 16/32/64 landed close to each other, and 16 carries the
# least padded-tail waste and free-list fragmentation, so 16 stands until
# a serving cell measures it (ROADMAP W4).
DEFAULT_BLOCK_SIZE = 16


def _interpret():
    return jax.default_backend() != "tpu"


def _kernel(tab_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, *, scale, block_size, tq):
    """One (sequence, kv-block) grid step: flash's online-softmax update
    with the K dimension walking the sequence's block table.

    In-kernel layout is row-major `(Tq*H, block_size)` scores — row
    `r = t*H + h` is query-window position `t`, head `h` — so the
    running stats mirror flash's `(rows, 128)` scratch pattern with
    rows = window × heads (`Tq == 1` reduces to the original head-major
    layout exactly).  Each row carries its own causal limit: query `t`
    sits at absolute position `length - Tq + t`, so row `r` admits key
    positions `< length - (Tq - 1 - t)`.  All score/stat math is f32
    regardless of pool dtype; the dots are elementwise-mul + reduce on
    the VPU — decode attention is memory-bound (few-row queries), the
    MXU has nothing to chew on."""
    b = pl.program_id(0)
    i = pl.program_id(1)
    nb = pl.num_programs(1)
    length = len_ref[b]

    @pl.when(i == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # the LAST window row's limit is `length` itself, so the block-skip
    # guard is unchanged from the Tq=1 kernel
    @pl.when(i * block_size < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                   # (Tq, H, D)
        k = k_ref[0].astype(jnp.float32)                   # (BS, H, D)
        v = v_ref[0].astype(jnp.float32)                   # (BS, H, D)
        h = q.shape[1]
        # s[t, h, s'] = q[t, h, :] . k[s', h, :] — head-batched window dots
        s = jnp.sum(q[:, None, :, :] * k[None, :, :, :], axis=-1)
        s = s.transpose(0, 2, 1).reshape(tq * h, -1) * scale  # (Tq*H, BS)
        kpos = i * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        # per-row causal limit: row r = t*H + h_ admits kpos < length -
        # (Tq - 1 - t); at Tq=1 this is exactly `kpos < length`
        row_t = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // h
        s = jnp.where(kpos < length - (tq - 1) + row_t, s, NEG_INF)
        m_prev = m_scr[:, 0]                               # (Tq*H,)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])                    # (Tq*H, BS)
        l_scr[:] = jnp.broadcast_to(
            (l_scr[:, 0] * alpha + jnp.sum(p, axis=1))[:, None],
            l_scr.shape)
        # acc[t*H + h_, d] += sum_s' p[t*H + h_, s'] * v[s', h_, d]
        p3 = p.reshape(tq, h, -1).transpose(2, 0, 1)       # (BS, Tq, H)
        acc_scr[:] = acc_scr[:] * alpha[:, None] + jnp.sum(
            p3[:, :, :, None] * v[:, None, :, :], axis=0).reshape(
            tq * h, -1)
        m_scr[:] = jnp.broadcast_to(m_cur[:, None], m_scr.shape)

    @pl.when(i == nb - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0] = (acc_scr[:] / l_safe[:, None]).reshape(
            o_ref.shape[1:]).astype(o_ref.dtype)


def _normalize_q(q):
    """Accept (B, H, D) or (B, Tq, H, D); return (B, Tq, H, D) + had_t
    flag (was the caller's q 4-d already).  Shape-only: no host->device
    conversion happens here — operands flow into the jitted/pallas call
    as-is, so a numpy caller pays one C++-fast-path commit per call
    instead of an eager convert op per operand (~73us each on this
    host, measured — it dominated the per-step decode cost at short
    context).  A 3-d reshape is a view on both numpy and jax arrays."""
    if not hasattr(q, "ndim"):
        q = np.asarray(q)
    if q.ndim == 4:
        return q, True
    if q.ndim != 3:
        raise ValueError(f"paged_attention: q must be (B, H, D) or "
                         f"(B, Tq, H, D), got shape {q.shape}")
    return q.reshape(q.shape[0], 1, *q.shape[1:]), False


def _check_operands(q, k_pool, v_pool, block_tables, lengths):
    b, tq, h, d = q.shape
    if k_pool.ndim != 4 or k_pool.shape != v_pool.shape:
        raise ValueError(
            f"paged_attention: pools must be matching (num_blocks, "
            f"block_size, H, D); got {k_pool.shape} / {v_pool.shape}")
    if k_pool.shape[2:] != (h, d):
        raise ValueError(
            f"paged_attention: pool heads/dim {k_pool.shape[2:]} != query "
            f"({h}, {d})")
    if block_tables.ndim != 2 or block_tables.shape[0] != b:
        raise ValueError(
            f"paged_attention: block_tables must be (B={b}, NB); got "
            f"{block_tables.shape}")
    if lengths.shape != (b,):
        raise ValueError(
            f"paged_attention: lengths must be (B={b},); got "
            f"{lengths.shape}")


@functools.lru_cache(maxsize=128)
def _kernel_call(b, nb, block_size, tq, h, d, out_dtype, scale, interpret):
    """Build (once per static geometry) the jitted pallas_call for one
    decode shape.  The decode hot path calls this kernel once per layer
    per token — an uncached eager pallas_call would re-trace (and on a
    TPU backend re-lower through Mosaic) every single call, which would
    dwarf the O(blocks-visited) work the kernel exists to deliver.  The
    jit wrapper carries the compilation cache; the lru key is exactly
    the set of values baked into the trace (the draft-window width `tq`
    included — each window width is its own grid geometry)."""
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # (block_tables, lengths)
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, tq, h, d),
                         lambda sb, i, tab, lens: (sb, 0, 0, 0)),
            pl.BlockSpec((1, block_size, h, d),
                         lambda sb, i, tab, lens: (tab[sb, i], 0, 0, 0)),
            pl.BlockSpec((1, block_size, h, d),
                         lambda sb, i, tab, lens: (tab[sb, i], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tq, h, d),
                               lambda sb, i, tab, lens: (sb, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((tq * h, 128), jnp.float32),   # running max
            pltpu.VMEM((tq * h, 128), jnp.float32),   # running denom
            pltpu.VMEM((tq * h, d), jnp.float32),     # output accumulator
        ],
    )
    return jax.jit(pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_size=block_size,
                          tq=tq),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, tq, h, d), jnp.dtype(out_dtype)),
        interpret=interpret,
    ))


def paged_attention(q, k_pool, v_pool, block_tables, lengths, scale=None):
    """Decode attention over a paged KV pool (see module docstring).

    Returns `(B, H, D)` (or `(B, Tq, H, D)` matching a 4-d `q`) in
    `q.dtype`.  `block_tables` entries beyond each row's real blocks
    must be valid pool indices (0-padding per the cache contract);
    `lengths` masks them out exactly."""
    q, had_t = _normalize_q(q)
    block_tables = _as_i32(block_tables)
    lengths = _as_i32(lengths)
    _check_operands(q, k_pool, v_pool, block_tables, lengths)
    b, tq, h, d = q.shape
    block_size = k_pool.shape[1]
    nb = block_tables.shape[1]
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    fn = _kernel_call(b, nb, block_size, tq, h, d, jnp.dtype(q.dtype).name,
                      float(scale), _interpret())
    out = fn(block_tables, lengths, q, k_pool, v_pool)
    return out if had_t else out[:, 0]


def _as_i32(x):
    """int32 view without an eager device op: numpy stays numpy (the jit
    boundary commits it on the C++ fast path), jax arrays only convert
    when the dtype is actually wrong."""
    if isinstance(x, np.ndarray) or not hasattr(x, "devices"):
        return np.asarray(x, np.int32)
    return x if x.dtype == jnp.int32 else x.astype(jnp.int32)


def window_walk(q, k_pool, v_pool, block_tables, lengths, scale):
    """The kernel's block walk as lax.scan + per-block dynamic indexing,
    vmapped over the batch — plain traceable jax, so the fused decode
    step (serving/jax_model.py) can inline it into ITS jitted program
    against the donated pool without a nested dispatch boundary.

    `q` is the canonical `(B, Tq, H, D)` window; returns the same
    shape.  NOT a gather-then-softmax: materializing the padded
    `(B, Lmax, H, D)` batch in-program and re-reading it through the
    einsum/softmax passes measured ~3x slower at bench contexts on the
    CPU backend — the online-softmax walk reads each pool byte once,
    exactly like the Pallas grid does."""
    b, tq, h, d = q.shape
    bs = k_pool.shape[1]
    qf = q.astype(jnp.float32)

    def one_row(tab, length, qr):
        # query t sits at absolute position length - Tq + t -> admits
        # key positions strictly below length - (Tq - 1 - t)
        limit = length - (tq - 1) + jnp.arange(tq, dtype=jnp.int32)

        def step(carry, bid):
            m, l, acc, i = carry
            k = jax.lax.dynamic_index_in_dim(k_pool, bid, 0,
                                             keepdims=False)
            v = jax.lax.dynamic_index_in_dim(v_pool, bid, 0,
                                             keepdims=False)
            s = jnp.einsum("thd,shd->ths", qr,
                           k.astype(jnp.float32)) * scale
            kpos = i * bs + jnp.arange(bs, dtype=jnp.int32)
            s = jnp.where(kpos[None, None, :] < limit[:, None, None],
                          s, NEG_INF)
            m_cur = jnp.maximum(m, jnp.max(s, axis=2))
            alpha = jnp.exp(m - m_cur)
            p = jnp.exp(s - m_cur[:, :, None])
            l = l * alpha + jnp.sum(p, axis=2)
            acc = acc * alpha[:, :, None] + jnp.einsum(
                "ths,shd->thd", p, v.astype(jnp.float32))
            return (m_cur, l, acc, i + 1), None

        init = (jnp.full((tq, h), NEG_INF, jnp.float32),
                jnp.zeros((tq, h), jnp.float32),
                jnp.zeros((tq, h, d), jnp.float32), jnp.int32(0))
        (_, l, acc, _), _ = jax.lax.scan(step, init, tab)
        return acc / jnp.maximum(l, 1e-30)[:, :, None]

    # output cast happens in-trace (free at dispatch time): the decode
    # contract is out.dtype == q.dtype on every arm
    return jax.vmap(one_row)(block_tables, lengths, qf).astype(q.dtype)


_reference_impl = functools.partial(jax.jit, static_argnames=("scale",))(
    window_walk)


def paged_attention_reference(q, k_pool, v_pool, block_tables, lengths,
                              scale=None):
    """The kernel's algorithm as one jitted XLA program — same operands,
    same masking contract, same online-softmax-over-blocks walk in f32.
    The off-TPU production paged arm (and the kernel's parity oracle):
    the table walk happens inside the compiled program against the
    resident pool, so a decode step costs one dispatch — no O(context)
    host memcpy pass, no materialized padded batch."""
    q, had_t = _normalize_q(q)
    block_tables = _as_i32(block_tables)
    lengths = _as_i32(lengths)
    _check_operands(q, k_pool, v_pool, block_tables, lengths)
    scale = 1.0 / math.sqrt(q.shape[-1]) if scale is None else float(scale)
    out = _reference_impl(q, k_pool, v_pool, block_tables, lengths, scale)
    return out if had_t else out[:, 0]


def supported(head_dim, dtype, block_size=DEFAULT_BLOCK_SIZE):
    """Whether the real-Mosaic kernel should take this decode on a TPU
    backend: head_dim a multiple of the dense-tile lane count and a
    native MXU dtype (the flash kernel's gate), block_size sublane-
    aligned.  Interpret mode (off-TPU) accepts anything — it is
    correctness-only and callers route production decode through
    :func:`paged_attention_reference` there."""
    if _interpret():
        return True
    return (head_dim % 64 == 0 and block_size % 8 == 0 and
            jnp.dtype(dtype).name in ("float32", "bfloat16"))
