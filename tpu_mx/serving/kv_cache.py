"""Paged KV cache: fixed-size blocks, a free-list allocator, block tables.

The serving runtime's memory manager (docs/serving.md).  A training step
owns one batch for its whole lifetime; a serving engine juggles thousands
of concurrent sequences whose lengths are unknown at admission.  Naive
per-sequence contiguous KV buffers either over-reserve (max_len for every
request — most of it never used) or reallocate-and-copy as sequences grow.
The paged design (vLLM's PagedAttention insight, applied to this stack's
layout) fixes both:

- **Blocks**: K and V live in ONE preallocated pool per layer, shaped
  ``(num_blocks, block_size, num_heads, head_dim)``.  A sequence's cache
  is a list of block ids — its **block table** — plus a length; logically
  contiguous, physically scattered.
- **Free-list allocator**: :class:`BlockAllocator` hands out block ids
  from a LIFO free list under one lock.  Exhaustion raises
  :class:`CacheExhausted` — the scheduler's backpressure signal (requeue /
  reject), NEVER an allocation attempt that OOMs the process.
- **Refcounts** (ISSUE 12): every held block carries a reference count.
  ``alloc`` hands out blocks at one reference; ``incref`` adds sharers
  (the shared-prefix index, a :meth:`PagedKVCache.fork` sibling);
  ``free`` DECREMENTS and only returns a block to the free list at
  zero.  Freeing a sequence whose blocks another live sequence shares
  therefore releases references, never data — the invariant behind
  "preemption never evicts a block another live sequence shares".
  Double-free (freeing an unheld block) stays loud.
- **O(1) append**: generating one token costs at most one free-list pop
  (amortized ``1/block_size`` pops) and one slot write — independent of
  how long the sequence already is.
- **Copy-free reuse**: finishing a sequence pushes its blocks straight
  back on the free list; the next sequence overwrites them.  No zeroing,
  no compaction, no copies.

Two storage modes share the allocator/table semantics (``storage=``):

- ``"host"`` (default): pools are host numpy — the CPU-testable layout
  tier-1 exercises, read through the dense-gather fallback.
- ``"device"``: pools are per-layer **device-resident** jax arrays
  (HBM on TPU); ``prefill``/``write``/``write_batch`` mutate them with
  jitted in-place index updates (buffer-donated where the backend
  supports donation) and the paged-attention decode kernel indexes them
  by raw block table (``tpu_mx/kernels/paged_attention.py``) — the
  cache never round-trips through the host on the decode path
  (docs/DIVERGENCES.md #27).  Same allocator, same block-table
  bookkeeping, same exhaustion-is-backpressure contract.

All public methods are thread-safe for BOOKKEEPING: the allocator has
its own lock and the table map is guarded by the cache lock, so a
scheduler thread can admit/evict while tests hammer alloc/free
concurrently (tests/test_serving.py).  Device-pool ARRAY access (writes
and :meth:`pool` readers) additionally belongs to the single engine
step thread: donation invalidates the previous buffer, so a reader
holding a stale pool reference across a write would observe a consumed
array — the serving data plane is single-threaded by design
(docs/serving.md), which is exactly this discipline.
"""
from __future__ import annotations

import functools
import itertools
import threading
import time
from collections import deque

import numpy as np

from ..base import MXNetError
from .. import telemetry as _telemetry
from .. import tracing as _tracing
from . import accounting as _accounting
from .accounting import INDEX_TENANT, CapacityLedger
from .prefix_cache import PrefixIndex, prefix_sharing_enabled
from .tenancy import DEFAULT_TENANT

__all__ = ["CacheExhausted", "BlockAllocator", "PagedKVCache",
           "PrefillPlan", "prefix_sharing_enabled"]

# ids for pinned prefill plans' ledger holders — unique per process so a
# forensic record never conflates two concurrently pinned plans
_plan_ids = itertools.count()


def _next_pow2(n):
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


# Jitted device-pool updaters, built on first device-mode cache.  Python
# scalars/arrays trace as arguments, so repeated writes share one
# compilation per operand shape; donating the pool makes the update
# genuinely in-place (measured ~9us vs ~6ms copy-on-write for a 16 MiB
# pool on this host's CPU backend) — which is why pool handles are
# step-thread-owned: the pre-write array object is CONSUMED by every
# write (module docstring).
_DEV_OPS = None


def _dev_ops():
    global _DEV_OPS
    if _DEV_OPS is None:
        import jax

        donate = (0,)

        @functools.partial(jax.jit, donate_argnums=donate)
        def write_slot(pool, bid, off, val):
            return pool.at[bid, off].set(val.astype(pool.dtype))

        @functools.partial(jax.jit, donate_argnums=donate)
        def write_rows(pool, bids, offs, vals):
            return pool.at[bids, offs].set(vals.astype(pool.dtype))

        @functools.partial(jax.jit, donate_argnums=donate)
        def write_blocks(pool, bids, chunk):
            return pool.at[bids].set(chunk.astype(pool.dtype))

        @functools.partial(jax.jit, donate_argnums=donate)
        def copy_block(pool, dst, src):
            # the copy-on-write primitive: one block's slots duplicated
            # on-device (the pool never round-trips through the host)
            return pool.at[dst].set(pool[src])

        _DEV_OPS = (write_slot, write_rows, write_blocks, copy_block)
    return _DEV_OPS


class CacheExhausted(MXNetError):
    """The block pool has no room for this allocation.  This is the
    BACKPRESSURE signal, not an error to crash on: the scheduler catches
    it and requeues (decode append) or defers admission (prefill) —
    docs/serving.md "Backpressure"."""


class BlockAllocator:
    """LIFO free-list allocator over ``num_blocks`` fixed-size blocks.

    ``alloc(n)`` is all-or-nothing: either all ``n`` ids are handed out
    or :class:`CacheExhausted` is raised and the free list is untouched —
    a partial grab would leak blocks on the error path.  ``free`` rejects
    ids the allocator did not hand out (double-free corrupts the pool
    silently; loud is the only acceptable failure mode).

    **Capacity ledger** (ISSUE 14): every reference additionally carries
    an attribution — the ``holder=`` a caller names on
    ``alloc``/``incref``/``free`` (a sequence, the prefix index, a
    pinned plan; ``None`` files under the ``_anon`` holder, so bare
    callers stay ledgered).  The ledger mutates under THIS lock, next to
    the refcount it mirrors, which is what makes ``audit()``'s identity
    — per block, attributed refs == refcount; per tenant, amortized
    bytes sum exactly to pool-used bytes — hold at every instant
    (tpu_mx/serving/accounting.py)."""

    def __init__(self, num_blocks, block_bytes=1):
        if int(num_blocks) < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        self.num_blocks = int(num_blocks)
        self._lock = threading.Lock()
        # LIFO: recently freed blocks are re-handed first (their pages are
        # the warmest — copy-free reuse on sequence completion)
        self._free = list(range(self.num_blocks - 1, -1, -1))
        self._held = set()
        self._refs = {}   # block id -> reference count (held blocks only)
        self.ledger = CapacityLedger(block_bytes)

    def alloc(self, n=1, holder=None):
        """``n`` block ids at one reference each, or raise
        :class:`CacheExhausted` (free list untouched — all-or-nothing).
        ``holder`` attributes the references in the capacity ledger."""
        n = int(n)
        with self._lock:
            if n > len(self._free):
                raise CacheExhausted(
                    f"KV cache exhausted: need {n} block(s), "
                    f"{len(self._free)}/{self.num_blocks} free — "
                    "backpressure, not OOM: requeue or reject")
            ids = [self._free.pop() for _ in range(n)]
            self._held.update(ids)
            for bid in ids:
                self._refs[bid] = 1
            self.ledger.hold(ids, holder)
            self.ledger.note_used(len(self._held))
        return ids

    def incref(self, block_ids, holder=None):
        """Add one reference to each (held) block — a sharer: the
        shared-prefix index, or a :meth:`PagedKVCache.fork` sibling.
        Increfing a block the allocator did not hand out is as loud as
        double-freeing one (a stale id would resurrect a freed block)."""
        with self._lock:
            for bid in block_ids:
                if bid not in self._held:
                    raise MXNetError(
                        f"BlockAllocator.incref: block {bid} is not held "
                        "(stale or foreign id) — sharing it would "
                        "resurrect freed storage")
            for bid in block_ids:
                self._refs[bid] += 1
            self.ledger.hold(block_ids, holder)

    def free(self, block_ids, holder=None):
        """Drop one reference per block; a block reaching ZERO
        references returns to the free list (copy-free: contents are
        left in place for the next owner to overwrite).  A block another
        holder still references survives — which is why freeing a
        preempted sequence can never corrupt a sequence sharing its
        prefix.  Freeing an unheld block (double free) stays loud, and
        so does naming a ``holder`` that does not hold the reference
        (the ledger's attribution would silently drift otherwise)."""
        with self._lock:
            for bid in block_ids:
                if bid not in self._held:
                    raise MXNetError(
                        f"BlockAllocator.free: block {bid} is not held "
                        "(double free or foreign id) — the pool would be "
                        "silently corrupted")
            # the ledger validates the holder's attribution BEFORE any
            # refcount moves, so a mis-attributed free changes nothing
            self.ledger.release(block_ids, holder)
            for bid in block_ids:
                self._refs[bid] -= 1
                if self._refs[bid] == 0:
                    del self._refs[bid]
                    self._held.discard(bid)
                    self._free.append(bid)

    def reassign(self, block_ids, src, dst):
        """Move the attributed ownership of one reference per block from
        holder ``src`` to ``dst`` WITHOUT touching refcounts — the
        commit-prefill handoff (a plan's pins become the registered
        sequence's references)."""
        with self._lock:
            for bid in block_ids:
                if bid not in self._held:
                    raise MXNetError(
                        f"BlockAllocator.reassign: block {bid} is not "
                        "held — cannot move attribution of a freed block")
            self.ledger.transfer(block_ids, src, dst)

    def describe(self, holder, kind=None, tenant=None, pinned=None):
        """Attach attribution metadata to a ledger holder (under the
        allocator lock, like every ledger mutation)."""
        with self._lock:
            self.ledger.describe(holder, kind=kind, tenant=tenant,
                                 pinned=pinned)

    def _fragmentation_locked(self):
        """1 - (largest contiguous free-id run / free blocks); 0 when
        the free list is empty.  Any block satisfies any allocation, so
        this is a locality signal (how scattered reuse has become), not
        an allocation-failure predictor."""
        if not self._free:
            return 0.0
        free = sorted(self._free)
        best = run = 1
        for a, b in zip(free, free[1:]):
            run = run + 1 if b == a + 1 else 1
            if run > best:
                best = run
        return 1.0 - best / len(free)

    def fragmentation(self):
        """Free-list fragmentation in [0, 1] (see the locked helper)."""
        with self._lock:
            return self._fragmentation_locked()

    def capacity_snapshot(self):
        """One consistent read of the pool's capacity state: counts,
        fragmentation, high watermark, every ledger holder row and the
        per-tenant attribution — the forensic record's raw material
        (holders and tenants share one totals pass — ledger.views)."""
        with self._lock:
            holders, tenants = self.ledger.views()
            return {
                "num_blocks": self.num_blocks,
                "block_bytes": self.ledger.block_bytes,
                "used_blocks": len(self._held),
                "free_blocks": len(self._free),
                "total_refs": sum(self._refs.values()),
                "high_watermark_blocks": self.ledger.high_watermark,
                "fragmentation": self._fragmentation_locked(),
                "holders": holders,
                "tenants": tenants,
            }

    def audit(self):
        """Verify the accounting identity (ledger vs refcounts, exact
        per-tenant byte sums — accounting.CapacityLedger.audit) and
        return the audit report; raises on any violation.  The serve CI
        tier runs this after every chaos storm."""
        with self._lock:
            report = self.ledger.audit(dict(self._refs))
            report["free_blocks"] = len(self._free)
            report["num_blocks"] = self.num_blocks
            report["fragmentation"] = self._fragmentation_locked()
            return report

    def refcount(self, block_id):
        """The block's live reference count (0 when not held)."""
        with self._lock:
            return self._refs.get(block_id, 0)

    def refcounts(self):
        """``{block_id: refcount}`` for every held block — the audit
        surface: after every sequence is freed and the prefix index
        dropped, this must be empty (CI's post-storm allocator audit)."""
        with self._lock:
            return dict(self._refs)

    @property
    def available(self):
        """Blocks currently on the free list."""
        with self._lock:
            return len(self._free)

    @property
    def used(self):
        with self._lock:
            return len(self._held)

    def utilization(self):
        """Used fraction of the pool, in [0, 1]."""
        with self._lock:
            return len(self._held) / self.num_blocks


class _Sequence:
    __slots__ = ("blocks", "length", "holder", "tenant")

    def __init__(self, holder=None, tenant=DEFAULT_TENANT):
        self.blocks = []
        self.length = 0
        self.holder = holder    # the sequence's ledger holder id
        self.tenant = tenant


class PrefillPlan:
    """A pinned prefix match (:meth:`PagedKVCache.match_prefix`):
    ``blocks`` are increfed physical ids covering the leading
    ``tokens_matched`` prompt tokens.  A plan MUST flow into exactly one
    of :meth:`PagedKVCache.commit_prefill` (which takes ownership of the
    pins) or :meth:`PagedKVCache.abandon_plan` (which releases them) —
    dropping it on the floor leaks references until the audit catches
    it."""

    __slots__ = ("blocks", "tokens_matched", "holder", "_consumed")

    def __init__(self, blocks, tokens_matched, holder=None):
        self.blocks = list(blocks)
        self.tokens_matched = int(tokens_matched)
        # the plan's capacity-ledger holder id (pinned attribution):
        # commit reassigns it to the sequence, abandon releases it
        self.holder = holder
        # a plan's pins are released exactly once (by commit_prefill or
        # abandon_plan).  Without this flag a double abandon — or an
        # abandon after commit — would free() blocks the plan no longer
        # owns, silently stealing ANOTHER holder's reference (the index
        # or a live sequence) and eventually serving a recycled block's
        # K/V as someone's cached prefix.  The allocator cannot catch
        # that (the block is legitimately held); the plan must.
        self._consumed = False

    def consume(self):
        """Mark the pins as spent; raises on a second consumption —
        the refcount analog of 'double-free stays loud'."""
        if self._consumed:
            raise MXNetError(
                "PrefillPlan already consumed (committed or abandoned) — "
                "releasing its pins again would steal another holder's "
                "reference and corrupt served K/V")
        self._consumed = True

    def __repr__(self):
        return (f"PrefillPlan({len(self.blocks)} shared blocks, "
                f"{self.tokens_matched} tokens"
                + (", consumed)" if self._consumed else ")"))


class PagedKVCache:
    """Block-pooled K/V storage for many concurrent sequences.

    One pool pair per call site::

        cache = PagedKVCache(num_layers=2, num_heads=4, head_dim=16,
                             block_size=16, num_blocks=256)
        cache.prefill("req-1", k, v)        # bulk-fill: k/v (N, L, H, D)
        pos = cache.reserve("req-1")        # O(1) append: one slot
        cache.write("req-1", layer, k1, v1) # fill the reserved slot
        kd, vd, lens = cache.gather_batch(["req-1", ...], layer)
        cache.free_sequence("req-1")        # blocks back to the free list

    ``reserve`` + per-layer ``write`` split the append because a decoder
    computes layer i's K/V only after layer i-1's attention — the slot is
    reserved once per token (the O(1) step), then each layer writes its
    projection into it as the forward proceeds.

    ``gather_batch`` is the dense-gather decode fallback: it materializes
    a padded ``(B, Lmax, H, D)`` view by copying block slices — O(total
    context) per call, the documented cost of serving attention without
    the paged kernel (docs/DIVERGENCES.md #27).  The paged decode path
    instead reads :meth:`batch_tables` + :meth:`pool` and indexes the
    pool in-kernel.
    """

    def __init__(self, num_layers, num_heads, head_dim, block_size=16,
                 num_blocks=256, dtype=np.float32, storage="host",
                 share_prefix=None, forensics=None):
        if storage not in ("host", "device"):
            raise ValueError(f"storage must be 'host' or 'device', "
                             f"got {storage!r}")
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.block_size = int(block_size)
        if self.block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        # per-token K/V footprint across all layers, both pools — the
        # unit of the prefill-bytes accounting, and (× block_size) the
        # capacity ledger's block-bytes denomination
        self._token_bytes = (self.num_layers * self.num_heads
                             * self.head_dim * 2 * np.dtype(dtype).itemsize)
        self.allocator = BlockAllocator(
            num_blocks, block_bytes=self._token_bytes * self.block_size)
        self.storage = storage
        # exhaustion forensics (ISSUE 14): a bounded ring of capacity
        # records — one per genuine CacheExhausted and per prefix-index
        # pressure eviction — persisted (rolling, atomic) as
        # <forensics>-capacity.json when a path prefix is armed
        self._forensics = deque(maxlen=256)
        self._forensics_path = (f"{forensics}-capacity.json"
                                if forensics else None)
        self._forensics_dumped = None   # monotonic time of last disk dump
        layer_shape = (self.allocator.num_blocks, self.block_size,
                       self.num_heads, self.head_dim)
        if storage == "device":
            try:
                import jax.numpy as jnp
            except ImportError:
                raise MXNetError(
                    "PagedKVCache: storage='device' needs jax — use the "
                    "default host storage (dense-gather decode) without "
                    "it") from None
            # per-layer pools (not one (L, N, ...) array): layer reads on
            # the decode hot path must be O(1) handle lookups, never a
            # per-step slice copy of the whole pool
            self._k_dev = [jnp.zeros(layer_shape, dtype)
                           for _ in range(self.num_layers)]
            self._v_dev = [jnp.zeros(layer_shape, dtype)
                           for _ in range(self.num_layers)]
            self.k_blocks = self.v_blocks = None
        else:
            shape = (self.num_layers,) + layer_shape
            self.k_blocks = np.zeros(shape, dtype)
            self.v_blocks = np.zeros(shape, dtype)
        self._lock = threading.RLock()
        self._seqs = {}
        # shared-prefix index (ISSUE 12): None = every prefill is
        # private (the pre-sharing behavior, bit-for-bit).  The knob
        # defaults to the TPUMX_PREFIX_SHARING env resolution so an
        # engine, the bench arms, and a bare test cache all agree.
        if share_prefix is None:
            share_prefix = prefix_sharing_enabled()
        if share_prefix and np.dtype(dtype) != np.float32:
            # the suffix prefill attends over PREFIX K/V read back from
            # the pool; a quantized pool (f16/bf16) would feed it
            # pool-rounded values where the sharing-off arm recomputes
            # the prefix at model precision — silently different logits
            # is the one failure mode sharing must never have, so a
            # lossy pool refuses loudly instead (docs/DIVERGENCES.md
            # #28; widen by writing the index's compute-precision copy
            # if a quantized shared pool is ever needed)
            raise ValueError(
                f"share_prefix requires a float32 pool (got "
                f"{np.dtype(dtype).name}): a lossy pool dtype would "
                "break the sharing-on/off bit-equality guarantee")
        self.prefix = PrefixIndex(self.block_size) if share_prefix else None
        self._prompt_tokens = 0     # tokens requested across prefills
        self._cached_tokens = 0     # of those, served from the index
        self._cow_copies = 0

    @property
    def device_resident(self):
        """True when the block pools live on the accelerator (jax
        arrays) rather than in host numpy — the `serve.
        pool_device_resident` gauge's source of truth."""
        return self.storage == "device"

    # -- bookkeeping ---------------------------------------------------------
    def _entry(self, seq_id):
        try:
            return self._seqs[seq_id]
        except KeyError:
            raise MXNetError(f"PagedKVCache: unknown sequence {seq_id!r} "
                             "(never prefilled, or already freed)") from None

    def has_sequence(self, seq_id):
        with self._lock:
            return seq_id in self._seqs

    def length(self, seq_id):
        """Tokens currently cached for ``seq_id`` (reserved slots count)."""
        with self._lock:
            return self._entry(seq_id).length

    def block_table(self, seq_id):
        """The sequence's block-id table (a copy), in position order."""
        with self._lock:
            return list(self._entry(seq_id).blocks)

    def num_sequences(self):
        with self._lock:
            return len(self._seqs)

    def utilization(self):
        return self.allocator.utilization()

    def blocks_for(self, num_tokens):
        """Blocks a ``num_tokens``-long prefill needs (admission math)."""
        return -(-int(num_tokens) // self.block_size)

    # -- writes --------------------------------------------------------------
    def _alloc(self, n, holder=None):
        """``allocator.alloc`` with prefix-cache pressure relief: on
        exhaustion, least-recently-matched index-only prefixes are
        released and the allocation retried ONCE.  When the pool is
        genuinely full of live sequence data, :class:`CacheExhausted`
        propagates — the backpressure contract is unchanged, the index
        merely never stands between a live request and free memory.
        Both the pressure eviction and the genuine exhaustion leave a
        capacity forensic record naming every live holder (ISSUE 14).
        Called under the cache lock."""
        try:
            return self.allocator.alloc(n, holder=holder)
        except CacheExhausted:
            if self.prefix is None:
                self._record_forensic("exhaustion", need=n)
                raise
            released = self.prefix.release(self.allocator, n)
            if released:
                _telemetry.counter("serve.prefix_evictions").inc(released)
                _tracing.emit("serve.prefix_evict", released=released,
                              need=int(n))
                self._record_forensic("pressure_evict", need=n,
                                      released=released)
            try:
                return self.allocator.alloc(n, holder=holder)
            except CacheExhausted:
                self._record_forensic("exhaustion", need=n)
                raise

    def _record_forensic(self, kind, need, released=0):
        """Snapshot WHO holds the pool at a capacity event — every
        holder (sequence/index/plan) with its tenant, block counts,
        pinned/shared state and age — into the bounded forensic ring,
        and persist the ring (rolling, atomic) when a path is armed.
        A ``CacheExhausted`` additionally lands on the flight-recorder
        timeline so a backpressure incident's black box names the
        forensic file.  Best-effort: forensics must never turn
        backpressure into a crash.  Called under the cache lock."""
        snap = self.allocator.capacity_snapshot()
        rec = {"kind": kind, "ts": time.time(), "need": int(need),
               "free": snap["free_blocks"], "released": int(released),
               "pool": {k: snap[k] for k in
                        ("num_blocks", "block_bytes", "used_blocks",
                         "total_refs", "high_watermark_blocks",
                         "fragmentation")},
               "holders": snap["holders"], "tenants": snap["tenants"]}
        self._forensics.append(rec)
        if kind == "exhaustion":
            _tracing.emit("serve.capacity_exhausted", need=int(need),
                          free=int(snap["free_blocks"]),
                          holders=len(snap["holders"]),
                          forensic=self._forensics_path or "")
        # disk dumps are rate-limited (>= 1 s apart, first record
        # always): the RING holds every record regardless, but a
        # sustained overload storm raises CacheExhausted per bounced
        # prefill and an O(ring) atomic rewrite under the cache lock
        # per event would stall the data plane exactly when it is
        # already exhausted.  flush_forensics() force-syncs at
        # teardown/audit time.
        now = time.monotonic()
        if self._forensics_path and (self._forensics_dumped is None
                                     or now - self._forensics_dumped
                                     >= 1.0):
            self._forensics_dumped = now
            try:
                _accounting.dump_forensics(self._forensics_path,
                                           self._forensics)
            except Exception:  # noqa: BLE001 — forensics are best-effort
                pass

    def forensic_records(self):
        """The in-memory capacity forensic ring (newest last)."""
        with self._lock:
            return list(self._forensics)

    def flush_forensics(self):
        """Force-sync the forensic ring to disk (bypassing the dump
        rate limit) — teardown and post-storm audit call this so the
        on-disk record set matches the ring exactly.  Returns the path
        written, or None (unarmed / empty ring)."""
        with self._lock:
            if not self._forensics_path or not self._forensics:
                return None
            self._forensics_dumped = time.monotonic()
            return _accounting.dump_forensics(self._forensics_path,
                                              self._forensics)

    def _fill(self, blocks, k, v, offset=0):
        """Write ``k``/``v`` (``(num_layers, T, H, D)``) into ``blocks``
        starting at slot ``offset`` of the first block (``offset`` is
        the in-block remainder of a block-aligned prefix — 0 everywhere
        today because only full blocks are shared).  Called under the
        cache lock, blocks privately owned by the caller."""
        length = k.shape[1]
        bs = self.block_size
        if self.storage == "device":
            _, _, write_blocks, _ = _dev_ops()
            nb = len(blocks)
            pad = nb * bs - length - offset
            bids = np.asarray(blocks, np.int32)
            for layer in range(self.num_layers):
                # one scatter per pool per layer: the prompt's K/V
                # crosses to the device once, zero-padded to whole
                # blocks (the tail slots are this sequence's own
                # future append slots)
                ck = np.pad(k[layer], ((offset, pad), (0, 0), (0, 0)))
                cv = np.pad(v[layer], ((offset, pad), (0, 0), (0, 0)))
                self._k_dev[layer] = write_blocks(
                    self._k_dev[layer], bids,
                    ck.reshape(nb, bs, *ck.shape[1:]))
                self._v_dev[layer] = write_blocks(
                    self._v_dev[layer], bids,
                    cv.reshape(nb, bs, *cv.shape[1:]))
        else:
            for i, bid in enumerate(blocks):
                lo = max(i * bs - offset, 0)
                hi = min((i + 1) * bs - offset, length)
                s0 = offset if i == 0 else 0
                self.k_blocks[:, bid, s0:s0 + hi - lo] = k[:, lo:hi]
                self.v_blocks[:, bid, s0:s0 + hi - lo] = v[:, lo:hi]

    def _account_prefill(self, computed_tokens, cached_tokens):
        """Prefill byte accounting + the hit-ratio gauge (under the
        cache lock; telemetry's registry lock is a leaf)."""
        self._prompt_tokens += computed_tokens + cached_tokens
        self._cached_tokens += cached_tokens
        _telemetry.counter("serve.prefill_bytes").inc(
            computed_tokens * self._token_bytes)
        if cached_tokens:
            _telemetry.counter("serve.prefix_hits").inc()
            _telemetry.counter("serve.prefill_bytes_saved").inc(
                cached_tokens * self._token_bytes)
        if self._prompt_tokens:
            _telemetry.gauge("serve.prefix_hit_ratio").set(
                self._cached_tokens / self._prompt_tokens)

    def prefill(self, seq_id, k, v, tokens=None, tenant=None):
        """Bulk-fill a new sequence's blocks in one call.

        ``k``/``v``: ``(num_layers, L, num_heads, head_dim)``.  Allocates
        exactly ``ceil(L / block_size)`` blocks all-or-nothing — on
        :class:`CacheExhausted` nothing is registered, so the scheduler
        can requeue the request and retry after an eviction.  ``tokens``
        (the prompt's token ids, optional) lets the shared-prefix index
        learn this sequence's full blocks for future reuse — omitted,
        the prefill stays private (the pre-sharing behavior).
        ``tenant`` is the capacity ledger's attribution key (defaults
        to the single-tenant default)."""
        k = np.asarray(k)
        v = np.asarray(v)
        want = (self.num_layers, k.shape[1], self.num_heads, self.head_dim)
        if k.shape != want or v.shape != want:
            raise ValueError(
                f"prefill: k/v must be (num_layers={self.num_layers}, L, "
                f"H={self.num_heads}, D={self.head_dim}); got {k.shape} / "
                f"{v.shape}")
        length = k.shape[1]
        if length < 1:
            raise ValueError("prefill: empty prompt")
        if tokens is not None and len(tokens) != length:
            raise ValueError(f"prefill: {len(tokens)} tokens for {length} "
                             "K/V positions")
        tenant = DEFAULT_TENANT if tenant is None else str(tenant)
        holder = f"seq:{seq_id}"
        with self._lock:
            if seq_id in self._seqs:
                raise MXNetError(f"prefill: sequence {seq_id!r} already "
                                 "cached (free it first)")
            blocks = self._alloc(self.blocks_for(length), holder=holder)
            self.allocator.describe(holder, kind="sequence", tenant=tenant)
            # fill BEFORE publishing in _seqs: a concurrent gather must
            # never see a registered-but-empty sequence (all-zero K/V
            # would be silently wrong logits, not an error)
            self._fill(blocks, k, v)
            entry = _Sequence(holder=holder, tenant=tenant)
            entry.blocks = blocks
            entry.length = length
            self._seqs[seq_id] = entry
            if self.prefix is not None and tokens is not None:
                self.prefix.insert(tokens, blocks, self.allocator)
            self._account_prefill(length, 0)

    # -- shared-prefix prefill (ISSUE 12) ------------------------------------
    def match_prefix(self, tokens, tenant=None):
        """The longest indexed full-block prefix of ``tokens``, PINNED:
        the matched blocks are increfed under the lock so pressure
        eviction can never reuse them between the match and the commit.
        Returns a :class:`PrefillPlan` or None (sharing off, or no
        match).  Every plan must reach :meth:`commit_prefill` or
        :meth:`abandon_plan`.  The pins are ledgered as a ``plan``
        holder under ``tenant`` — a backpressure forensic taken
        mid-plan attributes the pinned blocks to the tenant whose
        prefill pinned them."""
        if self.prefix is None:
            return None
        with self._lock:
            blocks, m = self.prefix.match(tokens)
            if not m:
                return None
            holder = f"plan:{next(_plan_ids)}"
            self.allocator.incref(blocks, holder=holder)
            self.allocator.describe(
                holder, kind="plan",
                tenant=DEFAULT_TENANT if tenant is None else str(tenant),
                pinned=True)
            return PrefillPlan(blocks, m, holder=holder)

    def gather_plan(self, plan):
        """The pinned prefix's K/V as host ``(num_layers, m, H, D)``
        arrays — the suffix prefill's attention operands.  A device pool
        pays one fetch here; acceptable because prefill is host-resident
        anyway (docs/DIVERGENCES.md #27) and the fetch replaces the
        whole prefix's projection matmuls."""
        m = plan.tokens_matched
        ks = np.empty((self.num_layers, m, self.num_heads, self.head_dim),
                      np.float32)
        vs = np.empty_like(ks)
        for layer in range(self.num_layers):
            kp, vp = self.pool(layer)
            if self.storage == "device":
                import jax.numpy as jnp
                # tpumx-lint: disable=hot-path-purity -- prefill-path
                # fetch of the shared prefix (one gather per layer per
                # SHARED prefill, replacing the prefix's full projection
                # compute); decode never takes this path
                idx = jnp.asarray(plan.blocks, jnp.int32)
                kp, vp = np.asarray(kp[idx]), np.asarray(vp[idx])
            else:
                kp, vp = kp[plan.blocks], vp[plan.blocks]
            ks[layer] = kp.reshape(-1, self.num_heads, self.head_dim)[:m]
            vs[layer] = vp.reshape(-1, self.num_heads, self.head_dim)[:m]
        return ks, vs

    def commit_prefill(self, seq_id, plan, k, v, tokens, tenant=None):
        """Register ``seq_id`` as the pinned prefix plus the computed
        suffix: ``k``/``v`` are ``(num_layers, S, H, D)`` projections
        for ``tokens[plan.tokens_matched:]``.  All-or-nothing like
        :meth:`prefill`: on ANY failure (suffix allocation hitting
        genuine exhaustion included) the plan's pins are released and
        nothing is registered — the scheduler defers and the retry
        re-plans from scratch.  On success the plan's pinned ledger
        attribution is reassigned to the sequence's holder."""
        k = np.asarray(k)
        v = np.asarray(v)
        m = plan.tokens_matched
        length = m + k.shape[1]
        tenant = DEFAULT_TENANT if tenant is None else str(tenant)
        holder = f"seq:{seq_id}"
        with self._lock:
            plan.consume()   # pins spent here, succeed or fail
            fresh = []
            published = False
            try:
                if seq_id in self._seqs:
                    raise MXNetError(f"commit_prefill: sequence {seq_id!r} "
                                     "already cached (free it first)")
                if length != len(tokens):
                    raise ValueError(
                        f"commit_prefill: {len(tokens)} tokens vs "
                        f"{m} matched + {k.shape[1]} suffix positions")
                if m % self.block_size != 0 or k.shape[1] < 1:
                    raise ValueError(
                        f"commit_prefill: matched prefix ({m}) must be "
                        f"block-aligned with a non-empty suffix")
                want = (self.num_layers, k.shape[1], self.num_heads,
                        self.head_dim)
                if k.shape != want or v.shape != want:
                    raise ValueError(
                        f"commit_prefill: suffix k/v must be {want}, got "
                        f"{k.shape} / {v.shape}")
                fresh = self._alloc(self.blocks_for(length)
                                    - len(plan.blocks), holder=holder)
                self.allocator.describe(holder, kind="sequence",
                                        tenant=tenant)
                self._fill(fresh, k, v)
                entry = _Sequence(holder=holder, tenant=tenant)
                entry.blocks = plan.blocks + fresh
                entry.length = length
                self._seqs[seq_id] = entry
                published = True
                self.prefix.insert(tokens, entry.blocks, self.allocator)
                # LAST, so the except arm below can still release the
                # pins under the plan's holder: the pinned attribution
                # becomes the sequence's (refcounts untouched)
                self.allocator.reassign(plan.blocks, plan.holder, holder)
            except BaseException:
                # ALL-or-nothing: unregister (only what THIS call
                # published — the already-cached guard's failure must
                # not destroy the pre-existing live sequence), release
                # the plan's pins AND any fresh blocks allocated above —
                # a fill/insert fault must not leak held refcounts (the
                # post-storm audit would catch it, after the pool had
                # already shrunk) or publish a half-built sequence
                if published:
                    self._seqs.pop(seq_id, None)
                if fresh:
                    self.allocator.free(fresh, holder=holder)
                self.allocator.free(plan.blocks, holder=plan.holder)
                raise
            self._account_prefill(k.shape[1], m)

    def abandon_plan(self, plan):
        """Release a plan's pins without committing (the model faulted
        between match and commit).  Like :meth:`commit_prefill` this
        consumes the plan — a second release raises instead of stealing
        another holder's reference."""
        with self._lock:
            plan.consume()
            self.allocator.free(plan.blocks, holder=plan.holder)

    def fork(self, parent_id, child_id, tenant=None):
        """Register ``child_id`` sharing ALL of ``parent_id``'s blocks
        (one incref per block) — the parallel-sampling shape: N
        generations from one prompt pay one prefill and one copy of the
        prompt's KV.  Both siblings copy-on-write their shared tail
        block on their next divergent append (:meth:`reserve`).
        ``tenant`` defaults to the parent's ledger attribution."""
        with self._lock:
            if child_id in self._seqs:
                raise MXNetError(f"fork: sequence {child_id!r} already "
                                 "cached (free it first)")
            parent = self._entry(parent_id)
            holder = f"seq:{child_id}"
            self.allocator.incref(parent.blocks, holder=holder)
            self.allocator.describe(
                holder, kind="sequence",
                tenant=parent.tenant if tenant is None else str(tenant))
            entry = _Sequence(holder=holder,
                              tenant=parent.tenant if tenant is None
                              else str(tenant))
            entry.blocks = list(parent.blocks)
            entry.length = parent.length
            self._seqs[child_id] = entry

    def _cow_tail(self, entry):
        """Copy-on-write the entry's (shared) tail block: allocate a
        private block, duplicate the tail's slots into it, drop one
        reference on the original.  The sharers keep reading the
        original bits; this sequence appends into its own copy — the
        write is invisible to them by construction."""
        old = entry.blocks[-1]
        new = self._alloc(1, holder=entry.holder)[0]
        if self.storage == "device":
            _, _, _, copy_block = _dev_ops()
            for layer in range(self.num_layers):
                self._k_dev[layer] = copy_block(self._k_dev[layer], new, old)
                self._v_dev[layer] = copy_block(self._v_dev[layer], new, old)
        else:
            self.k_blocks[:, new] = self.k_blocks[:, old]
            self.v_blocks[:, new] = self.v_blocks[:, old]
        entry.blocks[-1] = new
        self.allocator.free([old], holder=entry.holder)
        self._cow_copies += 1
        _telemetry.counter("serve.cow_copies").inc()

    def reserve(self, seq_id):
        """Reserve the next token's slot: the O(1) append.  At most one
        free-list pop (when the tail block is full); returns the position
        index the per-layer :meth:`write` calls will fill.  A partially
        filled tail block that is SHARED (refcount > 1 — a fork sibling
        or the prefix index holds it) is copy-on-written first: appends
        must never mutate bits another reader sees.  On
        :class:`CacheExhausted` the sequence is unchanged — the caller
        preempts it (free + requeue), never crashes."""
        with self._lock:
            entry = self._entry(seq_id)
            if entry.length % self.block_size == 0:
                entry.blocks.extend(self._alloc(1, holder=entry.holder))
            elif self.allocator.refcount(entry.blocks[-1]) > 1:
                self._cow_tail(entry)
            pos = entry.length
            entry.length = pos + 1
            return pos

    def reserve_window(self, seq_id, k):
        """Reserve ``k`` consecutive slots in one call — the speculative
        draft window's append (ISSUE 16).  All-or-nothing like every
        allocation on this class: on :class:`CacheExhausted` midway the
        freshly grabbed blocks are released and the length restored, so
        the caller preempts exactly as it would for a single-slot
        :meth:`reserve` (a completed copy-on-write of the shared tail is
        kept — it is semantically invisible: same bits, private copy).
        Returns the reserved positions ``[length, ..., length+k-1]``."""
        k = int(k)
        if k < 1:
            raise ValueError(f"reserve_window: k must be >= 1, got {k}")
        with self._lock:
            entry = self._entry(seq_id)
            base_nblocks = len(entry.blocks)
            base_length = entry.length
            try:
                if (entry.length % self.block_size != 0
                        and self.allocator.refcount(entry.blocks[-1]) > 1):
                    self._cow_tail(entry)
                need = (-(-(entry.length + k) // self.block_size)
                        - len(entry.blocks))
                if need > 0:
                    entry.blocks.extend(self._alloc(need,
                                                    holder=entry.holder))
            except CacheExhausted:
                fresh = entry.blocks[base_nblocks:]
                if fresh:
                    self.allocator.free(fresh, holder=entry.holder)
                    del entry.blocks[base_nblocks:]
                entry.length = base_length
                raise
            entry.length = base_length + k
            return list(range(base_length, base_length + k))

    def truncate(self, seq_id, length):
        """Shrink ``seq_id`` to ``length`` cached tokens — speculative
        decode's rejection path: the verify step reserved a whole draft
        window, the model accepted a prefix of it, and the unaccepted
        tail slots must stop being part of the sequence (the NEXT window
        overwrites those pool slots, but the length/table bookkeeping
        must agree with the accepted stream NOW).  Whole blocks past the
        new tail drop one reference each (shared blocks survive, as
        everywhere).  No-op when ``length`` already matches."""
        length = int(length)
        if length < 1:
            raise ValueError(f"truncate: length must be >= 1, got {length}")
        with self._lock:
            entry = self._entry(seq_id)
            if length > entry.length:
                raise MXNetError(
                    f"truncate: sequence {seq_id!r} holds {entry.length} "
                    f"tokens — cannot grow to {length} (use reserve)")
            keep = self.blocks_for(length)
            tail = entry.blocks[keep:]
            if tail:
                self.allocator.free(tail, holder=entry.holder)
                del entry.blocks[keep:]
            entry.length = length

    def window_slots(self, seq_ids, k):
        """The (block id, in-block offset) address of each sequence's
        last ``k`` reserved slots, as int32 ``(B, k)`` arrays — the
        fused decode step's in-program scatter coordinates (the device
        program writes the draft window's K/V straight into the donated
        pool at these addresses; no host-side write call happens at
        all)."""
        with self._lock:
            bids = np.empty((len(seq_ids), k), np.int32)
            offs = np.empty((len(seq_ids), k), np.int32)
            for i, s in enumerate(seq_ids):
                entry = self._entry(s)
                for j in range(k):
                    pos = entry.length - k + j
                    bids[i, j] = entry.blocks[pos // self.block_size]
                    offs[i, j] = pos % self.block_size
        return bids, offs

    def write(self, seq_id, layer, k, v):
        """Write one layer's K/V projection into the newest reserved slot
        (``k``/``v``: ``(num_heads, head_dim)``)."""
        with self._lock:
            entry = self._entry(seq_id)
            pos = entry.length - 1
            bid = entry.blocks[pos // self.block_size]
            off = pos % self.block_size
            if self.storage == "device":
                # numpy operands cross the jit boundary on the C++ fast
                # path; an eager jnp.asarray per operand costs ~73us of
                # dispatch each and dominated the per-token write cost
                write_slot, _, _, _ = _dev_ops()
                self._k_dev[layer] = write_slot(
                    self._k_dev[layer], bid, off, np.asarray(k))
                self._v_dev[layer] = write_slot(
                    self._v_dev[layer], bid, off, np.asarray(v))
            else:
                self.k_blocks[layer, bid, off] = k
                self.v_blocks[layer, bid, off] = v

    def write_batch(self, seq_ids, layer, k, v):
        """Write one layer's K/V for a whole decode batch into each
        sequence's newest reserved slot (``k``/``v``: ``(B, num_heads,
        head_dim)``).  On device storage this is ONE scatter per pool —
        the decode hot path's per-step write cost — instead of B
        round-trips; host storage loops the per-sequence slot writes."""
        with self._lock:
            slots = []
            for s in seq_ids:
                entry = self._entry(s)
                pos = entry.length - 1
                slots.append((entry.blocks[pos // self.block_size],
                              pos % self.block_size))
            if self.storage == "device":
                _, write_rows, _, _ = _dev_ops()
                bids = np.asarray([b for b, _ in slots], np.int32)
                offs = np.asarray([o for _, o in slots], np.int32)
                self._k_dev[layer] = write_rows(
                    self._k_dev[layer], bids, offs, np.asarray(k))
                self._v_dev[layer] = write_rows(
                    self._v_dev[layer], bids, offs, np.asarray(v))
            else:
                for i, (bid, off) in enumerate(slots):
                    self.k_blocks[layer, bid, off] = k[i]
                    self.v_blocks[layer, bid, off] = v[i]

    def write_window(self, seq_ids, layer, k, v):
        """Write one layer's K/V for a whole draft window into each
        sequence's last ``K`` reserved slots (``k``/``v``: ``(B, K,
        num_heads, head_dim)``) — the host-resident arm of speculative
        decode (ISSUE 16).  Device storage pays ONE scatter per pool for
        the whole ``B*K`` window (flattened rows), exactly like
        :meth:`write_batch` does for ``K == 1``."""
        kw = k.shape[1]
        bids, offs = self.window_slots(seq_ids, kw)
        with self._lock:
            if self.storage == "device":
                _, write_rows, _, _ = _dev_ops()
                flat = (len(seq_ids) * kw,) + k.shape[2:]
                self._k_dev[layer] = write_rows(
                    self._k_dev[layer], bids.ravel(), offs.ravel(),
                    np.asarray(k).reshape(flat))
                self._v_dev[layer] = write_rows(
                    self._v_dev[layer], bids.ravel(), offs.ravel(),
                    np.asarray(v).reshape(flat))
            else:
                for i in range(len(seq_ids)):
                    for j in range(kw):
                        self.k_blocks[layer, bids[i, j], offs[i, j]] = \
                            k[i, j]
                        self.v_blocks[layer, bids[i, j], offs[i, j]] = \
                            v[i, j]

    def free_sequence(self, seq_id):
        """Evict: drop one reference per block (copy-free — contents
        stay until reuse).  A block only this sequence held returns to
        the free list; one the prefix index or a fork sibling shares
        SURVIVES at its remaining count — freeing a preempted sequence
        can never evict a block another live sequence reads.  Returns
        the number of block references released."""
        with self._lock:
            entry = self._seqs.pop(seq_id, None)
            if entry is None:
                return 0
            self.allocator.free(entry.blocks, holder=entry.holder)
            return len(entry.blocks)

    def exclusive_blocks(self, seq_id):
        """How many of the sequence's blocks only IT holds (refcount
        1) — what freeing it would actually return to the pool.  The
        engine's preemption victim selection reads this: evicting a
        sequence whose blocks are all shared frees nothing."""
        with self._lock:
            entry = self._seqs.get(seq_id)
            if entry is None:
                return 0
            return sum(1 for b in entry.blocks
                       if self.allocator.refcount(b) == 1)

    def drop_prefix_cache(self):
        """Release EVERY prefix-index reference (teardown, tests, and
        the CI post-storm audit: after this plus freeing every sequence,
        ``allocator.refcounts()`` must be empty).  Returns the number of
        index entries dropped; 0 when sharing is off."""
        with self._lock:
            if self.prefix is None:
                return 0
            return self.prefix.drop_all(self.allocator)

    def prefix_stats(self):
        """Sharing observability: ``{sharing, prompt_tokens,
        cached_tokens, hit_ratio, prefill_bytes, prefill_bytes_saved,
        cow_copies}`` plus the index's own ``{nodes, lookups, hits,
        tokens_matched, evictions}`` when sharing is on."""
        with self._lock:
            out = {
                "sharing": self.prefix is not None,
                "prompt_tokens": self._prompt_tokens,
                "cached_tokens": self._cached_tokens,
                "hit_ratio": (self._cached_tokens / self._prompt_tokens
                              if self._prompt_tokens else 0.0),
                "prefill_bytes": ((self._prompt_tokens
                                   - self._cached_tokens)
                                  * self._token_bytes),
                "prefill_bytes_saved": (self._cached_tokens
                                        * self._token_bytes),
                "cow_copies": self._cow_copies,
            }
            if self.prefix is not None:
                out.update(self.prefix.stats())
            return out

    # -- reads: the paged-kernel operands ------------------------------------
    def pool(self, layer):
        """``layer``'s ``(num_blocks, block_size, H, D)`` K and V pools —
        the paged-attention kernel's HBM operands.  Device storage
        returns the resident jax arrays (an O(1) handle, no copy); host
        storage returns numpy views (the kernel's interpret-mode /
        parity-test arm pays the host->device copy per call, which is
        why production paged decode pairs with ``storage='device'``)."""
        if self.storage == "device":
            return self._k_dev[layer], self._v_dev[layer]
        return self.k_blocks[layer], self.v_blocks[layer]

    def pools(self):
        """EVERY layer's resident K and V pool handles, as two lists —
        the fused decode step's donated operands (serving/jax_model.py
        passes them into ONE jitted program that writes the window's
        K/V and returns the new buffers).  Device storage only: the
        whole point is that the handles are consumable device arrays."""
        if self.storage != "device":
            raise MXNetError(
                "PagedKVCache.pools: the fused decode step needs "
                "device-resident pools (storage='device')")
        return list(self._k_dev), list(self._v_dev)

    def adopt_pools(self, k_pools, v_pools):
        """Install the pool buffers a fused decode step returned — the
        other half of the donation handoff: the program CONSUMED the
        handles :meth:`pools` handed it, and these are their successors.
        Anything still holding a pre-step handle is stale by contract
        (module docstring: pool array access is step-thread-owned)."""
        if self.storage != "device":
            raise MXNetError(
                "PagedKVCache.adopt_pools: device storage only")
        if (len(k_pools) != self.num_layers
                or len(v_pools) != self.num_layers):
            raise ValueError(
                f"adopt_pools: expected {self.num_layers} pool pairs, "
                f"got {len(k_pools)}/{len(v_pools)}")
        self._k_dev = list(k_pools)
        self._v_dev = list(v_pools)

    def batch_tables(self, seq_ids):
        """The decode batch's raw block tables: int32 ``(B, NBpad)`` ids
        plus int32 ``(B,)`` true lengths — what the paged kernel walks.

        Rows are padded with block 0 past each sequence's real blocks
        (valid pool indices by construction — the kernel contract: the
        padded fetches are finite garbage the length mask excludes
        exactly), and NBpad is the batch max rounded up to a BUCKET —
        power of two up to 4 blocks, then multiples of 4 — so jitted
        consumers see a bounded set of shapes instead of recompiling at
        every block-boundary crossing.  The bucket is deliberately fine:
        pow2 buckets made the padded gather tail up to 2x the true
        context (a count on a CPU host, not a speed: the per-token cost
        over a long generation then grew with the padding); at
        mult-4 the tail is <=3 blocks and a 4096-block pool still
        compiles at most ~1k shapes over its whole lifetime."""
        with self._lock:
            entries = [self._entry(s) for s in seq_ids]
            tables = [(list(e.blocks), e.length) for e in entries]
        nb = max(len(blocks) for blocks, _ in tables)
        nbpad = _next_pow2(nb) if nb <= 4 else -(-nb // 4) * 4
        ids = np.zeros((len(tables), nbpad), np.int32)
        for i, (blocks, _) in enumerate(tables):
            ids[i, :len(blocks)] = blocks
        lengths = np.array([length for _, length in tables], np.int32)
        return ids, lengths

    # -- reads (the dense-gather fallback) -----------------------------------
    def gather(self, seq_id, layer):
        """One sequence's dense ``(L, H, D)`` K/V for ``layer`` — the
        block table resolved in one fancy-index gather (a copy; device
        storage gathers on-device, then fetches the result)."""
        with self._lock:
            entry = self._entry(seq_id)
            blocks = list(entry.blocks)
            length = entry.length
        kp, vp = self.pool(layer)
        if self.storage == "device":
            import jax.numpy as jnp
            idx = jnp.asarray(blocks, jnp.int32)
            kp, vp = np.asarray(kp[idx]), np.asarray(vp[idx])
        else:
            kp, vp = kp[blocks], vp[blocks]
        k = kp.reshape(-1, self.num_heads, self.head_dim)
        v = vp.reshape(-1, self.num_heads, self.head_dim)
        return k[:length], v[:length]

    def gather_batch(self, seq_ids, layer):
        """Padded dense K/V for a decode batch: ``(B, Lpad, H, D)`` pair
        plus the int32 ``(B,)`` true lengths.

        ONE rectangular fancy-index gather for the whole batch (not a
        per-block or per-sequence loop): the O(context) term of the
        dense fallback is a single numpy memcpy pass per pool, which is
        what keeps the measured per-token decode cost near-flat at bench
        scale (docs/serving.md).  Positions >= length are padding — tail
        blocks and block-0-padded rows ride along stale-but-finite, fine
        BY CONTRACT: the attention mask excludes every key/value column
        past ``lengths`` exactly (finite garbage in, exactly-0
        probability out; blocks only ever hold finite writes)."""
        tables = []
        with self._lock:
            for s in seq_ids:
                entry = self._entry(s)
                tables.append((list(entry.blocks), entry.length))
        bs = self.block_size
        b = len(tables)
        nbmax = max(len(blocks) for blocks, _ in tables)
        # every table padded to nbmax with block 0 makes the whole batch
        # ONE rectangular fancy-index gather (a single memcpy pass per
        # pool) — the padding rows are arbitrary-but-finite real block
        # contents the length mask excludes exactly
        ids = np.zeros((b, nbmax), np.intp)
        for i, (blocks, _) in enumerate(tables):
            ids[i, :len(blocks)] = blocks
        shape = (b, nbmax * bs, self.num_heads, self.head_dim)
        kp, vp = self.pool(layer)
        if self.storage == "device":
            # reference arm on a device pool: gather on-device by table,
            # then commit the (B, Lpad, H, D) result to host once.  The
            # numpy index array crosses the dispatch boundary on the C++
            # fast path (no eager jnp.asarray op), and the single host
            # commit sits behind an isinstance guard — the guarded-
            # fallback idiom the hot-path-purity pass recognizes, which
            # retired the justified suppression that used to live here
            # (ISSUE 16; the O(context) cost itself is the documented
            # dense-fallback price, docs/DIVERGENCES.md #27)
            idx = np.asarray(ids.ravel(), np.int32)
            k, v = kp[idx], vp[idx]
            if not isinstance(k, np.ndarray):
                k, v = np.asarray(k), np.asarray(v)
            k = k.reshape(shape)
            v = v.reshape(shape)
        else:
            k = kp[ids.ravel()].reshape(shape)
            v = vp[ids.ravel()].reshape(shape)
        lengths = np.array([length for _, length in tables], np.int32)
        return k, v, lengths

    def stats(self):
        """``{sequences, used_blocks, free_blocks, utilization}``."""
        with self._lock:
            n = len(self._seqs)
        return {"sequences": n,
                "used_blocks": self.allocator.used,
                "free_blocks": self.allocator.available,
                "utilization": self.allocator.utilization()}

    # -- capacity accounting (ISSUE 14) --------------------------------------
    def audit(self):
        """Verify the capacity accounting identity — per block,
        attributed ledger refs == the allocator refcount; per tenant,
        amortized bytes sum EXACTLY to pool-used bytes — and return the
        audit report (raises :class:`~tpu_mx.base.MXNetError` on any
        violation).  The serve CI tier runs this after every chaos
        storm; with every sequence freed and the prefix index dropped
        the report must show zero used blocks and no tenants."""
        with self._lock:
            report = self.allocator.audit()
            report["sequences"] = len(self._seqs)
            return report

    def capacity_stats(self):
        """The live capacity view the server publishes as gauges and
        hands the scheduler as ``capacity_signal``: pool geometry,
        used/free/high-watermark bytes, free-list fragmentation, pinned
        blocks (plan holders), prefix-index resident bytes (amortized),
        the optimistic reclaimable-under-pressure bound, and the
        per-tenant amortized/exclusive byte attribution."""
        with self._lock:
            snap = self.allocator.capacity_snapshot()
            snap["block_size"] = self.block_size
            snap["used_bytes"] = snap["used_blocks"] * snap["block_bytes"]
            snap["high_watermark_bytes"] = (snap["high_watermark_blocks"]
                                            * snap["block_bytes"])
            snap["pinned_blocks"] = sum(h["blocks"]
                                        for h in snap["holders"]
                                        if h["pinned"])
            idx = snap["tenants"].get(INDEX_TENANT)
            snap["index_bytes"] = idx["bytes_amortized"] if idx else 0.0
            snap["reclaimable_blocks"] = (
                self.prefix.reclaimable(self.allocator)
                if self.prefix is not None else 0)
            return snap
