"""Lazy pointwise-fusion engine for the imperative NDArray path.

The reference's dependency engine bulked imperative ops into segments
(MXNET_ENGINE_BULK_SIZE; [ver>=1.6] pointwise fusion in
REF:src/imperative/imperative_utils.h CreateEngineOp).  Here that becomes
real for the TPU-native stack: inside an ``engine.bulk()`` scope (or with
``TPUMX_FUSION=1`` always-on), ``ops._apply`` on *fusible* ops
(elementwise / broadcast / cast / reduce tails) appends a node to this
thread's pending :class:`FusionSegment` instead of dispatching, and
returns an NDArray whose buffer is a lazy thunk.  Any barrier flushes the
segment as ONE jitted callable:

  - a read of the buffer (``wait_to_read`` / ``asnumpy`` / ``asscalar`` /
    any ``_data`` access — the property on NDArray routes every read path
    here),
  - a non-fusible consumer (its ``_raw`` unwrap reads ``_data``),
  - an autograd tape boundary (entering/leaving ``record()``/``pause()``,
    or ``backward()``),
  - the segment reaching the engine bulk size,
  - ``engine.bulk()`` scope exit or ``waitall()``.

The jitted callable is memoized in a process-lifetime cache keyed by the
op-chain signature (op keys + dataflow wiring + baked-in scalar params +
which nodes are live outputs); jax.jit's own cache supplies the
shape/dtype/device specialization layer underneath, so one chain key
serves every input geometry.

Autograd composes by recording the flushed segment as a SINGLE tape node:
the pullback is ``jax.vjp`` over the fused function (jitted, recomputing
the forward — the classic rematerializing fused backward), so gradients
flow through fused segments with the same chain rule the eager tape
applies per op.

Numerics contract (documented in docs/performance.md): a fused segment
executes the *same primitive sequence* as the eager ops, compiled as one
XLA program — identical semantics to what ``hybridize()``/``jit`` already
gives the compiled path.  XLA may contract a multiply feeding an add into
an FMA inside a fused loop (excess precision, <=1 ulp per contraction
site, the fused result being the more accurate one); chains with no such
adjacency are bit-identical to eager, and ``TPUMX_FUSION=0`` restores
eager dispatch exactly.

Deferred-error divergence: an invalid op (e.g. a broadcast shape
mismatch) raises at the flush barrier, not at the op call site; the error
message names the ops in the segment.
"""
from __future__ import annotations

import os
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as _np

from . import telemetry as _telemetry
from . import tracing as _tracing

__all__ = ["enabled", "flush", "stats", "reset_stats", "pending_ops",
           "cache_stats", "FusionSegment"]


class _TLS(threading.local):
    def __init__(self):
        self.pending = None      # FusionSegment being built, or None
        self.scope_depth = 0     # engine.bulk() nesting depth
        self.suppress_depth = 0  # bulk(size<=1) anti-fusion nesting


_TLS_ = _TLS()

# process-lifetime jit caches: chain key -> jitted callable
_FWD_CACHE = {}
_BWD_CACHE = {}

stats = {
    "ops_fused": 0,          # ops appended to segments
    "segments_flushed": 0,   # segments executed
    "segments_dead": 0,      # segments whose every output died unread
    "cache_hits": 0,
    "cache_misses": 0,
    "flush_reasons": {},     # reason -> count
}


def reset_stats():
    for k in ("ops_fused", "segments_flushed", "segments_dead",
              "cache_hits", "cache_misses"):
        stats[k] = 0
    stats["flush_reasons"] = {}


def clear_cache():
    """Drop the memoized jitted segment programs (test hook)."""
    _FWD_CACHE.clear()
    _BWD_CACHE.clear()


def cache_stats():
    """Public jit-cache accessor: compiled-program counts plus hit/miss
    totals, backed by the telemetry registry counters
    (``fusion.cache_hits`` / ``fusion.cache_misses`` /
    ``fusion.flushes``)."""
    def val(name):
        m = _telemetry.get(name)
        return int(m.value) if m is not None else 0

    return {"programs": len(_FWD_CACHE),
            "bwd_programs": len(_BWD_CACHE),
            "hits": val("fusion.cache_hits"),
            "misses": val("fusion.cache_misses"),
            "segments_flushed": val("fusion.flushes")}


# os.environ.get costs ~3us per call (str->bytes encode in os.py) — far
# too much for a per-op-dispatch check.  On POSIX CPython the live
# mapping is os.environ._data with BYTES keys; read that directly,
# falling back to the portable path (Windows _data is str-keyed and
# upper-cased, so the bytes lookup would silently miss there).
# putenv/monkeypatch.setenv both go through os.environ, so _data stays
# current.
_ENV_DATA = getattr(os.environ, "_data", None) if os.name == "posix" \
    else None
if isinstance(_ENV_DATA, dict):
    def _fusion_env():
        v = _ENV_DATA.get(b"TPUMX_FUSION")
        return v.decode() if v is not None else None
else:  # pragma: no cover — non-CPython os.environ layout
    def _fusion_env():
        return os.environ.get("TPUMX_FUSION")


def enabled():
    """Is fusion dispatch active on this thread right now?

    TPUMX_FUSION=1 forces always-on, TPUMX_FUSION=0 forces off (restoring
    plain eager dispatch exactly, even inside ``engine.bulk()``); unset,
    fusion is active inside ``engine.bulk()`` scopes.  A ``bulk(size<=1)``
    scope SUPPRESSES fusion even under TPUMX_FUSION=1 — the reference's
    bulk-size-0/1 escape hatch must keep meaning "op-by-op here" (e.g. to
    localize a deferred error to its call site)."""
    if _TLS_.suppress_depth > 0:
        return False
    env = _fusion_env()
    if env == "1":
        return True
    if env == "0":
        return False
    return _TLS_.scope_depth > 0


def enter_scope():
    _TLS_.scope_depth += 1


def exit_scope():
    _TLS_.scope_depth -= 1
    flush("scope_exit")


def enter_suppress():
    flush("suppress_scope")  # ops before the scope must not see barriers move
    _TLS_.suppress_depth += 1


def exit_suppress():
    _TLS_.suppress_depth -= 1


def pending_ops():
    """Number of ops in this thread's pending segment (introspection)."""
    seg = _TLS_.pending
    return len(seg.fns) if seg is not None else 0


class _Lazy:
    """Marker a lazy NDArray holds in ``_lazy``: (segment, node index)."""

    __slots__ = ("segment", "index")

    def __init__(self, segment, index):
        self.segment = segment
        self.index = index


class FusionSegment:
    """A pending bulked op sequence: straight-line dataflow IR.

    Node inputs are specs: ``("e", i)`` external input i, ``("n", i)``
    output of node i.  Python scalars become weakly-typed 0-d external
    inputs — runtime arguments, exactly what eager dispatch passes to its
    per-primitive program.  Baking them as trace constants would (a) let
    XLA's algebraic simplifier fold them (e.g. divide-by-constant becomes
    multiply-by-reciprocal, a 1-ulp divergence from eager) and (b) key
    the cache on the value, so an lr schedule would recompile per step."""

    __slots__ = ("fns", "keys", "specs", "names", "nondiffs", "ext",
                 "ext_handles", "ext_ids", "handles", "avals", "bulk_size")

    def __init__(self, bulk_size):
        self.fns = []           # per node: the pure raw-array fn
        self.keys = []          # per node: hashable op key (incl. params)
        self.specs = []         # per node: tuple of input specs
        self.names = []         # per node: display name for errors
        self.nondiffs = []      # per node: eager-path nondiff flag
        self.ext = []           # external raw arrays, in first-use order
        self.ext_handles = []   # the NDArray handle per ext (None if raw)
        self.ext_ids = {}       # dedup key -> ext index
        self.handles = []       # per node: weakref to the result NDArray
        self.avals = []         # per node: lazily computed output aval
        self.bulk_size = bulk_size

    def _ext_index(self, raw, handle):
        # dedup by HANDLE identity for NDArray inputs: two distinct
        # handles can share one jax.Array (detach(), NDArray(nd)), and
        # collapsing them would route both cotangents into whichever
        # handle registered first, starving the other's .grad
        key = id(handle) if handle is not None else id(raw)
        idx = self.ext_ids.get(key)
        if idx is None:
            idx = len(self.ext)
            self.ext_ids[key] = idx
            self.ext.append(raw)
            self.ext_handles.append(handle)
        return idx

    def node_aval(self, i):
        """Output aval of node i without executing (jax abstract eval)."""
        if self.avals[i] is None:
            ins = []
            for kind, v in self.specs[i]:
                if kind == "e":
                    x = self.ext[v]
                    ins.append(jax.ShapeDtypeStruct(tuple(x.shape), x.dtype))
                elif kind == "n":
                    ins.append(self.node_aval(v))
                else:
                    ins.append(v)
            self.avals[i] = jax.eval_shape(self.fns[i], *ins)
        return self.avals[i]


def aval_of(lazy):
    return lazy.segment.node_aval(lazy.index)


_NDARRAY = None


def _ndarray_cls():
    global _NDARRAY
    if _NDARRAY is None:
        from .ndarray.ndarray import NDArray
        _NDARRAY = NDArray
    return _NDARRAY


def _lazy_ndarray(NDArray, segment, index):
    out = NDArray.__new__(NDArray)
    out._buf = None
    out._lazy = _Lazy(segment, index)
    out._grad = None
    out._grad_req = "write"
    out._tape_node = None
    out._version = 0
    return out


def append(fn, args, name, key, nondiff):
    """Append one fusible op to this thread's pending segment.

    Returns the lazy result NDArray, or None if an argument kind is not
    representable in the segment IR (caller falls back to eager)."""
    NDArray = _ndarray_cls()
    seg = _TLS_.pending
    if seg is None:
        from . import engine
        seg = FusionSegment(max(2, engine._bulk_size))
        _TLS_.pending = seg

    specs = []
    for a in args:
        if isinstance(a, NDArray):
            lz = a._lazy
            if lz is not None and lz.segment is seg:
                specs.append(("n", lz.index))
            else:
                # a lazy handle from another segment cannot normally
                # exist (one pending segment per thread; flush realizes
                # all) — ._data realizes through the property if it does
                specs.append(("e", seg._ext_index(a._data, a)))
        elif isinstance(a, (bool, int, float)):
            specs.append(("e", seg._ext_index(_scalar_ext(a), None)))
        elif isinstance(a, (jax.Array, _np.ndarray)):
            specs.append(("e", seg._ext_index(a, None)))
        else:
            # np.generic scalars, tracers, anything else: promotion or
            # identity semantics are not scalar-bakeable — let the caller
            # dispatch eagerly (a flush barrier via _raw)
            _telemetry.counter("fusion.eager_fallbacks").inc()
            return None

    idx = len(seg.fns)
    seg.fns.append(fn)
    seg.keys.append(key)
    seg.specs.append(tuple(specs))
    seg.names.append(name)
    seg.nondiffs.append(bool(nondiff))
    seg.avals.append(None)
    out = _lazy_ndarray(NDArray, seg, idx)
    seg.handles.append(weakref.ref(out))
    stats["ops_fused"] += 1
    if idx + 1 >= seg.bulk_size:
        flush("bulk_size")
    return out


_SCALAR_MEMO = {}


def _scalar_ext(v):
    """Python scalar -> weakly-typed 0-d jax array (memoized: the same
    literal recurs every chain iteration).  Weak typing preserves eager
    promotion semantics through the jit boundary."""
    key = (type(v), v)
    arr = _SCALAR_MEMO.get(key)
    if arr is None:
        arr = _SCALAR_MEMO[key] = jnp.asarray(v)
        if len(_SCALAR_MEMO) > 4096:  # unbounded-literal guard
            _SCALAR_MEMO.clear()
            _SCALAR_MEMO[key] = arr
    return arr


def realize(handle):
    """Barrier from NDArray._data: flush the segment backing `handle`."""
    lz = handle._lazy
    if lz is None:
        return
    if lz.segment is _TLS_.pending:
        flush("read_barrier")
    else:  # pragma: no cover — defensive: a detached segment still owed
        _execute(lz.segment, "read_barrier")
    if handle._lazy is not None:  # pragma: no cover — defensive
        raise RuntimeError("fusion flush failed to realize a lazy NDArray")


def flush(reason="barrier"):
    """Flush this thread's pending segment (no-op when none)."""
    seg = _TLS_.pending
    if seg is None:
        return
    _TLS_.pending = None
    _execute(seg, reason)


def _make_replay(fns, specs, nondiffs, out_idxs):
    """The fused program: replay the node chain over raw ext arrays.

    Nondiff node outputs are wrapped in ``lax.stop_gradient`` — identity
    in the forward (XLA erases it), and in the segment's single vjp it
    reproduces eager semantics exactly: an unrecorded op's output is a
    constant the tape never differentiates through."""
    from jax import lax
    single = len(out_idxs) == 1

    def fused(*ext):
        vals = []
        for fn, sp, nd_ in zip(fns, specs, nondiffs):
            ins = [ext[v] if kind == "e" else
                   (vals[v] if kind == "n" else v)
                   for kind, v in sp]
            out = fn(*ins)
            vals.append(lax.stop_gradient(out) if nd_ else out)
        if single:
            return vals[out_idxs[0]]
        return tuple(vals[i] for i in out_idxs)

    return fused


def _execute(seg, reason):
    from . import autograd

    stats["flush_reasons"][reason] = \
        stats["flush_reasons"].get(reason, 0) + 1
    if not seg.fns:
        return
    _telemetry.counter("fusion.flush_cause", cause=reason).inc()
    _telemetry.histogram("fusion.segment_ops",
                         buckets=_telemetry.SEGMENT_OPS_BUCKETS,
                         unit="ops").observe(len(seg.fns))

    # Live outputs: node results whose handle is still reachable and still
    # lazy on THIS segment.  Dead intermediates stay internal to the fused
    # program (never materialized) — the fusion win the eager path can't
    # have.  The live set rides the cache key: CPython's deterministic
    # refcounting makes it stable for a given call pattern.
    live = []      # (node index, handle)
    for i, ref in enumerate(seg.handles):
        h = ref()
        if h is not None and h._lazy is not None \
                and h._lazy.segment is seg:
            live.append((i, h))
    if not live:
        stats["segments_dead"] += 1
        _telemetry.counter("fusion.segments_dead").inc()
        return

    out_idxs = tuple(i for i, _ in live)
    chain_key = (tuple(seg.keys), tuple(seg.specs),
                 tuple(seg.nondiffs), len(seg.ext), out_idxs)

    fwd = _FWD_CACHE.get(chain_key)
    if fwd is None:
        stats["cache_misses"] += 1
        _telemetry.counter("fusion.cache_misses").inc()
        fwd = jax.jit(_make_replay(seg.fns, seg.specs, seg.nondiffs,
                                   out_idxs))
        _FWD_CACHE[chain_key] = fwd
    else:
        stats["cache_hits"] += 1
        _telemetry.counter("fusion.cache_hits").inc()

    try:
        results = fwd(*seg.ext)
    except Exception as e:
        raise type(e)(
            f"{e}\n(raised while flushing a fused op segment "
            f"[{' -> '.join(seg.names)}]; with fusion enabled, op errors "
            f"surface at the flush barrier, not the op call site)") from e
    if len(out_idxs) == 1:
        results = (results,)

    for (i, h), r in zip(live, results):
        h._buf = r
        h._lazy = None
    stats["segments_flushed"] += 1
    # telemetry scope differs from the legacy stats dict by design:
    # stats["ops_fused"] counts appends (incl. segments that later die
    # unread), fusion.ops_fused counts only ops that EXECUTED fused —
    # the number that tells an operator what the engine actually won
    _telemetry.counter("fusion.flushes").inc()
    _telemetry.counter("fusion.ops_fused").inc(len(seg.fns))
    # flight-recorder event at flush granularity (never per-op): the
    # black box can attribute a flush storm to the step that caused it
    _tracing.emit("fusion.flush", cause=reason, ops=len(seg.fns))

    # ---- autograd: the whole segment becomes ONE tape node -------------
    # Only inexact outputs of DIFF nodes join the tape: integer outputs
    # fall through unrecorded like eager (also keeps float0 cotangents
    # out of the jitted pullback), and a nondiff node's output is an
    # unrecorded constant eagerly — taping it would let a backward pass
    # overwrite leaf grads with zeros that eager never touches.
    rec = [(i, h) for i, h in live
           if not seg.nondiffs[i]
           and jnp.issubdtype(h._buf.dtype, jnp.inexact)]
    if not rec:
        return
    rec_idxs = tuple(i for i, _ in rec)
    # Differentiate only ext inputs with a tape-CONNECTED path to a
    # recorded output — a path through a nondiff node doesn't count
    # (eager never records that branch, so its leaves must receive NO
    # cotangent; the segment vjp would hand them stop_gradient zeros and
    # backward would overwrite real grads with them).  Per-node ext
    # reachability as bitmasks, nondiff nodes propagating nothing.
    ext_bit = {i: 1 << i for i, h in enumerate(seg.ext_handles)
               if h is not None
               and jnp.issubdtype(seg.ext[i].dtype, jnp.inexact)}
    masks = []
    for ni in range(len(seg.fns)):
        if seg.nondiffs[ni]:
            masks.append(0)
            continue
        m = 0
        for kind, v in seg.specs[ni]:
            if kind == "e":
                m |= ext_bit.get(v, 0)
            elif kind == "n":
                m |= masks[v]
        masks.append(m)
    needed = 0
    for i in rec_idxs:
        needed |= masks[i]
    diff_idx = tuple(i for i in sorted(ext_bit) if needed & ext_bit[i])
    if not diff_idx:
        return
    diff_handles = [seg.ext_handles[i] for i in diff_idx]
    if not autograd._needs_tape(diff_handles):
        return
    bwd_key = (chain_key, rec_idxs, diff_idx)
    ext = list(seg.ext)               # captured values: eager read-at-call
    fns, specs = list(seg.fns), list(seg.specs)
    nondiffs = list(seg.nondiffs)

    def vjp_call(cts):
        bwd = _BWD_CACHE.get(bwd_key)
        if bwd is None:
            replay = _make_replay(fns, specs, nondiffs, rec_idxs)

            def pullback(ext_ins, cts_):
                def diff_only(*dd):
                    full = list(ext_ins)
                    for i, d in zip(diff_idx, dd):
                        full[i] = d
                    return replay(*full)

                _, vjp_fn = jax.vjp(
                    diff_only, *[ext_ins[i] for i in diff_idx])
                return vjp_fn(cts_)

            bwd = jax.jit(pullback)
            _BWD_CACHE[bwd_key] = bwd
        return bwd(ext, cts)

    autograd._record_op(vjp_call, diff_handles, [h for _, h in rec],
                        name="fused_segment")
