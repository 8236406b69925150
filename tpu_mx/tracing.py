"""Flight recorder: step-scoped structured events + the crash black box.

The supervisor (docs/robustness.md) and deterministic resume make runs
*survivable* and *replayable*, but the *why* of a restart, rollback or
degrade used to be scattered: telemetry holds cumulative aggregates with
no step identity, the chrome-trace holds spans with no failure context,
and the supervisor's decisions lived only in transient log lines.  This
module is the forensic substrate:

- **Events** — :func:`emit` appends one typed record to a bounded
  in-memory ring buffer.  Every event carries the process-wide **trace
  context** (``run_id``, ``epoch``, ``step``, supervisor ``generation``,
  set by the training loop via :func:`set_context`) plus a payload whose
  fields are declared in the static :data:`KNOWN_EVENTS` catalog — event
  names are an API exactly like ``telemetry.KNOWN_METRICS`` (the
  tpumx-lint ``telemetry-catalog`` pass checks ``emit`` call sites
  statically, docs/static_analysis.md).  The context is deliberately
  process-global, not thread-local: the supervisor runs steps on a
  watchdog daemon thread, and an event emitted there must still carry
  the step that hung.
- **Ring buffer** — a ``collections.deque(maxlen=capacity)`` under one
  lock: sustained emit is O(1) and memory is bounded no matter how long
  the run; :func:`snapshot` copies it consistently.  Overflow is counted
  (``stats()['dropped']``), never silent.
- **Black box** — :func:`dump_blackbox` persists the last N events, a
  full telemetry snapshot, the live trace context and an environment
  fingerprint as ``<prefix>-blackbox.json`` through
  ``checkpoint.atomic_write`` (a crash mid-dump cannot tear it).  The
  supervisor dumps one on every recovery decision (watchdog fire →
  restart, NaN streak → rollback, degrade) and the SIGTERM preemption
  handler dumps one before exit — so a fault and the recovery it
  triggered share one correlated timeline.  ``tools/blackbox_report.py``
  renders it human-readable without importing jax.
- **Chrome trace** — events also merge into ``mx.profiler``'s event
  stream via ``profiler.record_span`` (zero-duration marks for
  instants, real intervals when ``t0``/``t1`` endpoints are passed), so
  the same timeline is visible in Perfetto next to the XLA annotations.

- **Train-step phases** — :class:`phase` is the one site every host-side
  phase of the compiled train step goes through: a
  ``jax.profiler.TraceAnnotation`` (``tpu_mx/train_step/<phase>``, on the
  profiler's timeline beside the device's operations) around the region,
  and the ``train_step.phase`` event with the same endpoints on exit.

``TPUMX_TRACING=0`` disables emission entirely: the disabled path is one
module-global check per call site (held to the same within-noise bar as
the telemetry exporter, docs/observability.md).

This module imports ONLY the stdlib at module level and is loadable
standalone (``tools/blackbox_report.py`` does) — the telemetry,
checkpoint and profiler bridges all degrade gracefully when the package
is absent.
"""
from __future__ import annotations

import json
import math
import os
import socket
import sys
import threading
import time
from collections import deque

__all__ = ["KNOWN_EVENTS", "BLACKBOX_FORMAT", "TRAIN_STEP_PHASES",
           "enabled", "configure", "emit", "phase", "set_context",
           "get_context",
           "snapshot", "stats", "reset", "validate_event",
           "blackbox_doc", "dump_blackbox", "blackbox_path",
           "validate_blackbox"]

BLACKBOX_FORMAT = "tpu_mx-blackbox-v1"

# The stable event-name catalog: name -> {payload field: type name}.
# Event NAMES AND FIELDS ARE AN API (docs/observability.md), statically
# checked at every emit() call site by tools/tpumx_lint.py's
# telemetry-catalog pass — keep this a literal dict so the linter can
# extract it by parsing, never importing.  Payload fields are optional
# but typed; undeclared fields are rejected at emit time.
KNOWN_EVENTS = {
    # compiled train step (tpu_mx/parallel/train_step.py): the step
    # histogram split into host-side phases (docs/observability.md
    # documents what each phase covers under the one-program step)
    "train_step.phase": {"phase": "str", "seconds": "float"},
    # fusion engine (tpu_mx/fusion.py): one event per executed flush
    "fusion.flush": {"cause": "str", "ops": "int"},
    # durability layer (tpu_mx/checkpoint.py, tpu_mx/elastic.py)
    "checkpoint.save": {"prefix": "str", "epoch": "int", "seconds": "float"},
    "checkpoint.verify": {"prefix": "str", "epoch": "int", "status": "str"},
    "checkpoint.retry": {"attempt": "int", "error": "str"},
    "checkpoint.preemption": {"signum": "int", "save_ok": "bool"},
    "elastic.resume": {"resume_from": "int"},
    "elastic.epoch_skipped": {"epoch": "int", "reason": "str"},
    # self-healing supervisor (tpu_mx/supervisor.py): every watchdog
    # fire, sentinel skip, classification and recovery decision
    "supervisor.watchdog_fire": {"name": "str", "deadline_seconds": "float"},
    "supervisor.sentinel_skip": {"loss": "float", "consecutive_bad": "int"},
    "supervisor.classify": {"kind": "str", "error": "str", "message": "str"},
    "supervisor.restart": {"n": "int", "backoff_seconds": "float",
                           "resume_epoch": "int"},
    "supervisor.rollback": {"n": "int", "resume_epoch": "int"},
    "supervisor.degrade": {"budget": "str", "error": "str"},
    "supervisor.blackbox": {"path": "str", "reason": "str"},
    # deterministic-resume capsules (tpu_mx/resume.py)
    "resume.capsule_write": {"kind": "str", "epoch": "int", "step": "int"},
    "resume.capsule_restore": {"used": "str", "epoch": "int", "step": "int",
                               "gap": "int"},
    # fault injection (tpu_mx/contrib/chaos.py): the injection and the
    # recovery it provokes share one timeline
    "chaos.inject": {"kind": "str"},
    # SDC defense plane (ISSUE 20; tpu_mx/parallel/integrity.py +
    # supervisor.py, docs/robustness.md "Silent data corruption
    # defense").  `integrity.fingerprint` records every published
    # cross-replica digest (the K-step cadence);  `integrity.vote` one
    # cohort comparison — agree=False IS the corruption verdict, with
    # `minority` the comma-joined voted-out rank(s) ("" when a tie
    # detected but could not attribute);  `integrity.quarantine` the
    # permanent eviction of a corrupt rank (never re-admitted — distinct
    # from fleet.leave/fleet.lost, which healed members survive);
    # `integrity.shadow_audit` one sampled bit-exact re-execution
    # (surface=train|decode);  `integrity.rollback` the surviving
    # majority's recovery decision, naming the last fingerprint-VERIFIED
    # step the restore is anchored to.
    "integrity.fingerprint": {"step": "int", "fp": "int", "rank": "int"},
    "integrity.vote": {"step": "int", "agree": "bool",
                       "majority_fp": "int", "minority": "str",
                       "world_size": "int"},
    "integrity.quarantine": {"rank": "int", "reason": "str",
                             "step": "int"},
    "integrity.shadow_audit": {"step": "int", "match": "bool",
                               "surface": "str"},
    "integrity.rollback": {"step": "int", "verified_step": "int",
                           "resume_epoch": "int"},
    # kvstore payload integrity (ISSUE 20): a pulled aggregate failed
    # its push-time checksum — corruption crossed the sync seam
    "kvstore.checksum_fail": {"key": "str"},
    # elastic fleet membership (tpu_mx/parallel/fleet.py + tools/launch.py
    # --supervise; docs/robustness.md "Elastic fleets").  Every membership
    # transition is on the timeline: `fleet.epoch` is the authoritative
    # record of a generation advance (who is in the world and why it
    # changed); join/leave/lost/rejoin are the per-member lifecycle;
    # `fleet.reshard` records a world-size transition driven through the
    # load_state_dict reshard seam (source=manifest for fault recovery,
    # source=live for planned scale-up from in-memory state);
    # restart_worker/degrade are the fleet supervisor's restart-budget
    # decisions.  The fleet generation is a PAYLOAD field here — the
    # trace-context `generation` field remains the supervisor's restore
    # generation.
    "fleet.epoch": {"generation": "int", "world_size": "int",
                    "reason": "str"},
    "fleet.join": {"member": "int", "generation": "int"},
    "fleet.leave": {"member": "int", "generation": "int", "reason": "str"},
    "fleet.lost": {"member": "int", "age_seconds": "float"},
    "fleet.rejoin": {"member": "int", "generation": "int"},
    "fleet.reshard": {"generation": "int", "from_world": "int",
                      "to_world": "int", "source": "str"},
    "fleet.restart_worker": {"member": "int", "n": "int",
                             "backoff_seconds": "float"},
    "fleet.degrade": {"world_size": "int", "reason": "str"},
    # fleet observability plane (ISSUE 18; tpu_mx/parallel/fleet_obs.py):
    # the windowed persistent-straggler detector's state FLIP — `rank`
    # is the attributed straggler, `excess_seconds` its mean per-step
    # excess over the fastest rank, `phase` the dominant slow phase
    # (data_wait/dispatch/loss_readback) and `steps` how many correlated
    # steps the window judged.  rank=-1 records the all-clear flip.
    "fleet.straggler": {"rank": "int", "excess_seconds": "float",
                        "phase": "str", "steps": "int"},
    # inference serving runtime (tpu_mx/serving/, docs/serving.md): the
    # request lifecycle.  Per-request events (admit/prefill/evict/reject)
    # are additionally stamped with the request-scoped `request` context
    # field (set_context(request=...) — the serving analog of the
    # training loop's step context), so a slow request's black box is
    # reconstructible; decode is batch-scoped and rides the engine-step
    # `step`/`generation` context like a train step.
    # `recovered` (ISSUE 19): True when the admission is a journal
    # recovery (scheduler.restore — gates bypassed), absent otherwise
    "serve.admit": {"request": "str", "prompt_tokens": "int",
                    "max_new_tokens": "int", "tenant": "str",
                    "recovered": "bool"},
    "serve.reject": {"request": "str", "reason": "str"},
    # `cached` (ISSUE 12): how many leading prompt tokens were served
    # from the shared-prefix index instead of computed — a prefill that
    # rode the cache attributes its speed honestly.  `replayed`
    # (ISSUE 19): how many already-committed GENERATED tokens this
    # prefill replayed in the same call — nonzero means this was a
    # restart/handoff recovery that rebuilt the stream in O(1 prefill)
    # instead of re-decoding
    "serve.prefill": {"request": "str", "tokens": "int", "seconds": "float",
                     "cached": "int", "replayed": "int"},
    "serve.decode": {"batch": "int", "tokens": "int", "seconds": "float"},
    "serve.evict": {"request": "str", "reason": "str", "generated": "int"},
    "serve.restart": {"n": "int", "reason": "str", "requeued": "int"},
    # emitted once per engine construction (so once per generation): the
    # decode-attention arm this engine resolved (dense / paged /
    # paged-kernel), where its KV pool lives (host / device), whether
    # the whole step runs as ONE fused device program (ISSUE 16) and
    # the speculative draft-window width (1 = speculation off) — a
    # restarted engine's black box records which data plane it was on
    # `sampling` (ISSUE 19): greedy or the host sampler spec — a
    # non-greedy engine pins fused off and the spec window to 1 (both
    # sample greedily/on-device and would fork the journaled stream)
    "serve.decode_path": {"path": "str", "storage": "str",
                          "sharing": "bool", "fused": "bool",
                          "spec_window": "int", "sampling": "str"},
    # graceful drain / hot handoff / degraded drain (ISSUE 19): one
    # event per admission-stopping transition — kind=drain (quiesce to
    # idle, admission closed), kind=handoff (live sessions migrated to
    # a fresh engine generation via prefill replay, no restart budget
    # spent), kind=degrade (budget exhausted: queued work failed, the
    # running batch migrated to one final generation and drained)
    "serve.drain": {"kind": "str", "inflight": "int", "pending": "int"},
    # shared-prefix index pressure eviction (ISSUE 12): one event per
    # relief pass — `released` index entries freed to satisfy a
    # `need`-block allocation (tpu_mx/serving/kv_cache.py::_alloc)
    "serve.prefix_evict": {"released": "int", "need": "int"},
    # capacity exhaustion (ISSUE 14): a genuine CacheExhausted — the
    # pool could not satisfy `need` blocks even after pressure relief.
    # `holders` counts the live ledger holders at fault time and
    # `forensic` names the rolling <prefix>-capacity.json record set
    # (empty when forensics are unarmed) that attributes every one of
    # them — rendered by tools/capacity_report.py without jax
    "serve.capacity_exhausted": {"need": "int", "free": "int",
                                 "holders": "int", "forensic": "str"},
    # per-request latency attribution (tpu_mx/serving/timeline.py,
    # ISSUE 11): emitted ONCE per request at finish/fail/reject — not
    # per phase transition, which would flood the ring — with the
    # request's wall clock decomposed into the typed phases.  The
    # invariant the serve CI tier gates: the phase fields sum to the
    # measured request latency within 5% (and the breakdown snapshot at
    # first-token time sums to the measured ttft).
    # `tenant`/`cached_tokens` (ISSUE 12): the tenant label the
    # per-tenant SLO report groups by, and the prompt tokens the final
    # attempt served from the shared-prefix cache (a cache-served
    # prefill's short `prefill` phase is attributed honestly, not
    # mistaken for noise).  NOTE for offline consumers: phase fields are
    # exactly the float fields other than latency/ttft (slo_report
    # derives them that way) — any new float here must be a phase.
    "serve.request_timeline": {
        "request": "str", "outcome": "str", "latency": "float",
        "ttft": "float", "queue_wait": "float", "prefill": "float",
        "decode_gap": "float", "restart_penalty": "float",
        "defer_stall": "float", "reject": "float",
        "tokens": "int", "requeues": "int", "defers": "int",
        "tenant": "str", "cached_tokens": "int"},
    # SLO monitor breach transitions (tpu_mx/serving/slo.py): emitted
    # when a declared target starts or stops breaching its multi-window
    # error-budget burn bar — the timeline record of WHEN the SLO state
    # flipped (the continuous state lives in the serve.slo_* gauges)
    "serve.slo": {"slo": "str", "breaching": "bool", "burn_rate": "float",
                  "estimate_seconds": "float", "attainment": "float",
                  "threshold_seconds": "float"},
}

# the documented values of train_step.phase's `phase` field (the whole
# device-side forward+backward+optimizer runs as ONE XLA program, so the
# phases are the HOST-side stations around it — docs/observability.md).
# In step order; all but loss_readback (emitted at the read site, after
# the step returns) tile CompiledTrainStep._step from entry to return.
TRAIN_STEP_PHASES = ("data_wait", "recompile", "rng_key", "dispatch",
                     "optimizer_update", "record", "loss_readback")

_TYPES = {"str": str, "int": int, "float": (int, float), "bool": bool}

# REENTRANT by requirement, not convenience: the SIGTERM preemption
# handler (checkpoint.PreemptionHandler) runs on the main thread between
# bytecodes and emits events + dumps a black box — if the interrupted
# frame was itself inside emit() (several per training step), a plain
# Lock would self-deadlock the whole preemption grace window
_lock = threading.RLock()
_DEFAULT_CAPACITY = 512
_ring: deque = deque(maxlen=_DEFAULT_CAPACITY)
_emitted = 0
_dropped = 0
_enabled = os.environ.get("TPUMX_TRACING", "1") != "0"

# the process-wide trace context every event is stamped with.  run_id is
# wall-clock-derived (an *identifier*, not an RNG seed — determinism
# applies to the training computation, not to forensic labels).
_context = {
    "run_id": "%s-%d-%d" % (socket.gethostname(), os.getpid(),
                            int(time.time())),
    "epoch": None,
    "step": None,
    "generation": 0,
    # request-scoped context (tpu_mx/serving/): the id of the request an
    # event belongs to, or None outside per-request work.  The serving
    # engine stamps it around admit/prefill/evict exactly like the
    # supervisor stamps epoch/step around a train step; batch-scoped
    # decode events leave it None and correlate via step/generation.
    "request": None,
    # fleet identity (ISSUE 18): this process's fleet rank and the
    # membership generation it has adopted, stamped by
    # tpu_mx/parallel/fleet.py on epoch adoption (None outside a
    # fleet).  `fleet_generation` is the MEMBERSHIP epoch — distinct
    # from `generation`, which remains the supervisor's restore
    # generation.  The cross-rank step correlation
    # (tpu_mx/parallel/fleet_obs.py) keys on (epoch, step,
    # fleet_generation) across ranks' shipped events.
    "rank": None,
    "fleet_generation": None,
}


def enabled():
    """Whether emit() records anything (``TPUMX_TRACING=0`` disables)."""
    return _enabled


def configure(enabled=None, capacity=None):
    """Adjust the recorder: ``enabled`` toggles emission, ``capacity``
    re-sizes the ring (keeping the newest events).  Returns the live
    ``(enabled, capacity)`` pair."""
    global _enabled, _ring
    with _lock:
        if enabled is not None:
            _enabled = bool(enabled)
        if capacity is not None:
            capacity = int(capacity)
            if capacity < 1:
                raise ValueError("tracing capacity must be >= 1")
            _ring = deque(_ring, maxlen=capacity)
        return _enabled, _ring.maxlen


def set_context(**fields):
    """Update the process-wide trace context (``run_id``, ``epoch``,
    ``step``, ``generation``, ``request``).  The training loop owns the
    first four: the supervisor stamps epoch/step/generation around every
    supervised step; the serving engine stamps step/generation per engine
    step and ``request`` around per-request work.  Every event emitted
    anywhere in the process — including on the watchdog daemon thread —
    carries the values current at emit time."""
    unknown = set(fields) - set(_context)
    if unknown:
        raise ValueError(f"unknown trace-context field(s) {sorted(unknown)} "
                         f"(have: {sorted(_context)})")
    with _lock:
        _context.update(fields)


def get_context():
    """A copy of the live trace context."""
    with _lock:
        return dict(_context)


# non-finite floats are encoded as these strings: strict JSON has no
# NaN/Infinity token, and a black box MUST parse in jq/browsers/any
# spec-compliant reader — a NaN loss is exactly what a divergence box
# records, so the encoding is part of the schema, not an edge case
_NONFINITE = {"nan": float("nan"), "inf": float("inf"),
              "-inf": float("-inf")}


def _check_payload(event, payload, normalize=False):
    """Shared by emit() and validate_event(): every payload field must be
    declared for `event` in :data:`KNOWN_EVENTS` with a matching type.
    ``normalize=True`` (the emit path) additionally rewrites non-finite
    floats to their string encoding so every ring record is strict-JSON
    safe; the validate path accepts either spelling."""
    decl = KNOWN_EVENTS.get(event)
    if decl is None:
        raise ValueError(f"unknown event name {event!r} — not in "
                         "tracing.KNOWN_EVENTS (stable event names are an "
                         "API; register new events in the catalog + "
                         "docs/observability.md)")
    for k, v in payload.items():
        if k not in decl:
            raise ValueError(f"{event}: undeclared payload field {k!r} "
                             f"(declared: {sorted(decl)})")
        want = _TYPES[decl[k]]
        if decl[k] == "float" and isinstance(v, str) and v in _NONFINITE:
            continue  # the strict-JSON encoding of a non-finite float
        if not isinstance(v, want) or (decl[k] != "bool"
                                       and isinstance(v, bool)):
            raise ValueError(f"{event}: payload field {k!r} must be "
                             f"{decl[k]}, got {type(v).__name__} {v!r}")
        if normalize and decl[k] == "float" \
                and not math.isfinite(float(v)):
            payload[k] = "nan" if v != v else ("inf" if v > 0 else "-inf")
    return payload


def validate_event(rec):
    """Raise ValueError unless `rec` is a schema-valid event record:
    a known ``event`` name, numeric ``ts``, the four context fields
    (``run_id`` str; ``epoch``/``step`` int or None; ``generation``
    int), and a ``data`` payload whose fields are declared — with the
    declared types — in :data:`KNOWN_EVENTS` (non-finite floats appear
    as their string encodings ``"nan"``/``"inf"``/``"-inf"``)."""
    if not isinstance(rec, dict):
        raise ValueError(f"event is {type(rec).__name__}, not an object")
    name = rec.get("event")
    if name not in KNOWN_EVENTS:
        raise ValueError(f"unknown event name {name!r} — not in "
                         "tracing.KNOWN_EVENTS (stable event names are an "
                         "API; register new events in the catalog + "
                         "docs/observability.md)")
    if not isinstance(rec.get("ts"), (int, float)) \
            or isinstance(rec.get("ts"), bool):
        raise ValueError(f"{name}: missing numeric 'ts'")
    if not isinstance(rec.get("run_id"), str) or not rec.get("run_id"):
        raise ValueError(f"{name}: missing 'run_id'")
    for field in ("epoch", "step"):
        v = rec.get(field, "missing")
        if v is not None and (not isinstance(v, int) or isinstance(v, bool)):
            raise ValueError(f"{name}: {field!r} must be int or None, "
                             f"got {v!r}")
    if not isinstance(rec.get("generation"), int) \
            or isinstance(rec.get("generation"), bool):
        raise ValueError(f"{name}: missing int 'generation'")
    # `request` joined the context with the serving runtime; events
    # recorded by older builds simply lack the key (still valid)
    req = rec.get("request")
    if req is not None and not isinstance(req, str):
        raise ValueError(f"{name}: 'request' must be str or None, "
                         f"got {req!r}")
    # `rank`/`fleet_generation` joined with the fleet observability
    # plane (ISSUE 18); same older-builds-lack-the-key rule
    for field in ("rank", "fleet_generation"):
        v = rec.get(field)
        if v is not None and (not isinstance(v, int)
                              or isinstance(v, bool)):
            raise ValueError(f"{name}: {field!r} must be int or None, "
                             f"got {v!r}")
    data = rec.get("data")
    if not isinstance(data, dict):
        raise ValueError(f"{name}: missing 'data' payload object")
    _check_payload(name, data)
    return rec


def emit(event, t0=None, t1=None, **payload):
    """Record one event into the ring buffer (no-op when disabled).

    ``payload`` fields must be declared in :data:`KNOWN_EVENTS` with
    matching types — a typo'd field or name raises immediately (and the
    lint pass catches unknown *names* statically).  ``t0``/``t1``
    (``time.perf_counter`` endpoints) additionally merge the interval
    into the profiler chrome-trace via ``profiler.record_span``; events
    without endpoints merge as zero-duration marks.  Returns the record
    (None when disabled)."""
    global _emitted, _dropped
    if not _enabled:
        return None
    decl = KNOWN_EVENTS.get(event)
    if t0 is not None and t1 is not None and decl and "seconds" in decl:
        payload.setdefault("seconds", t1 - t0)
    _check_payload(event, payload, normalize=True)
    rec = {"event": event, "ts": time.time(), "data": payload}
    with _lock:
        rec.update(_context)
        if len(_ring) == _ring.maxlen:
            _dropped += 1
        _emitted += 1
        _ring.append(rec)
    _merge_profiler(event, t0, t1, payload)
    return rec


_TraceAnnotation = None     # jax's, looked up at first use; False without jax


class phase:
    """One host-side phase of the compiled train step, on both clocks:
    ``with phase("dispatch"):`` opens the
    ``jax.profiler.TraceAnnotation`` ``tpu_mx/train_step/dispatch`` (a
    span on the profiler's timeline beside the device's operations;
    nanoseconds while no profiler session runs) and, on exit, records the
    ``train_step.phase`` event with the same ``perf_counter`` endpoints
    (the flight recorder, the black box and the chrome trace).
    ``TPUMX_TRACING=0`` skips the event and keeps the annotation; without
    jax (standalone load) there is no annotation.  ``t0`` and, after exit,
    ``seconds`` are the interval's start and length."""

    __slots__ = ("name", "t0", "seconds", "_annotation")

    def __init__(self, name):
        self.name = name
        self.seconds = 0.0

    def __enter__(self):
        global _TraceAnnotation
        if _TraceAnnotation is None:
            try:
                from jax.profiler import TraceAnnotation as _TraceAnnotation
            except ImportError:
                _TraceAnnotation = False
        self._annotation = _TraceAnnotation and \
            _TraceAnnotation("tpu_mx/train_step/" + self.name)
        if self._annotation:
            self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.seconds = t1 - self.t0
        if self._annotation:
            self._annotation.__exit__(*exc)
        emit("train_step.phase", t0=self.t0, t1=t1, phase=self.name)
        return False


def _merge_profiler(event, t0, t1, payload):
    """Mirror the event onto the profiler chrome-trace (one Perfetto
    timeline for events + spans + XLA).  The span name is qualified by
    the event's categorical field (``train_step.phase:dispatch``,
    ``chaos.inject:hang``, ``fusion.flush:read_barrier``) — without it
    every phase of a step would collapse into one indistinguishable
    aggregate row, defeating phase attribution.  Degrades to a no-op
    standalone (no package) or when the profiler is not recording."""
    try:
        from . import profiler
    except ImportError:
        return
    try:
        for key in ("phase", "kind", "cause"):
            v = payload.get(key)
            if isinstance(v, str):
                event = f"{event}:{v}"
                break
        if t0 is None or t1 is None:
            t0 = t1 = time.perf_counter()
        profiler.record_span(event, t0, t1, category="tracing")
    except Exception:
        pass  # profiler torn down mid-exit must not break emission


def snapshot(last=None):
    """A consistent copy of the ring's events, oldest first (``last=N``
    keeps only the newest N)."""
    with _lock:
        events = list(_ring)
    if last is not None:
        events = events[-int(last):]
    return events


def stats():
    """``{emitted, dropped, capacity, size}`` — overflow is visible,
    never silent (a black box whose window missed the fault says so)."""
    with _lock:
        return {"emitted": _emitted, "dropped": _dropped,
                "capacity": _ring.maxlen, "size": len(_ring)}


def reset():
    """Drop every event and context override (test hook); keeps run_id."""
    global _emitted, _dropped
    with _lock:
        _ring.clear()
        _emitted = 0
        _dropped = 0
        _context.update(epoch=None, step=None, generation=0, request=None,
                        rank=None, fleet_generation=None)


# ---------------------------------------------------------------------------
# the black box
# ---------------------------------------------------------------------------
def blackbox_path(prefix):
    return f"{prefix}-blackbox.json"


def _environment_fingerprint():
    """Where this process ran: enough to reproduce/attribute, nothing
    secret.  jax's version is recorded only when jax is ALREADY imported
    — a black box must be assemblable from a process that never booted
    it."""
    env = {k: v for k, v in sorted(os.environ.items())
           if k.startswith(("TPUMX_", "JAX_", "XLA_"))}
    jax_mod = sys.modules.get("jax")
    return {
        "hostname": socket.gethostname(),
        "pid": os.getpid(),
        "python": sys.version.split()[0],
        "platform": sys.platform,
        "argv": list(sys.argv),
        "cwd": os.getcwd(),
        "env": env,
        "jax": getattr(jax_mod, "__version__", None),
    }


def blackbox_doc(reason="", last=None):
    """Assemble (not persist) the black-box document: format tag, the
    trigger ``reason``, live trace context, the last N events, ring
    stats, a full telemetry snapshot and the environment fingerprint."""
    try:
        from . import telemetry
        # surface ring overflow (and any future bridge gauge) in the
        # box's own telemetry — one shared helper so the flush and
        # black-box export paths can never drift apart
        telemetry._refresh_bridge_gauges()
        tel = telemetry.snapshot()
    except ImportError:
        tel = []  # standalone module load: no telemetry registry
    return {
        "format": BLACKBOX_FORMAT,
        "reason": str(reason),
        "wall_time": time.time(),
        "written_at": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "context": get_context(),
        "stats": stats(),
        "events": snapshot(last=last),
        "telemetry": tel,
        "environment": _environment_fingerprint(),
    }


def dump_blackbox(prefix, reason="", last=None):
    """Persist the black box as ``<prefix>-blackbox.json`` through
    ``checkpoint.atomic_write`` (all-or-nothing: a crash mid-dump leaves
    the previous box, never a torn one) and return the path.

    The file is ROLLING — each dump overwrites the last — but the ring
    holds the full recent timeline, so the newest box still contains
    every earlier fault within the window (``stats.dropped`` says when
    the window was exceeded).  Render with ``tools/blackbox_report.py``.
    """
    path = blackbox_path(prefix)
    doc = blackbox_doc(reason=reason, last=last)
    try:
        # STRICT JSON: events are non-finite-safe by construction (emit
        # encodes NaN/Inf as strings), and a box that jq/browsers cannot
        # parse defeats the read-it-anywhere contract
        payload = json.dumps(doc, sort_keys=True, allow_nan=False)
    except ValueError:
        # a non-finite value outside the events (e.g. a telemetry
        # histogram that observed NaN): keep the box rather than lose
        # the post-mortem — python's reader accepts the NaN token
        payload = json.dumps(doc, sort_keys=True)
    try:
        from .checkpoint import atomic_write
    except ImportError:
        # standalone module load (no package → no durability layer): a
        # torn box is still parseable up to the tear worst-case, and
        # this path never runs inside the supervised stack
        # tpumx-lint: disable=durability -- degraded standalone mode
        # only; the package path below always uses atomic_write
        with open(path, "w", encoding="utf-8") as f:
            f.write(payload)
    else:
        with atomic_write(path, "w") as f:
            f.write(payload)
        try:
            from . import telemetry
            telemetry.counter("tracing.blackbox_dumps").inc()
        except ImportError:
            pass
    emit("supervisor.blackbox", path=path, reason=str(reason))
    return path


def validate_blackbox(doc):
    """Raise ValueError unless `doc` is a schema-valid black box: the
    known format tag, a complete context object, schema-valid events
    (each individually checked against :data:`KNOWN_EVENTS`), list-typed
    telemetry, and the ring stats/environment objects."""
    if not isinstance(doc, dict):
        raise ValueError(f"black box is {type(doc).__name__}, not an object")
    if doc.get("format") != BLACKBOX_FORMAT:
        raise ValueError(f"unknown black-box format {doc.get('format')!r} "
                         f"(this build reads {BLACKBOX_FORMAT})")
    ctx = doc.get("context")
    if not isinstance(ctx, dict) or \
            not {"run_id", "epoch", "step", "generation"} <= set(ctx):
        raise ValueError("black box missing a complete 'context' object "
                         "(run_id/epoch/step/generation)")
    if not isinstance(doc.get("events"), list):
        raise ValueError("black box missing the 'events' list")
    for i, rec in enumerate(doc["events"]):
        try:
            validate_event(rec)
        except ValueError as e:
            raise ValueError(f"events[{i}]: {e}") from e
    if not isinstance(doc.get("telemetry"), list):
        raise ValueError("black box missing the 'telemetry' list")
    for field in ("stats", "environment"):
        if not isinstance(doc.get(field), dict):
            raise ValueError(f"black box missing the {field!r} object")
    if not isinstance(doc.get("wall_time"), (int, float)):
        raise ValueError("black box missing numeric 'wall_time'")
    return doc
