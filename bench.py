"""Benchmark: ResNet-50 + BERT-base training throughput, single chip (the two
BASELINE.md headline metrics).

Runs the full compiled train step (fwd+bwd+optimizer update in one XLA
program, bf16 compute / f32 master state) for BOTH headline workloads and
prints ONE JSON line:
  {"metric": "resnet50_...", "value": N, "unit": "img/s", "vs_baseline": N,
   "mfu": ..., "bert": {"metric": "bert_base_...", ...}}
The primary record is ResNet-50 (driver contract); the BERT-base record rides
in the "bert" field (VERDICT r2 ask#2: both metrics, flash path confirmed).
vs_baseline is against the A100 ballparks in BASELINE.md.

ResNet-50 runs channels-last with the space-to-depth stem by default
(BENCH_STEM=classic reverts): the classic 7×7/2 stem feeds C=3 into the
128-lane MXU contraction ~43× under-filled; the 4×4 space-to-depth transform
makes the first conv contract over 48 channels (VERDICT r2 ask#1).

One process holds the chip: the outer process (this file, run with no args)
imports NO jax; it supervises `python bench.py --inner` children with a hard
timeout and retry/backoff and streams the child's stage prints to stderr.
It prints the JSON line of the attempt that measured something and exits 0
only if every requested leg succeeded; a failed leg, or every attempt dying,
is a non-zero exit, and no number from an earlier run is printed instead.
Without BENCH_SMOKE=1 a platform other than tpu is an error.

Env knobs: BENCH_SMOKE=1 (CPU smoke, small shapes), BENCH_LAYOUT=NCHW
(default NHWC), BENCH_STEM=classic (default s2d), BENCH_BATCH / BENCH_ITERS /
BENCH_BERT_BATCH / BENCH_BERT512_BATCH / BENCH_LSTM_BATCH /
BENCH_SSD_BATCH overrides, BENCH_BERT512_REMAT (default 1),
BENCH_SSD_BACKBONE (default vgg16_reduced — the reference config;
=compact for the r4 light backbone, comparator-less),
BENCH_MODELS ⊆ {resnet50, bert, bert512, scaling, lstm, ssd, fusion}
(fusion = the imperative pointwise-fusion A/B microbench, CPU-targeted,
not in the default on-chip set; default
resnet50,bert,bert512,lstm,ssd — all five workload benches, so the
driver's round-end record carries every hardware number; per-metric
persistence keeps a leg that dies mid-sweep from losing the earlier legs;
scaling = weak-scaling efficiency over all visible devices, BASELINE
metric 3, needs a multi-device mesh),
BENCH_ATTEMPTS (default 2), BENCH_TIMEOUT seconds per attempt (default 2400),
BENCH_SKIP_FRESH seconds (default 0 = off): carry a leg's stored record
instead of re-measuring when it is younger than this, so a retry after a
failed attempt spends its time on the legs still missing (carried
legs keep their own measured_at + carried_fresh=true; the quick-bench's
short-timing resnet record never qualifies via the min-iters gate).
Execution order is resnet, bert, lstm, ssd, bert512 — the giant bert512
remat compile runs last so a failure inside it cannot cost unmeasured legs.
MFU fields: `mfu` is XLA-cost-analysis-derived (the number of record,
VERDICT r4 ask#9); `mfu_analytic_model` is the hand FLOPs-model cross-check.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

def _lastgood_path():
    return os.environ.get(
        "BENCH_LASTGOOD_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_LASTGOOD.json"))

A100_RESNET50 = 2800.0   # img/s, BASELINE.md ballpark (AMP, 1×A100-80GB)
A100_BERT_BASE = 245.0   # seq/s, BASELINE.md ballpark midpoint (phase-1 128)
# Derived comparator ballparks for the workloads with no published A100
# number (VERDICT r4 ask#6; derivations with stated assumptions in
# BASELINE.md "Derived ballparks"):
A100_LSTM_PTB = 780_000.0   # tok/s: 79.6 MFLOPs/tok model @ 20% A100 util
A100_SSD512_VGG = 170.0     # img/s: NGC SSD300-RN50 utilization (~29%)
#                             transferred to the VGG16-reduced SSD-512 model
V5E_PEAK_FLOPS = 197e12  # bf16 peak, TPU v5e chip
RESNET50_TRAIN_FLOPS_PER_IMG = 3 * 2 * 4.09e9  # fwd GMACs*2, *3 for fwd+bwd


def a100_bert_512_ballpark():
    """Phase-2 (seq 512) comparator: iso-utilization transfer of the
    phase-1 A100 ballpark through the FLOPs model — ballpark_512 =
    ballpark_128 x flops(128)/flops(512) (~57 seq/s).  Documented in
    BASELINE.md; attention makes A100 utilization at 512 slightly worse,
    so this transfer is comparator-favoring (honest direction)."""
    f128 = bert_train_flops_per_seq(12, 768, 3072, 30522, 128,
                                    max(1, int(0.15 * 128)))
    f512 = bert_train_flops_per_seq(12, 768, 3072, 30522, 512,
                                    max(1, int(0.15 * 512)))
    return A100_BERT_BASE * f128 / f512


def bert_train_flops_per_seq(num_layers, units, hidden, vocab, seq_len,
                             n_masked):
    """Matmul-only train flops per sequence, counted per executed matmul
    (fwd 2·flops, bwd 4·flops): per-layer qkv/attn-out/ffn + the T² score
    and AV terms over all T positions, the MLM dense + tied vocab head over
    ONLY the n_masked positions (embedding lookups are gathers, not
    matmuls, and are excluded)."""
    per_tok_layer = 2 * units * (3 * units) + 2 * units * units \
        + 2 * 2 * units * hidden
    body = num_layers * seq_len * (per_tok_layer + 4 * seq_len * units)
    head = n_masked * (2 * units * units + 2 * vocab * units)
    return 3 * (body + head)


def log(msg):
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


_PERSIST_PLATFORM_OK = None


def _persist_platform_ok():
    """Only a process whose backend is the real TPU may write the store.
    The smoke guard below is not enough: a non-smoke CPU drive with the
    production metric name (e.g. a BENCH_BATCH=4 JAX_PLATFORMS=cpu
    verification run — r5 hit exactly this) would clobber a real-chip
    record.  Same platform contract as mfu_probe/longctx merge-on-write.
    BENCH_PERSIST_ANY_PLATFORM=1 bypasses for the store-logic tests."""
    global _PERSIST_PLATFORM_OK
    if os.environ.get("BENCH_PERSIST_ANY_PLATFORM") == "1":
        return True
    if _PERSIST_PLATFORM_OK is None:
        try:
            import jax
            platform = jax.devices()[0].platform
        except Exception as e:
            # transient probe failure: refuse THIS persist (loudly) but
            # don't cache — a later call in the same run may succeed
            log(f"persist refused: backend probe failed "
                f"({type(e).__name__}: {e}); record NOT stored")
            return False
        _PERSIST_PLATFORM_OK = platform == "tpu"
        if not _PERSIST_PLATFORM_OK:
            log(f"persist refused: platform is {platform}, not tpu — "
                "records from this process will NOT touch the store")
    return _PERSIST_PLATFORM_OK


def persist_lastgood(rec):
    """Write the measurement to BENCH_LASTGOOD.json the moment it exists
    (VERDICT r3 weak#2: round 3's official record was 0.0/error while a
    real number measured 11 h earlier sat only in an interim note — every
    good measurement must survive the process that produced it).  Atomic
    via tmp+rename so a kill mid-write can't corrupt the last record.
    Smoke-mode runs never persist: a CPU smoke number (whose metric name
    may not say "smoke" — e.g. weak_scaling_efficiency_dp8) must never
    mask a real-chip record.  The store is keyed by metric so a
    BENCH_MODELS=bert (or scaling) run can never clobber the resnet
    record.  Persist failures are logged, never raised: the resilience
    layer must not be able to kill a successful measurement run."""
    if os.environ.get("BENCH_SMOKE") == "1" or \
            "smoke" in rec.get("metric", ""):
        return
    if not _persist_platform_ok():
        return
    if rec.get("metric") == "weak_scaling_efficiency_dp1":
        # single-device placeholder (trivially 1.0), not a measurement —
        # it must never enter the store, where freshest-wins grafting
        # would let it shadow a real multi-device scaling record
        return
    try:
        path = _lastgood_path()
        try:
            with open(path) as f:
                store = json.load(f)
        except (OSError, ValueError):
            store = {}
        if not isinstance(store, dict):
            store = {}
        records = store.get("records")
        if not isinstance(records, dict):
            records = {}
        records[rec["metric"]] = {
            "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
            "commit": _git_head(),
            "record": rec}
        # durability-layer atomic write (tmp + fsync + rename, ISSUE 2): a
        # bench run killed mid-persist can never leave a truncated
        # BENCH_LASTGOOD.json that poisons the carry logic
        from tpu_mx.checkpoint import atomic_write
        with atomic_write(path, "w") as f:
            f.write(json.dumps({"records": records}, indent=1))
    except Exception as e:
        log(f"persist_lastgood failed (measurement still emitted): "
            f"{type(e).__name__}: {e}")


PRIMARY_METRIC = "resnet50_train_images_per_sec_per_chip"

# Canonical full-run timing iterations per leg (the official-record bar).
# Carried-record min-iters gates key on THESE, never on the env-derived
# BENCH_ITERS: a retry launched with both BENCH_SKIP_FRESH and a lowered
# BENCH_ITERS must not accept an equally short stored record as official
# (ADVICE r5 low, bench.py:1003).  Records timed below the bar also get
# vs_baseline stripped — the r5 quick-vs-full spread was 8.5% from
# iteration count alone, enough to fake a regression (VERDICT r5 weak#2).
FULL_RUN_ITERS = {"resnet50": 30, "lstm": 20, "ssd": 10}


def _strip_short_run_baseline(rec, leg):
    if rec.get("iters", 0) < FULL_RUN_ITERS[leg] and \
            rec.get("vs_baseline") is not None:
        rec["vs_baseline"] = None
        rec["vs_baseline_note"] = (
            f"short-timing run (iters < {FULL_RUN_ITERS[leg]}): too noisy "
            "for a baseline comparison; see VERDICT r5 weak#2")
    return rec


_GIT_HEAD = ("unresolved",)


def _git_head():
    """Commit of the current checkout (cached; None when unresolvable).
    Persisted records carry it so a carried record can be tied to the
    code that produced it (ADVICE r5 low, bench.py:310)."""
    global _GIT_HEAD
    if _GIT_HEAD == ("unresolved",):
        try:
            out = subprocess.run(
                ["git", "-C", os.path.dirname(os.path.abspath(__file__)),
                 "rev-parse", "HEAD"],
                capture_output=True, text=True, timeout=10)
            head = out.stdout.strip()
            _GIT_HEAD = (head if out.returncode == 0 and head else None,)
        except Exception:
            _GIT_HEAD = (None,)
    return _GIT_HEAD[0]


def load_lastgood():
    """Best stored measurement: the primary resnet metric if present,
    else the most recently measured other metric.  Returns (measured_at,
    record) or (None, None).  Tolerates any malformed store content.
    The program itself no longer reads the store back this way (the
    supervisor's stale re-emission is gone); the store-logic tests do,
    until ROADMAP S0/D2 fold the store away."""
    try:
        with open(_lastgood_path()) as f:
            store = json.load(f)
        records = store.get("records", {})
        entries = [v for v in records.values()
                   if isinstance(v, dict) and isinstance(v.get("record"),
                                                         dict)]
        entries = [v for v in entries
                   if isinstance(v["record"].get("value"), (int, float))
                   and v["record"]["value"] > 0]
        if not entries:
            return None, None

        def _graft_subs(v):
            # the store holds bert/scaling under their own metric keys
            # (always at least as fresh as any copy nested inside the
            # primary record, since the same run writes both) — serve the
            # per-key record of each alongside the primary.  Scaling keys
            # are dynamic (weak_scaling_efficiency_dp{n}), hence the
            # prefix match.
            rec = dict(v["record"])
            own = str(rec.get("metric") or "")

            def _field_of(metric, record=None):
                record = record or {}
                if metric == "bert_base_train_seqs_per_sec_per_chip":
                    return "bert"
                if metric == "bert_base_seq512_train_seqs_per_sec_per_chip":
                    return "bert512"
                if metric.startswith("weak_scaling_efficiency"):
                    # dynamic dp{n} key family — freshest wins, not
                    # dict order
                    return "scaling"
                if metric == "lstm_ptb_train_tokens_per_sec_per_chip":
                    return "lstm"
                if metric == "ssd512_train_images_per_sec_per_chip":
                    # the official key means the vgg16_reduced reference
                    # backbone from r5 on; a backbone-less record is the
                    # r4 compact measurement — surface it clearly labeled,
                    # never in the official slot (its 170 img/s comparator
                    # would be a wrong claim for a ~3x lighter model)
                    if record.get("backbone") == "vgg16_reduced":
                        return "ssd"
                    return "ssd_legacy_compact"
                if metric.startswith("ssd512_") and \
                        metric.endswith("_train_images_per_sec_per_chip"):
                    return "ssd_compact"  # explicitly-keyed non-vgg rows
                return None

            own_field = _field_of(own, rec)
            best = {}  # field -> store entry; freshest measured_at wins
            for key, sub in records.items():
                if key == own or not (isinstance(sub, dict)
                                      and isinstance(sub.get("record"),
                                                     dict)):
                    continue
                # same validity bar as primary selection: a null/zero
                # record must not be grafted either
                if not isinstance(sub["record"].get("value"),
                                  (int, float)) or sub["record"]["value"] <= 0:
                    continue
                field = _field_of(key, sub["record"])
                # never graft a sibling of the primary's own family (a
                # scaling primary carrying a staler scaling nested inside
                # itself would be contradictory, not supplementary)
                if field is None or field == own_field:
                    continue
                if field not in best or str(sub.get("measured_at", "")) > \
                        str(best[field].get("measured_at", "")):
                    best[field] = sub
            for field, sub in best.items():
                # carry the sub's own timestamp: it may come from a
                # different run than the primary, and this harness exists
                # because freshness misattribution cost round 3 its record
                rec[field] = dict(sub["record"],
                                  measured_at=sub.get("measured_at"))
                if field == "ssd_legacy_compact":
                    rec[field].setdefault("backbone", "compact")
                    rec[field]["note"] = (
                        "r4-era measurement on the light compact "
                        "backbone; not comparable to the vgg16_reduced "
                        "official row or its A100 ballpark")
            return v.get("measured_at"), rec

        for v in entries:
            if v["record"].get("metric") == PRIMARY_METRIC:
                return _graft_subs(v)
        v = max(entries, key=lambda v: str(v.get("measured_at", "")))
        return _graft_subs(v)
    except Exception:
        return None, None


def _fresh_stored(metric_key, max_age_s, require=None, min_iters=None,
                  validate=None):
    """Stored record for metric_key if it was measured on chip within
    max_age_s seconds, else None (BENCH_SKIP_FRESH: a retry after a
    failed attempt spends its time on the legs that still need measuring
    instead of re-timing ones banked minutes earlier).
    `require` narrows the match on record fields (e.g. ssd backbone: the
    official metric key predates the vgg16_reduced re-key, so an r4-era
    compact record must not satisfy it); `min_iters` keeps a short-timing
    quick-bench record from being carried as the official number;
    `validate(rec) -> bool` hooks leg-specific completeness checks (e.g.
    bert512's flash arm).  A record stamped with a different git commit
    than the current checkout is never carried — an intervening
    perf-affecting commit must be re-measured, not inherit the old
    number (ADVICE r5 low, bench.py:310); unstamped records (pre-stamp
    stores) carry with commit=None, auditable downstream."""
    try:
        with open(_lastgood_path()) as f:
            entry = json.load(f)["records"][metric_key]
        rec = entry["record"]
        if not isinstance(rec.get("value"), (int, float)) \
                or rec["value"] <= 0 or "error" in rec:
            return None
        for k, v in (require or {}).items():
            if rec.get(k) != v:
                return None
        if min_iters is not None and rec.get("iters", 0) < min_iters:
            return None
        if validate is not None and not validate(rec):
            return None
        stored_commit = entry.get("commit")
        head = _git_head()
        if stored_commit and head and stored_commit != head:
            log(f"{metric_key}: stored record is from commit "
                f"{stored_commit[:12]}, checkout is {head[:12]} — "
                "refusing to carry across code versions")
            return None
        import datetime
        measured = datetime.datetime.strptime(
            str(entry["measured_at"]), "%Y-%m-%dT%H:%M:%S%z")
        if 0 <= time.time() - measured.timestamp() <= max_age_s:
            return dict(rec, measured_at=entry["measured_at"],
                        carried_fresh=True, commit=stored_commit)
    except Exception:
        return None
    return None


# ---------------------------------------------------------------------------
# inner: the actual benchmark (may hang on a flaky backend; outer kills us)
# ---------------------------------------------------------------------------
def _fetch_loss(l):
    """Host-fetch the loss scalar — the sync point for every benchmark
    here (see the comment in _timed: the loss depends on the full update
    chain, so fetching it bounds every queued step)."""
    import numpy as np
    return float(np.asarray(l._data).ravel()[0])


def _timed(step_fn, fetch_loss, n):
    t0 = time.perf_counter()
    loss = None
    for _ in range(n):
        loss = step_fn()
    # Sync via a host fetch of the loss scalar: dispatch is asynchronous,
    # and the loss depends on the full weight-update chain, so fetching
    # it bounds every queued step (as valid a barrier as
    # block_until_ready, and the value is wanted on the host anyway).
    fetch_loss(loss)
    return time.perf_counter() - t0


def _run_timed(step_fn, fetch_loss, warmup, iters, repeats, unit_count, tag):
    _timed(step_fn, fetch_loss, 1)
    log(f"{tag}: first step done; warmup...")
    for _ in range(warmup):
        _timed(step_fn, fetch_loss, 1)
    log(f"{tag}: timing {iters} steps x {repeats} repeats...")
    best = None
    for r in range(repeats):
        dt = _timed(step_fn, fetch_loss, iters)
        log(f"  {tag} repeat {r}: {dt:.3f}s ({unit_count * iters / dt:.1f}/s)")
        best = dt if best is None else min(best, dt)
    return unit_count * iters / best


def _attach_mfu(rec, step, batch_args, per_sec, unit_flops, batch):
    """MFU fields (VERDICT r4 ask#9 — ONE definition of record):
    `mfu` is computed from XLA's own cost-analysis FLOPs of the compiled
    step (compiler-derived, immune to hand-model drift); the analytic
    FLOPs model rides as `mfu_analytic_model` for cross-check.  Falls
    back to the analytic model (with mfu_source saying so) only when
    cost_analysis is unavailable on the backend."""
    analytic = per_sec * unit_flops / V5E_PEAK_FLOPS
    rec["mfu_analytic_model"] = round(analytic, 4)
    try:
        ca = step.aot_compiled(*batch_args).cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        flops = float(ca.get("flops", 0.0))
    except Exception as e:
        log(f"cost_analysis unavailable ({type(e).__name__}: {e}); "
            f"mfu falls back to the analytic model")
        flops = 0.0
    if flops > 0:
        rec["mfu"] = round(flops * per_sec / batch / V5E_PEAK_FLOPS, 4)
        rec["mfu_source"] = "xla_cost_analysis"
        rec["analytic_vs_xla_flops_ratio"] = round(
            unit_flops * batch / flops, 4)
    else:
        rec["mfu"] = round(analytic, 4)
        rec["mfu_source"] = "analytic_model"
    return rec


def _bench_dtype(env_var, smoke):
    """(dtype, multi_precision) for a bench leg: bfloat16 on hardware by
    default, float32 in CPU smoke (keeps the nightly fast and smoke
    numerics boring); per-leg env override (=float32 reverts on chip).
    The resnet leg predates this helper and casts unconditionally."""
    dt = os.environ.get(env_var, "float32" if smoke else "bfloat16")
    return dt, dt != "float32"


def _is_oom(e):
    # explicit allocation-failure phrases only: a bare "hbm" mention (e.g.
    # a bandwidth note inside some other error) must NOT trigger the
    # silent batch fallback
    s = f"{type(e).__name__}: {e}".lower()
    return ("ran out of memory" in s or "out of memory" in s
            or "resource_exhausted" in s or "exceeded hbm capacity" in s)


def _batch_ladder(env_var, ladder):
    """BENCH_BATCH/BENCH_BERT_BATCH=N forces one size; unset runs the
    ladder largest-first, falling back on HBM OOM (larger batches usually
    win on MXU utilization but the margin to 16 GB is model-dependent —
    measure, don't guess)."""
    v = os.environ.get(env_var)
    return [int(v)] if v else list(ladder)


def _run_ladder(tag, ladder, once):
    """Try batch sizes largest-first; fall back on HBM OOM.  The last
    rung re-raises (no fallback left)."""
    for i, batch in enumerate(ladder):
        try:
            return once(batch)
        except Exception as e:
            if i + 1 < len(ladder) and _is_oom(e):
                log(f"{tag} batch {batch} OOM ({e}); "
                    f"falling back to {ladder[i + 1]}")
                continue
            raise


def bench_resnet(smoke, layout, stem):
    # 256-first: the r4 on-chip sweep measured 256 > 384 > 512
    # (2379 / 2275 / 2254 img/s) — past ~256 the extra HBM pressure
    # costs more than the MXU fill gains.
    ladder = _batch_ladder("BENCH_BATCH", (8,) if smoke else (256, 128))
    return _run_ladder("resnet", ladder,
                       lambda b: _resnet_once(smoke, layout, stem, b))


def _resnet_once(smoke, layout, stem, batch):
    import numpy as np
    import tpu_mx as mx
    from tpu_mx import gluon, nd
    from tpu_mx.gluon.model_zoo import vision
    from tpu_mx.layout import default_layout
    from tpu_mx.parallel import CompiledTrainStep

    if smoke:
        size, warmup, iters = 64, 1, 3
        classes, factory = 100, "resnet18_v1"
    else:
        size, warmup, iters = 224, 3, 30
        classes, factory = 1000, "resnet50_v1"
    iters = int(os.environ.get("BENCH_ITERS", iters))

    log(f"building {factory} ({layout}, stem={stem}), batch={batch}, "
        f"size={size}")
    shape = (batch, size, size, 3) if layout == "NHWC" else (batch, 3, size, size)
    with default_layout(layout):
        net = getattr(vision, factory)(classes=classes, stem=stem)
    if os.environ.get("BENCH_RESNET_REMAT", "0") == "1" and not smoke:
        # A/B knob, measured and REJECTED as a default (r4: 1847.2 vs
        # 2371.5 img/s at batch 256): recomputed conv outputs re-
        # materialize in HBM during the backward, so full-block remat ADDS
        # a pass over the conv activations on this bandwidth-bound step
        # (docs/performance.md roofline). Kept for memory-bound configs
        # where remat buys otherwise-impossible batch.
        from tpu_mx.gluon import nn as _nn
        n_remat = 0
        for stage in net.features._children.values():
            if isinstance(stage, _nn.HybridSequential):
                for blk in stage._children.values():
                    blk.remat()
                    n_remat += 1
        log(f"resnet: remat enabled on {n_remat} residual blocks")
    net.initialize(init="xavier")
    # Finalize deferred shapes on a tiny ON-DEVICE batch: param shapes
    # don't depend on batch, and a full-batch host tensor would cost
    # ~150 MB of host->device transfer + a batch-256 eager forward
    # before the first measurement.
    net.finalize_shapes(nd.random.uniform(shape=(2,) + shape[1:]))
    net.cast("bfloat16")

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    opt = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9,
                              wd=1e-4, multi_precision=True)
    step = CompiledTrainStep(net, loss_fn, opt, mesh=None)

    data = nd.cast(nd.random.uniform(shape=shape), "bfloat16")
    label = nd.random.randint(0, classes, (batch,), dtype="float32")

    log("resnet: compiling full train step (first call)...")
    img_s = _run_timed(lambda: step.step(data, label), _fetch_loss, warmup, iters,
                       1 if smoke else 3, batch, "resnet")
    rec = {
        "metric": "resnet50_train_images_per_sec_per_chip"
        if not smoke else "resnet18_smoke_images_per_sec",
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_s / A100_RESNET50, 4),
    }
    if not smoke:
        _attach_mfu(rec, step, (data, label), img_s,
                    RESNET50_TRAIN_FLOPS_PER_IMG, batch)
    rec["layout"] = layout
    rec["stem"] = stem
    rec["batch"] = batch
    rec["iters"] = iters  # self-describing: a 5-iter quick probe must be
    #                       distinguishable from the official 30-iter run
    if not smoke:
        _strip_short_run_baseline(rec, "resnet50")
    return rec


def bench_bert(smoke):
    # The r4 sweep (384 -> 724.9 seq/s > 256 -> 707 > 512 OOM remat-free)
    # was measured when "bf16" BERT silently ran f32 activations (the
    # dtype= bug fixed in r5): true-bf16 halves activation bytes, so the
    # ladder now probes 768/512 first — largest-first with OOM fallback
    # keeps the measured 384 as the safety net.
    ladder = _batch_ladder("BENCH_BERT_BATCH",
                           (8,) if smoke else (768, 512, 384, 256))
    return _run_ladder("bert", ladder, lambda b: _bert_once(smoke, b))


def bench_bert512(smoke):
    """Phase-2-style BERT-base seq-512 row (VERDICT r4 ask#5): the memory
    regime where flash attention + remat matter, in the official record.
    The value is the production auto-dispatch path, which at kv_len 512
    is the Pallas flash kernel (PERF.md section 6, PR 26)."""
    ladder = _batch_ladder("BENCH_BERT512_BATCH",
                           (4,) if smoke else (192, 128, 96, 64, 32))
    remat = os.environ.get("BENCH_BERT512_REMAT", "1") == "1"
    return _run_ladder("bert512", ladder,
                       lambda b: _bert_once(smoke, b, seq_len=512,
                                            remat=remat))


def _bert_once(smoke, batch, seq_len=128, remat=None):
    import numpy as np
    import tpu_mx as mx
    from tpu_mx import gluon, nd
    from tpu_mx.models.bert import BERTModel, bert_base_config
    from tpu_mx.parallel import CompiledTrainStep
    from tpu_mx.parallel.ring_attention import dispatch_counts

    if smoke:
        cfg = bert_base_config(vocab_size=1000, max_len=seq_len)
        cfg.update(num_layers=2, units=128, hidden_size=512, num_heads=2)
        warmup, iters, repeats = 1, 3, 1
    else:
        cfg = bert_base_config(max_len=seq_len)
        warmup, iters, repeats = 3, 20, 3
        if seq_len >= 512:
            iters = 10  # 4x the tokens per step; keep the leg's wall time

    # remat defaults OFF at seq 128: the r4 on-chip sweep measured
    # remat-free batch 384 at 724.9 seq/s vs remat batch 512 at 578.3
    # (recompute cost ~22% and the bigger batch does not pay for it) —
    # measured under the f32-activation dtype bug; the r5 true-bf16
    # ladder probes larger batches first and relies on OOM fallback.
    # dots_saveable measured strictly worse (OOM at 512 AND 256).  At seq
    # 512 the caller decides (bench_bert512 defaults remat ON — the
    # activation regime is 4x per sequence).
    if remat is None:
        remat = os.environ.get("BENCH_BERT_REMAT", "0") == "1"
    # BENCH_BERT_REMAT_POLICY=dots_saveable keeps MXU outputs across the
    # checkpoint boundary (less recompute, more HBM) — sweep on-chip
    policy = os.environ.get("BENCH_BERT_REMAT_POLICY") or None
    log(f"building bert ({cfg['num_layers']}L u{cfg['units']}), "
        f"batch={batch}, seq={seq_len}, remat={remat}, policy={policy}")
    # per-layer jax.checkpoint: batch 512 × seq 128 activations for 12
    # layers exceed the 16 GB HBM (measured 27 GB); remat trades ~1 extra
    # forward for O(1)-segment activation memory
    net = BERTModel(cfg, dtype="bfloat16", remat=remat,
                    remat_policy=policy)
    net.initialize()
    rng = np.random.RandomState(0)
    tokens = rng.randint(4, cfg["vocab_size"], (batch, seq_len)).astype(
        np.int32)
    types = np.zeros((batch, seq_len), np.int32)
    # reference pretraining contract: the vocab head runs ONLY on the 15%
    # masked positions (B, M) — full-T logits would be ~4 GB at this scale
    n_masked = max(1, int(0.15 * seq_len))
    positions = np.stack([rng.choice(seq_len, n_masked, replace=False)
                          for _ in range(batch)]).astype(np.int32)
    labels = np.take_along_axis(tokens, positions, axis=1)
    # ONE row through the masked head if anything is deferred — BERT
    # declares every dim so this is normally a no-op (an eager 12-layer
    # forward is pure cold-start waste)
    net.finalize_shapes(nd.array(tokens[:1]), nd.array(types[:1]), None,
                        nd.array(positions[:1]))

    class MLMLoss(gluon.loss.Loss):
        """CE over the gathered masked positions (every label is a real
        token id on this path — no ignore-index sentinel needed)."""

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
    t_nd, ty_nd = nd.array(tokens), nd.array(types)
    p_nd, l_nd = nd.array(positions), nd.array(labels)
    none_vl = None  # full sequences: no padding in the bench batch

    # dispatch counters are process-global and cumulative: snapshot before
    # this leg so a bert512 flash arm after a dense bert128 leg (or vice
    # versa) reports ITS OWN compiled path, not an earlier leg's
    counts0 = dict(dispatch_counts)
    log(f"bert(seq={seq_len}): compiling full train step (first call)...")
    seq_s = _run_timed(
        lambda: step.step(t_nd, ty_nd, none_vl, p_nd, l_nd), _fetch_loss,
        warmup, iters, repeats, batch, f"bert{seq_len}")

    # which attention path compiled in (VERDICT r2 ask#2: prove flash, not
    # the dense O(T²) fallback)
    if dispatch_counts["pallas_flash"] > counts0.get("pallas_flash", 0):
        path = "pallas_flash"
    elif dispatch_counts["ring"] > counts0.get("ring", 0):
        path = "ring"
    else:
        path = "xla_dense"
    flops = bert_train_flops_per_seq(cfg["num_layers"], cfg["units"],
                                     cfg["hidden_size"],
                                     cfg["vocab_size"], seq_len, n_masked)
    if smoke:
        metric, baseline = f"bert_smoke_seq{seq_len}_seqs_per_sec", None
    elif seq_len == 512:
        metric = "bert_base_seq512_train_seqs_per_sec_per_chip"
        baseline = a100_bert_512_ballpark()
    else:
        metric = "bert_base_train_seqs_per_sec_per_chip"
        baseline = A100_BERT_BASE
    rec = {
        "metric": metric,
        "value": round(seq_s, 2),
        "unit": "seq/s",
        "vs_baseline": round(seq_s / baseline, 4) if baseline else None,
        "attention_path": path,
        "seq_len": seq_len,
        "batch": batch,
        "iters": iters,
        "remat": bool(remat),
    }
    if not smoke:
        _attach_mfu(rec, step, (t_nd, ty_nd, none_vl, p_nd, l_nd), seq_s,
                    flops, batch)
    return rec


def bench_lstm(smoke):
    # 2048-first: the r4 third-session on-chip sweep measured
    # 512 -> 648k, 1024 -> 710k, 2048 -> 743k, 4096 -> 714k tok/s —
    # the scan amortizes per-step overhead up to 2048, then HBM pressure
    # wins.  Batch is recorded in the emitted record; PTB convergence
    # configs are far smaller (the classic is 20-32) and this metric is
    # per-chip THROUGHPUT at the annotated batch.
    ladder = _batch_ladder("BENCH_LSTM_BATCH",
                           (4,) if smoke else (2048, 1024, 512))
    return _run_ladder("lstm", ladder, lambda b: _lstm_once(smoke, b))


def _lstm_once(smoke, batch):
    """PTB word-level LSTM LM (BASELINE workload 3): medium config
    (vocab 10k, 2×650, bptt 35), full compiled train step, tokens/s.
    vs_baseline is against the DERIVED A100 ballpark in BASELINE.md
    (79.6 MFLOPs/tok analytic model at an assumed 20% cuDNN end-to-end
    utilization — no published A100 PTB number exists to cite; the
    derivation and its uncertainty band are documented there)."""
    import numpy as np
    import tpu_mx as mx
    from tpu_mx import gluon, nd
    from tpu_mx.gluon.block import HybridBlock
    from tpu_mx.models.lstm_lm import RNNModel
    from tpu_mx.parallel import CompiledTrainStep

    if smoke:
        vocab, emb, hid, layers, bptt = 1000, 64, 64, 1, 8
        warmup, iters, repeats = 1, 3, 1
    else:
        vocab, emb, hid, layers, bptt = 10000, 650, 650, 2, 35
        warmup, iters, repeats = 3, 20, 3
    iters = int(os.environ.get("BENCH_ITERS", iters))

    log(f"building lstm ({layers}x{hid}, bptt={bptt}), batch={batch}")
    model = RNNModel(mode="lstm", vocab_size=vocab, num_embed=emb,
                     num_hidden=hid, num_layers=layers, dropout=0.0)
    model.initialize(init="xavier")

    class FlatCE(gluon.loss.Loss):
        """CE over the flattened (T·B, V) logits — the word-LM target
        layout (REF:example/gluon/word_language_model).  Logits upcast to
        f32: log-softmax over a 10k vocab in bf16 loses the digits the
        loss needs."""

        def __init__(self, **kw):
            super().__init__(weight=None, batch_axis=0, **kw)
            self._ce = gluon.loss.SoftmaxCrossEntropyLoss()

        def hybrid_forward(self, F, logits, labels):
            v = logits.shape[-1]
            return self._ce(
                F.cast(F.reshape(logits, shape=(-1, v)), dtype="float32"),
                F.reshape(labels, shape=(-1,)))

    rng = np.random.RandomState(0)
    x = nd.array(rng.randint(0, vocab, (bptt, batch)), dtype="float32")
    y = nd.array(rng.randint(0, vocab, (bptt * batch,)), dtype="float32")
    model.finalize_shapes(x)  # no-op: RNNModel declares every dim
    # bf16 weights/activations (BENCH_LSTM_DTYPE=float32 reverts): the r4
    # 740k tok/s was measured in f32 — the same dtype-audit sweep that
    # caught BERT found the LSTM/SSD legs never cast.  Cell state runs in
    # the compute dtype over bptt=35 (a 120-step CPU A/B tracked f32 to
    # within 0.03 nats); the A100 comparator ballpark is derived at bf16
    # peak, so f32 here was comparator-unfair to us.
    ldt, lmp = _bench_dtype("BENCH_LSTM_DTYPE", smoke)
    if ldt != "float32":
        model.cast(ldt)
    opt = mx.optimizer.create("sgd", learning_rate=1.0,
                              multi_precision=lmp)
    step = CompiledTrainStep(model, FlatCE(), opt)
    log("lstm: compiling full train step (first call)...")
    tok_s = _run_timed(lambda: step.step(x, y), _fetch_loss, warmup, iters,
                       repeats, batch * bptt, "lstm")
    rec = {
        "metric": "lstm_ptb_train_tokens_per_sec_per_chip"
        if not smoke else "lstm_smoke_tokens_per_sec",
        "value": round(tok_s, 2), "unit": "tok/s",
        "vs_baseline": None if smoke else round(tok_s / A100_LSTM_PTB, 4),
        "baseline_note": None if smoke else
        "derived ballpark (BASELINE.md): FLOPs model @ 20% A100 util",
        "batch": batch, "bptt": bptt, "hidden": hid, "layers": layers,
        "iters": iters, "dtype": ldt,
    }
    return rec if smoke else _strip_short_run_baseline(rec, "lstm")


def bench_ssd(smoke):
    # 128-first: the r4 third-session on-chip sweep measured
    # 32 -> 186.5, 64 -> 282.2, 128 -> 485.2 img/s, 256 -> OOM —
    # per-step fixed cost (anchor/target gen, many small heads)
    # dominated the old batch-32 default.  128 is one doubling from the
    # OOM point, so the ladder keeps the fallbacks.
    ladder = _batch_ladder("BENCH_SSD_BATCH",
                           (2,) if smoke else (128, 64, 32))
    return _run_ladder("ssd", ladder, lambda b: _ssd_once(smoke, b))


def _ssd_once(smoke, batch):
    """SSD-512 detection training (BASELINE workload 5): anchors +
    MultiBoxTarget matching with hard negative mining + CE/smooth-L1,
    all inside ONE compiled train step (target generation included, under
    stop_gradient — the reference runs it in the data/aux path).
    The official row runs the REFERENCE backbone (vgg16_reduced, the
    symbol_factory 'vgg16_reduced' 512 config) so the derived A100
    comparator in BASELINE.md applies; BENCH_SSD_BACKBONE=compact keeps
    the r4 light-backbone configuration (vs_baseline null there — no
    defensible comparator for a custom backbone)."""
    import numpy as np
    import tpu_mx as mx
    from tpu_mx import gluon, nd
    from tpu_mx.gluon.block import HybridBlock
    from tpu_mx.models.ssd import SSD, SSDTrainingTargets, ssd_512
    from tpu_mx.parallel import CompiledTrainStep

    backbone = os.environ.get("BENCH_SSD_BACKBONE", "vgg16_reduced")
    if smoke:
        size, classes = 64, 3
        warmup, iters, repeats = 1, 2, 1
        net = SSD(classes, sizes=[[0.2, 0.35], [0.5, 0.7]],
                  ratios=[[1, 2, 0.5]] * 2, base_filters=(8, 16))
    else:
        size, classes = 512, 20
        warmup, iters, repeats = 3, 10, 3
        net = ssd_512(classes, backbone=backbone)
    iters = int(os.environ.get("BENCH_ITERS", iters))
    targets = SSDTrainingTargets()

    class SSDTrain(HybridBlock):
        """forward(x, labels) -> per-sample loss (the tuple outputs of
        SSD can't ride through the step's single-output contract, so the
        loss lives in the forward; the step's loss_fn is a pass-through
        mean).  Head outputs upcast to f32 before target-matching and the
        losses — box/matching math is threshold-sensitive; the backbone
        compute stays in the net's dtype."""

        def __init__(self, ssd_net, **kw):
            super().__init__(**kw)
            self.net = ssd_net
            self._cls = gluon.loss.SoftmaxCrossEntropyLoss()
            self._box = gluon.loss.HuberLoss()

        def forward(self, x, labels):
            from tpu_mx import autograd, nd as _nd
            anchors, cls_preds, box_preds = self.net(x)
            anchors = _nd.cast(anchors, "float32")
            cls_preds = _nd.cast(cls_preds, "float32")
            box_preds = _nd.cast(box_preds, "float32")
            with autograd.pause():
                loc_t, loc_m, cls_t = targets(anchors, labels, cls_preds)
            return self._cls(cls_preds, cls_t) + \
                self._box(box_preds * loc_m, loc_t * loc_m)

    sdt, smp = _bench_dtype("BENCH_SSD_DTYPE", smoke)
    log(f"building ssd (size={size}, classes={classes}, backbone="
        f"{'compact' if smoke else backbone}, dtype={sdt}), batch={batch}")
    wrapper = SSDTrain(net)
    wrapper.initialize(init="xavier")
    rng = np.random.RandomState(0)
    labels = np.full((batch, 2, 5), -1.0, np.float32)
    for b in range(batch):
        cls = rng.randint(0, classes)
        x0, y0 = rng.uniform(0.05, 0.5, 2)
        x1, y1 = min(x0 + 0.3, 0.95), min(y0 + 0.3, 0.95)
        labels[b, 0] = [cls, x0, y0, x1, y1]
    # images on device (a full-batch host tensor is ~100 MB of host->device
    # transfer — see the resnet leg note); structured labels stay host-built
    x_nd = nd.random.uniform(high=0.1, shape=(batch, 3, size, size))
    l_nd = nd.array(labels)
    wrapper.finalize_shapes(x_nd[:2], l_nd[:2])  # tiny on-device batch
    # bf16 backbone compute (BENCH_SSD_DTYPE=float32 reverts): r4's 485
    # img/s was measured in f32 — see the lstm note; heads/targets/losses
    # run f32 via the SSDTrain casts above
    if sdt != "float32":
        wrapper.cast(sdt)
        x_nd = nd.cast(x_nd, sdt)
    dummy = nd.array(np.zeros((1,), np.float32))
    opt = mx.optimizer.create("sgd", learning_rate=0.01, momentum=0.9,
                              wd=5e-4, multi_precision=smp)
    step = CompiledTrainStep(wrapper, gluon.loss.PassThrough(), opt)
    log("ssd: compiling full train step (first call)...")
    img_s = _run_timed(lambda: step.step(x_nd, l_nd, dummy), _fetch_loss,
                       warmup, iters, repeats, batch, "ssd")
    vsb = None
    note = None
    if smoke:
        metric = "ssd_smoke_images_per_sec"
    elif backbone == "vgg16_reduced":
        # the official row: reference backbone, comparator applies
        metric = "ssd512_train_images_per_sec_per_chip"
        vsb = round(img_s / A100_SSD512_VGG, 4)
        note = ("derived ballpark (BASELINE.md): NGC SSD300-RN50 "
                "utilization transferred to the VGG16-reduced SSD-512 "
                "FLOPs model")
    else:
        # a different workload gets a different key: the r4 compact
        # number must never be confusable with the vgg reference row
        metric = f"ssd512_{backbone}_train_images_per_sec_per_chip"
    rec = {
        "metric": metric,
        "value": round(img_s, 2), "unit": "img/s", "vs_baseline": vsb,
        "baseline_note": note,
        "batch": batch, "size": size,
        "backbone": "compact(smoke)" if smoke else backbone,
        "iters": iters, "dtype": sdt,
    }
    return rec if smoke else _strip_short_run_baseline(rec, "ssd")


def bench_fusion(smoke):
    """Imperative pointwise-chain microbench: the engine.bulk() lazy
    fusion engine's A/B receipts, fused and eager arms in the SAME run
    (ISSUE 1 acceptance).  Dispatch-overhead regime by design — a 32-op
    elementwise chain on a small array, where the reference's engine
    bulking (and ours) pays: the eager arm pays 32 Python+jnp dispatches
    and materializes 31 intermediates, the fused arm pays 32 lazy appends
    plus ONE memoized jitted program.  CPU is the official platform
    (JAX_PLATFORMS=cpu): on-chip numbers are dominated by the async
    dispatch queue, not the imperative overhead this measures."""
    import numpy as np
    import jax
    from tpu_mx import engine, fusion, nd

    chain_ops = 32
    shape = (64, 64)
    iters = 30 if smoke else 200
    repeats = 2 if smoke else 3
    x = nd.array(np.random.RandomState(0).rand(*shape).astype(np.float32))

    def chain(v):
        y = v
        for _ in range(chain_ops // 4):
            y = nd.sin(y)
            y = y * 1.0009
            y = y + 0.1
            y = nd.tanh(y)
        return y

    def run_arm(bulked, n):
        if bulked:
            for _ in range(n):
                with engine.bulk(chain_ops * 2):
                    chain(x).wait_to_read()
        else:
            for _ in range(n):
                chain(x).wait_to_read()

    # the eager arm must be REAL eager even if the driver exported
    # TPUMX_FUSION=1; the fused arm must fuse even under TPUMX_FUSION=0
    prior = os.environ.pop("TPUMX_FUSION", None)
    try:
        log(f"fusion: warming both arms ({chain_ops}-op chain, {shape})")
        run_arm(False, 2)
        run_arm(True, 2)  # compiles + caches the fused program
        eager = fused = None
        for r in range(repeats):
            t0 = time.perf_counter()
            run_arm(False, iters)
            e = (time.perf_counter() - t0) / iters
            t0 = time.perf_counter()
            run_arm(True, iters)
            f = (time.perf_counter() - t0) / iters
            log(f"  fusion repeat {r}: eager {e * 1e6:.0f}us "
                f"fused {f * 1e6:.0f}us ({e / f:.2f}x)")
            eager = e if eager is None else min(eager, e)
            fused = f if fused is None else min(fused, f)
    finally:
        if prior is not None:
            os.environ["TPUMX_FUSION"] = prior
    return {
        "metric": "imperative_pointwise_fusion_speedup"
        if not smoke else "imperative_fusion_smoke_speedup",
        "value": round(eager / fused, 3),
        "unit": "x",
        "vs_baseline": None,
        "eager_us_per_chain": round(eager * 1e6, 1),
        "fused_us_per_chain": round(fused * 1e6, 1),
        "chain_ops": chain_ops,
        "shape": list(shape),
        "iters": iters,
        "platform": jax.devices()[0].platform,
        # the public accessor (telemetry-backed): compiled-program count +
        # hit/miss totals persist with every benchmark receipt
        "fusion_cache": fusion.cache_stats(),
    }


def measure_decode_micro(contexts, block_size=16, batch=4, heads=4,
                         dim=16, seed=20260804, repeats=2, tq=1):
    """decode_attention micro-arm (ISSUE 9): one decode step's attention,
    paged arm (device-resident pool + block-table kernel/XLA twin) vs
    the dense-gather reference arm (host pool + padded host gather), at
    several context lengths.

    Each arm gets its OWN cache in its production storage mode, filled
    with identical fixed-seed K/V, so the A/B is the real data-plane
    swap and not a storage-mode hybrid.  Per-context receipt: per-call
    and per-sequence-token µs for both arms, min of ``repeats`` means
    (the standard min-of-repeats discipline).  Shared by the bench serve
    leg and tools/paged_sweep.py.

    ``tq > 1`` measures the WIDENED query window (ISSUE 16): the
    speculative verify call batches ``tq`` query positions per sequence
    into one attention step, so the per-TOKEN cost should amortize —
    ``*_us_per_tok`` is the comparable unit across Tq values."""
    import numpy as np
    from tpu_mx.serving import attention as _sattn
    from tpu_mx.serving.kv_cache import PagedKVCache

    rng = np.random.RandomState(seed)
    rows = []
    for ctx in contexts:
        nblocks = batch * (-(-int(ctx) // block_size)) + 8
        caches = {
            "dense": PagedKVCache(1, heads, dim, block_size=block_size,
                                  num_blocks=nblocks, storage="host"),
            "paged": PagedKVCache(1, heads, dim, block_size=block_size,
                                  num_blocks=nblocks, storage="device"),
        }
        ids = [f"s{i}" for i in range(batch)]
        for i in range(batch):
            k = rng.rand(1, ctx, heads, dim).astype(np.float32)
            v = rng.rand(1, ctx, heads, dim).astype(np.float32)
            for cache in caches.values():
                cache.prefill(ids[i], k, v)
        q = rng.rand(batch, tq, heads, dim).astype(np.float32) if tq > 1 \
            else rng.rand(batch, heads, dim).astype(np.float32)
        iters = max(8, min(64, (1 << 18) // int(ctx)))
        row = {"context": int(ctx), "batch": batch, "heads": heads,
               "dim": dim, "block_size": block_size, "tq": int(tq),
               "iters": iters}
        for kind, cache in caches.items():
            fn = lambda: _sattn.decode_attention(q, cache, ids, 0,
                                                 kind=kind)
            fn()                       # warm (jit compile / first-touch)
            best = None
            for _ in range(repeats):
                t0 = time.perf_counter()
                for _ in range(iters):
                    out = fn()
                dt = (time.perf_counter() - t0) / iters
                best = dt if best is None else min(best, dt)
            assert np.all(np.isfinite(out))
            row[f"{kind}_us_per_call"] = round(best * 1e6, 1)
            row[f"{kind}_us_per_seq"] = round(best * 1e6 / batch, 2)
            row[f"{kind}_us_per_tok"] = round(
                best * 1e6 / (batch * tq), 2)
        row["paged_speedup"] = round(
            row["dense_us_per_call"] / row["paged_us_per_call"], 3)
        rows.append(row)
        log(f"  decode micro ctx={ctx} tq={tq}: dense "
            f"{row['dense_us_per_call']}us paged "
            f"{row['paged_us_per_call']}us "
            f"({row['paged_speedup']}x)")
    return rows


def measure_prefix_trace(model, smoke, seed):
    """Shared-prefix heavy-tail trace (ISSUE 12): N tenants drawing
    prompts from K templates — the "millions of users on shared system
    prompts" regime — run through the Server with prefix sharing ON vs
    OFF, in BOTH decode modes, on the SAME fixed-seed trace.

    Receipts per mode: ``prefix_hit_ratio`` (cached / total prompt
    tokens), ``prefill_bytes`` per arm and the off/on reduction ratio
    (acceptance bar: >= 2x), wall-clock per arm, and the hard gate —
    greedy token streams BIT-identical between arms (sharing must be a
    pure storage/compute optimization, never a behavior change; the
    suffix prefill reproduces the full prefill's logits exactly,
    tests/test_multitenant.py).  Each arm ends with the allocator
    refcount audit: drop the index, assert every refcount returned to
    zero."""
    import numpy as np
    from tpu_mx import serving

    rng = np.random.RandomState(seed + 12)
    n_req = 16 if smoke else 48
    tenants = ["t0", "t1", "t2", "t3"]
    # 48-token templates = 3 full 16-blocks shareable per prompt; the
    # 2-6 token unique tails model per-user payloads on a shared prompt
    templates = [list(1 + rng.randint(0, 120, size=48)) for _ in range(4)]
    choices = rng.randint(0, len(templates), size=n_req)
    tails = [list(1 + rng.randint(0, 120, size=int(t)))
             for t in rng.randint(2, 7, size=n_req)]
    # heavy-tailed generation lengths, like the main serve trace
    outs = [int(v) for v in rng.choice([4, 8, 16, 64], size=n_req,
                                       p=[0.35, 0.30, 0.20, 0.15])]
    assign = [tenants[i % len(tenants)] for i in range(n_req)]

    def arm(share, mode):
        prior = os.environ.get("TPUMX_PAGED_DECODE")
        os.environ["TPUMX_PAGED_DECODE"] = mode
        try:
            srv = serving.Server(
                model, num_blocks=4096, block_size=16, max_batch=16,
                max_pending=n_req + 1, max_tokens=10 ** 9,
                prefix_sharing=share,
                tenants={t: {"weight": 1.0} for t in tenants})
            t0 = time.perf_counter()
            reqs = [srv.submit(templates[c] + tails[i],
                               max_new_tokens=outs[i], tenant=assign[i])
                    for i, c in enumerate(choices)]
            srv.run_until_idle()
            wall = time.perf_counter() - t0
            stats = srv.engine.cache.prefix_stats()
            # post-run allocator audit: every reference returns to zero
            srv.engine.cache.drop_prefix_cache()
            leftover = srv.engine.cache.allocator.refcounts()
            assert not leftover, f"refcount leak after trace: {leftover}"
            return [r.tokens for r in reqs], stats, wall
        finally:
            if prior is None:
                os.environ.pop("TPUMX_PAGED_DECODE", None)
            else:
                os.environ["TPUMX_PAGED_DECODE"] = prior

    rows = {}
    for mode, tag in (("0", "dense"), ("1", "paged")):
        on_streams, on, w_on = arm(True, mode)
        off_streams, off, w_off = arm(False, mode)
        assert on_streams == off_streams, (
            f"greedy streams diverged with sharing on ({tag} mode) — "
            "sharing must be invisible to outputs")
        ratio = off["prefill_bytes"] / max(on["prefill_bytes"], 1)
        assert on["hit_ratio"] > 0, on
        assert ratio >= 2.0, (
            f"prefill-bytes reduction {ratio:.2f}x < 2x bar ({tag})")
        rows[tag] = {
            "prefix_hit_ratio": round(on["hit_ratio"], 4),
            "prefill_bytes_sharing_on": on["prefill_bytes"],
            "prefill_bytes_sharing_off": off["prefill_bytes"],
            "prefill_bytes_reduction": round(ratio, 2),
            "prefill_bytes_saved": on["prefill_bytes_saved"],
            "index_nodes_peak": on.get("nodes", 0),
            "streams_identical": True,
            "wall_s_sharing_on": round(w_on, 3),
            "wall_s_sharing_off": round(w_off, 3),
        }
        log(f"serve: prefix trace [{tag}] hit_ratio "
            f"{rows[tag]['prefix_hit_ratio']} prefill bytes "
            f"{off['prefill_bytes']} -> {on['prefill_bytes']} "
            f"({ratio:.1f}x), streams identical")
    record = {"n_requests": n_req, "templates": len(templates),
              "tenants": len(tenants), "trace_seed": seed + 12,
              "block_size": 16, "modes": rows}
    # persist the receipt per the artifact protocol (merge-on-write,
    # atomic) alongside the BENCH record that also embeds it
    try:
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "tools"))
        from artifact_protocol import artifact, load_prior, write_atomic
        path = artifact("PREFIX_TRACE")
        prior = load_prior(path)
        merged_modes = dict(prior.get("modes", {}))
        merged_modes.update(rows)
        out = dict(record, modes=merged_modes, platform="host")
        write_atomic(path, out)
        log(f"serve: prefix-trace receipt -> {path}")
    except Exception as e:  # noqa: BLE001 — receipt persistence is
        log(f"serve: prefix-trace artifact write skipped: {e}")  # best-effort
    return record


def measure_fused_micro(model, smoke, block_size=16, batch=8, ctx=48,
                        seed=20260804):
    """Fused whole-step vs host-resident decode forward (ISSUE 16): the
    per-decode-step A/B at standard-trace shapes.  Both arms run the
    SAME paged engine config and the SAME prefilled batch; only the
    step dispatch differs — the host arm's per-layer numpy/attention
    interleave (O(layers) host<->device crossings) vs the one jitted
    device program (constant 3).  Two fresh-engine passes per arm, the
    first discarded: it compiles every table-width bucket the
    generation crosses, so the timed pass measures steady-state decode
    and not XLA compiles (min-of-passes would hide, not amortize, a
    mid-pass compile).  Receipt unit: per-TOKEN µs — the acceptance bar
    (fused >= 1.5x) is gated here, where the decode forward is isolated
    from the trace's shared prefill/scheduler/telemetry overhead."""
    import numpy as np
    from tpu_mx.serving.engine import EngineCore

    steps = 24 if smoke else 48
    rng = np.random.RandomState(seed)
    prompts = [list(1 + rng.randint(0, 120, size=ctx))
               for _ in range(batch)]

    class _Req:
        def __init__(self, i, prompt):
            self.id = f"fm{i}"
            self.prompt = prompt

    def arm(fused):
        prior = {k: os.environ.get(k)
                 for k in ("TPUMX_PAGED_DECODE", "TPUMX_FUSED_DECODE")}
        # both arms on the PAGED engine: the fused program needs the
        # device-resident pool, and the host arm must be the same
        # data plane for the A/B to isolate the step dispatch
        os.environ["TPUMX_PAGED_DECODE"] = "1"
        os.environ["TPUMX_FUSED_DECODE"] = fused
        try:
            best = None
            for timed in (False, True):
                eng = EngineCore(model, block_size=block_size,
                                 num_blocks=2048,
                                 warm_batch=batch if fused == "1"
                                 else None)
                items = []
                for i, p in enumerate(prompts):
                    req = _Req(i, p)
                    tok, _ = eng.prefill(req)
                    items.append((req, tok))
                t0 = time.perf_counter()
                for _ in range(steps):
                    results, _ = eng.decode(items)
                    items = [(r, results[r.id][-1]) for r, _ in items]
                dt = time.perf_counter() - t0
                if timed:
                    best = dt / steps
            return best
        finally:
            for k, v in prior.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    host = arm("0")
    fused = arm("1")
    row = {"batch": batch, "context": ctx, "steps": steps,
           "block_size": block_size,
           "host_us_per_step": round(host * 1e6, 1),
           "fused_us_per_step": round(fused * 1e6, 1),
           "host_us_per_tok": round(host * 1e6 / batch, 2),
           "fused_us_per_tok": round(fused * 1e6 / batch, 2),
           "fused_decode_speedup": round(host / fused, 3)}
    log(f"  fused micro: host {row['host_us_per_tok']}us/tok fused "
        f"{row['fused_us_per_tok']}us/tok "
        f"({row['fused_decode_speedup']}x)")
    assert row["fused_decode_speedup"] >= 1.5, (
        f"fused whole-step decode only {row['fused_decode_speedup']}x "
        "over the host-resident forward (acceptance bar 1.5x) — the "
        "one-device-program win regressed")
    return row


def bench_serve(smoke):
    """Serving A/B: continuous batching vs naive static batching over a
    synthetic heavy-traffic trace (ISSUE 8 acceptance), plus the ISSUE 9
    paged-decode receipts: the long-generation per-token-flat probe in
    BOTH decode modes and the decode_attention micro-arm (paged kernel /
    XLA twin vs dense-gather at 3+ context lengths), plus the ISSUE 12
    shared-prefix multi-tenant trace (measure_prefix_trace), plus the
    ISSUE 16 fused-step receipts: the whole-step-program vs
    host-resident-forward decode micro-arm (>= 1.5x bar gated in
    measure_fused_micro) and the fused / fused+speculative trace arms
    with accept-ratio and ITL-delta receipts (knob_arm below).

    Fixed-seed workload: Poisson arrivals (exponential inter-arrival
    gaps in engine-step units), mixed prompt lengths and heavy-tailed
    output lengths — the regime where static batching pads every slot to
    its batch's slowest member while continuous batching refills freed
    slots on the next step.  Both arms run the SAME trace through the
    SAME model/engine/cache config; only the scheduler differs
    (tpu_mx/serving/scheduler.py).  Reported: tokens/s per arm, the
    continuous/static speedup (acceptance bar: >= 2x), p50/p99 TTFT and
    ITL (exact percentiles off the per-request timestamps — the
    telemetry histograms are the production view, bucket-granular), and
    the O(1) receipt: per-token decode latency early vs late in a long
    generation (flat = the paged cache's append cost does not grow with
    generated length at this scale; the dense-gather O(context) term is
    below host overhead here, docs/serving.md)."""
    import numpy as np
    from tpu_mx import serving

    seed = 20260804
    n_req = 16 if smoke else 64
    long_gen = 64 if smoke else 256
    # 16-wide batches: wide enough that the static baseline's
    # pad-to-slowest waste is the realistic one (the wider the batch,
    # the worse the max-over-batch padding — and the better continuous
    # amortizes its fixed per-step cost)
    max_batch = 16
    rng = np.random.RandomState(seed)
    prompts = [list(1 + rng.randint(0, 120, size=int(n)))
               for n in rng.choice([8, 16, 32], size=n_req)]
    # heavy-tailed outputs: the 96-token tail is what static batching
    # pads every batch member to
    outs = [int(v) for v in rng.choice(
        [4, 8, 16, 96], size=n_req, p=[0.30, 0.30, 0.25, 0.15])]
    arrival_step = np.floor(np.cumsum(
        rng.exponential(0.5, size=n_req))).astype(int)
    model = serving.TinyLM(vocab_size=128, embed_dim=64, num_heads=4,
                           num_layers=2, seed=0)

    def pct(vals, q):
        return float(np.percentile(np.asarray(vals, np.float64), q))

    # the live-vs-exact bar (ISSUE 11): the SLO engine's windowed
    # bucket-merge estimates must track the exact offline percentiles —
    # the standing proof the "p99 right now" numbers a dashboard reads
    # can be trusted.  Smoke's 16-request percentiles are rank-noisy
    # (p99 of 16 samples rides the top order statistic), so the bar
    # loosens there; the full leg holds 10%.  The bar is ASSERTED on
    # the continuous arm (the production policy whose TTFT/ITL are the
    # leg's SLO receipts); the static strawman's deltas are recorded
    # but not gated — its batch-drain TTFT clusters are a point-mass
    # distribution where within-bucket interpolation can drift past
    # 10% at p50, a shape the intentionally-bad baseline manufactures.
    slo_rel_tol = 0.15 if smoke else 0.10

    def run_arm(sched_cls, assert_live=True):
        from tpu_mx import telemetry as _tel
        # reset each SLO histogram's window ring with a horizon covering
        # the whole arm, so the live estimate aggregates exactly this
        # arm's samples (cumulative state is untouched)
        _tel.histogram("serve.ttft_seconds").configure_window(600.0, 12)
        _tel.histogram("serve.itl_seconds").configure_window(600.0, 12)
        srv = serving.Server(
            model, scheduler=sched_cls(max_pending=n_req + 1,
                                       max_batch=max_batch,
                                       max_tokens=10 ** 9),
            num_blocks=4096, block_size=16)
        reqs, i, step = [], 0, 0
        # capacity receipts (ISSUE 14): per-step pool-bytes samples (one
        # O(1) counter read per step) for the steady-state figure; the
        # peak comes from the ledger's own high watermark after the arm
        cache = srv.engine.cache
        block_bytes = cache.allocator.ledger.block_bytes
        pool_samples = []
        t0 = time.perf_counter()
        while i < n_req or not srv.scheduler.idle():
            while i < n_req and arrival_step[i] <= step:
                reqs.append(srv.submit(prompts[i], max_new_tokens=outs[i]))
                i += 1
            srv.step()
            step += 1
            pool_samples.append(cache.allocator.used * block_bytes)
        wall = time.perf_counter() - t0
        cap = cache.capacity_stats()
        busy = [s for s in pool_samples if s > 0] or [0]
        pool = {"pool_peak_bytes": int(cap["high_watermark_bytes"]),
                "pool_steady_bytes": int(np.median(busy)),
                "pool_end_fragmentation": round(cap["fragmentation"], 4),
                "pool_block_bytes": int(block_bytes)}
        total = sum(len(r.tokens) for r in reqs)
        assert total == sum(outs), "lost tokens"
        # the live-vs-exact comparison below is only apples-to-apples
        # when no request was requeued: reset_generation clears the
        # token_times the exact list is built from, but the discarded
        # attempt's observations stay in the window ring.  The fixed
        # trace never preempts today — make that a loud precondition
        # rather than a confusing estimator-drift failure if the trace
        # or pool sizing is ever retuned.
        assert not any(r.requeues for r in reqs), (
            "bench arm saw requeues; live-vs-exact gate precondition "
            "broken — retune the trace or pool sizing")
        ttft = [r.ttft * 1e3 for r in reqs]
        itl = [dt * 1e3
               for r in reqs
               for dt in np.diff(r.token_times)] or [0.0]
        exact = {"ttft_ms_p50": round(pct(ttft, 50), 2),
                 "ttft_ms_p99": round(pct(ttft, 99), 2),
                 "itl_ms_p50": round(pct(itl, 50), 3),
                 "itl_ms_p99": round(pct(itl, 99), 3)}
        # The runtime SLO engine's windowed estimates next to the exact
        # offline percentiles.  GATED against the order-statistic
        # BRACKET [percentile(method=lower), percentile(method=higher)]:
        # a p99 of 64 requests rides the gap between the top two order
        # statistics, where the "exact" value is itself a convention
        # (linear/lower/higher disagree by the whole gap) — the bucket
        # estimate is guaranteed within one ~5% bucket of that bracket,
        # so the 10% bar is meaningful rather than rank-lottery.  The
        # linear-convention delta is reported alongside for the receipt.
        live, rel_errs, bracket_errs = {}, {}, {}
        for name, key, samples in (
                ("serve.ttft_seconds", "ttft_ms", ttft),
                ("serve.itl_seconds", "itl_ms", itl)):
            h = _tel.get(name)
            arr = np.asarray(samples, np.float64)
            for q, qtag in ((0.50, "p50"), (0.99, "p99")):
                est = h.window_quantile(q)
                assert est is not None, (name, "empty SLO window")
                est_ms = est * 1e3
                live[f"{key}_{qtag}"] = round(est_ms, 3)
                ex = exact[f"{key}_{qtag}"]
                rel_errs[f"{key}_{qtag}"] = round(
                    abs(est_ms - ex) / max(ex, 1e-9), 4)
                lo = float(np.percentile(arr, q * 100, method="lower"))
                hi = float(np.percentile(arr, q * 100, method="higher"))
                gap = max(lo - est_ms, est_ms - hi, 0.0)
                bracket_errs[f"{key}_{qtag}"] = round(
                    gap / max(ex, 1e-9), 4)
        worst = max(bracket_errs.values())
        assert not assert_live or worst <= slo_rel_tol, (
            f"live SLO estimate drifted {worst:.1%} outside the exact "
            f"order-statistic bracket (bar {slo_rel_tol:.0%}): "
            f"live={live} exact={exact}")
        return dict(exact, tokens_per_sec=round(total / wall, 1),
                    steps=step, wall_s=round(wall, 3),
                    slo_live=live, slo_live_rel_err=rel_errs,
                    slo_live_bracket_err=bracket_errs, **pool)

    # warm both code paths before timing either arm: the first prefill/
    # decode at each shape pays one-time numpy/dispatch setup (measured
    # ~6ms vs ~0.8ms for an L=32 prefill) that would otherwise be billed
    # entirely to whichever arm runs first — same discipline as the
    # fusion leg's dual-arm warmup
    wsrv = serving.Server(model, num_blocks=4096, block_size=16,
                          max_batch=max_batch)
    for p in ([8, 9] * 4, [8, 9] * 8, [8, 9] * 16):
        wsrv.submit(list(p), max_new_tokens=8)
    wsrv.run_until_idle()

    log(f"serve: {n_req}-request Poisson trace, continuous arm...")
    cont = run_arm(serving.ContinuousBatchingScheduler)
    log(f"  continuous: {cont['tokens_per_sec']} tok/s in "
        f"{cont['steps']} steps; ttft p50/p99 "
        f"{cont['ttft_ms_p50']}/{cont['ttft_ms_p99']} ms")
    log(f"  live SLO estimates: {cont['slo_live']} (vs exact-linear "
        f"worst {max(cont['slo_live_rel_err'].values()):.1%}; vs "
        f"order-statistic bracket worst "
        f"{max(cont['slo_live_bracket_err'].values()):.1%}, gated at "
        f"{slo_rel_tol:.0%})")
    log(f"  pool: peak {cont['pool_peak_bytes']} B, steady "
        f"{cont['pool_steady_bytes']} B, end fragmentation "
        f"{cont['pool_end_fragmentation']}")
    log("serve: static arm...")
    stat = run_arm(serving.StaticBatchingScheduler, assert_live=False)
    log(f"  static:     {stat['tokens_per_sec']} tok/s in "
        f"{stat['steps']} steps")
    speedup = cont["tokens_per_sec"] / max(stat["tokens_per_sec"], 1e-9)

    # O(1) receipt, BOTH decode modes: one long generation, ITL early vs
    # late.  The paged append is O(1); the dense arm additionally pays
    # the O(context) host gather, the paged arm only the in-program
    # block walk.  Two probe runs, window MEDIANS, min-of-pairs: a
    # single preempted-by-the-OS token (or one noisy run — or, on the
    # paged arm, a block-bucket jit compile) would otherwise fake or
    # hide growth — same min-of-repeats discipline as the other legs
    def flat_probe(mode):
        prior = os.environ.get("TPUMX_PAGED_DECODE")
        os.environ["TPUMX_PAGED_DECODE"] = mode
        try:
            early = late = None
            for _ in range(2):
                srv = serving.Server(model, num_blocks=4096,
                                     block_size=16)
                lr = srv.submit(prompts[0], max_new_tokens=long_gen)
                srv.run_until_idle()
                d = np.diff(lr.token_times) * 1e6
                e = float(np.median(d[8:40]))
                l = float(np.median(d[-32:]))
                early = e if early is None else min(early, e)
                late = l if late is None else min(late, l)
            return early, late
        finally:
            if prior is None:
                os.environ.pop("TPUMX_PAGED_DECODE", None)
            else:
                os.environ["TPUMX_PAGED_DECODE"] = prior

    early, late = flat_probe("0")
    log(f"serve: dense per-token decode early {early:.0f}us late "
        f"{late:.0f}us (x{late / early:.2f} over {long_gen} tokens)")
    pearly, plate = flat_probe("1")
    log(f"serve: paged per-token decode early {pearly:.0f}us late "
        f"{plate:.0f}us (x{plate / pearly:.2f} over {long_gen} tokens)")

    # decode_attention micro-arm: the data-plane A/B at fixed contexts
    micro = measure_decode_micro((64, 128, 256) if smoke
                                 else (128, 512, 2048))

    # ISSUE 16 receipts.  (1) The fused whole-step micro-arm: the
    # >= 1.5x acceptance bar is gated inside (per-token decode at
    # standard-trace shapes — decode isolated from shared overhead).
    fused_micro = measure_fused_micro(model, smoke, seed=seed)

    # (2) Trace-level arms on the SAME standard trace, paged engine:
    # host-resident forward vs fused program vs fused+speculative.
    # Each arm runs once discarded (compiles every batch/table-width
    # bucket the trace crosses) then once timed — steady-state serving,
    # the regime the tokens/sec receipt describes.  run_arm's live-SLO
    # bracket gate rides along, so the speculative arm's windowed
    # p50/p99 estimates are asserted within the 10% bar of
    # offline-exact (the ISSUE 16 acceptance wording).
    def knob_arm(fused, spec):
        from tpu_mx import telemetry as _tel
        prior = {k: os.environ.get(k)
                 for k in ("TPUMX_PAGED_DECODE", "TPUMX_FUSED_DECODE",
                           "TPUMX_SPECULATIVE")}
        os.environ["TPUMX_PAGED_DECODE"] = "1"
        os.environ["TPUMX_FUSED_DECODE"] = fused
        os.environ["TPUMX_SPECULATIVE"] = spec
        try:
            run_arm(serving.ContinuousBatchingScheduler,
                    assert_live=False)       # discarded: compile pass
            c0 = {n: getattr(_tel.get(n), "value", 0) or 0
                  for n in ("serve.spec_drafted", "serve.spec_accepted")}
            rec = run_arm(serving.ContinuousBatchingScheduler)
            drafted = (getattr(_tel.get("serve.spec_drafted"), "value",
                               0) or 0) - c0["serve.spec_drafted"]
            accepted = (getattr(_tel.get("serve.spec_accepted"), "value",
                                0) or 0) - c0["serve.spec_accepted"]
            rec["spec_drafted"] = int(drafted)
            rec["spec_accepted"] = int(accepted)
            rec["spec_accept_ratio"] = round(accepted / drafted, 4) \
                if drafted else None
            return rec
        finally:
            for k, v in prior.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    host_arm = knob_arm("0", "0")
    fused_arm = knob_arm("1", "0")
    spec_arm = knob_arm("1", "1")
    fused_trace_speedup = round(fused_arm["tokens_per_sec"]
                                / host_arm["tokens_per_sec"], 3)
    log(f"  fused trace arms (paged): host "
        f"{host_arm['tokens_per_sec']} tok/s, fused "
        f"{fused_arm['tokens_per_sec']} tok/s "
        f"({fused_trace_speedup}x end-to-end), fused+spec "
        f"{spec_arm['tokens_per_sec']} tok/s (accept ratio "
        f"{spec_arm['spec_accept_ratio']})")

    # shared-prefix multi-tenant trace (ISSUE 12): hit-ratio +
    # prefill-bytes receipts, sharing on/off, both decode modes,
    # streams gated bit-identical
    prefix = measure_prefix_trace(model, smoke, seed)

    return {
        "metric": "serve_continuous_tokens_per_sec"
        if not smoke else "serve_smoke_tokens_per_sec",
        "value": cont["tokens_per_sec"],
        "unit": "tok/s",
        "vs_baseline": None,
        "speedup_vs_static": round(speedup, 2),
        "continuous": cont,
        "static": stat,
        # live-vs-exact proof (ISSUE 11): the SLO engine's windowed
        # p50/p99 next to the offline-exact percentiles, per arm (the
        # per-metric deltas ride each arm's slo_live_rel_err /
        # slo_live_bracket_err; the assert in run_arm gates the
        # continuous arm's bracket distance — the static strawman's
        # deltas are recorded unasserted, see the comment above run_arm)
        "slo_live_max_rel_err": round(
            max(cont["slo_live_rel_err"].values()), 4),
        "slo_live_max_bracket_err": round(
            max(cont["slo_live_bracket_err"].values()), 4),
        "slo_live_rel_tol": slo_rel_tol,
        # capacity receipts (ISSUE 14), flat so the artifact trajectory
        # diffs them directly: the continuous arm's ledger high
        # watermark, the median nonzero pool residency, and end-state
        # free-list fragmentation — a future capacity regression (a
        # leak, a sharing break, a fragmentation explosion) moves these
        # before it moves tokens/sec
        "pool_peak_bytes": cont["pool_peak_bytes"],
        "pool_steady_bytes": cont["pool_steady_bytes"],
        "pool_end_fragmentation": cont["pool_end_fragmentation"],
        "pool_block_bytes": cont["pool_block_bytes"],
        # O(1)-append receipt.  A cache-less (recompute-the-prefix)
        # decode's per-token cost scales ~linearly with context —
        # "linear_would_be" is the late/early CONTEXT ratio such a decode
        # would show; the small measured residual is the documented
        # dense-gather O(context) fallback term (docs/DIVERGENCES.md
        # #27) riding on an O(1) paged append.
        "per_token_flat": {"early_itl_us": round(early, 1),
                           "late_itl_us": round(late, 1),
                           "late_over_early": round(late / early, 3),
                           "generated": long_gen,
                           "linear_would_be": round(
                               (len(prompts[0]) + long_gen - 16)
                               / (len(prompts[0]) + 24), 1)},
        # the same receipt on the paged decode path (TPUMX_PAGED_DECODE=1,
        # device-resident pool): acceptance bar late/early <= 1.15 over
        # the same >=4x context growth (ISSUE 9)
        "per_token_flat_paged": {"early_itl_us": round(pearly, 1),
                                 "late_itl_us": round(plate, 1),
                                 "late_over_early": round(plate / pearly,
                                                          3)},
        # decode_attention micro-arm: paged (device pool, block-table
        # program) vs dense-gather (host pool) per decode step at fixed
        # contexts — the bar is paged winning at the LONGEST context
        "decode_micro": micro,
        # ISSUE 16 fused-step receipts, flat so the trajectory diffs
        # them: the >= 1.5x bar lives on the DECODE micro-arm (gated in
        # measure_fused_micro — the whole-step program vs the O(layers)
        # host forward, isolated from shared trace overhead); the
        # end-to-end trace ratio is reported honestly unasserted (the
        # tiny model's prefill/scheduler/telemetry share dilutes it)
        "fused_us_per_tok": fused_micro["fused_us_per_tok"],
        "host_resident_us_per_tok": fused_micro["host_us_per_tok"],
        "fused_decode_speedup": fused_micro["fused_decode_speedup"],
        "fused_tokens_per_sec": fused_arm["tokens_per_sec"],
        "host_paged_tokens_per_sec": host_arm["tokens_per_sec"],
        "fused_trace_speedup": fused_trace_speedup,
        # speculative receipts: accept ratio + ITL deltas vs the fused
        # non-speculative arm on the same trace (negative delta = the
        # draft window bought latency); the spec arm's windowed SLO
        # estimates passed run_arm's 10% bracket gate to get here
        "spec_tokens_per_sec": spec_arm["tokens_per_sec"],
        "spec_accept_ratio": spec_arm["spec_accept_ratio"],
        "spec_drafted": spec_arm["spec_drafted"],
        "spec_accepted": spec_arm["spec_accepted"],
        "spec_itl_ms_p50": spec_arm["itl_ms_p50"],
        "spec_itl_ms_p99": spec_arm["itl_ms_p99"],
        "spec_itl_ms_p50_delta": round(
            spec_arm["itl_ms_p50"] - fused_arm["itl_ms_p50"], 3),
        "spec_itl_ms_p99_delta": round(
            spec_arm["itl_ms_p99"] - fused_arm["itl_ms_p99"], 3),
        "fused_micro": fused_micro,
        "fused_arm": fused_arm,
        "host_paged_arm": host_arm,
        "spec_arm": spec_arm,
        # shared-prefix multi-tenant receipts (ISSUE 12): hit ratio,
        # prefill-bytes reduction (bar >= 2x) and stream-equality gate
        # per decode mode; also persisted as PREFIX_TRACE_<round>.json
        "prefix_trace": prefix,
        "n_requests": n_req,
        "max_batch": max_batch,
        "trace_seed": seed,
        "model": {"vocab": model.vocab_size, "embed": model.embed_dim,
                  "heads": model.num_heads, "layers": model.num_layers},
        "platform": "host",   # numpy data plane; the dense-gather decode
                              # fallback is the measured path (#27)
    }


def bench_scaling(smoke):
    """Weak-scaling efficiency over all visible devices (BASELINE metric 3
    'scaling efficiency' — the full 8→256-chip number needs a pod slice;
    this harness measures whatever mesh the process sees, e.g. the
    8-virtual-device CPU mesh in smoke or a real slice when available):
    throughput(dp=N, batch=N·b) / (N · throughput(dp=1, batch=b))."""
    import jax
    import tpu_mx as mx
    from tpu_mx import gluon, nd
    from tpu_mx.gluon.model_zoo import vision
    from tpu_mx.layout import default_layout
    from tpu_mx.parallel import CompiledTrainStep, make_mesh

    n = len(jax.devices())
    if n == 1:
        log("scaling: only one device visible — weak scaling is trivially "
            "1.0; skipping the duplicate run (needs a pod slice)")
        return {"metric": "weak_scaling_efficiency_dp1", "value": 1.0,
                "unit": "ratio", "vs_baseline": 1.0,
                "note": "single device; measure on a multi-chip slice"}
    per_dev_batch, size, iters = (4, 32, 3) if smoke else (64, 96, 10)

    def throughput(ndev):
        batch = per_dev_batch * ndev
        with default_layout("NHWC"):
            net = vision.resnet18_v1(classes=100)
        net.initialize(init="xavier")
        net.finalize_shapes(nd.random.uniform(shape=(2, size, size, 3)))
        x = nd.random.uniform(shape=(batch, size, size, 3))
        mesh = make_mesh({"dp": ndev}, devices=jax.devices()[:ndev]) \
            if ndev > 1 else None
        opt = mx.optimizer.create("sgd", learning_rate=0.1)
        step = CompiledTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                 opt, mesh=mesh)
        y = nd.random.randint(0, 100, (batch,), dtype="float32")
        _timed(lambda: step.step(x, y), _fetch_loss, 1)    # compile
        dt = _timed(lambda: step.step(x, y), _fetch_loss, iters)
        return batch * iters / dt

    t1 = throughput(1)
    tn = throughput(n)
    eff = tn / (n * t1)
    log(f"scaling: dp=1 {t1:.1f} img/s, dp={n} {tn:.1f} img/s, "
        f"efficiency {eff:.3f}")
    return {
        "metric": f"weak_scaling_efficiency_dp{n}",
        "value": round(eff, 4),
        "unit": "ratio",
        "vs_baseline": round(eff, 4),
        "throughput_dp1": round(t1, 2),
        f"throughput_dp{n}": round(tn, 2),
    }


def inner():
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    layout = os.environ.get("BENCH_LAYOUT", "NHWC")
    stem = os.environ.get("BENCH_STEM", "s2d")
    models = [m.strip() for m in
              os.environ.get("BENCH_MODELS",
                             "resnet50,bert,bert512,lstm,ssd").split(",")
              if m.strip()]
    unknown = set(models) - {"resnet50", "bert", "bert512", "scaling",
                             "lstm", "ssd", "fusion", "serve"}
    if unknown or not models:
        raise SystemExit(f"BENCH_MODELS: unknown/empty model list {models}")
    log(f"inner start (smoke={smoke}, layout={layout}, stem={stem}, "
        f"models={models})")

    import jax
    if smoke:
        jax.config.update("jax_platforms", "cpu")

    # persistent compile cache: the big train steps take minutes to
    # compile, and a second run on the same machine should not pay that
    # again (BENCH_COMPILE_CACHE=0 disables for all the on-chip tools)
    if not smoke:
        from tpu_mx.runtime import enable_shared_compilation_cache
        enable_shared_compilation_cache()

    if os.environ.get("BENCH_SIMULATE_WEDGE") == "1":
        # test hook for the outer supervisor's hang handling: behave like
        # a backend stuck in a C call (jax.devices() never returns,
        # 'backend up' never printed) without needing a broken backend
        log("probing backend (jax.devices)...")
        time.sleep(3600)

    log("probing backend (jax.devices)...")
    t0 = time.perf_counter()
    devs = jax.devices()
    log(f"backend up: {devs[0].platform} x{len(devs)} "
        f"in {time.perf_counter() - t0:.1f}s")
    if not smoke and devs[0].platform != "tpu":
        # a measurement path that finds no chip fails; only the explicit
        # BENCH_SMOKE=1 control-flow check runs on the CPU
        raise SystemExit(
            f"bench: platform is {devs[0].platform!r}, not 'tpu' — no "
            "accelerator to measure (BENCH_SMOKE=1 runs the CPU smoke)")

    log("staged warmup: tiny jit matmul...")
    import jax.numpy as jnp
    t0 = time.perf_counter()
    x = jnp.ones((256, 256), jnp.bfloat16)
    jax.jit(lambda a: a @ a)(x).block_until_ready()
    log(f"tiny jit ok in {time.perf_counter() - t0:.1f}s")

    # BENCH_SKIP_FRESH=<seconds>: carry a leg's stored record instead of
    # re-measuring when it is younger than this (0/unset = always measure;
    # smoke never carries), so a retry after a failed attempt spends its
    # time on the missing legs.
    try:
        skip_fresh = 0.0 if smoke else \
            float(os.environ.get("BENCH_SKIP_FRESH", "0") or 0)
    except ValueError:
        skip_fresh = 0.0

    rec = None
    if "resnet50" in models:
        # canonical-iters gate: the CURRENT run's BENCH_ITERS must not
        # lower the bar a stored record has to clear (ADVICE r5 low)
        rec = _fresh_stored(
            PRIMARY_METRIC, skip_fresh,
            min_iters=FULL_RUN_ITERS["resnet50"]) \
            if skip_fresh else None
        if rec is not None:
            log(f"resnet: carrying fresh record from {rec['measured_at']} "
                f"(BENCH_SKIP_FRESH={skip_fresh:.0f}s)")
        else:
            rec = bench_resnet(smoke, layout, stem)
        if rec is not None and not rec.get("carried_fresh"):
            # stream + persist the primary record as soon as it exists: if
            # a later sub-bench dies/hangs and the attempt is killed, the
            # measurement still survives on disk (and the outer's next
            # attempt can narrow BENCH_MODELS from the logs)
            log("resnet record: " + json.dumps(rec))
            persist_lastgood(rec)
    # a requested leg that raises is recorded with its error, the other
    # legs still run and print, and the run then exits non-zero
    failed = []
    bert_rec = scal_rec = None
    try:
        if "bert" in models:
            bert_rec = _fresh_stored(
                "bert_base_train_seqs_per_sec_per_chip", skip_fresh) \
                if skip_fresh else None
            if bert_rec is not None:
                log(f"bert: carrying fresh record from "
                    f"{bert_rec['measured_at']} (BENCH_SKIP_FRESH)")
        if bert_rec is None:
            bert_rec = bench_bert(smoke) if "bert" in models else None
        if bert_rec is not None and not bert_rec.get("carried_fresh"):
            # persist the moment it exists: a later leg running past the
            # attempt timeout kills the process before any end-of-inner
            # persist loop could run
            log("bert record: " + json.dumps(bert_rec))
            persist_lastgood(bert_rec)
    except Exception as e:
        log(f"bert bench failed: {type(e).__name__}: {e}")
        failed.append("bert")
        bert_rec = {"metric": "bert_base_train_seqs_per_sec_per_chip",
                    "value": 0.0, "unit": "seq/s", "vs_baseline": 0.0,
                    "error": f"{type(e).__name__}: {e}"[:300]}
    try:
        scal_rec = bench_scaling(smoke) if "scaling" in models else None
        if scal_rec is not None:
            log("scaling record: " + json.dumps(scal_rec))
            persist_lastgood(scal_rec)
    except Exception as e:
        log(f"scaling bench failed: {type(e).__name__}: {e}")
        failed.append("scaling")
        scal_rec = {"metric": "weak_scaling_efficiency", "value": 0.0,
                    "unit": "ratio", "vs_baseline": 0.0,
                    "error": f"{type(e).__name__}: {e}"[:300]}
    # secondary workloads (BASELINE configs 3 and 5): persisted under
    # their own metric keys and attached to the combined record
    extra_recs = {}
    ssd_backbone = os.environ.get("BENCH_SSD_BACKBONE", "vgg16_reduced")
    extra_metrics = {
        "bert512": "bert_base_seq512_train_seqs_per_sec_per_chip",
        "lstm": "lstm_ptb_train_tokens_per_sec_per_chip",
        "fusion": "imperative_pointwise_fusion_speedup",
        "serve": "serve_continuous_tokens_per_sec",
        "ssd": "ssd512_train_images_per_sec_per_chip"
        if ssd_backbone == "vgg16_reduced"
        else f"ssd512_{ssd_backbone}_train_images_per_sec_per_chip"}

    def _bert512_complete(rec_):
        # a carried bert512 record must be one the Pallas kernel made: an
        # older dense-arm record triggers a re-measure, not a 4h carry
        return rec_.get("attention_path") == "pallas_flash"
    # bert512 deliberately runs LAST: its remat+flash compile is the
    # largest program this file builds — the riskiest leg must not sit
    # in front of cheap ones
    for name, fn_extra in (("fusion", bench_fusion), ("serve", bench_serve),
                           ("lstm", bench_lstm), ("ssd", bench_ssd),
                           ("bert512", bench_bert512)):
        if name not in models:
            continue
        # fusion and serve re-measure in seconds: never carry them
        if skip_fresh and name not in ("fusion", "serve"):
            # lstm/ssd honor BENCH_ITERS too, so they need the same
            # short-timing-record gate as resnet — keyed on the CANONICAL
            # full-run counts, not the env-derived value (ADVICE r5 low);
            # bert/bert512 ladders use fixed iter counts no env can shorten
            leg_min_iters = FULL_RUN_ITERS.get(name)
            cached = _fresh_stored(
                extra_metrics[name], skip_fresh,
                require={"backbone": ssd_backbone} if name == "ssd"
                else None, min_iters=leg_min_iters,
                validate=_bert512_complete if name == "bert512" else None)
            if cached is not None:
                log(f"{name}: carrying fresh record from "
                    f"{cached['measured_at']} (BENCH_SKIP_FRESH)")
                extra_recs[name] = cached
                continue
        try:
            r = fn_extra(smoke)
            log(f"{name} record: " + json.dumps(r))
            persist_lastgood(r)
            extra_recs[name] = r
        except Exception as e:
            log(f"{name} bench failed: {type(e).__name__}: {e}")
            failed.append(name)
            extra_recs[name] = {"metric": extra_metrics[name], "value": 0.0,
                                "unit": "", "vs_baseline": None,
                                "error": f"{type(e).__name__}: {e}"[:300]}
    if rec is None:
        rec = bert_rec or scal_rec or next(iter(extra_recs.values()))
    if bert_rec is not None and rec is not bert_rec:
        rec["bert"] = bert_rec
    if scal_rec is not None and rec is not scal_rec:
        rec["scaling"] = scal_rec
    for name, r in extra_recs.items():
        if rec is not r:
            rec[name] = r
    # no final persist: every successful record was already persisted
    # under its own metric key at measurement time, and re-persisting the
    # combined record here would store the primary key WITH nested
    # sub-records — the store pollution the per-key design exists to
    # avoid (load_lastgood grafts the freshest subs back at read time)
    print(json.dumps(rec), flush=True)
    if failed:
        raise SystemExit(f"requested legs failed: {', '.join(failed)}; "
                         "see stderr")


# ---------------------------------------------------------------------------
# outer: supervisor — no jax import, hard timeouts, retry, partial JSON
# ---------------------------------------------------------------------------
def _run_attempt(timeout, probe_timeout):
    """Run one --inner child.  The child's stderr is teed through so the
    stage log stays visible, and watched for the 'backend up' marker: a
    child whose jax.devices() hangs in a C call is killed after
    probe_timeout instead of burning the full budget.  Returns
    (rc, stdout_lines, err_or_None)."""
    import threading
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--inner"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    backend_up = threading.Event()

    def tee():
        for line in proc.stderr:
            sys.stderr.write(line)
            sys.stderr.flush()
            if "backend up" in line:
                backend_up.set()

    t = threading.Thread(target=tee, daemon=True)
    t.start()
    start = time.monotonic()
    while True:
        rc = proc.poll()
        if rc is not None:
            break
        elapsed = time.monotonic() - start
        if not backend_up.is_set() and elapsed > probe_timeout:
            proc.kill()
            proc.wait()
            return None, [], (f"backend probe did not come up within "
                              f"{probe_timeout:.0f}s")
        if elapsed > timeout:
            proc.kill()
            proc.wait()
            return None, [], f"timed out after {timeout:.0f}s"
        time.sleep(1.0)
    out = (proc.stdout.read() or "").strip().splitlines()
    return rc, out, None


def outer():
    attempts = int(os.environ.get("BENCH_ATTEMPTS", "2"))
    # all workloads compile+run in one attempt; 2400s keeps a
    # slow-but-alive sweep from being killed mid-run, and per-metric
    # persistence means even a killed attempt keeps its finished legs
    timeout = float(os.environ.get("BENCH_TIMEOUT", "2400"))
    probe_timeout = float(os.environ.get("BENCH_PROBE_TIMEOUT", "300"))
    last_err = "unknown"
    for attempt in range(1, attempts + 1):
        log(f"attempt {attempt}/{attempts} (timeout {timeout:.0f}s, "
            f"probe {probe_timeout:.0f}s)")
        rc, out, err = _run_attempt(timeout, probe_timeout)
        if err is None:
            json_lines = [ln for ln in out if ln.startswith("{")]
            if json_lines:
                # what this attempt measured, whether or not every leg
                # succeeded; the child's exit code says which
                print(json_lines[-1], flush=True)
                if rc != 0:
                    log(f"attempt {attempt}: a requested leg failed "
                        f"(rc={rc})")
                return int(rc != 0)
            err = f"rc={rc}, stdout tail: {out[-3:] if out else '(empty)'}"
        last_err = f"attempt {attempt}: {err}"
        if attempt < attempts:
            log(last_err + "; backing off 15s")
            time.sleep(15)
    # every attempt failed: say so and exit non-zero.  No number measured
    # by an earlier run is printed in this run's place.
    log(f"all attempts failed; {last_err}")
    return 1


if __name__ == "__main__":
    if "--inner" in sys.argv:
        inner()
    else:
        sys.exit(outer())
