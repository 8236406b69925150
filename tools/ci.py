"""Run the full test suite and fail LOUDLY if anything is red.

VERDICT r2 weak#2 post-mortem: a round once shipped with a failing test
because the suite stopped being run to completion.  This gate is the
snapshot-time check: `python tools/ci.py` exits nonzero with an
unmissable banner when any test fails, and prints per-tier timing so the
slowest tier stays visible.

Tiers: lint — tools/tpumx_lint.py, the framework-aware two-phase static
analyzer (project index + call graph, then the rule passes) enforcing
the durability/determinism/sync-point/concurrency/telemetry/
hot-path-purity contracts on every line including branches no fault
schedule executes (docs/static_analysis.md; fastest tier, no device,
runs FIRST so a contract violation fails before any test time is spent,
and asserts LINT_BUDGET_SECONDS so the index phase can never silently
blow up tier runtime) — then core
(`-m "not slow"`, <5 min), slow (virtual-mesh parallelism,
full-model layout trains, op-audit sweep, native C++ tier), the example
smokes, chaos (the fault-injection durability tests re-run under a fixed
TPUMX_CHAOS_SEED, docs/robustness.md), native-asan — an
AddressSanitizer build+run of
`native/tpumx_io_test.cpp`, the one multithreaded-shared-state code the
project owns (threads + shared queues; the reference ran ASAN CI,
SURVEY §5.2 / VERDICT r5 missing#6) — then obs: a tiny instrumented
train loop run with TPUMX_TELEMETRY set, whose emitted JSONL must
validate against the telemetry schema AND the stable metric-name catalog
(tools/telemetry_report.py --validate; docs/observability.md — an
accidental metric rename fails this tier), plus the flight-recorder leg:
one chaos-crashed supervised run per failure class (hang, NaN streak,
crash, SIGTERM) must leave a schema-valid black box whose timeline links
injection -> detection -> decision, rendered by tools/blackbox_report.py
under a poisoned jax import — and soak: a supervised
training run under a fixed-seed randomized chaos schedule (hang, NaN
streak, crash-mid-save, torn write) that must finish with a verified
latest checkpoint, a finite loss, and ≥1 recorded restart, rollback and
watchdog fire (tpu_mx/supervisor.py; docs/robustness.md) — and serve: a
fixed-seed request storm against the serving runtime (tpu_mx/serving/,
docs/serving.md) under reject_storm, slow_decode_step and NaN-logits
chaos, which must end with ZERO lost requests, a schema-valid black box
per injected fault (rendered without jax), and catalog-valid serving
metrics.  `--core-only` runs just the first for a quick gate.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

TIERS = [
    ("core", ["tests/", "-m", "not slow",
              "--deselect", "tests/test_examples.py"], None),
    ("slow", ["tests/", "-m", "slow",
              "--deselect", "tests/test_examples.py"], None),
    ("examples", ["tests/test_examples.py"], None),
    # fault-injection tier: the durability/recovery tests re-run with a
    # FIXED chaos seed so every injected crash/tear/backoff byte boundary
    # is reproducible run-to-run (ISSUE 2; the core tier runs these too,
    # but under whatever seed the environment happens to carry)
    ("chaos", ["tests/test_checkpoint.py", "tests/test_elastic.py",
               "tests/test_supervisor.py", "tests/test_fleet.py",
               "-m", "not slow"], {"TPUMX_CHAOS_SEED": "20260804"}),
]


# Hard wall-clock budget for the whole-tree lint (index build included).
# The two-phase analyzer measures ~5 s on this host (ISSUE 10: phase 1
# index + phase 2 passes; was ~3 s lexical-only); the budget is sized to
# ride out CI-host scheduling noise while still failing LOUDLY if the
# index phase ever regresses to per-file re-parsing or superlinear call
# graph work — a silent 10x here would eat the whole tier's cheapness.
LINT_BUDGET_SECONDS = 15.0


def lint_tier():
    """Run the static contract checker over the default tree; any
    unsuppressed, non-baselined finding is a red tier, and so is blowing
    the LINT_BUDGET_SECONDS wall-clock budget (the index phase must stay
    cheap — this tier runs FIRST on every CI invocation).  JSON mode so
    the gate parses the count rather than scraping human output."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    t0 = time.time()
    try:
        run = subprocess.run(
            [sys.executable, os.path.join(repo, "tools", "tpumx_lint.py"),
             "--format", "json"],
            capture_output=True, text=True, timeout=120, cwd=repo)
    except subprocess.TimeoutExpired as e:
        print(f"  lint: timed out: {e}")
        return 1
    elapsed = time.time() - t0
    if elapsed > LINT_BUDGET_SECONDS:
        print(f"  lint: whole-tree run took {elapsed:.1f}s — over the "
              f"{LINT_BUDGET_SECONDS:.0f}s tier budget; the index phase "
              "has regressed (profile tools/lint/index.py before raising "
              "the budget)")
        return 1
    if run.returncode != 0:
        # surface the findings (re-rendered from JSON) in the CI log
        try:
            payload = json.loads(run.stdout)
            for f in payload.get("findings", []):
                print(f"  {f['path']}:{f['line']}: [{f['rule']}] "
                      f"{f['message']}")
            for e in payload.get("errors", []):
                print(f"  lint error: {e}")
        except ValueError:
            print((run.stdout or "") + (run.stderr or ""))
        return run.returncode or 1
    return 0


def native_asan():
    """Compile and run the native io C++ unit tier under
    -fsanitize=address.  Returns a process-style rc (0 = green).  The
    tpumx_io_test source skips its RLIMIT_AS observable under ASAN (the
    shadow reservation needs terabytes of address space); everything
    else — threaded decode, RecordIO scan, det label bounds — runs with
    heap/use-after-free checking armed."""
    if shutil.which("g++") is None:
        print("  native-asan: g++ not found — cannot run the sanitizer "
              "tier (counts as FAIL: the gate must not pass vacuously)")
        return 1
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(repo, "native", "tpumx_io_test.cpp")
    try:
        with tempfile.TemporaryDirectory() as d:
            binary = os.path.join(d, "tpumx_io_test_asan")
            cc = subprocess.run(
                ["g++", "-O1", "-g", "-std=c++17", "-fsanitize=address",
                 src, "-o", binary, "-ljpeg", "-lpthread"],
                capture_output=True, text=True, timeout=300)
            if cc.returncode != 0:
                print(f"  native-asan: compile failed:\n{cc.stderr[-2000:]}")
                return cc.returncode or 1
            run = subprocess.run([binary], capture_output=True, text=True,
                                 timeout=300)
            out = (run.stdout or "") + (run.stderr or "")
            if run.returncode != 0 or "ALL PASS" not in out:
                print(f"  native-asan: run failed (rc={run.returncode}):\n"
                      f"{out[-3000:]}")
                return run.returncode or 1
    except subprocess.TimeoutExpired as e:
        # a wedged compile or a hung test binary (e.g. the threaded-decode
        # deadlock this tier exists to police) must surface as a FAIL row
        # in the results table, not crash the driver
        print(f"  native-asan: timed out: {e}")
        return 1
    return 0


# The obs tier's workload: every instrumented subsystem the acceptance
# criteria name must emit — the compiled train step (recompiles + step
# latency), the fusion engine (flushes), and the durable checkpoint path
# (save latency histogram).  Runs on the CPU backend like the test suite.
OBS_SCRIPT = """
import os
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import tpu_mx as mx
from tpu_mx import nd, engine, elastic, gluon, telemetry
from tpu_mx.gluon import nn
from tpu_mx.parallel import CompiledTrainStep

net = nn.HybridSequential()
net.add(nn.Dense(16, activation="relu"), nn.Dense(2))
net.initialize()
net(nd.ones((1, 4)))
X = np.random.RandomState(0).rand(16, 4).astype(np.float32)
Y = (X.sum(1) > 2).astype(np.float32)
step = CompiledTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                         mx.optimizer.create("sgd", learning_rate=0.1))
for _ in range(4):
    step.step(nd.array(X), nd.array(Y))
step.sync_to_net()
telemetry.flush()  # mid-run append-mode snapshot

x = nd.array(np.ones((8, 8), np.float32))
for _ in range(3):
    with engine.bulk(8):
        nd.tanh(x * 1.5 + 0.5).wait_to_read()

prefix = os.path.join(os.path.dirname(os.environ["TPUMX_TELEMETRY"]), "ck")
elastic.save_checkpoint(prefix, 0, net=net)
assert elastic.latest_checkpoint(prefix)[0] == 0
telemetry.flush(final=True)  # atomic final snapshot
"""

OBS_REQUIRED = ("fusion.flushes", "checkpoint.save_seconds",
                "train_step.recompiles", "train_step.steps")


# The obs tier's flight-recorder leg (ISSUE 7): chaos-crash a supervised
# run once per failure class — hang, NaN streak, crash-mid-save, SIGTERM
# preemption — and assert each leaves a readable, schema-valid black box
# whose timeline links injection -> detection -> supervisor decision by
# shared (epoch, step, generation) trace context.  The rendering check
# (blackbox_report.py must work WITHOUT jax) runs in the driver below.
BLACKBOX_SCRIPT = """
import json
import os
import signal
import time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import tpu_mx as mx
from tpu_mx import checkpoint as ckpt, elastic, gluon, nd, tracing
from tpu_mx.contrib import chaos
from tpu_mx.gluon import nn
from tpu_mx.parallel import CompiledTrainStep
from tpu_mx.supervisor import Supervisor

D = os.environ["TPUMX_BLACKBOX_DIR"]
R = np.random.RandomState(0)
X = R.rand(32, 4).astype(np.float32)
Y = (X.sum(1) > 2).astype(np.float32)
NB, BS = 4, 8


def build():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(2))
    net.initialize()
    net(nd.ones((1, 4)))
    step = CompiledTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                             mx.optimizer.create("sgd", learning_rate=0.05))
    return net, step


def supervised(tag, fault, **sup_kw):
    tracing.reset()
    prefix = os.path.join(D, tag)
    net, step = build()

    def save_fn(e):
        step.sync_to_net()
        elastic.save_checkpoint(prefix, e, net=net)

    def restore_fn():
        e = elastic.auto_resume(prefix, net=net)
        step.sync_from_net()
        return e

    sup = Supervisor(save_fn=save_fn, restore_fn=restore_fn,
                     blackbox=prefix, backoff=0.05, cooldown=0.0, **sup_kw)

    def epoch_fn(epoch):
        for i in range(NB):
            xb, yb = X[i * BS:(i + 1) * BS], Y[i * BS:(i + 1) * BS]
            sup.step(lambda: step.step(nd.array(xb), nd.array(yb)))

    with chaos.enable(**fault):
        res = sup.run(epoch_fn, 0, 3)
    assert res.ok, (tag, res.as_dict())
    path = tracing.blackbox_path(prefix)
    assert os.path.exists(path), (tag, "no black box dumped")
    box = json.load(open(path))
    tracing.validate_blackbox(box)
    return box


def chain(box, kind, *decisions):
    # injection -> detection -> decision, joined on (epoch, generation):
    # a NaN streak's divergence is declared a step after the first
    # poisoned loss, so the step is recorded but not part of the join
    evs = box["events"]
    inj = [e for e in evs if e["event"] == "chaos.inject"
           and e["data"]["kind"] == kind]
    assert inj, (kind, [e["event"] for e in evs])
    key = (inj[0]["epoch"], inj[0]["generation"])
    assert inj[0]["step"] is not None, inj[0]
    got = [e["event"] for e in evs
           if (e["epoch"], e["generation"]) == key]
    for want in decisions:
        assert want in got, (kind, want, got)


box = supervised("bb-hang", dict(hang_step=6, hang_seconds=30, seed=1),
                 deadline=2.0, compile_grace=60.0)
chain(box, "hang", "supervisor.watchdog_fire", "supervisor.classify",
      "supervisor.restart")

box = supervised("bb-nan", dict(nan_after=NB + 2, nan_streak=2, seed=1),
                 skip_limit=1)
chain(box, "nan", "supervisor.sentinel_skip", "supervisor.classify",
      "supervisor.rollback")

box = supervised("bb-crash",
                 dict(crash_after_bytes=200, match=".params", seed=1))
chain(box, "crash", "supervisor.classify", "supervisor.restart")

# SIGTERM preemption: the handler's emergency save + black box, no exit
tracing.reset()
prefix = os.path.join(D, "bb-sigterm")
net, step = build()


def emergency():
    step.sync_to_net()
    elastic.save_checkpoint(prefix, 0, net=net)


handle = ckpt.preemption_handler(emergency, exit=False,
                                 blackbox_prefix=prefix)
for i in range(2):
    step.step(nd.array(X[:BS]), nd.array(Y[:BS]))
os.kill(os.getpid(), signal.SIGTERM)
for _ in range(100):  # delivery is prompt but asynchronous
    if handle.triggered:
        break
    time.sleep(0.05)
assert handle.triggered and handle.save_ok, (handle.triggered,
                                             handle.save_ok)
box = json.load(open(tracing.blackbox_path(prefix)))
tracing.validate_blackbox(box)
names = [e["event"] for e in box["events"]]
assert "checkpoint.preemption" in names, names
assert "checkpoint.save" in names, names
print("BLACKBOX OK", flush=True)
"""

# what the rendered report must contain per failure-class box: the
# injection, the detection and the matching decision in prose
BLACKBOX_EXPECT = {
    "bb-hang": ("chaos hang injected", "watchdog fired", "restart #"),
    "bb-nan": ("chaos nan injected", "sentinel skipped batch",
               "rollback #"),
    "bb-crash": ("chaos crash injected", "classified transient",
                 "restart #"),
    "bb-sigterm": ("checkpoint.preemption", "save_ok=True"),
}


# The soak tier's workload: a REAL supervised training run under a
# fixed-seed randomized fault schedule — hang, NaN streak, crash-mid-save,
# torn write — that must end with a verified latest checkpoint, a finite
# loss, and every recovery path provably taken (ISSUE 4 acceptance) —
# followed by the deterministic-resume leg (ISSUE 5 acceptance): a
# capsule-enabled run chaos-crashed mid-epoch must reproduce the
# uninterrupted run's per-step loss trajectory and final weights EXACTLY,
# with a zero resume_step_gap.
# The schedule is derived from TPUMX_CHAOS_SEED so a red run reproduces.
SOAK_SCRIPT = """
import contextlib
import math
import os
import random
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import tpu_mx as mx
from tpu_mx import checkpoint as ckpt, elastic, gluon, nd, telemetry
from tpu_mx.contrib import chaos
from tpu_mx.gluon import nn
from tpu_mx.parallel import CompiledTrainStep
from tpu_mx.supervisor import Supervisor

SEED = int(os.environ.get("TPUMX_CHAOS_SEED", "0"))
rng = random.Random(SEED)
prefix = os.path.join(os.path.dirname(os.environ["TPUMX_TELEMETRY"]),
                      "soak")

net = nn.HybridSequential()
net.add(nn.Dense(8, activation="relu"), nn.Dense(2))
net.initialize()
net(nd.ones((1, 4)))
step = CompiledTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                         mx.optimizer.create("sgd", learning_rate=0.05))
R = np.random.RandomState(SEED)
X = R.rand(64, 4).astype(np.float32)
Y = (X.sum(1) > 2).astype(np.float32)
NB, BS, EPOCHS = 4, 16, 10

# the randomized fault schedule (all positions seed-derived).  Ranges
# keep the script's own assertions satisfiable for EVERY seed: the torn
# epoch stays below EPOCHS-1 (the final epoch must verify as latest) and
# the NaN streak starts early enough to fit inside its epoch (a streak
# split across the chaos scope would disarm after one skip — no rollback)
hang_epoch = rng.randint(2, 3)
nan_epoch = rng.randint(4, 5)
crash_epoch = rng.randint(6, 7)
torn_epoch = rng.randint(8, EPOCHS - 2)
EPOCH_FAULTS = {
    hang_epoch: dict(hang_step=rng.randint(1, NB), seed=SEED),
    nan_epoch: dict(nan_after=rng.randint(1, NB - 1), nan_streak=2,
                    seed=SEED),
}
SAVE_FAULTS = {
    crash_epoch: dict(crash_after_bytes=200, match=".params", seed=SEED),
    torn_epoch: dict(torn_write=120, match=".params", seed=SEED),
}
print("SOAK schedule: hang@%d nan@%d crash@%d torn@%d" %
      (hang_epoch, nan_epoch, crash_epoch, torn_epoch), flush=True)


def save_fn(epoch):
    faults = SAVE_FAULTS.pop(epoch, None)  # pop: the retried save is clean
    with (chaos.enable(**faults) if faults else contextlib.nullcontext()):
        step.sync_to_net()
        elastic.save_checkpoint(prefix, epoch, net=net)


def restore_fn():
    start = elastic.auto_resume(prefix, net=net)
    step.sync_from_net()
    return start


sup = Supervisor(save_fn=save_fn, restore_fn=restore_fn,
                 deadline=20.0, compile_grace=60.0, max_restarts=5,
                 max_rollbacks=3, skip_limit=1, backoff=0.05,
                 cooldown=0.0, seed=SEED, blackbox=prefix)


def epoch_fn(epoch):
    faults = EPOCH_FAULTS.pop(epoch, None)
    with (chaos.enable(**faults) if faults else contextlib.nullcontext()):
        for i in range(NB):
            xb, yb = X[i * BS:(i + 1) * BS], Y[i * BS:(i + 1) * BS]
            sup.step(lambda: step.step(nd.array(xb), nd.array(yb)))


res = sup.run(epoch_fn, begin_epoch=0, num_epoch=EPOCHS)
print("SOAK result:", res.as_dict(), flush=True)
assert res.status == "completed", res.as_dict()
# ≥1 recorded restart, rollback, watchdog fire, skipped batch (acceptance)
assert res.restarts >= 2, res.as_dict()       # hang + crash-mid-save
assert res.rollbacks >= 1, res.as_dict()      # NaN streak past the budget
assert res.watchdog_fires >= 1, res.as_dict()
assert res.batches_skipped >= 1, res.as_dict()
# finite final loss, verified latest checkpoint
assert res.final_loss is not None and math.isfinite(res.final_loss)
epoch, path = elastic.latest_checkpoint(prefix)
assert epoch == EPOCHS - 1, (epoch, path)
assert ckpt.verify_checkpoint(prefix, epoch)[0] == "verified"
# the torn epoch is on disk but detectably corrupt (manifest caught it)
assert ckpt.verify_checkpoint(prefix, torn_epoch)[0] == "corrupt"
assert ckpt.newest_verified_epoch(prefix) == EPOCHS - 1

# ---- flight-recorder leg (ISSUE 7 acceptance): every injected fault is
# linked to its detection and the supervisor's decision by shared
# (epoch, generation) trace context, in a schema-valid black box.  The
# per-recovery boxes were dumped during the run; this final audit dump
# captures the WHOLE timeline (the ring still holds it) including the
# torn write, whose detection only happens at the verify above.
import json as _json
from tpu_mx import tracing
bb_path = tracing.dump_blackbox(prefix, reason="soak post-run audit")
bb = _json.load(open(bb_path))
tracing.validate_blackbox(bb)
EVS = bb["events"]


def correlated(kind, *names):
    inj = [e for e in EVS if e["event"] == "chaos.inject"
           and e["data"]["kind"] == kind]
    assert inj, (kind, sorted({e["event"] for e in EVS}))
    key = (inj[0]["epoch"], inj[0]["generation"])
    got = [e["event"] for e in EVS if (e["epoch"], e["generation"]) == key]
    for n in names:
        assert n in got, (kind, n, got)


correlated("hang", "supervisor.watchdog_fire", "supervisor.classify",
           "supervisor.restart")
correlated("nan", "supervisor.sentinel_skip", "supervisor.classify",
           "supervisor.rollback")
correlated("crash", "supervisor.classify", "supervisor.restart")
# torn write: no exception at injection time — the manifest verification
# above is the detection, and both are on the same timeline
assert any(e["event"] == "chaos.inject"
           and e["data"]["kind"] == "torn_write" for e in EVS)
assert any(e["event"] == "checkpoint.verify"
           and e["data"].get("status") == "corrupt" for e in EVS)
assert telemetry.get("tracing.blackbox_dumps").value >= 3  # per recovery
print("SOAK blackbox leg OK", flush=True)

# ---- deterministic-resume leg (ISSUE 5 acceptance): a chaos-crashed-
# then-capsule-resumed run must reproduce the uninterrupted fixed-seed
# run's per-step loss trajectory and final weights EXACTLY — not just
# "finite and completed".  Capsules restore the RNG streams, the data
# iterator's shuffle/cursor and the mid-epoch train state, so the
# trajectories are compared with ==, no tolerance.
from tpu_mx import resume as tres
from tpu_mx import random as trandom


def det_build(seed):
    trandom.seed(seed)
    n = nn.HybridSequential()
    n.add(nn.Dense(8, activation="relu"), nn.Dense(2))
    n.initialize()
    n(nd.ones((1, 4)))
    s = CompiledTrainStep(n, gluon.loss.SoftmaxCrossEntropyLoss(),
                          mx.optimizer.create("sgd", learning_rate=0.05))
    it = mx.io.NDArrayIter(X, Y, batch_size=BS, shuffle=True,
                           last_batch_handle="discard", seed=seed)
    return n, s, it


def det_run(tag, crash_at=None):
    pfx = prefix + "-det-" + tag
    net, step, it = det_build(123)
    mgr = tres.CapsuleManager(pfx, iters=[it], state=step, interval=1)
    det_sup = Supervisor(capsule=mgr, backoff=0.01, seed=0)

    def det_save(e):
        step.sync_to_net()
        elastic.save_checkpoint(pfx, e, net=net, capsule=mgr)

    def det_restore():
        e = elastic.auto_resume(pfx, net=net)
        step.sync_from_net()
        return e

    det_sup.save_fn, det_sup.restore_fn = det_save, det_restore
    losses = {}

    def det_epoch(epoch):
        if not det_sup.resume_step(epoch):
            it.reset()
        for batch in it:
            def one(b=batch):
                v = float(step.step(b.data[0], b.label[0]).asnumpy().mean())
                losses[(epoch, det_sup.step_in_epoch + 1)] = v
                return v
            det_sup.step(one)

    ctx = chaos.enable(crash_at_step=crash_at, seed=SEED) if crash_at \
        else contextlib.nullcontext()
    with ctx:
        r = det_sup.run(det_epoch, 0, 3)
    assert r.ok, r.as_dict()
    step.sync_to_net()
    return losses, [p.data().asnumpy() for p in
                    net.collect_params().values()], r


det_losses_a, det_w_a, _ = det_run("a")
det_losses_b, det_w_b, det_res_b = det_run("b", crash_at=rng.randint(5, 10))
assert det_res_b.restarts >= 1, det_res_b.as_dict()
assert det_losses_a == det_losses_b, (det_losses_a, det_losses_b)
for wa, wb in zip(det_w_a, det_w_b):
    assert np.array_equal(wa, wb), "post-recovery weights diverged"
# the soak tier FAILS if the resume left a replay gap (must be 0 under
# capsules — an exact-batch or exact-replay resume, never lost batches)
assert telemetry.get("resume.resume_step_gap").value == 0
print("SOAK deterministic-resume leg OK", flush=True)
telemetry.flush(final=True)
print("SOAK OK", flush=True)
"""

# "supervisor" / "resume" are telemetry_report require-presets: the
# supervisor recovery counters (restarts/rollbacks/watchdog_fires/
# batches_skipped — the degraded gauge is rightly 0 on a healthy soak)
# and the deterministic-resume counters (capsules written + a restore
# that actually went through the capsule path; the resume_step_gap
# gauge must be 0 and is asserted inside the soak script itself)
SOAK_REQUIRED = ("supervisor", "resume", "chaos.injections",
                 "checkpoint.corrupt_detected", "train_step.steps",
                 "tracing.blackbox_dumps")


# The soak tier's membership-churn leg (ISSUE 17): a two-member fleet in
# one process (the single-controller convention — member 0 drives the
# model on the full global batch; member 1 is a logical peer kept alive
# by a heartbeat thread, exactly what a real worker's beat loop does).
# The seeded schedule partitions member 1 (chaos `partition_worker`:
# beats suppressed, process alive) so its lease expires mid-epoch — the
# supervisor classifies the resulting MembershipChange as `membership`,
# reshards dp=2 -> dp=1 from the last verified manifest + capsule, and
# later admits the healed member back at the next epoch (reshard up).
# A second window SIGTERMs the training rank mid-step (chaos
# `preempt_worker_at_step`) — classified and survived, not fatal.
# Hard assertions: the churn run consumes the IDENTICAL global
# sample-id ledger as the uninterrupted oracle (zero skipped, zero
# duplicated), losses/weights match to float-reduction tolerance
# (dp=1 and dp=2 reassociate the batch sum — bitwise equality across
# the world change is impossible BY MEASUREMENT, ~1e-9), the no-train
# reshard round-trip dp=2 -> dp=1 -> dp=2 is BIT-exact, and the run
# ends completed with a verified latest epoch.
FLEET_SCRIPT = """
import math
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
import random
import signal
import threading
import time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import tpu_mx as mx
from tpu_mx import checkpoint as ckpt, elastic, gluon, nd, telemetry
from tpu_mx import random as trandom
from tpu_mx import resume as tres
from tpu_mx.contrib import chaos
from tpu_mx.gluon import nn
from tpu_mx.parallel import CompiledTrainStep, make_mesh
from tpu_mx.parallel.fleet import Fleet
from tpu_mx.supervisor import Supervisor

assert jax.device_count() >= 2, jax.devices()
SEED = int(os.environ.get("TPUMX_CHAOS_SEED", "0"))
rng = random.Random(SEED)
root = os.path.dirname(os.environ["TPUMX_TELEMETRY"])
prefix = os.path.join(root, "fleet-ck")

R = np.random.RandomState(SEED)
X = R.rand(64, 4).astype(np.float32)
Y = (X.sum(1) > 2).astype(np.float32)
BS, NB, EPOCHS, LEASE = 16, 4, 8, 1.0

# seeded churn schedule: partition early (heal = next epoch), preempt
# well after the rejoin so the chaos windows never overlap
PART_EPOCH, PART_STEP = rng.randint(1, 2), rng.randint(1, NB)
PREEMPT_EPOCH, PREEMPT_STEP = rng.randint(4, 6), rng.randint(1, NB)
print("FLEET schedule: partition@(%d,%d) preempt@(%d,%d)" %
      (PART_EPOCH, PART_STEP, PREEMPT_EPOCH, PREEMPT_STEP), flush=True)


# chaos preempts with a real SIGTERM; this harness must survive it the
# way a dying rank's peers do — as a WorkerFailure out of the step
def _on_term(sig, frame):
    raise elastic.WorkerFailure("preempted: SIGTERM mid-step")


signal.signal(signal.SIGTERM, _on_term)


def build_net():
    trandom.seed(123)
    n = nn.HybridSequential(prefix="fl_")
    n.add(nn.Dense(8, in_units=4, activation="relu", prefix="fc1_"))
    n.add(nn.Dense(2, in_units=8, prefix="fc2_"))
    n.initialize()
    n(nd.ones((1, 4)))
    return n


def make_step(world):
    mesh = make_mesh({"dp": 2}) if world >= 2 else \\
        make_mesh({"dp": 1}, devices=jax.devices()[:1])
    n = build_net()
    s = CompiledTrainStep(n, gluon.loss.SoftmaxCrossEntropyLoss(),
                          mx.optimizer.create("sgd", learning_rate=0.05),
                          mesh=mesh)
    return n, s


def make_iter():
    return mx.io.NDArrayIter(X, Y, batch_size=BS, shuffle=True,
                             last_batch_handle="discard", seed=123)


def weights(n):
    return [p.data().asnumpy() for p in n.collect_params().values()]


# ---- oracle: the uninterrupted fixed-seed run, dp=2 throughout ----
o_net, o_step = make_step(2)
o_it = make_iter()
o_ledger, o_losses = {}, {}
for epoch in range(EPOCHS):
    o_it.reset()
    for i, batch in enumerate(o_it):
        o_ledger[(epoch, i + 1)] = tuple(
            int(v) for v in o_it.global_batch_ids())
        o_losses[(epoch, i + 1)] = float(
            o_step.step(batch.data[0], batch.label[0]).asnumpy().mean())
o_step.sync_to_net()
o_w = weights(o_net)
print("FLEET oracle done", flush=True)

# ---- the churn run ----
f0 = Fleet(os.path.join(root, "fleet"), member=0, controller=True,
           lease=LEASE)
f0.advance(world=[0, 1], reason="launch")
f0.join()
f1 = Fleet(os.path.join(root, "fleet"), member=1, lease=LEASE)
f1.join()

stop_beats = threading.Event()


def beat_loop():  # member 1's liveness, decoupled from the train loop
    while not stop_beats.is_set():
        f1.heartbeat()
        time.sleep(LEASE / 10.0)


threading.Thread(target=beat_loop, daemon=True).start()

H = {}
H["net"], H["step"] = make_step(2)
it = make_iter()
mgr = tres.CapsuleManager(prefix, iters=[it], state=H["step"], interval=1,
                          fleet=f0)


def save_fn(epoch):
    H["step"].sync_to_net()
    elastic.save_checkpoint(prefix, epoch, net=H["net"], capsule=mgr)


def restore_fn():
    # the membership branch acks the new epoch BEFORE restoring, so the
    # adopted world size here is the post-churn one — rebuild the step
    # on the new mesh and point the capsule at it (load_state_dict then
    # re-places every leaf: the reshard seam)
    H["net"], H["step"] = make_step(max(1, f0.acked_world_size))
    mgr.state = H["step"]
    e = elastic.auto_resume(prefix, net=H["net"])
    H["step"].sync_from_net()
    return e


sup = Supervisor(save_fn=save_fn, restore_fn=restore_fn, capsule=mgr,
                 fleet=f0, deadline=30.0, compile_grace=60.0,
                 max_restarts=4, backoff=0.05, cooldown=0.0, seed=SEED,
                 blackbox=prefix)

ledger, losses = {}, {}
open_ctx, fired = [], set()


def epoch_fn(epoch):
    if epoch == PART_EPOCH + 1 and "part" in fired and "heal" not in fired:
        fired.add("heal")  # partition heals: member 1's beats resume
        open_ctx.pop().__exit__(None, None, None)
        assert f0.wait_member(1, timeout=10), "healed member never beat"
    if not sup.resume_step(epoch):
        it.reset()
    for batch in it:
        nxt = sup.step_in_epoch + 1
        if epoch == PART_EPOCH and nxt >= PART_STEP and "part" not in fired:
            fired.add("part")
            c = chaos.enable(partition_worker=1, seed=SEED)
            c.__enter__()
            open_ctx.append(c)
            time.sleep(LEASE * 1.5)  # outlive member 1's lease
        if epoch == PREEMPT_EPOCH and nxt >= PREEMPT_STEP \\
                and "pre" not in fired:
            fired.add("pre")
            c = chaos.enable(preempt_worker_at_step=1, preempt_rank=0,
                             seed=SEED)
            c.__enter__()
            open_ctx.append(c)

        def one(b=batch):
            v = float(H["step"].step(b.data[0], b.label[0])
                      .asnumpy().mean())
            k = (epoch, sup.step_in_epoch + 1)
            ledger[k] = tuple(int(x) for x in it.global_batch_ids())
            losses[k] = v
            return v

        sup.step(one)


try:
    res = sup.run(epoch_fn, begin_epoch=0, num_epoch=EPOCHS)
finally:
    stop_beats.set()
    while open_ctx:
        open_ctx.pop().__exit__(None, None, None)

print("FLEET result:", res.as_dict(), flush=True)
assert res.status == "completed", res.as_dict()
assert fired >= {"part", "heal", "pre"}, fired
assert res.restarts >= 1, res.as_dict()  # the preempt (not membership)

# exact replay: the churn run consumed the IDENTICAL batch sequence
assert set(ledger) == set(o_ledger), (len(ledger), len(o_ledger))
assert ledger == o_ledger, "sample-id ledger diverged from the oracle"
for epoch in range(EPOCHS):  # zero skipped, zero duplicated
    ids = sorted(i for (e, s), v in ledger.items() if e == epoch
                 for i in v)
    assert ids == list(range(len(X))), (epoch, ids[:8])

# loss-curve/weight parity: gated numerically — dp=1 and dp=2 psums
# reassociate the batch sum (measured ~1e-9), bitwise across the world
# change is not a sound gate
for k in sorted(o_losses):
    assert math.isclose(losses[k], o_losses[k],
                        rel_tol=1e-4, abs_tol=1e-6), \\
        (k, losses[k], o_losses[k])
H["step"].sync_to_net()
for a, b in zip(o_w, weights(H["net"])):
    assert np.allclose(a, b, rtol=1e-5, atol=1e-6), "weights diverged"

# membership accounting: >=2 reshards (down + up), >=1 rejoin, the lost
# worker counted, and the epoch gauge moved past the launch generation
assert telemetry.get("fleet.reshards").value >= 2
assert telemetry.get("fleet.rejoins").value >= 1
assert telemetry.get("fleet.lost_workers").value >= 1
assert telemetry.get("fleet.membership_epoch").value >= 3

# completed with a verified latest epoch
final_epoch, _path = elastic.latest_checkpoint(prefix)
assert final_epoch == EPOCHS - 1, final_epoch
assert ckpt.verify_checkpoint(prefix, final_epoch)[0] == "verified"

# the reshard seam itself is lossless: a no-train round trip back onto
# the original mesh is BIT-exact
def flat(sd, pre="", out=None):
    out = {} if out is None else out
    if isinstance(sd, dict):
        for k2 in sorted(sd):
            flat(sd[k2], pre + "/" + str(k2), out)
    else:
        try:
            out[pre] = np.asarray(sd)
        except Exception:
            pass
    return out


sd_f = H["step"].state_dict()
_n1, s1 = make_step(1)
s1.load_state_dict(sd_f)
_n2, s2 = make_step(2)
s2.load_state_dict(s1.state_dict())
fa, fb = flat(sd_f), flat(s2.state_dict())
assert set(fa) == set(fb)
for k in fa:
    assert np.array_equal(fa[k], fb[k]), k
print("FLEET reshard round-trip bit-exact OK", flush=True)
telemetry.flush(final=True)
print("FLEET OK", flush=True)
"""

# "fleet" is the telemetry_report require-preset (membership_epoch +
# reshards + rejoins all nonzero); resume/chaos/train_step gate that the
# churn actually rode the capsule path under injected faults
FLEET_REQUIRED = ("fleet", "resume", "chaos.injections",
                  "train_step.steps")


# The soak tier's STRAGGLER sub-leg (ISSUE 18): a real 2-worker fleet
# under `tools/launch.py --supervise` with the `slow_worker_rank` chaos
# knob delaying every rank-1 step inside the measured data_wait window,
# run through BOTH churn shapes (mid-step SIGTERM preempt -> evict ->
# restart -> rejoin, and partition -> lease expiry -> heal -> rejoin).
# Each worker trains a tiny real model through CompiledTrainStep — the
# phase events the cross-rank attribution correlates come from the
# production train-step path, not a simulation.  Gates: the controller's
# fleet.step_skew_seconds gauge moved, the windowed detector names the
# injected rank with the injected dominant phase in the fleet black box,
# `fleet_report --validate` passes on that box under POISONED jax (the
# report tools never boot the accelerator stack), and
# `telemetry_report --merge --require fleet_obs` holds the aggregation
# identity across the controller + per-worker registries.
STRAGGLER_WORKER = """
import os
import sys
import threading
import time

sys.path.insert(0, os.environ["TPUMX_REPO"])
member = int(os.environ["TPUMX_FLEET_MEMBER"])
# per-rank telemetry sink: workers inherit the controller's env, and a
# shared JSONL would interleave two processes' appends
os.environ["TPUMX_TELEMETRY"] = os.path.join(
    os.environ["TPUMX_CI_DIR"], "worker-%d.jsonl" % member)
# the CPU backend cannot run cross-process collectives: drop the
# coordinator env before the tpu_mx import boots jax.distributed (also
# keeps XLA's preemption notifier off the chaos SIGTERM)
for k in ("TPUMX_COORDINATOR", "TPUMX_NUM_PROC", "TPUMX_PROC_ID"):
    os.environ.pop(k, None)

import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import tpu_mx as mx
from tpu_mx import gluon, nd, telemetry, tracing
from tpu_mx import random as trandom
from tpu_mx.contrib import chaos
from tpu_mx.elastic import WorkerFailure
from tpu_mx.gluon import nn
from tpu_mx.parallel import CompiledTrainStep
from tpu_mx.parallel.fleet import Fleet, MembershipChange

LEASE = float(os.environ.get("TPUMX_FLEET_LEASE", "2.0"))
if os.environ.get("TPUMX_CI_SCENARIO") == "partition" and member == 1:
    # armed programmatically, NOT via TPUMX_CHAOS: the partition must
    # HEAL mid-run, which a parse-once env knob cannot express
    cfg = chaos._Config(partition_worker=1, slow_worker_rank=1,
                        slow_worker_seconds=0.2)
    chaos._config = cfg

    def _heal():
        with cfg.lock:
            cfg.partition_worker = None
    # heal just past the lease horizon: ONE eviction cycle (expire ->
    # evict -> heal -> rejoin), not a churn storm
    threading.Timer(LEASE * 1.2, _heal).start()

f = Fleet.from_env()
f.join()
f.await_admission(timeout=60)

trandom.seed(7)
net = nn.HybridSequential(prefix="sw_")
net.add(nn.Dense(4, in_units=4, activation="relu", prefix="fc1_"))
net.add(nn.Dense(2, in_units=4, prefix="fc2_"))
net.initialize()
net(nd.ones((1, 4)))
step = CompiledTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                         mx.optimizer.create("sgd", learning_rate=0.05))
R = np.random.RandomState(3)
X = R.rand(8, 4).astype(np.float32)
Y = (X.sum(1) > 2).astype(np.float32)

STEPS = int(os.environ.get("TPUMX_CI_STEPS", "24"))
s = 0
deadline = time.monotonic() + 120
while s < STEPS and time.monotonic() < deadline:
    try:
        f.on_step()
    except MembershipChange:
        try:
            f.ack()
            f.shard()
        except WorkerFailure:
            # evicted (lease expired while partitioned): rejoin at the
            # next epoch instead of dying
            f.join()
            f.await_admission(timeout=60)
        continue
    s += 1
    # both ranks walk the SAME (epoch, step) grid — the cross-rank
    # correlation joins on these keys (+ the membership generation the
    # fleet stamps into the trace context).  The baseline pace keeps
    # the ranks within the same generation window long enough to
    # correlate: an unpaced fast rank would finish the whole grid
    # before the chaos-slowed one left step 2, and a step only ONE rank
    # observed has no skew
    tracing.set_context(epoch=s // 8, step=s % 8)
    step.step(nd.array(X), nd.array(Y))
    time.sleep(0.15)
telemetry.flush(final=True)
f.leave()
print("WORKER DONE", member, flush=True)
"""


# The soak tier's SDC sub-leg (ISSUE 20): a real 3-worker fleet of
# IDENTICAL replicas (same init seed, same data grid — cross-replica
# fingerprints must agree bit-exactly) under `tools/launch.py
# --supervise`, with the `bitflip_param_at_step` chaos knob flipping one
# mantissa bit in rank 1's committed parameters.  The next fingerprint
# vote must name rank 1 as the minority: rank 1 quarantines itself and
# dies, the launcher refuses the restart (permanent, unlike a transient
# eviction), and the survivors roll back to the last VERIFIED weights
# and replay.  Gates: the quarantine record exists and rank 1 was never
# respawned, the survivors' final weights are bit-equal to an
# uninjected fixed-seed run, the fleet black box carries a schema-valid
# corruption verdict readable under POISONED jax, and the merged
# telemetry passes `--require integrity`.
#
# TPUMX_CI_BASELINE=1 runs the SAME training loop with no fleet, no
# integrity plane and no chaos — the bit-equality oracle.  Keeping both
# arms in one script is load-bearing: the comparison only proves the
# rollback path exact if the two arms share every line of the loop.
SDC_WORKER = """
import os
import sys
import time

sys.path.insert(0, os.environ["TPUMX_REPO"])
baseline = os.environ.get("TPUMX_CI_BASELINE") == "1"
member = int(os.environ.get("TPUMX_FLEET_MEMBER", "-1"))
if not baseline:
    # per-rank telemetry sink: workers inherit the controller's env, and
    # a shared JSONL would interleave the processes' appends
    os.environ["TPUMX_TELEMETRY"] = os.path.join(
        os.environ["TPUMX_CI_DIR"], "worker-%d.jsonl" % member)
for k in ("TPUMX_COORDINATOR", "TPUMX_NUM_PROC", "TPUMX_PROC_ID"):
    os.environ.pop(k, None)

import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import tpu_mx as mx
from tpu_mx import gluon, nd, telemetry
from tpu_mx import random as trandom
from tpu_mx.gluon import nn
from tpu_mx.parallel import CompiledTrainStep


def build():
    # identical replicas: every rank (and the uninjected baseline) seeds
    # the SAME init and walks the SAME fixed batch
    trandom.seed(11)
    np.random.seed(11)
    net = nn.HybridSequential(prefix="sdc_")
    net.add(nn.Dense(4, in_units=4, activation="relu", prefix="fc1_"))
    net.add(nn.Dense(2, in_units=4, prefix="fc2_"))
    net.initialize()
    net(nd.ones((1, 4)))
    step = CompiledTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                             mx.optimizer.create("sgd",
                                                 learning_rate=0.05))
    return net, step


R = np.random.RandomState(3)
X = R.rand(8, 4).astype(np.float32)
Y = (X.sum(1) > 2).astype(np.float32)
STEPS = int(os.environ.get("TPUMX_CI_STEPS", "16"))


def snapshot(net, step):
    step.sync_to_net()
    return {k: p.data().asnumpy().copy()
            for k, p in net.collect_params().items()}


def dump_final(net, step, tag):
    step.sync_to_net()
    out = {k: p.data().asnumpy()
           for k, p in net.collect_params().items()}
    np.savez(os.path.join(os.environ["TPUMX_CI_DIR"],
                          "final-%s.npz" % tag), **out)


if baseline:
    net, step = build()
    for _ in range(STEPS):
        step.step(nd.array(X), nd.array(Y))
    dump_final(net, step, "baseline")
    print("WORKER DONE baseline", flush=True)
    sys.exit(0)

from tpu_mx.elastic import WorkerFailure
from tpu_mx.parallel.fleet import Fleet, MembershipChange
from tpu_mx.parallel.integrity import DataCorruption, IntegrityMonitor

net, step = build()
# compile BEFORE the lease clock starts: the first jit build takes
# longer than a CI-sized lease, and a rank that joins then disappears
# into XLA for that long reads as partitioned
step.aot_compiled(nd.array(X), nd.array(Y))
f = Fleet.from_env()
f.join()
f.await_admission(timeout=60)
# the vote wait doubles as a step barrier (compile/scheduling skew is
# absorbed at the vote, not accumulated) and heartbeats through it —
# a rank blocked on slower peers must not read as partitioned
mon = IntegrityMonitor(f.root, rank=member, world=f.world(),
                       interval=4, vote_timeout=30.0,
                       heartbeat=f.heartbeat)
verified = snapshot(net, step)   # step 0: init is trivially verified
s = 0
deadline = time.monotonic() + 180
while s < STEPS and time.monotonic() < deadline:
    try:
        f.on_step()
    except MembershipChange:
        try:
            f.ack()
            f.shard()
        except WorkerFailure:
            # transiently evicted (not quarantined — that rank died
            # below): rejoin at the next epoch
            f.join()
            f.await_admission(timeout=60)
        mon.set_world(f.world())
        continue
    step.step(nd.array(X), nd.array(Y))
    s += 1
    try:
        mon.on_committed_step(s, fp=step.fingerprint())
    except DataCorruption as e:
        if e.self_corrupt:
            # the vote named THIS rank: quarantine self (permanent) and
            # die loudly — the launcher must refuse the restart
            f.quarantine(member, reason=str(e)[:200], step=s)
            telemetry.flush(final=True)
            print("WORKER QUARANTINED", member, flush=True)
            sys.exit(3)
        # survivor: drop the corrupt rank from the vote cohort NOW (its
        # stale fingerprint file must not poison the replayed vote),
        # restore the last VERIFIED weights and replay from there
        mon.set_world([m for m in mon.world if m not in e.minority])
        for k, p in net.collect_params().items():
            p.set_data(nd.array(verified[k]))
        step.sync_from_net()
        s = e.verified_step
        continue
    if mon.verified_step == s:
        verified = snapshot(net, step)
telemetry.flush(final=True)
dump_final(net, step, str(member))
f.leave()
print("WORKER DONE", member, flush=True)
"""


# The serve tier's workload (ISSUE 8): a fixed-seed request storm
# against the serving runtime with every serving chaos knob armed in
# turn — reject_storm (admission backpressure + client resubmit), a
# hung decode (slow_decode_step -> watchdog -> classified engine
# restart) and NaN logits (nan_after -> NumericDivergence -> restart).
# Storm prompts share a per-storm template prefix so the shared-prefix
# index (ISSUE 12 — the tier runs with TPUMX_PREFIX_SHARING=1) is
# actually exercised under every fault, not just present.
# Hard assertions: ZERO lost requests (every submission eventually
# completes with its full token budget), a schema-valid black box per
# injected fault whose timeline correlates injection -> decision by
# shared (step, generation), catalog-valid serving metrics, and the
# post-storm allocator audit — with the prefix index dropped, every
# block refcount is back at zero (no reference leaks under restarts,
# preemption, or requeues).
SERVE_SCRIPT = """
import json
import os
import random
from tpu_mx import serving, telemetry, tracing
from tpu_mx.contrib import chaos
from tpu_mx.serving import AdmissionReject
from tpu_mx.telemetry import ATTRIBUTION_TOLERANCE as ATOL

D = os.environ["TPUMX_SERVE_DIR"]
SEED = int(os.environ.get("TPUMX_CHAOS_SEED", "0"))
SHARING = os.environ.get("TPUMX_PREFIX_SHARING", "0") not in ("", "0")
rng = random.Random(SEED)
model = serving.TinyLM(vocab_size=64, embed_dim=32, num_heads=2,
                       num_layers=2, seed=SEED % 997)


def storm(tag, fault, n_req=12, **srv_kw):
    tracing.reset()
    prefix = os.path.join(D, tag)
    srv = serving.Server(model, num_blocks=96, block_size=8, max_batch=4,
                         max_pending=64, max_tokens=100000, backoff=0.0,
                         blackbox=prefix,
                         # ISSUE 19: every storm runs with the durable
                         # committed-token journal armed — the journal
                         # write path must survive the same faults the
                         # data plane does (and the telemetry gate
                         # requires its counters nonzero)
                         journal=prefix + "-jr",
                         slo=serving.SLOMonitor(("itl_p99 < 30s",
                                                 "ttft_p99 < 30s"),
                                                windows=(5.0, 30.0)),
                         **srv_kw)
    # a 12-token storm template: every prompt shares its first full
    # 8-block, so prefix sharing (when armed) is hit by request #2 on
    template = [1 + rng.randrange(40) for _ in range(12)]
    todo = [(template + [1 + rng.randrange(40)
                         for _ in range(rng.randint(1, 5))],
             rng.randint(2, 8)) for _ in range(n_req)]
    reqs = []
    with chaos.enable(seed=SEED, **fault):
        for prompt, mnt in todo:
            while True:   # backpressure contract: a reject is a signal
                try:      # to drain and RESUBMIT, never a lost request
                    reqs.append(srv.submit(prompt, max_new_tokens=mnt))
                    break
                except AdmissionReject as e:
                    assert e.reason in ("reject_storm", "queue_full"), e
                    srv.run_until_idle()
        srv.run_until_idle()
    for (prompt, mnt), r in zip(todo, reqs):   # ZERO lost requests
        assert r.state == "done", (tag, r)
        assert len(r.tokens) == mnt, (tag, r, mnt)
        # the SLO engine's attribution invariant (ISSUE 11): the typed
        # phases must sum to the independently stamped wall clock within
        # telemetry.ATTRIBUTION_TOLERANCE (1 ms absolute floor for
        # sub-ms requests), restart-penalty phases included — a seam
        # that stops closing its interval, or double-counts one, breaks
        # this for every faulted request
        tl = r.timeline
        lat = r.finished_at - r.submitted_at
        assert abs(tl.total - lat) <= max(ATOL * lat, 1e-3), (
            tag, r.id, tl.total, lat, tl.phases)
        ttft_sum = sum(tl.ttft_breakdown.values())
        assert abs(ttft_sum - r.ttft) <= max(ATOL * r.ttft, 1e-3), (
            tag, r.id, ttft_sum, r.ttft, tl.ttft_breakdown)
    if srv.restarts:
        # every in-flight request the restart requeued must carry a
        # nonzero restart_penalty phase (the re-run is attributed, not
        # smeared into queue_wait)
        bounced = [r for r in reqs if r.timeline.requeues]
        assert bounced, tag
        assert all(r.timeline.phases.get("restart_penalty", 0) > 0
                   for r in bounced), (tag, bounced)
        # zero-regeneration receipt (ISSUE 19): recovery was paid with
        # replay prefills, not re-decoded catch-up steps
        replays = [e for e in tracing.snapshot()
                   if e["event"] == "serve.prefill"
                   and e["data"]["replayed"] > 0]
        assert replays, tag
        assert telemetry.get("serve.redecode_tokens") is None, tag
        if SHARING and tracing.stats()["dropped"] == 0:
            # satellite bugfix: the requeued storm requests share the
            # template — their replays must RIDE the rebuilt engine's
            # prefix index (prefix re-prefilled once, hit thereafter),
            # not re-prefill it once per request
            assert any(e["data"]["cached"] > 0 for e in replays), (
                tag, [(e["data"]["request"], e["data"]["cached"],
                       e["data"]["replayed"]) for e in replays])
    # the live monitor published its gauges and signal hook
    sig = srv.slo_signal
    assert sig is not None and not sig["breaching"], (tag, sig)
    assert srv.scheduler.slo_signal is sig, tag
    for name in ("itl_p99", "ttft_p99"):
        assert telemetry.get("serve.slo_estimate_seconds",
                             slo=name) is not None, (tag, name)
    # post-storm allocator audit (ISSUE 12): every sequence is done and
    # evicted; with the prefix index dropped, every block refcount must
    # be back at zero — restarts, preemptions and requeues may not leak
    # references.  When sharing is armed, the template prompts must have
    # actually HIT the index (the storm exercises sharing, not just
    # carries the knob).
    cache = srv.engine.cache
    if SHARING:
        st = cache.prefix_stats()
        assert st["hits"] > 0, (tag, st)
    # post-storm ledger audit (ISSUE 14), BEFORE the index drop: the
    # accounting identity — per block, attributed refs == refcount; per
    # tenant, amortized bytes sum EXACTLY to pool-used bytes — must
    # hold at the storm's end state (audit raises on any violation)
    cache.audit()
    cache.drop_prefix_cache()
    leftover = cache.allocator.refcounts()
    assert not leftover, (tag, leftover)
    assert cache.allocator.used == 0, (tag, cache.stats())
    # ... and AFTER the drop: zero residual attributed bytes
    rep = cache.audit()
    assert rep["used_blocks"] == 0 and not rep["tenants"], (tag, rep)
    # an end-of-run audit box: unlike the restart-time box it contains
    # the finished requests' serve.request_timeline events — what
    # tools/slo_report.py's worst-request section (and its offline
    # re-check of the attribution invariant) reads
    tracing.dump_blackbox(prefix + "-audit",
                          reason=f"serve {tag} slo audit")
    path = tracing.blackbox_path(prefix)
    if not os.path.exists(path):   # faults with no restart (reject
        tracing.dump_blackbox(prefix, reason=f"serve {tag} audit")
    box = json.load(open(path))
    tracing.validate_blackbox(box)
    return srv, box


def correlated(box, kind, *names):
    evs = box["events"]
    inj = [e for e in evs if e["event"] == "chaos.inject"
           and e["data"]["kind"] == kind]
    assert inj, (kind, sorted({e["event"] for e in evs}))
    key = (inj[0]["step"], inj[0]["generation"])
    got = [e["event"] for e in evs
           if (e["step"], e["generation"]) == key]
    for n in names:
        assert n in got, (kind, n, got)


srv, box = storm("sv-reject", dict(reject_storm=3))
assert srv.restarts == 0
correlated(box, "reject_storm", "serve.reject")

srv, box = storm("sv-hang", dict(slow_decode_step=5,
                                 slow_decode_seconds=30), deadline=1.0)
assert srv.restarts == 1, srv.restarts
correlated(box, "slow_decode_step", "serve.restart")

srv, box = storm("sv-nan", dict(nan_after=4))
assert srv.restarts == 1, srv.restarts
correlated(box, "nan", "serve.restart")

assert telemetry.get("serve.engine_restarts").value == 2
assert telemetry.get("serve.requests", state="requeued").value >= 1

# capacity pressure leg (ISSUE 14): a deliberately small pool forces
# genuine CacheExhausted (preemption) and, with sharing armed, prefix
# pressure evictions.  Every exhaustion must leave a forensic record
# naming 100% of live holders, the dump on disk must be schema-valid,
# and the ledger identity must hold through the whole ordeal.
tracing.reset()
cappfx = os.path.join(D, "sv-capacity")
srv = serving.Server(model, num_blocks=10, block_size=4, max_batch=4,
                     max_pending=64, max_tokens=100000, backoff=0.0,
                     blackbox=cappfx,
                     tenants={"t0": {"weight": 2.0}, "t1": {"weight": 1.0}})
caps = [srv.submit([1, 2, 3, 4, 5, 6, 7], max_new_tokens=8,
                   tenant=f"t{i % 2}") for i in range(6)]
srv.run_until_idle()
for r in caps:
    assert r.state == "done" and len(r.tokens) == 8, r
cache = srv.engine.cache
recs = cache.forensic_records()
n_exh = sum(1 for r in recs if r["kind"] == "exhaustion")
assert n_exh > 0, "the pressure leg must genuinely exhaust the pool"
exh_events = [e for e in tracing.snapshot()
              if e["event"] == "serve.capacity_exhausted"]
assert exh_events, "no serve.capacity_exhausted on the timeline"
if tracing.stats()["dropped"] == 0:   # ring intact: 1:1 with records
    assert len(exh_events) == n_exh, (len(exh_events), n_exh)
from tpu_mx.serving import validate_forensic_doc
cache.flush_forensics()   # disk dumps are rate-limited; audit wants 1:1
with open(cappfx + "-capacity.json") as f:
    capdoc = json.load(f)
validate_forensic_doc(capdoc)   # holders-complete + identity per record
assert len(capdoc["records"]) == len(recs), (len(capdoc["records"]),
                                             len(recs))
cache.audit()
cache.drop_prefix_cache()
assert not cache.allocator.refcounts()
rep = cache.audit()
assert rep["used_blocks"] == 0 and not rep["tenants"], rep
print("CAPACITY LEG OK", flush=True)

# the decode-path observables must record the arm this leg actually ran
# on: the black boxes carrying serve.decode_path for the restarted
# generations, with the ISSUE 16 fused/spec_window fields.  The fused
# arm runs attention INSIDE its one device program — decode_attention
# is never dispatched, so its counter is asserted only on the host arms
# and the fused legs assert the whole-step observables instead (the
# constant-3 host-crossing receipt included).
from tpu_mx.serving.speculative import resolve_spec_window
kind = ("paged" if os.environ.get("TPUMX_PAGED_DECODE", "0")
        not in ("", "0") else "dense")
FUSED = (kind != "dense" and
         os.environ.get("TPUMX_FUSED_DECODE", "0") not in ("", "0"))
SPECW = resolve_spec_window()
if FUSED:
    assert telemetry.get("serve.fused_steps") is not None
    assert telemetry.get("serve.decode_attention", kind=kind) is None
    # per-token crossings = 3 / tokens-emitted-that-step: the constant-3
    # numerator means the gauge can never exceed 3.0 (one sequence, one
    # token), and any host-resident re-entry (4*layers numerator) would
    # blow straight past it
    xing = telemetry.get("serve.host_crossings_per_token")
    assert xing is not None and 0.0 < xing.value <= 3.0, xing
else:
    assert telemetry.get("serve.decode_attention",
                         kind=kind) is not None, kind
if SPECW > 1:
    assert telemetry.get("serve.spec_drafted").value > 0
    ratio = telemetry.get("serve.spec_accept_ratio")
    assert ratio is not None and 0.0 <= ratio.value <= 1.0, ratio
paths = [e for e in box["events"] if e["event"] == "serve.decode_path"]
assert paths and all(e["data"]["path"] == kind for e in paths), (kind, paths)
assert all(e["data"]["fused"] is FUSED for e in paths), (FUSED, paths)
assert all(e["data"]["spec_window"] == SPECW for e in paths), (SPECW, paths)
telemetry.flush(final=True)
print("SERVE OK", flush=True)
"""

# Kernel-parity gate (ISSUE 9): a fixed trace decoded through the dense
# reference arm and through the FORCED Pallas kernel (interpret mode on
# CPU — the real kernel code path) must produce identical greedy token
# streams through the Server path, and the raw attention outputs must
# agree within the documented f32-stats tolerance (DIVERGENCES #27).
SERVE_PARITY_SCRIPT = """
import os
import numpy as np
from tpu_mx import serving
from tpu_mx.serving.attention import decode_attention

SEED = int(os.environ.get("TPUMX_CHAOS_SEED", "0"))
model = serving.TinyLM(vocab_size=64, embed_dim=32, num_heads=2,
                       num_layers=2, seed=SEED % 997)
prompts = [[5, 6, 7], [9, 2], [1] * 7]


def run(mode, fused="0", spec="0"):
    os.environ["TPUMX_PAGED_DECODE"] = mode
    os.environ["TPUMX_FUSED_DECODE"] = fused
    os.environ["TPUMX_SPECULATIVE"] = spec
    srv = serving.Server(model, num_blocks=64, max_batch=4)
    reqs = [srv.submit(p, max_new_tokens=6) for p in prompts]
    srv.run_until_idle()
    return [r.tokens for r in reqs]


dense = run("0")
kernel = run("kernel")
assert dense == kernel, (dense, kernel)

# ISSUE 16: the fused whole-step program and speculative decode are pure
# perf arms — every (decode mode, fused, spec) combination must emit the
# dense reference's exact greedy streams (greedy verification is
# lossless; the fused program imports the SAME weights)
for mode in ("0", "1", "kernel"):
    for fused in ("0", "1"):
        for spec in ("0", "1"):
            got = run(mode, fused, spec)
            assert got == dense, (mode, fused, spec, got, dense)

# raw-logits tolerance on a shared churned cache (both arms, same pool)
os.environ["TPUMX_PAGED_DECODE"] = "0"
eng = serving.EngineCore(model, block_size=4, num_blocks=32)
rng = np.random.RandomState(SEED % 2311)
for i, length in enumerate((6, 3, 9)):
    k = rng.rand(2, length, 2, 16).astype(np.float32)
    eng.cache.prefill(f"s{i}", k, k * 0.5)
eng.cache.free_sequence("s1")
k = rng.rand(2, 5, 2, 16).astype(np.float32)
eng.cache.prefill("s3", k, -k)
q = rng.rand(3, 2, 16).astype(np.float32)
ids = ["s0", "s2", "s3"]
want = decode_attention(q, eng.cache, ids, 1, kind="dense")
got = decode_attention(q, eng.cache, ids, 1, kind="paged-kernel")
drift = float(np.max(np.abs(got - want)))
assert drift <= 2e-5, drift
print(f"SERVE PARITY OK drift={drift:.2e}", flush=True)
"""

# Zero-regeneration recovery gate (ISSUE 19), stage 1: a victim process
# with the committed-token journal armed that the chaos layer kills with
# a REAL ``os._exit(137)`` mid-decode (TPUMX_CHAOS=kill9_at_decode_step
# is wired from the driver's env).  The driver asserts rc == 137; stage
# 2 (SERVE_RECOVERY_SCRIPT) then recovers from the journal this process
# left behind — a genuinely cross-process crash, not a simulated one.
SERVE_KILL9_CHILD = """
import os
from tpu_mx import serving

D = os.environ["TPUMX_SERVE_DIR"]
SEED = int(os.environ.get("TPUMX_CHAOS_SEED", "0"))
model = serving.TinyLM(vocab_size=64, embed_dim=32, num_heads=2,
                       num_layers=2, seed=SEED % 997)
srv = serving.Server(model, num_blocks=96, block_size=8, max_batch=4,
                     backoff=0.0, journal=os.path.join(D, "k9"))
for i, p in enumerate(([7, 8, 9], [7, 8, 10, 11], [3, 4])):
    srv.submit(p, max_new_tokens=48, request_id=f"r{i}")
srv.run_until_idle()   # TPUMX_CHAOS=kill9_at_decode_step=30 fires here
print("KILL9 DID NOT FIRE", flush=True)
"""

# Stage 2 of the recovery gate, a FRESH process: (1) resume the victim's
# streams from the fsync'd journal bit-identical to an uninterrupted
# run; (2) drain & hot handoff under live load with zero client-visible
# failures; (3) the A/B restart-penalty gate — on >=128-committed-token
# streams, prefill replay (ONE prefill per sequence) must beat the
# legacy prompt-replay arm (sequential re-decode of every committed
# token) by >= 3x on the worst request's restart_penalty phase.
SERVE_RECOVERY_SCRIPT = """
import os
from tpu_mx import serving, telemetry, tracing
from tpu_mx.contrib import chaos
from tpu_mx.serving import AdmissionReject
from tpu_mx.serving.journal import journal_path
from tpu_mx.serving.journal import load as journal_load

D = os.environ["TPUMX_SERVE_DIR"]
SEED = int(os.environ.get("TPUMX_CHAOS_SEED", "0"))
model = serving.TinyLM(vocab_size=64, embed_dim=32, num_heads=2,
                       num_layers=2, seed=SEED % 997)


def cval(name):
    rec = telemetry.get(name)
    return 0 if rec is None else rec.value


def reference(prompts, max_new):
    srv = serving.Server(model, num_blocks=96, block_size=8, max_batch=4,
                         backoff=0.0)
    reqs = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
    srv.run_until_idle()
    return [list(r.tokens) for r in reqs]


# --- leg 1: cross-process kill -9 recovery ----------------------------
# The victim (SERVE_KILL9_CHILD, rc=137) left a journal in D.  Recovery
# must resume every stream from the fsync'd committed ledger with ONE
# prefill each — bit-identical to the uninterrupted run, zero tokens
# re-decoded, zero lost, the committed prefix untouched.
entries = journal_load(journal_path(os.path.join(D, "k9")))
assert len(entries) == 3, sorted(entries)
assert not any(e["fallback"] for e in entries.values()), entries
survivors = {rid: list(e["tokens"]) for rid, e in entries.items()}
assert any(survivors.values()), "the victim committed no work"
ref = reference(([7, 8, 9], [7, 8, 10, 11], [3, 4]), 48)
srv = serving.Server(model, num_blocks=96, block_size=8, max_batch=4,
                     backoff=0.0, journal=os.path.join(D, "k9"))
handles = srv.recover()
srv.run_until_idle()
for i in range(3):
    got = list(handles[f"r{i}"].tokens)
    assert got == ref[i], (i, got, ref[i])
    assert got[:len(survivors[f"r{i}"])] == survivors[f"r{i}"], i
assert telemetry.get("serve.redecode_tokens") is None
assert cval("serve.replay_requests") == sum(
    1 for t in survivors.values() if t)
print("KILL9 RECOVERY OK", flush=True)

# --- leg 2: planned maintenance under live load -----------------------
tracing.reset()
dprompts = ([11, 12, 13], [11, 12, 14], [5, 6])
dref = reference(dprompts, 12)
srv = serving.Server(model, num_blocks=96, block_size=8, max_batch=4,
                     backoff=0.0, journal=os.path.join(D, "drain"))
reqs = [srv.submit(p, max_new_tokens=12) for p in dprompts]
for _ in range(4):
    srv.step()          # live mid-decode state
n = srv.handoff()       # hot handoff onto a fresh engine generation
assert n == 3, n
assert srv.restarts == 0, srv.restarts
srv.drain()             # quiesce: finish every live stream
assert [list(r.tokens) for r in reqs] == dref   # bit-identical streams
assert all(r.state == "done" for r in reqs), reqs
try:
    srv.submit([1], max_new_tokens=2)
    raise AssertionError("a draining server accepted an admission")
except AdmissionReject as e:
    assert e.reason == "draining", e
srv.resume_admission()
late = srv.submit([1], max_new_tokens=2)
srv.run_until_idle()
assert late.state == "done", late
kinds = [e["data"]["kind"] for e in tracing.snapshot()
         if e["event"] == "serve.drain"]
assert kinds == ["handoff", "drain"], kinds
print("DRAIN LEG OK", flush=True)

# --- leg 3: the zero-regeneration payoff, CI-gated --------------------
# Warm the replay-prefill sequence lengths OUTSIDE the timed phase: the
# replay prefill re-feeds prompt+committed (~135 tokens) in one call, a
# length nothing else in this process has compiled — without the warmup
# the gate would time XLA compilation, not recovery work.
for L in (133, 134, 135, 136, 137):
    reference([[1 + i % 40 for i in range(L)]], 1)


def deep_storm(tag, fault, replay, **srv_kw):
    # a fault deep into decode: every stream has >= 128 committed
    # tokens when it fires, the worst case for prompt replay
    srv = serving.Server(model, num_blocks=96, block_size=8, max_batch=4,
                         backoff=0.0, replay=replay,
                         journal=os.path.join(D, tag), **srv_kw)
    with chaos.enable(seed=SEED, **fault):
        reqs = [srv.submit(p, max_new_tokens=140)
                for p in ([21, 22, 23], [21, 22, 24])]
        srv.run_until_idle()
    assert srv.restarts == 1, (tag, srv.restarts)
    for r in reqs:
        assert r.state == "done" and len(r.tokens) == 140, (tag, r)
        assert r.timeline.requeues == 1, (tag, r.id)
    return max(r.timeline.phases["restart_penalty"] for r in reqs)


# receipt: a HANG storm (watchdog restart) 132 committed tokens deep —
# recovery is exactly one replay prefill per sequence, zero re-decoded
before_rq, before_rt = cval("serve.replay_requests"), cval(
    "serve.replay_tokens")
deep_storm("hang-replay", dict(slow_decode_step=132,
                               slow_decode_seconds=30),
           replay=True, deadline=2.0)
assert cval("serve.replay_requests") - before_rq == 2   # ONE prefill each
replayed = cval("serve.replay_tokens") - before_rt
assert replayed >= 2 * 128, replayed   # >= 128 committed per stream
assert cval("serve.redecode_tokens") == 0

# the >= 3x gate runs on the NaN fault: the health gate detects it at
# decode-check speed (sub-ms), so restart_penalty measures RECOVERY
# work, not fault-detection latency — on the hang arm above both
# recovery strategies pay the same 2s watchdog wait, which would mask
# the replay win
before_rq, before_rt = cval("serve.replay_requests"), cval(
    "serve.replay_tokens")
pen_replay = deep_storm("ab-replay", dict(nan_after=132), replay=True)
assert cval("serve.replay_requests") - before_rq == 2
assert cval("serve.replay_tokens") - before_rt >= 2 * 128
assert cval("serve.redecode_tokens") == 0

before_rq, before_rd = cval("serve.replay_requests"), cval(
    "serve.redecode_tokens")
pen_legacy = deep_storm("ab-legacy", dict(nan_after=132), replay=False)
assert cval("serve.replay_requests") - before_rq == 0
redecoded = cval("serve.redecode_tokens") - before_rd
assert redecoded >= 2 * 128, redecoded
assert pen_legacy >= 3.0 * pen_replay, (pen_legacy, pen_replay)
print("AB GATE OK replay=%.1fms legacy=%.1fms ratio=%.1fx"
      % (pen_replay * 1e3, pen_legacy * 1e3, pen_legacy / pen_replay),
      flush=True)
telemetry.flush(final=True)
print("RECOVER OK", flush=True)
"""

SERVE_REQUIRED = ("serve", "chaos.injections")

# per-box markers the RENDERED report (tools/blackbox_report.py, run
# under a poisoned jax import) must contain: the injection and the
# decision in prose
SERVE_BOX_EXPECT = {
    "sv-reject": ("chaos reject_storm injected", "admission rejected"),
    "sv-hang": ("chaos slow_decode_step injected", "engine restart #"),
    "sv-nan": ("chaos nan injected", "engine restart #"),
}


def _serve_storm_leg(mode, spec="0", fused="0"):
    """One full chaos-storm pass (the three faults) with the decode arm
    pinned to `mode` ("0" = dense-gather reference, "1" = paged:
    device-resident pool + block-table program) and shared-prefix KV
    reuse ENABLED (ISSUE 12: the self-healing contract must hold with
    sharing on — the storm script's post-storm allocator audit asserts
    every refcount returns to zero), then telemetry validation and
    jax-less black-box rendering.  ISSUE 16 adds `spec`
    (TPUMX_SPECULATIVE) and `fused` (TPUMX_FUSED_DECODE): the fused
    whole-step arm and speculative windows must survive the same storms
    with zero lost requests and a clean post-storm allocator audit
    (fused silently downgrades to the host arm on mode "0" — the script
    recomputes the effective arm and asserts the matching observables)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tag_mode = "dense" if mode in ("", "0") else "paged"
    if spec not in ("", "0"):
        tag_mode += "+spec"
    if fused not in ("", "0"):
        tag_mode += "+fused"
    with tempfile.TemporaryDirectory() as d:
        jsonl = os.path.join(d, "telemetry.jsonl")
        env = dict(os.environ, TPUMX_TELEMETRY=jsonl, JAX_PLATFORMS="cpu",
                   TPUMX_CHAOS_SEED="20260804", TPUMX_SERVE_DIR=d,
                   TPUMX_PAGED_DECODE=mode, TPUMX_PREFIX_SHARING="1",
                   TPUMX_SPECULATIVE=spec, TPUMX_FUSED_DECODE=fused)
        env.pop("TPUMX_CHAOS", None)    # the script arms its own faults
        env.pop("TPUMX_TRACING", None)  # the black boxes need the recorder
        try:
            run = subprocess.run([sys.executable, "-c", SERVE_SCRIPT],
                                 env=env, cwd=repo, capture_output=True,
                                 text=True, timeout=600)
        except subprocess.TimeoutExpired as e:
            print(f"  serve[{tag_mode}]: request storm timed out: {e}")
            return 1
        if run.returncode != 0 or "SERVE OK" not in (run.stdout or ""):
            print(f"  serve[{tag_mode}]: request storm failed "
                  f"(rc={run.returncode}):\n"
                  f"{((run.stdout or '') + (run.stderr or ''))[-4000:]}")
            return run.returncode or 1
        try:
            val = subprocess.run(
                [sys.executable, os.path.join(repo, "tools",
                                              "telemetry_report.py"),
                 jsonl, "--validate", "--require",
                 ",".join(SERVE_REQUIRED)],
                capture_output=True, text=True, timeout=120)
        except subprocess.TimeoutExpired as e:
            print(f"  serve[{tag_mode}]: telemetry validation timed out: "
                  f"{e}")
            return 1
        if val.returncode != 0:
            print(f"  serve[{tag_mode}]: telemetry validation failed "
                  f"(rc={val.returncode}):\n"
                  f"{((val.stdout or '') + (val.stderr or ''))[-3000:]}")
            return val.returncode or 1
        report = os.path.join(repo, "tools", "blackbox_report.py")
        for tag, expect in SERVE_BOX_EXPECT.items():
            box = os.path.join(d, f"{tag}-blackbox.json")
            code = ("import sys, runpy; "
                    "sys.modules['jax'] = None; "
                    "sys.modules['tpu_mx'] = None; "
                    f"sys.argv = ['blackbox_report.py', {box!r}, "
                    "'--validate']; "
                    f"runpy.run_path({report!r}, run_name='__main__')")
            try:
                ren = subprocess.run([sys.executable, "-c", code],
                                     capture_output=True, text=True,
                                     timeout=120)
            except subprocess.TimeoutExpired as e:
                print(f"  serve[{tag_mode}]: blackbox report timed out "
                      f"on {tag}: {e}")
                return 1
            out = (ren.stdout or "") + (ren.stderr or "")
            if ren.returncode != 0:
                print(f"  serve[{tag_mode}]: blackbox report failed on "
                      f"{tag} (rc={ren.returncode}):\n{out[-3000:]}")
                return 1
            missing = [m for m in expect if m not in out]
            if missing:
                print(f"  serve[{tag_mode}]: blackbox report for {tag} "
                      f"is missing timeline markers {missing}:"
                      f"\n{out[-3000:]}")
                return 1
        # the SLO ops surface, under the same poisoned-jax discipline:
        # schema-gate the storm's telemetry (window sub-objects
        # included) plus the end-of-run audit box, whose request
        # timelines slo_report re-checks against the 5% attribution
        # invariant offline — and whose worst-request section must
        # actually render recorded timelines
        slo_tool = os.path.join(repo, "tools", "slo_report.py")
        audit = os.path.join(d, "sv-nan-audit-blackbox.json")
        code = ("import sys, runpy; "
                "sys.modules['jax'] = None; "
                "sys.modules['tpu_mx'] = None; "
                f"sys.argv = ['slo_report.py', {jsonl!r}, "
                f"'--box', {audit!r}, '--validate']; "
                f"runpy.run_path({slo_tool!r}, run_name='__main__')")
        try:
            slo = subprocess.run([sys.executable, "-c", code],
                                 capture_output=True, text=True,
                                 timeout=120)
        except subprocess.TimeoutExpired as e:
            print(f"  serve[{tag_mode}]: slo_report timed out: {e}")
            return 1
        out = (slo.stdout or "") + (slo.stderr or "")
        if slo.returncode != 0:
            print(f"  serve[{tag_mode}]: slo_report failed "
                  f"(rc={slo.returncode}):\n{out[-3000:]}")
            return 1
        # "serving.SLOMonitor state" appears only in the ARMED gauge
        # rendering — the none-armed fallback line also says "Live
        # monitor gauges", which would let missing serve.slo_* series
        # slip through a looser marker
        missing = [m for m in ("SLO targets", "Worst requests by latency",
                               "serving.SLOMonitor state",
                               "Restart recovery",
                               "Per-tenant SLO state")
                   if m not in out]
        if missing or "top 5 of 0 recorded" in out:
            print(f"  serve[{tag_mode}]: slo_report output is missing "
                  f"sections {missing or ['request timelines']}:"
                  f"\n{out[-3000:]}")
            return 1
        # the capacity ops surface (ISSUE 14), same poisoned-jax
        # discipline: schema-gate the storm's telemetry (the per-tenant
        # pool_bytes identity re-checked offline per snapshot) plus the
        # pressure leg's forensic dump, whose records must name 100% of
        # the live holders and satisfy the identity record-by-record
        cap_tool = os.path.join(repo, "tools", "capacity_report.py")
        capjson = os.path.join(d, "sv-capacity-capacity.json")
        code = ("import sys, runpy; "
                "sys.modules['jax'] = None; "
                "sys.modules['tpu_mx'] = None; "
                f"sys.argv = ['capacity_report.py', {jsonl!r}, "
                f"'--forensics', {capjson!r}, '--validate']; "
                f"runpy.run_path({cap_tool!r}, run_name='__main__')")
        try:
            cap = subprocess.run([sys.executable, "-c", code],
                                 capture_output=True, text=True,
                                 timeout=120)
        except subprocess.TimeoutExpired as e:
            print(f"  serve[{tag_mode}]: capacity_report timed out: {e}")
            return 1
        out = (cap.stdout or "") + (cap.stderr or "")
        if cap.returncode != 0:
            print(f"  serve[{tag_mode}]: capacity_report failed "
                  f"(rc={cap.returncode}):\n{out[-3000:]}")
            return 1
        missing = [m for m in ("Ledger timeline",
                               "Per-tenant pool attribution",
                               "Exhaustion forensics", "schema OK")
                   if m not in out]
        if missing or "0 forensic record(s)" in out:
            print(f"  serve[{tag_mode}]: capacity_report output is "
                  f"missing sections {missing or ['forensic records']}:"
                  f"\n{out[-3000:]}")
            return 1
    return 0


def _serve_recovery_leg(mode):
    """The zero-regeneration recovery gate (ISSUE 19), per decode mode:
    stage 1 runs SERVE_KILL9_CHILD with the journal armed and chaos
    wired to ``os._exit(137)`` mid-decode (the driver asserts the 137);
    stage 2 runs SERVE_RECOVERY_SCRIPT in a FRESH process — journal
    recovery bit-identical to the uninterrupted run, drain & hot
    handoff under live load, and the A/B gate (prefill replay beats the
    legacy prompt-replay arm >= 3x on restart_penalty for streams with
    >= 128 committed tokens); then the jax-less slo_report rendering of
    the restart-recovery section from the leg's telemetry."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tag_mode = "dense" if mode in ("", "0") else "paged"
    with tempfile.TemporaryDirectory() as d:
        jsonl = os.path.join(d, "telemetry.jsonl")
        base = dict(os.environ, JAX_PLATFORMS="cpu",
                    TPUMX_CHAOS_SEED="20260807", TPUMX_SERVE_DIR=d,
                    TPUMX_PAGED_DECODE=mode, TPUMX_PREFIX_SHARING="1",
                    TPUMX_SPECULATIVE="0", TPUMX_FUSED_DECODE="0")
        for k in ("TPUMX_CHAOS", "TPUMX_TRACING", "TPUMX_TELEMETRY",
                  "TPUMX_PREFILL_REPLAY"):
            base.pop(k, None)
        kenv = dict(base, TPUMX_CHAOS="kill9_at_decode_step=30")
        try:
            kid = subprocess.run([sys.executable, "-c", SERVE_KILL9_CHILD],
                                 env=kenv, cwd=repo, capture_output=True,
                                 text=True, timeout=600)
        except subprocess.TimeoutExpired as e:
            print(f"  serve[{tag_mode}]: kill -9 victim timed out: {e}")
            return 1
        if kid.returncode != 137 or "KILL9 DID NOT FIRE" in (kid.stdout
                                                             or ""):
            print(f"  serve[{tag_mode}]: kill -9 victim exited "
                  f"rc={kid.returncode}, wanted 137:\n"
                  f"{((kid.stdout or '') + (kid.stderr or ''))[-3000:]}")
            return 1
        renv = dict(base, TPUMX_TELEMETRY=jsonl)
        try:
            rec = subprocess.run([sys.executable, "-c",
                                  SERVE_RECOVERY_SCRIPT],
                                 env=renv, cwd=repo, capture_output=True,
                                 text=True, timeout=600)
        except subprocess.TimeoutExpired as e:
            print(f"  serve[{tag_mode}]: recovery leg timed out: {e}")
            return 1
        if rec.returncode != 0 or "RECOVER OK" not in (rec.stdout or ""):
            print(f"  serve[{tag_mode}]: recovery leg failed "
                  f"(rc={rec.returncode}):\n"
                  f"{((rec.stdout or '') + (rec.stderr or ''))[-4000:]}")
            return rec.returncode or 1
        # the recovery ops surface, under the poisoned-jax discipline:
        # slo_report must render the restart-recovery section with the
        # leg's replay/journal receipts (and schema-gate the telemetry)
        slo_tool = os.path.join(repo, "tools", "slo_report.py")
        code = ("import sys, runpy; "
                "sys.modules['jax'] = None; "
                "sys.modules['tpu_mx'] = None; "
                f"sys.argv = ['slo_report.py', {jsonl!r}, '--validate']; "
                f"runpy.run_path({slo_tool!r}, run_name='__main__')")
        try:
            slo = subprocess.run([sys.executable, "-c", code],
                                 capture_output=True, text=True,
                                 timeout=120)
        except subprocess.TimeoutExpired as e:
            print(f"  serve[{tag_mode}]: recovery slo_report timed out: "
                  f"{e}")
            return 1
        out = (slo.stdout or "") + (slo.stderr or "")
        if slo.returncode != 0:
            print(f"  serve[{tag_mode}]: recovery slo_report failed "
                  f"(rc={slo.returncode}):\n{out[-3000:]}")
            return 1
        missing = [m for m in ("Restart recovery", "replayed sequences",
                               "replayed tokens", "journal")
                   if m not in out]
        if missing:
            print(f"  serve[{tag_mode}]: recovery slo_report output is "
                  f"missing sections {missing}:\n{out[-3000:]}")
            return 1
        ab = [ln for ln in (rec.stdout or "").splitlines()
              if ln.startswith("AB GATE OK")]
        print(f"  serve[{tag_mode}]: recovery gate OK "
              f"({ab[0] if ab else 'RECOVER OK'})")
    return 0


def serve_tier():
    """Run the chaos request storm against the serving runtime in BOTH
    decode modes (dense-gather reference and TPUMX_PAGED_DECODE=1 —
    ISSUE 9: the self-healing contract is data-plane-independent), plus
    the ISSUE 16 legs (fused whole-step arm + TPUMX_SPECULATIVE=1 in
    both decode modes — on dense the fused knob downgrades to the host
    arm, which is itself part of the contract), then the kernel-parity
    gate: the forced Pallas kernel (interpret on CPU) must reproduce
    the dense arm's greedy tokens exactly — fused on/off and
    speculative on/off included — and its logits within the documented
    tolerance.  ISSUE 19 adds the zero-regeneration recovery gate per
    decode mode: a real cross-process kill -9 recovered from the
    committed-token journal, drain & hot handoff under live load, and
    the CI-gated >= 3x restart_penalty win of prefill replay over the
    legacy prompt-replay arm."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for mode, spec, fused in (("0", "0", "0"), ("1", "0", "0"),
                              ("0", "1", "1"), ("1", "1", "1")):
        rc = _serve_storm_leg(mode, spec, fused)
        if rc != 0:
            return rc
    # the ISSUE 19 recovery gate (kill -9 + journal recovery, drain &
    # handoff, replay-vs-redecode A/B), on both decode data planes
    for mode in ("0", "1"):
        rc = _serve_recovery_leg(mode)
        if rc != 0:
            return rc
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               TPUMX_CHAOS_SEED="20260804")
    env.pop("TPUMX_CHAOS", None)
    try:
        par = subprocess.run([sys.executable, "-c", SERVE_PARITY_SCRIPT],
                             env=env, cwd=repo, capture_output=True,
                             text=True, timeout=600)
    except subprocess.TimeoutExpired as e:
        print(f"  serve: kernel-parity gate timed out: {e}")
        return 1
    if par.returncode != 0 or "SERVE PARITY OK" not in (par.stdout or ""):
        print(f"  serve: kernel-parity gate failed "
              f"(rc={par.returncode}):\n"
              f"{((par.stdout or '') + (par.stderr or ''))[-4000:]}")
        return par.returncode or 1
    print(f"  {(par.stdout or '').strip().splitlines()[-1]}")
    return 0


def soak_tier():
    """Run the supervised chaos-soak training job with a FIXED chaos seed
    and bounded wall-clock, then validate its telemetry (the supervisor
    metrics must all be nonzero — recovery paths taken, not assumed)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with tempfile.TemporaryDirectory() as d:
        jsonl = os.path.join(d, "telemetry.jsonl")
        env = dict(os.environ, TPUMX_TELEMETRY=jsonl, JAX_PLATFORMS="cpu",
                   TPUMX_CHAOS_SEED="20260804")
        env.pop("TPUMX_CHAOS", None)  # the script arms its own schedule
        env.pop("TPUMX_TRACING", None)  # the blackbox leg needs the recorder
        try:
            run = subprocess.run([sys.executable, "-c", SOAK_SCRIPT],
                                 env=env, cwd=repo, capture_output=True,
                                 text=True, timeout=600)
        except subprocess.TimeoutExpired as e:
            print(f"  soak: supervised run timed out: {e}")
            return 1
        if run.returncode != 0 or "SOAK OK" not in (run.stdout or ""):
            print(f"  soak: supervised run failed (rc={run.returncode}):\n"
                  f"{((run.stdout or '') + (run.stderr or ''))[-4000:]}")
            return run.returncode or 1
        try:
            val = subprocess.run(
                [sys.executable, os.path.join(repo, "tools",
                                              "telemetry_report.py"),
                 jsonl, "--validate", "--require", ",".join(SOAK_REQUIRED)],
                capture_output=True, text=True, timeout=120)
        except subprocess.TimeoutExpired as e:
            print(f"  soak: telemetry validation timed out: {e}")
            return 1
        if val.returncode != 0:
            print(f"  soak: telemetry validation failed "
                  f"(rc={val.returncode}):\n"
                  f"{((val.stdout or '') + (val.stderr or ''))[-3000:]}")
            return val.returncode or 1
    # membership-churn leg (ISSUE 17): seeded partition -> reshard down,
    # heal -> rejoin -> reshard up, SIGTERM preempt survived — with the
    # global sample-id ledger gated against the uninterrupted oracle
    with tempfile.TemporaryDirectory() as d:
        jsonl = os.path.join(d, "telemetry.jsonl")
        env = dict(os.environ, TPUMX_TELEMETRY=jsonl, JAX_PLATFORMS="cpu",
                   TPUMX_CHAOS_SEED="20260804",
                   XLA_FLAGS="--xla_force_host_platform_device_count=2")
        env.pop("TPUMX_CHAOS", None)  # the script arms its own schedule
        env.pop("TPUMX_TRACING", None)
        try:
            run = subprocess.run([sys.executable, "-c", FLEET_SCRIPT],
                                 env=env, cwd=repo, capture_output=True,
                                 text=True, timeout=600)
        except subprocess.TimeoutExpired as e:
            print(f"  soak: fleet churn run timed out: {e}")
            return 1
        if run.returncode != 0 or "FLEET OK" not in (run.stdout or ""):
            print(f"  soak: fleet churn run failed (rc={run.returncode}):\n"
                  f"{((run.stdout or '') + (run.stderr or ''))[-4000:]}")
            return run.returncode or 1
        try:
            val = subprocess.run(
                [sys.executable, os.path.join(repo, "tools",
                                              "telemetry_report.py"),
                 jsonl, "--validate", "--require",
                 ",".join(FLEET_REQUIRED)],
                capture_output=True, text=True, timeout=120)
        except subprocess.TimeoutExpired as e:
            print(f"  soak: fleet telemetry validation timed out: {e}")
            return 1
        if val.returncode != 0:
            print(f"  soak: fleet telemetry validation failed "
                  f"(rc={val.returncode}):\n"
                  f"{((val.stdout or '') + (val.stderr or ''))[-3000:]}")
            return val.returncode or 1
    # straggler sub-leg (ISSUE 18): the injected straggler must be
    # named, with its dominant phase, under BOTH churn shapes
    for scenario in ("preempt", "partition"):
        rc = _straggler_leg(repo, scenario)
        if rc:
            return rc
    # SDC storm sub-leg (ISSUE 20): an injected parameter bit-flip must
    # be voted out, quarantined, never re-admitted — and the survivors'
    # rollback must cost ZERO correctness (bit-equal to uninjected)
    return _sdc_leg(repo)


def _straggler_leg(repo, scenario):
    """One supervised 2-worker fleet with rank 1 chaos-slowed, churned by
    ``scenario`` ("preempt": SIGTERM rank 0 mid-step -> evict -> restart
    -> rejoin; "partition": rank 1's beats suppressed -> lease expiry ->
    evict -> heal -> rejoin).  Gates the whole observability plane on
    the resulting artifacts."""
    with tempfile.TemporaryDirectory() as d:
        fleet_dir = os.path.join(d, "fleet")
        ctl_jsonl = os.path.join(d, "controller.jsonl")
        worker = os.path.join(d, "worker.py")
        with open(worker, "w") as f:
            f.write(STRAGGLER_WORKER)
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   TPUMX_TELEMETRY=ctl_jsonl, TPUMX_REPO=repo,
                   TPUMX_CI_DIR=d, TPUMX_CI_SCENARIO=scenario,
                   TPUMX_CI_STEPS="24")
        env.pop("TPUMX_CHAOS", None)   # scenario wiring below only
        env.pop("TPUMX_TRACING", None)
        argv = [sys.executable, os.path.join(repo, "tools", "launch.py"),
                "--supervise", "-n", "2", "--fleet-dir", fleet_dir,
                "--max-restarts", "2", "--backoff", "1.0",
                "--lease", "2.0", "--join-timeout", "60"]
        if scenario == "preempt":
            # the env-wired shape: rank 1 straggles all run, rank 0 is
            # SIGTERMed mid-step and comes back chaos-stripped
            argv += ["--env", "TPUMX_CHAOS=slow_worker_rank=1,"
                             "slow_worker_seconds=0.25,"
                             "preempt_worker_at_step=6,preempt_rank=0"]
        argv += [sys.executable, worker]
        try:
            run = subprocess.run(argv, env=env, cwd=repo,
                                 capture_output=True, text=True,
                                 timeout=600)
        except subprocess.TimeoutExpired as e:
            print(f"  soak: straggler/{scenario} run timed out: {e}")
            return 1
        if run.returncode != 0:
            print(f"  soak: straggler/{scenario} supervised run failed "
                  f"(rc={run.returncode}):\n"
                  f"{((run.stdout or '') + (run.stderr or ''))[-4000:]}")
            return run.returncode or 1
        box = os.path.join(fleet_dir, "fleet-blackbox.json")
        try:
            with open(box, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            print(f"  soak: straggler/{scenario}: no readable fleet "
                  f"black box at {box}: {e}")
            return 1
        sig = (doc.get("fleet") or {}).get("straggler_signal") or {}
        if not (sig.get("straggling") and sig.get("rank") == 1
                and sig.get("dominant_phase") == "data_wait"):
            print(f"  soak: straggler/{scenario}: detector did not name "
                  f"the injected rank/phase (signal={sig})")
            return 1
        skews = [c.get("skew_seconds", 0.0)
                 for c in (doc.get("fleet") or {}).get("skew_timeline", [])]
        if not skews or max(skews) <= 0.0:
            print(f"  soak: straggler/{scenario}: skew never moved "
                  f"(timeline={skews[:8]})")
            return 1
        # the report tool must work — and name rank 1 + the phase — on a
        # machine with NO accelerator stack (poisoned jax/tpu_mx)
        report = os.path.join(repo, "tools", "fleet_report.py")
        poison = ("import sys, runpy; sys.modules['jax'] = None; "
                  "sys.modules['tpu_mx'] = None; "
                  f"sys.argv = ['fleet_report', {box!r}, '--validate']; "
                  f"runpy.run_path({report!r}, run_name='__main__')")
        try:
            rep = subprocess.run([sys.executable, "-c", poison],
                                 capture_output=True, text=True,
                                 timeout=120)
        except subprocess.TimeoutExpired as e:
            print(f"  soak: straggler/{scenario}: fleet_report timed "
                  f"out: {e}")
            return 1
        out = rep.stdout or ""
        if rep.returncode != 0 or "rank 1" not in out \
                or "data_wait" not in out:
            print(f"  soak: straggler/{scenario}: fleet_report "
                  f"--validate failed (rc={rep.returncode}):\n"
                  f"{(out + (rep.stderr or ''))[-3000:]}")
            return rep.returncode or 1
        # aggregation identity across the controller + worker registries
        files = [ctl_jsonl] + [os.path.join(d, f"worker-{r}.jsonl")
                               for r in (0, 1)]
        missing = [p for p in files if not os.path.exists(p)]
        if missing:
            print(f"  soak: straggler/{scenario}: missing telemetry "
                  f"file(s): {missing}")
            return 1
        try:
            val = subprocess.run(
                [sys.executable, os.path.join(repo, "tools",
                                              "telemetry_report.py"),
                 "--merge", *files, "--validate",
                 "--require", "fleet_obs"],
                capture_output=True, text=True, timeout=120)
        except subprocess.TimeoutExpired as e:
            print(f"  soak: straggler/{scenario}: merged validation "
                  f"timed out: {e}")
            return 1
        if val.returncode != 0:
            print(f"  soak: straggler/{scenario}: merged telemetry "
                  f"validation failed (rc={val.returncode}):\n"
                  f"{((val.stdout or '') + (val.stderr or ''))[-3000:]}")
            return val.returncode or 1
        print(f"  soak: straggler/{scenario}: rank 1/data_wait "
              f"attributed, max skew {max(skews):.3f}s, merged "
              "identity holds")
    return 0


def _sdc_leg(repo):
    """One supervised 3-worker fleet of identical replicas with a seeded
    parameter bit-flip injected into rank 1's committed weights.  Gates
    the whole SDC defense plane: vote -> minority attribution ->
    self-quarantine -> launcher restart refusal -> survivor rollback to
    the last verified weights, bit-equal to an uninjected run."""
    import numpy as np
    with tempfile.TemporaryDirectory() as d:
        fleet_dir = os.path.join(d, "fleet")
        ctl_jsonl = os.path.join(d, "controller.jsonl")
        worker = os.path.join(d, "worker.py")
        with open(worker, "w") as f:
            f.write(SDC_WORKER)
        # the uninjected oracle first: same script, same seed, same
        # grid — no fleet, no integrity plane, no chaos
        base_env = dict(os.environ, JAX_PLATFORMS="cpu", TPUMX_REPO=repo,
                        TPUMX_CI_DIR=d, TPUMX_CI_BASELINE="1",
                        TPUMX_CI_STEPS="16")
        for k in ("TPUMX_CHAOS", "TPUMX_TRACING", "TPUMX_TELEMETRY"):
            base_env.pop(k, None)
        try:
            run = subprocess.run([sys.executable, worker], env=base_env,
                                 cwd=repo, capture_output=True, text=True,
                                 timeout=300)
        except subprocess.TimeoutExpired as e:
            print(f"  soak: sdc baseline timed out: {e}")
            return 1
        if run.returncode != 0:
            print(f"  soak: sdc baseline run failed "
                  f"(rc={run.returncode}):\n"
                  f"{((run.stdout or '') + (run.stderr or ''))[-4000:]}")
            return run.returncode or 1
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   TPUMX_TELEMETRY=ctl_jsonl, TPUMX_REPO=repo,
                   TPUMX_CI_DIR=d, TPUMX_CI_STEPS="16")
        for k in ("TPUMX_CHAOS", "TPUMX_TRACING", "TPUMX_CI_BASELINE"):
            env.pop(k, None)
        argv = [sys.executable, os.path.join(repo, "tools", "launch.py"),
                "--supervise", "-n", "3", "--fleet-dir", fleet_dir,
                "--max-restarts", "2", "--backoff", "1.0",
                "--lease", "4.0", "--join-timeout", "60",
                "--min-workers", "1",
                # the flip lands AFTER commit 6 on rank 1 only — the
                # step-8 vote is the first to see the divergence
                "--env", "TPUMX_CHAOS=bitflip_param_at_step=6,"
                         "bitflip_rank=1",
                sys.executable, worker]
        try:
            run = subprocess.run(argv, env=env, cwd=repo,
                                 capture_output=True, text=True,
                                 timeout=600)
        except subprocess.TimeoutExpired as e:
            print(f"  soak: sdc supervised run timed out: {e}")
            return 1
        out = (run.stdout or "") + (run.stderr or "")
        # rc 1 is EXPECTED: a quarantine is a degraded outcome and
        # supervise surfaces any nonzero worker exit as a failed launch
        if run.returncode not in (0, 1):
            print(f"  soak: sdc supervised run died "
                  f"(rc={run.returncode}):\n{out[-4000:]}")
            return run.returncode or 1
        if "WORKER QUARANTINED 1" not in out:
            print(f"  soak: sdc: rank 1 never self-quarantined:\n"
                  f"{out[-4000:]}")
            return 1
        if "WORKER DONE 0" not in out or "WORKER DONE 2" not in out:
            print(f"  soak: sdc: a survivor did not finish:\n"
                  f"{out[-4000:]}")
            return 1
        if "worker 1 quarantined" not in out:
            print(f"  soak: sdc: launcher never refused the restart:\n"
                  f"{out[-4000:]}")
            return 1
        if "worker 1 exited 3; restart" in out:
            print(f"  soak: sdc: launcher RESPAWNED a quarantined "
                  f"rank:\n{out[-4000:]}")
            return 1
        qrec = os.path.join(fleet_dir, "quarantine", "1.json")
        if not os.path.exists(qrec):
            print(f"  soak: sdc: no quarantine record at {qrec}")
            return 1
        # zero-correctness-cost rollback: both survivors' final weights
        # bit-equal to the uninjected fixed-seed run
        base = np.load(os.path.join(d, "final-baseline.npz"))
        for rank in (0, 2):
            fin = np.load(os.path.join(d, f"final-{rank}.npz"))
            for k in base.files:
                a, b = base[k], fin[k]
                if a.dtype != b.dtype or a.shape != b.shape \
                        or a.tobytes() != b.tobytes():
                    print(f"  soak: sdc: rank {rank} final weights "
                          f"diverge from the uninjected run at {k!r}")
                    return 1
        # the black box must carry the corruption verdict, and the
        # report tool must validate it on a machine with NO accelerator
        # stack (poisoned jax/tpu_mx)
        box = os.path.join(fleet_dir, "fleet-blackbox.json")
        try:
            with open(box, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            print(f"  soak: sdc: no readable fleet black box at "
                  f"{box}: {e}")
            return 1
        cv = (((doc.get("fleet") or {}).get("corruption") or {})
              .get("verdict") or {})
        if cv.get("clean") is not False or cv.get("quarantined") != [1] \
                or cv.get("suspected") != [1] \
                or not cv.get("mismatch_steps"):
            print(f"  soak: sdc: black box corruption verdict wrong: "
                  f"{cv}")
            return 1
        report = os.path.join(repo, "tools", "fleet_report.py")
        poison = ("import sys, runpy; sys.modules['jax'] = None; "
                  "sys.modules['tpu_mx'] = None; "
                  f"sys.argv = ['fleet_report', {box!r}, '--validate']; "
                  f"runpy.run_path({report!r}, run_name='__main__')")
        try:
            rep = subprocess.run([sys.executable, "-c", poison],
                                 capture_output=True, text=True,
                                 timeout=120)
        except subprocess.TimeoutExpired as e:
            print(f"  soak: sdc: fleet_report timed out: {e}")
            return 1
        if rep.returncode != 0 or "QUARANTINED" not in (rep.stdout or ""):
            print(f"  soak: sdc: fleet_report --validate failed "
                  f"(rc={rep.returncode}):\n"
                  f"{((rep.stdout or '') + (rep.stderr or ''))[-3000:]}")
            return rep.returncode or 1
        # merged telemetry: fingerprints published, votes held, the
        # injected flip counted as a mismatch, the corrupt rank counted
        # as quarantined
        files = [ctl_jsonl] + [os.path.join(d, f"worker-{r}.jsonl")
                               for r in (0, 1, 2)]
        missing = [p for p in files if not os.path.exists(p)]
        if missing:
            print(f"  soak: sdc: missing telemetry file(s): {missing}")
            return 1
        try:
            val = subprocess.run(
                [sys.executable, os.path.join(repo, "tools",
                                              "telemetry_report.py"),
                 "--merge", *files, "--validate",
                 "--require", "integrity"],
                capture_output=True, text=True, timeout=120)
        except subprocess.TimeoutExpired as e:
            print(f"  soak: sdc: merged validation timed out: {e}")
            return 1
        if val.returncode != 0:
            print(f"  soak: sdc: merged telemetry validation failed "
                  f"(rc={val.returncode}):\n"
                  f"{((val.stdout or '') + (val.stderr or ''))[-3000:]}")
            return val.returncode or 1
        print("  soak: sdc: rank 1 voted out + quarantined, restart "
              "refused, survivors bit-equal to uninjected run, "
              "corruption verdict valid")
    return 0


def obs_tier():
    """Run the instrumented train loop with TPUMX_TELEMETRY set, then
    validate the emitted JSONL (schema + metric-name catalog + required
    nonzero metrics).  Returns a process-style rc (0 = green)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with tempfile.TemporaryDirectory() as d:
        jsonl = os.path.join(d, "telemetry.jsonl")
        env = dict(os.environ, TPUMX_TELEMETRY=jsonl, JAX_PLATFORMS="cpu")
        env.pop("TPUMX_CHAOS", None)  # a chaos-armed env would tear the run
        # TPUMX_FUSION=0 would force the bulk() blocks eager and zero the
        # required fusion.flushes
        env.pop("TPUMX_FUSION", None)
        try:
            run = subprocess.run([sys.executable, "-c", OBS_SCRIPT],
                                 env=env, cwd=repo, capture_output=True,
                                 text=True, timeout=600)
        except subprocess.TimeoutExpired as e:
            print(f"  obs: train loop timed out: {e}")
            return 1
        if run.returncode != 0:
            print(f"  obs: train loop failed (rc={run.returncode}):\n"
                  f"{((run.stdout or '') + (run.stderr or ''))[-3000:]}")
            return run.returncode or 1
        try:
            val = subprocess.run(
                [sys.executable, os.path.join(repo, "tools",
                                              "telemetry_report.py"),
                 jsonl, "--validate", "--require", ",".join(OBS_REQUIRED)],
                capture_output=True, text=True, timeout=120)
        except subprocess.TimeoutExpired as e:
            print(f"  obs: telemetry validation timed out: {e}")
            return 1
        out = (val.stdout or "") + (val.stderr or "")
        if val.returncode != 0:
            print(f"  obs: telemetry validation failed "
                  f"(rc={val.returncode}):\n{out[-3000:]}")
            return val.returncode or 1
        # the SLO ops surface must schema-gate the same snapshot (rc
        # 0/1/2 contract like blackbox_report): window sub-objects are
        # part of the record schema, and a training-only file must
        # render cleanly (no serving data is "no data", not an error)
        try:
            slo = subprocess.run(
                [sys.executable, os.path.join(repo, "tools",
                                              "slo_report.py"),
                 jsonl, "--validate"],
                capture_output=True, text=True, timeout=120)
        except subprocess.TimeoutExpired as e:
            print(f"  obs: slo_report validation timed out: {e}")
            return 1
        if slo.returncode != 0:
            print(f"  obs: slo_report validation failed "
                  f"(rc={slo.returncode}):\n"
                  f"{((slo.stdout or '') + (slo.stderr or ''))[-3000:]}")
            return slo.returncode or 1
        # capacity_report must hold to the same rc contract on a
        # training-only snapshot: no serving data renders as "no data",
        # never as an error, and the training-side twins (per-shape
        # compiles, checkpoint bytes, host RSS) validate in catalog
        try:
            cap = subprocess.run(
                [sys.executable, os.path.join(repo, "tools",
                                              "capacity_report.py"),
                 jsonl, "--validate"],
                capture_output=True, text=True, timeout=120)
        except subprocess.TimeoutExpired as e:
            print(f"  obs: capacity_report validation timed out: {e}")
            return 1
        if cap.returncode != 0:
            print(f"  obs: capacity_report validation failed "
                  f"(rc={cap.returncode}):\n"
                  f"{((cap.stdout or '') + (cap.stderr or ''))[-3000:]}")
            return cap.returncode or 1
        rc = _blackbox_leg(repo, env)
        if rc != 0:
            return rc
    return 0


def _blackbox_leg(repo, env):
    """Chaos-crash a supervised run per failure class (hang, NaN streak,
    crash, SIGTERM) and assert each leaves a schema-valid black box whose
    timeline links injection -> detection -> decision — then render every
    box with tools/blackbox_report.py under a POISONED jax import, the
    proof the post-mortem path needs no accelerator stack."""
    with tempfile.TemporaryDirectory() as d:
        env = dict(env, TPUMX_BLACKBOX_DIR=d)
        env.pop("TPUMX_TRACING", None)  # the recorder must be armed
        try:
            run = subprocess.run([sys.executable, "-c", BLACKBOX_SCRIPT],
                                 env=env, cwd=repo, capture_output=True,
                                 text=True, timeout=600)
        except subprocess.TimeoutExpired as e:
            print(f"  obs: blackbox leg timed out: {e}")
            return 1
        if run.returncode != 0 or "BLACKBOX OK" not in (run.stdout or ""):
            print(f"  obs: blackbox leg failed (rc={run.returncode}):\n"
                  f"{((run.stdout or '') + (run.stderr or ''))[-4000:]}")
            return run.returncode or 1
        report = os.path.join(repo, "tools", "blackbox_report.py")
        for tag, expect in BLACKBOX_EXPECT.items():
            box = os.path.join(d, f"{tag}-blackbox.json")
            # poison jax/tpu_mx in sys.modules: if the report tool (or
            # anything it loads) tries to import either, it fails loudly
            code = ("import sys, runpy; "
                    "sys.modules['jax'] = None; "
                    "sys.modules['tpu_mx'] = None; "
                    f"sys.argv = ['blackbox_report.py', {box!r}, "
                    "'--validate']; "
                    f"runpy.run_path({report!r}, run_name='__main__')")
            try:
                ren = subprocess.run([sys.executable, "-c", code],
                                     capture_output=True, text=True,
                                     timeout=120)
            except subprocess.TimeoutExpired as e:
                print(f"  obs: blackbox report timed out on {tag}: {e}")
                return 1
            out = (ren.stdout or "") + (ren.stderr or "")
            # runpy re-raises SystemExit(0) silently; nonzero -> rc != 0
            if ren.returncode != 0:
                print(f"  obs: blackbox report failed on {tag} "
                      f"(rc={ren.returncode}):\n{out[-3000:]}")
                return 1
            missing = [m for m in expect if m not in out]
            if missing:
                print(f"  obs: blackbox report for {tag} is missing "
                      f"timeline markers {missing}:\n{out[-3000:]}")
                return 1
    return 0


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--core-only", action="store_true",
                    help="run just the <5 min core tier")
    opts = ap.parse_args()  # unknown args fail fast, not silently run all
    tiers = TIERS[:1] if opts.core_only else TIERS
    results = []
    # lint first, ALWAYS (core-only included): seconds of static checking
    # that fails the build before any pytest time is spent
    t0 = time.time()
    results.append(("lint", lint_tier(), time.time() - t0))
    for name, args, env_extra in tiers:
        t0 = time.time()
        env = None
        if env_extra:
            env = dict(os.environ)
            env.update(env_extra)
        proc = subprocess.run([sys.executable, "-m", "pytest", "-q", *args],
                              env=env)
        results.append((name, proc.returncode, time.time() - t0))
    if not opts.core_only:
        t0 = time.time()
        results.append(("native-asan", native_asan(), time.time() - t0))
        t0 = time.time()
        results.append(("obs", obs_tier(), time.time() - t0))
        t0 = time.time()
        results.append(("soak", soak_tier(), time.time() - t0))
        t0 = time.time()
        results.append(("serve", serve_tier(), time.time() - t0))
    print()
    red = False
    for name, rc, dt in results:
        status = "PASS" if rc == 0 else "FAIL"
        red = red or rc != 0
        print(f"  {status}  {name:10s} {dt:7.1f}s")
    if red:
        print("\n" + "!" * 64)
        print("!!  TEST SUITE RED — do NOT snapshot/ship this state  !!")
        print("!" * 64)
        return 1
    print("\nall tiers green")
    return 0


if __name__ == "__main__":
    sys.exit(main())
