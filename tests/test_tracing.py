"""Flight recorder (tpu_mx/tracing.py) — ISSUE 7.

Covers: the bounded ring buffer (memory under sustained emit,
thread-safety under concurrent emit+snapshot), the typed KNOWN_EVENTS
catalog, trace-context propagation across the watchdog thread boundary,
the subsystem instrumentation (train-step phases, fusion flushes,
capsule writes, chaos injections), and the crash black box on EVERY
supervisor exit path — watchdog restart, numeric rollback, transient
crash, degrade, SIGTERM preemption — each schema-valid and correlated
(injection -> detection -> decision share the (epoch, step, generation)
trace context)."""
import json
import os
import signal
import threading
import time

import numpy as np
import pytest

import tpu_mx as mx
from tpu_mx import checkpoint as ckpt, elastic, nd, supervisor, telemetry, \
    tracing
from tpu_mx.contrib import chaos
from tpu_mx.gluon import nn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_recorder():
    """Tracing state is process-global by design — isolate every test."""
    tracing.reset()
    tracing.configure(enabled=True, capacity=512)
    yield
    tracing.reset()
    tracing.configure(enabled=True, capacity=512)


def events(name=None):
    evs = tracing.snapshot()
    return [e for e in evs if name is None or e["event"] == name]


# ---------------------------------------------------------------------------
# emit + catalog
# ---------------------------------------------------------------------------
def test_emit_stamps_trace_context():
    tracing.set_context(epoch=3, step=12, generation=2)
    rec = tracing.emit("chaos.inject", kind="hang")
    assert rec["epoch"] == 3 and rec["step"] == 12
    assert rec["generation"] == 2
    assert rec["run_id"] and isinstance(rec["ts"], float)
    assert rec["data"] == {"kind": "hang"}
    tracing.validate_event(rec)


def test_unknown_event_name_rejected():
    with pytest.raises(ValueError, match="unknown event name"):
        tracing.emit("supervisor.totally_new_event")


def test_undeclared_payload_field_rejected():
    with pytest.raises(ValueError, match="undeclared payload field"):
        tracing.emit("chaos.inject", kind="hang", severity=9)


def test_payload_types_enforced():
    with pytest.raises(ValueError, match="must be str"):
        tracing.emit("chaos.inject", kind=42)
    with pytest.raises(ValueError, match="must be int"):
        tracing.emit("fusion.flush", cause="read_barrier", ops="three")
    # float fields accept ints; bool is NOT an int here
    tracing.emit("train_step.phase", phase="dispatch", seconds=1)
    with pytest.raises(ValueError, match="must be int"):
        tracing.emit("fusion.flush", cause="x", ops=True)


def test_unknown_context_field_rejected():
    with pytest.raises(ValueError, match="unknown trace-context field"):
        tracing.set_context(world_size=8)


def test_emit_is_reentrant_for_signal_handlers():
    """The SIGTERM preemption handler runs on the main thread between
    bytecodes and emits events — if the interrupted frame holds the
    tracing lock, emit must not self-deadlock (the lock is reentrant by
    requirement)."""
    with tracing._lock:
        rec = tracing.emit("chaos.inject", kind="hang")
    assert rec is not None


def test_nonfinite_floats_encode_as_strings_strict_json(tmp_path):
    """Strict JSON has no NaN token; a NaN loss — exactly what a
    divergence box records — must encode as its string form so jq /
    browsers / any spec-compliant reader can parse the box."""
    rec = tracing.emit("supervisor.sentinel_skip", loss=float("nan"),
                       consecutive_bad=1)
    assert rec["data"]["loss"] == "nan"
    assert tracing.emit("train_step.phase", phase="dispatch",
                        seconds=float("inf"))["data"]["seconds"] == "inf"
    assert tracing.emit("train_step.phase", phase="dispatch",
                        seconds=float("-inf"))["data"]["seconds"] == "-inf"
    tracing.validate_event(rec)  # the string spelling is schema-legal
    path = tracing.dump_blackbox(str(tmp_path / "ck"), reason="nan box")
    with open(path, encoding="utf-8") as f:
        text = f.read()
    assert "NaN" not in text and "Infinity" not in text
    tracing.validate_blackbox(json.loads(text))


def test_span_endpoints_fill_seconds():
    t0 = time.perf_counter()
    rec = tracing.emit("train_step.phase", t0=t0, t1=t0 + 0.25,
                       phase="dispatch")
    assert rec["data"]["seconds"] == pytest.approx(0.25)


def test_events_merge_into_profiler_with_qualified_names(tmp_path,
                                                         monkeypatch):
    """Chrome-trace merge: the span name carries the categorical field
    — five phases must not collapse into one aggregate row."""
    from tpu_mx import profiler
    # the session's chrome trace and XLA trace go under tmp_path, not
    # the working directory
    monkeypatch.setitem(profiler._state, "filename",
                        str(tmp_path / "profile.json"))
    monkeypatch.setitem(profiler._state, "trace_dir",
                        str(tmp_path / "profile_xla_trace"))
    profiler.set_state("run")
    try:
        t0 = time.perf_counter()
        tracing.emit("train_step.phase", t0=t0, t1=t0 + 0.001,
                     phase="dispatch")
        tracing.emit("train_step.phase", t0=t0, t1=t0 + 0.002,
                     phase="loss_readback")
        tracing.emit("chaos.inject", kind="hang")
        names = {e["name"] for e in profiler._events
                 if e.get("cat") == "tracing"}
    finally:
        profiler.set_state("stop")
        profiler.dumps(reset=True)
    assert {"train_step.phase:dispatch", "train_step.phase:loss_readback",
            "chaos.inject:hang"} <= names


def test_validate_event_rejections():
    good = tracing.emit("chaos.inject", kind="nan")
    for mutate, match in [
            (lambda r: r.update(event="nope"), "unknown event name"),
            (lambda r: r.pop("ts"), "numeric 'ts'"),
            (lambda r: r.update(run_id=""), "run_id"),
            (lambda r: r.update(generation="x"), "generation"),
            (lambda r: r.update(epoch="x"), "epoch"),
            (lambda r: r.update(data={"kind": 7}), "must be str"),
            (lambda r: r.update(data={"oops": 1}), "undeclared")]:
        bad = dict(good, data=dict(good["data"]))
        mutate(bad)
        with pytest.raises(ValueError, match=match):
            tracing.validate_event(bad)


def test_disabled_path_records_nothing():
    tracing.configure(enabled=False)
    assert tracing.emit("chaos.inject", kind="hang") is None
    assert tracing.snapshot() == []
    assert tracing.stats()["emitted"] == 0
    tracing.configure(enabled=True)
    assert tracing.emit("chaos.inject", kind="hang") is not None


# ---------------------------------------------------------------------------
# the ring buffer
# ---------------------------------------------------------------------------
def test_ring_bounded_under_sustained_emit():
    tracing.configure(capacity=64)
    for i in range(10_000):
        tracing.emit("train_step.phase", phase="dispatch", seconds=0.001)
    st = tracing.stats()
    assert st["size"] == 64 and st["capacity"] == 64
    assert st["emitted"] == 10_000
    assert st["dropped"] == 10_000 - 64
    assert len(tracing.snapshot()) == 64


def test_snapshot_keeps_newest_and_last_n():
    tracing.configure(capacity=4)
    for i in range(8):
        tracing.emit("fusion.flush", cause=f"c{i}", ops=i)
    causes = [e["data"]["cause"] for e in tracing.snapshot()]
    assert causes == ["c4", "c5", "c6", "c7"]  # oldest evicted, order kept
    assert [e["data"]["cause"] for e in tracing.snapshot(last=2)] \
        == ["c6", "c7"]


def test_configure_capacity_keeps_newest():
    for i in range(10):
        tracing.emit("fusion.flush", cause=f"c{i}", ops=i)
    tracing.configure(capacity=3)
    assert [e["data"]["cause"] for e in tracing.snapshot()] \
        == ["c7", "c8", "c9"]
    with pytest.raises(ValueError):
        tracing.configure(capacity=0)


def test_thread_safety_concurrent_emit_and_snapshot():
    tracing.configure(capacity=128)
    N_THREADS, N_EMITS = 8, 500
    errors = []
    stop = threading.Event()

    def emitter(tid):
        try:
            for i in range(N_EMITS):
                tracing.emit("train_step.phase", phase="dispatch",
                             seconds=float(i))
        except Exception as e:  # pragma: no cover - the failure mode
            errors.append(e)

    def snapshotter():
        try:
            while not stop.is_set():
                for rec in tracing.snapshot():
                    tracing.validate_event(rec)  # never a torn record
                tracing.stats()
        except Exception as e:  # pragma: no cover - the failure mode
            errors.append(e)

    threads = [threading.Thread(target=emitter, args=(t,), daemon=True)
               for t in range(N_THREADS)]
    snap = threading.Thread(target=snapshotter, daemon=True)
    snap.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    stop.set()
    snap.join(30)
    assert not errors
    st = tracing.stats()
    assert st["emitted"] == N_THREADS * N_EMITS
    assert st["size"] == 128
    assert st["dropped"] == st["emitted"] - 128


def test_context_propagates_across_watchdog_thread():
    """The satellite proof: the supervisor runs steps on a daemon
    watchdog thread; an event emitted THERE must carry the step context
    set on the main thread (the context is process-global, not
    thread-local)."""
    tracing.set_context(epoch=5, step=7, generation=1)
    tid = {}

    def on_watchdog_thread():
        tid["worker"] = threading.get_ident()
        return tracing.emit("chaos.inject", kind="hang")

    rec = supervisor.run_with_deadline(on_watchdog_thread, 5.0)
    assert tid["worker"] != threading.get_ident()  # really another thread
    assert (rec["epoch"], rec["step"], rec["generation"]) == (5, 7, 1)


# ---------------------------------------------------------------------------
# subsystem instrumentation
# ---------------------------------------------------------------------------
def _train_step():
    from tpu_mx import gluon
    from tpu_mx.parallel import CompiledTrainStep
    net = nn.HybridSequential()
    net.add(nn.Dense(6, activation="relu"), nn.Dense(2))
    net.initialize()
    net(nd.ones((1, 4)))
    return net, CompiledTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.create("sgd", learning_rate=0.05))


def test_train_step_phase_events():
    net, step = _train_step()
    X = np.random.RandomState(0).rand(8, 4).astype(np.float32)
    Y = (X.sum(1) > 2).astype(np.float32)
    for _ in range(2):
        step.step(nd.array(X), nd.array(Y))
    phases = [e["data"]["phase"] for e in events("train_step.phase")]
    assert phases.count("data_wait") == 2
    assert phases.count("dispatch") == 2
    assert phases.count("optimizer_update") == 2
    assert phases.count("recompile") == 1  # first step only
    for e in events("train_step.phase"):
        assert e["data"]["seconds"] >= 0
        assert e["data"]["phase"] in tracing.TRAIN_STEP_PHASES


def test_train_step_loss_readback_phase_under_watchdog():
    net, step = _train_step()
    X = np.random.RandomState(0).rand(4, 4).astype(np.float32)
    Y = (X.sum(1) > 2).astype(np.float32)
    step.step(nd.array(X), nd.array(Y), deadline=30.0)
    phases = [e["data"]["phase"] for e in events("train_step.phase")]
    assert "loss_readback" in phases


def test_fusion_flush_event():
    from tpu_mx import engine
    x = nd.array(np.ones((4, 4), np.float32))
    with engine.bulk(8):
        nd.tanh(x * 1.5 + 0.5).wait_to_read()
    flushes = events("fusion.flush")
    assert flushes, "no fusion.flush event emitted"
    assert flushes[-1]["data"]["cause"] == "read_barrier"
    assert flushes[-1]["data"]["ops"] >= 3


def test_checkpoint_and_capsule_events(tmp_path):
    from tpu_mx import resume as tresume
    prefix = str(tmp_path / "ck")
    net = nn.Dense(3, in_units=4)
    net.initialize()
    elastic.save_checkpoint(prefix, 0, net=net)
    mgr = tresume.CapsuleManager(prefix)
    mgr.write_epoch_file(0)
    ckpt.verify_checkpoint(prefix, 0)
    assert events("checkpoint.save")[-1]["data"]["epoch"] == 0
    assert events("resume.capsule_write")[-1]["data"]["kind"] == "epoch"
    ver = events("checkpoint.verify")[-1]["data"]
    assert ver["epoch"] == 0 and ver["status"] == "verified"


def test_chaos_injection_shares_step_context():
    tracing.set_context(epoch=2, step=9, generation=0)
    with chaos.enable(nan_after=1):
        assert np.isnan(chaos.poison_loss(1.0))
    inj = events("chaos.inject")[-1]
    assert inj["data"]["kind"] == "nan"
    assert (inj["epoch"], inj["step"]) == (2, 9)


# ---------------------------------------------------------------------------
# the black box
# ---------------------------------------------------------------------------
def test_dump_blackbox_schema_and_atomicity(tmp_path):
    tracing.set_context(epoch=1, step=2, generation=0)
    tracing.emit("chaos.inject", kind="hang")
    before = telemetry.counter("tracing.blackbox_dumps").value
    path = tracing.dump_blackbox(str(tmp_path / "ck"), reason="unit test")
    assert path == str(tmp_path / "ck-blackbox.json")
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    tracing.validate_blackbox(doc)
    assert doc["reason"] == "unit test"
    assert doc["context"]["epoch"] == 1
    assert any(e["event"] == "chaos.inject" for e in doc["events"])
    assert doc["environment"]["pid"] == os.getpid()
    # the telemetry snapshot rode along, schema-valid
    for rec in doc["telemetry"]:
        telemetry.validate_record(rec)
    assert telemetry.counter("tracing.blackbox_dumps").value == before + 1
    # went through atomic_write: no tmp debris next to it
    assert not [p for p in os.listdir(tmp_path) if ".tmp." in p]


def test_validate_blackbox_rejections(tmp_path):
    doc = tracing.blackbox_doc(reason="x")
    tracing.validate_blackbox(doc)
    with pytest.raises(ValueError, match="format"):
        tracing.validate_blackbox(dict(doc, format="v999"))
    with pytest.raises(ValueError, match="events"):
        tracing.validate_blackbox(dict(doc, events="nope"))
    bad_event = dict(doc, events=[{"event": "nope"}])
    with pytest.raises(ValueError, match=r"events\[0\]"):
        tracing.validate_blackbox(bad_event)
    with pytest.raises(ValueError, match="context"):
        tracing.validate_blackbox(dict(doc, context={"run_id": "r"}))
    # an EXTRA context key must not mask a missing required one (the
    # generation field is what the correlation join relies on)
    with pytest.raises(ValueError, match="context"):
        tracing.validate_blackbox(dict(doc, context={
            "run_id": "r", "epoch": 1, "step": 2, "extra": 1}))


# -- every supervisor exit path dumps one --------------------------------
def _sup(prefix, **kw):
    kw.setdefault("backoff", 0.01)
    kw.setdefault("seed", 0)
    kw.setdefault("blackbox", prefix)
    return supervisor.Supervisor(**kw)


def _load_box(prefix):
    path = tracing.blackbox_path(prefix)
    assert os.path.exists(path), "no black box dumped"
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    tracing.validate_blackbox(doc)
    return doc


def _chain(doc, kind, *wanted):
    """Injection -> detection -> decision share (epoch, generation)."""
    evs = doc["events"]
    inj = [e for e in evs if e["event"] == "chaos.inject"
           and e["data"]["kind"] == kind]
    assert inj, [e["event"] for e in evs]
    key = (inj[0]["epoch"], inj[0]["generation"])
    got = [e["event"] for e in evs if (e["epoch"], e["generation"]) == key]
    for name in wanted:
        assert name in got, (kind, name, got)
    return inj[0]


def test_blackbox_on_watchdog_restart(tmp_path):
    prefix = str(tmp_path / "ck")
    sup = _sup(prefix, restore_fn=lambda: 0, deadline=0.2,
               compile_grace=0.0)
    armed = {"on": True}

    def epoch_fn(epoch):
        for _ in range(2):
            if epoch == 0 and armed["on"]:
                armed["on"] = False
                with chaos.enable(hang_step=1, hang_seconds=10.0):
                    sup.step(lambda: 1.0)
            else:
                sup.step(lambda: 1.0)

    res = sup.run(epoch_fn, num_epoch=2)
    assert res.ok and res.watchdog_fires == 1
    doc = _load_box(prefix)
    inj = _chain(doc, "hang", "supervisor.watchdog_fire",
                 "supervisor.classify", "supervisor.restart")
    assert inj["step"] == 1
    cls = [e for e in doc["events"] if e["event"] == "supervisor.classify"]
    assert cls[0]["data"]["kind"] == "transient"


def test_blackbox_on_numeric_rollback(tmp_path):
    prefix = str(tmp_path / "ck")
    net = nn.Dense(3, in_units=4)
    net.initialize()
    sup = _sup(prefix,
               save_fn=lambda e: elastic.save_checkpoint(prefix, e, net=net),
               restore_fn=lambda: elastic.auto_resume(prefix, net=net),
               skip_limit=1)
    armed = {"on": True}

    def epoch_fn(epoch):
        if epoch == 1 and armed["on"]:
            armed["on"] = False
            with chaos.enable(nan_after=1, nan_streak=2):
                for _ in range(3):
                    sup.step(lambda: 1.0)
        else:
            for _ in range(3):
                sup.step(lambda: 1.0)

    res = sup.run(epoch_fn, num_epoch=3)
    assert res.ok and res.rollbacks == 1
    doc = _load_box(prefix)
    _chain(doc, "nan", "supervisor.sentinel_skip", "supervisor.classify",
           "supervisor.rollback")
    skips = [e for e in doc["events"]
             if e["event"] == "supervisor.sentinel_skip"]
    assert skips and skips[0]["data"]["consecutive_bad"] == 1
    assert skips[0]["data"]["loss"] == "nan"  # strict-JSON encoding


def test_blackbox_on_transient_crash_restart(tmp_path):
    prefix = str(tmp_path / "ck")
    net = nn.Dense(3, in_units=4)
    net.initialize()
    sup = _sup(prefix,
               save_fn=lambda e: elastic.save_checkpoint(prefix, e, net=net),
               restore_fn=lambda: elastic.auto_resume(prefix, net=net))
    armed = {"on": True}

    def save_and_maybe_crash(epoch):
        if epoch == 1 and armed["on"]:
            armed["on"] = False
            with chaos.enable(crash_after_bytes=50, match=".params"):
                elastic.save_checkpoint(prefix, epoch, net=net)
        else:
            elastic.save_checkpoint(prefix, epoch, net=net)

    sup.save_fn = save_and_maybe_crash

    def epoch_fn(epoch):
        for _ in range(2):
            sup.step(lambda: 1.0)

    res = sup.run(epoch_fn, num_epoch=3)
    assert res.ok and res.restarts == 1
    doc = _load_box(prefix)
    _chain(doc, "crash", "supervisor.classify", "supervisor.restart")


def test_blackbox_on_degrade(tmp_path):
    prefix = str(tmp_path / "ck")
    sup = _sup(prefix, restore_fn=lambda: 0, max_restarts=1)

    def epoch_fn(epoch):
        raise OSError("persistent fault")

    res = sup.run(epoch_fn, num_epoch=2)
    assert res.status == "degraded"
    doc = _load_box(prefix)
    names = [e["event"] for e in doc["events"]]
    assert "supervisor.degrade" in names
    deg = [e for e in doc["events"]
           if e["event"] == "supervisor.degrade"][0]
    assert deg["data"]["budget"] == "restarts"
    assert "black box" not in doc["reason"] or doc["reason"]
    assert doc["reason"].startswith("degraded:")


def test_blackbox_on_sigterm_preemption(tmp_path):
    prefix = str(tmp_path / "ck")
    net = nn.Dense(3, in_units=4)
    net.initialize()
    handle = ckpt.preemption_handler(
        lambda: elastic.save_checkpoint(prefix, 0, net=net),
        exit=False, blackbox_prefix=prefix)
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        for _ in range(200):
            if handle.triggered:
                break
            time.sleep(0.01)
    finally:
        handle.uninstall()
    assert handle.triggered and handle.save_ok
    doc = _load_box(prefix)
    pre = [e for e in doc["events"]
           if e["event"] == "checkpoint.preemption"]
    assert pre and pre[0]["data"]["save_ok"] is True
    assert pre[0]["data"]["signum"] == signal.SIGTERM
    assert doc["reason"].startswith("preemption signal")


def test_blackbox_dump_failure_never_masks_the_fault(tmp_path,
                                                     monkeypatch):
    """A broken dump path must not turn a recoverable fault into a new
    crash — forensics are best-effort."""
    prefix = str(tmp_path / "ck")
    sup = _sup(prefix, restore_fn=lambda: 0, max_restarts=2)
    monkeypatch.setattr(tracing, "dump_blackbox",
                        lambda *a, **k: 1 / 0)
    armed = {"on": True}

    def epoch_fn(epoch):
        if armed["on"]:
            armed["on"] = False
            raise OSError("transient")

    res = sup.run(epoch_fn, num_epoch=1)
    assert res.ok and res.restarts == 1


# ---------------------------------------------------------------------------
# blackbox_report.py (rendered WITHOUT jax — subprocess-proven)
# ---------------------------------------------------------------------------
def _report(box_path, *extra):
    import subprocess
    import sys
    report = os.path.join(REPO, "tools", "blackbox_report.py")
    args = [box_path, *extra]
    code = ("import sys, runpy; "
            "sys.modules['jax'] = None; sys.modules['tpu_mx'] = None; "
            f"sys.argv = ['blackbox_report.py'] + {list(args)!r}; "
            f"runpy.run_path({report!r}, run_name='__main__')")
    return subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120)


def test_blackbox_report_renders_without_jax(tmp_path):
    tracing.set_context(epoch=2, step=3, generation=0)
    tracing.emit("chaos.inject", kind="hang")
    tracing.emit("supervisor.watchdog_fire", name="step@epoch2",
                 deadline_seconds=30.0)
    tracing.emit("supervisor.classify", kind="transient",
                 error="WatchdogTimeout", message="hung")
    tracing.emit("supervisor.restart", n=2, backoff_seconds=0.5,
                 resume_epoch=3)
    path = tracing.dump_blackbox(str(tmp_path / "ck"), reason="unit")
    run = _report(path, "--validate")
    assert run.returncode == 0, run.stdout + run.stderr
    out = run.stdout
    # the human-readable chain the ISSUE asks for, one line
    assert "chaos hang injected -> watchdog fired at 30s -> " \
           "classified transient (WatchdogTimeout) -> " \
           "restart #2 from epoch 3" in out
    assert "epoch 2 step 3:" in out
    assert "schema OK" in out


def test_blackbox_report_validate_fails_on_bad_box(tmp_path):
    path = str(tmp_path / "bad-blackbox.json")
    doc = tracing.blackbox_doc()
    doc["events"] = [{"event": "not.in.catalog"}]
    with open(path, "w", encoding="utf-8") as f:
        f.write(json.dumps(doc))
    run = _report(path, "--validate")
    assert run.returncode == 1
    assert "VALIDATION FAILED" in run.stderr
    # without --validate it still renders (post-mortems beat strictness)
    run2 = _report(path)
    assert run2.returncode == 0
    run3 = _report(str(tmp_path / "missing.json"))
    assert run3.returncode == 2
