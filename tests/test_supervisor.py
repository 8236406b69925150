"""Self-healing supervisor (tpu_mx/supervisor.py) — every recovery path
is PROVOKED via chaos injection, not assumed (ISSUE 4).

Covers: the hung-step watchdog (incl. recompile-aware grace and the
deliberately hung elastic.barrier), the numeric sentinel (skip budget,
spike + grad-norm detection), failure classification, rollback to the
last *good* epoch under injected divergence (in-process AND subprocess),
transient restarts with resume, graceful degradation, and the
module.fit(supervised=) integration."""
import contextlib
import json
import math
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import tpu_mx as mx
from tpu_mx import checkpoint as ckpt, elastic, nd, resume, supervisor, \
    telemetry
from tpu_mx.contrib import chaos
from tpu_mx.gluon import nn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _dense(value=1.0):
    net = nn.Dense(3, in_units=4)
    net.initialize()
    net.weight.set_data(nd.full((3, 4), float(value)))
    return net


def _sup(**kw):
    kw.setdefault("backoff", 0.01)
    kw.setdefault("seed", 0)
    return supervisor.Supervisor(**kw)


# ---------------------------------------------------------------------------
# run_with_deadline: the watchdog primitive
# ---------------------------------------------------------------------------
def test_watchdog_passes_value_and_exceptions_through():
    assert supervisor.run_with_deadline(lambda: 42, 5.0) == 42
    assert supervisor.run_with_deadline(lambda: 42, None) == 42  # off
    with pytest.raises(ZeroDivisionError):
        supervisor.run_with_deadline(lambda: 1 // 0, 5.0)


def test_watchdog_converts_hang_to_worker_failure():
    before = telemetry.counter("supervisor.watchdog_fires").value
    with pytest.raises(supervisor.WatchdogTimeout, match="hung past"):
        supervisor.run_with_deadline(lambda: time.sleep(5.0), 0.1,
                                     name="hung-step")
    # WatchdogTimeout IS a WorkerFailure (transient for classification)
    assert issubclass(supervisor.WatchdogTimeout, elastic.WorkerFailure)
    assert telemetry.counter("supervisor.watchdog_fires").value == before + 1


def test_watchdog_recompile_grace_extends_deadline():
    """A step past its deadline with the grace signal moved (= a jit build
    started) gets ONE grace extension instead of being killed."""
    sig = [0]

    def compiling_step():
        sig[0] += 1          # "a recompile started"
        time.sleep(0.3)      # ... and outlives the base deadline
        return "compiled"

    assert supervisor.run_with_deadline(
        compiling_step, 0.05, grace=5.0,
        grace_signal=lambda: sig[0]) == "compiled"

    # without a moved signal the same overrun still fires
    with pytest.raises(supervisor.WatchdogTimeout):
        supervisor.run_with_deadline(lambda: time.sleep(0.3), 0.05,
                                     grace=5.0, grace_signal=lambda: 0)


def test_watchdog_against_deliberately_hung_barrier(monkeypatch):
    """The satellite proof: a hung collective inside elastic.barrier (dead
    peer — sync_global_devices never returns) becomes a clean
    WorkerFailure within the timeout, not an eternal hang."""
    import jax
    from jax.experimental import multihost_utils
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jax, "process_index", lambda: 0)
    monkeypatch.setattr(multihost_utils, "sync_global_devices",
                        lambda tag: threading.Event().wait())  # hangs forever
    t0 = time.time()
    with pytest.raises(elastic.WorkerFailure, match="timed out"):
        elastic.barrier("test-hung", timeout=0.2)
    assert time.time() - t0 < 5.0  # returned promptly, not after "forever"


# ---------------------------------------------------------------------------
# numeric sentinel
# ---------------------------------------------------------------------------
def test_sentinel_skip_budget_then_divergence():
    s = supervisor.NumericSentinel(skip_limit=2)
    assert s.observe(1.0) == "ok"
    assert s.observe(float("nan")) == "skip"
    assert s.observe(float("inf")) == "skip"
    assert s.observe(float("nan")) == "diverge"
    # a good batch in between resets the consecutive-bad streak
    s2 = supervisor.NumericSentinel(skip_limit=1)
    assert s2.observe(float("nan")) == "skip"
    assert s2.observe(1.0) == "ok"
    assert s2.observe(float("nan")) == "skip"
    # skip_limit=0: first bad batch escalates immediately
    s3 = supervisor.NumericSentinel(skip_limit=0)
    assert s3.observe(float("nan")) == "diverge"


def test_sentinel_spike_and_grad_norm():
    s = supervisor.NumericSentinel(skip_limit=0, spike_factor=10.0)
    for _ in range(6):
        assert s.observe(2.0) == "ok"
    assert s.observe(2.5) == "ok"          # ordinary wobble
    assert s.observe(50.0) == "diverge"    # 25× the median: a spike
    g = supervisor.NumericSentinel(skip_limit=0, max_grad_norm=100.0)
    assert g.observe(1.0, grad_norm=5.0) == "ok"
    assert g.observe(1.0, grad_norm=500.0) == "diverge"
    assert g.observe(1.0, grad_norm=float("nan")) == "diverge"


def test_classification_table():
    """The failure-classification table from docs/robustness.md."""
    c = supervisor.classify
    assert c(OSError("nfs hiccup")) == "transient"
    assert c(elastic.WorkerFailure("dead peer")) == "transient"
    assert c(supervisor.WatchdogTimeout("hung")) == "transient"
    assert c(chaos.ChaosCrash("simulated kill")) == "transient"
    assert c(supervisor.NumericDivergence("nan")) == "numeric"
    assert c(TypeError("a programming error")) == "fatal"
    assert c(mx.base.MXNetError("bad usage")) == "fatal"
    assert c(KeyboardInterrupt()) == "fatal"


# ---------------------------------------------------------------------------
# the supervised loop: restart / rollback / degrade
# ---------------------------------------------------------------------------
def test_transient_failure_restarts_and_resumes(tmp_path):
    prefix = str(tmp_path / "ck")
    net = _dense(1.0)
    flaky = {"armed": True}
    sup = _sup(save_fn=lambda e: elastic.save_checkpoint(prefix, e, net=net),
               restore_fn=lambda: elastic.auto_resume(prefix, net=net))

    def epoch_fn(epoch):
        if epoch == 2 and flaky["armed"]:
            flaky["armed"] = False
            raise OSError("transient filesystem fault")
        for i in range(2):
            sup.step(lambda: 0.5 + epoch)

    res = sup.run(epoch_fn, begin_epoch=0, num_epoch=4)
    assert res.ok and res.restarts == 1
    assert elastic.latest_checkpoint(prefix)[0] == 3
    assert math.isfinite(res.final_loss)


def test_chaos_hang_step_fires_watchdog_then_recovers(tmp_path):
    """hang_step chaos stalls one step past the deadline; the watchdog
    converts it to a restart and the retried (disarmed) step succeeds."""
    prefix = str(tmp_path / "ck")
    net = _dense(2.0)
    sup = _sup(save_fn=lambda e: elastic.save_checkpoint(prefix, e, net=net),
               restore_fn=lambda: elastic.auto_resume(prefix, net=net),
               deadline=0.2, compile_grace=0.0)
    with chaos.enable(hang_step=3, hang_seconds=30.0) as cfg:
        res = sup.run(lambda epoch: [sup.step(lambda: 1.0)
                                     for _ in range(2)],
                      begin_epoch=0, num_epoch=3)
        assert cfg.hangs == 1
    assert res.ok and res.watchdog_fires == 1 and res.restarts == 1
    assert elastic.latest_checkpoint(prefix)[0] == 2


def test_divergence_rolls_back_to_last_good_epoch(tmp_path):
    """NaN streak past the skip budget → rollback lands on the last GOOD
    epoch's weights, and re-enters AT the poisoned epoch (which was never
    saved)."""
    prefix = str(tmp_path / "ck")
    net = _dense(1.0)
    resumes = []

    def save_fn(epoch):
        # stamp the weights with the epoch so the restore is provable
        net.weight.set_data(nd.full((3, 4), 10.0 + epoch))
        elastic.save_checkpoint(prefix, epoch, net=net)

    def restore_fn():
        e = elastic.auto_resume(prefix, net=net)
        resumes.append(e)
        return e

    sup = _sup(save_fn=save_fn, restore_fn=restore_fn, skip_limit=1)
    poison = {"armed": True}

    def epoch_fn(epoch):
        if epoch == 2 and poison["armed"]:
            poison["armed"] = False
            with chaos.enable(nan_after=1, nan_streak=2):
                for _ in range(3):
                    sup.step(lambda: 1.0)
        else:
            for _ in range(3):
                sup.step(lambda: 1.0)

    res = sup.run(epoch_fn, begin_epoch=0, num_epoch=4)
    assert res.ok
    assert res.rollbacks == 1 and res.batches_skipped == 1
    # initial resume found nothing (0); the rollback resumed FROM epoch 2
    # (last good = epoch 1 — not the poisoned epoch 2, which never saved)
    assert resumes == [0, 2]
    assert elastic.latest_checkpoint(prefix)[0] == 3
    # weights on disk for epoch 1 are the last-good stamp
    net2 = nn.Dense(3, in_units=4)
    for epoch, params in elastic.candidate_checkpoints(prefix):
        if epoch == 1:
            net2.load_parameters(params)
    np.testing.assert_allclose(net2.weight.data().asnumpy(), 11.0)


def test_fatal_error_propagates_immediately(tmp_path):
    sup = _sup(max_restarts=5)
    calls = []

    def epoch_fn(epoch):
        calls.append(epoch)
        raise TypeError("a programming error — must NOT be retried")

    with pytest.raises(TypeError):
        sup.run(epoch_fn, num_epoch=3)
    assert calls == [0] and sup.restarts == 0


def test_degradation_after_exhausted_restarts(tmp_path):
    """max-restarts exhausted → clean durable final save + structured
    degraded status + the degraded-mode gauge, NOT an unbounded loop."""
    prefix = str(tmp_path / "ck")
    net = _dense(7.0)
    hooked = []
    sup = _sup(save_fn=lambda e: elastic.save_checkpoint(prefix, e, net=net),
               restore_fn=lambda: elastic.auto_resume(prefix, net=net),
               max_restarts=2,
               on_degraded=lambda s, err: hooked.append(type(err).__name__))

    def epoch_fn(epoch):
        raise OSError("persistent fault")

    res = sup.run(epoch_fn, num_epoch=5)
    assert res.status == "degraded" and not res.ok
    assert "restarts exhausted" in res.reason
    assert res.restarts == 3  # 2 allowed + the one that broke the budget
    assert hooked == ["OSError"]
    # the degraded final save is durable and resumable
    epoch, _ = elastic.latest_checkpoint(prefix)
    assert epoch is not None
    assert ckpt.verify_checkpoint(prefix, epoch)[0] == "verified"
    assert telemetry.get("supervisor.degraded").value == 1


def test_rollback_budget_degrades(tmp_path):
    sup = _sup(restore_fn=lambda: 0, skip_limit=0, max_rollbacks=1)

    def epoch_fn(epoch):
        with chaos.enable(nan_after=1, nan_streak=1):
            sup.step(lambda: 1.0)

    res = sup.run(epoch_fn, num_epoch=3)
    assert res.status == "degraded"
    assert "rollbacks exhausted" in res.reason
    assert res.rollbacks == 2


def test_supervised_step_observable_forms():
    """Scalars, NDArrays, (loss, grad_norm) tuples and None all feed the
    sentinel correctly."""
    sup = _sup(skip_limit=0, max_grad_norm=10.0)
    sup._epoch = 0
    assert sup.step(lambda: 1.25) == 1.25
    out = sup.step(lambda: nd.array([1.0, 2.0]))
    np.testing.assert_allclose(out.asnumpy(), [1.0, 2.0])
    assert sup.step(lambda: (0.5, 3.0)) == (0.5, 3.0)
    assert sup.step(lambda: None) is None          # no numeric check
    assert sup.step(lambda: "opaque") == "opaque"  # non-numeric: no check
    with pytest.raises(supervisor.NumericDivergence):
        sup.step(lambda: (0.5, 99.0))  # grad norm over budget


# ---------------------------------------------------------------------------
# module.fit(supervised=) integration
# ---------------------------------------------------------------------------
def _toy_iter(batch_size=4, n=16):
    X = np.random.RandomState(0).rand(n, 4).astype(np.float32)
    Y = (X.sum(1) > 2).astype(np.float32)
    return mx.io.NDArrayIter(X, Y, batch_size=batch_size,
                             label_name="softmax_label")


def _toy_symbol():
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=2, name="fc1")
    return mx.sym.SoftmaxOutput(fc, mx.sym.Variable("softmax_label"),
                                name="softmax")


def test_module_fit_supervised_checkpoints_and_completes(tmp_path):
    prefix = str(tmp_path / "fit")
    mod = mx.module.Module(_toy_symbol(), context=[mx.cpu()])
    res = mod.fit(_toy_iter(), num_epoch=3,
                  optimizer_params=(("learning_rate", 0.05),),
                  supervised=supervisor.Supervise(prefix=prefix, seed=0))
    assert res.ok and res.status == "completed"
    assert elastic.latest_checkpoint(prefix)[0] == 2
    assert ckpt.verify_checkpoint(prefix, 2)[0] == "verified"
    assert math.isfinite(res.final_loss)
    # a dict config works too, and resumes from the checkpoints above
    mod2 = mx.module.Module(_toy_symbol(), context=[mx.cpu()])
    res2 = mod2.fit(_toy_iter(), num_epoch=4,
                    supervised={"prefix": prefix, "seed": 0})
    assert res2.ok
    assert elastic.latest_checkpoint(prefix)[0] == 3


def test_module_fit_supervised_requires_prefix():
    mod = mx.module.Module(_toy_symbol(), context=[mx.cpu()])
    with pytest.raises(mx.base.MXNetError, match="prefix"):
        mod.fit(_toy_iter(), num_epoch=1,
                supervised=supervisor.Supervise())


def test_module_fit_supervised_rolls_back_on_divergence(tmp_path):
    """In-process divergence proof on the real Module path: nan_after
    poisons the sentinel observable mid-fit; the run still completes with
    ≥1 rollback and a verified final checkpoint."""
    prefix = str(tmp_path / "fit")
    mod = mx.module.Module(_toy_symbol(), context=[mx.cpu()])
    with chaos.enable(nan_after=6, nan_streak=2, seed=0) as cfg:
        res = mod.fit(_toy_iter(), num_epoch=3,
                      supervised=supervisor.Supervise(
                          prefix=prefix, skip_limit=1, seed=0))
        assert cfg.nans_fired == 2
    assert res.ok and res.rollbacks == 1 and res.batches_skipped == 1
    epoch, _ = elastic.latest_checkpoint(prefix)
    assert epoch == 2
    assert ckpt.verify_checkpoint(prefix, epoch)[0] == "verified"


# ---------------------------------------------------------------------------
# deterministic resume: the bit-identical proof (ISSUE 5 tentpole)
# ---------------------------------------------------------------------------
def _det_build(seed):
    """Fixed-seed net + compiled step + shuffled iterator — everything a
    run's trajectory depends on."""
    from tpu_mx import gluon
    from tpu_mx.parallel import CompiledTrainStep
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(2))
    net.initialize()
    net(nd.ones((1, 4)))
    step = CompiledTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                             mx.optimizer.create("sgd", learning_rate=0.05))
    R = np.random.RandomState(7)
    X = R.rand(32, 4).astype(np.float32)
    Y = (X.sum(1) > 2).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=8, shuffle=True,
                           last_batch_handle="discard", seed=seed)
    return net, step, it


def _det_run(prefix, crash_at=None, epochs=3):
    net, step, it = _det_build(11)
    mgr = resume.CapsuleManager(prefix, iters=[it], state=step, interval=1)
    sup = supervisor.Supervisor(capsule=mgr, backoff=0.01, seed=0)

    def save_fn(e):
        step.sync_to_net()
        elastic.save_checkpoint(prefix, e, net=net, capsule=mgr)

    def restore_fn():
        e = elastic.auto_resume(prefix, net=net)
        step.sync_from_net()
        return e

    sup.save_fn, sup.restore_fn = save_fn, restore_fn
    losses = {}

    def epoch_fn(epoch):
        if not sup.resume_step(epoch):
            it.reset()
        for batch in it:
            def one(b=batch):
                v = float(step.step(b.data[0], b.label[0]).asnumpy().mean())
                losses[(epoch, sup.step_in_epoch + 1)] = v
                return v
            sup.step(one)

    ctx = chaos.enable(crash_at_step=crash_at, seed=0) if crash_at \
        else contextlib.nullcontext()
    with ctx:
        res = sup.run(epoch_fn, begin_epoch=0, num_epoch=epochs)
    assert res.ok, res.as_dict()
    step.sync_to_net()
    weights = [p.data().asnumpy().copy()
               for p in net.collect_params().values()]
    return losses, weights, res


def test_bit_identical_resume_after_midepoch_crash(tmp_path):
    """THE acceptance proof: run A trains uninterrupted; run B is
    chaos-crashed mid-epoch (after step 6 of 12 commits) and supervised-
    resumed through the step capsule.  Their per-step loss sequences and
    final weights must match EXACTLY — the capsule restored the RNG
    streams, the shuffle/cursor and the mid-epoch train state, so run B
    re-fed nothing and skipped nothing."""
    la, wa, _ = _det_run(str(tmp_path / "a"))
    lb, wb, rb = _det_run(str(tmp_path / "b"), crash_at=6)
    assert rb.restarts == 1
    assert set(la) == {(e, s) for e in range(3) for s in range(1, 5)}
    assert la == lb  # float-exact per-step loss trajectories
    assert wa and all(np.array_equal(a, b) for a, b in zip(wa, wb))
    assert telemetry.gauge("resume.resume_step_gap").value == 0


def test_chaos_crash_at_step_fires_after_commit_and_disarms():
    sup = _sup(restore_fn=lambda: 0)
    seen = []
    with chaos.enable(crash_at_step=3, seed=0) as cfg:
        res = sup.run(lambda e: [sup.step(lambda: seen.append(1) or 1.0)
                                 for _ in range(4)], num_epoch=2)
        assert cfg.step_crashes == 1
    assert res.ok and res.restarts == 1
    # the 3rd step COMMITTED before the crash (raise-after-commit), then
    # the restart re-ran epoch 0 (no capsule manager armed here)
    assert len(seen) == 3 + 8
    assert telemetry.get("chaos.injections", kind="crash_step").value >= 1


def test_module_fit_capsule_resumes_midepoch_exactly(tmp_path):
    """module.fit(supervised=Supervise(capsule=True, capsule_interval=1))
    crashed mid-epoch resumes at the exact batch: final params are
    bit-identical to the uninterrupted fixed-seed fit."""
    def fit(prefix, crash_at=None):
        mx.random.seed(4)
        mod = mx.module.Module(_toy_symbol(), context=[mx.cpu()])
        X = np.random.RandomState(1).rand(16, 4).astype(np.float32)
        Y = (X.sum(1) > 2).astype(np.float32)
        it = mx.io.NDArrayIter(X, Y, batch_size=4, shuffle=True, seed=4,
                               label_name="softmax_label")
        ctx = chaos.enable(crash_at_step=crash_at, seed=0) if crash_at \
            else contextlib.nullcontext()
        with ctx:
            res = mod.fit(it, num_epoch=3,
                          optimizer_params=(("learning_rate", 0.05),
                                            ("momentum", 0.9)),
                          supervised=supervisor.Supervise(
                              prefix=prefix, capsule=True,
                              capsule_interval=1, seed=0))
        assert res.ok, res.as_dict()
        arg, aux = mod.get_params()
        return {k: v.asnumpy() for k, v in arg.items()}, res

    wa, _ = fit(str(tmp_path / "a"))
    wb, rb = fit(str(tmp_path / "b"), crash_at=6)  # epoch 1, step 2 of 4
    assert rb.restarts == 1
    for k in wa:
        np.testing.assert_array_equal(wa[k], wb[k])
    # every epoch's manifest carries its verified capsule
    man = ckpt.read_manifest(str(tmp_path / "b"), 2)
    assert "b-0002.capsule.json" in man["files"]
    assert ckpt.verify_checkpoint(str(tmp_path / "b"), 2)[0] == "verified"
    assert telemetry.gauge("resume.resume_step_gap").value == 0


# ---------------------------------------------------------------------------
# the subprocess rollback proof (satellite)
# ---------------------------------------------------------------------------
_ROLLBACK_SCRIPT = """\
import os
import tpu_mx as mx
from tpu_mx import elastic, nd, supervisor
from tpu_mx.contrib import chaos
from tpu_mx.gluon import nn

prefix = os.environ["SUP_PREFIX"]
net = nn.Dense(3, in_units=4)
net.initialize()

def save_fn(epoch):
    net.weight.set_data(nd.full((3, 4), 10.0 + epoch))
    elastic.save_checkpoint(prefix, epoch, net=net)

def restore_fn():
    e = elastic.auto_resume(prefix, net=net)
    print("RESUME_FROM", e,
          "WEIGHT", float(net.weight.data().asnumpy()[0, 0]), flush=True)
    return e

sup = supervisor.Supervisor(save_fn=save_fn, restore_fn=restore_fn,
                            skip_limit=0, backoff=0.01, seed=0)
armed = [True]

def epoch_fn(epoch):
    if epoch == 2 and armed[0]:
        armed[0] = False
        with chaos.enable(nan_after=2, nan_streak=1):
            for _ in range(3):
                sup.step(lambda: 1.0)
    else:
        for _ in range(3):
            sup.step(lambda: 1.0)

res = sup.run(epoch_fn, begin_epoch=0, num_epoch=4)
assert res.ok, res.as_dict()
assert res.rollbacks == 1, res.as_dict()
print("STATUS", res.status, flush=True)
"""


@pytest.mark.slow
def test_subprocess_divergence_resumes_from_last_good_epoch(tmp_path):
    """A real training process hit by mid-training divergence rolls back
    to the last GOOD epoch (weights prove it — not the poisoned one) and
    finishes with every epoch durably verified."""
    prefix = str(tmp_path / "job")
    script = tmp_path / "train.py"
    script.write_text(_ROLLBACK_SCRIPT)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["SUP_PREFIX"] = prefix
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("TPUMX_CHAOS", None)
    proc = subprocess.run([sys.executable, str(script)], text=True,
                          capture_output=True, timeout=240, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    lines = [l for l in proc.stdout.splitlines() if l.startswith("RESUME")]
    # first resume: fresh start (epoch 0, random init).  The divergence at
    # epoch 2 rolled back to resume FROM epoch 2 with epoch 1's weights
    # (11.0) — the poisoned epoch was never committed
    assert lines[0].startswith("RESUME_FROM 0 "), lines
    assert lines[1] == "RESUME_FROM 2 WEIGHT 11.0", lines
    assert "STATUS completed" in proc.stdout
    for epoch in range(4):
        assert ckpt.verify_checkpoint(prefix, epoch)[0] == "verified"


# ---------------------------------------------------------------------------
# review-hardening regressions
# ---------------------------------------------------------------------------
def test_numeric_degrade_restores_instead_of_saving_poison(tmp_path):
    """Rollback budget exhausted on divergence: the degraded exit must NOT
    commit the (poisoned) live weights as a newer verified epoch — it
    restores the last good checkpoint, which stays newest."""
    prefix = str(tmp_path / "ck")
    net = _dense(1.0)
    saves, restores = [], []

    def save_fn(e):
        saves.append(e)
        elastic.save_checkpoint(prefix, e, net=net)

    def restore_fn():
        restores.append(1)
        return elastic.auto_resume(prefix, net=net)

    sup = _sup(save_fn=save_fn, restore_fn=restore_fn, skip_limit=0,
               max_rollbacks=1)
    good = {"done": False}

    def epoch_fn(epoch):
        if epoch == 0 and not good["done"]:
            good["done"] = True
            sup.step(lambda: 1.0)  # one good epoch checkpoints below
            return
        with chaos.enable(nan_after=1, nan_streak=1):
            sup.step(lambda: 1.0)

    res = sup.run(epoch_fn, num_epoch=5)
    assert res.status == "degraded"
    # only the good epochs were ever saved — no degraded-save of epoch ≥1
    assert saves == [0], saves
    assert elastic.latest_checkpoint(prefix)[0] == 0
    # and the degraded exit restored the last good state one final time
    assert len(restores) >= 3  # initial resume + rollbacks + final restore


def test_train_step_discards_stale_result_after_restore():
    """The zombie-step guard: a watchdog-abandoned step finishing AFTER a
    state restore must not apply its stale update over the restored
    weights."""
    from tpu_mx import gluon
    from tpu_mx.parallel import CompiledTrainStep
    net = nn.Dense(2, in_units=4)
    net.initialize()
    step = CompiledTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                             mx.optimizer.create("sgd", learning_rate=0.1))
    x = nd.array(np.random.RandomState(0).rand(4, 4).astype(np.float32))
    y = nd.array(np.zeros(4, dtype=np.float32))
    step.step(x, y)  # compile + one real step
    gen0 = step._generation
    t0 = step._t
    # "restore": rebind fresh param arrays (as auto_resume's
    # load_parameters does — the step donated the originals) and sync —
    # sync_from_net bumps the generation
    net.weight.set_data(nd.full((2, 4), 0.5))
    net.bias.set_data(nd.full((2,), 0.0))
    step.sync_from_net()
    vals0 = {k: np.asarray(v) for k, v in step.values.items()}
    assert step._generation == gen0 + 1
    # … so a step that started under the OLD generation is discarded
    loss = step._step((x, y), None, expect_gen=gen0)
    assert np.isfinite(float(loss.asnumpy()))
    assert step._t == t0  # no state advanced
    for k, v in step.values.items():
        np.testing.assert_array_equal(np.asarray(v), vals0[k])
    # a current-generation step applies normally
    step._step((x, y), None, expect_gen=step._generation)
    assert step._t == t0 + 1


def test_train_step_zombie_thread_mid_flight_restore_discarded():
    """The full race, on the DEFAULT path (no explicit expect_gen): a step
    blocked mid-execution on an abandoned thread, a restore on the main
    thread, then the step unblocks — its result must be discarded."""
    from tpu_mx import gluon
    from tpu_mx.parallel import CompiledTrainStep
    net = nn.Dense(2, in_units=4)
    net.initialize()
    step = CompiledTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                             mx.optimizer.create("sgd", learning_rate=0.1))
    x = nd.array(np.random.RandomState(0).rand(4, 4).astype(np.float32))
    y = nd.array(np.zeros(4, dtype=np.float32))
    step.step(x, y)  # compile + one real step
    orig_jitted = step._jitted
    entered, gate = threading.Event(), threading.Event()

    def blocking_jitted(*args):
        entered.set()
        assert gate.wait(30)  # "hung collective"
        return orig_jitted(*args)

    step._jitted = blocking_jitted
    zombie = threading.Thread(target=lambda: step._step((x, y), None),
                              daemon=True)
    zombie.start()
    assert entered.wait(30)
    # main thread: the watchdog fired, the supervisor restores
    step._jitted = orig_jitted
    net.weight.set_data(nd.full((2, 4), 0.5))
    net.bias.set_data(nd.full((2,), 0.0))
    step.sync_from_net()
    t_restored = step._t
    vals0 = {k: np.asarray(v) for k, v in step.values.items()}
    # the zombie unblocks and finishes — its stale result is discarded
    gate.set()
    zombie.join(30)
    assert not zombie.is_alive()
    assert step._t == t_restored
    for k, v in step.values.items():
        np.testing.assert_array_equal(np.asarray(v), vals0[k])


# ---------------------------------------------------------------------------
# the bit-identical-resume SUBPROCESS proof (ISSUE 5 acceptance)
# ---------------------------------------------------------------------------
_DETERMINISM_SCRIPT = """\
import json
import os
import numpy as np
import tpu_mx as mx
from tpu_mx import elastic, nd, resume, supervisor, gluon
from tpu_mx.contrib import chaos
from tpu_mx.gluon import nn
from tpu_mx.parallel import CompiledTrainStep

MODE = os.environ["DET_MODE"]          # "run" or "crash"
prefix = os.environ["DET_PREFIX"]
out = os.environ.get("DET_OUT", "")

mx.random.seed(11)
net = nn.HybridSequential()
net.add(nn.Dense(8, activation="relu"), nn.Dense(2))
net.initialize()
net(nd.ones((1, 4)))
step = CompiledTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                         mx.optimizer.create("sgd", learning_rate=0.05))
R = np.random.RandomState(7)
X = R.rand(32, 4).astype(np.float32)
Y = (X.sum(1) > 2).astype(np.float32)
it = mx.io.NDArrayIter(X, Y, batch_size=8, shuffle=True,
                       last_batch_handle="discard", seed=11)

mgr = resume.CapsuleManager(prefix, iters=[it], state=step, interval=1)
sup = supervisor.Supervisor(capsule=mgr, backoff=0.01, seed=0)

def save_fn(e):
    step.sync_to_net()
    elastic.save_checkpoint(prefix, e, net=net, capsule=mgr)

def restore_fn():
    e = elastic.auto_resume(prefix, net=net)
    step.sync_from_net()
    return e

sup.save_fn, sup.restore_fn = save_fn, restore_fn
losses = {}

def epoch_fn(epoch):
    if not sup.resume_step(epoch):
        it.reset()
    for batch in it:
        def one(b=batch):
            v = float(step.step(b.data[0], b.label[0]).asnumpy().mean())
            losses["%d:%d" % (epoch, sup.step_in_epoch + 1)] = v
            return v
        sup.step(one)

if MODE == "crash":
    # a TRUE mid-epoch process death: os._exit(137) right after the 6th
    # supervised step commits (its update applied, its capsule written)
    with chaos.enable(crash_at_step=6, hard=1, seed=0):
        sup.run(epoch_fn, begin_epoch=0, num_epoch=3)
    raise SystemExit("crash_at_step did not fire")

res = sup.run(epoch_fn, begin_epoch=0, num_epoch=3)
assert res.ok, res.as_dict()
step.sync_to_net()
np.savez(out + ".npz", **{str(i): p.data().asnumpy() for i, p in
                          enumerate(net.collect_params().values())})
with open(out + ".json", "w") as f:
    json.dump(losses, f)
print("DET DONE", flush=True)
"""


@pytest.mark.slow
def test_subprocess_bit_identical_resume(tmp_path):
    """The headline cross-process proof: run A trains 3 epochs
    uninterrupted.  Run B is hard-killed (os._exit) mid-epoch after step
    6 commits; a FRESH process resumes it through the step capsule.  The
    resumed process's first recorded step is exactly step 7 (epoch 1,
    step 3 — nothing re-fed, nothing skipped), its per-step losses match
    run A's bit-for-bit, and so do the final weights."""
    script = tmp_path / "det.py"
    script.write_text(_DETERMINISM_SCRIPT)
    env_base = dict(os.environ)
    env_base["JAX_PLATFORMS"] = "cpu"
    env_base["PYTHONPATH"] = REPO + os.pathsep + env_base.get("PYTHONPATH",
                                                              "")
    env_base.pop("TPUMX_CHAOS", None)

    def run(mode, prefix, out=""):
        env = dict(env_base, DET_MODE=mode, DET_PREFIX=prefix, DET_OUT=out)
        return subprocess.run([sys.executable, str(script)], text=True,
                              capture_output=True, timeout=240, env=env)

    a = run("run", str(tmp_path / "a"), str(tmp_path / "out_a"))
    assert a.returncode == 0, a.stdout + a.stderr
    crash = run("crash", str(tmp_path / "b"))
    assert crash.returncode == 137, crash.stdout + crash.stderr
    b = run("run", str(tmp_path / "b"), str(tmp_path / "out_b"))
    assert b.returncode == 0, b.stdout + b.stderr

    la = json.loads((tmp_path / "out_a.json").read_text())
    lb = json.loads((tmp_path / "out_b.json").read_text())
    # the resumed process recorded ONLY steps 7..12: exact-batch resume —
    # epoch 1 steps 1-2 (committed before the kill) were never re-fed
    assert sorted(lb) == ["1:3", "1:4", "2:1", "2:2", "2:3", "2:4"], lb
    for k, v in lb.items():
        assert la[k] == v, (k, la[k], v)  # bit-identical losses
    wa = np.load(str(tmp_path / "out_a.npz"))
    wb = np.load(str(tmp_path / "out_b.npz"))
    for k in wa.files:
        np.testing.assert_array_equal(wa[k], wb[k])
    for epoch in range(3):
        assert ckpt.verify_checkpoint(str(tmp_path / "b"),
                                      epoch)[0] == "verified"


def test_for_module_rollback_reloads_optimizer_states(tmp_path):
    """With save_optimizer_states=True, a rollback restores the optimizer
    state WITH the weights (diverged momentum must not survive)."""
    prefix = str(tmp_path / "fit")
    mod = mx.module.Module(_toy_symbol(), context=[mx.cpu()])
    loaded = []
    orig_load = mod.load_optimizer_states
    mod.load_optimizer_states = lambda f: (loaded.append(f), orig_load(f))
    with chaos.enable(nan_after=6, nan_streak=2, seed=0):
        res = mod.fit(_toy_iter(), num_epoch=3,
                      optimizer="sgd",
                      optimizer_params=(("learning_rate", 0.05),
                                        ("momentum", 0.9)),
                      supervised=supervisor.Supervise(
                          prefix=prefix, skip_limit=1,
                          save_optimizer_states=True, seed=0))
    assert res.ok and res.rollbacks == 1
    # the rollback restore reloaded the last good epoch's .states
    assert loaded and all(f.endswith(".states") for f in loaded), loaded
    man = ckpt.read_manifest(prefix, 2)
    assert "fit-0002.states" in man["files"]
