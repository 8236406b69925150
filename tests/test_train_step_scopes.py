"""The compiled train step's one span vocabulary (ISSUE 25): jax.named_scope
names inside the program (STEP_SCOPES), profiler annotations and
train_step.phase events around it (tracing.phase, TRAIN_STEP_PHASES), and
the benchmark's copy of both (benchmark/scopes.py)."""
import glob
import os
import re
import sys
import time

import jax
import numpy as np
import pytest

import tpu_mx as mx
from tpu_mx import gluon, nd, tracing
from tpu_mx.gluon import nn
from tpu_mx.parallel import CompiledTrainStep, make_mesh
from tpu_mx.parallel.train_step import STEP_SCOPES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
GRAD, GRAD_SYNC, GRAD_ACCUM, OPTIMIZER, FINGERPRINT = STEP_SCOPES


@pytest.fixture(autouse=True)
def _fresh_recorder():
    tracing.reset()
    tracing.configure(enabled=True, capacity=512)
    yield
    tracing.reset()
    tracing.configure(enabled=True, capacity=512)


@pytest.fixture
def bench_scopes():
    """benchmark/scopes.py, imported as the readers import it."""
    sys.path.insert(0, BENCH)
    try:
        import scopes
        yield scopes
    finally:
        sys.path.remove(BENCH)


def _net(dtype=None):
    mx.random.seed(11)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"),
            nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    net(nd.ones((1, 8)))
    if dtype:
        net.cast(dtype)
    return net


def _batch(dtype=None):
    x = nd.array(np.random.RandomState(0).rand(8, 8).astype(np.float32))
    y = nd.array(np.random.RandomState(1).randint(0, 4, (8,)),
                 dtype="float32")
    return (nd.cast(x, dtype) if dtype else x), y


def _plain():
    return CompiledTrainStep(
        _net(), gluon.loss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.create("sgd", learning_rate=0.1)), _batch()


def _mp():
    return CompiledTrainStep(
        _net(dtype="bfloat16"), gluon.loss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9,
                            multi_precision=True)), _batch(dtype="bfloat16")


def _accum():
    return CompiledTrainStep(
        _net(), gluon.loss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.create("sgd", learning_rate=0.1),
        accum_steps=2), _batch()


def _compressed():
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    return CompiledTrainStep(
        _net(), gluon.loss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.create("sgd", learning_rate=0.1), mesh=mesh,
        gradient_compression={"type": "int8"}), _batch()


def _op_names(compiled):
    text = compiled.as_text()
    return re.search(r"HloModule (\w+)", text).group(1), \
        set(re.findall(r'op_name="([^"]*)"', text))


def _scopes_in(names):
    return {s for s in STEP_SCOPES
            if any(f"/{s}/" in f"/{n}/" for n in names)}


# -- (a) inside the program ------
@pytest.mark.parametrize("build, applies", [
    (_plain, {GRAD, OPTIMIZER, FINGERPRINT}),
    (_mp, {GRAD, OPTIMIZER, FINGERPRINT}),
    (_accum, {GRAD, GRAD_ACCUM, OPTIMIZER, FINGERPRINT}),
    (_compressed, {GRAD, GRAD_SYNC, OPTIMIZER, FINGERPRINT}),
], ids=["plain", "mp", "accum2", "compressed_dp2"])
def test_the_lowered_step_names_its_scopes(build, applies):
    step, batch = build()
    module, names = _op_names(step.aot_compiled(*batch))
    assert module == "jit_tpumx_train_step"
    assert _scopes_in(names) == applies
    # forward and backward part by what JAX transposes
    grad = [n for n in names if f"/{GRAD}/" in n]
    assert [n for n in grad if "transpose(" in n]
    assert [n for n in grad if "transpose(" not in n]
    if build is _accum:
        raw = tuple(b._data for b in batch)
        module, names = _op_names(step._accum_jit.lower(
            step.values, step._gacc, jax.random.PRNGKey(0), *raw).compile())
        assert module == "jit_tpumx_accum_step"
        assert _scopes_in(names) == {GRAD, GRAD_ACCUM}


# -- (b) around the program ------
def test_phase_events_are_in_order_and_tile_the_step():
    # sixty layers: some four hundred pytree leaves through the jit call,
    # half of what BERT-base passes, so that the host's share of a step is
    # of a real step's kind.  What lies between two phases is one emit
    # each, microseconds unless the CPU backend's own threads take the
    # core just then: the best steady step shows how the phases tile, the
    # others only how busy the machine was
    mx.random.seed(11)
    net = nn.HybridSequential()
    for _ in range(60):
        net.add(nn.Dense(8, activation="relu"))
    net.add(nn.Dense(4))
    net.initialize()
    net(nd.ones((1, 8)))
    step = CompiledTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.create("sgd", learning_rate=0.01, momentum=0.9))
    x, y = _batch()
    order = {p: i for i, p in enumerate(tracing.TRAIN_STEP_PHASES)}
    walls = {}
    for i in range(9):
        tracing.set_context(epoch=0, step=i)
        t0 = time.perf_counter()
        loss = step.step(x, y)
        walls[i] = time.perf_counter() - t0
        loss.asscalar()
    covered = []
    for i in range(9):
        events = [e["data"] for e in tracing.snapshot()
                  if e["event"] == "train_step.phase" and e["step"] == i]
        phases = [e["phase"] for e in events]
        assert phases == sorted(phases, key=order.__getitem__)
        assert phases == [p for p in tracing.TRAIN_STEP_PHASES
                          if p != "loss_readback"
                          and (p != "recompile" or i == 0)]
        total = sum(e["seconds"] for e in events)
        assert total <= walls[i]
        covered.append(total / walls[i])
    assert covered[0] > 0.9            # the step that builds and compiles
    assert max(covered[1:]) > 0.9, covered


def test_the_phases_are_annotations_on_the_profilers_timeline(
        tmp_path, bench_scopes):
    import xplane
    step, batch = _plain()
    step.step(*batch).asscalar()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(3):
            # on this thread: xplane.load keys the host's lines by thread
            # name, so a second annotating "python3" thread (the watchdog's)
            # would take this one's place
            loss = step.step(*batch)
        loss.asscalar()
    finally:
        jax.profiler.stop_trace()
    trace = xplane.load(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[0])
    steps = bench_scopes.spans(trace, bench_scopes.STEP_SPAN)
    assert len(steps) == 3
    for phase in bench_scopes.PHASES:
        inside = bench_scopes.spans(
            trace, f"{bench_scopes.STEP_SPAN}/{phase}")
        # no build in a warm step, and no read site without a watchdog
        assert len(inside) == (
            0 if phase in ("recompile", "loss_readback") else 3), phase
        assert all(any(s0 <= s and e <= e0 for s0, e0 in steps)
                   for s, e in inside)
    overhead = bench_scopes.host_step_overhead_ms(trace)
    assert 0 < overhead < 1e3 * max(e - s for s, e in steps) / 1e9
    assert any(n.startswith("PjitFunction(tpumx_train_step)")
               for n, _, _ in trace["host"])


# -- (c) TPUMX_TRACING=0 ------
def test_with_tracing_off_no_event_is_recorded_and_the_step_runs():
    tracing.configure(enabled=False)
    step, batch = _plain()
    losses = [float(step.step(*batch).asscalar()) for _ in range(3)]
    assert losses[-1] < losses[0]
    assert tracing.snapshot() == []
    with tracing.phase("dispatch") as p:
        pass
    assert p.seconds >= 0 and tracing.snapshot() == []
    tracing.configure(enabled=True)
    with tracing.phase("dispatch"):
        pass
    assert [e["data"]["phase"] for e in tracing.snapshot()] == ["dispatch"]


# -- (d) the benchmark's literals ------
def test_the_benchmarks_literals_equal_the_programs(bench_scopes):
    from tpu_mx.parallel.fleet_obs import ATTRIBUTION_PHASES
    assert bench_scopes.SCOPES == STEP_SCOPES
    assert bench_scopes.PHASES == tracing.TRAIN_STEP_PHASES
    assert ATTRIBUTION_PHASES == tracing.TRAIN_STEP_PHASES
    assert bench_scopes.STEP_SPAN == "tpu_mx/train_step"

