"""Tests of benchmark/scopes.py and of the readers on top of it: hand-made
traces whose answers can be worked out on paper, and a small scoped step
recorded on the chip with the program's own annotations.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""
import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import scopes  # noqa: E402
import xplane  # noqa: E402

SCOPED = ["step_forward_ms", "step_backward_ms", "step_optimizer_ms",
          "step_fingerprint_ms"]
READERS = SCOPED + ["host_step_overhead_ms", "collective_ms_per_step",
                    "exposed_collective_ms_per_step"]


def read(name, trace):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read({"trace": trace})


def scoped_step(prefix="jit(tpumx_train_step)/train_step."):
    """Two steps of 1000 ns.  In each: forward 200, backward 400 (one of its
    operations under a transposed scope name, as JAX writes a custom
    gradient's), a copy under no scope 50, an all-reduce 100, optimizer 100,
    accumulation fold 20, fingerprint 30; 100 ns idle."""
    ops = {"fwd": ("convolution fusion", prefix + "grad/jvp(...i,oi->...o)/"
                   "dot_general:"),
           "bwd": ("convolution fusion", prefix + "grad/transpose(jvp(...i,"
                   "oi->...o))/dot_general:"),
           "bwd2": ("loop fusion", prefix + "grad/transpose(train_step.grad)/"
                    "jvp()/select_n:"),
           "copy": ("data formatting", ""),
           "all-reduce.1": ("all-reduce", "jit(tpumx_train_step)/transpose("
                            "jvp())/reduce_sum:"),
           "opt": ("loop fusion", prefix + "optimizer/jit(norm)/reduce_sum:"),
           "acc": ("loop fusion", prefix + "grad_accum/add:"),
           "fp": ("loop fusion", prefix + "fingerprint/reduce_sum:")}
    name = {k: f"%{k} = f32[8] fusion(f32[8] %p)" for k in ops}
    meta = {name[k]: {"hlo_category": c, "tf_op": t}
            for k, (c, t) in ops.items()}
    lines = {"XLA Modules": [], "XLA Ops": []}
    for t0 in (0, 1000):
        lines["XLA Modules"].append(("jit_tpumx_train_step(1)", t0, 1000))
        at = t0
        for k, d in (("fwd", 200), ("bwd", 300), ("bwd2", 100), ("copy", 50),
                     ("all-reduce.1", 100), ("opt", 100), ("acc", 20),
                     ("fp", 30)):
            lines["XLA Ops"].append((name[k], at, d))
            at += d
    span = scopes.STEP_SPAN
    host = [(span, 0, 500), (span + "/data_wait", 10, 40),
            (span + "/dispatch", 60, 400), (span + "/record", 470, 20),
            (span, 600, 900), (span + "/dispatch", 650, 700),
            (span, 2000, 300), (span + "/dispatch", 2050, 100),
            ("bench/enqueue_step", 0, 500)]
    return {"devices": {"/device:TPU:0": {"lines": lines, "meta": meta}},
            "host": host}


def test_the_scopes_split_a_step_as_named():
    trace = scoped_step()
    got = {m: read(m, trace) for m in SCOPED}
    assert got == pytest.approx({
        "step_forward_ms": 200e-6, "step_backward_ms": 400e-6,
        "step_optimizer_ms": 120e-6, "step_fingerprint_ms": 30e-6})
    busy_s, _ = xplane.busy(trace)
    # the copy and the all-reduce lie under no scope
    assert sum(got.values()) == pytest.approx(1e3 * busy_s / 2 - 150e-6)
    assert scopes.under("jit(f)/train_step.grad/jvp()/add:", (scopes.GRAD,))
    assert not scopes.under("jit(f)/jit(train_step.gradient)/add:",
                            scopes.SCOPES)


def test_a_program_that_names_no_scope_reads_as_nothing():
    """The parent of the PR that brought the scopes: same operations, no
    names.  Not 0: a metric that is absent is left out of the line."""
    trace = scoped_step(prefix="jit(fn)/")
    trace["host"] = [e for e in trace["host"] if e[0].startswith("bench/")]
    assert [read(m, trace) for m in SCOPED] == [None] * 4
    assert read("host_step_overhead_ms", trace) is None
    # collectives are found by their HLO category, scopes or none
    assert read("collective_ms_per_step", trace) == pytest.approx(100e-6)


@pytest.mark.parametrize("metric", READERS)
def test_a_new_reader_with_nothing_to_read_returns_nothing(metric):
    assert read(metric, None) is None
    assert read(metric, {"devices": {}, "host": []}) is None


def test_host_step_overhead_is_the_span_less_its_dispatch():
    # 500 - 400, 900 - 700, 300 - 100: the median of 100, 200, 200
    assert read("host_step_overhead_ms", scoped_step()) == \
        pytest.approx(200e-6)


def test_a_synchronous_collective_is_all_exposed():
    trace = scoped_step()
    assert read("collective_ms_per_step", trace) == pytest.approx(100e-6)
    assert read("exposed_collective_ms_per_step", trace) == \
        pytest.approx(100e-6)


def test_a_collective_half_covered_by_compute_reads_half():
    """An asynchronous all-reduce, whole on `Async XLA Ops` from start to
    done (200 ns a step): compute runs under its first half, the core waits
    in all-reduce-done for the second."""
    dot = "%fusion.1 = bf16[8,8] fusion(bf16[8,8] %a), kind=kOutput"
    start = "%all-reduce-start.1 = bf16[8] all-reduce-start(bf16[8] %g)"
    done = "%all-reduce-done.1 = bf16[8] all-reduce-done(bf16[8] %s)"
    meta = {dot: {"hlo_category": "convolution fusion", "tf_op": ""},
            start: {"hlo_category": "all-reduce-start", "tf_op": ""},
            done: {"hlo_category": "all-reduce-done", "tf_op": ""}}
    lines = {"XLA Modules": [("jit_tpumx_train_step(1)", 0, 1000),
                             ("jit_tpumx_train_step(1)", 1000, 1000)],
             "XLA Ops": [], "Async XLA Ops": []}
    for t0 in (0, 1000):
        lines["XLA Ops"] += [(dot, t0, 600), (start, t0 + 600, 0),
                             (dot, t0 + 600, 100), (done, t0 + 700, 100)]
        lines["Async XLA Ops"].append((start, t0 + 600, 200))
    trace = {"devices": {"/device:TPU:0": {"lines": lines, "meta": meta}},
             "host": []}
    assert read("collective_ms_per_step", trace) == pytest.approx(200e-6)
    assert read("exposed_collective_ms_per_step", trace) == \
        pytest.approx(100e-6)


FIXTURE = os.path.join(BENCH, "fixtures", "scoped_step.xplane.pb.gz")


def test_the_recorded_scoped_step():
    """Two blocks of two steps of the benchmark's own bert-base-uncased
    builder at the toy sizes of its `rehearse` groups (2 layers, hidden 128,
    batch 4, seq 32; bf16 with f32 masters, LAMB) through CompiledTrainStep,
    recorded on a TPU v5e as run.py records (PR 25's chip call), with the
    program's scopes and annotations beside the benchmark's.  The plane of
    HLO protos, which nothing here reads, is cut out of the file."""
    trace = xplane.load(FIXTURE)
    assert list(trace["devices"]) == ["/device:TPU:0"]
    dev = xplane.first_device(trace)
    assert {"jit_tpumx_train_step", "jit__threefry_split"} <= \
        {m.split("(")[0] for m, _, _ in dev["lines"]["XLA Modules"]}
    steps, _ = xplane.stretch(dev)
    assert len(steps) == 4 and len(scopes.spans(trace, scopes.STEP_SPAN)) == 4
    names = {n for n, _, _ in trace["host"]}
    assert {f"{scopes.STEP_SPAN}/{p}" for p in scopes.PHASES} - names == \
        {f"{scopes.STEP_SPAN}/recompile", f"{scopes.STEP_SPAN}/loss_readback"}
    assert {"bench/enqueue_step", "bench/fetch_loss"} <= names
    got = {m: read(m, trace) for m in READERS}
    assert got == pytest.approx({
        "step_forward_ms": 0.0456985, "step_backward_ms": 0.05027775,
        "step_optimizer_ms": 0.01141125, "step_fingerprint_ms": 0.02944675,
        "host_step_overhead_ms": 1.384355, "collective_ms_per_step": 0.0,
        "exposed_collective_ms_per_step": 0.0})
    busy_s, _ = xplane.busy(trace)
    four = sum(got[m] for m in SCOPED)
    assert 0.9 * busy_s < four * len(steps) / 1e3 <= busy_s
