"""Tests of the yardstick itself.  They live under benchmark/ because a
benchmark PR may add files nowhere else; run them with

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

Nothing here loads the TPU's library: the two cases that start the command
run it as a child on the CPU.
"""
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import blocks  # noqa: E402
import xplane  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def manifest(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def run_command(root, *args, env=None):
    full = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT, **(env or {}))
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        cwd=root, env=full, capture_output=True, text=True, timeout=600)


# -- BENCHMARK.json --------------------------------------------------------------
def test_manifest_names_and_units():
    b = manifest()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    names += [w[k] for w in b["workloads"] for k in ("config", "traffic")]
    assert all(NAME.match(n) for n in names), names
    metrics = b["end_to_end"] + b["per_layer"]
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(len(w["why"]) <= 200 for w in b["workloads"] + b["configs"])


def test_manifest_cells_and_bounds():
    b = manifest()
    cells = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(set(cells)) == len(cells)
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= 1
    assert all(w["chips"] in (1, 4) for w in b["workloads"])
    assert {w["config"] for w in b["workloads"]} == \
        {c["name"] for c in b["configs"]}
    end = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in end and all(0 < m["bound"] <= 0.1 for m in end.values())
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in end.values())
    assert all(m["moves"] in end for m in b["per_layer"])
    cell_names = {w["name"] for w in b["workloads"]}
    assert all(set(m.get("workloads", [])) <= cell_names
               for m in b["per_layer"])


@pytest.mark.parametrize("cell", [w["name"] for w in manifest()["workloads"]])
def test_every_file_of_a_cell_exists(cell):
    b = manifest()
    w = next(x for x in b["workloads"] if x["name"] == cell)
    config = next(c for c in b["configs"] if c["name"] == w["config"])
    assert config["file"] == f"benchmark/configs/{w['config']}.json"
    wanted = [config["file"], f"benchmark/configs/{w['config']}.py",
              f"benchmark/references/{w['config']}.py",
              f"benchmark/traffic/{w['traffic']}.json"]
    wanted += [f"benchmark/layer_metrics/{m['name']}.py"
               for m in b["per_layer"]
               if cell in m.get("workloads", [cell])]
    assert [p for p in wanted if not os.path.exists(os.path.join(ROOT, p))] \
        == []
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    widths = re.compile(r"(hidden|intermediate|_dim$|_rank$|head_size|"
                        r"channels|experts_per_tok)")
    assert not [k for k in cfg["reduced"] + config["reduced"]
                if widths.search(k)]
    assert cfg["reference_comparison"]["tolerance"]


# -- the block statistic -----------------------------------------------------------
def test_a_stalled_block_moves_the_rate_and_not_the_steady_rate():
    steady = [1.0675, 1.0676, 1.0674, 1.0675, 1.0677, 1.0675, 1.0676]
    stalled = steady[:3] + [1.9] + steady[3:]
    a, b = blocks.summary(steady, 960), blocks.summary(stalled, 960)
    # end to end: all samples over all time, so the stall costs its 9%
    assert b["samples_per_s"] == pytest.approx(8 * 960 / sum(stalled))
    assert b["samples_per_s"] < 0.92 * a["samples_per_s"]
    # per layer: the median block does not move, and the stall is counted
    assert b["steady_samples_per_s"] == \
        pytest.approx(a["steady_samples_per_s"], rel=1e-4)
    assert b["slow_block_share"] == pytest.approx(100 / 8)
    # the time between blocks belongs to the window too
    assert blocks.summary(steady, 960, sum(steady) + 0.1)["samples_per_s"] \
        < a["samples_per_s"]


def test_a_block_the_clock_would_cut_is_not_started():
    times, now, deadline = [1.0, 1.0], 0.0, 5.5
    while blocks.fits(now, deadline, times):
        now += 1.0
        times.append(1.0)
    assert now == 5.0 and now <= deadline        # five whole blocks, no sixth
    assert not blocks.fits(4.96, 6.0, [1.0])     # 4.96 + 1.05 > 6


@pytest.mark.parametrize("times", [[1.0], [1.0, 1.0, 1.0], [1.0, 3.0],
                                   [0.5, 1.0, 1.0, 1.0, 0.2]])
def test_slow_block_share_is_never_negative(times):
    assert 0.0 <= blocks.slow_block_share(times) <= 100.0


# -- the trace: the harness's reductions and the readers' --------------------------
def reader(name, bench=BENCH):
    """layer_metrics/<name>.py of a benchmark directory, as run.py loads it."""
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(bench, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def hand_built():
    """Two steps of 100 ns with a 10 ns gap between them.  In each: a dot of
    60 ns, a 5 ns hole, an element-wise fusion of 30 ns (5 ns unused at the
    end).  A tiny other program runs after them, outside the stretch."""
    dot = "%fusion.1 = bf16[8,8] fusion(bf16[8,8] %a), kind=kOutput"
    add = "%add_fusion.7 = bf16[8,8] fusion(bf16[8,8] %b), kind=kLoop"
    meta = {dot: {"hlo_category": "convolution fusion",
                  "tf_op": "jit(fn)/jvp(...i,oi->...o)/dot_general:"},
            add: {"hlo_category": "loop fusion", "tf_op": "jit(fn)/jvp()/add:"}}
    lines = {"XLA Modules": [("jit_fn(1)", 0, 100), ("jit_fn(1)", 110, 100),
                             ("jit_tiny(2)", 300, 1)],
             "XLA Ops": [(dot, 0, 60), (add, 65, 30), (dot, 110, 60),
                         (add, 175, 30), ("%x = f32[] negate(f32[] %y)", 300, 1)]}
    host = [("bench/enqueue_step", 0, 99), ("bench/fetch_loss", 99, 300),
            ("PjRtExecute", 0, 500)]
    return {"devices": {"/device:TPU:0": {"lines": lines, "meta": meta}},
            "host": host}


def test_busy_union_and_idle_share():
    trace = hand_built()
    steps, ops = xplane.stretch(xplane.first_device(trace))
    assert steps == [[0, 100], [110, 210]] and len(ops) == 4
    assert xplane.busy(trace) == pytest.approx((180e-9, 210e-9))
    assert reader("device_idle_share").read({"trace": trace}) == \
        pytest.approx(100 / 7)
    assert reader("step_device_ms").read({"trace": trace}) == \
        pytest.approx(100e-6)
    assert xplane.union([(0, 5), (3, 8), (10, 12)]) == [[0, 8], [10, 12]]


def test_families_are_named_and_the_mxu_share_splits():
    trace = hand_built()
    ops = xplane.breakdown(trace)["device_ops"]
    assert ops == [
        ["convolution_fusion:jvp_...i_oi-_...o_/dot_general", 120e-9],
        ["loop_fusion:jvp__/add", 60e-9]]
    assert all(re.match(r"^[A-Za-z0-9_.:/-]+$", n) for n, _ in ops)
    assert reader("mxu_op_share").read({"trace": trace}) == \
        pytest.approx(100 * 120 / 180)
    # without metadata the instruction's stem stands in, never the HLO text
    assert xplane.family("%multiply_reduce_fusion.19 = (f32[]) fusion()",
                         {}) == "multiply_reduce_fusion"


def test_idle_gaps_by_what_the_host_was_doing():
    trace = hand_built()
    gaps = dict(xplane.breakdown(trace)["idle_gaps"])
    assert gaps == pytest.approx({"in_step/enqueue_step": 5e-9,
                                  "in_step/fetch_loss": 10e-9,
                                  "between_steps/fetch_loss": 15e-9})
    busy_s, window_s = xplane.busy(trace)
    assert sum(gaps.values()) == pytest.approx(window_s - busy_s)


@pytest.mark.parametrize("metric", ["device_idle_share", "step_device_ms",
                                    "mxu_op_share"])
def test_a_reader_with_nothing_to_read_returns_nothing(metric):
    assert reader(metric).read({"trace": None}) is None
    assert reader(metric).read({"trace": {"devices": {}, "host": []}}) is None


FIXTURE = os.path.join(BENCH, "fixtures", "tiny_attention_step.xplane.pb.gz")


def test_the_recorded_trace():
    """Four executions of a small attention-shaped step recorded on a TPU
    v5e (PR 23's chip call), with the benchmark's own annotations."""
    trace = xplane.load(FIXTURE)
    assert list(trace["devices"]) == ["/device:TPU:0"]
    assert {"bench/enqueue_step", "bench/fetch_loss"} <= \
        {n for n, _, _ in trace["host"]}
    steps, _ = xplane.stretch(xplane.first_device(trace))
    assert len(steps) == 4
    run = {"trace": trace}
    assert reader("step_device_ms").read(run) == pytest.approx(0.1807625)
    busy_s, window_s = xplane.busy(trace)
    assert busy_s == pytest.approx(718569e-9) and busy_s < window_s
    assert 90 < reader("mxu_op_share").read(run) < 99   # a mix: not 0, not 100
    names = [n for n, _ in xplane.breakdown(trace)["device_ops"]]
    assert "convolution_fusion:transpose_jvp_bhqk_bhkd-_bhqd__/dot_general" \
        in names
    assert not [n for n in names if " " in n or "=" in n or "%" in n]


# -- the command ---------------------------------------------------------------------
def test_without_a_chip_the_command_fails_and_names_the_platform():
    done = run_command(ROOT, "--workload", "bert-base.mlm128", "--seed", "1",
                       "--seconds", "1", "--trace", "0")
    assert done.returncode != 0
    assert "platform 'cpu'" in done.stderr
    assert not [l for l in done.stdout.splitlines() if l.startswith("{")]


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """A copy of the benchmark to which a cell, a configuration and a
    per-layer metric are added as NEW files and NEW entries of
    BENCHMARK.json: no file that was there is edited."""
    root = str(tmp_path_factory.mktemp("grown"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {}
    for d, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()
    bench = os.path.join(root, "benchmark")
    for kind, ext in (("configs", ".json"), ("configs", ".py"),
                      ("references", ".py")):
        shutil.copy(os.path.join(bench, kind, "bert-base-uncased" + ext),
                    os.path.join(bench, kind, "bert-twin" + ext))
    with open(os.path.join(bench, "traffic", "mlm64.json"), "w") as f:
        json.dump({"name": "mlm64", "batch": 8, "seq_len": 64,
                   "block_steps": 2,
                   "rehearse": {"batch": 2, "seq_len": 16,
                                "block_steps": 3}}, f)
    with open(os.path.join(bench, "layer_metrics", "steps_counted.py"),
              "w") as f:
        f.write("def read(run):\n"
                "    return run['blocks']['blocks'] * "
                "run['mix']['block_steps']\n")
    # a metric from the trace's raw events, with a reduction of its own
    with open(os.path.join(bench, "layer_metrics", "longest_op_us.py"),
              "w") as f:
        f.write("import xplane\n\n\ndef read(run):\n"
                "    if not run['trace']:\n        return None\n"
                "    _, ops = xplane.stretch(xplane.first_device("
                "run['trace']))\n"
                "    return max(d for _, _, d in ops) / 1e3\n")
    b = manifest(root)
    b["configs"].append({"name": "bert-twin", "source": "test",
                         "file": "benchmark/configs/bert-twin.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "bert-twin.mlm64", "config": "bert-twin",
                           "traffic": "mlm64", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "steps_counted", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "training entry",
                           "moves": "samples_per_s",
                           "workloads": ["bert-twin.mlm64"]})
    b["per_layer"].append({"name": "longest_op_us", "unit": "us",
                           "better": "lower", "source": "device_trace",
                           "layer": "kernels", "moves": "samples_per_s"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    done = run_command(root, "--workload", "bert-twin.mlm64", "--seed",
                       "3000000011", "--seconds", "2", "--trace", "1",
                       "--rehearse-cpu")
    for path, content in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == content, path
    return done, bench


def test_new_files_and_entries_alone_add_a_cell_config_and_metric(grown):
    done, _ = grown
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.splitlines()[-1])
    blocks_line = next(json.loads(l.split(" ", 2)[2])
                       for l in done.stdout.splitlines()
                       if l.startswith("bench blocks "))
    assert blocks_line["block_steps"] == 3 and blocks_line["batch"] == 2
    assert last["metrics"]["steps_counted"]["value"] == last["attempted"] > 0
    assert last["metrics"]["steps_counted"]["unit"] == "count"


def test_a_metric_from_the_trace_is_a_new_file_too(grown):
    """The new reader is handed what xplane.load returns (as run.py hands
    it in a traced run) and reduces the raw events itself."""
    trace = xplane.load(FIXTURE)
    _, ops = xplane.stretch(xplane.first_device(trace))
    assert reader("longest_op_us", grown[1]).read({"trace": trace}) == \
        max(d for _, _, d in ops) / 1e3 > 0
    assert reader("longest_op_us", grown[1]).read({"trace": None}) is None


def test_rehearsal_prints_the_contract_line_without_device_metrics(grown):
    done, _ = grown
    assert "NOT A CHIP RUN" in done.stdout
    last = json.loads(done.stdout.splitlines()[-1])
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] is True and last["device"]["platform"] == "cpu"
    # counts only: every other per-layer metric is the device's
    assert set(last["metrics"]) == {"steps_counted"}
    assert "busy_s" not in last["device"] and "breakdown" not in last
