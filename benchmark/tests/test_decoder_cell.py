"""Tests of what ISSUE 27 added to the yardstick: decoder_scopes.py's matcher
and reductions on a hand-made trace whose answers can be worked out on
paper and on a small decoder step recorded on the chip, the roofline's work
functions, and the new cell's rehearsal.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""
import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import decoder_scopes  # noqa: E402
import xplane  # noqa: E402

CELL = "glm-4.7-flash.pretrain4k"
READERS = ["moe_experts_ms", "moe_route_ms", "mla_attend_ms", "mtp_ms"]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("path,scope,inside", [
    ("jit(f)/train_step.grad/jvp(mla.attend)/jit(_fwd)/pallas_call:",
     "mla.attend", True),
    ("jit(f)/train_step.grad/jvp(mtp)/mla.attend/add:", "mla.attend", True),
    ("jit(f)/train_step.grad/jvp(mtp)/mla.attend/add:", "mtp", True),
    ("jit(f)/train_step.grad/transpose(jvp(moe.route))/gather:",
     "moe.route", True),
    ("jit(f)/train_step.grad/transpose(jvp(train_step.grad))/jvp()/"
     "checkpoint/rematted_computation/moe.experts/mul:", "moe.experts", True),
    ("jit(f)/train_step.grad/jvp(mtpx)/add:", "mtp", False),
    ("jit(f)/train_step.grad/jvp(lm_head)/dot_general:", "mtp", False),
    ("jit(f)/train_step.optimizer/add:", "moe.experts", False),
    ("", "mtp", False)])
def test_the_matcher_takes_wrapped_and_bare_components(path, scope, inside):
    assert decoder_scopes.under(path, (scope,)) is inside


def decoder_step():
    """Two steps of 1000 ns.  In each, forward: attention kernel 100, routing
    gather 40, a grouped product 60 (no op path), the SwiGLU 20, the
    multi-token module's attention kernel 50; backward, from the first
    transposed operation on: the SwiGLU recomputed 20, the grouped product
    recomputed 60 and its two backward products 70 each, the SwiGLU's
    backward 30, the combine's backward 25; the optimizer 45."""
    g = "jit(tpumx_train_step)/train_step.grad/"
    back = g + "transpose(jvp(train_step.grad))/jvp()/checkpoint/"
    ops = {"attend": ("custom-call", g + "jvp(mla.attend)/jit(_fwd)/"
                      "pallas_call:"),
           "route": ("data formatting", g + "jvp(moe.route)/gather:"),
           "ragged-dot-none.1": ("custom-call", "ragged-dot-none:"),
           "swiglu": ("loop fusion", g + "jvp(moe.experts)/jit(silu)/mul:"),
           "mtp_attend": ("custom-call", g + "jvp(mtp)/mla.attend/jit(_fwd)/"
                          "pallas_call:"),
           "swiglu_again": ("loop fusion", back + "rematted_computation/"
                            "moe.experts/jit(silu)/mul:"),
           "ragged-dot-none.2": ("custom-call", "ragged-dot-none:"),
           "ragged-dot-none.3": ("custom-call", "ragged-dot-none:"),
           "ragged-dot-none.4": ("custom-call", "ragged-dot-none:"),
           "swiglu_back": ("loop fusion", back + "moe.experts/mul:"),
           "combine_back": ("loop fusion", back + "moe.combine/add:"),
           "opt": ("loop fusion", "jit(tpumx_train_step)/"
                   "train_step.optimizer/add:")}
    name = {k: f"%{k} = bf16[8] fusion(bf16[8] %p)" for k in ops}
    meta = {name[k]: {"hlo_category": c, "tf_op": t}
            for k, (c, t) in ops.items()}
    lines = {"XLA Modules": [], "XLA Ops": []}
    for t0 in (0, 1000):
        lines["XLA Modules"].append(("jit_tpumx_train_step(1)", t0, 1000))
        at = t0
        for k, d in (("attend", 100), ("route", 40),
                     ("ragged-dot-none.1", 60), ("swiglu", 20),
                     ("mtp_attend", 50), ("swiglu_again", 20),
                     ("ragged-dot-none.2", 60), ("ragged-dot-none.3", 70),
                     ("ragged-dot-none.4", 70), ("swiglu_back", 30),
                     ("combine_back", 25), ("opt", 45)):
            lines["XLA Ops"].append((name[k], at, d))
            at += d
    return {"devices": {"/device:TPU:0": {"lines": lines, "meta": meta}},
            "host": []}


def test_the_reductions_on_a_hand_made_step():
    run = {"trace": decoder_step()}
    # grouped 60 + 60 + 70 + 70 and the SwiGLU's 20 + 20 + 30, a step
    assert reader("moe_experts_ms").read(run) == pytest.approx(330e-6)
    assert reader("moe_route_ms").read(run) == pytest.approx(65e-6)
    assert reader("mla_attend_ms").read(run) == pytest.approx(150e-6)
    assert reader("mtp_ms").read(run) == pytest.approx(50e-6)


@pytest.mark.parametrize("metric", READERS + ["moe_experts_roofline"])
def test_a_reader_with_nothing_to_read_returns_nothing(metric):
    """No trace, an empty one, or a program that names no decoder scope
    (the parent commit's): None, never 0 and never an exception."""
    scoped = os.path.join(BENCH, "fixtures", "scoped_step.xplane.pb.gz")
    for trace in (None, {"devices": {}, "host": []}, xplane.load(scoped)):
        assert reader(metric).read(
            {"trace": trace, "peaks": None, "cfg": {}}) is None


def test_the_roofline_is_the_traced_steps_rows_need_over_all_the_scopes_time(
        monkeypatch):
    """Nothing is taken off for recomputation, and the rows are those of
    the two traced steps: the history's, counted back over the three
    blocks of two steps that the window ran after them."""
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    history = [9e9, 4000.0, 4192.0] + [9e9] * 6      # oldest first
    counted = [{"rows_routed_here": 9e9, "held": (0, 8),
                "rows_routed_here_history": history}] * 2
    monkeypatch.setattr(decoder_scopes, "census", lambda run: counted)
    roofline = reader("moe_experts_roofline")
    run = {"trace": decoder_step(), "peaks": peaks,
           "first_enqueue_s": [0.005] * 3, "mix": {"block_steps": 2},
           "cfg": {"hidden_size": 2048, "moe_intermediate_size": 1536}}
    assert roofline.traced_rows(run, counted) == [4096.0, 4096.0]
    need = 2 * roofline.layer_need_s(4096, 8, 2048, 1536, peaks)
    assert roofline.read(run) == pytest.approx(100 * need / 330e-9)
    # a history that does not reach back to the traced steps: nothing
    short = [dict(c, rows_routed_here_history=history[2:]) for c in counted]
    monkeypatch.setattr(decoder_scopes, "census", lambda run: short)
    assert roofline.read(run) is None
    monkeypatch.setattr(decoder_scopes, "census", lambda run: None)
    assert roofline.read(run) is None


def test_the_rooflines_work_for_one_layer():
    """4,096 real rows through 8 held experts of 2048 x 1536: 232 GFLOP
    (1.177 ms at 197 TFLOP/s) against 667 MB (0.814 ms at 819 GB/s)."""
    need = reader("moe_experts_roofline").layer_need_s(
        4096, 8, 2048, 1536,
        {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert need == pytest.approx(6 * 4096 * 3 * 2048 * 1536 / 197e12)
    assert need == pytest.approx(1.1773e-3, rel=1e-3)
    # with few rows the weights' bytes bound it
    few = reader("moe_experts_roofline").layer_need_s(
        64, 8, 2048, 1536,
        {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert few == pytest.approx((3 * 150994944 + 3 * 64 * 17408) / 819e9)


def test_every_file_of_the_new_cell_exists_and_no_width_is_reduced():
    """What test_benchmark.py's test_every_file_of_a_cell_exists asks of a
    cell, with the widths spelt out: its pattern `hidden` also takes the
    depth key `num_hidden_layers` for one."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    w = next(x for x in b["workloads"] if x["name"] == CELL)
    config = next(c for c in b["configs"] if c["name"] == w["config"])
    wanted = [config["file"], f"benchmark/configs/{w['config']}.py",
              f"benchmark/references/{w['config']}.py",
              f"benchmark/traffic/{w['traffic']}.json"]
    wanted += [f"benchmark/layer_metrics/{m['name']}.py"
               for m in b["per_layer"]
               if CELL in m.get("workloads", [CELL])]
    assert [p for p in wanted if not os.path.exists(os.path.join(ROOT, p))] \
        == []
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    widths = re.compile(r"(hidden_size|intermediate|_dim$|_rank$|head_size|"
                        r"channels|experts_per_tok)")
    assert not [k for k in cfg["reduced"] if widths.search(k)]
    assert set(cfg["published"]) >= set(cfg["reduced"])
    assert cfg["reference_comparison"]["tolerance"]


FIXTURE = os.path.join(BENCH, "fixtures", "decoder_step.xplane.pb.gz")


def test_the_recorded_decoder_step():
    """Four executions of a small decoder's train step recorded on a TPU
    v5e (PR 27's chip call B: hidden 256, two heads of 64, T 512, the
    flash kernel, 2 of 8 experts held, the multi-token module), with the
    benchmark's own annotations."""
    trace = xplane.load(FIXTURE)
    steps, ops = decoder_scopes.step_ops(trace)
    assert len(steps) == 4 and decoder_scopes.names_decoder(ops)
    grouped = [o for o in ops if decoder_scopes.is_grouped(o[0], o[1])]
    # a step: three expert layers x (3 forward + 3 recomputed + 6 backward)
    # products, and XLA's three group-metadata kernels a layer with them
    assert len(grouped) == 4 * (36 + 9)
    assert {p for _, p, _, _ in grouped} == {"ragged-dot-none:",
                                            "ragged-dot-metadata:"}
    kernels = [p for _, p, _, _ in ops if "pallas_call" in p]
    assert kernels and all(decoder_scopes.under(
        p, (decoder_scopes.MLA_ATTEND,)) for p in kernels)
    run = {"trace": trace}
    values = {m: reader(m).read(run) for m in READERS}
    assert all(v > 0 for v in values.values())
    # the module's share lies inside the step's, attention's inside both
    assert values["mtp_ms"] < reader("step_device_ms").read(run)
    with open(FIXTURE[:-len(".xplane.pb.gz")] + ".json") as f:
        recorded = json.load(f)     # the readers' values when it was recorded
    for m in READERS:
        assert values[m] == pytest.approx(recorded[m]), m


def test_rehearsal_of_the_new_cell_reports_its_counts():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147483659", "--seconds", "2", "--trace", "1",
         "--rehearse-cpu"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] is True
    assert set(last["metrics"]) == {"attn_flash_dispatches"}
    assert last["metrics"]["attn_flash_dispatches"]["value"] == 0  # a CPU


def test_the_readings_tool_rehearses_and_refuses_the_lowered_control():
    """configs/glm-4.7-flash.readings.py at toy sizes: the honest error on
    two seeds, the six wrong variants from one compiled program, and the
    all-bfloat16 control, which run.py's own comparison refuses by the
    routing's limits."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "configs",
                                      "glm-4.7-flash.readings.py"),
         "--rehearse-cpu", "--seeds", "5,2147483659"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    said = dict(line.split(" ", 1) for line in done.stdout.splitlines()
                if line.split(" ", 1)[0].split("_")[0] in
                ("honest", "wrong", "low"))
    said = {k: json.loads(v) for k, v in said.items()}
    with open(os.path.join(BENCH, "configs", "glm-4.7-flash.json")) as f:
        limits = json.load(f)["reference_comparison"]["tolerance"]
    assert set(limits) == set(said["honest_seed_5"])
    for seed in (5, 2147483659):
        honest = said[f"honest_seed_{seed}"]
        assert honest["route_choice"] == 0 and honest["route_weights"] < 1e-5
    assert {k for k in said if k.startswith("wrong_")} == {
        "wrong_" + w for w in ("bias_in_weight", "no_scaling", "softmax_gate",
                               "capacity_1", "no_rope", "norm_over_held")}
    low = said["low_all_against_f32"]
    assert low["route_weights"] > limits["route_weights"]
    assert said["low_all_correct"] is False
