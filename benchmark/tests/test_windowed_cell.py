"""Tests of what ISSUE 31 added to the yardstick: attention_scopes.py's
reduction and the five new readers on a hand-made trace whose answers can be
worked out on paper and on a small windowed decoder step recorded on the
chip, the window roofline's work functions, the new cell's rehearsal and
its readings tool.

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

import attention_scopes  # noqa: E402
import decoder_scopes  # noqa: E402
import xplane  # noqa: E402

CELL = "smallthinker-21ba3b.extend16k"
NAME = "smallthinker-21ba3b"
TRACE_READERS = ["attn_window_ms", "attn_full_ms", "lm_head_ms"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def windowed_step():
    """Two steps of 1500 ns.  In each, forward: the global layer's kernel
    200 and its head layout 10, a window layer's rotary turn 15 and its
    kernel 90, a projection 50, the routing gather 40, the head's chunk
    product 120; backward, from the first transposed operation on: the
    head's chunk recomputed 120 and its backward 130, the window kernel
    recomputed 90 and its two backward kernels 80 and 110, the global
    layer's two backward kernels 150 and 170; the optimizer 45."""
    g = "jit(tpumx_train_step)/train_step.grad/"
    back = g + "transpose(jvp(train_step.grad))/jvp()/checkpoint/"
    kernel = "custom-call"
    ops = {"full_fwd": (kernel, g + "jvp(attn.full)/jit(_fwd)/pallas_call:"),
           "full_heads": ("data formatting", g + "jvp(attn.full)/transpose:"),
           "turn": ("loop fusion", g + "jvp(attn.window)/mul:"),
           "window_fwd": (kernel, g + "jvp(attn.window)/jit(_fwd)/"
                          "pallas_call:"),
           "project": ("convolution fusion", g + "jvp(attn.project)/"
                       "dot_general:"),
           "route": ("data formatting", g + "jvp(moe.route)/gather:"),
           "head": ("convolution fusion", g + "jvp(lm_head)/checkpoint/"
                    "dot_general:"),
           "head_again": ("convolution fusion", g + "transpose(jvp(lm_head))/"
                          "checkpoint/rematted_computation/dot_general:"),
           "head_back": ("convolution fusion", g + "transpose(jvp(lm_head))/"
                         "dot_general:"),
           "window_again": (kernel, back + "rematted_computation/attn.window/"
                            "jit(_fwd)/pallas_call:"),
           "window_dq": (kernel, back + "attn.window/jit(_bwd_call)/"
                         "pallas_call:"),
           "window_dkv": (kernel, back + "attn.window/jit(_bwd_call)/"
                          "pallas_call:"),
           "full_dq": (kernel, back + "attn.full/jit(_bwd_call)/"
                       "pallas_call:"),
           "full_dkv": (kernel, back + "attn.full/jit(_bwd_call)/"
                        "pallas_call:"),
           "opt": ("loop fusion", "jit(tpumx_train_step)/"
                   "train_step.optimizer/add:")}
    name = {k: f"%{k} = bf16[8] fusion(bf16[8] %p)" for k in ops}
    meta = {name[k]: {"hlo_category": c, "tf_op": t}
            for k, (c, t) in ops.items()}
    lines = {"XLA Modules": [], "XLA Ops": []}
    for t0 in (0, 1500):
        lines["XLA Modules"].append(("jit_tpumx_train_step(1)", t0, 1500))
        at = t0
        for k, d in (("full_fwd", 200), ("full_heads", 10), ("turn", 15),
                     ("window_fwd", 90), ("project", 50), ("route", 40),
                     ("head", 120), ("head_again", 120), ("head_back", 130),
                     ("window_again", 90), ("window_dq", 80),
                     ("window_dkv", 110), ("full_dq", 150), ("full_dkv", 170),
                     ("opt", 45)):
            lines["XLA Ops"].append((name[k], at, d))
            at += d
    return {"devices": {"/device:TPU:0": {"lines": lines, "meta": meta}},
            "host": []}


def test_the_reductions_on_a_hand_made_step():
    run = {"trace": windowed_step()}
    # the rotary turn 15 and the kernels 90 + 90 + 80 + 110, a step
    assert reader("attn_window_ms").read(run) == pytest.approx(385e-6)
    # the head layout 10 and the kernels 200 + 150 + 170
    assert reader("attn_full_ms").read(run) == pytest.approx(530e-6)
    assert reader("lm_head_ms").read(run) == pytest.approx(370e-6)
    assert attention_scopes.scope_ms(
        run["trace"], (attention_scopes.ATTN_WINDOW,), kernels=True) \
        == pytest.approx(370e-6)
    assert attention_scopes.scope_ms(
        run["trace"], (attention_scopes.ATTN_PROJECT,)) \
        == pytest.approx(50e-6)
    # the accepted readers see their own scopes in the same step
    assert reader("moe_route_ms").read(run) == pytest.approx(40e-6)


@pytest.mark.parametrize("metric", TRACE_READERS + ["attn_window_roofline"])
def test_a_reader_with_nothing_to_read_returns_nothing(metric):
    """No trace, an empty one, or a program that names no such scope (the
    parent commit's, or the other decoder cell's): None, never 0 and never
    an exception."""
    traces = [None, {"devices": {}, "host": []}, xplane.load(os.path.join(
        BENCH, "fixtures", "scoped_step.xplane.pb.gz"))]
    if metric != "lm_head_ms":      # the other decoder names its head too
        traces.append(xplane.load(os.path.join(
            BENCH, "fixtures", "decoder_step.xplane.pb.gz")))
    for trace in traces:
        assert reader(metric).read({"trace": trace, "peaks": None, "cfg": {},
                                    "mix": {}}) is None


def test_the_counter_reads_nothing_where_no_window_was_dispatched(
        monkeypatch):
    dispatch = importlib.import_module("tpu_mx.parallel.ring_attention")
    share = reader("attn_blocks_run_share")
    monkeypatch.setattr(dispatch, "window_blocks", {"grid": 0, "run": 0})
    assert share.read({}) is None
    monkeypatch.setattr(dispatch, "window_blocks", {"grid": 1536, "run": 420})
    assert share.read({}) == pytest.approx(27.34375)
    monkeypatch.delattr(dispatch, "window_blocks")  # the parent's program
    assert share.read({}) is None


def test_the_window_rooflines_work_for_one_layer():
    """T 16,384, window 4,096, 28 query heads over 4 of 128: 58.72 M pairs,
    2.526 TFLOP (12.82 ms at 197 TFLOP/s) against 805 MB (0.98 ms at 819
    GB/s); the global layer's 134.23 M pairs for comparison."""
    roofline = reader("attn_window_roofline")
    assert roofline.pairs(16384, 4096) == 58722304
    assert roofline.pairs(16384, 16384) == roofline.pairs(16384, 99999) \
        == 134225920
    assert roofline.pairs(8, 3) == 6 + 5 * 3       # rows see 1, 2, 3, 3, ...
    need = roofline.layer_need_s(16384, 4096, 128, 28, 4, PEAKS)
    assert need == pytest.approx(12 * 58722304 * 128 * 28 / 197e12)
    assert need == pytest.approx(12.82e-3, rel=1e-3)
    # at a short sequence the bytes bound it
    few = roofline.layer_need_s(128, 64, 128, 28, 4, PEAKS)
    assert few == pytest.approx(128 * 128 * 2 * 6 * (28 + 4) / 819e9)


def test_the_roofline_is_the_windows_need_over_its_kernels_time():
    """Three window layers of the cell's sizes over the hand-made step's
    370 ns of kernels under attn.window, recomputed forward included."""
    roofline = reader("attn_window_roofline")
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        cfg = json.load(f)
    run = {"trace": windowed_step(), "peaks": PEAKS, "cfg": cfg,
           "mix": {"seq_len": 16384, "batch": 1}}
    need = 3 * roofline.layer_need_s(16384, 4096, 128, 28, 4, PEAKS)
    assert roofline.read(run) == pytest.approx(100 * need / 370e-9)
    assert roofline.read(dict(run, peaks=None)) is None


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
    mine = [m["name"] for m in b["per_layer"]
            if CELL in m.get("workloads", [CELL])]
    assert set(mine) >= {"attn_window_ms", "attn_full_ms", "lm_head_ms",
                         "attn_window_roofline", "attn_blocks_run_share",
                         "moe_experts_ms", "moe_route_ms",
                         "moe_max_load_ratio", "attn_flash_dispatches"}
    assert not {"moe_experts_roofline", "mla_attend_ms", "mtp_ms"} & set(mine)
    wanted += [f"benchmark/layer_metrics/{m}.py" for m in mine]
    assert [p for p in wanted if not os.path.exists(os.path.join(ROOT, p))] \
        == []
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == config["reduced"] == [
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
    widths = re.compile(r"(hidden_size|intermediate|_dim$|_rank$|head_size|"
                        r"channels|active_primary_experts)")
    assert not [k for k in cfg["reduced"] if widths.search(k)]
    assert set(cfg["published"]) >= set(cfg["reduced"])
    assert cfg["reference_comparison"]["tolerance"]
    # the published widths, heads, window, theta and experts a token
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["moe_ffn_hidden_size"],
            cfg["moe_num_active_primary_experts"], cfg["sliding_window_size"],
            cfg["rope_theta"], cfg["max_position_embeddings"]) \
        == (2560, 128, 28, 4, 768, 6, 4096, 1500000, 16384)
    assert cfg["rope_layout"] == cfg["sliding_window_layout"] \
        == [0, 1, 1, 1] * 13
    with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
        assert json.load(f)["seq_len"] == cfg["max_position_embeddings"]


FIXTURE = os.path.join(BENCH, "fixtures", "windowed_step.xplane.pb.gz")


def test_the_recorded_windowed_step():
    """Four executions of a small windowed grouped-query decoder's train step
    recorded on a TPU v5e (PR 31's first chip call: hidden 256, 4 query
    heads over 2 of 64, T 1024, one global layer and one with a window of
    256, the head in chunks of 256), with the benchmark's own annotations."""
    trace = xplane.load(FIXTURE)
    steps, ops = decoder_scopes.step_ops(trace)
    assert len(steps) == 4
    kernels = [p for _, p, _, _ in ops if attention_scopes.KERNEL in p]
    # a step and a layer: forward, recomputed forward, dq, dk/dv
    assert len(kernels) == 4 * 2 * 4
    assert sum(decoder_scopes.under(p, (attention_scopes.ATTN_WINDOW,))
               for p in kernels) == len(kernels) // 2
    assert all(decoder_scopes.under(p, (attention_scopes.ATTN_WINDOW,
                                        attention_scopes.ATTN_FULL))
               for p in kernels)
    run = {"trace": trace}
    readers = TRACE_READERS + ["moe_route_ms", "moe_experts_ms",
                               "step_device_ms"]
    values = {m: reader(m).read(run) for m in readers}
    assert all(v > 0 for v in values.values())
    assert sum(values[m] for m in readers[:-1]) < values["step_device_ms"]
    with open(FIXTURE[:-len(".xplane.pb.gz")] + ".json") as f:
        recorded = json.load(f)     # the readers' values when it was recorded
    for m in readers:
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
    """configs/smallthinker-21ba3b.readings.py at toy sizes: the honest error
    on two seeds, the nine wrong variants from one compiled program, and
    the all-bfloat16 control, which run.py's own comparison refuses by the
    routing's limits."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "configs",
                                      NAME + ".readings.py"),
         "--rehearse-cpu", "--seeds", "5,2147483659"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    said = dict(line.split(" ", 1) for line in done.stdout.splitlines()
                if line.split(" ", 1)[0].split("_")[0] in
                ("honest", "wrong", "low"))
    said = {k: json.loads(v) for k, v in said.items()}
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        limits = json.load(f)["reference_comparison"]["tolerance"]
    assert set(limits) == set(said["honest_seed_5"])
    for seed in (5, 2147483659):
        honest = said[f"honest_seed_{seed}"]
        assert honest["route_choice"] == 0 and honest["route_weights"] < 1e-5
    assert {k for k in said if k.startswith("wrong_")} == {
        "wrong_" + w for w in (
            "no_window", "window_off_by_one", "rope_on_global", "no_rope",
            "router_after_attention", "sigmoid_gate", "silu_experts",
            "kv_heads_interleaved", "norm_over_held")}
    honest = said["honest_seed_2147483659"]
    for wrong in (k for k in said if k.startswith("wrong_")):
        assert max(said[wrong][k] / max(honest[k], 1e-6) for k in honest) \
            > 2, wrong
    low = said["low_all_against_f32"]
    assert low["route_weights"] > limits["route_weights"]
    assert said["low_all_correct"] is False
