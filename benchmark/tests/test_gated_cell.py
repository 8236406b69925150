"""Tests of what ISSUE 34 added to the yardstick: the cell's files and its
published widths key by key, gate_scopes.py's literal and the two new
readers on a hand-made trace whose answers can be worked out on paper and on
a small head-gated mixed decoder step recorded on the chip, the window
kernels' work functions, the new cell's rehearsal and its readings tool.

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
import gate_scopes  # noqa: E402
import xplane  # noqa: E402

CELL = "laguna-s-2.1.pretrain8k"
NAME = "laguna-s-2.1"
TRACE_READERS = ["attn_gate_ms", "attn_window_ms", "attn_full_ms",
                 "lm_head_ms"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SHARED = ["attn_flash_dispatches", "attn_window_ms", "attn_full_ms",
          "attn_blocks_run_share", "lm_head_ms", "moe_experts_ms",
          "moe_route_ms", "moe_max_load_ratio", "moe_tail_share",
          "moe_head_rows_share"]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def gated_step():
    """Two steps of 1000 ns.  In each, forward: a projection 50, the full
    layer's kernel 200, its gate's product 12 and multiply 8, a window
    layer's rotary turn 15, its kernel 30, its gate's product 18 and
    multiply 10; backward, from the first transposed operation on: the
    window layer's gate recomputed 28 and transposed 40, its kernel
    recomputed 30 and its two backward kernels 35 and 45, the full layer's
    gate transposed 25 and its two backward kernels 150 and 170; the
    optimizer 45."""
    g = "jit(tpumx_train_step)/train_step.grad/"
    back = g + "transpose(jvp(train_step.grad))/jvp()/checkpoint/"
    kernel = "custom-call"
    ops = {"project": ("convolution fusion", g + "jvp(attn.project)/"
                       "dot_general:"),
           "full_fwd": (kernel, g + "jvp(attn.full)/jit(_fwd)/pallas_call:"),
           "full_gate_dot": ("convolution fusion", g + "jvp(attn.gate)/"
                             "dot_general:"),
           "full_gate_mul": ("loop fusion", g + "jvp(attn.gate)/mul:"),
           "turn": ("loop fusion", g + "jvp(attn.window)/mul:"),
           "window_fwd": (kernel, g + "jvp(attn.window)/jit(_fwd)/"
                          "pallas_call:"),
           "window_gate_dot": ("convolution fusion", g + "jvp(attn.gate)/"
                               "dot_general:"),
           "window_gate_mul": ("loop fusion", g + "jvp(attn.gate)/logistic:"),
           "window_gate_again": ("loop fusion", back + "rematted_computation/"
                                 "attn.gate/mul:"),
           "window_gate_back": ("convolution fusion", back + "attn.gate/"
                                "dot_general:"),
           "window_again": (kernel, back + "rematted_computation/attn.window/"
                            "jit(_fwd)/pallas_call:"),
           "window_dq": (kernel, back + "attn.window/jit(_bwd_call)/"
                         "pallas_call:"),
           "window_dkv": (kernel, back + "attn.window/jit(_bwd_call)/"
                          "pallas_call:"),
           "full_gate_back": ("convolution fusion", g + "transpose(jvp("
                              "attn.gate))/dot_general:"),
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
    for t0 in (0, 1000):
        lines["XLA Modules"].append(("jit_tpumx_train_step(1)", t0, 1000))
        at = t0
        for k, d in (("project", 50), ("full_fwd", 200),
                     ("full_gate_dot", 12), ("full_gate_mul", 8),
                     ("turn", 15), ("window_fwd", 30),
                     ("window_gate_dot", 18), ("window_gate_mul", 10),
                     ("window_gate_again", 28), ("window_gate_back", 40),
                     ("window_again", 30), ("window_dq", 35),
                     ("window_dkv", 45), ("full_gate_back", 25),
                     ("full_dq", 150), ("full_dkv", 170), ("opt", 45)):
            lines["XLA Ops"].append((name[k], at, d))
            at += d
    return {"devices": {"/device:TPU:0": {"lines": lines, "meta": meta}},
            "host": []}


def test_the_reductions_on_a_hand_made_step():
    run = {"trace": gated_step()}
    # 12 + 8 + 18 + 10 forward, 28 recomputed, 40 + 25 backward, a step
    assert reader("attn_gate_ms").read(run) == pytest.approx(141e-6)
    # the gate is no part of the attention scopes beside it
    assert reader("attn_window_ms").read(run) == pytest.approx(155e-6)
    assert reader("attn_full_ms").read(run) == pytest.approx(520e-6)
    assert attention_scopes.scope_ms(
        run["trace"], (attention_scopes.ATTN_WINDOW,), kernels=True) \
        == pytest.approx(140e-6)
    assert attention_scopes.scope_ms(
        run["trace"], (gate_scopes.ATTN_GATE,), kernels=True) is None


@pytest.mark.parametrize("metric", ["attn_gate_ms", "window_kernel_roofline"])
def test_a_new_reader_with_nothing_to_read_returns_nothing(metric):
    """No trace, an empty one, or a program that names no such scope (the
    parent commit's, on any of its cells): None, never 0 and never an
    exception."""
    traces = [None, {"devices": {}, "host": []}] + [
        xplane.load(os.path.join(BENCH, "fixtures", name + ".xplane.pb.gz"))
        for name in ("scoped_step", "decoder_step")]
    if metric == "attn_gate_ms":    # the 16k cell has windows and no gate
        traces.append(xplane.load(os.path.join(
            BENCH, "fixtures", "windowed_step.xplane.pb.gz")))
    for trace in traces:
        for cfg in ({}, config()):
            assert reader(metric).read({
                "trace": trace, "peaks": None, "cfg": cfg,
                "mix": {"seq_len": 8192, "batch": 1}}) is None
    # the 16k cell's trace and its configuration: windows, but no per-layer
    # head counts to take the work from
    with open(os.path.join(BENCH, "configs",
                           "smallthinker-21ba3b.json")) as f:
        other = json.load(f)
    assert reader("window_kernel_roofline").read({
        "trace": xplane.load(os.path.join(
            BENCH, "fixtures", "windowed_step.xplane.pb.gz")),
        "peaks": PEAKS, "cfg": other,
        "mix": {"seq_len": 16384, "batch": 1}}) is None


def test_the_window_kernels_work_for_one_layer():
    """T 8,192, window 512, 72 query heads over 8 of 128: 4,063,488 pairs
    (512 x 513 / 2 + 7,680 x 512), 0.4494 TFLOP (2.281 ms at 197 TFLOP/s)
    against 1.007 GB of six passes over 80 heads' rows (1.229 ms at 819
    GB/s): the FLOPs bound it; three such layers need 6.84 ms.  A full
    layer's 33.56 M pairs at 48 heads: 12.56 ms."""
    roofline = reader("window_kernel_roofline")
    assert roofline.pairs(8192, 512) == 512 * 513 // 2 + 7680 * 512 == 4063488
    assert roofline.pairs(8192, 8192) == roofline.pairs(8192, 99999) \
        == 33558528
    assert roofline.pairs(8, 3) == 6 + 5 * 3       # rows see 1, 2, 3, 3, ...
    need = roofline.layer_need_s(8192, 512, 128, 72, 8, PEAKS)
    assert need == pytest.approx(12 * 4063488 * 128 * 72 / 197e12)
    assert need == pytest.approx(2.281e-3, rel=1e-3)
    assert 6 * 8192 * 128 * 2 * 80 / 819e9 == pytest.approx(1.229e-3,
                                                            rel=1e-3)
    assert roofline.layer_need_s(8192, 8192, 128, 48, 8, PEAKS) \
        == pytest.approx(12.56e-3, rel=1e-3)
    # at a short sequence the bytes bound it
    few = roofline.layer_need_s(128, 64, 128, 72, 8, PEAKS)
    assert few == pytest.approx(128 * 128 * 2 * 6 * 80 / 819e9)
    cfg = config()
    assert roofline.window_heads(cfg) == [72, 72, 72]
    assert roofline.need_s(cfg, {"seq_len": 8192, "batch": 1}, PEAKS) \
        == pytest.approx(6.84e-3, rel=1e-3)
    # each layer at its own head count: a layer list that differs, differs
    odd = dict(cfg, num_attention_heads_per_layer=[48, 72, 36, 72, 48])
    assert roofline.need_s(odd, {"seq_len": 8192, "batch": 2}, PEAKS) \
        == pytest.approx(2 * 2.5 * need)


def test_the_roofline_is_the_windows_need_over_its_kernels_time():
    """The cell's three window layers over the hand-made step's 140 ns of
    kernels under attn.window, recomputed forward included."""
    roofline = reader("window_kernel_roofline")
    run = {"trace": gated_step(), "peaks": PEAKS, "cfg": config(),
           "mix": {"seq_len": 8192, "batch": 1}}
    need = 3 * roofline.layer_need_s(8192, 512, 128, 72, 8, PEAKS)
    assert roofline.read(run) == pytest.approx(100 * need / 140e-9)
    assert roofline.read(dict(run, peaks=None)) is None
    # the 16k cell's reader would take 48 heads for the window layers' 72:
    # the file has no key that it reads, so it reports nothing here
    assert "sliding_window_layout" not in run["cfg"]
    assert reader("attn_window_roofline").read(run) is None


def test_the_blocks_run_share_of_the_cells_window_layers():
    """blocks_run(8192, 8192, True, 512) at today's blocks of 512 x 1,024:
    23 of 128, 17.97%, three times the need (6.06% of the square's pairs
    lie inside a window)."""
    from tpu_mx.kernels.flash_attention import blocks_run
    grid, run = blocks_run(8192, 8192, True, 512)
    assert (grid, run) == (128, 23)
    dispatch = importlib.import_module("tpu_mx.parallel.ring_attention")
    share = reader("attn_blocks_run_share")
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(dispatch, "window_blocks", {"grid": grid, "run": run})
        assert share.read({}) == pytest.approx(17.97, abs=0.005)
    assert 100 * reader("window_kernel_roofline").pairs(8192, 512) \
        / 8192 ** 2 == pytest.approx(6.06, abs=0.01)


def test_every_file_of_the_new_cell_exists_and_no_width_is_reduced():
    """What test_benchmark.py's test_every_file_of_a_cell_exists asks of a
    cell, with the widths spelt out: its pattern `hidden` also takes the
    depth key `num_hidden_layers` for one."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    w = next(x for x in b["workloads"] if x["name"] == CELL)
    assert (w["chips"], w["traffic"]) == (1, "pretrain8k")
    assert "32x" in w["why"]
    entry = next(c for c in b["configs"] if c["name"] == w["config"])
    wanted = [entry["file"], f"benchmark/configs/{w['config']}.py",
              f"benchmark/references/{w['config']}.py",
              f"benchmark/traffic/{w['traffic']}.json"]
    mine = [m["name"] for m in b["per_layer"]
            if CELL in m.get("workloads", [CELL])]
    assert set(mine) >= set(SHARED) | {"attn_gate_ms",
                                       "window_kernel_roofline", "mfu"}
    assert not {"moe_experts_roofline", "attn_window_roofline",
                "mla_attend_ms", "mtp_ms"} & set(mine)
    # the two new metrics list the new cell alone; the shared lists end in it
    for m in b["per_layer"]:
        if m["name"] in ("attn_gate_ms", "window_kernel_roofline"):
            assert m["workloads"] == [CELL]
        elif m["name"] in SHARED:
            assert m["workloads"][-1] == CELL
    wanted += [f"benchmark/layer_metrics/{m}.py" for m in mine]
    assert [p for p in wanted if not os.path.exists(os.path.join(ROOT, p))] \
        == []
    cfg = config()
    assert cfg["source"] == entry["source"] \
        == "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json"
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    widths = re.compile(r"(hidden_size|intermediate|_dim$|_rank$|head_size|"
                        r"channels|experts_per_tok)")
    assert not [k for k in cfg["reduced"] if widths.search(k)]
    assert cfg["published"]["num_hidden_layers"] == 48 \
        and cfg["published"]["num_experts"] == 256 \
        and cfg["published"]["vocab_size"] == 100352
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 8, 12544)
    assert cfg["deployment"]["chips_sharing_a_layer"] == 32 \
        and cfg["deployment"]["experts_routed_over"] == 256 \
        and cfg["deployment"]["held_experts"] == [0, 8]
    assert cfg["reference_comparison"]["tolerance"]
    assert {"gate", "router_scores", "qk_norm", "initializer"} \
        <= set(cfg["assumed"])
    # the published widths, heads, window, thetas, router and experts a
    # token, key by key
    assert {k: cfg[k] for k in (
        "model_type", "hidden_size", "intermediate_size",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "max_position_embeddings", "attention_bias", "rms_norm_eps",
        "num_experts_per_tok", "moe_intermediate_size",
        "shared_expert_intermediate_size", "norm_topk_prob",
        "decoder_sparse_step", "mlp_only_layers", "tie_word_embeddings",
        "gating", "sliding_window", "moe_apply_router_weight_on_input",
        "moe_routed_scaling_factor", "moe_router_logit_softcapping")} == {
        "model_type": "laguna", "hidden_size": 3072,
        "intermediate_size": 12288, "num_attention_heads": 48,
        "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 1048576, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts_per_tok": 10,
        "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [0],
        "tie_word_embeddings": False, "gating": "per-head",
        "sliding_window": 512, "moe_apply_router_weight_on_input": False,
        "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0}
    assert cfg["rope_parameters"] == {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}
    period = ["full_attention"] + ["sliding_attention"] * 3
    assert cfg["layer_types"] == period * 12
    assert cfg["num_attention_heads_per_layer"] == [48, 72, 72, 72] * 12
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert cfg["gating_types"] == ["per_head"] * 48
    with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
        mix = json.load(f)
    assert (mix["seq_len"], mix["batch"], mix["block_steps"]) == (8192, 1, 2)
    assert mix["seq_len"] == cfg["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"]
    # against the catalog's row, where the guide is installed
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Laguna-S-2.1")
        assert row["source_url"] == cfg["source"]
        assert {k: v for k, v in row["config"].items()
                if cfg[k] != v} == {k: cfg["published"][k]
                                    for k in cfg["reduced"]}


def test_gate_scopes_holds_one_literal():
    assert gate_scopes.SCOPES == ("attn.gate",)
    assert gate_scopes.ATTN_GATE not in attention_scopes.SCOPES
    assert gate_scopes.ATTN_GATE not in decoder_scopes.SCOPES


FIXTURE = os.path.join(BENCH, "fixtures", "gated_step.xplane.pb.gz")


def test_the_recorded_gated_step():
    """Four executions of a small head-gated mixed decoder's train step
    recorded on a TPU v5e (PR 34's first chip call: hidden 256, a full layer
    of 12 gated heads over 2 of 64 that turns half of each head, two layers
    of 18 with a window of 256, T 1024, a dense layer then 2 of 16 experts
    and a shared one, the head in chunks of 256), with the benchmark's own
    annotations."""
    trace = xplane.load(FIXTURE)
    steps, ops = decoder_scopes.step_ops(trace)
    assert len(steps) == 4
    kernels = [p for _, p, _, _ in ops if attention_scopes.KERNEL in p]
    # a step and a layer: forward, recomputed forward, dq, dk/dv
    assert len(kernels) == 4 * 3 * 4
    assert sum(decoder_scopes.under(p, (attention_scopes.ATTN_WINDOW,))
               for p in kernels) == 2 * len(kernels) // 3
    gate = [p for _, p, _, _ in ops
            if decoder_scopes.under(p, (gate_scopes.ATTN_GATE,))]
    assert gate and not any(attention_scopes.KERNEL in p for p in gate)
    assert not any(decoder_scopes.under(p, attention_scopes.SCOPES)
                   for p in gate)
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
    # the roofline reader on the recording, at the recording's own sizes
    cfg = dict(config(), **recorded["sizes"])
    share = reader("window_kernel_roofline").read({
        "trace": trace, "peaks": PEAKS, "cfg": cfg,
        "mix": {"seq_len": 1024, "batch": 1}})
    assert share == pytest.approx(recorded["window_kernel_roofline"])
    assert 0 < share < 100


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
    """configs/laguna-s-2.1.readings.py at toy sizes: the honest error on two
    seeds, the eleven wrong variants from one compiled program, and the
    all-bfloat16 control, which run.py's own comparison refuses by the
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
    limits = config()["reference_comparison"]["tolerance"]
    assert set(limits) == set(said["honest_seed_5"])
    for seed in (5, 2147483659):
        honest = said[f"honest_seed_{seed}"]
        assert honest["route_choice"] == 0 and honest["route_weights"] < 1e-5
    assert {k for k in said if k.startswith("wrong_")} == {
        "wrong_" + w for w in (
            "gate_off", "gate_after_output_projection",
            "rotary_whole_head_in_full_layers", "yarn_off",
            "attention_factor_off", "thetas_swapped", "window_off_by_one",
            "softmax_scores", "scaling_off", "chosen_not_normalised",
            "shared_expert_off")}
    honest = said["honest_seed_2147483659"]
    for wrong in (k for k in said if k.startswith("wrong_")):
        # gate_off leaves W_g no gradient to divide by: null, beyond all
        assert max(float("inf") if said[wrong][k] is None
                   else said[wrong][k] / max(honest[k], 1e-6)
                   for k in honest) > 2, wrong
    low = said["low_all_against_f32"]
    assert low["route_weights"] > limits["route_weights"]
    assert said["low_all_correct"] is False
