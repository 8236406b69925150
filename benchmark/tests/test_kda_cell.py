"""Tests of what the Kimi-Linear cell adds to the yardstick: the cell's configuration
against the catalog's row key by key, kda_scopes.py's literals, the new
readers on a hand-made trace whose answers can be worked out on paper, the
chunked rule's work functions, the new entries and the lists the cell was
appended to, and the new cell's rehearsal.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import kda_scopes  # noqa: E402
import pass_scopes  # noqa: E402
import xplane  # noqa: E402

CELL = "kimi-linear-48b-a3b.pretrain8k-b2"
NAME = "kimi-linear-48b-a3b"
NEW = ["kda_scan_ms", "kda_conv_ms", "kda_project_ms",
       "kda_chunked_dispatches", "kda_scan_roofline"]
SHARED = ["attn_flash_dispatches", "mla_attend_ms", "moe_experts_ms",
          "moe_route_ms", "moe_max_load_ratio", "moe_tail_share",
          "moe_head_rows_share", "moe_experts_roofline", "lm_head_ms",
          "flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms", "flash_remat_ms",
          "attn_layout_ms", "attn_project_ms", "mlp_dense_ms"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "kda_cell_reader_" + name,
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def traffic():
    with open(os.path.join(BENCH, "traffic", "pretrain8k-b2.json")) as f:
        return json.load(f)


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_configuration_is_the_catalogs_row_but_for_the_cut():
    cfg = config()
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert {k: cfg["published"][k] for k in cfg["reduced"]} == {
        "num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840}
    assert [cfg[k] for k in cfg["reduced"]] == [5, 8, 20480]
    dep = cfg["deployment"]
    assert (dep["chips_sharing_a_layer"], dep["experts_routed_over"],
            dep["held_experts"], dep["first_layer_kept"]) == (32, 256, [0, 8],
                                                              0)
    assert dep["parameters"]["total"] == 602449792
    assert cfg["linear_attn_config"] == {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4}
    assert {"kda_projections", "kda_filter", "kda_norms", "kda_output",
            "kda_decay_init", "head_dim", "router_scores", "initializer",
            "optimizer", "learning_rate"} <= set(cfg["assumed"])
    mix = traffic()
    assert (mix["seq_len"], mix["batch"], mix["block_steps"]) == (8192, 2, 1)
    # each held expert sees 16,384 x 8 / 256 = 512 rows a step
    assert mix["seq_len"] * mix["batch"] * cfg["num_experts_per_token"] \
        // dep["experts_routed_over"] == 512
    # a JSON-lines catalog of published configurations, where one is given
    catalog = os.environ.get("MODEL_CONFIG_CATALOG", "")
    if catalog and os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
        assert row["source_url"] == cfg["source"]
        assert {k: v for k, v in row["config"].items()
                if cfg[k] != v} == {k: cfg["published"][k]
                                    for k in cfg["reduced"]}


def test_kda_scopes_holds_three_literals():
    assert kda_scopes.SCOPES == ("kda.project", "kda.conv", "kda.scan")
    assert not set(kda_scopes.SCOPES) & set(pass_scopes.OWNERS)


def kda_step():
    """Two steps of 1000 ns.  In each, forward: the projections 40, the
    filters 10, the scan's body inside its loop 30, an MLA projection 20;
    backward: the scan recomputed 30 (under the checkpoint), the scan
    transposed 60, the filters transposed 15, the projections transposed
    70; the optimizer 45."""
    g = "jit(tpumx_train_step)/train_step.grad/"
    back = g + "transpose(jvp(train_step.grad))/jvp()/checkpoint/"
    ops = {"proj": ("convolution fusion", g + "jvp(kda.project)/"
                    "dot_general:"),
           "conv": ("loop fusion", g + "jvp(kda.conv)/mul:"),
           "scan": ("convolution fusion", g + "jvp(kda.scan)/while/body/"
                    "dot_general:"),
           "mla": ("convolution fusion", g + "jvp(mla.project)/"
                   "dot_general:"),
           "scan_again": ("convolution fusion", back + "rematted_computation/"
                          "kda.scan/while/body/dot_general:"),
           "scan_back": ("convolution fusion", back + "transpose(jvp("
                         "kda.scan))/while/body/dot_general:"),
           "conv_back": ("loop fusion", back + "transpose(jvp(kda.conv))/"
                         "mul:"),
           "proj_back": ("convolution fusion", back + "kda.project/"
                         "dot_general:"),
           "opt": ("loop fusion", "jit(tpumx_train_step)/"
                   "train_step.optimizer/add:")}
    name = {k: f"%{k} = bf16[8] fusion(bf16[8] %p)" for k in ops}
    meta = {name[k]: {"hlo_category": c, "tf_op": t}
            for k, (c, t) in ops.items()}
    lines = {"XLA Modules": [], "XLA Ops": []}
    for t0 in (0, 1000):
        lines["XLA Modules"].append(("jit_tpumx_train_step(1)", t0, 1000))
        at = t0
        for k, d in (("proj", 40), ("conv", 10), ("scan", 30), ("mla", 20),
                     ("scan_again", 30), ("scan_back", 60),
                     ("conv_back", 15), ("proj_back", 70), ("opt", 45)):
            lines["XLA Ops"].append((name[k], at, d))
            at += d
    return {"devices": {"/device:TPU:0": {"lines": lines, "meta": meta}},
            "host": []}


def test_the_reductions_on_a_hand_made_step():
    run = {"trace": kda_step(), "peaks": PEAKS, "cfg": config(),
           "mix": traffic()}
    assert reader("kda_scan_ms").read(run) == pytest.approx(120e-6)
    assert reader("kda_conv_ms").read(run) == pytest.approx(25e-6)
    assert reader("kda_project_ms").read(run) == pytest.approx(110e-6)
    # the KDA names are no part of the attention's
    assert reader("attn_project_ms").read(run) == pytest.approx(20e-6)
    roofline = reader("kda_scan_roofline")
    need = roofline.need_s(config(), traffic(), PEAKS)
    assert roofline.read(run) == pytest.approx(100 * need / 120e-9)


def test_the_chunked_rules_work_for_one_layer():
    """Two sequences of 8,192 positions, 32 heads of 128, chunks of 64:
    8,192 (sequence, head, chunk)s of 8 C^2 d + 2/3 C^3 + 6 C d^2 =
    10.66 MFLOP forward, four passes' worth 349.4 GFLOP (1.77 ms at 197
    TFLOP/s), against three passes of q, k, v, o in bf16 and g in f32
    (12 bytes an element of 67.1 M) and the chunk states in f32 written and
    read (8 x 134 M bytes): 3.49 GB, 4.26 ms at 819 GB/s.  The bytes bound
    it; four KDA layers need 17.0 ms a step."""
    roofline = reader("kda_scan_roofline")
    b, t, h, d = 2, 8192, 32, 128
    per = 8 * 64 ** 2 * d + 2 * 64 ** 3 / 3 + 6 * 64 * d * d
    assert roofline.layer_flops(b, t, h, d) == pytest.approx(
        4 * b * h * t / 64 * per)
    assert roofline.layer_flops(b, t, h, d) / 1e9 == pytest.approx(349.4,
                                                                   abs=0.1)
    n = b * t * h * d
    assert roofline.layer_bytes(b, t, h, d) == 36 * n + 8 * b * h * t / 64 \
        * d * d
    assert roofline.kda_layers(config()) == 4
    assert roofline.need_s(config(), traffic(), PEAKS) == pytest.approx(
        4 * roofline.layer_bytes(b, t, h, d) / 819e9)
    assert roofline.need_s(config(), traffic(), PEAKS) * 1e3 == \
        pytest.approx(17.0, abs=0.1)
    # a cut that keeps the first three layers keeps three KDA layers
    assert roofline.kda_layers(dict(config(), num_hidden_layers=3)) == 3


@pytest.mark.parametrize("metric", NEW)
def test_a_new_reader_with_nothing_to_read_returns_nothing(metric):
    """No trace, an empty one, or a program that names no KDA scope (the
    parent commit's, on any of its cells), or no counter: None, never 0
    and never an exception."""
    traces = [None, {"devices": {}, "host": []}] + [
        xplane.load(os.path.join(BENCH, "fixtures", name + ".xplane.pb.gz"))
        for name in ("decoder_step", "conv_step")]
    for trace in traces:
        for cfg in ({}, config()):
            assert reader(metric).read({
                "trace": trace, "peaks": PEAKS, "cfg": cfg, "mix": traffic(),
                "dispatch": {"pallas_flash": 2}}) is None


def test_the_counter_reads_the_dispatch():
    assert reader("kda_chunked_dispatches").read(
        {"dispatch": {"pallas_flash": 1, "kda_chunked": 9}}) == 9


def test_the_new_entries_and_the_lists_the_cell_joined():
    b = manifest()
    w = next(x for x in b["workloads"] if x["name"] == CELL)
    assert (w["chips"], w["traffic"], w["config"]) == (1, "pretrain8k-b2",
                                                       NAME)
    assert "32x" in w["why"] and "44%" in w["why"]
    assert b["workloads"][-1] == w and b["configs"][-1]["name"] == NAME
    names = [m["name"] for m in b["per_layer"]]
    assert names[-len(NEW):] == NEW
    for m in b["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["layer"] == "token mixer"
            assert m["moves"] == "samples_per_s"
        elif m["name"] in SHARED:
            assert m["workloads"][-1] == CELL
        else:
            assert CELL not in m.get("workloads", [])
    for m in NEW:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           m + ".py"))


def test_rehearsal_of_the_new_cell_reports_its_counts():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147483659", "--seconds", "6", "--trace", "1",
         "--rehearse-cpu"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] is True
    assert set(last["metrics"]) == {"attn_flash_dispatches",
                                    "kda_chunked_dispatches"}
    assert last["metrics"]["attn_flash_dispatches"]["value"] == 0  # a CPU
    # four KDA layers in each program that traces the model, and the rule
    # alone in the comparison: never 0
    assert last["metrics"]["kda_chunked_dispatches"]["value"] >= 5
