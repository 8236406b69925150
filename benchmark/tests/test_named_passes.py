"""Tests of what ISSUE 36 added to the yardstick: pass_scopes.py's literals
and its one reduction, the eight readers of the step's passes on a hand-made
trace whose answers can be worked out on paper, on the older chip recordings
(programs whose kernels carry no name) and on a small head-gated mixed
decoder step recorded on the chip with the names, and the eight entries.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""
import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import attention_scopes  # noqa: E402
import decoder_scopes  # noqa: E402
import gate_scopes  # noqa: E402
import pass_scopes  # noqa: E402
import scopes  # noqa: E402
import xplane  # noqa: E402

FLASH_CELLS = ["bert-base.mlm512", "glm-4.7-flash.pretrain4k",
               "smallthinker-21ba3b.extend16k", "laguna-s-2.1.pretrain8k"]
DECODER_CELLS = FLASH_CELLS[1:]
# name: (unit, better, layer, cells), in the order of the entries
NEW = {
    "flash_fwd_ms": ("ms", "lower", "kernels", FLASH_CELLS),
    "flash_dq_ms": ("ms", "lower", "kernels", FLASH_CELLS),
    "flash_dkv_ms": ("ms", "lower", "kernels", FLASH_CELLS),
    "flash_remat_ms": ("ms", "lower", "kernels", DECODER_CELLS),
    "attn_layout_ms": ("ms", "lower", "attention dispatch", DECODER_CELLS),
    "attn_project_ms": ("ms", "lower", "attention dispatch", DECODER_CELLS),
    "mlp_dense_ms": ("ms", "lower", "compiled step",
                     [DECODER_CELLS[0], DECODER_CELLS[2]]),
    "step_owned_share": ("%", "higher", "compiled step", DECODER_CELLS),
}
OLD_RECORDINGS = ["gated_step", "windowed_step", "decoder_step"]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_all(trace):
    return {m: reader(m).read({"trace": trace}) for m in NEW}


def recording(name):
    return xplane.load(os.path.join(BENCH, "fixtures",
                                    name + ".xplane.pb.gz"))


def named_step():
    """Two steps of 2000 ns.  In each, forward: the projections 50, the dense
    layer's MLP 60, the full layer's turn 10 and its kernel 100, the window
    layer's kernel 30, that layer's shared expert 20, a norm that nobody
    owns 25; a `while` of 90 around two chunks of the head's loss, 40 each,
    and an allocation of no duration at the first one's start; backward:
    the window layer's kernel again 30 and its turn again 5, `delta` 6, dq
    35, dk/dv 45, the shared expert 22, the projections' two 70, a grouped
    product without an op path 15; the full layer's kernel again 100, dq
    110, dk/dv 130; the dense MLP again 60 and backward 110; the optimizer
    7."""
    g = "jit(tpumx_train_step)/train_step.grad/"
    back = g + "transpose(jvp(train_step.grad))/jvp()/checkpoint/"
    again = back + "rematted_computation/"
    kernel, dot, loop = "custom-call", "convolution fusion", "loop fusion"
    ops = {
        "project": (dot, g + "jvp(attn.project)/dot_general:", 50),
        "dense": (dot, g + "jvp(mlp.dense)/dot_general:", 60),
        "full_turn": (loop, g + "jvp(attn.full)/mul:", 10),
        "full_fwd": (kernel, g + "jvp(attn.full)/jit(_fwd)/flash.fwd/"
                     "pallas_call:", 100),
        "window_fwd": (kernel, g + "jvp(attn.window)/jit(_fwd)/flash.fwd/"
                       "pallas_call:", 30),
        "shared": (dot, g + "jvp(moe.shared)/mlp.dense/dot_general:", 20),
        "norm": (loop, g + "jvp()/rsqrt:", 25),
        "while": ("while", "", 90),
        "alloc": (kernel, "", 0),
        "chunk": (dot, g + "jvp(lm_head)/while/body/dot_general:", 40),
        "window_again": (kernel, again + "attn.window/jit(_fwd)/flash.fwd/"
                         "pallas_call:", 30),
        "window_turn_again": (loop, again + "attn.window/mul:", 5),
        "window_delta": (loop, back + "attn.window/jit(_bwd_call)/"
                         "reduce_sum:", 6),
        "window_dq": (kernel, back + "attn.window/jit(_bwd_call)/flash.dq/"
                      "pallas_call:", 35),
        "window_dkv": (kernel, back + "attn.window/jit(_bwd_call)/"
                       "flash.dkv/pallas_call:", 45),
        "shared_back": (dot, back + "moe.shared/mlp.dense/dot_general:", 22),
        "project_back": (dot, back + "attn.project/dot_general:", 70),
        "grouped": (kernel, "", 15),
        "full_again": (kernel, again + "attn.full/jit(_fwd)/flash.fwd/"
                       "pallas_call:", 100),
        "full_dq": (kernel, back + "attn.full/jit(_bwd_call)/flash.dq/"
                    "pallas_call:", 110),
        "full_dkv": (kernel, back + "attn.full/jit(_bwd_call)/flash.dkv/"
                     "pallas_call:", 130),
        "dense_again": (dot, again + "mlp.dense/dot_general:", 60),
        "dense_back": (dot, back + "mlp.dense/dot_general:", 110),
        "opt": (loop, "jit(tpumx_train_step)/train_step.optimizer/add:", 7)}
    name = {k: f"%{k} = bf16[8] fusion(bf16[8] %p)" for k in ops}
    name["grouped"] = "%ragged-dot-none.1 = bf16[8] custom-call(bf16[8] %p)"
    meta = {name[k]: {"hlo_category": c, "tf_op": t}
            for k, (c, t, _) in ops.items()}
    lines = {"XLA Modules": [], "XLA Ops": []}
    for t0 in (0, 2000):
        lines["XLA Modules"].append(("jit_tpumx_train_step(1)", t0, 2000))
        at = t0
        for k, (_, _, d) in ops.items():
            if k == "chunk":        # the two chunks fill the `while`
                lines["XLA Ops"] += [(name[k], at, d), (name[k], at + 45, d)]
                at += 90
            else:
                lines["XLA Ops"].append((name[k], at, d))
                at += d if k != "while" else 0      # its inside follows
        assert at - t0 == 1130
    return {"devices": {"/device:TPU:0": {"lines": lines, "meta": meta}},
            "host": []}


def test_pass_scopes_is_the_union_of_the_literals_files():
    assert pass_scopes.FLASH == ("flash.fwd", "flash.dq", "flash.dkv")
    assert (pass_scopes.MLP_DENSE, pass_scopes.REMAT) \
        == ("mlp.dense", "rematted_computation")
    assert pass_scopes.MODEL == decoder_scopes.SCOPES \
        + attention_scopes.SCOPES + gate_scopes.SCOPES + ("mlp.dense",)
    assert pass_scopes.OWNERS == pass_scopes.MODEL + scopes.SCOPES[1:]
    assert scopes.GRAD == scopes.SCOPES[0] not in pass_scopes.OWNERS
    assert len(set(pass_scopes.OWNERS)) == len(pass_scopes.OWNERS) == 17
    # a kernel's name owns nothing: its scope around it does
    assert not set(pass_scopes.FLASH) & set(pass_scopes.OWNERS)
    assert pass_scopes.ATTEND == ("mla.attend", "attn.window", "attn.full")
    assert pass_scopes.PROJECT == ("mla.project", "attn.project")


def test_the_reductions_on_a_hand_made_step():
    got = read_all(named_step())
    assert got["flash_fwd_ms"] == pytest.approx(260e-6)    # 100 + 30, twice
    assert got["flash_remat_ms"] == pytest.approx(130e-6)
    assert got["flash_dq_ms"] == pytest.approx(145e-6)
    assert got["flash_dkv_ms"] == pytest.approx(175e-6)
    # the turns 10 + 5 and `delta` 6: under the attention's names, under
    # none of the kernels'
    assert got["attn_layout_ms"] == pytest.approx(21e-6)
    assert got["attn_project_ms"] == pytest.approx(120e-6)
    # layer 0's 60 + 60 + 110; the shared expert's 42 are the expert layer's
    assert got["mlp_dense_ms"] == pytest.approx(230e-6)
    # all but the norm's 25, of the 1120 that ran: the `while`'s 90 are its
    # two chunks' 80, counted once; the allocation takes no time
    assert got["step_owned_share"] == pytest.approx(100 * 1095 / 1120)


def test_the_parts_add_up_to_the_scopes_that_exist():
    """The kernel's name is the component before `pallas_call`, which still
    ends the op path: attention_scopes.KERNEL finds what it found, and the
    four new readers split what attn_window_ms + attn_full_ms read."""
    trace = named_step()
    got = read_all(trace)
    kernels = attention_scopes.scope_ms(trace, pass_scopes.ATTEND,
                                        kernels=True)
    assert got["flash_fwd_ms"] + got["flash_dq_ms"] + got["flash_dkv_ms"] \
        == pytest.approx(kernels) == pytest.approx(580e-6)
    whole = reader("attn_window_ms").read({"trace": trace}) \
        + reader("attn_full_ms").read({"trace": trace})
    assert kernels + got["attn_layout_ms"] == pytest.approx(whole)
    assert attention_scopes.scope_ms(
        trace, (attention_scopes.ATTN_WINDOW,), kernels=True) \
        == pytest.approx(140e-6)


def test_a_while_is_never_counted_and_its_inside_once():
    _, ops = decoder_scopes.step_ops(named_step())
    ran = pass_scopes.leaves(ops)
    assert not [n for n, _, _, _ in ran if n.startswith("%while")]
    assert len([n for n, _, _, _ in ran if n.startswith("%chunk")]) == 4
    assert sum(d for _, _, _, d in ran) == 2 * 1120
    assert sum(d for _, _, _, d in ops) == 2 * (1120 + 90)
    # nested twice: the innermost alone runs
    nest = [("outer", "", 0, 100), ("inner", "", 10, 50), ("leaf", "", 20, 5),
            ("leaf", "", 30, 5), ("alone", "", 100, 7), ("alloc", "", 100, 0)]
    assert sorted(pass_scopes.leaves(nest)) == [
        ("alone", "", 100, 7), ("leaf", "", 20, 5), ("leaf", "", 30, 5)]
    assert pass_scopes.leaves([]) == []


def test_the_unowned_families_are_named_largest_first():
    assert pass_scopes.unowned_families(named_step()) == [
        ["loop_fusion:train_step.grad/jvp__/rsqrt", pytest.approx(25e-6)]]
    assert pass_scopes.unowned_families(None) is None


@pytest.mark.parametrize("metric", list(NEW))
def test_a_new_reader_with_nothing_to_read_returns_nothing(metric):
    """No trace, an empty one, or a program whose model names nothing (the
    BERT step's recording): None, never 0 and never an exception."""
    for trace in (None, {"devices": {}, "host": []},
                  recording("scoped_step"), recording("tiny_attention_step")):
        assert reader(metric).read({"trace": trace}) is None


@pytest.mark.parametrize("name", OLD_RECORDINGS)
def test_a_program_without_the_kernel_names_reads_none(name):
    """The three older chip recordings: decoder steps whose flash kernels
    end `jit(_fwd)/pallas_call` and `jit(_bwd_call)/pallas_call`, as the
    parent's do (and as a program does that a compile cache served the
    parent's executable).  The split by pass reads nothing; the projections,
    whose scope is the parent's own, read a number."""
    trace = recording(name)
    assert not pass_scopes.names_kernels(trace)
    got = read_all(trace)
    for metric in ("flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms",
                   "flash_remat_ms", "attn_layout_ms", "mlp_dense_ms"):
        assert got[metric] is None, metric
    assert got["attn_project_ms"] > 0
    # the parent's names own most of a step already, the dense MLP apart
    assert 85 < got["step_owned_share"] < 100


FIXTURE = os.path.join(BENCH, "fixtures", "named_step.xplane.pb.gz")


def test_the_recorded_named_step():
    """Four executions of gated_step's small head-gated mixed decoder
    (hidden 256, a full layer of 12 gated heads over 2 of 64, two layers of
    18 with a window of 256, T 1024, a dense layer then 2 of 16 experts and
    a shared one, the head in chunks of 256) recorded on a TPU v5e from an
    empty compile cache (PR 36's first chip call), its kernels under their
    names."""
    trace = recording("named_step")
    steps, ops = decoder_scopes.step_ops(trace)
    assert len(steps) == 4
    kernels = [p for _, p, _, _ in ops if attention_scopes.KERNEL in p]
    # a step and a layer: forward, recomputed forward, dq, dk/dv, each named
    assert len(kernels) == 4 * 3 * 4
    for scope, n in zip(pass_scopes.FLASH, (2, 1, 1)):
        found = [p for p in kernels if decoder_scopes.under(p, (scope,))]
        assert len(found) == 4 * 3 * n
        assert all(p.rstrip(":").split("/")[-2:]
                   == [scope, attention_scopes.KERNEL] for p in found)
    assert pass_scopes.names_kernels(trace)
    got = read_all(trace)
    assert all(v is not None and v > 0 for v in got.values())
    under = attention_scopes.scope_ms(trace, pass_scopes.ATTEND,
                                      kernels=True)
    assert got["flash_fwd_ms"] + got["flash_dq_ms"] + got["flash_dkv_ms"] \
        == pytest.approx(under, abs=1e-6)
    run = {"trace": trace}
    whole = reader("attn_window_ms").read(run) \
        + reader("attn_full_ms").read(run)
    assert got["attn_layout_ms"] == pytest.approx(whole - under, abs=1e-6)
    # every layer under one checkpoint: each forward kernel runs twice
    assert got["flash_remat_ms"] < got["flash_fwd_ms"]
    assert got["flash_remat_ms"] == pytest.approx(got["flash_fwd_ms"] / 2,
                                                  rel=0.05)
    assert 0 < got["step_owned_share"] <= 100
    assert got["mlp_dense_ms"] + got["attn_project_ms"] \
        < reader("step_device_ms").read(run)
    # a `while` (the head's chunks, the expert layers' slabs) never, its
    # inside once: the leaves fit into the steps, all operations do not
    ran = pass_scopes.leaves(ops)
    whiles = [o for o in ops if o[0].startswith("%while")]
    assert whiles and not set(whiles) & set(ran)
    busy = sum(e - s for s, e in xplane.union(
        (s, s + d) for _, _, s, d in ops))
    assert sum(d for _, _, _, d in ran) <= busy \
        < sum(d for _, _, _, d in ops)
    with open(FIXTURE[:-len(".xplane.pb.gz")] + ".json") as f:
        recorded = json.load(f)     # the readers' values when it was recorded
    for m in list(NEW) + ["attn_window_ms", "attn_full_ms", "attn_gate_ms",
                          "lm_head_ms", "moe_route_ms", "moe_experts_ms",
                          "step_device_ms"]:
        assert reader(m).read(run) == pytest.approx(recorded[m]), m


def test_the_eight_entries_follow_the_ones_that_were_there():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cells = [w["name"] for w in b["workloads"]]
    names = [m["name"] for m in b["per_layer"]]
    at = names.index("attn_grid_run_share") + 1
    assert names[at:at + len(NEW)] == list(NEW)
    for m in b["per_layer"][at:at + len(NEW)]:
        unit, better, layer, listed = NEW[m["name"]]
        assert m == {"name": m["name"], "unit": unit, "better": better,
                     "source": "device_trace", "layer": layer,
                     "moves": "samples_per_s", "workloads": listed}
        # in the cells' own order, and never the two that reach no kernel
        assert listed == [c for c in cells if c in listed]
        assert not {"bert-base.mlm128", "resnet50.imagenet224"} & set(listed)
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
    # what read the kernels before still reads them, by the same literal
    assert attention_scopes.KERNEL == "pallas_call"
    for old in ("attn_window_ms", "attn_full_ms", "mla_attend_ms",
                "attn_window_roofline", "window_kernel_roofline"):
        assert old in names[:at]
