"""Tests of what ISSUE 33 added to the yardstick (ISSUE 32 wrote them): the
two readers of the expert layers' census that say how much of the sorted
order ran (the first slab, which always runs, and those past it that did)
and how often any did.

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

import decoder_scopes  # noqa: E402

READERS = ["moe_tail_share", "moe_head_rows_share"]
CELLS = ["glm-4.7-flash.pretrain4k", "smallthinker-21ba3b.extend16k"]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def layer(head_rows, history, pairs=32768):
    """A census entry of a layer that holds 8 of 64 experts."""
    load = [pairs / 64.0] * 64
    return {"layer": "moe", "held": (0, 8), "expert_load": load,
            "rows_routed_here": sum(history[-1:]), "max_expert_load": 512.0,
            "head_rows": head_rows, "rows_routed_here_history": history}


def test_the_shares_on_a_census_worked_out_on_paper(monkeypatch):
    """Two layers of 32,768 pairs with a head of 8,192: one never passed
    it in four steps, the other in one of four (a step AT the head's rows
    runs nothing more): 1 of 8 pairs ran a slab past the head, so 9 slabs
    of a quarter of the pairs ran in 8 steps."""
    counted = [layer(8192, [4000.0, 4100.0, 8192.0, 4096.0]),
               layer(8192, [4000.0, 8193.0, 4100.0, 4096.0])]
    monkeypatch.setattr(decoder_scopes, "census", lambda run: counted)
    assert reader("moe_tail_share").read({}) == pytest.approx(12.5)
    assert reader("moe_head_rows_share").read({}) == pytest.approx(
        25.0 * 9 / 8)
    # three slabs of four; every pair here runs them all and no more; no
    # row here still runs the head; a layer of fewer pairs whose head is
    # half of them
    counted = [layer(8192, [16385.0, 32768.0, 0.0]),
               layer(8192, [2000.0], pairs=16384)]
    assert reader("moe_head_rows_share").read({}) == pytest.approx(
        100.0 * (0.75 + 1.0 + 0.25 + 0.5) / 4)
    assert reader("moe_tail_share").read({}) == pytest.approx(50.0)


@pytest.mark.parametrize("metric", READERS)
def test_a_census_without_head_rows_reads_as_nothing(metric, monkeypatch):
    """The parent's census (no `head_rows`), a program with no expert
    layer, a layer that has not run a step: None, never 0 and never an
    exception."""
    parents = layer(8192, [4000.0])
    del parents["head_rows"]
    unrun = dict(layer(0, []), expert_load=[0.0] * 64)
    for counted in ([parents], None, [], [unrun]):
        monkeypatch.setattr(decoder_scopes, "census", lambda run: counted)
        assert reader(metric).read({"cfg": {}}) is None
    monkeypatch.undo()
    assert reader(metric).read({"cfg": {}}) is None     # nothing live


@pytest.mark.parametrize("metric", READERS)
def test_the_entries_name_the_decoder_cells_and_the_routing_layer(metric):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    entry, = [m for m in b["per_layer"] if m["name"] == metric]
    assert entry == {"name": metric, "unit": "%", "better": "lower",
                     "source": "program_counter", "layer": "routing",
                     "moves": "samples_per_s", "workloads": CELLS}
    # appended: the accepted entries stand before them, in their order
    names = [m["name"] for m in b["per_layer"]]
    assert names[-2:] == READERS and names[-3] == "lm_head_ms"
