"""bench.py's record store and supervisor.

Every successful measurement is persisted (metric-keyed, atomically) the
moment it exists, so a later leg dying cannot take it along.  When every
bench attempt dies the supervisor exits non-zero and prints nothing: a
number measured by an earlier run is never emitted in a failed run's
place.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _load_bench_module():
    import importlib.util
    spec = importlib.util.spec_from_file_location("bench_mod", BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _bypass_platform_gate(monkeypatch):
    """The store-logic tests run on the CPU backend; without this bypass
    the platform gate (see test_cpu_platform_never_persists) would turn
    every persist into a no-op and the tests would assert on nothing."""
    monkeypatch.setenv("BENCH_PERSIST_ANY_PLATFORM", "1")


def test_cpu_platform_never_persists(tmp_path, monkeypatch):
    """A non-smoke run on a non-TPU backend must not write the store even
    with a production metric name: a JAX_PLATFORMS=cpu verification drive
    (BENCH_BATCH=4) clobbered the real-chip resnet record in r5.

    jax.devices is stubbed rather than called: the real probe would
    report tpu on the on-chip tier, inverting the assert."""
    import types
    import jax
    monkeypatch.setenv("BENCH_LASTGOOD_PATH", str(tmp_path / "lg.json"))
    monkeypatch.delenv("BENCH_PERSIST_ANY_PLATFORM", raising=False)
    monkeypatch.setattr(jax, "devices",
                        lambda *a, **k: [types.SimpleNamespace(
                            platform="cpu")])
    bench = _load_bench_module()
    bench.persist_lastgood({"metric": bench.PRIMARY_METRIC, "value": 0.39})
    assert bench.load_lastgood() == (None, None)


def test_persist_and_load_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("BENCH_LASTGOOD_PATH", str(tmp_path / "lg.json"))
    bench = _load_bench_module()
    rec = {"metric": "resnet50_train_images_per_sec_per_chip",
           "value": 2400.75, "unit": "img/s", "vs_baseline": 0.857}
    bench.persist_lastgood(rec)
    ts, loaded = bench.load_lastgood()
    assert loaded == rec
    assert ts  # a timestamp string was recorded


def test_smoke_records_never_persisted(tmp_path, monkeypatch):
    monkeypatch.setenv("BENCH_LASTGOOD_PATH", str(tmp_path / "lg.json"))
    bench = _load_bench_module()
    bench.persist_lastgood({"metric": "resnet18_smoke_images_per_sec",
                            "value": 99.0})
    ts, loaded = bench.load_lastgood()
    assert loaded is None and ts is None


def test_smoke_env_never_persists_even_unmarked_metric(tmp_path,
                                                       monkeypatch):
    """A BENCH_SMOKE=1 process must not persist ANY record, even one whose
    metric name carries no 'smoke' (the scaling metric bit us here: a CPU
    smoke weak_scaling_efficiency_dp8 record clobbered the real-chip
    resnet lastgood)."""
    monkeypatch.setenv("BENCH_LASTGOOD_PATH", str(tmp_path / "lg.json"))
    monkeypatch.setenv("BENCH_SMOKE", "1")
    bench = _load_bench_module()
    bench.persist_lastgood({"metric": "weak_scaling_efficiency_dp8",
                            "value": 0.11})
    ts, loaded = bench.load_lastgood()
    assert loaded is None and ts is None


def test_secondary_metric_never_clobbers_primary(tmp_path, monkeypatch):
    """The store is keyed by metric: a later BENCH_MODELS=bert or scaling
    run must not overwrite the resnet record, and the resnet record stays
    the preferred stale-emission choice."""
    monkeypatch.setenv("BENCH_LASTGOOD_PATH", str(tmp_path / "lg.json"))
    bench = _load_bench_module()
    resnet = {"metric": bench.PRIMARY_METRIC, "value": 2400.75}
    bench.persist_lastgood(resnet)
    bench.persist_lastgood({"metric": "bert_base_train_seqs_per_sec_per_chip",
                            "value": 150.0})
    bench.persist_lastgood({"metric": "weak_scaling_efficiency_dp8",
                            "value": 1.0})
    ts, loaded = bench.load_lastgood()
    # the primary stays the stale-emission choice, with the independently
    # stored bert + scaling records grafted in (a resnet-only run must not
    # cost the round its bert measurement — the r4 batch sweep did exactly
    # that), each carrying its OWN measured_at (they may come from
    # different runs than the primary)
    assert loaded["value"] == 2400.75
    assert loaded["bert"]["value"] == 150.0
    assert loaded["scaling"]["value"] == 1.0
    assert loaded["bert"]["measured_at"] and loaded["scaling"]["measured_at"]
    store = json.loads((tmp_path / "lg.json").read_text())
    assert len(store["records"]) == 3  # all three survive side by side


def test_scaling_graft_freshest_wins_and_dp1_placeholder_skipped(
        tmp_path, monkeypatch):
    """The scaling key family is dynamic (weak_scaling_efficiency_dp{n});
    the graft must pick the freshest by measured_at, not dict order, and
    the single-device dp1 placeholder must never mask a real record."""
    monkeypatch.setenv("BENCH_LASTGOOD_PATH", str(tmp_path / "lg.json"))
    bench = _load_bench_module()
    bench.persist_lastgood({"metric": bench.PRIMARY_METRIC, "value": 2400.0})
    # hand-write two scaling entries with explicit timestamps (older dp8
    # real record listed AFTER a newer-keyed entry to defeat dict order)
    store = json.loads((tmp_path / "lg.json").read_text())
    store["records"]["weak_scaling_efficiency_dp4"] = {
        "measured_at": "2026-07-31T00:00:00+0000",
        "record": {"metric": "weak_scaling_efficiency_dp4", "value": 0.93}}
    store["records"]["weak_scaling_efficiency_dp8"] = {
        "measured_at": "2026-07-30T00:00:00+0000",
        "record": {"metric": "weak_scaling_efficiency_dp8", "value": 0.91}}
    (tmp_path / "lg.json").write_text(json.dumps(store))
    _, loaded = bench.load_lastgood()
    assert loaded["scaling"]["value"] == 0.93  # freshest, not last-listed
    # the dp1 placeholder is refused at the persist layer itself (it can
    # reach persist_lastgood both via the sub-record loop and as the
    # top-level record of a scaling-only run)
    bench.persist_lastgood({"metric": "weak_scaling_efficiency_dp1",
                            "value": 1.0})
    store = json.loads((tmp_path / "lg.json").read_text())
    assert "weak_scaling_efficiency_dp1" not in store["records"]


def test_graft_skips_invalid_and_own_family_records(tmp_path, monkeypatch):
    """A null/zero per-key record must not be grafted (same validity bar
    as primary selection), and a scaling primary must not carry a staler
    sibling scaling record nested inside itself."""
    monkeypatch.setenv("BENCH_LASTGOOD_PATH", str(tmp_path / "lg.json"))
    bench = _load_bench_module()
    store = {"records": {
        "weak_scaling_efficiency_dp4": {
            "measured_at": "2026-07-31T00:00:00+0000",
            "record": {"metric": "weak_scaling_efficiency_dp4",
                       "value": 0.93}},
        "weak_scaling_efficiency_dp8": {
            "measured_at": "2026-07-30T00:00:00+0000",
            "record": {"metric": "weak_scaling_efficiency_dp8",
                       "value": 0.91}},
        "bert_base_train_seqs_per_sec_per_chip": {
            "measured_at": "2026-07-31T00:00:00+0000",
            "record": {"metric": "bert_base_train_seqs_per_sec_per_chip",
                       "value": None}},
    }}
    (tmp_path / "lg.json").write_text(json.dumps(store))
    _, loaded = bench.load_lastgood()
    # fallback primary = freshest entry (dp4); no sibling scaling nested,
    # and the null bert record is not grafted
    assert loaded["metric"] == "weak_scaling_efficiency_dp4"
    assert "scaling" not in loaded and "bert" not in loaded


def test_bert_only_store_never_self_nests(tmp_path, monkeypatch):
    """When the only stored record IS the bert record, the graft must not
    nest it inside itself."""
    monkeypatch.setenv("BENCH_LASTGOOD_PATH", str(tmp_path / "lg.json"))
    bench = _load_bench_module()
    bert = {"metric": "bert_base_train_seqs_per_sec_per_chip",
            "value": 150.0}
    bench.persist_lastgood(bert)
    ts, loaded = bench.load_lastgood()
    assert loaded == bert and "bert" not in loaded


def test_graft_prefers_per_key_record_over_nested_copy(tmp_path,
                                                       monkeypatch):
    """The per-metric key is written by the same run that measured it, so
    it is always at least as fresh as a copy nested inside the primary —
    a later bert-only run must win over the stale nested value."""
    monkeypatch.setenv("BENCH_LASTGOOD_PATH", str(tmp_path / "lg.json"))
    bench = _load_bench_module()
    resnet = {"metric": bench.PRIMARY_METRIC, "value": 2400.0,
              "bert": {"metric": "bert_base_train_seqs_per_sec_per_chip",
                       "value": 456.0}}
    bench.persist_lastgood(resnet)
    bench.persist_lastgood({"metric": "bert_base_train_seqs_per_sec_per_chip",
                            "value": 500.0})
    _, loaded = bench.load_lastgood()
    assert loaded["bert"]["value"] == 500.0


def test_corrupt_store_never_raises(tmp_path, monkeypatch):
    monkeypatch.setenv("BENCH_LASTGOOD_PATH", str(tmp_path / "lg.json"))
    bench = _load_bench_module()
    for content in ("null", "[1,2]", '{"records": {"m": "notadict"}}',
                    '{"records": {"m": {"record": {"value": "2400"}}}}'):
        (tmp_path / "lg.json").write_text(content)
        assert bench.load_lastgood() == (None, None)
    # and persisting over a corrupt store recovers it
    (tmp_path / "lg.json").write_text("null")
    rec = {"metric": bench.PRIMARY_METRIC, "value": 5.0}
    bench.persist_lastgood(rec)
    assert bench.load_lastgood()[1] == rec


def test_persist_failure_never_raises(tmp_path, monkeypatch):
    """A persist failure must not be able to kill a successful inner run
    (the measurement is still printed/emitted by the caller)."""
    monkeypatch.setenv("BENCH_LASTGOOD_PATH",
                       str(tmp_path / "no" / "such" / "dir" / "lg.json"))
    bench = _load_bench_module()
    bench.persist_lastgood({"metric": bench.PRIMARY_METRIC, "value": 5.0})


def test_zero_value_record_not_served(tmp_path, monkeypatch):
    monkeypatch.setenv("BENCH_LASTGOOD_PATH", str(tmp_path / "lg.json"))
    bench = _load_bench_module()
    bench.persist_lastgood({"metric": "resnet50_train_images_per_sec_per_chip",
                            "value": 0.0, "error": "boom"})
    ts, loaded = bench.load_lastgood()
    assert loaded is None


@pytest.mark.slow
@pytest.mark.parametrize("with_store", [True, False])
def test_all_attempts_failing_exits_nonzero_and_prints_nothing(tmp_path,
                                                                with_store):
    """End-to-end: outer supervisor + a child hung in the backend probe
    (BENCH_SIMULATE_WEDGE sleeps before 'backend up' is ever printed).
    The run exits non-zero and emits no record — in particular not the
    stored measurement of an earlier run."""
    lg = tmp_path / "lg.json"
    rec = {"metric": "resnet50_train_images_per_sec_per_chip",
           "value": 2400.75, "unit": "img/s", "vs_baseline": 0.857,
           "mfu": 0.2991}
    if with_store:
        lg.write_text(json.dumps({"records": {rec["metric"]: {
            "measured_at": "2026-07-30T04:38:00", "record": rec}}}))
    env = dict(os.environ)
    env.update(BENCH_LASTGOOD_PATH=str(lg), BENCH_SIMULATE_WEDGE="1",
               BENCH_PROBE_TIMEOUT="3", BENCH_TIMEOUT="30",
               BENCH_ATTEMPTS="1", BENCH_SMOKE="1")
    out = subprocess.run([sys.executable, BENCH], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "probe" in out.stderr
    if with_store:
        # the on-disk record itself is untouched by the failed run
        assert json.loads(lg.read_text())["records"][rec["metric"]][
            "record"] == rec


def test_failed_requested_leg_exits_nonzero_after_printing(monkeypatch,
                                                           capsys):
    """A leg that raises is printed with its error beside whatever else
    the run measured, and the run then exits non-zero."""
    monkeypatch.setenv("BENCH_SMOKE", "1")
    monkeypatch.setenv("BENCH_MODELS", "lstm,ssd")
    bench = _load_bench_module()

    def boom(smoke):
        raise RuntimeError("mosaic said no")

    monkeypatch.setattr(bench, "bench_lstm", boom)
    monkeypatch.setattr(bench, "bench_ssd", lambda smoke: {
        "metric": "ssd_smoke", "value": 1.0, "unit": "img/s"})
    with pytest.raises(SystemExit) as exc:
        bench.inner()
    assert exc.value.code not in (0, None) and "lstm" in str(exc.value.code)
    emitted = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "mosaic said no" in emitted["error"]
    assert emitted["ssd"]["value"] == 1.0


def test_non_tpu_platform_is_an_error_without_smoke():
    """Without BENCH_SMOKE=1 the measurement path fails on a machine with
    no chip instead of timing the CPU under a device metric's name."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_MODELS="lstm")
    env.pop("BENCH_SMOKE", None)
    out = subprocess.run([sys.executable, BENCH, "--inner"], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "platform is 'cpu'" in out.stderr


def test_run_ladder_oom_fallback():
    """The batch ladder falls back on OOM only, keeps the first success,
    and re-raises a last-rung OOM or any non-OOM error (the lstm/ssd
    benches joined the ladder in r4 s3 — 128 sits one doubling from the
    measured SSD OOM point, so the fallback is load-bearing)."""
    bench = _load_bench_module()

    calls = []

    def oom_then_ok(batch):
        calls.append(batch)
        if batch > 64:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        return {"batch": batch}

    assert bench._run_ladder("t", (128, 64, 32), oom_then_ok) == \
        {"batch": 64}
    assert calls == [128, 64]

    # non-OOM errors do not fall back
    def boom(batch):
        raise ValueError("shape mismatch")

    with pytest.raises(ValueError):
        bench._run_ladder("t", (128, 64), boom)

    # OOM on the last rung re-raises
    def always_oom(batch):
        raise RuntimeError("ran out of memory")

    with pytest.raises(RuntimeError):
        bench._run_ladder("t", (128,), always_oom)

    # a bare "hbm" mention is NOT an OOM (guard against silent fallback)
    def hbm_note(batch):
        raise RuntimeError("hbm bandwidth note, not an allocation error")

    with pytest.raises(RuntimeError):
        bench._run_ladder("t", (128, 64), hbm_note)


def _store_with(tmp_path, monkeypatch, rec, measured_at=None):
    """Persist rec via the real persist path, optionally rewriting the
    stored measured_at (to age the record for the freshness tests)."""
    path = tmp_path / "lg.json"
    monkeypatch.setenv("BENCH_LASTGOOD_PATH", str(path))
    bench = _load_bench_module()
    bench.persist_lastgood(rec)
    if measured_at is not None:
        store = json.loads(path.read_text())
        store["records"][rec["metric"]]["measured_at"] = measured_at
        path.write_text(json.dumps(store))
    return bench


def test_fresh_stored_carries_recent_record(tmp_path, monkeypatch):
    """BENCH_SKIP_FRESH: a record measured minutes ago is carried with
    carried_fresh=True and its own measured_at, so a retry after a
    failed attempt spends its time on the legs still missing."""
    rec = {"metric": "bert_base_train_seqs_per_sec_per_chip",
           "value": 790.89, "iters": 20}
    bench = _store_with(tmp_path, monkeypatch, rec)
    got = bench._fresh_stored(rec["metric"], 3600)
    assert got is not None
    assert got["value"] == 790.89
    assert got["carried_fresh"] is True
    assert got["measured_at"]


def test_fresh_stored_rejects_old_record(tmp_path, monkeypatch):
    rec = {"metric": "bert_base_train_seqs_per_sec_per_chip",
           "value": 726.09}
    bench = _store_with(tmp_path, monkeypatch, rec,
                        measured_at="2026-07-31T11:52:17+0000")
    assert bench._fresh_stored(rec["metric"], 14400) is None


def test_fresh_stored_min_iters_gates_quick_bench(tmp_path, monkeypatch):
    """The quick stage's 5-iter resnet number must never be carried as
    the official 30-iter record."""
    rec = {"metric": "resnet50_train_images_per_sec_per_chip",
           "value": 2303.33, "iters": 5}
    bench = _store_with(tmp_path, monkeypatch, rec)
    assert bench._fresh_stored(rec["metric"], 3600, min_iters=30) is None
    assert bench._fresh_stored(rec["metric"], 3600, min_iters=5) is not None


def test_fresh_stored_require_narrows_match(tmp_path, monkeypatch):
    """The r4-era compact-backbone ssd record shares the official metric
    key; require={'backbone': 'vgg16_reduced'} must reject it."""
    rec = {"metric": "ssd512_train_images_per_sec_per_chip",
           "value": 485.18, "backbone": "compact"}
    bench = _store_with(tmp_path, monkeypatch, rec)
    key = rec["metric"]
    assert bench._fresh_stored(
        key, 3600, require={"backbone": "vgg16_reduced"}) is None
    assert bench._fresh_stored(
        key, 3600, require={"backbone": "compact"}) is not None


def test_fresh_stored_rejects_error_zero_and_future(tmp_path, monkeypatch):
    key = "lstm_ptb_train_tokens_per_sec_per_chip"
    bench = _store_with(tmp_path, monkeypatch, {"metric": key, "value": 0.0})
    assert bench._fresh_stored(key, 3600) is None
    bench = _store_with(tmp_path, monkeypatch,
                        {"metric": key, "value": 100.0, "error": "wedge"})
    assert bench._fresh_stored(key, 3600) is None
    # a future-dated measured_at (clock skew) must not qualify as fresh
    import datetime
    future = (datetime.datetime.now(datetime.timezone.utc) +
              datetime.timedelta(hours=2)).strftime("%Y-%m-%dT%H:%M:%S%z")
    bench = _store_with(tmp_path, monkeypatch,
                        {"metric": key, "value": 100.0},
                        measured_at=future)
    assert bench._fresh_stored(key, 3600) is None


def test_fresh_stored_missing_store_and_key(tmp_path, monkeypatch):
    monkeypatch.setenv("BENCH_LASTGOOD_PATH", str(tmp_path / "absent.json"))
    bench = _load_bench_module()
    assert bench._fresh_stored("anything", 3600) is None
    bench = _store_with(tmp_path, monkeypatch,
                        {"metric": "some_other_metric", "value": 5.0})
    assert bench._fresh_stored("not_that_metric", 3600) is None


def test_fresh_stored_extra_leg_min_iters(tmp_path, monkeypatch):
    """lstm/ssd honor BENCH_ITERS too: a short manual sanity run must not
    be carried as the official leg (review finding, session 4)."""
    rec = {"metric": "lstm_ptb_train_tokens_per_sec_per_chip",
           "value": 700000.0, "iters": 3}
    bench = _store_with(tmp_path, monkeypatch, rec)
    assert bench._fresh_stored(rec["metric"], 3600, min_iters=20) is None
