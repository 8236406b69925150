"""tpumx-lint (tools/tpumx_lint.py + tools/lint/): the static checker.

Per ISSUE 6 acceptance: every pass is demonstrated to BOTH fire on its
target pattern AND stay silent on the nearest legitimate look-alike
(atomic_write's own open, tpu_mx/random.py's own PRNGKey, a seeded
private RandomState, host np.prod in a hot path, ...), plus the
suppression- and baseline-mechanism tests and the repo-wide gate: the
tree this test suite ships with must lint clean.

ISSUE 10 added the interprocedural tier: caller-holds-lock proofs and
their FP guards, transitive unlocked-mutation witnesses, hot-path-purity
through one and two helper hops (incl. the PR-9 eager-asarray-in-decode
regression fixture), the wrapped-raw-open durability hop, re-exported
emitter aliases across modules, and index round-trip/staleness.
Multi-file fixtures go through ``lint_sources({relpath: src, ...})`` —
one project index spans the set, exactly like the real run.

No jax needed: the linter is pure stdlib and these tests drive it on
in-memory fixture snippets via ``lint_source(src, fake_relpath)``.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")
sys.path.insert(0, TOOLS)

import tpumx_lint  # noqa: E402

CATALOG = frozenset({"fusion.flushes", "train_step.steps"})
EVENT_CATALOG = frozenset({"chaos.inject", "supervisor.restart"})


def run(src, path, rules=None, known=CATALOG, known_events=EVENT_CATALOG):
    found, suppressed = tpumx_lint.lint_source(
        textwrap.dedent(src), path, known_metrics=known, rules=rules,
        known_events=known_events)
    return found, suppressed


def run_multi(files, rules=None, known=CATALOG,
              known_events=EVENT_CATALOG):
    """Multi-file fixture: ONE project index spans the whole dict, so
    cross-module call chains and re-exports resolve (ISSUE 10)."""
    found, suppressed = tpumx_lint.lint_sources(
        {p: textwrap.dedent(s) for p, s in files.items()},
        known_metrics=known, rules=rules, known_events=known_events)
    return found, suppressed


def rules_of(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# durability
# ---------------------------------------------------------------------------
def test_durability_fires_on_raw_state_writes():
    found, _ = run("""
        import pickle
        import numpy as np

        def save(path, obj, arr):
            with open(path, "wb") as f:      # raw binary write
                f.write(b"x")
            pickle.dump(obj, open(path, "wb"))
            np.save("model.params", arr)
        """, "tpu_mx/foo.py", rules={"durability"})
    assert len(found) == 4  # two opens, one pickle.dump, one np.save
    assert set(rules_of(found)) == {"durability"}


def test_durability_silent_on_atomic_write_internals_and_reads():
    # the nearest look-alikes: the durability layer's OWN tmp open, plain
    # reads, an append-mode telemetry stream, and the serialize-to-BytesIO
    # idiom that feeds atomic_write
    found, _ = run("""
        import io
        import numpy as np

        def atomic_write(path, mode="wb"):
            raw = open(path + ".tmp", mode)   # the layer itself
            return raw

        def load(path):
            with open(path, "rb") as f:
                return f.read()

        def append_log(path, line):
            with open(path, "a") as f:
                f.write(line)

        def save(fname, payload):
            bio = io.BytesIO()
            np.savez(bio, **payload)
        """, "tpu_mx/foo.py", rules={"durability"})
    assert found == []


def test_durability_tools_scope_only_flags_state_shaped_paths():
    src = """
        import json

        def report(results):
            with open("bench_report.json", "w") as f:   # report: fine
                json.dump(results, f)

        def emergency(prefix, blob):
            with open(prefix + "-0001.params", "w") as f:   # state!
                f.write(blob)
        """
    found, _ = run(src, "tools/report.py", rules={"durability"})
    assert len(found) == 1
    assert "params" in found[0].message
    # the same source in library scope flags BOTH writes
    found_lib, _ = run(src, "tpu_mx/report.py", rules={"durability"})
    assert len(found_lib) == 2


# ---------------------------------------------------------------------------
# determinism
# ---------------------------------------------------------------------------
def test_determinism_fires_on_stray_rng():
    found, _ = run("""
        import time
        import numpy as np
        import jax

        def augment(x):
            return x * np.random.uniform()          # global stream

        def fresh_stream():
            return jax.random.PRNGKey(0)            # escapes capsules

        def entropy_seeded():
            return np.random.RandomState()          # OS entropy

        def wall_clock():
            rng = np.random.RandomState(int(time.time()))
            return rng
        """, "tpu_mx/foo.py", rules={"determinism"})
    assert len(found) == 4
    assert set(rules_of(found)) == {"determinism"}


def test_determinism_flags_a_dropout_site_drawing_its_own_mask():
    found, _ = run("""
        import jax

        def Dropout(x, key, p):
            keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
            return x * keep

        def attend(p, dropout_key, dropout_rate):
            return jax.random.bernoulli(dropout_key, 1.0 - dropout_rate,
                                        p.shape)

        def random_bernoulli(key, prob, shape):      # the sampling API
            return jax.random.bernoulli(key, prob, shape)

        def variational_dropout(F, keep, like):      # through the API
            return F.random.bernoulli(prob=keep, shape=like.shape)
        """, "tpu_mx/foo.py", rules={"determinism"})
    assert len(found) == 2 and all("dropout_keep" in f.message for f in found)


def test_determinism_silent_on_blessed_patterns():
    # seeded private RandomState (iterator pattern), host_rng() routing,
    # and take_key() are all contract-compliant
    found, _ = run("""
        import numpy as np
        from .random import host_rng, take_key

        class It:
            def __init__(self, seed):
                self._rng = np.random.RandomState(seed)

        def augment(x):
            return x * host_rng().uniform()

        def draw():
            return take_key()
        """, "tpu_mx/foo.py", rules={"determinism"})
    assert found == []


def test_determinism_keyword_seed_is_seeded():
    # RandomState(seed=7) is the same blessed pattern as RandomState(7)
    found, _ = run("""
        import numpy as np
        a = np.random.RandomState(seed=7)
        b = np.random.default_rng(seed=0)
        c = np.random.RandomState(seed=None)    # explicit None: entropy
        """, "tpu_mx/foo.py", rules={"determinism"})
    assert len(found) == 1
    assert found[0].line_text.strip().startswith("c =")


def test_determinism_exempts_the_framework_rng_and_tools():
    src = """
        import jax
        import numpy as np
        key = jax.random.PRNGKey(0)
        np.random.seed(7)
        """
    # tpu_mx/random.py IS the framework stream: its PRNGKey is the point
    found, _ = run(src, "tpu_mx/random.py", rules={"determinism"})
    assert found == []
    # tools are entry points that seed themselves; library scope only
    found, _ = run(src, "tools/bench_helper.py", rules={"determinism"})
    assert found == []
    found, _ = run(src, "tpu_mx/foo.py", rules={"determinism"})
    assert len(found) == 2


def test_determinism_time_seeding_flagged_everywhere():
    found, _ = run("""
        import random
        import time
        import numpy as np
        r = random.Random(time.time_ns())
        g = np.random.default_rng(seed=time.time_ns())   # keyword spelling
        """, "tools/launch_helper.py", rules={"determinism"})
    assert len(found) == 2
    assert all("wall-clock" in f.message for f in found)


def test_determinism_flags_typed_key_constructor():
    # jax.random.key() is the current recommended constructor — the same
    # capsule-escaping fresh stream as the legacy PRNGKey
    found, _ = run("""
        import jax
        k = jax.random.key(0)
        """, "tpu_mx/foo.py", rules={"determinism"})
    assert len(found) == 1 and "take_key" in found[0].message
    # but an unrelated .key attribute call is not an RNG constructor
    found, _ = run("""
        def f(holder):
            return holder.key(0)
        """, "tpu_mx/foo.py", rules={"determinism"})
    assert found == []


# ---------------------------------------------------------------------------
# sync-point
# ---------------------------------------------------------------------------
def test_sync_point_fires_in_hot_paths():
    src = """
        def flush(seg, loss):
            host = seg.out.asnumpy()            # implicit sync
            scalar = loss.item()                # implicit sync
            mean = float(loss.mean())           # blocking reduction
            return host, scalar, mean
        """
    found, _ = run(src, "tpu_mx/fusion.py", rules={"sync-point"})
    assert len(found) == 3
    assert set(rules_of(found)) == {"sync-point"}
    # optimizer scope: only update*/create_state*/step bodies are hot
    found, _ = run("""
        def update_core(w, g):
            return float(g.mean())
        def helper(g):
            return float(g.mean())
        """, "tpu_mx/optimizer/optimizer.py", rules={"sync-point"})
    assert len(found) == 1
    assert found[0].context == "update_core"


def test_sync_point_silent_on_look_alikes():
    found, _ = run("""
        import numpy as np

        def step(self, cfg, shape, x):
            lr = float(cfg.lr)                  # plain attribute: host
            thr = float(cfg.get("thr", 0.5))    # dict method: host
            n = int(np.prod(shape))             # host math on a shape
            x.wait_to_read()                    # EXPLICIT sync: allowed
            x.block_until_ready()               # EXPLICIT sync: allowed
            return lr, thr, n
        """, "tpu_mx/parallel/train_step.py", rules={"sync-point"})
    assert found == []
    # identical code OUTSIDE a hot path is never flagged
    found, _ = run("""
        def report(loss):
            return float(loss.mean()), loss.asnumpy()
        """, "tpu_mx/metric.py", rules={"sync-point"})
    assert found == []


# ---------------------------------------------------------------------------
# concurrency
# ---------------------------------------------------------------------------
def test_concurrency_fires_on_thread_and_lock_misuse():
    found, _ = run("""
        import threading

        class Loop:
            def __init__(self):
                self._lock = threading.Lock()
                self.gen = 0

            def start(self):
                t = threading.Thread(target=self.run)   # no daemon=
                t.start()

            def bump(self):
                with self._lock:
                    self.gen += 1

            def reset(self):
                self.gen = 0        # lock-free mutation of a guarded attr
        """, "tpu_mx/foo.py", rules={"concurrency"})
    assert len(found) == 2
    msgs = " ".join(f.message for f in found)
    assert "daemon" in msgs and "lock" in msgs


def test_concurrency_silent_on_disciplined_code():
    found, _ = run("""
        import threading

        class Loop:
            def __init__(self):
                self._lock = threading.Lock()
                self.gen = 0          # pre-publication: no thread yet

            def start(self):
                self.w = threading.Thread(target=self.run, daemon=True)
                self.w.start()
                j = threading.Thread(target=self.run, daemon=False)
                j.start()
                j.join()

            def bump(self):
                with self._lock:
                    self.gen += 1

            def free(self):
                self.other = 1        # never lock-guarded anywhere: fine
        """, "tpu_mx/foo.py", rules={"concurrency"})
    assert found == []


def test_concurrency_join_rule_ignores_path_and_string_joins():
    # os.path.join / ", ".join must not vacuously satisfy the
    # non-daemon-needs-a-join rule; a real t.join() must
    src = textwrap.dedent("""
        import os
        import threading

        def go(f):
            p = os.path.join("a", "b")
            s = ", ".join(["x"])
            t = threading.Thread(target=f, daemon=False)
            t.start()
            {join}return p, s
        """)
    found, _ = run(src.format(join=""), "tpu_mx/foo.py",
                   rules={"concurrency"})
    assert len(found) == 1 and "join" in found[0].message
    found, _ = run(src.format(join="t.join()\n    "), "tpu_mx/foo.py",
                   rules={"concurrency"})
    assert found == []


def test_concurrency_module_level_lock_dict_pair_fires():
    # the checkpoint._intended shape (ROADMAP limitation closed in
    # ISSUE 8): module-level lock/state pairs, not just class-scoped
    found, _ = run("""
        import threading

        _lock = threading.Lock()
        _intended = {}
        _count = 0

        def put(key, info):
            with _lock:
                _intended[key] = info

        def evict(key):
            _intended[key] = None       # lock-free subscript mutation

        def bump():
            global _count
            with _lock:
                _count += 1

        def reset():
            global _count
            _count = 0                  # lock-free global rebind
        """, "tpu_mx/foo.py", rules={"concurrency"})
    assert len(found) == 2
    msgs = " ".join(f.message for f in found)
    assert "_intended" in msgs and "_count" in msgs
    assert "module global" in msgs


def test_concurrency_module_level_silent_on_look_alikes():
    found, _ = run("""
        import threading

        _lock = threading.Lock()
        _intended = {}
        _env_parsed = False

        _intended["init"] = 1           # import time: pre-publication

        class Boot:
            _intended_copy = dict(_intended)   # class body: import time

        def put(key, info):
            with _lock:
                _intended[key] = info

        def parse():
            # never lock-guarded anywhere: single-discipline, fine
            global _env_parsed
            _env_parsed = True

        def local_shadow(_intended):
            _intended["x"] = 1          # parameter shadows the global

        def local_rebind():
            _intended = {}              # no global decl: a local
            _intended["x"] = 1
        """, "tpu_mx/foo.py", rules={"concurrency"})
    assert found == []


def test_concurrency_module_level_closure_under_lock_still_unguarded():
    # defining a function under a lock does not RUN it under the lock
    found, _ = run("""
        import threading

        _lock = threading.Lock()
        _state = {}

        def guarded(k, v):
            with _lock:
                _state[k] = v

        def maker():
            with _lock:
                def inner(k):
                    _state[k] = 0       # runs later, lock-free
                return inner
        """, "tpu_mx/foo.py", rules={"concurrency"})
    assert len(found) == 1 and "_state" in found[0].message


def test_concurrency_thread_alias_and_annotated_assign():
    # `from threading import Thread as T` must still be detected, and an
    # ANNOTATED lock-free assignment of a guarded attr must still flag
    found, _ = run("""
        from threading import Thread as T

        class C:
            def start(self, f):
                T(target=f).start()          # aliased, no daemon=

            def bump(self):
                with self._lock:
                    self.gen = 1

            def reset(self):
                self.gen: int = 0            # annotated, lock-free
        """, "tpu_mx/foo.py", rules={"concurrency"})
    assert len(found) == 2
    # a local class merely named Thread is NOT threading's
    found, _ = run("""
        from mypool import Thread

        def go(f):
            Thread(target=f).start()
        """, "tpu_mx/foo.py", rules={"concurrency"})
    assert found == []


def test_concurrency_closure_inside_init_keeps_exemption():
    # an init-time helper closure runs during construction, before the
    # object is published — its assignments are pre-publication too
    found, _ = run("""
        class C:
            def __init__(self):
                def setup():
                    self.x = 1
                setup()

            def bump(self):
                with self._lock:
                    self.x = 2
        """, "tpu_mx/foo.py", rules={"concurrency"})
    assert found == []


def test_concurrency_closure_under_lock_is_not_guarded():
    # defining a function under a lock does not make its body run under
    # the lock — assignments inside it must count as UNguarded
    found, _ = run("""
        class C:
            def a(self):
                with self._lock:
                    def cb():
                        self.x = 1          # runs later, lock-free
                    self.x = 2              # guarded
                    return cb

            def b(self):
                self.x = 3                  # unguarded -> finding
        """, "tpu_mx/foo.py", rules={"concurrency"})
    # both cb's assignment and b's assignment conflict with the guard
    assert len(found) == 2


# ---------------------------------------------------------------------------
# telemetry-catalog
# ---------------------------------------------------------------------------
def test_telemetry_catalog_fires_on_unknown_and_dynamic_names():
    found, _ = run("""
        from tpu_mx import telemetry

        def instrument(name):
            telemetry.counter("fusion.flushez").inc()    # typo
            telemetry.gauge(name).set(1)                 # unverifiable
        """, "tpu_mx/foo.py", rules={"telemetry-catalog"})
    assert len(found) == 2
    assert "fusion.flushez" in found[0].message


def test_telemetry_catalog_silent_on_known_names_and_other_objects():
    found, _ = run("""
        from tpu_mx import telemetry as _telemetry

        def instrument(db):
            _telemetry.counter("fusion.flushes").inc()
            with _telemetry.span("train_step.steps"):
                pass
            db.counter("not.a.metric")     # unrelated object's .counter
        """, "tpu_mx/foo.py", rules={"telemetry-catalog"})
    assert found == []
    # the telemetry module itself manipulates names generically: exempt
    found, _ = run("""
        from tpu_mx import telemetry
        telemetry.counter("internal.name")
        """, "tpu_mx/telemetry.py", rules={"telemetry-catalog"})
    assert found == []


def test_catalog_extraction_matches_the_live_module():
    known = tpumx_lint.load_known_metrics()
    assert known is not None
    # spot-check names every PR so far instrumented
    for name in ("fusion.flushes", "checkpoint.atomic_writes",
                 "supervisor.restarts", "resume.capsules_written"):
        assert name in known


def test_tracing_catalog_fires_on_unknown_and_dynamic_event_names():
    found, _ = run("""
        from tpu_mx import tracing as _tracing

        def instrument(name):
            _tracing.emit("supervisor.restartz", n=1)   # typo
            _tracing.emit(name, kind="hang")            # unverifiable
        """, "tpu_mx/foo.py", rules={"telemetry-catalog"})
    assert len(found) == 2
    assert "supervisor.restartz" in found[0].message
    assert "KNOWN_EVENTS" in found[0].message


def test_tracing_catalog_silent_on_known_names_and_lookalikes():
    found, _ = run("""
        from tpu_mx import tracing
        from tpu_mx.tracing import emit

        def instrument(logger):
            tracing.emit("chaos.inject", kind="hang")
            emit("supervisor.restart", n=2)     # from-imported emitter
            logger.emit("not.an.event")         # unrelated object's .emit
        """, "tpu_mx/foo.py", rules={"telemetry-catalog"})
    assert found == []
    # the tracing module itself manipulates names generically: exempt
    found, _ = run("""
        from tpu_mx import tracing
        tracing.emit("internal.name")
        """, "tpu_mx/tracing.py", rules={"telemetry-catalog"})
    assert found == []


def test_event_catalog_extraction_matches_the_live_module():
    known = tpumx_lint.load_known_events()
    assert known is not None
    import tpu_mx.tracing as live
    assert known == frozenset(live.KNOWN_EVENTS)
    for name in ("chaos.inject", "supervisor.watchdog_fire",
                 "train_step.phase", "resume.capsule_restore"):
        assert name in known


# ---------------------------------------------------------------------------
# suppression mechanism
# ---------------------------------------------------------------------------
def test_suppression_inline_and_comment_block():
    src = """
        def f(path, b):
            g = open(path, "wb")  # tpumx-lint: disable=durability -- why
            # tpumx-lint: disable=durability -- long justification that
            # wraps over several comment lines before the statement
            h = open(path, "wb")
            return g, h
        """
    found, suppressed = run(src, "tpu_mx/foo.py", rules={"durability"})
    assert found == []
    assert len(suppressed) == 2


def test_suppression_is_rule_specific():
    src = """
        import numpy as np
        def f(path):
            # tpumx-lint: disable=determinism -- wrong rule on purpose
            g = open(path, "wb")
            return g
        """
    found, suppressed = run(src, "tpu_mx/foo.py", rules={"durability"})
    assert len(found) == 1 and suppressed == []
    # disable=all suppresses any rule
    src2 = src.replace("disable=determinism", "disable=all")
    found, suppressed = run(src2, "tpu_mx/foo.py", rules={"durability"})
    assert found == [] and len(suppressed) == 1


# ---------------------------------------------------------------------------
# baseline mechanism
# ---------------------------------------------------------------------------
def test_baseline_round_trip_and_line_drift(tmp_path):
    src = 'def f(p):\n    return open(p, "wb")\n'
    found, _ = tpumx_lint.lint_source(src, "tpu_mx/foo.py",
                                      rules={"durability"})
    assert len(found) == 1
    bl = tmp_path / "baseline.json"
    tpumx_lint.write_baseline(str(bl), found)
    fps = tpumx_lint.read_baseline(str(bl))
    assert found[0].fingerprint() in fps
    # unrelated lines added ABOVE must not resurrect the finding: the
    # fingerprint hashes scope + line text, not the line number
    drifted = "import os\n\n\n" + src
    found2, _ = tpumx_lint.lint_source(drifted, "tpu_mx/foo.py",
                                       rules={"durability"})
    assert len(found2) == 1
    assert found2[0].fingerprint() in fps
    assert found2[0].line != found[0].line


def test_baseline_unknown_format_rejected(tmp_path):
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"format": "something-else", "findings": []}))
    with pytest.raises(SystemExit):
        tpumx_lint.read_baseline(str(bl))


# ---------------------------------------------------------------------------
# CLI + repo-wide gate
# ---------------------------------------------------------------------------
def test_cli_json_format_and_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text('import pickle\n'
                   'def f(o, p):\n'
                   '    pickle.dump(o, open(p, "wb"))\n')
    # path under tmp is not library/tools scope for open(); force it via
    # a state-shaped literal to prove scoping, then check the JSON shape
    bad2 = tmp_path / "bad2.py"
    bad2.write_text('def f(b):\n'
                    '    with open("x-0001.params", "wb") as f:\n'
                    '        f.write(b)\n')
    out = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "tpumx_lint.py"),
         str(bad2), "--format", "json", "--baseline",
         str(tmp_path / "none.json")],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 1, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["findings"] and \
        payload["findings"][0]["rule"] == "durability"
    assert {"rule", "path", "line", "col", "message", "context",
            "fingerprint"} <= set(payload["findings"][0])


def test_cli_fails_closed_on_missing_target_and_lost_catalog(
        tmp_path, monkeypatch, capsys):
    # a typo'd path must not read as a clean lint
    out = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "tpumx_lint.py"),
         "no_such_file.py", "--baseline", str(tmp_path / "none.json")],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 2, out.stdout + out.stderr
    assert "not found" in out.stdout + out.stderr
    # and a catalog the extractor cannot parse must not silently disable
    # the telemetry-catalog pass: main() fails closed with a pointed
    # message (e.g. after KNOWN_METRICS becomes a computed expression)
    assert tpumx_lint.load_known_metrics(repo=str(tmp_path)) is None
    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")
    # main() resolves the loaders from the cli module's namespace (the
    # tpumx_lint entry point re-exports it as tpumx_lint.cli)
    monkeypatch.setattr(tpumx_lint.cli, "load_known_metrics",
                        lambda **kw: None)
    rc = tpumx_lint.main([str(ok), "--baseline",
                          str(tmp_path / "none.json")])
    assert rc == 2
    assert "KNOWN_METRICS" in capsys.readouterr().err
    # the event catalog fails closed the same way (ISSUE 7: the
    # telemetry-catalog pass covers tracing.KNOWN_EVENTS too)
    monkeypatch.undo()
    assert tpumx_lint.load_known_events(repo=str(tmp_path)) is None
    monkeypatch.setattr(tpumx_lint.cli, "load_known_events",
                        lambda **kw: None)
    rc = tpumx_lint.main([str(ok), "--baseline",
                          str(tmp_path / "none.json")])
    assert rc == 2
    assert "KNOWN_EVENTS" in capsys.readouterr().err
    # but a rules subset that excludes the catalog pass still runs
    rc = tpumx_lint.main([str(ok), "--rules", "durability",
                          "--baseline", str(tmp_path / "none.json")])
    assert rc == 0


def test_repo_lints_clean():
    """The shipped tree must have zero unsuppressed findings — this is
    the same gate tools/ci.py's lint tier enforces."""
    known = tpumx_lint.load_known_metrics()
    known_events = tpumx_lint.load_known_events()
    findings, suppressed, errors = tpumx_lint.lint_paths(
        tpumx_lint.DEFAULT_TARGETS, known_metrics=known,
        known_events=known_events)
    assert errors == []
    baseline = tpumx_lint.read_baseline(
        os.path.join(TOOLS, "tpumx_lint_baseline.json"))
    fresh = [f for f in findings if f.fingerprint() not in baseline]
    assert fresh == [], "\n".join(f.render() for f in fresh)
    # every suppression in the tree must carry a justification ("--"):
    # a bare disable hides a contract violation with no explanation
    assert len(suppressed) >= 1
    repo = os.path.dirname(TOOLS)
    for f in suppressed:
        with open(os.path.join(repo, f.path), encoding="utf-8") as fh:
            lines = fh.read().splitlines()
        block = [lines[f.line - 1]]
        ln = f.line - 2
        while ln >= 0 and lines[ln].lstrip().startswith("#"):
            block.append(lines[ln])
            ln -= 1
        directives = [t for t in block if "tpumx-lint: disable" in t]
        assert directives, f.render()
        assert any("--" in t for t in directives), (
            f"unjustified suppression at {f.path}:{f.line} — append "
            f"'-- <why the contract does not apply>'")


# ---------------------------------------------------------------------------
# interprocedural concurrency: caller-holds-lock proofs (ISSUE 10)
# ---------------------------------------------------------------------------
def test_caller_holds_lock_helper_proven_safe():
    # the train_step._reset_accumulation shape: every call site holds the
    # lock, so the helper's lock-free mutation is PROVEN safe — the
    # suppression that used to be required is now a lint no-op
    found, _ = run("""
        import threading

        class Step:
            def __init__(self):
                self._state_lock = threading.Lock()
                self.micro = 0

            def restore(self):
                with self._state_lock:
                    self.micro = 1
                    self._reset()

            def rollback(self):
                with self._state_lock:
                    self._reset()

            def _reset(self):
                self.micro = 0      # caller provably holds the lock
        """, "tpu_mx/foo.py", rules={"concurrency"})
    assert found == []


def test_caller_holds_lock_fp_guard_one_unlocked_caller():
    # ONE lock-free caller breaks the proof: the finding returns and
    # names the lock-free witness chain
    found, _ = run("""
        import threading

        class Step:
            def __init__(self):
                self._state_lock = threading.Lock()
                self.micro = 0

            def restore(self):
                with self._state_lock:
                    self.micro = 1
                    self._reset()

            def public(self):
                self._reset()       # no lock: the proof fails

            def _reset(self):
                self.micro = 0
        """, "tpu_mx/foo.py", rules={"concurrency"})
    assert len(found) == 1
    assert "reached lock-free from" in found[0].message
    assert "Step.public" in found[0].message


def test_transitive_unlocked_mutation_two_hops():
    # entry -> _mid -> _reset: the mutation two hops below an UNLOCKED
    # public entry point is a finding carrying the whole witness chain
    src = """
        import threading

        class Step:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0

            def locked_set(self):
                with self._lock:
                    self.n = 1

            def entry(self):
                {lock_prefix}self._mid()

            def _mid(self):
                self._reset()

            def _reset(self):
                self.n = 0
        """
    found, _ = run(src.format(lock_prefix=""), "tpu_mx/foo.py",
                   rules={"concurrency"})
    assert len(found) == 1
    assert "Step.entry -> Step._mid -> Step._reset" in found[0].message
    # FP guard: the SAME chain with the entry taking the lock is proven
    # safe end-to-end (lock context propagates through both hops)
    locked = src.format(
        lock_prefix="with self._lock:\n                    ")
    found, _ = run(locked, "tpu_mx/foo.py", rules={"concurrency"})
    assert found == []


def test_module_global_caller_holds_lock_proven():
    # the module-scoped analog: a helper mutating a module global is
    # proven safe when its only callers hold the module lock
    src = """
        import threading

        _lock = threading.Lock()
        _state = {{}}

        def put(k, v):
            with _lock:
                _state[k] = v
                _evict(k)

        def _evict(k):
            _state[k] = None

        {extra}
        """
    found, _ = run(src.format(extra=""), "tpu_mx/foo.py",
                   rules={"concurrency"})
    assert found == []
    # FP guard: one lock-free caller and the finding is back
    found, _ = run(src.format(
        extra="def flush_all(k):\n            _evict(k)"),
        "tpu_mx/foo.py", rules={"concurrency"})
    assert len(found) == 1 and "_state" in found[0].message
    assert "flush_all" in found[0].message


def test_cycle_optimism_never_memoized():
    # mutual recursion _x <-> _n with ONE lock-free entry: BOTH bodies'
    # mutations must be flagged whatever the evaluation order — the
    # optimistic in-cycle assumption is correct for the outermost query
    # but must never be CACHED (a memoized provisional 'locked' verdict
    # for _n would silently discharge a real race)
    src = """
        import threading

        _lock = threading.Lock()
        _state = {{}}

        def put(k):
            with _lock:
                _state[k] = 1
                _x(k)

        def _x(k):
            _state[k] = 2
            _n(k)

        def _n(k):
            _state[k] = 3
            _x(k)

        def entry(k):
            {prefix}_x(k)
        """
    found, _ = run(src.format(prefix=""), "tpu_mx/foo.py",
                   rules={"concurrency"})
    assert len(found) == 2
    assert all("_state" in f.message for f in found)
    # FP guard: the SAME cycle with every external entry locked is the
    # documented greatest-fixpoint case — proven safe end to end
    locked = src.format(prefix="with _lock:\n                ")
    found, _ = run(locked, "tpu_mx/foo.py", rules={"concurrency"})
    assert found == []


def test_train_step_lock_proof_holds_on_the_real_tree():
    """The ISSUE 10 acceptance bar: the caller-holds-lock suppressions in
    tpu_mx/parallel/train_step.py are GONE (the pass proves the shape),
    and the proof actually discharges on the shipped file."""
    repo = os.path.dirname(TOOLS)
    rel = "tpu_mx/parallel/train_step.py"
    with open(os.path.join(repo, rel), encoding="utf-8") as f:
        src = f.read()
    assert "disable=concurrency -- caller" not in src, (
        "caller-holds-lock suppressions must stay deleted: the "
        "interprocedural pass proves them now")
    found, _ = tpumx_lint.lint_source(src, rel, rules={"concurrency"})
    assert found == [], "\n".join(f.render() for f in found)
    idx = tpumx_lint.build_index({rel: tpumx_lint.FileCtx(rel, src)})
    assert idx.always_locked(rel, "CompiledTrainStep._reset_accumulation")


# ---------------------------------------------------------------------------
# hot-path-purity (ISSUE 10)
# ---------------------------------------------------------------------------
def test_hot_path_purity_jnp_asarray_one_helper_hop():
    found, _ = run("""
        import jax.numpy as jnp

        def decode_attention(q, cache, seq_ids, layer):
            return _prep(q)

        def _prep(q):
            return jnp.asarray(q)       # eager commit, one hop from root

        def offline_tool(q):
            return jnp.asarray(q)       # unreachable from any root: fine
        """, "tpu_mx/serving/attention.py", rules={"hot-path-purity"})
    assert len(found) == 1
    assert "decode_attention -> _prep" in found[0].message
    assert found[0].context == "_prep"


def test_hot_path_purity_silent_inside_jit_boundary():
    # jnp.asarray INSIDE a jitted function is a trace-time no-op — the
    # jit boundary is the blessed commit point (nearest look-alike)
    found, _ = run("""
        import jax
        import jax.numpy as jnp

        def decode_attention(q, cache, seq_ids, layer):
            return _commit(q)

        @jax.jit
        def _commit(q):
            return jnp.asarray(q)
        """, "tpu_mx/serving/attention.py", rules={"hot-path-purity"})
    assert found == []
    # and a conversion behind an isinstance fast-path guard (the
    # NDArray.__init__ / _as_i32 shape) stays silent too
    found, _ = run("""
        import numpy as np
        import jax.numpy as jnp

        def decode_attention(q, cache, seq_ids, layer):
            return _as_dev(q)

        def _as_dev(x):
            if not isinstance(x, np.ndarray):
                x = jnp.asarray(x)      # only foreign inputs pay
            return x
        """, "tpu_mx/serving/attention.py", rules={"hot-path-purity"})
    assert found == []


def test_hot_path_purity_two_helper_hops_cross_module():
    found, _ = run_multi({
        "tpu_mx/serving/attention.py": """
            from .kv_cache import prep

            def decode_attention(q, cache, seq_ids, layer):
                return prep(q)
            """,
        "tpu_mx/serving/kv_cache.py": """
            import jax.numpy as jnp

            def prep(q):
                return _stage(q)

            def _stage(q):
                return jnp.asarray(q)   # two hops, different module
            """,
    }, rules={"hot-path-purity"})
    assert len(found) == 1
    assert found[0].path == "tpu_mx/serving/kv_cache.py"
    assert "decode_attention -> prep -> _stage" in found[0].message


def test_hot_path_purity_pr9_decode_regression():
    """The exact PR-9 cliff, as a regression fixture: a cache-write
    helper on the decode path eagerly converting its operand before the
    jitted update (~73 µs of dispatch per operand per token) — a lint
    error now.  The fixed idiom (raw operand through the jit boundary)
    is the FP guard."""
    src = """
        import jax
        import jax.numpy as jnp

        _OPS = None

        def _ops():
            global _OPS
            if _OPS is None:
                _OPS = jax.jit(lambda pool, val: pool + val)
            return _OPS

        def decode_attention(q, cache, seq_ids, layer):
            return _write(cache, q)

        def _write(pool, val):
            op = _ops()
            return op(pool, {operand})
        """
    found, _ = run(src.format(operand="jnp.asarray(val)"),
                   "tpu_mx/serving/attention.py",
                   rules={"hot-path-purity"})
    assert len(found) == 1 and "PR-9" in found[0].message
    assert "_write" in found[0].message
    # the fix: the raw operand crosses the jit boundary (C++ fast path);
    # the memo-guarded jit construction in _ops is fine either way
    found, _ = run(src.format(operand="val"),
                   "tpu_mx/serving/attention.py",
                   rules={"hot-path-purity"})
    assert found == []


def test_hot_path_purity_np_asarray_device_readback():
    found, _ = run_multi({
        "tpu_mx/kernels/mykern.py": """
            def kern(q):
                return q
            """,
        "tpu_mx/serving/attention.py": """
            import numpy as np
            from ..kernels.mykern import kern

            def decode_attention(q, cache, seq_ids, layer):
                out = np.asarray(kern(q))    # device value -> host
                shape = np.asarray([1, 2])   # host math: silent
                return out, shape
            """,
    }, rules={"hot-path-purity"})
    assert len(found) == 1
    assert "reads a device value back to host" in found[0].message
    # same shape via a kernel-bound local (the _paged_decode fn= pattern)
    found, _ = run_multi({
        "tpu_mx/kernels/mykern.py": """
            def kern_a(q):
                return q

            def kern_b(q):
                return q
            """,
        "tpu_mx/serving/attention.py": """
            import numpy as np
            from ..kernels import mykern as _pk

            def decode_attention(q, cache, seq_ids, layer):
                fn = _pk.kern_a if layer else _pk.kern_b
                return np.asarray(fn(q))
            """,
        "tpu_mx/kernels/__init__.py": "",
    }, rules={"hot-path-purity"})
    assert len(found) == 1


def test_hot_path_purity_guarded_readback_fallback_exempt():
    """The guarded-fallback idiom (ISSUE 16): an np.asarray readback
    tested behind isinstance is the sanctioned device/host-polymorphic
    normalization — the kernel returns a device array only on the arm
    that ran it, and the fallback re-binds the SAME value.  The
    unguarded sibling readback must still fire."""
    found, _ = run_multi({
        "tpu_mx/kernels/mykern.py": """
            def kern(q):
                return q
            """,
        "tpu_mx/serving/attention.py": """
            import numpy as np
            from ..kernels.mykern import kern

            def decode_attention(q, cache, seq_ids, layer):
                out = kern(q)
                if not isinstance(out, np.ndarray):
                    out = np.asarray(out)        # guarded: exempt
                bad = np.asarray(kern(q))        # unguarded: finding
                return out, bad
            """,
    }, rules={"hot-path-purity"})
    assert len(found) == 1
    assert found[0].line and "reads a device value back" in found[0].message


def test_hot_path_purity_item_and_uncached_jit():
    found, _ = run("""
        import jax

        def decode_attention(q, cache, seq_ids, layer):
            s = _scalar(q)
            return _apply(q), s

        def _scalar(q):
            return q.item()                    # readback in a helper

        def _apply(q):
            return jax.jit(lambda x: x + 1)(q)  # fresh wrapper per call
        """, "tpu_mx/serving/attention.py", rules={"hot-path-purity"})
    assert len(found) == 2
    msgs = " ".join(f.message for f in found)
    assert ".item()" in msgs and "retraces" in msgs
    # memo-guarded construction (the _dev_ops shape) is the look-alike
    found, _ = run("""
        import jax

        _F = None

        def decode_attention(q, cache, seq_ids, layer):
            return _apply(q)

        def _apply(q):
            global _F
            if _F is None:
                _F = jax.jit(lambda x: x + 1)
            return _F(q)
        """, "tpu_mx/serving/attention.py", rules={"hot-path-purity"})
    assert found == []


# ---------------------------------------------------------------------------
# one-hop helper indirection: durability + sync-point (ISSUE 10)
# ---------------------------------------------------------------------------
def test_durability_wrapped_raw_open_one_hop():
    src = """
        def save(prefix, blob):
            dump(prefix + "-0001.params", blob)     # state via a wrapper

        def report(results):
            dump("bench_notes.txt", results)        # not state: fine

        def dump(path, blob):
            with open(path, "w") as f:
                f.write(blob)
        """
    found, _ = run(src, "tools/report.py", rules={"durability"})
    assert len(found) == 1
    assert found[0].context == "save"
    assert "wrapper" in found[0].message
    # a helper named like the durability layer IS the commit layer
    found, _ = run(src.replace("dump", "write_atomic"),
                   "tools/report.py", rules={"durability"})
    assert found == []


def test_durability_library_wrapper_not_double_flagged():
    # in library scope the helper's own open is the (one) finding; the
    # call site must not duplicate it
    found, _ = run("""
        def save(prefix, blob):
            dump(prefix + "-0001.params", blob)

        def dump(path, blob):
            with open(path, "w") as f:
                f.write(blob)
        """, "tpu_mx/foo.py", rules={"durability"})
    assert len(found) == 1
    assert found[0].context == "dump"


def test_sync_point_one_helper_hop():
    files = {
        "tpu_mx/parallel/train_step.py": """
            from ..metric import read_scalar

            def step(x):
                return read_scalar(x)
            """,
        "tpu_mx/metric.py": """
            def read_scalar(x):
                return x.item()
            """,
    }
    found, _ = run_multi(files, rules={"sync-point"})
    assert len(found) == 1
    assert found[0].path == "tpu_mx/parallel/train_step.py"
    assert "tpu_mx/metric.py" in found[0].message
    assert ".item()" in found[0].message
    # a justified suppression AT THE HELPER covers its callers too
    files["tpu_mx/metric.py"] = """
        def read_scalar(x):
            # tpumx-lint: disable=sync-point -- cold-path eval readback
            return x.item()
        """
    found, _ = run_multi(files, rules={"sync-point"})
    assert found == []


# ---------------------------------------------------------------------------
# re-exported emitter aliases across modules (ISSUE 10)
# ---------------------------------------------------------------------------
def test_telemetry_catalog_follows_cross_module_reexport():
    files = {
        "tpu_mx/telemetry.py": """
            def counter(name, **labels):
                pass
            """,
        "tpu_mx/obs.py": "from .telemetry import counter\n",
        "tpu_mx/user.py": """
            from .obs import counter as C

            def f():
                C("fusion.flushez")     # typo, two re-export hops away
                C("fusion.flushes")     # known: fine
            """,
    }
    found, _ = run_multi(files, rules={"telemetry-catalog"})
    assert len(found) == 1
    assert "fusion.flushez" in found[0].message
    # FP guard: a re-exported function that merely SHARES the emitter
    # name but comes from an unrelated module is not checked
    found, _ = run_multi({
        "tpu_mx/db.py": """
            def counter(name):
                pass
            """,
        "tpu_mx/user2.py": """
            from .db import counter

            def g():
                counter("not.a.metric")
            """,
    }, rules={"telemetry-catalog"})
    assert found == []


# ---------------------------------------------------------------------------
# index: round-trip, staleness, dirty region (ISSUE 10)
# ---------------------------------------------------------------------------
LOCK_FIXTURE = textwrap.dedent("""
    import threading

    class Step:
        def __init__(self):
            self._lock = threading.Lock()
            self.n = 0

        def restore(self):
            with self._lock:
                self.n = 1
                self._reset()

        def _reset(self):
            self.n = 0
    """)


def test_index_round_trip_and_staleness(tmp_path):
    rel = "tpu_mx/foo.py"
    idx = tpumx_lint.build_index(
        {rel: tpumx_lint.FileCtx(rel, LOCK_FIXTURE)})
    assert idx.always_locked(rel, "Step._reset")
    path = tmp_path / "index.json"
    tpumx_lint.write_index(str(path), idx)
    idx2 = tpumx_lint.read_index(str(path))
    assert idx2 is not None
    assert idx2.files == idx.files
    # verdict parity from the DESERIALIZED summaries: link() rebuilds the
    # call graph without re-parsing any source
    assert idx2.always_locked(rel, "Step._reset")
    # staleness is sha-keyed: touching the source changes the entry
    touched = tpumx_lint.summarize_file(
        tpumx_lint.FileCtx(rel, LOCK_FIXTURE + "\n# touched\n"))
    assert touched["sha"] != idx.files[rel]["sha"]
    # a foreign/stale format never loads (the cache rebuilds instead)
    path.write_text(json.dumps({"format": "something-else"}))
    assert tpumx_lint.read_index(str(path)) is None
    path.write_text("{not json")
    assert tpumx_lint.read_index(str(path)) is None


def test_index_dirty_region_spans_callers_and_callees():
    ctxs = {
        "tpu_mx/a.py": "from .b import f\n\ndef top():\n    return f()\n",
        "tpu_mx/b.py": "from .c import g\n\ndef f():\n    return g()\n",
        "tpu_mx/c.py": "def g():\n    return 1\n",
        "tpu_mx/d.py": "def lonely():\n    return 2\n",
    }
    idx = tpumx_lint.build_index(
        {p: tpumx_lint.FileCtx(p, s) for p, s in ctxs.items()})
    region = idx.dirty_region({"tpu_mx/b.py"})
    # a dirty b.py can change a.py's verdicts (lock context flows down)
    # and c.py's (reachability flows up) — d.py is untouched
    assert {"tpu_mx/a.py", "tpu_mx/b.py", "tpu_mx/c.py"} <= region
    assert "tpu_mx/d.py" not in region


def test_changed_only_cli_end_to_end(tmp_path):
    """--changed-only in a scratch git repo: only the dirty file's region
    is analyzed, findings surface, and the index cache round-trips."""
    repo = tmp_path / "repo"
    pkg = repo / "tpu_mx"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "good.py").write_text("def ok():\n    return 1\n")
    # --repo makes catalog extraction repo-relative (the scratch tree's
    # OWN contracts, not the host's) — and the tool fails closed without
    # them, so the scratch repo carries minimal literal catalogs
    (pkg / "telemetry.py").write_text('KNOWN_METRICS = frozenset({"m.ok"})\n')
    (pkg / "tracing.py").write_text('KNOWN_EVENTS = frozenset({"e.ok"})\n')
    env = {**os.environ, "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
           "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t"}
    for cmd in (["git", "init", "-q"], ["git", "add", "-A"],
                ["git", "commit", "-qm", "seed"]):
        subprocess.run(cmd, cwd=repo, env=env, check=True, timeout=60,
                       capture_output=True)
    # dirty file with a library-scope durability violation
    (pkg / "bad.py").write_text(
        'def f(p, b):\n    with open(p, "wb") as fh:\n        fh.write(b)\n')
    out = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "tpumx_lint.py"),
         "tpu_mx", "--changed-only", "--format", "json",
         "--repo", str(repo),
         "--baseline", str(tmp_path / "none.json"),
         "--index", str(tmp_path / "index.json")],
        capture_output=True, text=True, timeout=120, cwd=repo,
        env={**env, "PYTHONPATH": ""})
    assert out.returncode == 1, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert [f["rule"] for f in payload["findings"]] == ["durability"]
    assert payload["changed_region"] == ["tpu_mx/bad.py"]
    assert os.path.exists(tmp_path / "index.json")

    def rerun():
        out = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "tpumx_lint.py"),
             "tpu_mx", "--changed-only", "--format", "json",
             "--repo", str(repo),
             "--baseline", str(tmp_path / "none.json"),
             "--index", str(tmp_path / "index.json")],
            capture_output=True, text=True, timeout=120, cwd=repo,
            env={**env, "PYTHONPATH": ""})
        return out, json.loads(out.stdout or "{}")

    # an untracked DIRECTORY: git prints one '?? tpu_mx/sub/' line — the
    # violating file inside must still enter the changed set
    (pkg / "bad.py").write_text("def f():\n    return 0\n")
    sub = pkg / "sub"
    sub.mkdir()
    (sub / "__init__.py").write_text("")
    (sub / "worse.py").write_text(
        'def g(p, b):\n    with open(p, "wb") as fh:\n        fh.write(b)\n')
    out, payload = rerun()
    assert out.returncode == 1, out.stdout + out.stderr
    assert [f["path"] for f in payload["findings"]] \
        == ["tpu_mx/sub/worse.py"]

    # sha staleness without git dirt: commit everything (tree clean),
    # then rewrite a tracked file IN the same commit shape a pull
    # produces — the cache's sha mismatch alone must re-analyze it
    for cmd in (["git", "add", "-A"], ["git", "commit", "-qm", "r2"]):
        subprocess.run(cmd, cwd=repo, env=env, check=True, timeout=60,
                       capture_output=True)
    (pkg / "good.py").write_text(
        'def ok(p, b):\n    with open(p, "wb") as fh:\n        fh.write(b)\n')
    for cmd in (["git", "add", "-A"], ["git", "commit", "-qm", "r3"]):
        subprocess.run(cmd, cwd=repo, env=env, check=True, timeout=60,
                       capture_output=True)
    out, payload = rerun()
    assert out.returncode == 1, out.stdout + out.stderr
    assert [f["path"] for f in payload["findings"]] == ["tpu_mx/good.py"]

    # deleting a tracked file is not an error: the entry leaves the
    # cache and the deleted path still shows in the reported region
    (pkg / "good.py").unlink()
    out, payload = rerun()
    assert out.returncode == 0, out.stdout + out.stderr
    assert payload["findings"] == []
    assert "tpu_mx/good.py" in payload["changed_region"]
    idx = json.load(open(tmp_path / "index.json"))
    assert "tpu_mx/good.py" not in idx["files"]

    # --write-baseline under --changed-only would shred the full
    # baseline: rejected as a usage error
    out = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "tpumx_lint.py"),
         "tpu_mx", "--changed-only", "--write-baseline",
         "--repo", str(repo), "--index", str(tmp_path / "index.json")],
        capture_output=True, text=True, timeout=120, cwd=repo,
        env={**env, "PYTHONPATH": ""})
    assert out.returncode == 2
    assert "full run" in out.stderr


def test_lambda_under_lock_does_not_prove_callee_locked():
    # a lambda DEFINED inside `with lock:` may run later, off-lock (the
    # deferred-callback shape): its call must NOT count as a locked
    # call site, or always_locked() would discharge a real race
    found, _ = run("""
        import threading

        class Step:
            def __init__(self):
                self._lock = threading.Lock()
                self._cbs = []
                self.n = 0

            def locked_set(self):
                with self._lock:
                    self.n = 1
                    self._cbs.append(lambda: self._reset())

            def _reset(self):
                self.n = 0
        """, "tpu_mx/foo.py", rules={"concurrency"})
    assert len(found) == 1
    assert "reached lock-free from" in found[0].message
