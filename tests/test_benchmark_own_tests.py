"""The yardstick's own tests (benchmark/tests), case by case in tier-1.

Each file there is loaded by path, unedited, and its tests stand here under
a class of its own, so that two files may use one name and a case that
breaks is a new name in the run's `failed`.  What they need besides is what
running them from the root gave them: `benchmark/` on the import path while
they run, and children (they start run.py and the readings tool) on the CPU
without this suite's XLA_FLAGS, whose eight host devices are not theirs.
"""
import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


def _load(name):
    """benchmark/tests/<name>.py as a module; each puts benchmark/ on the
    import path as it is imported, which is undone here: every worker
    collects this file, and only the tests below should see that path."""
    path, before = os.path.join(BENCH, "tests", name + ".py"), list(sys.path)
    spec = importlib.util.spec_from_file_location("benchmark_tests_" + name,
                                                  path)
    module = importlib.util.module_from_spec(spec)
    try:
        spec.loader.exec_module(module)
    finally:
        sys.path[:] = before
    return module


def _cases(module):
    return {n: staticmethod(f) for n, f in vars(module).items()
            if n.startswith("test_") and callable(f)}


_benchmark = _load("test_benchmark")
TestBenchmark = type("TestBenchmark", (), _cases(_benchmark))
TestScopes = type("TestScopes", (), _cases(_load("test_scopes")))
TestDecoderCell = type("TestDecoderCell", (), _cases(_load("test_decoder_cell")))
TestWindowedCell = type("TestWindowedCell", (),
                        _cases(_load("test_windowed_cell")))
TestExpertHead = type("TestExpertHead", (), _cases(_load("test_expert_head")))
TestGatedCell = type("TestGatedCell", (), _cases(_load("test_gated_cell")))
TestNamedPasses = type("TestNamedPasses", (),
                       _cases(_load("test_named_passes")))
grown = _benchmark.grown        # test_benchmark.py's one fixture


@pytest.fixture(scope="module", autouse=True)
def _as_run_from_the_root():
    import jax
    jax.devices()       # this process's backend is built before its flags go
    with pytest.MonkeyPatch.context() as patch:
        patch.syspath_prepend(BENCH)
        patch.delenv("XLA_FLAGS", raising=False)
        patch.setenv("JAX_PLATFORMS", "cpu")
        yield
