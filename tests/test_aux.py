"""Aux subsystems: profiler, monitor, runtime features, engine API
(reference test analog: tests/python/unittest/test_profiler.py,
test_engine.py)."""
import os

import numpy as np
import pytest

import tpu_mx as mx
from tpu_mx import nd


def test_profiler_scope_and_dumps(tmp_path):
    fname = str(tmp_path / "profile.json")
    mx.profiler.set_config(filename=fname, profile_all=True)
    mx.profiler.set_state("run")
    with mx.profiler.scope("matmul_region"):
        a = nd.array(np.random.rand(32, 32).astype(np.float32))
        b = nd.dot(a, a)
        b.wait_to_read()
    task = mx.profiler.Task("mytask")
    task.start()
    task.stop()
    c = mx.profiler.Counter("imgs", value=0)
    c.increment(5)
    mx.profiler.Marker("tick").mark()
    mx.profiler.set_state("stop")
    assert os.path.exists(fname)
    table = mx.profiler.dumps()
    assert "matmul_region" in table
    assert "mytask" in table


def test_profiler_pause_resume(tmp_path):
    mx.profiler.set_config(filename=str(tmp_path / "p.json"))
    mx.profiler.set_state("run")
    mx.profiler.pause()
    with mx.profiler.scope("hidden"):
        pass
    mx.profiler.resume()
    with mx.profiler.scope("visible"):
        pass
    mx.profiler.set_state("stop")
    table = mx.profiler.dumps(reset=True)
    assert "visible" in table and "hidden" not in table


def test_monitor_records_stats():
    from tpu_mx import gluon
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(8, activation="relu"))
    net.add(gluon.nn.Dense(4))
    net.initialize()
    mon = mx.monitor.Monitor(interval=2, pattern=".*")
    mon.install(net)
    x = nd.array(np.random.rand(2, 16).astype(np.float32))
    seen = []
    for _ in range(4):
        mon.tic()
        net(x)
        seen.append(mon.toc())
    # interval=2: batches 0 and 2 record, 1 and 3 do not
    assert len(seen[0]) > 0 and len(seen[2]) > 0
    assert seen[1] == [] and seen[3] == []
    step, name, stat = seen[0][0]
    assert isinstance(stat, float) and np.isfinite(stat)


def test_monitor_pattern_filter():
    from tpu_mx import gluon
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(4))
    net.initialize()
    mon = mx.monitor.Monitor(interval=1, pattern="nomatch_.*")
    mon.install(net)
    mon.tic()
    net(nd.array(np.random.rand(2, 8).astype(np.float32)))
    assert mon.toc() == []


def test_runtime_feature_list():
    feats = mx.runtime.feature_list()
    assert feats
    names = {f.name for f in feats}
    assert {"JAX", "CPU", "PROFILER"} <= names
    features = mx.runtime.Features()
    assert features.is_enabled("JAX")


def test_engine_api():
    assert mx.engine.engine_type() == "JaxAsyncDispatch"
    prev = mx.engine.set_bulk_size(32)
    assert mx.engine.set_bulk_size(prev) == 32
    with mx.engine.bulk(64):
        a = nd.array(np.ones((4, 4), np.float32))
        b = a * 2
    mx.engine.wait_for_all()
    np.testing.assert_allclose(b.asnumpy(), 2.0)


def test_monitor_uninstall():
    from tpu_mx import gluon
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(4))
    net.initialize()
    mon = mx.monitor.Monitor(interval=1)
    mon.install(net)
    mon.install(net)  # double install -> duplicated hooks until uninstall
    mon.uninstall()
    mon.tic()
    net(nd.array(np.random.rand(2, 8).astype(np.float32)))
    assert mon.toc() == []


def test_profiler_new_session_clears_events(tmp_path):
    f1, f2 = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    import json
    mx.profiler.set_config(filename=f1)
    mx.profiler.set_state("run")
    with mx.profiler.scope("first"):
        pass
    mx.profiler.set_state("stop")
    mx.profiler.set_config(filename=f2)
    mx.profiler.set_state("run")
    with mx.profiler.scope("second"):
        pass
    mx.profiler.set_state("stop")
    names = {e["name"] for e in json.load(open(f2))["traceEvents"]}
    assert "second" in names and "first" not in names


def test_lbsgd_trains():
    from tpu_mx import gluon, autograd
    net = gluon.nn.Dense(4)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "lbsgd",
                            {"learning_rate": 0.5, "momentum": 0.9,
                             "warmup_epochs": 1, "updates_per_epoch": 2})
    X = np.random.RandomState(0).rand(16, 8).astype(np.float32)
    losses = []
    for _ in range(10):
        with autograd.record():
            loss = (net(nd.array(X)) ** 2).mean()
        loss.backward()
        trainer.step(16)
        losses.append(float(loss.asnumpy()))
    assert losses[-1] < losses[0]


def test_inception_v3_registered():
    from tpu_mx.gluon.model_zoo import vision
    assert "inception_v3" in [m for m in vision.get_model.__globals__["_models"]]


def test_engine_push_async_hook():
    """The Horovod-era external-op injection point (MXEnginePushAsync
    analog): fn sees settled reads and can rebind writes."""
    import numpy as np
    from tpu_mx import engine, nd

    a = nd.array(np.array([1.0, 2.0], np.float32))
    out = nd.zeros((2,))

    def external(reads, writes):
        writes[0]._rebind((reads[0] * 3)._data)
        return "ok"

    assert engine.push_async(external, [a], [out]) == "ok"
    np.testing.assert_allclose(out.asnumpy(), [3.0, 6.0])
    assert engine.push_sync is engine.push_async


def test_persistent_compilation_cache(tmp_path):
    """runtime.set_compilation_cache writes program artifacts that a fresh
    process would reuse (cache dir gains entries after a novel compile)."""
    import jax
    import jax.numpy as jnp
    from tpu_mx import runtime
    d = tmp_path / "xla_cache"
    runtime.set_compilation_cache(str(d), min_compile_time_secs=0.0)
    try:
        @jax.jit
        def f(x):
            return (x @ x.T).sum() + 12345.678  # novel constant -> novel key
        f(jnp.ones((64, 64))).block_until_ready()
        entries = list(d.rglob("*")) if d.exists() else []
        assert entries, "no cache entries written"
    finally:
        # jax latched the cache at the first compile: without a reset the
        # process goes on reading and writing it, and a later test in the
        # same worker gets executables whose op names are another
        # program's (tests/test_named_passes.py)
        from jax.experimental.compilation_cache import compilation_cache
        jax.config.update("jax_compilation_cache_dir", None)
        compilation_cache.reset_cache()


def test_mcc_and_nll_metrics():
    import tpu_mx.metric as M
    m = M.MCC()
    m.update([np.array([1, 1, 0, 0])], [np.array([0.9, 0.8, 0.2, 0.6])])
    assert abs(m.get()[1] - 2 / np.sqrt(12)) < 1e-6
    m.reset()
    assert m.get()[1] != m.get()[1] or m.num_inst == 0  # nan or empty
    nll = M.NegativeLogLikelihood()
    nll.update([np.array([0, 1])], [np.array([[0.9, 0.1], [0.4, 0.6]])])
    assert abs(nll.get()[1] -
               (-np.log(0.9) - np.log(0.6)) / 2) < 1e-6
    # registry creation by name
    assert mx.metric.create("mcc").name == "mcc"
    assert mx.metric.create("nll-loss").name == "nll-loss"


def test_mixed_and_load_initializers():
    import tpu_mx.initializer as I
    from tpu_mx.gluon import nn
    from tpu_mx import nd
    mix = I.Mixed([".*bias", ".*"], [I.Zero(), I.Constant(2.0)])
    net = nn.Dense(3, in_units=2)
    net.initialize(init=mix)
    assert (net.bias.data().asnumpy() == 0).all()
    assert (net.weight.data().asnumpy() == 2.0).all()
    ld = I.Load({"w": np.arange(4.0)}, default_init=I.Zero())
    assert (ld("w", (4,)) == np.arange(4.0)).all()
    import pytest as _pytest
    with _pytest.raises(ValueError, match="shape mismatch"):
        ld("w", (5,))


def test_device_init_samples_on_device():
    """Standard initializers sample with the device PRNG (no host numpy
    transfer), driven by mx.random.seed; see initializer.device_sample."""
    import jax
    import tpu_mx as mx
    import tpu_mx.initializer as I
    from tpu_mx.gluon import nn

    def build():
        mx.random.seed(7)
        net = nn.Dense(8, in_units=16)
        net.initialize(init="xavier")
        return net.weight.data().asnumpy(), net.bias.data().asnumpy()

    w1, b1 = build()
    w2, _ = build()
    assert (w1 == w2).all()          # device PRNG is mx.random.seed-driven
    assert (b1 == 0).all()           # name-dispatch: bias -> 0
    # xavier-uniform bounds: scale = sqrt(3 / avg_fan(16,8)) = 0.5
    assert abs(w1).max() <= 0.5 and abs(w1).std() > 0.05

    # direct surface: jax array of the requested dtype; aux names get
    # their convention constants
    out = I.Xavier().device_sample("blk_weight", (4, 8), "bfloat16")
    assert isinstance(out, jax.Array) and str(out.dtype) == "bfloat16"
    var = I.Xavier().device_sample("bn_running_var", (4,))
    assert (np.asarray(var) == 1.0).all()

    # no device rule / custom __call__ semantics -> host path (None)
    assert I.Orthogonal().device_sample("w", (4, 4)) is None
    assert I.Bilinear().device_sample("w", (1, 1, 4, 4)) is None
    assert I.LSTMBias().device_sample("h2h_bias", (8,)) is None
    # LSTMBias host path still sets the forget-gate block to 1
    b = I.LSTMBias()("h2h_bias", (8,))
    assert (b[2:4] == 1.0).all() and b.sum() == 2.0


def test_hybrid_first_call_deferred_init_no_tracer_leak():
    """Deferred init firing INSIDE the hybridize trace must fall back to
    the host path: device sampling (even jnp.full for aux params) would
    stage into the jaxpr and leave a tracer in Parameter._data."""
    import jax
    from tpu_mx import nd
    from tpu_mx.gluon import nn
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.BatchNorm(), nn.Dense(2))
    net.initialize()
    net.hybridize()
    out1 = net(nd.ones((2, 4)))  # params finalize inside this trace
    for p in net.collect_params().values():
        assert not isinstance(p.data()._data, jax.core.Tracer), p.name
    out2 = net(nd.ones((2, 4)))  # cached program, concrete params
    np.testing.assert_array_equal(out1.asnumpy(), out2.asnumpy())


def test_device_init_host_revert_knob(monkeypatch):
    import tpu_mx.initializer as I
    monkeypatch.setenv("TPUMX_HOST_INIT", "1")
    assert I.Xavier().device_sample("w", (2, 2)) is None
    monkeypatch.delenv("TPUMX_HOST_INIT")
    assert I.Xavier().device_sample("w", (2, 2)) is not None


def test_symbolic_check_helpers_and_tensorrt_stub():
    import tpu_mx.test_utils as T
    x = mx.sym.Variable("x")
    y = x * 2.0 + 1.0
    T.check_symbolic_forward(y, [np.array([1.0, 2.0], np.float32)],
                             [np.array([3.0, 5.0], np.float32)])
    T.check_symbolic_backward(y, [np.array([1.0, 2.0], np.float32)],
                              [np.ones(2, np.float32)],
                              [np.full(2, 2.0, np.float32)])
    T.assert_exception(lambda: 1 / 0, ZeroDivisionError)
    s2 = T.rand_shape_2d(5, 5)
    assert len(s2) == 2 and all(1 <= v <= 5 for v in s2)
    from tpu_mx.contrib import tensorrt
    with pytest.raises(mx.MXNetError, match="StableHLO"):
        tensorrt.optimize_graph(None)


def test_speedometer_and_do_checkpoint(tmp_path, caplog):
    """callback.Speedometer logs throughput; do_checkpoint saves epoch
    params loadable via model.load_checkpoint (REF callback.py/model.py)."""
    import logging
    from tpu_mx import callback, model as model_mod, nd
    from tpu_mx.gluon import nn

    class Batch:
        pass

    sp = callback.Speedometer(batch_size=32, frequent=2, auto_reset=False)
    p = Batch()
    p.epoch, p.nbatch, p.eval_metric = 0, 2, None
    with caplog.at_level(logging.INFO):
        sp(p)       # first call arms the timer
        p.nbatch = 4
        sp(p)       # second hits count %% frequent == 0 and logs
    assert any("Speed" in r.message or "samples/sec" in r.message
               for r in caplog.records), caplog.records

    net = nn.Dense(3, in_units=2)
    net.initialize()
    net(nd.ones((1, 2)))
    sym_name = str(tmp_path / "mm")
    # module-level checkpoint format helpers (reference filename contract)
    sym = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=3,
                                name="fc")
    args = {k: p_.data() for k, p_ in net.collect_params().items()}
    model_mod.save_checkpoint(sym_name, 3, sym, args, {})
    import os
    assert os.path.exists(sym_name + "-0003.params")
    loaded_sym, arg2, aux2 = model_mod.load_checkpoint(sym_name, 3)
    assert "fc" in [n for n in loaded_sym.get_internals().list_outputs()][0] \
        or loaded_sym is not None
    for k in args:
        np.testing.assert_allclose(arg2[k].asnumpy(), args[k].asnumpy())


def test_shared_compilation_cache_env_gate(monkeypatch):
    """enable_shared_compilation_cache: one env knob disables the cache
    for ALL on-chip tools."""
    from tpu_mx import runtime
    monkeypatch.setenv("BENCH_COMPILE_CACHE", "0")
    assert runtime.enable_shared_compilation_cache() is None


@pytest.mark.parametrize("from_env", [True, False])
def test_shared_compilation_cache_placement(tmp_path, from_env):
    """Where JAX_COMPILATION_CACHE_DIR is set the cache directory is the
    environment's (jax reads it at import; the program sets none in code);
    unset, it is the fixed <checkout>/.jax_cache.  A fresh interpreter,
    because jax reads the variable once, at import."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    env.pop("BENCH_COMPILE_CACHE", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "elsewhere")
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax\n"
         "from tpu_mx import runtime\n"
         "d = runtime.enable_shared_compilation_cache()\n"
         "print(d)\n"
         "print(jax.config.jax_compilation_cache_dir)\n"
         "print(jax.config.jax_persistent_cache_min_entry_size_bytes)\n"],
        env=env, capture_output=True, text=True, timeout=120, cwd=tmp_path)
    assert out.returncode == 0, out.stderr[-2000:]
    returned, configured, min_bytes = out.stdout.split()
    want = str(tmp_path / "elsewhere") if from_env \
        else os.path.join(repo, ".jax_cache")
    assert returned == configured == want
    assert min_bytes == "0"  # thresholds applied on both branches
