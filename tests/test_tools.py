"""Tools: im2rec + launch.py (reference analog: the dmlc local tracker
distributed tests, SURVEY §4 'distributed tests without a real cluster')."""
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
cv2 = pytest.importorskip("cv2")


def _multiprocess_collectives_supported():
    """Whether the jax backend can run CROSS-PROCESS collectives.  The
    CPU backend cannot: any 2-process psum/barrier raises
    INVALID_ARGUMENT "Multiprocess computations aren't implemented on
    the CPU backend" (jax 0.4.37) — process-group formation and virtual
    single-process meshes work, the collective dispatch itself does not.
    Capability-keyed (not env-keyed) so the skip lifts itself the moment
    these tests run against a real TPU/GPU backend."""
    try:
        import jax
        return jax.default_backend() != "cpu"
    except Exception:  # no jax at all: the tests below cannot run either
        return False


# The three 2-process tests below exercise REAL cross-process collectives
# (elastic barrier death detection, dist_sync kvstore reduce, multi-host
# CompiledTrainStep).  They failed on every CPU-backend run since the
# seed — a backend capability gap, not a regression — and were carried as
# "fails at seed too" folklore until ISSUE 10 made the condition explicit.
_needs_multiprocess_collectives = pytest.mark.skipif(
    not _multiprocess_collectives_supported(),
    reason="needs cross-process collectives: the CPU jax backend raises "
           "'Multiprocess computations aren't implemented on the CPU "
           "backend' (capability gap, present at seed; runs on TPU/GPU)")


def _env_cpu():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def test_im2rec_roundtrip(tmp_path):
    # class-per-folder layout
    for cls in ("cat", "dog"):
        d = tmp_path / "imgs" / cls
        d.mkdir(parents=True)
        for i in range(3):
            img = (np.random.RandomState(i).rand(32, 40, 3) * 255
                   ).astype(np.uint8)
            cv2.imwrite(str(d / f"{i}.jpg"), img)
    prefix = str(tmp_path / "out")
    subprocess.run([sys.executable, os.path.join(REPO, "tools/im2rec.py"),
                    "--list", prefix, str(tmp_path / "imgs")],
                   check=True, env=_env_cpu())
    assert os.path.exists(prefix + ".lst")
    subprocess.run([sys.executable, os.path.join(REPO, "tools/im2rec.py"),
                    prefix, str(tmp_path / "imgs")],
                   check=True, env=_env_cpu())
    from tpu_mx import recordio
    r = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    assert len(r.keys) == 6
    header, img = recordio.unpack_img(r.read_idx(r.keys[0]))
    assert img.shape == (32, 40, 3)
    labels = set()
    for k in r.keys:
        h, _ = recordio.unpack(r.read_idx(k))
        labels.add(float(np.asarray(h.label).ravel()[0]))
    assert labels == {0.0, 1.0}
    # and the native pipeline can consume the packed file
    from tpu_mx.io import ImageRecordIter
    it = ImageRecordIter(path_imgrec=prefix + ".rec",
                         data_shape=(3, 16, 16), batch_size=3)
    assert next(iter(it)).data[0].shape == (3, 3, 16, 16)


@pytest.mark.slow
def test_launch_local_spmd(tmp_path):
    """launch.py -n 2: both processes join one jax.distributed group and
    agree on rank/size (the dist_sync_kvstore.py pattern)."""
    script = tmp_path / "worker.py"
    script.write_text(
        "import tpu_mx as mx\n"
        "ok = mx.kvstore.dist_init()\n"
        "assert ok\n"
        "kv = mx.kvstore.create('dist_sync')\n"
        "print(f'RANK={kv.rank} SIZE={kv.num_workers}', flush=True)\n"
        "assert kv.num_workers == 2\n")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/launch.py"), "-n", "2",
         sys.executable, str(script)],
        capture_output=True, text=True, env=_env_cpu(), timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    # the two workers share the stdout pipe; writes can interleave mid-line
    import re
    ranks = sorted(re.findall(r"RANK=(\d) SIZE=(\d)", out.stdout))
    assert ranks == [("0", "2"), ("1", "2")], out.stdout


@pytest.mark.slow
@_needs_multiprocess_collectives
def test_elastic_barrier_detects_dead_rank(tmp_path):
    """A killed rank in a 2-process run produces a clean WorkerFailure within
    the timeout instead of an indefinite hang (SURVEY §5.3)."""
    script = tmp_path / "worker.py"
    script.write_text(
        "import sys, time\n"
        "import tpu_mx as mx\n"
        "mx.kvstore.dist_init()\n"
        "import jax\n"
        "rank = jax.process_index()\n"
        "mx.elastic.barrier('warmup', timeout=60)  # both alive: fine\n"
        "print(f'WARMUP-OK rank={rank}', flush=True)\n"
        "if rank == 1:\n"
        "    sys.exit(0)  # rank 1 'dies' before the next barrier\n"
        "t0 = time.time()\n"
        "try:\n"
        "    mx.elastic.barrier('epoch', timeout=8)\n"
        "    print('UNEXPECTED-PASS', flush=True)\n"
        "except mx.elastic.WorkerFailure as e:\n"
        "    dt = time.time() - t0\n"
        "    assert dt < 30, dt\n"
        "    assert 'resume' in str(e)\n"
        "    print(f'DETECTED rank={rank} after {dt:.1f}s', flush=True)\n")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/launch.py"), "-n", "2",
         sys.executable, str(script)],
        capture_output=True, text=True, env=_env_cpu(), timeout=300)
    assert "DETECTED rank=0" in out.stdout, (out.stdout, out.stderr[-1500:])
    assert "UNEXPECTED-PASS" not in out.stdout


def test_auto_resume_contract(tmp_path):
    """latest_checkpoint + auto_resume restart training from the newest
    epoch's params (single-process check of the --resume contract)."""
    import numpy as np
    import tpu_mx as mx
    from tpu_mx import nd
    from tpu_mx.gluon import nn

    net = nn.Dense(3, in_units=4)
    net.initialize()
    prefix = str(tmp_path / "ckpt")
    for epoch in (0, 1, 2):
        net.weight.set_data(nd.full((3, 4), float(epoch)))
        net.save_parameters(f"{prefix}-{epoch:04d}.params")
    epoch, path = mx.elastic.latest_checkpoint(prefix)
    assert epoch == 2 and path.endswith("-0002.params")

    net2 = nn.Dense(3, in_units=4)
    start = mx.elastic.auto_resume(prefix, net=net2)
    assert start == 3
    np.testing.assert_allclose(net2.weight.data().asnumpy(), 2.0)
    # fresh run: no checkpoints -> epoch 0
    assert mx.elastic.auto_resume(str(tmp_path / "none")) == 0


def test_ssh_launcher_command_construction(tmp_path):
    """--launcher ssh builds the right per-rank ssh argv + env protocol
    (REF:dmlc_tracker/ssh.py) — validated without a cluster."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import importlib
        launch = importlib.import_module("launch")
    finally:
        sys.path.pop(0)

    hf = tmp_path / "hosts.txt"
    hf.write_text("# cluster\nnode-a\nnode-b  # gpu box\n\n")
    hosts = launch.read_hostfile(str(hf))
    assert hosts == ["node-a", "node-b"]

    cmds = launch.build_ssh_commands(
        hosts, 4, "head:9999", ["python", "train.py", "--lr", "0.1"],
        env_extra=["FOO=bar baz"])
    assert len(cmds) == 4
    # round-robin placement
    assert [h for h, _ in cmds] == ["node-a", "node-b", "node-a", "node-b"]
    for rank, (host, argv) in enumerate(cmds):
        assert argv[0] == "ssh" and argv[-2] == host
        remote = argv[-1]
        assert f"TPUMX_PROC_ID={rank}" in remote
        assert "TPUMX_NUM_PROC=4" in remote
        assert "TPUMX_COORDINATOR=head:9999" in remote
        assert "FOO='bar baz'" in remote
        assert remote.endswith("python train.py --lr 0.1")

    with pytest.raises(ValueError):
        empty = tmp_path / "empty.txt"
        empty.write_text("# nothing\n")
        launch.read_hostfile(str(empty))


def test_local_launcher_is_a_cpu_simulation():
    """Local workers run on the CPU; a JAX_PLATFORMS that names an
    accelerator is refused with more than one local worker (each process
    would open every chip)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import importlib
        launch = importlib.import_module("launch")
    finally:
        sys.path.pop(0)
    assert launch.local_platform(4, "") == "cpu"
    assert launch.local_platform(4, "cpu") == "cpu"
    assert launch.local_platform(1, "tpu") == "tpu"
    for requested in ("tpu", "tpu,cpu", "cuda"):
        with pytest.raises(SystemExit, match="CPU simulation"):
            launch.local_platform(2, requested)


@pytest.mark.slow
@_needs_multiprocess_collectives
def test_dist_sync_kvstore_cross_process_sum(tmp_path):
    """Eager dist_sync push/pull performs a REAL cross-process reduce
    (REF:tests/nightly/dist_sync_kvstore.py): pulled values can only arise
    from summing both ranks' pushes."""
    script = tmp_path / "worker.py"
    script.write_text(
        "import numpy as np\n"
        "import tpu_mx as mx\n"
        "from tpu_mx import nd\n"
        "mx.kvstore.dist_init()\n"
        "kv = mx.kvstore.create('dist_sync')\n"
        "rank, size = kv.rank, kv.num_workers\n"
        "assert size == 2\n"
        "# no-updater path: pull returns the cross-worker sum of pushes\n"
        "kv.init('a', nd.zeros((3, 4)))\n"
        "kv.push('a', nd.full((3, 4), rank + 1.0))  # ranks push 1s and 2s\n"
        "out = nd.zeros((3, 4))\n"
        "kv.pull('a', out=out)\n"
        "np.testing.assert_allclose(out.asnumpy(), 3.0)  # 1 + 2\n"
        "# multi-key, shaped: sum_r (rank+1)*arange = 3*arange\n"
        "base = np.arange(6, dtype=np.float32).reshape(2, 3)\n"
        "kv.init(['k0', 'k1'], [nd.zeros((2, 3)), nd.zeros((2, 3))])\n"
        "kv.push(['k0', 'k1'], [nd.array(base * (rank + 1)),\n"
        "                        nd.array(base * 10 * (rank + 1))])\n"
        "o0, o1 = nd.zeros((2, 3)), nd.zeros((2, 3))\n"
        "kv.pull(['k0', 'k1'], out=[o0, o1])\n"
        "np.testing.assert_allclose(o0.asnumpy(), base * 3)\n"
        "np.testing.assert_allclose(o1.asnumpy(), base * 30)\n"
        "# updater path (update_on_kvstore): w += global grad sum, same on\n"
        "# every rank\n"
        "kv.set_updater(lambda k, g, w: w.__iadd__(g))\n"
        "kv.init('w', nd.zeros((5,)))\n"
        "kv.push('w', nd.full((5,), float(2 ** rank)))  # 1 and 2 -> sum 3\n"
        "wout = nd.zeros((5,))\n"
        "kv.pull('w', out=wout)\n"
        "np.testing.assert_allclose(wout.asnumpy(), 3.0)\n"
        "print(f'KVOK rank={rank}', flush=True)\n")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/launch.py"), "-n", "2",
         sys.executable, str(script)],
        capture_output=True, text=True, env=_env_cpu(), timeout=300)
    assert out.returncode == 0, (out.stdout[-1000:], out.stderr[-2000:])
    import re
    assert sorted(re.findall(r"KVOK rank=(\d)", out.stdout)) == ["0", "1"], \
        out.stdout


def test_bandwidth_tool():
    """tools/bandwidth.py (REF:tools/bandwidth/measure.py analog) emits
    parseable per-collective records with positive bandwidth."""
    import json as _json
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bandwidth.py"),
         "--devices", "8", "--sizes", "0.5", "--iters", "2"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-500:]
    recs = [_json.loads(l) for l in out.stdout.splitlines()
            if l.startswith("{")]
    names = {r["collective"] for r in recs}
    assert names == {"psum", "all_gather", "reduce_scatter", "ppermute"}
    assert all(r["alg_bandwidth_gbps"] > 0 for r in recs)
    assert all(r["devices"] == 8 for r in recs)


def test_parse_log_table():
    """tools/parse_log.py (REF:tools/parse_log.py analog): Speedometer +
    fit log lines -> per-epoch table."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "parse_log", os.path.join(REPO, "tools", "parse_log.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    lines = [
        "INFO Epoch[0] Batch [20]\tSpeed: 100.00 samples/sec\taccuracy=0.5",
        "INFO Epoch[0] Batch [40]\tSpeed: 140.00 samples/sec\taccuracy=0.6",
        "INFO Epoch[0] Train-accuracy=0.612000",
        "INFO Epoch[0] Time cost=12.500",
        "INFO Epoch[0] Validation-accuracy=0.580000",
        "INFO Epoch[1] Batch [20]\tSpeed: 150.00 samples/sec\taccuracy=0.7",
        "INFO Epoch[1] Train-accuracy=0.713000",
        "INFO Epoch[1] Time cost=11.000",
        "unrelated noise line",
    ]
    rows = mod.parse(lines)
    assert len(rows) == 2
    assert rows[0]["epoch"] == 0
    assert rows[0]["speed_mean"] == 120.0
    assert rows[0]["train-accuracy"] == 0.612
    assert rows[0]["val-accuracy"] == 0.58
    assert rows[0]["time_s"] == 12.5
    assert rows[1]["speed_mean"] == 150.0
    md = mod.render(rows, "markdown")
    assert "| epoch |" in md and "120.0" in md
    csv = mod.render(rows, "csv")
    assert csv.splitlines()[0].startswith("epoch,")
    import json as _json
    assert _json.loads(mod.render(rows, "json"))[1]["epoch"] == 1


def test_strict_kvstore_flag_raises_on_eager_dist(monkeypatch):
    """TPUMX_STRICT_KVSTORE=1 turns the slow eager dist push into a loud
    error (VERDICT r3 weak#6) instead of a silent degradation."""
    import tpu_mx as mx
    from tpu_mx.base import MXNetError
    kv = mx.kv.create("dist_sync")
    # single process: pretend we're a 2-worker job so _global_sum engages
    monkeypatch.setattr(kv, "_is_dist", True, raising=False)
    monkeypatch.setattr(kv, "_num_workers", 2, raising=False)
    monkeypatch.setenv("TPUMX_STRICT_KVSTORE", "1")
    kv.init("w", mx.nd.zeros((3,)))
    with pytest.raises(MXNetError, match="STRICT_KVSTORE"):
        kv.push("w", mx.nd.ones((3,)))


@pytest.mark.slow
@_needs_multiprocess_collectives
def test_launch_two_process_compiled_train_step(tmp_path):
    """Full multi-host SPMD path: TWO processes x 4 virtual devices form
    one dp=8 mesh and run the SAME CompiledTrainStep — both ranks must
    produce identical loss/weights, equal to a single-process dp=8 run
    (SURVEY §2.3 'DP multi-host sync' beyond the kvstore-math check)."""
    import numpy as np
    script = tmp_path / "worker.py"
    script.write_text(
        "import numpy as np\n"
        "import tpu_mx as mx\n"
        "mx.kvstore.dist_init()\n"
        "import jax\n"
        "assert jax.device_count() == 8, jax.device_count()\n"
        "from tpu_mx import gluon, nd\n"
        "from tpu_mx.gluon import nn\n"
        "from tpu_mx.parallel import CompiledTrainStep, make_mesh\n"
        "np.random.seed(0)\n"
        "mx.random.seed(0)\n"
        "net = nn.HybridSequential()\n"
        "net.add(nn.Dense(16, in_units=8, activation='relu'),\n"
        "        nn.Dense(4, in_units=16))\n"
        "net.initialize(init='xavier')\n"
        "net(nd.ones((1, 8)))\n"
        "mesh = make_mesh({'dp': 8}, devices=jax.devices())\n"
        "step = CompiledTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),\n"
        "                         mx.optimizer.create('sgd', learning_rate=0.1),\n"
        "                         mesh=mesh)\n"
        "x = np.random.RandomState(7).rand(16, 8).astype(np.float32)\n"
        "y = np.random.RandomState(8).randint(0, 4, (16,)).astype(np.float32)\n"
        "loss = None\n"
        "for _ in range(3):\n"
        "    loss = step.step(nd.array(x), nd.array(y))\n"
        "print(f'RANK{jax.process_index()} "
        "LOSS={float(np.asarray(loss._data)):.6f}', flush=True)\n")
    env = _env_cpu()
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/launch.py"), "-n", "2",
         sys.executable, str(script)],
        capture_output=True, text=True, env=env, timeout=400)
    assert out.returncode == 0, out.stderr[-2000:]
    import re
    losses = {m.group(1): float(m.group(2)) for m in
              re.finditer(r"RANK(\d) LOSS=([\d.]+)", out.stdout)}
    assert set(losses) == {"0", "1"}, out.stdout
    assert losses["0"] == losses["1"]  # equal to 6 printed decimals

    # single-process dp=8 oracle (conftest's virtual mesh), same seeds
    import jax
    import tpu_mx as mx
    from tpu_mx import gluon, nd
    from tpu_mx.gluon import nn
    from tpu_mx.parallel import CompiledTrainStep, make_mesh
    np.random.seed(0)
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, in_units=8, activation="relu"),
            nn.Dense(4, in_units=16))
    net.initialize(init="xavier")
    net(nd.ones((1, 8)))
    step = CompiledTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                             mx.optimizer.create("sgd", learning_rate=0.1),
                             mesh=make_mesh({"dp": 8},
                                            devices=jax.devices()))
    x = np.random.RandomState(7).rand(16, 8).astype(np.float32)
    y = np.random.RandomState(8).randint(0, 4, (16,)).astype(np.float32)
    for _ in range(3):
        loss = step.step(nd.array(x), nd.array(y))
    np.testing.assert_allclose(float(np.asarray(loss._data)),
                               losses["0"], rtol=1e-5)
