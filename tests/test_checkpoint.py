"""Durability layer (tpu_mx/checkpoint.py) under injected faults.

Every claim in docs/robustness.md has a falsifying chaos test here:
atomic commit vs crash, manifest-vs-torn-write, retention safety, retry
backoff, preemption-handler emergency save, and the kvstore persistence
satellites (ISSUE 2)."""
import json
import os
import pickle
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import tpu_mx as mx
from tpu_mx import checkpoint as ckpt, nd
from tpu_mx.base import MXNetError
from tpu_mx.contrib import chaos
from tpu_mx.gluon import nn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _dense(value=1.0):
    net = nn.Dense(3, in_units=4)
    net.initialize()
    net.weight.set_data(nd.full((3, 4), float(value)))
    return net


# ---------------------------------------------------------------------------
# atomic_write
# ---------------------------------------------------------------------------
def test_atomic_write_commits_and_leaves_no_debris(tmp_path):
    p = tmp_path / "out.bin"
    with ckpt.atomic_write(str(p)) as f:
        f.write(b"hello durable world")
    assert p.read_bytes() == b"hello durable world"
    assert [x for x in os.listdir(tmp_path) if ".tmp." in x] == []


def test_atomic_write_exception_preserves_old_content(tmp_path):
    p = tmp_path / "out.bin"
    p.write_bytes(b"OLD")
    with pytest.raises(RuntimeError):
        with ckpt.atomic_write(str(p)) as f:
            f.write(b"NEW-PARTIAL")
            raise RuntimeError("writer blew up")
    assert p.read_bytes() == b"OLD"  # destination untouched
    assert [x for x in os.listdir(tmp_path) if ".tmp." in x] == []


def test_atomic_write_text_mode(tmp_path):
    p = tmp_path / "out.json"
    with ckpt.atomic_write(str(p), "w") as f:
        f.write(json.dumps({"a": 1}))
    assert json.loads(p.read_text()) == {"a": 1}


def test_chaos_crash_leaves_old_file_and_tmp_debris(tmp_path):
    """A simulated kill mid-write must look like a real one: destination
    keeps its previous content, the partial tmp file stays on disk, and a
    later (post-restart) save over the same path succeeds."""
    p = tmp_path / "state.bin"
    p.write_bytes(b"EPOCH1" * 10)
    with chaos.enable(crash_after_bytes=16) as cfg:
        with pytest.raises(chaos.ChaosCrash):
            with ckpt.atomic_write(str(p)) as f:
                f.write(b"EPOCH2" * 100)
    assert cfg.crashes == 1
    assert p.read_bytes() == b"EPOCH1" * 10
    debris = [x for x in os.listdir(tmp_path) if ".tmp." in x]
    assert debris, "a crash leaves the partial tmp file behind"
    # recovery save (chaos disarmed) goes through cleanly
    with ckpt.atomic_write(str(p)) as f:
        f.write(b"EPOCH2" * 100)
    assert p.read_bytes() == b"EPOCH2" * 100


# ---------------------------------------------------------------------------
# manifests + verification
# ---------------------------------------------------------------------------
def test_manifest_roundtrip_verifies(tmp_path):
    prefix = str(tmp_path / "ck")
    nd.save(f"{prefix}-0001.params", {"w": nd.ones((2, 2))})
    man = ckpt.write_manifest(prefix, 1, [f"{prefix}-0001.params"])
    assert man["format"] == ckpt.MANIFEST_FORMAT
    assert "ck-0001.params" in man["files"]
    assert man["files"]["ck-0001.params"]["size"] > 0
    status, problems = ckpt.verify_checkpoint(prefix, 1)
    assert (status, problems) == ("verified", [])


def test_verify_flags_torn_file_explicitly(tmp_path):
    """The acceptance-criteria check: a torn write (disk bytes < intended
    bytes) is named file-by-file by verify_checkpoint."""
    prefix = str(tmp_path / "ck")
    with chaos.enable(torn_write=64, match=".params") as cfg:
        nd.save(f"{prefix}-0001.params", {"w": nd.ones((8, 8))})
        ckpt.write_manifest(prefix, 1, [f"{prefix}-0001.params"])
    assert cfg.tears >= 1
    assert os.path.getsize(f"{prefix}-0001.params") == 64
    status, problems = ckpt.verify_checkpoint(prefix, 1)
    assert status == "corrupt"
    assert any("ck-0001.params" in p and "torn" in p for p in problems), \
        problems


def test_verify_flags_bitrot_via_sha256(tmp_path):
    prefix = str(tmp_path / "ck")
    nd.save(f"{prefix}-0001.params", {"w": nd.ones((4, 4))})
    ckpt.write_manifest(prefix, 1, [f"{prefix}-0001.params"])
    # same-size corruption: size check passes, sha256 must catch it
    with open(f"{prefix}-0001.params", "r+b") as f:
        f.seek(10)
        f.write(b"\xff\xff\xff")
    status, problems = ckpt.verify_checkpoint(prefix, 1)
    assert status == "corrupt"
    assert any("sha256" in p for p in problems), problems


def test_verify_missing_file_and_legacy_status(tmp_path):
    prefix = str(tmp_path / "ck")
    nd.save(f"{prefix}-0001.params", {"w": nd.ones((2, 2))})
    ckpt.write_manifest(prefix, 1, [f"{prefix}-0001.params"])
    os.remove(f"{prefix}-0001.params")
    status, problems = ckpt.verify_checkpoint(prefix, 1)
    assert status == "corrupt" and any("missing" in p for p in problems)
    # manifest-less epoch with files on disk = legacy (loadable, unverified)
    nd.save(f"{prefix}-0002.params", {"w": nd.ones((2, 2))})
    assert ckpt.verify_checkpoint(prefix, 2)[0] == "legacy"
    # nothing at all = corrupt
    assert ckpt.verify_checkpoint(prefix, 3)[0] == "corrupt"


def test_unreadable_manifest_is_corrupt_not_crash(tmp_path):
    prefix = str(tmp_path / "ck")
    nd.save(f"{prefix}-0001.params", {"w": nd.ones((2, 2))})
    with open(ckpt.manifest_path(prefix, 1), "w") as f:
        f.write('{"format": "tpu_mx-manifest-v1", "files": {')  # truncated
    status, problems = ckpt.verify_checkpoint(prefix, 1)
    assert status == "corrupt" and any("unreadable" in p for p in problems)


def test_update_manifest_adds_states_file(tmp_path):
    prefix = str(tmp_path / "ck")
    nd.save(f"{prefix}-0001.params", {"w": nd.ones((2, 2))})
    ckpt.write_manifest(prefix, 1, [f"{prefix}-0001.params"])
    with ckpt.atomic_write(f"{prefix}-0001.states") as f:
        f.write(pickle.dumps({"momentum": 0.9}))
    ckpt.update_manifest(prefix, 1, [f"{prefix}-0001.states"])
    man = ckpt.read_manifest(prefix, 1)
    assert set(man["files"]) == {"ck-0001.params", "ck-0001.states"}
    assert ckpt.verify_checkpoint(prefix, 1)[0] == "verified"


# ---------------------------------------------------------------------------
# retention
# ---------------------------------------------------------------------------
def _write_epoch(prefix, epoch, value):
    nd.save(f"{prefix}-{epoch:04d}.params", {"w": nd.full((2, 2), value)})
    ckpt.write_manifest(prefix, epoch, [f"{prefix}-{epoch:04d}.params"])


def test_retention_keeps_last_k(tmp_path):
    prefix = str(tmp_path / "ck")
    for e in range(1, 6):
        _write_epoch(prefix, e, e)
    removed = ckpt.apply_retention(prefix, keep_last=2)
    assert removed == [1, 2, 3]
    assert ckpt.list_epochs(prefix) == [4, 5]
    assert ckpt.verify_checkpoint(prefix, 5)[0] == "verified"


def test_retention_never_deletes_newest_verified(tmp_path):
    """keep_last=1 with a corrupt newest epoch must still keep the newest
    VERIFIED epoch — retention can't destroy the only recovery point."""
    prefix = str(tmp_path / "ck")
    for e in (1, 2, 3):
        _write_epoch(prefix, e, e)
    # corrupt the newest epoch's params (truncate under the manifest)
    with open(f"{prefix}-0003.params", "r+b") as f:
        f.truncate(16)
    assert ckpt.verify_checkpoint(prefix, 3)[0] == "corrupt"
    removed = ckpt.apply_retention(prefix, keep_last=1)
    assert removed == [1]
    assert ckpt.list_epochs(prefix) == [2, 3]  # 2 = newest verified, kept
    assert ckpt.verify_checkpoint(prefix, 2)[0] == "verified"


def test_retention_spares_shared_symbol_json(tmp_path):
    """prefix-symbol.json is shared by every epoch: retention of old epochs
    must not delete it (the Module checkpoint layout)."""
    prefix = str(tmp_path / "net")
    sym_path = f"{prefix}-symbol.json"
    with open(sym_path, "w") as f:
        f.write("{}")
    for e in (1, 2, 3):
        nd.save(f"{prefix}-{e:04d}.params", {"w": nd.ones((2, 2))})
        ckpt.write_manifest(prefix, e,
                            [sym_path, f"{prefix}-{e:04d}.params"])
    ckpt.apply_retention(prefix, keep_last=1)
    assert os.path.exists(sym_path)
    assert ckpt.list_epochs(prefix) == [3]
    assert ckpt.verify_checkpoint(prefix, 3)[0] == "verified"


# ---------------------------------------------------------------------------
# retry
# ---------------------------------------------------------------------------
def test_retry_transient_oserror_succeeds(monkeypatch):
    sleeps = []
    monkeypatch.setattr(ckpt.time, "sleep", sleeps.append)
    calls = []
    with chaos.enable(transient_oserror=2) as cfg:
        def op():
            calls.append(1)
            chaos.maybe_oserror("probe")
            return "ok"
        assert ckpt.retry(op, attempts=4, seed=0) == "ok"
    assert len(calls) == 3 and cfg.oserrors_fired == 2
    assert len(sleeps) == 2
    # jittered exponential growth: second sleep strictly above base*2 floor
    assert sleeps[0] >= 0.05 and sleeps[1] >= 0.10


def test_retry_exhaustion_reraises(monkeypatch):
    monkeypatch.setattr(ckpt.time, "sleep", lambda s: None)
    with chaos.enable(transient_oserror=10):
        def op():
            chaos.maybe_oserror("probe")
        with pytest.raises(OSError, match="transient"):
            ckpt.retry(op, attempts=3, seed=0)


def test_retry_never_swallows_chaos_crash(monkeypatch):
    """A simulated kill is not a transient error: retry must re-raise it
    immediately instead of retrying a 'crashed' process."""
    monkeypatch.setattr(ckpt.time, "sleep", lambda s: None)
    calls = []
    def op():
        calls.append(1)
        raise chaos.ChaosCrash("dead")
    with pytest.raises(chaos.ChaosCrash):
        ckpt.retry(op, attempts=5, seed=0)
    assert len(calls) == 1


def test_retry_backoff_deterministic_under_seed(monkeypatch):
    def run():
        sleeps = []
        monkeypatch.setattr(ckpt.time, "sleep", sleeps.append)
        def op():
            if len(sleeps) < 3:
                raise OSError("flaky fs")
            return "done"
        assert ckpt.retry(op, attempts=5, seed=42) == "done"
        return sleeps
    assert run() == run()


# ---------------------------------------------------------------------------
# TPUMX_CHAOS env parsing
# ---------------------------------------------------------------------------
def test_chaos_env_config_parsing(monkeypatch):
    monkeypatch.setenv(
        "TPUMX_CHAOS", "torn_write=128,match=.params,seed=7,slow_io=0.5")
    monkeypatch.setattr(chaos, "_env_parsed", False)
    monkeypatch.setattr(chaos, "_config", None)
    cfg = chaos.configure_from_env()
    assert cfg.torn_write == 128 and cfg.match == ".params"
    assert cfg.seed == 7 and cfg.slow_io == 0.5
    assert cfg.matches("x-0001.params") and not cfg.matches("x.manifest.json")
    monkeypatch.setattr(chaos, "_config", None)  # disarm for other tests


def test_chaos_env_not_parsed_when_unset(monkeypatch):
    monkeypatch.delenv("TPUMX_CHAOS", raising=False)
    monkeypatch.setattr(chaos, "_env_parsed", False)
    monkeypatch.setattr(chaos, "_config", None)
    assert chaos.configure_from_env() is None


# ---------------------------------------------------------------------------
# module/model checkpoint path commits a manifest
# ---------------------------------------------------------------------------
def _mlp_sym():
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    return mx.sym.SoftmaxOutput(fc, mx.sym.Variable("softmax_label"),
                                name="softmax")


def test_module_checkpoint_commits_verified_manifest(tmp_path):
    prefix = str(tmp_path / "mlp")
    mod = mx.module.Module(_mlp_sym(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 10))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params(initializer=mx.init.Normal(0.1))
    mod.init_optimizer(optimizer="sgd")
    mod.save_checkpoint(prefix, 3, save_optimizer_states=True)
    status, problems = ckpt.verify_checkpoint(prefix, 3)
    assert (status, problems) == ("verified", [])
    man = ckpt.read_manifest(prefix, 3)
    # the shared, every-save-rewritten symbol.json is deliberately NOT in
    # the verified file set (it would corrupt older epochs on a symbol
    # change); its save-time hash rides the unverified "shared" table
    assert set(man["files"]) == {"mlp-0003.params", "mlp-0003.states"}
    assert man["shared"]["mlp-symbol.json"]["sha256"]
    assert man["git_head"] and man["epoch"] == 3


# ---------------------------------------------------------------------------
# orbax (CompiledTrainStep) commit marker + fallback
# ---------------------------------------------------------------------------
def _small_step():
    from tpu_mx import gluon
    from tpu_mx.parallel import CompiledTrainStep, make_mesh
    mx.random.seed(3)
    net = nn.Dense(4, in_units=8, prefix="ckstep_")
    net.initialize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    opt = mx.optimizer.create("sgd", learning_rate=0.1)
    return CompiledTrainStep(net, loss_fn, opt, mesh=make_mesh({"dp": 8}))


def test_orbax_commit_marker_and_fallback(tmp_path):
    step = _small_step()
    x = nd.array(np.random.RandomState(0).rand(8, 8).astype(np.float32))
    y = nd.array(np.arange(8, dtype=np.float32) % 4)
    step.step(x, y)
    good = str(tmp_path / "good")
    step.save_checkpoint(good)
    marker = step.commit_marker_path(good)
    assert os.path.exists(marker)
    assert json.load(open(marker))["format"] == "tpu_mx-orbax-commit-v1"

    step.step(x, y)
    uncommitted = str(tmp_path / "uncommitted")
    step.save_checkpoint(uncommitted)
    os.remove(step.commit_marker_path(uncommitted))  # simulate interruption

    fresh = _small_step()
    restored = fresh.load_checkpoint(uncommitted, fallback_paths=[good])
    assert restored == os.path.abspath(good)  # marker-less primary skipped
    assert fresh._t == 1

    with pytest.raises(MXNetError, match="no restorable checkpoint"):
        fresh.load_checkpoint(str(tmp_path / "never-existed"),
                              fallback_paths=[str(tmp_path / "also-missing")])


def test_orbax_back_to_back_async_saves_both_get_markers(tmp_path):
    """A second async save must not orphan the first save's pending commit
    marker: both checkpoints end up verified."""
    step = _small_step()
    x = nd.array(np.random.RandomState(0).rand(8, 8).astype(np.float32))
    y = nd.array(np.arange(8, dtype=np.float32) % 4)
    step.step(x, y)
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    step.save_checkpoint(a, block=False)
    step.step(x, y)
    step.save_checkpoint(b, block=False)  # no wait_for_checkpoint between
    step.wait_for_checkpoint()
    assert os.path.exists(step.commit_marker_path(a))
    assert os.path.exists(step.commit_marker_path(b))
    # each marker records the t of the state it SAVED, not stamp-time t
    assert json.load(open(step.commit_marker_path(a)))["t"] == 1
    assert json.load(open(step.commit_marker_path(b)))["t"] == 2


def test_chaos_torn_write_text_mode_byte_boundary(tmp_path):
    """Byte-count faults apply to the utf-8 ENCODING in text mode: a
    multi-byte payload tears at the configured byte offset (nearest char
    boundary at-or-before it), not at a character count."""
    p = tmp_path / "unicode.json"
    payload = "é" * 50  # 2 bytes per char: 100 bytes, 50 chars
    with chaos.enable(torn_write=25) as cfg:
        with ckpt.atomic_write(str(p), "w") as f:
            f.write(payload)
    assert cfg.tears == 1
    on_disk = p.read_bytes()
    assert len(on_disk) == 24  # 25 splits an 'é': partial byte dropped
    assert on_disk.decode("utf-8") == "é" * 12


# ---------------------------------------------------------------------------
# preemption handler
# ---------------------------------------------------------------------------
def test_preemption_handler_in_process(tmp_path):
    """SIGINT triggers exactly one emergency save; uninstall restores the
    previous handler (in-process variant: exit=False)."""
    prefix = str(tmp_path / "pre")
    net = _dense(5.0)
    saves = []
    def save():
        saves.append(1)
        mx.elastic.save_checkpoint(prefix, 9, net=net)
    h = ckpt.preemption_handler(save, signals=(signal.SIGUSR1,), exit=False)
    try:
        os.kill(os.getpid(), signal.SIGUSR1)
        time.sleep(0.05)
        # reentrancy guard: a second delivery must not save twice (after
        # the first fire the handler restores the previous disposition, so
        # exercise the guard by invoking the handler body directly)
        h._handle(signal.SIGUSR1, None)
    finally:
        h.uninstall()
    assert h.triggered and h.save_ok and saves == [1]
    assert ckpt.verify_checkpoint(prefix, 9)[0] == "verified"
    net2 = nn.Dense(3, in_units=4)
    assert mx.elastic.auto_resume(prefix, net=net2) == 10
    np.testing.assert_allclose(net2.weight.data().asnumpy(), 5.0)


@pytest.mark.slow
def test_preemption_handler_sigterm_subprocess(tmp_path):
    """The real contract: a SIGTERM'd training process writes one durable,
    resumable checkpoint on its way out (exit code 128+15)."""
    prefix = str(tmp_path / "job")
    script = tmp_path / "train.py"
    script.write_text(
        "import sys, time\n"
        "import tpu_mx as mx\n"
        "from tpu_mx import nd\n"
        "from tpu_mx.gluon import nn\n"
        f"prefix = {str(prefix)!r}\n"
        "net = nn.Dense(3, in_units=4)\n"
        "net.initialize()\n"
        "net.weight.set_data(nd.full((3, 4), 7.0))\n"
        "epoch = [4]\n"
        "h = mx.checkpoint.preemption_handler(\n"
        "    lambda: mx.elastic.save_checkpoint(prefix, epoch[0], net=net))\n"
        "print('READY', flush=True)\n"
        "time.sleep(120)  # 'training'; the driver SIGTERMs us mid-sleep\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen([sys.executable, str(script)],
                            stdout=subprocess.PIPE, text=True, env=env)
    try:
        line = proc.stdout.readline()
        assert "READY" in line, line
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
    finally:
        proc.kill()
    assert rc == 128 + signal.SIGTERM
    assert ckpt.verify_checkpoint(prefix, 4)[0] == "verified"
    net2 = nn.Dense(3, in_units=4)
    assert mx.elastic.auto_resume(prefix, net=net2) == 5
    np.testing.assert_allclose(net2.weight.data().asnumpy(), 7.0)


# ---------------------------------------------------------------------------
# kvstore persistence satellites
# ---------------------------------------------------------------------------
def test_kvstore_uninitialized_key_raises_mxnet_error():
    kv = mx.kv.create("local")
    with pytest.raises(MXNetError, match="not initialized; call kv.init"):
        kv.push("w", nd.ones((3,)))
    with pytest.raises(MXNetError, match="not initialized; call kv.init"):
        kv.pull("w", out=nd.zeros((3,)))
    kv.init("w", nd.zeros((3,)))
    kv.push("w", nd.ones((3,)))  # initialized: fine
    out = nd.zeros((3,))
    kv.pull("w", out=out)
    np.testing.assert_allclose(out.asnumpy(), 1.0)


def test_kvstore_dump_optimizer_roundtrip(tmp_path):
    fname = str(tmp_path / "opt.states")
    kv = mx.kv.create("local")
    kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.25,
                                         momentum=0.9))
    kv.init("w", nd.zeros((4,)))
    kv.push("w", nd.ones((4,)))
    kv.save_optimizer_states(fname, dump_optimizer=True)
    # a FRESH kvstore with no optimizer set restores both states and the
    # optimizer object (the reference's PS-server pickle contract)
    kv2 = mx.kv.create("local")
    kv2.load_optimizer_states(fname)
    assert kv2._optimizer is not None
    assert kv2._optimizer.lr == 0.25 and kv2._optimizer.momentum == 0.9
    assert kv2._updater is not None
    assert set(kv2._updater.get_states()) == set(kv._updater.get_states())


def test_kvstore_states_without_optimizer_stays_legacy_format(tmp_path):
    fname = str(tmp_path / "opt.states")
    kv = mx.kv.create("local")
    kv.set_optimizer(mx.optimizer.create("sgd"))
    kv.init("w", nd.zeros((4,)))
    kv.push("w", nd.ones((4,)))
    kv.save_optimizer_states(fname)  # dump_optimizer=False (default)
    with open(fname, "rb") as f:
        payload = pickle.load(f)
    assert "__tpumx_format__" not in payload  # bare states dict, as before
    kv2 = mx.kv.create("local")
    kv2.set_optimizer(mx.optimizer.create("sgd"))
    kv2.load_optimizer_states(fname)
    assert set(kv2._updater.get_states()) == set(kv._updater.get_states())


# ---------------------------------------------------------------------------
# shared symbol.json vs per-epoch manifests (the parked ROADMAP bug)
# ---------------------------------------------------------------------------
def _module_symbol(extra_layer=False):
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=3, name="fc1")
    if extra_layer:
        fc = mx.sym.Activation(fc, act_type="relu", name="relu1")
    return fc


def test_symbol_rewrite_keeps_older_epochs_verified(tmp_path):
    """`{prefix}-symbol.json` is rewritten by EVERY model.save_checkpoint;
    listing it in per-epoch manifests made a later save with a changed
    symbol flip every older epoch to "corrupt", defeating the
    fall-back-to-older-epoch contract.  It is excluded now (its content
    hash rides the manifest's unverified "shared" table instead)."""
    prefix = str(tmp_path / "m")
    arg = {"fc1_weight": nd.ones((3, 4)), "fc1_bias": nd.zeros((3,))}
    mx.model.save_checkpoint(prefix, 0, _module_symbol(), arg, {})
    mx.model.save_checkpoint(prefix, 1, _module_symbol(), arg, {})
    man = ckpt.read_manifest(prefix, 0)
    assert "m-symbol.json" not in man["files"]
    assert man["shared"]["m-symbol.json"]["sha256"]

    # the symbol CHANGES (a new layer): older epochs must stay verified
    mx.model.save_checkpoint(prefix, 2, _module_symbol(extra_layer=True),
                             arg, {})
    for epoch in (0, 1, 2):
        assert ckpt.verify_checkpoint(prefix, epoch)[0] == "verified", epoch

    # torn-fallback proof: corrupt the newest epoch's params; the elastic
    # path must fall back to epoch 1 — which a symbol-bearing manifest
    # would have declared corrupt too, leaving nothing to resume from
    with open(f"{prefix}-0002.params", "r+b") as f:
        f.truncate(10)
    assert ckpt.verify_checkpoint(prefix, 2)[0] == "corrupt"
    assert mx.elastic.latest_checkpoint(prefix)[0] == 1
    sym, arg2, aux2 = mx.model.load_checkpoint(prefix, 1)
    np.testing.assert_allclose(arg2["fc1_weight"].asnumpy(), 1.0)


def test_module_save_checkpoint_states_ride_manifest_after_symbol_fix(
        tmp_path):
    """Module.save_checkpoint(save_optimizer_states=True) still folds the
    .states file into the (symbol-less) manifest."""
    from tpu_mx.io.io import DataBatch
    prefix = str(tmp_path / "mod")
    sym = mx.sym.SoftmaxOutput(_module_symbol(),
                               mx.sym.Variable("softmax_label"))
    mod = mx.module.Module(sym, context=[mx.cpu()])
    mod.bind(data_shapes=[("data", (2, 4))],
             label_shapes=[("softmax_label", (2,))])
    mod.init_params()
    mod.init_optimizer()
    mod.forward_backward(DataBatch(data=[nd.ones((2, 4))],
                                   label=[nd.zeros((2,))]))
    mod.update()
    mod.save_checkpoint(prefix, 3, save_optimizer_states=True)
    man = ckpt.read_manifest(prefix, 3)
    assert set(man["files"]) == {"mod-0003.params", "mod-0003.states"}
    assert "mod-symbol.json" not in man["files"]
    assert ckpt.verify_checkpoint(prefix, 3)[0] == "verified"
