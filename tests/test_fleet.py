"""Elastic fleet membership (tpu_mx/parallel/fleet.py, ISSUE 17): the
membership-epoch protocol, exact-replay resharding of the data stream,
generation-tagged barriers, the chaos preempt/partition knobs, and — in the
slow tier — the cross-process kill-and-rejoin proof driven through
``tools/launch.py --supervise`` (docs/robustness.md "Elastic fleets")."""
import importlib
import json
import os
import pickle
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import tpu_mx as mx
from tpu_mx import checkpoint as ckpt, elastic, nd, resume, supervisor
from tpu_mx import gluon, telemetry
from tpu_mx.base import MXNetError
from tpu_mx.contrib import chaos
from tpu_mx.gluon import nn
from tpu_mx.io import NDArrayIter
from tpu_mx.parallel import fleet as fleet_mod
from tpu_mx.parallel.fleet import Fleet, MembershipChange

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cval(name, **labels):
    m = telemetry.get(name, **labels)
    return 0 if m is None else m.value


def _import_launch():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        return importlib.import_module("launch")
    finally:
        sys.path.pop(0)


# ---------------------------------------------------------------------------
# the membership-epoch protocol
# ---------------------------------------------------------------------------
def test_membership_epoch_lifecycle(tmp_path):
    """Launch -> lose a worker (lease expiry) -> quiesce -> reshard ->
    rejoin at the NEXT epoch: the whole protocol on one store."""
    root = tmp_path / "fleet"
    f0 = Fleet(root, member=0, controller=True, lease=0.2)
    assert f0.generation == 0 and f0.world() == []

    ep = f0.advance(world=[0, 1], reason="launch")
    assert ep["generation"] == 1 and ep["world"] == [0, 1]
    # optimistic admission: worker 1 has not booted yet, but it is
    # PENDING (no record at all), never "lost" — the lease judges only
    # members that have joined at least once
    assert f0.lost() == []

    f0.join()
    assert f0.acked_generation == 1 and f0.shard() == (0, 2)
    f1 = Fleet(root, member=1, lease=0.2)
    f1.join()
    assert f1.shard() == (1, 2)
    assert sorted(f0.live()) == [0, 1]

    # worker 1 goes silent; its lease expires; the controller evicts it
    time.sleep(0.3)
    f0.heartbeat()
    assert f0.lost() == [1]
    ep = f0.reconcile()
    assert ep["generation"] == 2 and ep["world"] == [0]

    # worker 0 notices at the next step boundary and quiesces
    with pytest.raises(MembershipChange) as ei:
        f0.check()
    assert ei.value.generation == 2 and ei.value.world_size == 1
    assert isinstance(ei.value, elastic.WorkerFailure)  # classify seam
    f0.ack()
    assert f0.shard() == (0, 1)
    f0.check()  # adopted: quiet again

    # worker 1 comes back: pending until the controller opens the NEXT
    # epoch (late joiners are admitted only at an epoch bump)
    f1.join()
    assert f1.acked_generation == 1  # still holds its stale epoch
    assert f0.joiners() == [1]
    ep = f0.reconcile()
    assert ep["generation"] == 3 and ep["world"] == [0, 1]
    assert ep["reason"] == "rejoin"
    f1.await_admission(timeout=5)
    assert f1.acked_generation == 3 and f1.shard() == (1, 2)

    # and worker 0 quiesces/reshards once more for the scale-up
    with pytest.raises(MembershipChange):
        f0.on_step()
    f0.ack()
    assert f0.shard() == (0, 2)


def test_fleet_handle_misuse_raises(tmp_path):
    f = Fleet(tmp_path / "f", controller=True)
    with pytest.raises(ValueError):
        f.join()  # no member slot
    with pytest.raises(elastic.WorkerFailure):
        f.ack()  # no epoch on disk yet
    w = Fleet(tmp_path / "f", member=3)
    w.join()
    with pytest.raises(elastic.WorkerFailure):
        w.shard()  # never admitted


def test_fleet_from_env(tmp_path):
    env = {fleet_mod.ENV_DIR: str(tmp_path / "fl"),
           fleet_mod.ENV_MEMBER: "2", fleet_mod.ENV_LEASE: "3.5"}
    f = Fleet.from_env(env)
    assert (f.member, f.lease) == (2, 3.5)
    assert Fleet.from_env({}) is None  # static-world processes


def test_leave_is_pending_not_lost(tmp_path):
    """A clean leaver withdraws its record; with no record it is pending,
    so the controller's reconcile does not burn an epoch evicting a
    worker that already said goodbye."""
    root = tmp_path / "f"
    f0 = Fleet(root, member=0, controller=True, lease=0.2)
    f0.advance(world=[0, 1])
    f0.join()
    f1 = Fleet(root, member=1, lease=0.2)
    f1.join()
    f1.leave()
    time.sleep(0.25)
    f0.heartbeat()
    assert f0.lost() == []
    assert f0.reconcile() is None  # membership unchanged


# ---------------------------------------------------------------------------
# satellite: generation-tagged barriers — zombies raise, never wedge
# ---------------------------------------------------------------------------
def test_barrier_stale_generation_raises_loudly(tmp_path):
    f = Fleet(tmp_path / "f", member=0, controller=True, lease=5.0)
    f.advance(world=[0], reason="launch")
    f.join()
    assert f.barrier_tag("grads") == "grads@1"
    elastic.barrier("grads", fleet=f)  # generations match: no-op, no raise

    f.advance(world=[0, 1], reason="scale-up")  # epoch moves under us
    with pytest.raises(elastic.WorkerFailure,
                       match="stale fleet generation 1"):
        elastic.barrier("grads", fleet=f)  # detected BEFORE the collective
    f.ack()
    assert f.barrier_tag("grads") == "grads@2"
    elastic.barrier("grads", fleet=f)


# ---------------------------------------------------------------------------
# exact-replay resharding of the data stream (io.NDArrayIter)
# ---------------------------------------------------------------------------
_X = np.arange(64, dtype=np.float32).reshape(64, 1)


def _iter(num_workers=1, rank=0, seed=5):
    return NDArrayIter(_X, batch_size=8, shuffle=True, seed=seed,
                       last_batch_handle="discard",
                       num_workers=num_workers, rank=rank)


def _gids(it):
    return [int(v) for v in it.global_batch_ids()]


def _mine(it):
    return [int(v) for v in it.getdata()[0].asnumpy().ravel()]


def _advance(it):
    if not it.iter_next():
        it.reset()
        assert it.iter_next()


def test_shards_compose_to_the_global_stream():
    """Every rank of a 2-world slices the SAME global selection the
    1-world consumes: concat of the rank slices == the oracle batch."""
    oracle = _iter()
    r0, r1 = _iter(2, 0), _iter(2, 1)
    assert r0.batch_size == 4  # batch_size is always the GLOBAL batch
    for _ in range(16):  # two epochs: reset parity rides the private RNG
        for it in (oracle, r0, r1):
            _advance(it)
        ref = _gids(oracle)
        assert _gids(r0) == ref and _gids(r1) == ref
        assert _mine(r0) + _mine(r1) == ref
        assert _mine(oracle) == ref


def test_set_shard_mid_epoch_continues_global_sequence():
    """The live 2->1->2 re-partition: only the local slice changes, the
    global cursor/permutation/RNG never move — the exact-replay
    invariant a membership change relies on."""
    oracle = _iter()
    it = _iter(2, 0)
    seq, ref = [], []
    for step in range(12):
        if step == 3:
            it.set_shard(0, 1)   # lost the peer: consume alone
        if step == 7:
            it.set_shard(1, 2)   # peer rejoined; we even switch rank
        _advance(it)
        _advance(oracle)
        seq.append(_gids(it))
        ref.append(_gids(oracle))
    assert seq == ref
    with pytest.raises(MXNetError, match="not\\s+divisible"):
        it.set_shard(0, 3)  # 8 % 3 != 0 — replay boundaries would shift


def test_state_v2_repartitions_across_worlds():
    """A v2 (sharded) state restores into ANY world at the same global
    batch — the capsule-driven N->M replay path."""
    src = _iter(2, 0)
    for _ in range(3):
        _advance(src)
    state = src.state_dict()
    assert state["version"] == 2
    assert state["shard"] == {"num_workers": 2, "rank": 0, "global_batch": 8}

    expect = []
    for _ in range(4):
        _advance(src)
        expect.append(_gids(src))

    for nw, rank in ((1, 0), (2, 1), (4, 3)):
        it = _iter(nw, rank)
        it.load_state_dict(state)  # keeps ITS OWN (rank, num_workers)
        got = []
        for _ in range(4):
            _advance(it)
            got.append(_gids(it))
            lb = 8 // nw
            assert _mine(it) == got[-1][rank * lb:(rank + 1) * lb]
        assert got == expect

    # captured at a different global batch: refused, not guessed
    other = NDArrayIter(_X, batch_size=16, shuffle=True, seed=5,
                        num_workers=2, rank=0,
                        last_batch_handle="discard")
    with pytest.raises(MXNetError, match="global batch"):
        other.load_state_dict(state)


def test_state_v1_into_sharded_iterator_refuses():
    """A v1 state has no shard map — it may be a per-worker LOCAL stream,
    so a sharded iterator refuses it; the blessed path (load unsharded,
    then set_shard) replays exactly."""
    src = _iter()
    for _ in range(2):
        _advance(src)
    state = src.state_dict()
    assert state["version"] == 1 and "shard" not in state

    with pytest.raises(MXNetError, match="v1 iterator state"):
        _iter(2, 0).load_state_dict(state)

    blessed = _iter()
    blessed.load_state_dict(state)  # unsharded: v1 means what it said
    blessed.set_shard(1, 2)
    _advance(src)
    _advance(blessed)
    assert _gids(blessed) == _gids(src)
    assert _mine(blessed) == _gids(src)[4:]


# ---------------------------------------------------------------------------
# capsules: v2 world map, v1 same-world compatibility + surfaced gap
# ---------------------------------------------------------------------------
def test_capsule_v2_records_the_world(tmp_path):
    it = _iter(2, 0)
    mgr = resume.CapsuleManager(str(tmp_path / "run"), iters=[it])
    cap = resume.read_capsule(mgr.write_epoch_file(3))
    assert cap["format"] == resume.CAPSULE_FORMAT
    assert cap["world"] == {"num_workers": 2, "rank": 0, "generation": 0}

    # fleet-attached capture records the ADOPTED epoch's coordinates
    f = Fleet(tmp_path / "fl", member=1, controller=True, lease=5.0)
    f.advance(world=[0, 1])
    f.join()
    mgr = resume.CapsuleManager(str(tmp_path / "run2"), iters=[it], fleet=f)
    cap = resume.read_capsule(mgr.write_epoch_file(0))
    assert cap["world"] == {"num_workers": 2, "rank": 1, "generation": 1}


def test_capsule_v1_epoch_restores_same_world(tmp_path):
    """Acceptance: pre-fleet capsule v1 files still restore on the
    unsharded (same-world) path — their fields mean what they always
    meant."""
    prefix = str(tmp_path / "run")
    src = _iter()
    for _ in range(3):
        _advance(src)
    mgr = resume.CapsuleManager(prefix, iters=[src])
    path = mgr.write_epoch_file(2)
    cap = json.loads(open(path).read())
    cap["format"] = resume.CAPSULE_FORMAT_V1
    cap.pop("world")
    with open(path, "w") as fh:
        fh.write(json.dumps(cap))

    dst = _iter()
    mgr2 = resume.CapsuleManager(prefix, iters=[dst])
    assert mgr2.restore(sup=None, resume_from=3) == 3
    assert telemetry.gauge("resume.resume_step_gap").value == 0
    _advance(src)
    _advance(dst)
    assert _gids(dst) == _gids(src)


def test_capsule_v1_step_under_sharded_world_surfaces_gap(tmp_path):
    """A v1 STEP capsule under a sharded pipeline cannot be
    re-partitioned: refused, and the unreplayable batches are SURFACED
    (resume.resume_step_gap), never guessed."""
    prefix = str(tmp_path / "run")
    it = _iter(2, 0)
    body = {"format": resume.CAPSULE_FORMAT_V1, "epoch": 0, "step": 3,
            "wall_time": 0.0,
            "rng": resume.encode_state(mx.random.get_state()),
            "iters": [resume.encode_state(it.state_dict())]}
    with open(resume.step_capsule_path(prefix), "w") as fh:
        fh.write(json.dumps(body))

    mgr = resume.CapsuleManager(prefix, iters=[it])
    assert mgr.restore(sup=None, resume_from=0) == 0
    assert telemetry.gauge("resume.resume_step_gap").value == 3


# ---------------------------------------------------------------------------
# satellite bugfix: kvstore world-size cache follows the membership epoch
# ---------------------------------------------------------------------------
def test_kvstore_cache_invalidated_on_generation_bump(tmp_path):
    kv = mx.kvstore.create("dist_sync")
    assert kv.num_workers == 1  # static single-process world
    f = Fleet(tmp_path / "fl", member=0, controller=True, lease=5.0)
    try:
        f.advance(world=[0, 1, 2, 3])
        f.join()  # bumps the process-global generation token
        assert kv.num_workers == 4  # cache re-read, fleet is authority
        f.advance(world=[0, 1])
        f.ack()
        assert kv.num_workers == 2
    finally:
        # drop the process-global fleet observation so later tests see a
        # static world again
        fleet_mod._live_world = None
        kv2 = mx.kvstore.create("dist_sync")
        assert kv2.num_workers == 1


# ---------------------------------------------------------------------------
# satellite: chaos knobs — preempt_worker_at_step / partition_worker
# ---------------------------------------------------------------------------
def test_chaos_partition_suppresses_heartbeats(tmp_path):
    f = Fleet(tmp_path / "f", member=1, controller=True, lease=5.0)
    f.advance(world=[1])
    f.join()
    beat0 = f.members()[1]["beat"]
    before = _cval("chaos.injections", kind="partition_worker")
    with chaos.enable(partition_worker=1) as cfg:
        assert chaos.partitioned(1) is True
        assert chaos.partitioned(0) is False
        assert chaos.partitioned(None) is False
        f.heartbeat()  # silently dropped — the ABSENCE is the fault
        f.heartbeat()
        assert f.members()[1]["beat"] == beat0
        assert cfg.partitions >= 3
        # counted once in injections{kind}, on the first suppressed beat
        assert _cval("chaos.injections",
                     kind="partition_worker") == before + 1
    assert chaos.partitioned(1) is False  # disarmed with the config
    f.heartbeat()
    assert f.members()[1]["beat"] == beat0 + 1


def test_chaos_preempt_sends_real_sigterm():
    fired = []
    prev = signal.signal(signal.SIGTERM, lambda s, _f: fired.append(s))
    try:
        before = _cval("chaos.injections", kind="preempt_worker")
        with chaos.enable(preempt_worker_at_step=3, preempt_rank=2) as cfg:
            chaos.maybe_preempt(2)
            chaos.maybe_preempt(0)  # other ranks don't advance the count
            chaos.maybe_preempt(2)
            assert not fired and cfg.fleet_steps_seen == 2
            chaos.maybe_preempt(2)  # rank 2's third step: SIGTERM
            time.sleep(0.05)
            assert fired == [signal.SIGTERM]
            assert cfg.preempts == 1
            assert _cval("chaos.injections",
                         kind="preempt_worker") == before + 1
            chaos.maybe_preempt(2)  # one-shot: the restart survives
            assert len(fired) == 1
    finally:
        signal.signal(signal.SIGTERM, prev)


# ---------------------------------------------------------------------------
# supervisor classification: WorkerFailure + moved epoch == membership
# ---------------------------------------------------------------------------
def test_supervisor_classifies_membership_not_fault(tmp_path):
    """A peer dies MID-COLLECTIVE: the step raises a plain WorkerFailure
    (barrier timeout), the lease expires, and the supervisor classifies
    it as a membership event — reshard via restore_fn under the NEW
    world, no restart budget burned (max_restarts=0 proves it)."""
    root = tmp_path / "fleet"
    f0 = Fleet(root, member=0, controller=True, lease=0.15)
    f0.advance(world=[0, 1], reason="launch")
    f0.join()
    f1 = Fleet(root, member=1, lease=0.15)
    f1.join()  # ...and never beats again: the dead peer

    reshards0 = _cval("fleet.reshards")
    restore_worlds = []

    def restore_fn():
        # ack() ran BEFORE restore: the new world is already visible,
        # so the mesh rebuild / load_state_dict reshard happens here
        restore_worlds.append(f0.acked_world_size)
        return 0

    state = {"attempt": 0}

    def one_step():
        state["attempt"] += 1
        if state["attempt"] == 1:
            time.sleep(0.4)  # the peer's lease expires mid-collective
            f0.heartbeat()   # WE are alive — only the peer went silent
            raise elastic.WorkerFailure(
                "barrier 'grads@1' timed out after 0.4s: a worker is "
                "dead or hung")
        return 0.25

    sup = supervisor.Supervisor(None, restore_fn, fleet=f0,
                                max_restarts=0, resume=False, backoff=0.0)

    def epoch_fn(_epoch):
        for _ in range(2):
            sup.step(one_step)

    res = sup.run(epoch_fn, num_epoch=1)
    assert res.status == "completed"
    assert res.restarts == 0          # membership != fault: no budget burn
    assert restore_worlds == [1]
    assert f0.acked_generation == 2 and f0.acked_world_size == 1
    assert _cval("fleet.reshards") == reshards0 + 1


# ---------------------------------------------------------------------------
# reshard seam: dp=2 -> dp=1 -> dp=2 round-trip is bit-exact
# ---------------------------------------------------------------------------
def test_reshard_live_roundtrip_bit_exact():
    """Acceptance: weights AND optimizer state are bit-exact once back on
    the original mesh — the no-train reshard round-trip moves arrays
    between meshes without touching a single mantissa bit."""
    import jax
    from tpu_mx.parallel import CompiledTrainStep, make_mesh

    def build():
        mx.random.seed(123)
        net = nn.HybridSequential(prefix="fl_")
        net.add(nn.Dense(8, in_units=4, activation="relu", prefix="fc1_"))
        net.add(nn.Dense(2, in_units=8, prefix="fc2_"))
        net.initialize()
        net(nd.ones((1, 4)))
        return net

    def make_step(world):
        mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2]) \
            if world == 2 else make_mesh({"dp": 1},
                                         devices=jax.devices()[:1])
        opt = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9)
        return CompiledTrainStep(net=build(),
                                 loss_fn=gluon.loss.SoftmaxCrossEntropyLoss(),
                                 optimizer=opt, mesh=mesh)

    rng = np.random.RandomState(7)
    x = nd.array(rng.rand(8, 4).astype(np.float32))
    y = nd.array(rng.randint(0, 2, (8,)).astype(np.float32))
    step2 = make_step(2)
    for _ in range(3):
        step2.step(x, y)  # momentum buffers move off zero
    ref = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(np.asarray, step2.state_dict()))

    reshards0 = _cval("fleet.reshards")
    step1 = fleet_mod.reshard_live(step2, lambda: make_step(1),
                                   from_world=2, to_world=1)
    back = fleet_mod.reshard_live(step1, lambda: make_step(2),
                                  from_world=1, to_world=2)
    assert _cval("fleet.reshards") == reshards0 + 2

    got = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(np.asarray, back.state_dict()))
    assert len(got) == len(ref)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)  # BIT-exact, optimizer included


# ---------------------------------------------------------------------------
# launcher pieces (pure)
# ---------------------------------------------------------------------------
def test_restart_backoff_jitter_bounds():
    import random as _random
    launch = _import_launch()
    rng = _random.Random(0)
    for attempt in range(1, 5):
        lo = 0.5 * 2 ** (attempt - 1) * 0.5
        hi = 0.5 * 2 ** (attempt - 1) * 1.5
        for _ in range(20):
            v = launch.restart_backoff(0.5, attempt, rng)
            assert lo <= v < hi


# ---------------------------------------------------------------------------
# slow tier: the cross-process kill-and-rejoin proof
# ---------------------------------------------------------------------------
_WORKER = textwrap.dedent("""
    import json, os, pickle, sys, time
    sys.path.insert(0, os.environ["TPUMX_REPO"])
    root = os.environ["TPUMX_TEST_ROOT"]
    member = int(os.environ["TPUMX_FLEET_MEMBER"])
    with open(os.path.join(root, f"started-{member}.log"), "a") as fh:
        fh.write(str(os.getpid()) + "\\n")

    # The CPU backend cannot run cross-process collectives, so this proof
    # exercises the fleet protocol (files) and the data stream (pure
    # function of the seed) WITHOUT jax.distributed: drop the coordinator
    # env before the tpu_mx import boots it.  That also keeps XLA's
    # preemption notifier from swallowing the chaos SIGTERM — default
    # SIGTERM disposition is the preemption being simulated.
    for k in ("TPUMX_COORDINATOR", "TPUMX_NUM_PROC", "TPUMX_PROC_ID"):
        os.environ.pop(k, None)

    import numpy as np
    from tpu_mx import checkpoint as ckpt
    from tpu_mx.io import NDArrayIter
    from tpu_mx.elastic import WorkerFailure
    from tpu_mx.parallel.fleet import Fleet, MembershipChange

    f = Fleet.from_env()
    f.join()
    f.await_admission(timeout=60)
    sync = time.monotonic() + 10  # don't step before the cohort is up —
    for m in f.world():           # but a peer that already finished and
        if m == f.member:         # left is not worth dying over, and the
            continue              # wait must not starve OUR OWN lease
        while m not in f.live() and time.monotonic() < sync:
            f.heartbeat()
            time.sleep(0.05)
    r, w = f.shard()

    GBS = 8
    X = np.arange(64, dtype=np.float32).reshape(64, 1)
    it = NDArrayIter(X, batch_size=GBS, shuffle=True, seed=5,
                     last_batch_handle="discard")
    spath = os.path.join(root, "stream.pkl")
    step = 0
    if os.path.exists(spath):      # restarted worker: adopt the published
        with open(spath, "rb") as fh:          # GLOBAL cursor (v2 state)
            pub = pickle.load(fh)
        it.load_state_dict(pub["state"])
        step = pub["step"]
    it.set_shard(r, w)

    # every incarnation consumes at least 8 batches past where it came in;
    # rank 0 additionally runs until it has lived the WHOLE churn story:
    # the rejoin epoch (generation >= 3) plus 3 batches back at full world
    target = max(16, step + 8)
    post_rejoin = 0
    led = open(os.path.join(root, f"ledger-{member}-{os.getpid()}.jsonl"),
               "a", buffering=1)
    pace = 0.25 if member == 0 else 0.05
    deadline = time.monotonic() + 90
    while time.monotonic() < deadline:
        try:
            f.on_step()
        except MembershipChange:
            f.ack()
            try:
                r, w = f.shard()
            except WorkerFailure:
                # evicted while quiesced (a pause outlived the lease):
                # rejoin at the next epoch instead of dying, and re-adopt
                # the published global cursor we fell behind on
                f.join()
                f.await_admission(timeout=60)
                r, w = f.shard()
                if os.path.exists(spath):
                    with open(spath, "rb") as fh:
                        pub = pickle.load(fh)
                    it.load_state_dict(pub["state"])
                    step = pub["step"]
            it.set_shard(r, w)
            led.write(json.dumps({"membership": True, "step": step,
                                  "gen": f.acked_generation,
                                  "world": w}) + "\\n")
            continue
        if member == 0:
            if step >= target and f.acked_generation >= 3 \
                    and post_rejoin >= 3:
                break
            if step >= 48:   # hard cap: let the assertions explain
                break
        elif step >= target:
            break
        if not it.iter_next():
            it.reset()
            assert it.iter_next()
        step += 1
        if member == 0 and f.acked_generation >= 3:
            post_rejoin += 1
        led.write(json.dumps(
            {"step": step, "gen": f.acked_generation, "rank": r,
             "world": w,
             "gids": [int(v) for v in it.global_batch_ids()],
             "mine": [int(v) for v in
                      it.getdata()[0].asnumpy().ravel()]}) + "\\n")
        if r == 0:  # publish the global stream for late joiners
            with ckpt.atomic_write(spath, mode="wb") as fh:
                pickle.dump({"step": step, "state": it.state_dict()}, fh)
        time.sleep(pace)
    f.leave()
    led.close()
""")


def _oracle_ids(steps=64):
    it = _iter()
    out = {}
    for s in range(1, steps + 1):
        _advance(it)
        out[s] = _gids(it)
    return out


def _read_jsonl(path):
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def _sub_env(extra=None):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
                "TPUMX_REPO": REPO})
    env.update(extra or {})
    return env


@pytest.mark.slow
def test_supervised_fleet_kill_and_rejoin(tmp_path):
    """End-to-end churn under ``tools/launch.py --supervise``: chaos
    SIGTERMs rank 1 mid-run, the launcher evicts it (dp=2 -> dp=1),
    restarts it with the chaos knob stripped, admits it at the next epoch
    (dp=1 -> dp=2) — and every rank's sample-id ledger is IDENTICAL to an
    uninterrupted run's, with zero skipped or duplicated samples."""
    root = tmp_path / "run"
    root.mkdir()
    fdir = str(tmp_path / "fleet")
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER)

    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "--supervise", "-n", "2", "--fleet-dir", fdir,
         "--max-restarts", "2", "--backoff", "3.0", "--lease", "2.0",
         "--join-timeout", "60",
         "--env", f"TPUMX_TEST_ROOT={root}",
         "--env", "TPUMX_CHAOS=preempt_worker_at_step=3,preempt_rank=1",
         sys.executable, str(worker)],
        env=_sub_env(), capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"

    # rank 1 really was SIGTERMed and restarted (two incarnations)
    pids1 = (root / "started-1.log").read_text().split()
    assert len(pids1) == 2, r.stderr
    assert len((root / "started-0.log").read_text().split()) == 1

    oracle = _oracle_ids()

    # rank 0's ledger: the uninterrupted global sequence, despite living
    # through dp=2 -> dp=1 -> dp=2 — no step skipped, none repeated
    rows0 = []
    for p in root.glob("ledger-0-*.jsonl"):
        rows0 += _read_jsonl(p)
    steps0 = sorted((row for row in rows0 if "gids" in row),
                    key=lambda row: row["step"])
    hi = steps0[-1]["step"]
    assert [row["step"] for row in steps0] == list(range(1, hi + 1))
    assert hi >= 16
    for row in steps0:
        assert row["gids"] == oracle[row["step"]]
    # zero skipped/duplicated samples in every full 64-sample epoch window
    for lo in range(1, hi - 6, 8):
        window = sum((oracle[s] for s in range(lo, lo + 8)), [])
        assert sorted(window) == list(range(64))
    worlds = [row["world"] for row in steps0]
    assert worlds[0] == 2, r.stderr      # launched at dp=2
    assert 1 in worlds, r.stderr         # consumed alone after the evict
    assert worlds[-1] == 2, r.stderr     # back at dp=2 after the rejoin
    memberships = [row for row in rows0 if row.get("membership")]
    assert len(memberships) >= 2  # the eviction AND the rejoin epochs
    assert memberships[-1]["gen"] >= 3

    # rank 1's SECOND incarnation: admitted at generation >= 3, resumed
    # from the published GLOBAL cursor, sliced the identical stream
    second = _read_jsonl(root / f"ledger-1-{pids1[1]}.jsonl")
    resumed = [row for row in second if "gids" in row]
    assert len(resumed) >= 4, "restarted worker barely consumed"
    for row in resumed:
        assert row["gen"] >= 3 and row["world"] == 2 and row["rank"] == 1
        assert row["gids"] == oracle[row["step"]]
        assert row["mine"] == oracle[row["step"]][4:]

    # the fleet store converged back to the full world
    gen = json.loads(open(os.path.join(fdir, "gen.json")).read())
    assert gen["world"] == [0, 1] and gen["generation"] >= 3


_BUDGET_WORKER = textwrap.dedent("""
    import json, os, sys, time
    member = int(os.environ["TPUMX_FLEET_MEMBER"])
    root = os.environ["TPUMX_TEST_ROOT"]
    with open(os.path.join(root, f"started-{member}.log"), "a") as fh:
        fh.write(str(os.getpid()) + "\\n")
    if member == 1:
        sys.exit(3)  # hopeless: dies before it ever joins

    sys.path.insert(0, os.environ["TPUMX_REPO"])
    for k in ("TPUMX_COORDINATOR", "TPUMX_NUM_PROC", "TPUMX_PROC_ID"):
        os.environ.pop(k, None)  # no collectives: see the churn worker
    from tpu_mx import checkpoint as ckpt
    from tpu_mx.parallel.fleet import Fleet, MembershipChange

    f = Fleet.from_env()
    f.join()
    f.await_admission(timeout=30)
    end = time.monotonic() + 2.0
    while time.monotonic() < end:
        try:
            f.on_step()
        except MembershipChange:
            f.ack()
        time.sleep(0.1)
    # the surviving world still commits durable work after the degrade
    with ckpt.atomic_write(os.path.join(root, "final-save.json"),
                           mode="w") as fh:
        fh.write(json.dumps({"world": sorted(f.world()),
                             "generation": f.acked_generation}))
    f.leave()
""")


@pytest.mark.slow
def test_supervised_restart_budget_degrades(tmp_path):
    """Restart-budget exhaustion: the launcher stops restarting the
    hopeless worker, dumps the black box, lets the healthy world finish
    its durable save — and the job still exits nonzero (a degraded run
    is not a clean one)."""
    root = tmp_path / "run"
    root.mkdir()
    fdir = str(tmp_path / "fleet")
    worker = tmp_path / "worker.py"
    worker.write_text(_BUDGET_WORKER)

    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "--supervise", "-n", "2", "--fleet-dir", fdir,
         "--max-restarts", "1", "--backoff", "0.05", "--lease", "10",
         "--join-timeout", "5", "--min-workers", "1",
         "--env", f"TPUMX_TEST_ROOT={root}",
         sys.executable, str(worker)],
        env=_sub_env(), capture_output=True, text=True, timeout=180)
    assert r.returncode == 1, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "restart budget exhausted" in r.stderr

    # exactly max_restarts + 1 incarnations of the hopeless worker
    assert len((root / "started-1.log").read_text().split()) == 2
    # the degrade dumped the flight recorder next to the fleet store
    assert list(__import__("pathlib").Path(fdir).glob("*blackbox*.json"))
    # the healthy world finished and saved durably
    final = json.loads((root / "final-save.json").read_text())
    assert final["world"] == [0]
    gen = json.loads(open(os.path.join(fdir, "gen.json")).read())
    assert gen["world"] == [0]
