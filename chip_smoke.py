"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the training path through the entry points a user
calls, at BERT-base's full width on random seeded weights, then gives
serving's device arm a short leg:

  A     seq 128, batch 64: the XLA dense attention arm, 10 LAMB steps.
  mesh  with >= 4 devices: phase A's batch over make_mesh({"dp": 4}) and
        over {"dp": 2, "tp": 2}; the dp=4 step-0 loss must match one chip.
  B     seq 512, batch 32, remat, TPUMX_ATTENTION=flash: the Pallas flash
        kernel on the normal path.
  C     Server(TinyLM) on the fused paged-decode arm (a SMALL model: 2
        layers, 512 wide — the only serving model there is), its tokens
        compared with the XLA twin of the paged kernel on the same device.
  D     a toy causal decoder (models/decoder.py CausalLM: latent attention
        with heads of 64; then two head-gated grouped-query layers over 2
        key/value heads: 4 query heads with a window of 128, narrower than
        the kernel's key block, and 6 that see the whole past and turn half
        of each head by YaRN's frequencies; dropless experts, 2 of 8 held,
        multi-token head; seq 512),
        TPUMX_ATTENTION=flash, 10 AdamW steps: the flash kernel's causal
        path, its window arm and its dk/dv group sum, and XLA:TPU's grouped
        product outside the benchmark.  Every loss finite, the loss
        falling, no row dropped, the window's blocks counted.  Then the
        flash arm alone against the dense arm at the benchmark's two window
        shapes (W 512 at T 8,192, W 4,096 at T 16,384): the band's index
        maps on Mosaic (ISSUE 35), and at heads of 128 under a window, of
        256 and of 64: the forward's lane-replicated row statistics and
        their repeat over the key block and the head (ISSUE 38).  Last, two
        steps of the same decoder under the profiler, from a program
        compiled past the persistent cache: the three kernels stand in the
        device's op paths under the names the program gives them
        (`flash.fwd`, forward and recomputed, `flash.dq`, `flash.dkv`;
        ISSUE 36), each missing one named.

    python chip_smoke.py              # needs a TPU; exits non-zero without
    python chip_smoke.py --tiny-cpu   # same control flow, toy sizes, CPU

It exits non-zero if any phase raises or any check fails, and prints as its
last line {"ok": true, "device": {...}} only when everything passed.  The
times it prints are facts for CHANGES.md, not benchmark metrics.
"""
import argparse
import contextlib
import functools
import glob
import importlib.metadata
import json
import logging
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
from unittest import mock

SEED = 0
# Phase A overfits ONE fixed batch, the example's own learn-signal
# (examples/bert/pretrain.py): 10 steps at the example's overfit rate.
STEPS_A, LR = 10, 1e-3
# bf16 activations: two layouts of the same step differ by the order of
# each matmul's partial sums, ~2^-9 relative per rounding, averaged over
# ~1200 masked positions — far inside 0.05 on a loss of ~10.3, while a
# shard that saw the wrong rows or weights moves the loss by whole units.
MESH_LOSS_TOL = 0.05


def check(ok, what):
    if not ok:
        raise AssertionError(what)
    print(f"  ok: {what}")


def env(**pins):
    """Pin environment knobs for one phase (they are read at trace time)."""
    return mock.patch.dict(os.environ, pins)


class Compiles:
    """Programs this process had XLA build, compiled or loaded from the
    persistent cache (jax.monitoring's backend-compile event covers
    both)."""

    def __init__(self):
        import jax.monitoring
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event.endswith("backend_compile_duration"):
            self.n += 1


@contextlib.contextmanager
def past_the_compile_cache():
    """Programs built inside are compiled, never loaded.  The persistent
    cache keys a program on its text without debug information, so one that
    differs from a cached program only in its names is served that one's
    executable, op paths and all."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    try:
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def cache_entries(directory):
    return len(os.listdir(directory)) \
        if directory and os.path.isdir(directory) else 0


def bert_config(tiny, seq_len):
    from tpu_mx.models.bert import bert_base_config
    if not tiny:
        return bert_base_config(max_len=seq_len)
    cfg = bert_base_config(vocab_size=1000, max_len=seq_len)
    cfg.update(num_layers=2, units=128, hidden_size=512, num_heads=2)
    return cfg


def mlm_batch(cfg, batch, seq_len):
    """One fixed synthetic MLM batch: the vocab head runs only on the 15%
    masked positions, as in the benchmark's BERT cells."""
    import numpy as np
    rng = np.random.RandomState(SEED)
    tokens = rng.randint(4, cfg["vocab_size"], (batch, seq_len)).astype(
        np.int32)
    types = np.zeros((batch, seq_len), np.int32)
    n_masked = max(1, int(0.15 * seq_len))
    positions = np.stack([rng.choice(seq_len, n_masked, replace=False)
                          for _ in range(batch)]).astype(np.int32)
    labels = np.take_along_axis(tokens, positions, axis=1)
    return tokens, types, positions, labels


def train(tag, cfg, batch, seq_len, steps, compiles, remat=False,
          mesh_axes=None, presharded=False):
    """Build BERT + LAMB + CompiledTrainStep the way examples/bert/
    pretrain.py does and take `steps` steps on one fixed batch.
    Returns (losses, step)."""
    import jax
    import tpu_mx as mx
    from tpu_mx import gluon, nd
    from tpu_mx.models.bert import (BERTModel, bert_data_specs,
                                    bert_sharding_rules)
    from tpu_mx.parallel import (CompiledTrainStep, NamedSharding, P,
                                 make_mesh)

    class MLMLoss(gluon.loss.Loss):
        def __init__(self, **kw):
            super().__init__(weight=None, batch_axis=0, **kw)
            self._ce = gluon.loss.SoftmaxCrossEntropyLoss()

        def hybrid_forward(self, F, logits, labels):
            vocab = logits.shape[-1]
            return F.mean(self._ce(F.reshape(logits, shape=(-1, vocab)),
                                   F.reshape(labels, shape=(-1,))))

    mx.random.seed(SEED)  # same weights and dropout keys in every layout
    net = BERTModel(cfg, dtype="bfloat16", remat=remat)
    net.initialize()
    tokens, types, positions, labels = mlm_batch(cfg, batch, seq_len)
    net.finalize_shapes(nd.array(tokens[:1]), nd.array(types[:1]), None,
                        nd.array(positions[:1]))
    opt = mx.optimizer.create("lamb", learning_rate=LR,
                              multi_precision=True)
    mesh = rules = data_specs = None
    if mesh_axes:
        mesh = make_mesh(mesh_axes, devices=jax.devices()[:4])
        rules = bert_sharding_rules()
        # bert_data_specs() names (tokens, token_types, labels); the MLM
        # batch adds valid_length (None: no leaves) and the masked
        # positions, which shard like the labels they select
        tok, typ, lab = bert_data_specs()
        data_specs = (tok, typ, P(), lab, lab)
    step = CompiledTrainStep(net, MLMLoss(), opt, mesh=mesh, rules=rules,
                             data_specs=data_specs)
    if presharded:
        put = lambda a: jax.device_put(a, NamedSharding(mesh, P("dp")))
        args = (put(tokens), put(types), None, put(positions), put(labels))
        on = {s.device for s in args[0].addressable_shards}
        check(len(on) == 4, f"{tag}: batch shards sit on 4 devices")
    else:
        # the default user path: nd.array puts the batch on device 0 (with
        # a mesh, the step's in_shardings reshard it every step)
        args = (nd.array(tokens), nd.array(types), None,
                nd.array(positions), nd.array(labels))

    losses, seconds, warm = [], [], None
    for i in range(steps):
        t0 = time.perf_counter()
        loss = step.step(*args)
        loss.wait_to_read()                 # block_until_ready ...
        losses.append(float(loss.asscalar()))   # ... and a host fetch
        seconds.append(time.perf_counter() - t0)
        if i == 0:
            warm = compiles.n
    steady = statistics.median(seconds[1:])
    print(f"  {tag}: first step {seconds[0]:.1f} s (compile + run), steady "
          f"step {steady * 1e3:.1f} ms (median of {steps - 1})")
    print(f"  {tag}: losses " + " ".join(f"{l:.3f}" for l in losses))
    check(all(math.isfinite(l) for l in losses), f"{tag}: every loss finite")
    check(abs(losses[0] - math.log(cfg["vocab_size"])) < 1.0,
          f"{tag}: step-0 loss {losses[0]:.3f} within 1 of ln(vocab) = "
          f"{math.log(cfg['vocab_size']):.2f} (random-init MLM)")
    check(compiles.n == warm,
          f"{tag}: {compiles.n - warm} compilations after warm-up")
    return losses, step


def state_platforms(step):
    import jax
    leaves = jax.tree_util.tree_leaves(
        (step.values, step.masters, step.opt_states))
    return {next(iter(x.devices())).platform for x in leaves}


def phase_a(tiny, platform, compiles):
    from tpu_mx.parallel.ring_attention import dispatch_counts
    seq_len, batch = 128, (8 if tiny else 64)
    print(f"phase A: BERT seq {seq_len} batch {batch}, dense arm")
    before = dict(dispatch_counts)
    losses, step = train("A", bert_config(tiny, seq_len), batch, seq_len,
                         STEPS_A, compiles)
    check(sum(losses[-3:]) < sum(losses[:3]),
          f"A: mean of last 3 losses below mean of first 3 over {STEPS_A} "
          f"steps on one fixed batch at lr {LR} (memorization)")
    check(dispatch_counts["xla_dense"] > before["xla_dense"]
          and dispatch_counts["pallas_flash"] == before["pallas_flash"],
          "A: attention dispatched to xla_dense (auto: dense below kv 256)")
    check(state_platforms(step) == {platform},
          f"A: parameters, masters and optimizer state live on {platform}")
    return losses


def phase_mesh(tiny, platform, compiles, one_chip_losses):
    import numpy as np
    seq_len, batch, steps = 128, (8 if tiny else 64), 3
    cfg = bert_config(tiny, seq_len)

    def run(tag, axes, presharded):
        print(f"phase mesh {tag}: phase A's batch over {axes}, batch "
              + ("pre-sharded over dp" if presharded
                 else "fed from device 0 (resharded by the step)"))
        losses, step = train(tag, cfg, batch, seq_len, steps, compiles,
                             mesh_axes=axes, presharded=presharded)
        check(all(len(v.sharding.device_set) == 4
                  for v in step.values.values()),
              f"{tag}: every parameter's sharding spans 4 devices")
        check(state_platforms(step) == {platform},
              f"{tag}: parameters, masters and optimizer state on "
              f"{platform}")
        return step, abs(losses[0] - one_chip_losses[0])

    _, diff = run("dp4", {"dp": 4}, True)
    check(diff <= MESH_LOSS_TOL,
          f"dp4: step-0 loss differs from one chip by {diff:.2e} <= "
          f"{MESH_LOSS_TOL} (bf16 tolerance)")

    step, diff = run("dp2xtp2", {"dp": 2, "tp": 2}, False)
    print(f"  dp2xtp2: step-0 loss differs from one chip by {diff:.2e}")
    # under tp each device holds its share of the parameter bytes and no
    # more: replicated tensors whole, tp-sharded tensors halved
    held = {}
    for v in step.values.values():
        for s in v.addressable_shards:
            held[s.device] = held.get(s.device, 0) + s.data.nbytes
    share = sum(int(np.prod(v.sharding.shard_shape(v.shape)))
                * v.dtype.itemsize for v in step.values.values())
    total = sum(v.nbytes for v in step.values.values())
    check(set(held.values()) == {share} and share < total,
          f"dp2xtp2: each of {len(held)} devices holds {share / 1e6:.1f} "
          f"MB of {total / 1e6:.1f} MB of parameters")


def phase_b(tiny, platform, compiles):
    from tpu_mx.parallel.ring_attention import dispatch_counts
    seq_len, batch, steps = 512, (2 if tiny else 32), 4
    # off the chip the dispatch declines the kernel (interpret mode is
    # correctness-only), so the tiny CPU mode expects the dense arm here
    want, other = (("pallas_flash", "xla_dense") if platform == "tpu"
                   else ("xla_dense", "pallas_flash"))
    print(f"phase B: BERT seq {seq_len} batch {batch}, remat, "
          f"TPUMX_ATTENTION=flash, expecting {want}")
    warnings = []
    handler = logging.Handler(logging.WARNING)
    handler.emit = warnings.append
    logger = logging.getLogger("tpu_mx.parallel.ring_attention")
    logger.addHandler(handler)
    before = dict(dispatch_counts)
    try:
        with env(TPUMX_ATTENTION="flash"):
            train("B", bert_config(tiny, seq_len), batch, seq_len, steps,
                  compiles, remat=True)
    finally:
        logger.removeHandler(handler)
    check(dispatch_counts[want] > before[want]
          and dispatch_counts[other] == before[other],
          f"B: attention dispatched to {want}, not {other}")
    check(not any("dense O(T^2) XLA fallback" in r.getMessage()
                  for r in warnings),
          "B: no dense-fallback warning fired")


def phase_c(platform, compiles):
    import jax
    import numpy as np
    from tpu_mx import tracing
    from tpu_mx.serving import Server, TinyLM
    on_chip = platform == "tpu"
    print("phase C: Server(TinyLM 2 layers x 512 wide, 8 heads of 64), "
          "fused paged decode — a small model, serving's only one")
    model = TinyLM(vocab_size=128, embed_dim=512, num_heads=8, num_layers=2,
                   seed=SEED)
    prompts = [list(np.random.RandomState(SEED + n).randint(0, 128, size=n))
               for n in (5, 17, 33, 9, 24, 48)]

    def serve(tag, block_size, want_kernel, precision):
        """All prompts to "done" through Server; returns their tokens."""
        with jax.default_matmul_precision(precision):
            t0 = time.perf_counter()
            srv = Server(model, block_size=block_size, max_batch=8)
            built, warm = time.perf_counter() - t0, compiles.n
            event = [e for e in tracing.snapshot()
                     if e["event"] == "serve.decode_path"][-1]["data"]
            check((event["path"], event["storage"], event["fused"])
                  == ("paged", "device", True),
                  f"C {tag}: decode path paged, storage device, fused")
            check(srv.engine.jax_model.use_kernel is want_kernel,
                  f"C {tag}: fused step built with use_kernel="
                  f"{want_kernel}")
            t0 = time.perf_counter()
            reqs = [srv.submit(p, max_new_tokens=16) for p in prompts]
            srv.run_until_idle()
            served = time.perf_counter() - t0
        check(all(r.state == "done" and len(r.tokens) == 16 for r in reqs),
              f"C {tag}: {len(reqs)} requests done, 16 tokens each")
        n_tok = sum(len(r.tokens) for r in reqs)
        print(f"  C {tag}: server built and warmed in {built:.1f} s, "
              f"{n_tok} tokens in {served:.2f} s, compilations after "
              f"warm-up {compiles.n - warm}")
        return [list(r.tokens) for r in reqs]

    with env(TPUMX_PAGED_DECODE="1", TPUMX_FUSED_DECODE="1"):
        # the normal path: the kernel's own gate takes head_dim 64 and
        # block 16 on a TPU; off the chip the same knobs give the XLA twin
        serve("normal path", 16, on_chip, "default")
        # f32 matmuls run as bf16 passes on the MXU by default, and the
        # kernel's VPU dots do not, so tokens are compared at "highest".
        # Block size 12 fails the kernel's sublane gate, which is how the
        # public knobs select its XLA twin (window_walk, the body of
        # paged_attention_reference) on the same device; block size does
        # not enter the math.
        kernel = serve("normal path at highest", 16, on_chip, "highest")
        twin = serve("XLA twin at highest", 12, False, "highest")
    check(kernel == twin, "C: kernel arm and XLA twin emit the same tokens")


def phase_d(tiny, platform, compiles):
    import numpy as np
    import tpu_mx as mx
    from tpu_mx import gluon, nd
    from tpu_mx.models.decoder import CausalLM
    from tpu_mx.parallel import CompiledTrainStep, load_census
    from tpu_mx.parallel.ring_attention import (
        _seen_signatures, dispatch_counts, window_blocks)
    seq_len, steps = (64 if tiny else 512), 10
    want = "pallas_flash" if platform == "tpu" else "xla_dense"
    print(f"phase D: toy causal decoder seq {seq_len} batch 2, remat, "
          f"TPUMX_ATTENTION=flash, expecting {want}")
    latent = dict(num_heads=2, q_rank=64, kv_rank=64, nope_dim=48,
                  rope_dim=16, v_dim=64, rope_theta=1e6)
    # the third layer's queries see a quarter of the sequence, a window
    # narrower than the kernel's key block (the whole sequence here); it and
    # the fourth, which sees all of the past and turns half of each head by
    # YaRN's frequencies, gate every head's output (ISSUE 34)
    windowed = dict(kind="grouped_query", num_heads=4, num_kv_heads=2,
                    head_dim=64, rope_theta=1e6, window=seq_len // 4,
                    gate=True)
    half_turned = dict(kind="grouped_query", num_heads=6, num_kv_heads=2,
                       head_dim=64, rope_theta=5e5, rotary_dim=32,
                       yarn=dict(factor=8, original_length=seq_len // 4,
                                 attention_factor=1.2), gate=True)
    cfg = dict(
        vocab_size=1024, units=256, num_layers=4, num_dense_layers=1,
        dense_hidden=512, epsilon=1e-5,
        # the last: the multi-token module's
        attention=[latent, latent, windowed, half_turned, latent],
        moe=dict(hidden_size=128, num_experts=8, top_k=2,
                 held_experts=(0, 2), scaling=1.8, shared_hidden=128),
        mtp_depth=1, mtp_weight=0.3)
    mx.random.seed(SEED)
    net = CausalLM(cfg, dtype="bfloat16", remat=True)
    net.initialize(mx.init.Normal(0.02))
    tokens = nd.array(np.random.RandomState(SEED).randint(
        0, cfg["vocab_size"], (2, seq_len)), dtype="int32")
    adamw = functools.partial(mx.optimizer.create, "adamw",
                              learning_rate=3e-4, beta2=0.95, wd=0.1,
                              multi_precision=True)
    before, blocks = dict(dispatch_counts), dict(window_blocks)
    with env(TPUMX_ATTENTION="flash"):
        step = CompiledTrainStep(net, gluon.loss.PassThrough(), adamw())
        losses = [float(step.step(tokens, tokens).asscalar())
                  for _ in range(steps)]
    print("  losses: " + " ".join(f"{l:.4f}" for l in losses))
    check(all(math.isfinite(l) for l in losses), "D: every loss is finite")
    # random-init next-token loss plus 0.3 of the multi-token head's
    check(abs(losses[0] - 1.3 * math.log(cfg["vocab_size"])) < 1.0,
          f"D: first loss {losses[0]:.3f} is 1.3 ln(vocab) within 1")
    check(losses[-1] < losses[0] - 0.5,
          f"D: loss fell, {losses[0]:.3f} -> {losses[-1]:.3f}")
    check(dispatch_counts[want] > before[want],
          f"D: causal attention dispatched to {want}")
    detail = f"kv_heads=2 window={seq_len // 4}"
    check(any(path == want and detail in said
              for path, said in _seen_signatures),
          f"D: the grouped window layer ({detail}) dispatched to {want}")
    check(any(path == want and f"shape=(2, 6, {seq_len}, 64)" in said
              and "kv_heads=2" in said and "window" not in said
              for path, said in _seen_signatures),
          f"D: the half-turned full layer (6 heads over 2) dispatched to "
          f"{want}")
    gates = [l.attention.gate_weight for l in net.decoder_layers()
             if hasattr(l.attention, "gate_weight")]
    check([g.shape[0] for g in gates] == [4, 6],
          "D: two layers gate their heads, 4 and 6 of them")
    grid, run = (window_blocks[k] - blocks[k] for k in ("grid", "run"))
    # at this length the default blocks make a grid of one: counted, and
    # nothing to skip (the benchmark's 16k cell is where blocks are skipped)
    check(platform != "tpu" or 0 < run <= grid,
          f"D: the windowed flash call counted its blocks, {run} of "
          f"{grid} run")
    step.sync_to_net()
    census = load_census(net)
    check(len(census) == 4 and all(
        c["rows_routed_here"] > 0
        and sum(c["expert_load"]) == 2 * seq_len * 2 for c in census),
        "D: every expert layer counted a choice for every token, rows "
        "routed here " + ", ".join(str(int(c["rows_routed_here"]))
                                   for c in census))
    forced_past_the_first_slab(net, cfg)
    the_band_against_the_dense_arm(tiny, platform)
    the_rows_layout_against_the_dense_arm(tiny)
    the_kernels_names_on_a_trace(net, tokens, adamw(), platform)


def flash_against_the_dense_arm(t, window, group, d=128):
    """One key/value head of `d` under `group` query heads at length t, bf16,
    causal: relative error of the flash arm's output and three gradients
    against the dense arm, which runs a query head at a time in f32 at the
    highest precision (seven heads' scores at 16,384 would not fit), and
    what the dispatch counted of the window's blocks (grid, run, walked)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from tpu_mx.parallel.ring_attention import attention, window_blocks
    keys = jax.random.split(jax.random.key(SEED), 4)
    q, k, v, do = (jax.random.normal(key, (1, heads, t, d), dtype)
                   for key, heads, dtype in zip(
                       keys, (group, 1, 1, group),
                       (jnp.bfloat16,) * 3 + (jnp.float32,)))

    def arm(q, k, v, do):
        out, pull = jax.vjp(lambda *a: attention(
            *a, causal=True, window=window).astype(jnp.float32), q, k, v)
        return (out,) + pull(do)
    before = dict(window_blocks)
    with env(TPUMX_ATTENTION="flash"):
        got = [np.asarray(a, np.float32) for a in jax.jit(arm)(q, k, v, do)]
    counted = tuple(window_blocks[kind] - before[kind]
                    for kind in ("grid", "run", "walked"))
    with env(TPUMX_ATTENTION="dense"), \
            jax.default_matmul_precision("highest"):
        dense = jax.jit(arm)
        heads = [[np.asarray(a) for a in dense(
            q[:, h:h + 1].astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), do[:, h:h + 1])] for h in range(group)]
    want = [np.concatenate([h[i] for h in heads], 1) for i in (0, 1)] \
        + [sum(h[i] for h in heads) for i in (2, 3)]
    return [float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))
            for a, b in zip(got, want)], counted


def the_band_against_the_dense_arm(tiny, platform):
    """The flash arm under a window alone, at the two benchmark cells' window
    layers (one key/value head of each: 9 query heads over it at T 8,192
    under W 512, 7 at T 16,384 under W 4,096; heads of 128, bf16).  Mosaic
    lowers the band's index maps only here; the dispatch's count of the
    steps walked says the band engaged."""
    for t, window, group, blocks in (
            ((256, 64, 9, None), (512, 128, 7, None)) if tiny else
            ((8192, 512, 9, (256, 31, 32)),
             (16384, 4096, 7, (512, 140, 160)))):
        errors, counted = flash_against_the_dense_arm(t, window, group)
        # bf16 against f32: some 2e-3 on the output (the benchmark's
        # attend_window reads 0.0022), a few times that on the gradients;
        # a block of the band left out or run twice reads 1e-1 and more
        check(max(errors) < 2e-2,
              f"D: T {t} under W {window}, {group} query heads a key/value "
              "head: the flash arm is the dense arm, relative error of out, "
              "dq, dk, dv: " + " ".join(f"{e:.2e}" for e in errors))
        check(platform != "tpu" or counted == blocks,
              f"D: its grid is the band: (square, run, walked) {counted}")


def the_rows_layout_against_the_dense_arm(tiny):
    """The forward kernel keeps a row's maximum and sum as (rows, 128)
    tiles with every lane alike and repeats whole registers over the key
    block and the head (ISSUE 38; `kernels/flash_attention.py` `_lanes`):
    a Mosaic that lowers the repeat, or the leading lanes of a head of 64,
    otherwise fails here by name.  Heads of 128 under a window (key blocks
    of 512: four repeats), of 256 (key blocks of 1,024: eight, and two over
    the accumulator) and of 64 (the leading half of the lanes)."""
    for t, window, group, d in (
            ((256, 128, 2, 128), (256, None, 1, 256), (256, None, 1, 64))
            if tiny else
            ((2048, 512, 2, 128), (2048, None, 1, 256), (2048, None, 1, 64))):
        errors, _ = flash_against_the_dense_arm(t, window, group, d)
        check(max(errors) < 2e-2,
              f"D: the rows' layout, heads of {d} at T {t}"
              + (f" under W {window}" if window else "")
              + ": the flash arm is the dense arm, relative error of out, "
              "dq, dk, dv: " + " ".join(f"{e:.2e}" for e in errors))


def the_kernels_names_on_a_trace(net, tokens, opt, platform):
    """Two steps of phase D's decoder under the profiler: the device's
    operations carry the flash kernels' own names in their op paths, the
    forward kernel's in the forward pass and again under the checkpoint,
    and the dense layer's.  The guard against a jax or Mosaic that drops a
    scope round a `pallas_call`; off the chip there is no kernel to find,
    and only the control flow runs."""
    import jax
    from tpu_mx import gluon
    from tpu_mx.kernels.flash_attention import FLASH_SCOPES
    from tpu_mx.parallel import CompiledTrainStep
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "benchmark"))
    import xplane       # the yardstick's own view of a trace
    fwd, dq, dkv = FLASH_SCOPES
    trace_dir = tempfile.mkdtemp(prefix="chip_smoke_trace_")
    try:
        with env(TPUMX_ATTENTION="flash"), past_the_compile_cache():
            step = CompiledTrainStep(net, gluon.loss.PassThrough(), opt)
            step.step(tokens, tokens).asscalar()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        for _ in range(2):
            step.step(tokens, tokens).asscalar()
        jax.profiler.stop_trace()
        trace = xplane.load(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb"))[0])
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    dev = xplane.first_device(trace)
    paths = [dev["meta"].get(n, {}).get("tf_op", "")
             for n, _, _ in xplane.stretch(dev)[1]] if dev else []
    kernels = [p for p in paths if "pallas_call" in p]
    print(f"  traced {len(paths)} device operations, {len(kernels)} of them "
          "kernels")
    again = "rematted_computation"
    for what, found in (
            (f"{fwd} in the forward pass",
             [p for p in kernels if f"/{fwd}/" in p and again not in p]),
            (f"{fwd} under {again}",
             [p for p in kernels if f"/{fwd}/" in p and again in p]),
            (dq, [p for p in kernels if f"/{dq}/" in p]),
            (dkv, [p for p in kernels if f"/{dkv}/" in p]),
            ("mlp.dense", [p for p in paths if "mlp.dense" in p])):
        check(platform != "tpu" or found,
              f"D: the trace's op paths name {what}"
              + (f", {len(found)} operations, e.g. {found[0]}"
                 if found else ""))
    check(platform != "tpu" or not [
        p for p in kernels if not any(f"/{s}/" in p for s in FLASH_SCOPES)],
        "D: no kernel of the traced steps is without a name")


def forced_past_the_first_slab(net, cfg):
    """One more step of one expert layer with the selection bias sending
    every token to the two held experts: 2,048 rows against slabs of 1,024,
    so the loop over slabs runs twice, forward and backward, here on the
    chip, where XLA:TPU's grouped product leaves the rows no group owns
    unwritten.  Its reference is the unforced layer that holds the same
    two experts as ALL its experts: the same choice and weights without a
    bias, one slab and no loop."""
    import numpy as np
    from tpu_mx import autograd, nd
    from tpu_mx.parallel import DroplessMoE, load_census
    found = []
    net.apply_fn(lambda b: isinstance(b, DroplessMoE) and found.append(b))
    trained = found[0]
    units, moe = cfg["units"], cfg["moe"]

    def layer(experts):
        block = DroplessMoE(units, moe["hidden_size"], experts, 2,
                            held_experts=range(2), scaling=moe["scaling"])
        block.initialize()
        block.cast("bfloat16")
        block.gate_weight.set_data(trained.gate_weight.data()[:experts])
        for name in ("expert_w1", "expert_w3", "expert_w2"):
            getattr(block, name).set_data(getattr(trained, name).data())
        # the bias on the held experts alone: it decides only where there
        # are others to choose
        block.select_bias.set_data(np.where(
            np.arange(experts) < 2, 100.0, 0.0).astype(np.float32))
        return block
    rng = np.random.RandomState(SEED)
    x, head = (nd.array(rng.randn(1024, units), dtype="bfloat16")
               for _ in range(2))
    sides = []
    for block in (layer(moe["num_experts"]), layer(2)):
        x.attach_grad()
        with autograd.record():
            y = block(x)
        y.backward(head)
        census, = load_census(block)
        sides.append((census, [a.asnumpy().astype(np.float32) for a in (
            y, x.grad, block.gate_weight.grad[:2], block.expert_w1.grad,
            block.expert_w3.grad, block.expert_w2.grad)]))
    (forced, got), (whole, want) = sides
    check(forced["rows_routed_here"] == 2048 == 2 * forced["head_rows"]
          and whole["head_rows"] == 2048,
          f"D: the forced layer ran two slabs ({forced['rows_routed_here']:.0f}"
          f" rows in slabs of {forced['head_rows']}) and the reference is "
          "one slab")
    check(all(np.isfinite(a).all() for a in got),
          "D: the slabs left no unwritten row to read (all finite)")
    errors = [float(np.sqrt(np.mean((a - b) ** 2))
                    / (np.sqrt(np.mean(b ** 2)) + 1e-30))
              for a, b in zip(got, want)]
    # two bf16 programs: they stand one rounding apart (3e-3 to 4e-3 on the
    # chip, PR 32), where an unwritten or a dropped row would read 1e-1 or NaN
    check(max(errors) < 1e-2,
          "D: two slabs under the loop equal the layer that is one slab, "
          "relative error of y, dx, dgate, dw1, dw3, dw2: "
          + " ".join(f"{e:.2e}" for e in errors))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny-cpu", action="store_true",
                    help="toy sizes on the CPU: checks this script's "
                         "control flow, NOT the chip")
    args = ap.parse_args()

    # Gate first, before any model code: jax falls back to the CPU with
    # only a warning when libtpu cannot start, and every dispatch in the
    # tree would then quietly take its off-chip arm and the run would pass.
    import jax
    import jaxlib
    if args.tiny_cpu:
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    platform = devices[0].platform
    if args.tiny_cpu:
        print("NOT A CHIP RUN: --tiny-cpu runs toy sizes on the CPU to "
              "check this script's control flow")
    elif platform != "tpu":
        sys.exit(f"chip_smoke: no accelerator — jax.devices()[0].platform "
                 f"is {platform!r}, not 'tpu' (JAX_PLATFORMS="
                 f"{os.environ.get('JAX_PLATFORMS')!r}); --tiny-cpu runs "
                 "the control flow on the CPU")
    # the program: a directory that holds only this script stops here,
    # before a chip run has printed anything
    from tpu_mx.runtime import enable_shared_compilation_cache
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"platform={platform} device_kind={device['kind']!r} "
          f"count={device['count']} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} libtpu={libtpu}")

    cache_dir = enable_shared_compilation_cache()
    entries0 = cache_entries(cache_dir)
    print(f"compile cache: {cache_dir} ({entries0} entries)")
    compiles = Compiles()

    t0 = time.perf_counter()
    losses = phase_a(args.tiny_cpu, platform, compiles)
    if len(devices) >= 4:
        phase_mesh(args.tiny_cpu, platform, compiles, losses)
    else:
        print(f"phase mesh: skipped, {len(devices)} device(s) < 4")
    phase_b(args.tiny_cpu, platform, compiles)
    phase_c(platform, compiles)
    phase_d(args.tiny_cpu, platform, compiles)
    print(f"all phases passed in {time.perf_counter() - t0:.0f} s; "
          f"{compiles.n} compilations; compile cache {cache_dir}: "
          f"{entries0} -> {cache_entries(cache_dir)} entries")
    result = {"ok": True, "device": device}
    if args.tiny_cpu:
        result["tiny_cpu"] = True
    print(json.dumps(result))


if __name__ == "__main__":
    main()
