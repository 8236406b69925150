"""Short-T flash-kernel block sweep vs XLA dense (VERDICT r4 ask#4).

`auto` sends attention to the Pallas flash kernel from kv_len 256 where
attention dropout is active and from 512 where it is not, and to XLA
dense below (`parallel/ring_attention.py` `_auto_prefers_flash`; no
option).  That crossover was read in BERT-base's whole train step, both
arms pinned (`PERF.md` section 6, PR 26, has the table); this tool has
never run on the chip.  It times attention alone (fwd+bwd,
causal, bf16, no dropout) and answers "is the kernel's overhead below
the crossover tunable or structural?":

  - for each T it measures XLA dense and the flash kernel at every valid
    (block_q, block_k) combination (the kernel's only tuning surface);
  - constant token budget across T (B = tokens/T) so rows are comparable;
  - per-combo rows merge into FLASH_SWEEP_<round>.json immediately
    (artifact-protocol semantics: partial reruns merge, a TPU-less run
    refuses to clobber).

Note the structural expectation: at T <= 512 `_pick_block` already
collapses to a single (T, T) block per b*h grid cell, so there is
nothing smaller to pipeline — if no combo closes the gap, the honest
outcome is "dense below the crossover is final" and the rule stands.

    python tools/flash_sweep.py [--lens 128,256,512,1024]
        [--tokens 65536] [--heads 12] [--dim 64] [--iters 10]
"""
from __future__ import annotations

import argparse
import itertools
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


DEFAULT_LENS = (128, 256, 512, 1024)


def log(msg):
    print(f"[flash_sweep {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


def measure(attn_fn, b, h, t, d, iters):
    import jax
    import jax.numpy as jnp
    from tpu_mx.runtime import fetch_sync
    key = jax.random.PRNGKey(0)
    qk, kk, vk = jax.random.split(key, 3)
    q = jax.random.normal(qk, (b, h, t, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, h, t, d), jnp.bfloat16)
    v = jax.random.normal(vk, (b, h, t, d), jnp.bfloat16)

    def loss_and_grads(q, k, v):
        return jax.value_and_grad(
            lambda q, k, v: attn_fn(q, k, v).astype(jnp.float32).mean(),
            argnums=(0, 1, 2))(q, k, v)

    step = jax.jit(loss_and_grads)
    fetch_sync(step(q, k, v)[0])                  # compile + settle
    t0 = time.perf_counter()
    l = None
    for _ in range(iters):
        l, _ = step(q, k, v)
    fetch_sync(l)
    dt = (time.perf_counter() - t0) / iters
    return {"ms_per_step": round(dt * 1e3, 3),
            "tok_per_s": int(b * t / dt)}


def main():
    from artifact_protocol import (artifact, load_prior,
                                   merge_prior_sections, refuses_clobber,
                                   write_atomic)
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=artifact("FLASH_SWEEP"))
    ap.add_argument("--lens",
                    default=",".join(str(t) for t in DEFAULT_LENS))
    ap.add_argument("--tokens", type=int, default=65536,
                    help="constant token budget; B = tokens / T")
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--cpu-smoke", action="store_true",
                    help="tiny-shape CPU pass through the full code path "
                         "(interpret-mode kernel; r4 lesson: never ship a "
                         "chip tool whose Python path never ran)")
    args = ap.parse_args()

    import jax
    if args.cpu_smoke:
        jax.config.update("jax_platforms", "cpu")
        if args.lens == ap.get_default("lens"):
            args.lens = "128,256"
        args.tokens, args.iters = 512, 1
        if args.out == artifact("FLASH_SWEEP"):
            args.out = "/tmp/flash_sweep_smoke.json"
    from tpu_mx.runtime import enable_shared_compilation_cache
    enable_shared_compilation_cache()
    platform = jax.devices()[0].platform
    prior = load_prior(args.out)
    if refuses_clobber(prior, platform) or \
            (platform != "tpu" and not args.cpu_smoke):
        log(f"platform is {platform}, not tpu; refusing (hardware sweep)")
        return 1
    import jax.numpy as jnp
    from tpu_mx.kernels.flash_attention import mha_flash_attention

    h, d = args.heads, args.dim
    geom = {"H": h, "D": d, "iters": args.iters, "causal": True}
    record = {"measured_at": time.strftime("%Y-%m-%dT%H:%M:%S+0000",
                                           time.gmtime()),
              "platform": platform,
              "config": "fwd+bwd, causal, bf16, loss-fetch-bounded, "
                        "constant token budget across T",
              "sweep": {}}
    # merge only same-platform priors: a tpu artifact never absorbs cpu
    # smoke rows, and the smoke path still exercises the merge machinery
    merge_prior_sections(record, prior, ("sweep",),
                         require_platform=platform)

    def dense(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) / (d ** 0.5)
        tq = s.shape[-2]
        mask = jnp.arange(tq)[:, None] >= jnp.arange(tq)[None, :]
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p,
                          v.astype(jnp.float32)).astype(q.dtype)

    for t in [int(x) for x in args.lens.split(",") if x.strip()]:
        b = max(1, args.tokens // t)
        row = dict(geom, B=b, T=t,
                   measured_at=time.strftime("%Y-%m-%dT%H:%M:%S+0000",
                                             time.gmtime()))
        log(f"T={t} B={b}: dense...")
        try:
            row["dense"] = measure(dense, b, h, t, d, args.iters)
        except Exception as e:
            row["dense"] = {"error": f"{type(e).__name__}: {e}"[:300]}
        # every valid block combo <= T (the kernel clamps anyway; dedup)
        combos = sorted({(min(bq, t), min(bk, t))
                         for bq in (128, 256, 512)
                         for bk in (128, 256, 512, 1024)})
        row["flash"] = {}
        best = None
        for bq, bk in combos:
            tag = f"bq{bq}_bk{bk}"
            log(f"T={t} B={b}: flash {tag}...")
            try:
                r = measure(lambda q, k, v: mha_flash_attention(
                    q, k, v, causal=True, block_q=bq, block_k=bk),
                    b, h, t, d, args.iters)
            except Exception as e:
                r = {"error": f"{type(e).__name__}: {e}"[:300]}
            row["flash"][tag] = r
            if "tok_per_s" in r and (best is None or
                                     r["tok_per_s"] > best[1]):
                best = (tag, r["tok_per_s"])
            record["sweep"][f"T={t}"] = row
            write_atomic(args.out, record)
        if best and "tok_per_s" in row.get("dense", {}):
            row["best_flash"] = best[0]
            row["flash_vs_dense"] = round(best[1] /
                                          row["dense"]["tok_per_s"], 4)
            log(f"T={t}: best flash {best[0]} = "
                f"{row['flash_vs_dense']:.3f}x dense")
        # the watcher's resume contract keys off this: a wedge mid-row
        # leaves complete unset and the stage re-runs (merge keeps the
        # finished combos)
        row["complete"] = True
        record["sweep"][f"T={t}"] = row
        write_atomic(args.out, record)
    log(f"done: {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
