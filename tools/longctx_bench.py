"""Long-context single-chip sweep: flash-kernel causal attention fwd+bwd
tokens/sec across sequence lengths (SURVEY §5.7; LONGCTX_<round>.json was
produced ad hoc last session — this makes the measurement reproducible
and extends it to T=64k).

The flash kernel's O(T) memory is what makes ≥16k context possible on one
16 GB chip at all: dense attention's backward materializes O(B·H·T²)
probabilities (≥12 GB at T=16k) and OOMs.  Ring attention (sp-sharded)
extends the same kernel across a pod slice — that path is exercised by
tests/test_parallel.py and the driver's dryrun; this tool measures the
single-chip kernel roofline.

    python tools/longctx_bench.py [--out LONGCTX_<round>.json]
                                  [--lens 4096,8192,...] [--dense-at 8192]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

DEFAULT_LENS = (4096, 8192, 16384, 32768, 65536)
DEFAULT_DENSE_AT = 8192


def log(msg):
    print(f"[longctx {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def measure(attn_fn, b, h, t, d, iters=10):
    import jax
    import jax.numpy as jnp
    from tpu_mx.runtime import fetch_sync
    key = jax.random.PRNGKey(0)
    qk, kk, vk = jax.random.split(key, 3)
    q = jax.random.normal(qk, (b, h, t, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, h, t, d), jnp.bfloat16)
    v = jax.random.normal(vk, (b, h, t, d), jnp.bfloat16)

    def loss_and_grads(q, k, v):
        l, g = jax.value_and_grad(
            lambda q, k, v: attn_fn(q, k, v).astype(jnp.float32).mean(),
            argnums=(0, 1, 2))(q, k, v)
        return l, g

    step = jax.jit(loss_and_grads)
    # timing is bounded by fetch_sync: a host fetch of the scalar loss,
    # which depends on all the timed work (tpu_mx.runtime.fetch_sync)
    fetch_sync(step(q, k, v)[0])                  # compile + settle
    t0 = time.perf_counter()
    for _ in range(iters):
        l, _ = step(q, k, v)
    fetch_sync(l)
    dt = (time.perf_counter() - t0) / iters
    return {"ms_per_step": round(dt * 1e3, 2),
            "tok_per_s": int(b * t / dt)}


def main():
    from artifact_protocol import artifact
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=artifact("LONGCTX"))
    ap.add_argument("--lens",
                    default=",".join(str(t) for t in DEFAULT_LENS))
    ap.add_argument("--dense-at", type=int, default=DEFAULT_DENSE_AT,
                    help="also measure XLA dense attention at this T "
                         "(0 disables); T>=16384 dense OOMs by design")
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    import jax
    from tpu_mx.runtime import enable_shared_compilation_cache
    enable_shared_compilation_cache()
    platform = jax.devices()[0].platform
    if platform != "tpu":
        log(f"platform is {platform}, not tpu; refusing to overwrite the "
            "hardware artifact")
        return 1
    from tpu_mx.kernels.flash_attention import mha_flash_attention

    from artifact_protocol import (load_prior, merge_prior_sections,
                                   write_atomic)

    b, h, d = 1, args.heads, args.dim
    # every row carries its own geometry: merged-in rows may come from a
    # run with different --heads/--dim/--iters, and the row is the only
    # place that provenance survives the merge
    geom = {"B": b, "H": h, "D": d, "iters": args.iters}
    record = {
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S+0000", time.gmtime()),
        "config": "single chip, bf16, causal, full fwd+bwd, "
                  "loss-fetch-bounded timing, steady state; per-row "
                  "geometry in each entry",
        "platform": platform,
        "flash_kernel": {}, "dense_comparison": {},
    }
    # a partial rerun (--lens 65536 retry after a transport blip) must
    # MERGE into the existing artifact, not clobber the other rows (the
    # artifact_protocol contract); this run's rows replace their own keys.
    # require_platform: a non-tpu-labeled prior must never be grafted
    # into this platform=tpu artifact (advisor r4 finding #1)
    merge_prior_sections(record, load_prior(args.out),
                         ("flash_kernel", "dense_comparison"),
                         require_platform="tpu")
    row_ts = lambda: time.strftime("%Y-%m-%dT%H:%M:%S+0000", time.gmtime())
    flash = lambda q, k, v: mha_flash_attention(q, k, v, causal=True)
    for t in [int(x) for x in args.lens.split(",") if x.strip()]:
        log(f"flash T={t}...")
        try:
            record["flash_kernel"][f"T={t}"] = dict(
                measure(flash, b, h, t, d, args.iters), **geom,
                measured_at=row_ts())
            log(f"  {record['flash_kernel'][f'T={t}']}")
        except Exception as e:
            record["flash_kernel"][f"T={t}"] = dict(
                {"error": f"{type(e).__name__}: {e}"[:300]}, **geom,
                measured_at=row_ts())
            log(f"  T={t} failed: {type(e).__name__}")
        write_atomic(args.out, record)

    if args.dense_at:
        import jax.numpy as jnp

        def dense(q, k, v):
            s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                           k.astype(jnp.float32)) / (d ** 0.5)
            tq = s.shape[-2]
            mask = jnp.arange(tq)[:, None] >= jnp.arange(tq)[None, :]
            p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
            return jnp.einsum("bhqk,bhkd->bhqd", p,
                              v.astype(jnp.float32)).astype(q.dtype)

        t = args.dense_at
        log(f"dense T={t}...")
        try:
            rec = measure(dense, b, h, t, d, args.iters)
            # only compare against a flash row of the SAME geometry: a
            # merged-in prior row may have been measured with different
            # --heads/--dim/--iters, and a cross-geometry ratio would be
            # a wrong claim with self-consistent-looking fields
            frow = record["flash_kernel"].get(f"T={t}", {})
            ft = frow.get("ms_per_step") if all(
                frow.get(k) == v for k, v in geom.items()) else None
            if ft:
                rec["note"] = (
                    f"flash is {rec['ms_per_step'] / ft:.2f}x faster than "
                    f"dense at T={t}; dense backward's O(B*H*T^2) "
                    "probabilities stop fitting HBM at T>=16384 - flash's "
                    "O(T) memory is what makes single-chip long context "
                    "possible")
        except Exception as e:
            # e.g. --dense-at 16384: the dense backward OOMs by design —
            # record it like a flash T-failure instead of losing the run
            rec = {"error": f"{type(e).__name__}: {e}"[:300]}
            log(f"  dense T={t} failed: {type(e).__name__}")
        record["dense_comparison"][f"T={t}"] = dict(rec, **geom,
                                                    measured_at=row_ts())
    record["note"] = (
        "SURVEY 5.7 long-context on real silicon; ring attention "
        "(sp-sharded) extends this across a pod slice. Timing is "
        "loss-fetch-bounded.")
    write_atomic(args.out, record)
    log(f"done: {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
