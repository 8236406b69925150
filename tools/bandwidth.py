"""Collective bandwidth measurement over the device mesh
(REF:tools/bandwidth/measure.py — the reference measured KVStore push/pull
bandwidth between devices/servers; the TPU-native analog measures the XLA
collectives that replaced them: psum, all_gather, reduce_scatter,
ppermute over the ICI/DCN mesh).

    python tools/bandwidth.py --sizes 1,4,16 --axis dp
    python tools/bandwidth.py --devices 8        # CPU: virtualize 8

Prints one JSON line per (collective, size): algorithmic bandwidth
GB/s = bytes_moved / time, where bytes_moved uses the standard ring-
algorithm accounting (2·(n-1)/n·size for allreduce, (n-1)/n·size for
all_gather/reduce_scatter, size for ppermute).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="1,4,16,64",
                    help="per-device payload MB, comma separated")
    ap.add_argument("--axis", default="dp")
    ap.add_argument("--devices", type=int, default=0,
                    help="virtualize N CPU devices if fewer are present")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    if args.devices:
        flag = f"--xla_force_host_platform_device_count={args.devices}"
        if "--xla_force_host_platform_device_count" not in os.environ.get(
                "XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
    import jax
    if args.devices > 1:
        # must happen BEFORE the first jax.devices() query — that call
        # initializes and pins the backend (same rule as __graft_entry__)
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    from tpu_mx.runtime import fetch_sync
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_mx.parallel import make_mesh

    n = args.devices or len(jax.devices())
    mesh = make_mesh({args.axis: n}, devices=jax.devices()[:n])
    ax = args.axis
    perm = [(i, (i + 1) % n) for i in range(n)]

    # per-device bytes moved, as a multiple of the per-device INPUT shard:
    # ring allreduce 2(n-1)/n of the (sharded) input, ring all_gather
    # sends/receives (n-1) shard-sized blocks, reduce_scatter (n-1)/n,
    # ppermute exactly one shard
    colls = {
        "psum": (lambda x: lax.psum(x, ax), 2.0 * (n - 1) / n),
        "all_gather": (lambda x: lax.all_gather(x, ax), float(n - 1)),
        "reduce_scatter": (
            lambda x: lax.psum_scatter(x, ax, tiled=True), (n - 1) / n),
        "ppermute": (lambda x: lax.ppermute(x, ax, perm), 1.0),
    }

    for mb in (float(s) for s in args.sizes.split(",")):
        elems_per_dev = max(1, int(mb * 1e6 / 4))
        x = jnp.ones((n * elems_per_dev,), jnp.float32)
        x = jax.device_put(x, NamedSharding(mesh, P(ax)))
        for name, (fn, factor) in colls.items():
            sm = jax.shard_map(fn, mesh=mesh, in_specs=P(ax),
                               out_specs=(P(None) if name == "all_gather"
                                          else P(ax)), check_vma=False)
            jitted = jax.jit(sm)
            # bound by a host fetch of a slice that depends on the work
            # (tpu_mx.runtime.fetch_sync)
            fetch_sync(jitted(x)[:1])  # compile+warm
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = jitted(x)
            fetch_sync(out[:1])
            dt = (time.perf_counter() - t0) / args.iters
            moved = factor * elems_per_dev * 4
            print(json.dumps({
                "collective": name, "axis": ax, "devices": n,
                "payload_mb_per_device": round(mb, 3),
                "time_ms": round(dt * 1e3, 3),
                "alg_bandwidth_gbps": round(moved / dt / 1e9, 3),
            }), flush=True)


if __name__ == "__main__":
    main()
