"""INT8 vs float inference A/B at fixed batch on chip (VERDICT r4 ask#7).

The r4 quantized-inference number (146 img/s after the jit fix) was only
ever compared to its own eager baseline (16 img/s), never to FLOAT
inference of the same net at the same batch — and divergence #21 already
concedes bf16 is the TPU fast path (the MXU has no native int8 advantage
the way GPU dp4a/IMMA tensor cores do).  This measures, for the model-zoo
ResNet-50 at a fixed batch:

  - f32 inference (hybridized, one XLA program),
  - bf16 inference (cast net — the production serving path),
  - INT8 inference (contrib.quantization.quantize_net, its own jit),

plus the parameter-memory footprint of each arm — if int8 loses on
throughput, its honest value is weight memory/serving footprint, and the
artifact says so with numbers.  Artifact: INT8_AB_<round>.json
(merge-on-write, TPU-only).

    python tools/int8_ab.py [--batch 128] [--iters 20]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


ARMS = ("f32", "bf16", "int8")


def log(msg):
    print(f"[int8_ab {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def _timed(fn, fetch, warmup, iters):
    out = fn()
    fetch(out)
    for _ in range(warmup):
        fetch(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    fetch(out)
    return (time.perf_counter() - t0) / iters


def _param_bytes(params):
    import numpy as np
    total = 0
    for p in params.values():
        d = getattr(p, "_data", None)
        if d is None and callable(getattr(p, "data", None)):
            d = p.data()
        if d is not None:
            total += d.size * np.dtype(str(d.dtype)).itemsize
    return total


def main():
    from artifact_protocol import (artifact, load_prior,
                                   merge_prior_sections, refuses_clobber,
                                   write_atomic)
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=artifact("INT8_AB"))
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--cpu-smoke", action="store_true",
                    help="tiny-shape CPU pass through the full code path")
    args = ap.parse_args()

    import jax
    if args.cpu_smoke:
        jax.config.update("jax_platforms", "cpu")
        args.batch, args.iters, args.warmup = 2, 1, 0
        if args.out == artifact("INT8_AB"):
            args.out = "/tmp/int8_ab_smoke.json"
    from tpu_mx.runtime import enable_shared_compilation_cache, fetch_sync
    enable_shared_compilation_cache()
    platform = jax.devices()[0].platform
    prior = load_prior(args.out)
    if refuses_clobber(prior, platform) or \
            (platform != "tpu" and not args.cpu_smoke):
        log(f"platform is {platform}, not tpu; refusing (hardware A/B)")
        return 1

    import numpy as np
    from tpu_mx import nd
    from tpu_mx.contrib import quantization as q
    from tpu_mx.gluon.model_zoo import vision
    from tpu_mx.layout import default_layout

    b = args.batch
    record = {"measured_at": time.strftime("%Y-%m-%dT%H:%M:%S+0000",
                                           time.gmtime()),
              "platform": platform, "model": "resnet50_v1 (NHWC, s2d)",
              "batch": b, "iters": args.iters, "arms": {}}
    # same-platform merge: tpu artifacts never absorb cpu smoke rows
    merge_prior_sections(record, prior, ("arms",),
                         require_platform=platform)

    log(f"building resnet50_v1 batch={b}...")
    rng = np.random.RandomState(0)
    with default_layout("NHWC"):
        net = vision.resnet50_v1(classes=1000, stem="s2d")
    net.initialize(init="xavier")
    x_np = rng.rand(b, 224, 224, 3).astype(np.float32)
    x = nd.array(x_np)
    net(x)  # finalize deferred shapes
    net.hybridize()
    fetch = lambda o: fetch_sync(o._data[0, 0])

    def arm(name, fn, params):
        log(f"{name}: compiling + timing...")
        try:
            dt = _timed(fn, fetch, args.warmup, args.iters)
            row = {"img_per_s": round(b / dt, 2),
                   "ms_per_batch": round(dt * 1e3, 2),
                   "param_bytes": _param_bytes(params)}
        except Exception as e:
            row = {"error": f"{type(e).__name__}: {e}"[:400]}
            log(f"  {name} failed: {row['error']}")
        # self-describing rows (artifact_protocol contract): merged-in
        # rows may come from runs with different --batch/--iters, and the
        # row is the only place that provenance survives the merge
        row["batch"] = b
        row["iters"] = args.iters
        row["measured_at"] = time.strftime("%Y-%m-%dT%H:%M:%S+0000",
                                           time.gmtime())
        record["arms"][name] = row
        write_atomic(args.out, record)
        return row

    f32 = arm("f32", lambda: net(x), net.collect_params())

    net.cast("bfloat16")
    xb = nd.cast(x, "bfloat16")
    net.hybridize()  # re-trace at the new dtype
    bf16 = arm("bf16", lambda: net(xb), net.collect_params())

    # quantize from a fresh f32 copy (cast-back corrupts calibration)
    with default_layout("NHWC"):
        qsrc = vision.resnet50_v1(classes=1000, stem="s2d")
    qsrc.initialize(init="xavier")
    calib = nd.array(x_np[:16])
    qsrc(calib)
    log("quantizing (calibration)...")
    qnet = q.quantize_net(qsrc, calib_data=calib)
    int8 = arm("int8", lambda: qnet(x), qsrc.collect_params())
    # serving-footprint story: quantized leaf weights store 1 byte/elem
    # (scales are negligible); everything else stays float.  The arm's
    # OWN param_bytes must be the quantized footprint — reporting the
    # float source net's bytes there would claim int8 saves nothing.
    # Skip entirely on a failed arm: an error row must not carry a
    # fabricated footprint.
    if "error" not in int8:
        try:
            wq = sum(p._data.size
                     for name, p in qsrc.collect_params().items()
                     if name.endswith("weight") and p._data is not None)
            float_bytes = int8.get("param_bytes", 0)
            int8["param_bytes_float_source"] = float_bytes
            int8["param_bytes"] = int(wq + max(float_bytes - wq * 4, 0))
            int8["param_bytes_note"] = ("int8 weights at 1 B/elem + "
                                        "non-quantized leaves at source "
                                        "dtype (analytic; wrapper storage "
                                        "is closure-internal)")
            write_atomic(args.out, record)
        except Exception as e:
            log(f"int8 footprint calc failed: {type(e).__name__}: {e}")

    if "img_per_s" in bf16 and "img_per_s" in int8:
        record["int8_vs_bf16"] = round(int8["img_per_s"] /
                                       bf16["img_per_s"], 4)
        record["verdict"] = (
            "int8 FASTER than bf16" if record["int8_vs_bf16"] > 1.0 else
            "int8 SLOWER than bf16 - its honest value on TPU is weight "
            "memory/serving footprint, not throughput (divergence #21)")
        write_atomic(args.out, record)
        log(f"int8 vs bf16: {record['int8_vs_bf16']:.3f}x "
            f"({record['verdict']})")
    log(f"done: {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
