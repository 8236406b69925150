"""Offline f32-surface audit of a compiled train step (VERDICT r4 ask#1:
the ResNet step is HBM-bound and `convert_reduce_fusion` burns 20.5 ms —
find every activation-sized f32 tensor the traced program materializes,
BEFORE spending chip time measuring).

Dtypes are backend-independent at the StableHLO level, so this runs on
CPU with a small batch (the dtype pattern does not depend on batch) and
reports:
  - every f32 tensor type above a per-image element threshold, with the
    op kinds that produce it (activation-sized f32 = 2x the bytes of the
    bf16 tensor it shadows);
  - the convert-op census (bf16->f32 / f32->bf16) by operand size class.

Usage:
    JAX_PLATFORMS=cpu PYTHONPATH=. \
        python tools/dtype_audit.py [--model resnet|bert|lstm|ssd] [--batch 8]
"""
from __future__ import annotations

import argparse
import collections
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def log(msg):
    print(f"[dtype_audit {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


_TENSOR = re.compile(r"tensor<([0-9x]+)x(f32|bf16|f16|i32|i8|ui8|i1)>")


def _elems(dims):
    n = 1
    for d in dims.split("x"):
        n *= int(d)
    return n


def audit_text(text, batch, per_img_threshold=16384):
    """Scan StableHLO text: per-line tensor types + op name.  Returns
    (big_f32, converts) where big_f32 maps shape->set(op kinds) for f32
    results above threshold*batch elements."""
    thresh = per_img_threshold * batch
    big_f32 = collections.defaultdict(collections.Counter)
    converts = collections.Counter()
    for line in text.splitlines():
        line = line.strip()
        m_op = re.match(r'%?[\w.#]+ = "?([\w.]+)"?', line)
        op = m_op.group(1) if m_op else "?"
        tensors = _TENSOR.findall(line)
        if not tensors:
            continue
        if "convert" in op:
            # operand -> result dtype transition, bucketed by size
            if len(tensors) >= 2:
                src, dst = tensors[0][1], tensors[-1][1]
                size = "big" if _elems(tensors[0][0]) >= thresh else "small"
                converts[f"{src}->{dst} ({size})"] += 1
            continue
        # result type is the LAST tensor on an assignment line
        dims, dt = tensors[-1]
        if dt == "f32" and _elems(dims) >= thresh:
            big_f32[dims][op] += 1
    return big_f32, converts


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet",
                    choices=["resnet", "bert", "lstm", "ssd"])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--per-img-threshold", type=int, default=16384,
                    help="f32 tensors above this many elements PER BATCH "
                         "ROW are reported (16384 = 128x128, well below "
                         "any conv activation)")
    args = ap.parse_args()

    import jax
    jax.config.update("jax_platforms", "cpu")
    import hlo_inspect

    log(f"building {args.model} batch={args.batch} (CPU, trace-only)...")
    builders = {"resnet": hlo_inspect.build_resnet_step,
                "bert": hlo_inspect.build_bert_step,
                "lstm": hlo_inspect.build_lstm_step,
                "ssd": hlo_inspect.build_ssd_step}
    step, batch_args = builders[args.model](False, args.batch)
    log("lowering...")
    import jax.numpy as jnp
    from tpu_mx import random as _random
    raw = tuple(b._data if b is not None and hasattr(b, "_data") else b
                for b in batch_args)
    if step._jitted is None:
        step._build(len(raw))
        step.place()
    key = _random.take_key()
    gacc = step._gacc if step._accum > 1 else {}
    lowered = step._jitted.lower(
        step.values, step.masters, step.opt_states, step._efs, gacc,
        jnp.asarray(1.0, jnp.float32), jnp.asarray(0.1, jnp.float32),
        key, *raw)
    text = lowered.as_text()
    log(f"stablehlo: {len(text.splitlines())} lines")
    big_f32, converts = audit_text(text, args.batch,
                                   args.per_img_threshold)
    print(f"== activation-sized f32 results (>= "
          f"{args.per_img_threshold} elems/batch-row) ==")
    rows = sorted(big_f32.items(), key=lambda kv: -_elems(kv[0]))
    if not rows:
        print("  (none — every large tensor is bf16/int)")
    total = 0
    for dims, ops in rows:
        n = _elems(dims)
        total += n * sum(ops.values())
        print(f"  f32[{dims}] ({n / 1e6:.1f}M elems): "
              + ", ".join(f"{k}x{v}" for k, v in ops.most_common()))
    print(f"  TOTAL large-f32 result elements: {total / 1e6:.1f}M "
          f"(x4 bytes if materialized)")
    print("== convert census ==")
    for k, v in converts.most_common():
        print(f"  {k}: {v}")


if __name__ == "__main__":
    main()
