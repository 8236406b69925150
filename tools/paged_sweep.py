"""Paged-decode KV block-size sweep.

ISSUE 9's tuning satellite, on the bench harness's decode_attention
micro-arm (bench.measure_decode_micro — the same fixed-seed A/B the
serve leg persists):

- **Block-size sweep**: the serving KV block size trades free-list
  churn (amortized ``1/block_size`` pops per token) against padded-tail
  waste, table length and gather granularity.  Each (block_size,
  context) cell measures the paged arm (device pool, block-table
  program) and the dense-gather arm per decode step.  The default lives
  at ``tpu_mx/kernels/paged_attention.py DEFAULT_BLOCK_SIZE``; update it
  only with receipts from this tool.

ISSUE 16 widens the sweep with a **Tq axis**: the speculative verify
call batches ``Tq`` query positions per sequence into ONE attention
step, so each (block_size, context, tq) cell now records per-TOKEN
amortization (``*_us_per_tok``).  Row keys carry the axis
(``bs{B}_ctx{C}_tq{T}``) and the record is stamped ``record_rev=2``:
a rev-1 artifact (``bs{B}_ctx{C}`` keys, no tq field) uses a DIFFERENT
keyspace, so this tool REFUSES to merge into one — rename it or start
a new TPUMX_ROUND rather than mixing row schemas.

Artifact-protocol semantics (tools/artifact_protocol.py): rows merge on
rerun, writes are atomic, and a TPU-less run refuses to clobber a
platform=tpu artifact.

    TPUMX_ROUND=r08 python tools/paged_sweep.py \
        [--block-sizes 8,16,32,64] [--contexts 256,1024] \
        [--tq 1,4] [--batch 4]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from artifact_protocol import (artifact, load_prior,  # noqa: E402
                               merge_prior_sections, refuses_clobber,
                               write_atomic)

DEFAULT_BLOCK_SIZES = (8, 16, 32, 64)
DEFAULT_CONTEXTS = (256, 1024)
DEFAULT_TQS = (1, 4)
# rev 2 (ISSUE 16): rows gained the Tq axis — keys are bs{B}_ctx{C}_tq{T}
# and carry a "tq" field.  Bump on any row-keyspace/schema change.
RECORD_REV = 2


def log(msg):
    print(f"[paged_sweep {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--block-sizes", default=",".join(
        str(b) for b in DEFAULT_BLOCK_SIZES))
    ap.add_argument("--contexts", default=",".join(
        str(c) for c in DEFAULT_CONTEXTS))
    ap.add_argument("--tq", default=",".join(str(t) for t in DEFAULT_TQS),
                    help="query-window widths (speculative verify Tq)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--out", default=artifact("PAGED_SWEEP"))
    args = ap.parse_args()
    block_sizes = [int(b) for b in args.block_sizes.split(",") if b]
    contexts = [int(c) for c in args.contexts.split(",") if c]
    tqs = [int(t) for t in args.tq.split(",") if t]
    if any(t < 1 for t in tqs):
        log(f"--tq must be >= 1, got {tqs}")
        return 1

    import jax
    import bench

    platform = jax.default_backend()
    prior = load_prior(args.out)
    if refuses_clobber(prior, platform):
        log(f"{args.out} holds platform=tpu rows; this {platform} run "
            "refuses to clobber them (artifact protocol)")
        return 1
    if prior and prior.get("record_rev", 1) != RECORD_REV:
        # a rev-1 artifact keys rows WITHOUT the tq axis: merging would
        # mix keyspaces and a later reader could double-count.  Refuse.
        log(f"{args.out} is record_rev={prior.get('record_rev', 1)} "
            f"(this tool writes rev {RECORD_REV}, row keys now carry "
            "the tq axis) — rename the old artifact or start a new "
            "TPUMX_ROUND instead of mixing row schemas")
        return 1

    record = {
        "record_rev": RECORD_REV,
        "platform": platform,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "git_head": bench._git_head(),
        "geometry": {"batch": args.batch, "heads": args.heads,
                     "dim": args.dim},
        "rows": {},
    }
    # graft prior rows in BEFORE the first per-row write: the row-at-a-
    # time durability writes below must never clobber sibling rows from
    # an earlier (e.g. partial-retry) run — this run's rows still win
    # their own keys as they land (merge-on-write contract)
    merge_prior_sections(record, prior, ["rows"],
                         require_platform=platform)
    for bs in block_sizes:
        # contexts must tile meaningfully: skip block sizes larger than
        # the shortest context rather than measuring a 1-block table
        usable = [c for c in contexts if c >= bs * 2]
        if not usable:
            log(f"block_size={bs}: no usable context (all < 2 blocks), "
                "skipped")
            continue
        for tq in tqs:
            # every window row needs >= 1 attendable key: ctx > tq
            win = [c for c in usable if c > tq]
            if not win:
                log(f"block_size={bs} tq={tq}: no usable context, "
                    "skipped")
                continue
            log(f"block_size={bs} tq={tq}: contexts {win}")
            rows = bench.measure_decode_micro(win, block_size=bs,
                                              batch=args.batch,
                                              heads=args.heads,
                                              dim=args.dim, tq=tq)
            for row in rows:
                key = f"bs{bs}_ctx{row['context']}_tq{tq}"
                record["rows"][key] = row
                write_atomic(args.out, record)  # row-at-a-time durability

    write_atomic(args.out, record)
    if not record["rows"]:
        log(f"done: 0 rows (every block size skipped for the given "
            f"contexts) -> {args.out} holds no row")
        return 0
    best = min(record["rows"].values(),
               key=lambda r: r["paged_us_per_seq"])
    log(f"done: {len(record['rows'])} rows -> {args.out}; best "
        f"paged us/seq: bs{best['block_size']}@ctx{best['context']} = "
        f"{best['paged_us_per_seq']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
