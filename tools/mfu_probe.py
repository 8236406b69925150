"""MFU / roofline probe on the real chip (VERDICT r3 ask#3 + weak#7).

For each workload config this measures the full compiled train step and
records, side by side:
  - measured throughput + MFU from the analytic FLOPs model (bench.py's),
  - XLA's OWN cost-analysis FLOPs and the MFU implied by them — the
    cross-check VERDICT weak#7 asked for (the analytic model is
    hand-maintained; if the two disagree badly the model is wrong),
  - layout/copy smell counts from the compiled HLO (transpose/pad/copy),
  - the compiled memory analysis (are we near the 16 GB HBM ceiling?).

Every config's record is persisted to MFU_PROBE_<round>.json as soon as it
exists, so a later config dying keeps the earlier rows.  Run by hand:
    python tools/mfu_probe.py [--out PATH] [--configs resnet:512,...]
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

V5E_PEAK_FLOPS = 197e12

# the default probe sweep
DEFAULT_CONFIGS = ("resnet:256", "resnet:512", "bert:512", "bert:256",
                   "bert_flash:512")


def log(msg):
    print(f"[mfu {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def _is_oom(e):
    s = f"{type(e).__name__}: {e}".lower()
    return ("ran out of memory" in s or "out of memory" in s
            or "resource_exhausted" in s or "exceeded hbm capacity" in s)


def _compile_step(step, batch_args):
    # the AOT lower+compile path lives on CompiledTrainStep itself now
    # (bench.py's XLA-cost MFU shares it)
    return step.aot_compiled(*batch_args)


def _timed_steps(step, batch_args, warmup, iters):
    import numpy as np
    fetch = lambda l: float(np.asarray(l._data).ravel()[0])
    loss = step.step(*batch_args)
    fetch(loss)
    for _ in range(warmup):
        fetch(step.step(*batch_args))
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step.step(*batch_args)
    fetch(loss)
    return (time.perf_counter() - t0) / iters


def probe_one(model, batch):
    import contextlib

    attn_override = None
    if model == "bert_dense":
        # A/B the attention path: at T=128 the single-block flash kernel
        # vs XLA's fused dense attention is an empirical question.  The
        # env knob is read at TRACE time, so it must span compile+timing.
        # Since auto now RESOLVES to dense at short T (the measured r4
        # winner), the flash arm needs an explicit pin — the plain 'bert'
        # config measures what production auto picks.
        model, attn_override = "bert", "dense"
    elif model == "bert_flash":
        model, attn_override = "bert", "flash"
    with contextlib.ExitStack() as stack:
        if attn_override:
            prior = os.environ.get("TPUMX_ATTENTION")
            os.environ["TPUMX_ATTENTION"] = attn_override

            def restore():
                if prior is None:
                    os.environ.pop("TPUMX_ATTENTION", None)
                else:
                    os.environ["TPUMX_ATTENTION"] = prior

            stack.callback(restore)
        return _probe_one(model, batch)


def _probe_one(model, batch):
    import hlo_inspect
    import bench as bench_mod

    # record what the trace will actually read, not what the caller
    # thinks it set — a user-level TPUMX_ATTENTION pin applies to every
    # rung and must show up in the artifact
    attn_mode = os.environ.get("TPUMX_ATTENTION", "auto")
    log(f"building {model} batch={batch} (attention={attn_mode})...")
    if model == "resnet":
        step, batch_args = hlo_inspect.build_resnet_step(False, batch)
        unit_flops = bench_mod.RESNET50_TRAIN_FLOPS_PER_IMG
    else:
        step, batch_args = hlo_inspect.build_bert_step(False, batch)
        seq_len, n_masked = 128, max(1, int(0.15 * 128))
        unit_flops = bench_mod.bert_train_flops_per_seq(
            12, 768, 3072, 30522, seq_len, n_masked)

    log("compiling...")
    compiled = _compile_step(step, batch_args)
    txt = compiled.as_text()
    ops, convs, fusions = hlo_inspect.analyze(txt)
    smells = {k: ops.get(k, 0) for k in
              ("transpose", "copy", "pad", "reshape", "convert")}
    xla_flops = None
    try:
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        xla_flops = float(ca.get("flops", 0.0)) or None
    except Exception as e:
        log(f"cost_analysis unavailable: {e}")
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                mem[k] = int(v)
    except Exception:
        pass

    log("timing...")
    sec = _timed_steps(step, batch_args, warmup=3, iters=15)
    per_sec = batch / sec
    rec = {
        "model": model, "batch": batch,
        "attention": attn_mode,
        "step_seconds": round(sec, 5),
        "throughput_per_sec": round(per_sec, 2),
        "mfu_analytic_model": round(per_sec * unit_flops / V5E_PEAK_FLOPS,
                                    4),
        "hlo": {"fusions": fusions, "smells": smells,
                "n_convolutions": len(convs)},
        "memory": mem,
    }
    if xla_flops:
        # cost_analysis flops are per program execution (the whole batch)
        rec["xla_cost_flops_per_step"] = xla_flops
        rec["mfu_xla_cost"] = round(xla_flops / sec / V5E_PEAK_FLOPS, 4)
        rec["analytic_vs_xla_flops_ratio"] = round(
            (unit_flops * batch) / xla_flops, 4)
    # ONE number of record (VERDICT r4 ask#9): mfu = the XLA-cost value
    # when the backend exposes cost_analysis, analytic model otherwise;
    # both raw fields stay for the cross-check
    rec["mfu"] = rec.get("mfu_xla_cost", rec["mfu_analytic_model"])
    rec["mfu_source"] = ("xla_cost_analysis" if xla_flops
                         else "analytic_model")
    return rec


def main():
    ap = argparse.ArgumentParser()
    from artifact_protocol import artifact
    ap.add_argument("--out", default=artifact("MFU_PROBE"))
    ap.add_argument("--configs", default=",".join(DEFAULT_CONFIGS))
    ap.add_argument("--cpu", action="store_true",
                    help="force CPU (harness smoke; mirrors conftest)")
    args = ap.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    else:
        from tpu_mx.runtime import enable_shared_compilation_cache
        enable_shared_compilation_cache()
    platform = jax.devices()[0].platform
    from artifact_protocol import (load_prior, merge_prior_sections,
                                   refuses_clobber, write_atomic)
    record = {"ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
              "platform": platform, "peak_flops": V5E_PEAK_FLOPS,
              "configs": {}}
    prior = load_prior(args.out)
    if refuses_clobber(prior, platform):
        log(f"platform is {platform}, not tpu; refusing to overwrite "
            f"the hardware artifact {args.out} (pass --out elsewhere "
            "for a smoke run)")
        return 1
    # a partial run (--configs retry after one transport blip) must MERGE
    # into the existing artifact, not clobber the other rows: keep prior
    # same-platform rows for configs this run does not touch (this run's
    # result, including a recorded error, still replaces its own row)
    if not args.cpu:
        merge_prior_sections(record, prior, ("configs",),
                             require_platform=platform)
    if platform != "tpu" and not args.cpu:
        record["skipped"] = True
        record["reason"] = f"platform is {platform}, not tpu"
        log(record["reason"])
        probed = []
    else:
        record["skipped"] = False
        probed = []  # keys THIS run attempts (exit code ignores merged rows)
        seen_ok = set()
        for item in args.configs.split(","):
            model, b = item.strip().split(":")
            batch = int(b)
            if args.cpu:  # smoke shapes: prove the harness, not the chip
                batch = min(batch, 8)
            if model in seen_ok and args.cpu:
                continue
            probed.append(f"{model}:{batch}")
            t0 = time.perf_counter()
            try:
                rec = probe_one(model, batch)
                record["configs"][f"{model}:{batch}"] = rec
                seen_ok.add(model)
                log(f"{model}:{batch} -> {rec['throughput_per_sec']}/s "
                    f"mfu={rec['mfu_analytic_model']}")
            except Exception as e:
                err = f"{type(e).__name__}: {e}"[:400]
                record["configs"][f"{model}:{batch}"] = {
                    "model": model, "batch": batch, "error": err,
                    "oom": _is_oom(e),
                    "seconds": round(time.perf_counter() - t0, 1)}
                log(f"{model}:{batch} FAILED {err}")
            write_atomic(args.out, record)
    write_atomic(args.out, record)
    ok = (not record["skipped"] and probed and
          any("error" not in record["configs"][k] for k in probed))
    log(f"done: {args.out}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
