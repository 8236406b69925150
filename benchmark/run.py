"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It finds the cell in BENCHMARK.json and, by the names there, the files that
belong to it: configs/<config>.json and .py, references/<config>.py,
traffic/<traffic>.json and layer_metrics/<metric>.py.  It builds the system
under test through the public API, compares it with the plain reference,
warms up, measures whole fenced blocks for --seconds, and prints as its
last line the contract's JSON object.  Earlier lines start with "bench ".

Without a TPU (or with fewer chips than the cell asks for) it exits
non-zero and names the platform.  --rehearse-cpu runs the same control flow
at toy sizes on the CPU, says so, and reports counts only.
"""
import time
T_START = time.perf_counter()

import argparse
import glob
import importlib.util
import json
import math
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE]

import blocks  # noqa: E402


def load_module(kind, name):
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def say(name, value):
    print(f"bench {name} {json.dumps(value)}", flush=True)


class Compiles:
    """Programs this process had XLA build, compiled or loaded from the
    persistent cache (jax.monitoring's backend-compile event covers both);
    copied from chip_smoke.py."""

    def __init__(self):
        import jax.monitoring
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event.endswith("backend_compile_duration"):
            self.n += 1


def run_block(step, batch, n_steps):
    """n_steps steps enqueued back to back, fenced by a host fetch of the
    last loss (it depends on the whole update chain).  Returns (seconds,
    seconds the first enqueue took while the device queue was empty, the
    losses of the block, still on the device: fetching them is no part of
    training, so fetch() does it after the window)."""
    from jax.profiler import TraceAnnotation
    t0 = time.perf_counter()
    with TraceAnnotation("bench/enqueue_step"):
        losses = [step.step(*batch)]
    first = time.perf_counter() - t0
    for _ in range(n_steps - 1):
        with TraceAnnotation("bench/enqueue_step"):
            losses.append(step.step(*batch))
    with TraceAnnotation("bench/fetch_loss"):
        losses[-1].wait_to_read()
        losses[-1].asscalar()
    return time.perf_counter() - t0, first, losses


def fetch(losses):
    return [float(l.asscalar()) for l in losses]


def relative_rms(a, b):
    import numpy as np
    return float(np.sqrt(np.mean(np.square(a - b)))
                 / np.sqrt(np.mean(np.square(b))))


def compare_with_reference(cfg, config_mod, reference, net, batch, rehearse):
    """Relative RMS error of each compared output against the plain
    reference, held to the tolerances in the configuration file (set from
    readings on the chip at published widths, so a rehearsal only reports)."""
    spec = cfg["reference_comparison"]
    got, want = config_mod.compare(reference, net, batch, spec["sample"])
    errors = {k: relative_rms(got[k], want[k]) for k in got}
    over = {k: e for k, e in errors.items()
            if not e <= spec["tolerance"].get(k, math.inf)}
    result = {"errors": errors, "tolerance": spec["tolerance"],
              "ok": rehearse or not over, "what": spec["what"]}
    say("reference_comparison", result)
    # the comparison's programs leave the device before the step is built:
    # peak_hbm_gib is the system's memory, not the yardstick's
    import jax
    jax.clear_caches()
    return result["ok"]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy sizes on the CPU: checks the control flow, "
                         "NOT the chip; reports counts only")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        sys.exit(f"benchmark: no workload {args.workload!r} in "
                 "BENCHMARK.json")
    seconds = args.seconds or bench["run_seconds"]
    rehearse = args.rehearse_cpu
    cfg = load_json("configs", cell["config"])
    mix = load_json("traffic", cell["traffic"])
    if rehearse:
        cfg.update(cfg.get("rehearse", {}))
        mix.update(mix.get("rehearse", {}))
    wanted = bench["per_layer"] if args.trace else bench["end_to_end"]
    # a rehearsal reports counts only: nothing it could time or size is the
    # device's
    wanted = [m for m in wanted
              if cell["name"] in m.get("workloads", [cell["name"]])
              and not (rehearse and m["unit"] != "count")]

    # Gate first, before any model code: jax falls back to the CPU with only
    # a warning when libtpu cannot start, and the run would then "pass".
    import jax
    if rehearse:
        jax.config.update("jax_platforms", "cpu")
        print("NOT A CHIP RUN: --rehearse-cpu runs toy sizes on the CPU to "
              "check the benchmark's control flow; no device metric follows")
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if not rehearse and (platform != "tpu" or len(devices) < cell["chips"]):
        sys.exit(f"benchmark: {cell['name']} needs {cell['chips']} TPU "
                 f"chip(s); jax reports platform {platform!r} with "
                 f"{len(devices)} device(s) (JAX_PLATFORMS="
                 f"{os.environ.get('JAX_PLATFORMS')!r}). --rehearse-cpu "
                 "runs the control flow on the CPU")
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if not rehearse and kind not in peaks:
        sys.exit(f"benchmark: device kind {kind!r} is not in peaks.json")
    devices = devices[:cell["chips"]]

    phases, mark = {}, [T_START]

    def phase(name):
        now = time.perf_counter()
        phases[name] = now - mark[0]
        mark[0] = now

    import tpu_mx  # noqa: F401  (a directory without the program stops here)
    from tpu_mx.parallel import make_mesh
    from tpu_mx.parallel.ring_attention import dispatch_counts
    from tpu_mx.runtime import enable_shared_compilation_cache
    # the program's own choice: JAX_COMPILATION_CACHE_DIR if set, else the
    # fixed <checkout>/.jax_cache
    say("compile_cache", {"directory": enable_shared_compilation_cache()})
    # Keep every program, however quickly it compiled.  The program keeps
    # those that took a second, and the build's many small programs take
    # about that: by chance in the cache or not, they made set-up differ by
    # 13 s between two calls (PERF.md, section 6).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = Compiles()
    config_mod = load_module("configs", cell["config"])
    reference = load_module("references", cell["config"])
    phase("imports")

    mesh = make_mesh(mix["mesh"], devices=devices) if mix.get("mesh") else None
    dispatch0 = dict(dispatch_counts)
    net, make_step = config_mod.build(cfg, mix, args.seed, mesh)
    batch = config_mod.make_batch(cfg, mix, args.seed, mesh)
    jax.block_until_ready([a for a in batch if a is not None])
    phase("build_and_weights")

    reference_ok = compare_with_reference(cfg, config_mod, reference, net,
                                          batch, rehearse)
    phase("reference_comparison")

    step = make_step()
    n_steps, samples = mix["block_steps"], mix["batch"] * mix["block_steps"]
    first_loss = fetch(run_block(step, batch, 1)[2])[0]
    phase("compile_or_cache_load")
    # the same program again, ahead of time, for its temporaries: the
    # device's own memory statistics do not see them (PERF.md, section 2)
    memory = step.aot_compiled(*batch).memory_analysis()
    phase("memory_analysis")
    run_block(step, batch, 1)
    phase("warm_up")
    discarded = [run_block(step, batch, n_steps)[0] for _ in range(2)]
    phase("discarded_blocks")
    setup_s = time.perf_counter() - T_START
    say("programs", {"built_or_loaded_in_set_up": compiles.n})
    say("setup_s_phases", dict(phases, total=setup_s))

    # -- the measured window ---------------------------------------------------
    compiles_before = compiles.n
    deadline = time.perf_counter() + seconds
    history, times, enqueue, losses, traced = list(discarded), [], [], [], None
    if args.trace and not rehearse:
        trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        # two blocks, or four seconds, whichever is less
        for _ in range(max(1, min(2, int(4.0 / discarded[-1])))):
            losses += run_block(step, batch, n_steps)[2]
        jax.profiler.stop_trace()
        traced = glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb"))[0]
    span_start = time.perf_counter()
    while blocks.fits(time.perf_counter(), deadline, history):
        seconds_, first, block_losses = run_block(step, batch, n_steps)
        span_end = time.perf_counter()
        history.append(seconds_)
        times.append(seconds_)
        enqueue.append(first)
        losses += block_losses
    compiled_in_window = compiles.n - compiles_before
    if not times:
        sys.exit(f"benchmark: no whole block fits into {seconds} s")
    stats = blocks.summary(times, samples, span_end - span_start)
    losses = fetch(losses)
    say("blocks", dict(stats, discarded_s=discarded,
                       block_steps=n_steps, batch=mix["batch"]))

    # -- after the window --------------------------------------------------------
    memory_stats = [d.memory_stats() or {} for d in devices]
    live = max(m.get("bytes_in_use", 0) for m in memory_stats)
    peak_stat = max(m.get("peak_bytes_in_use", 0) for m in memory_stats)
    temp = memory.temp_size_in_bytes
    # what the cell needs on one chip: what stays on the fullest chip after
    # the window plus the step program's temporaries
    memory_peak = max(peak_stat, live + temp)
    say("memory", {"bytes_in_use": live, "peak_bytes_in_use": peak_stat,
                   "step_temp_bytes": temp,
                   "step_argument_bytes": memory.argument_size_in_bytes,
                   "step_output_bytes": memory.output_size_in_bytes,
                   "step_alias_bytes": memory.alias_size_in_bytes,
                   "memory_peak_bytes": memory_peak})

    center, band = config_mod.loss_center(cfg, mix), cfg["loss_band"]["width"]
    checks = {
        "reference_comparison": reference_ok,
        "every_loss_finite": all(math.isfinite(l) for l in losses),
        # the band is for published sizes: a rehearsal only reports
        "first_loss_in_band": rehearse or abs(first_loss - center) <= band,
        "last_loss_below_first": losses[-1] < first_loss,
        "no_compilation_in_window": compiled_in_window == 0}
    say("checks", dict(checks, first_loss=first_loss,
                       last_loss=losses[-1], band=[center - band, center + band],
                       compiled_in_window=compiled_in_window))

    run = {"cell": cell, "cfg": cfg, "mix": mix, "chips": cell["chips"],
           "blocks": stats, "first_enqueue_s": enqueue, "setup_s": setup_s,
           "peak_hbm_gib": memory_peak / 2 ** 30, "step_temp_bytes": temp,
           "samples_per_s": stats["samples_per_s"],
           "flops_per_sample": config_mod.flops_per_sample(cfg, mix),
           "peaks": peaks.get(kind),
           "dispatch": {k: v - dispatch0[k]
                        for k, v in dispatch_counts.items()},
           "trace": None}
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": all(checks.values()), "attempted": len(losses),
              "failed": sum(not math.isfinite(l) for l in losses)}
    if traced:
        import xplane
        # the readers get every event of the trace as plain tuples
        run["trace"] = xplane.load(traced)
        shutil.rmtree(trace_dir)    # tens of megabytes a run, read once
        busy_s, window_s = xplane.busy(run["trace"])
        if not busy_s > 0:
            sys.exit("benchmark: the trace shows no operation on the device")
        device.update(busy_s=busy_s, window_s=window_s)
        result["breakdown"] = xplane.breakdown(run["trace"])
    metrics = {}
    for m in wanted:
        # what the harness takes itself is in `run`; the rest has a reader
        value = run[m["name"]] if m["name"] in run else \
            load_module("layer_metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result.update(metrics=metrics, device=device)
    if rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
