"""The readings behind laguna-s-2.1.json's tolerances, in one process:

    python3 benchmark/configs/laguna-s-2.1.readings.py --seeds 1,2 [--out file.json]

For each seed the honest error (the system against the f32 reference, as
the cell's own comparison has it); on the last seed every deliberately wrong
variant of the reference (one compiled f32 program serves them all: `wrong`
goes in as a traced index), and the control, the reference computed all in
bfloat16 in place of the f32 one, put through run.py's own
compare_with_reference at the limits in the file: it has to come out as not
correct (PERF.md, section 6, PR 34, says how long it takes).  --rehearse-cpu runs the same control flow at the toy sizes on
the CPU; its numbers are no readings.
"""
import argparse
import gc
import json
import math
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]

import numpy as np  # noqa: E402

import run as harness  # noqa: E402

NAME = "laguna-s-2.1"


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="1,2")
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    import jax
    if args.rehearse_cpu:
        jax.config.update("jax_platforms", "cpu")
        print("NOT A CHIP RUN: toy sizes on the CPU, no reading follows")
    elif jax.devices()[0].platform != "tpu":
        sys.exit("readings: published widths need a TPU; --rehearse-cpu "
                 "runs the control flow on the CPU")
    config_mod = harness.load_module("configs", NAME)
    reference = harness.load_module("references", NAME)
    cfg = harness.load_json("configs", NAME)
    mix = harness.load_json("traffic", "pretrain8k")
    if args.rehearse_cpu:
        cfg.update(cfg["rehearse"])
        mix.update(mix["rehearse"])
    n = cfg["reference_comparison"]["sample"]
    out, programs = {}, {}

    def say(name, value):
        out[name] = value
        print(name, json.dumps(value), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(out, f, indent=1)

    def errors(got, want):
        """Relative RMS errors; None where the reference's side is all zero
        (gate_off leaves W_g no gradient): beyond every limit."""
        with np.errstate(divide="ignore", invalid="ignore"):
            found = {k: harness.relative_rms(got[k], want[k]) for k in got}
        return {k: e if math.isfinite(e) else None for k, e in found.items()}

    seeds = [int(s) for s in args.seeds.split(",")]
    for seed in seeds:
        net, _ = config_mod.build(cfg, mix, seed)
        batch = config_mod.make_batch(cfg, mix, seed)
        got, aux = config_mod.system_outputs(net, batch, n)
        want = config_mod.reference_outputs(reference, net, aux,
                                            programs=programs)
        say(f"honest_seed_{seed}", errors(got, want))
        if seed == seeds[-1]:
            for wrong in reference.WRONG:
                say(f"wrong_{wrong}", errors(got, config_mod.reference_outputs(
                    reference, net, aux, wrong=wrong, programs=programs)))
            low = config_mod.reference_outputs(reference, net, aux, low="all")
            say("low_all_against_f32", errors(low, want))
            # the harness's own verdict on the control, at the file's limits
            # (rehearse=False: a rehearsal would only report)
            say("low_all_correct", harness.compare_with_reference(
                cfg, types.SimpleNamespace(compare=lambda *a: (got, low)),
                reference, net, batch, False))
        del net, batch, got, want, aux
        gc.collect()


if __name__ == "__main__":
    main()
