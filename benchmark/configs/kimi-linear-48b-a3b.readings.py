"""The readings behind kimi-linear-48b-a3b.json's tolerances, in one process:

    python3 benchmark/configs/kimi-linear-48b-a3b.readings.py --seeds 1,2 [--out file.json]

For each seed the honest error (the system against the f32 reference, as
the cell's own comparison has it: the net's forward and one step of the
timed program over the whole batch); on the last seed every deliberately
wrong variant of the reference (one compiled f32 program serves them all:
`wrong` goes in as a traced index), the control, the reference computed
all in bfloat16 in place of the f32 one, and two planted faults of the
timed step, half of the batch left out and a state left unchanged, each
put through run.py's own compare_with_reference at the limits in the
file: the control and the faults have to come out as not correct, and so
does each wrong variant (PERF.md, section 6, says how long it takes).

On every seed, besides, what the first updates' and the routing's errors
are made of.  AdamW's first step is -lr (g / (|g| + eps) + wd w), nearly
-lr sign(g): where the system's gradient and the reference's differ in
sign, the two updates differ by 2 lr.  `flips_seed_<n>` gives, for each
compared leaf, the share of elements whose gradient differs in sign, the
share of those whose reference gradient lies within three times the RMS
of the gradient's error, and the update's error from those elements alone
and from all the others.  The timed step is a program of its own, and its
expert layers' inputs may differ from the forward's by a rounding: a
forward hook copies them out of the step (a host callback traced into it),
the program's routing function is asked for the step's own choice, and
`route_rows_differing_seed_<n>` counts, for each expert layer, the rows
whose eight experts differ from the forward's; where any do, the step's
outputs are read again against the reference handed the step's choice
(after the step, whose state then leaves the chip: the net is not used
again).
--rehearse-cpu runs the same control flow at the toy sizes on the CPU; its
numbers are no readings.
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

NAME = "kimi-linear-48b-a3b"


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="1,2")
    ap.add_argument("--out", default=None)
    ap.add_argument("--wrong", default="all",
                    help="the wrong variants to read, comma-separated, "
                         "\"all\" or \"none\" (each is a pass of the "
                         "reference over the whole batch)")
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
    mix = harness.load_json("traffic", "pretrain8k-b2")
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
        """Relative RMS errors; None where the reference's side is all
        zero: beyond every limit."""
        with np.errstate(divide="ignore", invalid="ignore"):
            found = {k: harness.relative_rms(got[k], want[k]) for k in got}
        return {k: e if math.isfinite(e) else None for k, e in found.items()}

    def verdict(name, got, want):
        """The errors and the harness's own verdict, at the file's limits
        (rehearse=False: a rehearsal would only report)."""
        say(name + "_errors", errors(got, want))
        say(name + "_correct", harness.compare_with_reference(
            cfg, types.SimpleNamespace(compare=lambda *a: (got, want)),
            reference, None, None, False))

    def flips(got, want):
        """For each compared leaf: what the update's error is made of."""
        lr = cfg["system"]["learning_rate"]
        found = {}
        for leaf in config_mod.LEAVES:
            gs, gr = (np.asarray(d["grad_" + leaf], np.float64)
                      for d in (got, want))
            us, ur = got["update_" + leaf], want["update_" + leaf]
            flip = np.sign(gs) != np.sign(gr)
            noise = math.sqrt(np.mean((gs - gr) ** 2))
            share = float(flip.mean())
            found[leaf] = {
                "flipped_share": share,
                "flipped_within_3_noise": float(np.mean(
                    np.abs(gr[flip]) <= 3 * noise)) if flip.any() else None,
                "update": harness.relative_rms(us, ur),
                "update_from_the_flipped": harness.relative_rms(
                    np.where(flip, us, ur), ur),
                "update_from_the_others": harness.relative_rms(
                    np.where(flip, ur, us), ur),
                "two_lr_over_the_flipped": 2 * lr * math.sqrt(share)
                / math.sqrt(np.mean(np.square(ur, dtype=np.float64)))}
        return found

    seeds = [int(s) for s in args.seeds.split(",")]
    for seed in seeds:
        net, _ = config_mod.build(cfg, mix, seed)
        batch = config_mod.make_batch(cfg, mix, seed)
        got, aux = config_mod.system_forward(net, batch, n)
        # every reference reads the weights before the system's step
        want = config_mod.reference_outputs(reference, net, aux,
                                            programs=programs)
        last = seed == seeds[-1]
        if last:
            wrongs = {w: config_mod.reference_outputs(
                reference, net, aux, wrong=w, programs=programs)
                for w in reference.WRONG
                if args.wrong == "all" or w in args.wrong.split(",")}
            low = config_mod.reference_outputs(reference, net, aux,
                                               low="all", programs=programs)
            # the timed step with half of the batch left out reads against
            # the whole batch's reference as the whole step reads against
            # the half's
            half = config_mod.reference_outputs(
                reference, net, aux, programs=programs,
                first=len(aux["tokens"]) // 2)
        # the weights the step starts from, and the rows its own expert
        # layers read
        before = jax.device_get(config_mod.weights(net))
        inputs, hooks = config_mod.tap_expert_inputs(net)
        got.update(config_mod.system_step(net, batch, aux))
        jax.effects_barrier()
        for h in hooks:
            h.detach()
        say(f"honest_seed_{seed}", errors(got, want))
        say(f"flips_seed_{seed}", flips(got, want))
        hp, _ = config_mod.hyper(cfg)
        xs = [inputs[i].reshape(-1, inputs[i].shape[-1])
              for i in range(len(inputs))]
        chosen = [np.asarray(c) for c, _ in config_mod.route_rows(
            xs, [(p["moe"]["router"], p["moe"]["bias"])
                 for p in before["layers"] if "moe" in p], hp)]
        differing = [int(np.any(np.sort(a, -1) != np.sort(b, -1), -1).sum())
                     for a, b in zip(aux["chosen"], chosen)]
        say(f"route_rows_differing_seed_{seed}",
            {"rows": differing, "of": [len(c) for c in chosen]})
        if any(differing):
            # the step's state is read: the reference's programs get the
            # chip's memory, with what they read on the host
            scanned = jax.device_get(aux["scan_inputs"])
            for a in jax.live_arrays():
                a.delete()
            stepped = config_mod.reference_outputs(
                reference, net, dict(aux, chosen=chosen, inputs=xs,
                                     scan_inputs=scanned),
                programs=programs, params=before)
            keys = [k for k in got
                    if k == "loss" or k.startswith(("grad_", "update_"))]
            say(f"honest_seed_{seed}_with_the_steps_choice", errors(
                {k: got[k] for k in keys}, {k: stepped[k] for k in keys}))
        if last:
            for wrong, wrong_want in wrongs.items():
                verdict(f"wrong_{wrong}", got, wrong_want)
            say("low_all_against_f32", errors(low, want))
            verdict("low_all", got, low)
            say("fault_half_batch", errors(got, half))
            verdict("fault_half_batch", got, half)
            # a state the step left unchanged
            frozen = dict(got, **{k: np.zeros_like(v) for k, v in got.items()
                                  if k.startswith("update_")})
            say("fault_frozen", errors(frozen, want))
            verdict("fault_frozen", frozen, want)
        cfg.pop("live", None)
        del net, batch, got, want, aux, before, inputs, xs
        gc.collect()


if __name__ == "__main__":
    main()
