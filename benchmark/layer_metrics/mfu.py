"""Layer: kernels.  Model FLOP/s utilisation (%): the FLOPs that forward and
backward need per sample (the configuration's flops_per_sample, from shapes;
nothing recomputed is counted) x samples_per_s, over chips x the bf16 peak
of peaks.json."""


def read(run):
    return 100.0 * run["flops_per_sample"] * run["samples_per_s"] / (
        run["chips"] * run["peaks"]["bf16_flops_per_s"])
