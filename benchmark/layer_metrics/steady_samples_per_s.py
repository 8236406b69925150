"""Layer: training entry.  The samples of one block over the MEDIAN block
time: the rate the step sustains when nothing stalls.  The end-to-end
samples_per_s is all samples over all time and falls with every stall;
the distance between the two is what the stalls cost."""


def read(run):
    return run["blocks"]["steady_samples_per_s"]
