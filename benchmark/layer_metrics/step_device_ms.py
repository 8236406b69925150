"""Layer: compiled step.  Device trace: the median duration of the step
module's executions on the first device (`XLA Modules` line), in ms."""
import statistics

import xplane


def read(run):
    if not run["trace"]:
        return None
    steps, _ = xplane.stretch(xplane.first_device(run["trace"]))
    return statistics.median(e - s for s, e in steps) / 1e6 if steps else None
