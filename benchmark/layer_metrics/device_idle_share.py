"""Layer: device.  Device trace: 1 - the union of the intervals in which an
operation ran, over the traced stretch (first to last execution of the step
module), averaged over the chips used, in %."""
import xplane


def read(run):
    if not run["trace"]:
        return None
    busy_s, window_s = xplane.busy(run["trace"])
    return 100.0 * (1.0 - busy_s / window_s) if window_s else None
