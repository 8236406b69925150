"""Layer: training entry.  Host clock around the call that enqueues the
FIRST step of each block, when the device queue is empty (later calls of a
block wait on the queue and read the device's step time instead); median
over the untraced blocks, in ms."""
import statistics


def read(run):
    return 1e3 * statistics.median(run["first_enqueue_s"])
