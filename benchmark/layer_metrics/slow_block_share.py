"""Layer: training entry.  Share (%) of counted blocks slower than 1.02 x
the median block: how often the loop stalled."""


def read(run):
    return run["blocks"]["slow_block_share"]
