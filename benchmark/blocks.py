"""The block statistic.  A block is `block_steps` train steps enqueued back
to back and ended by a host fetch of the last loss.  Throughput, the
end-to-end samples_per_s, is every counted sample over all the time from
the first counted block's start to the last one's end, so a stall costs
what it costs a user.  Beside it stand the rate over the MEDIAN block
(steady_samples_per_s), which one stalled block does not move, and the share
of slow blocks: the two say whether a fall came from the step or from
stalls."""
import statistics

SLOW = 1.02   # fifty times the 0.04% that blocks spread by when nothing stalls


def fits(now, deadline, block_times):
    """May another block start?  Only if a block as long as the recent ones
    would end inside the window: a block that the clock would cut is never
    started, so every counted block is whole."""
    return now + 1.05 * statistics.median(block_times[-5:]) <= deadline


def summary(times, samples_per_block, span_s=None):
    """`span_s`: first counted block's start to the last one's end (the
    blocks follow each other at once, so it is their sum plus microseconds;
    it defaults to the sum)."""
    median = statistics.median(times)
    span_s = sum(times) if span_s is None else span_s
    return {"blocks": len(times), "span_s": span_s, "min_s": min(times),
            "median_s": median, "max_s": max(times),
            "mean_s": statistics.mean(times),
            "samples_per_s": samples_per_block * len(times) / span_s,
            "steady_samples_per_s": samples_per_block / median,
            "slow_block_share": slow_block_share(times), "times_s": times}


def slow_block_share(times):
    """Share (%) of blocks slower than SLOW x the median block."""
    limit = SLOW * statistics.median(times)
    return 100.0 * sum(t > limit for t in times) / len(times)
