"""Layer: token mixer.  How many times the chunked delta rule
(models/decoder.py's kda_chunked) was traced while this cell was built and
warmed up (ring_attention.dispatch_counts["kda_chunked"], counted at trace
time): the guard that Kimi Delta Attention never falls back to a scan a
position at a time.  A program without the counter reports nothing."""


def read(run):
    return run["dispatch"].get("kda_chunked")
