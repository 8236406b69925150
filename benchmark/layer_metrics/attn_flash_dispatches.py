"""Layer: attention dispatch.  How many attention call signatures the
dispatch sent to the Pallas flash kernel while this cell was built and
warmed up (ring_attention.dispatch_counts["pallas_flash"], counted at trace
time).  0 means every attention ran as dense XLA."""


def read(run):
    return run["dispatch"]["pallas_flash"]
