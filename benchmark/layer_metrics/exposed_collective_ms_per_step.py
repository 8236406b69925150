"""Layer: sharding.  Device trace, first device: the part of the
collectives' intervals (collective_ms_per_step) in which no other operation
of the same chip runs, per step, in ms: communication that hides behind
nothing."""
import scopes


def read(run):
    return scopes.collective_ms(run["trace"], exposed=True)
