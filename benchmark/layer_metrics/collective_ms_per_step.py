"""Layer: sharding.  Device trace, first device: time per step in
collectives (by hlo_category or instruction name; on `Async XLA Ops` whole
from start to done, else on `XLA Ops`), in ms."""
import scopes


def read(run):
    return scopes.collective_ms(run["trace"])
