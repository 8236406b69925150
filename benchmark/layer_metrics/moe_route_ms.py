"""Layer: routing.  Device trace, first device: time per step in the
operations under `moe.route` (scores, choice, sort and gather into the
expert-ordered buffer) and `moe.combine` (back to token order, weighted
sum), forward, backward and recomputed, in ms."""
import decoder_scopes


def read(run):
    return decoder_scopes.scope_ms(
        run["trace"], (decoder_scopes.MOE_ROUTE, decoder_scopes.MOE_COMBINE))
