"""Layer: token mixer.  Device trace, first device: time per step in the
operations under `kda.scan`: Kimi Delta Attention's decays, beta and the
chunked delta rule (the scan over chunks with the WY form inside each),
forward, recomputed and backward, in every KDA layer, in ms."""
import kda_scopes
import pass_scopes


def read(run):
    return pass_scopes.scope_ms(run["trace"], (kda_scopes.KDA_SCAN,))
