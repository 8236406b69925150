"""Layer: token mixer.  Device trace, first device: time per step in the
operations under `kda.project`: Kimi Delta Attention's projections into q,
k, v and beta, the low-rank paths of the decay and the output gate, the
output norm and gate and W_o, forward, recomputed and backward, in every
KDA layer, in ms."""
import kda_scopes
import pass_scopes


def read(run):
    return pass_scopes.scope_ms(run["trace"], (kda_scopes.KDA_PROJECT,))
