"""Layer: token mixer.  Device trace, first device: time per step in the
operations under `kda.conv`: Kimi Delta Attention's three causal filters of
4 taps with their SiLU and the L2 norms of q and k, forward, recomputed and
backward, in every KDA layer, in ms."""
import kda_scopes
import pass_scopes


def read(run):
    return pass_scopes.scope_ms(run["trace"], (kda_scopes.KDA_CONV,))
