"""Layer: attention dispatch.  Device trace, first device: time per step in
the operations under `attn.project` or `mla.project`: the projections into
queries, keys and values (through the latent and its norms where the layer
has one) and the output projection, forward, backward and recomputed, in
ms."""
import pass_scopes


def read(run):
    return pass_scopes.scope_ms(run["trace"], pass_scopes.PROJECT)
