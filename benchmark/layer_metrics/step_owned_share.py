"""Layer: compiled step.  Device trace, first device: share (%) of the
device time of the traced stretch's leaf operations (a `while` or a
`conditional` counts through its inside, never itself) that lies under a
name the program gives a part of the step (pass_scopes.OWNERS: the decoder
blocks', the attention's, the gate's, the dense MLP's, the step's own but
for `train_step.grad`) or is a grouped product: how much of a step the
per-layer metrics can own.  The rest is norms, residuals, the embedding
and whatever else has no name yet."""
import pass_scopes


def read(run):
    return pass_scopes.owned_share(run["trace"])
