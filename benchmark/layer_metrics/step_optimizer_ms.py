"""Layer: compiled step.  Device trace, first device: time per step in the
operations under train_step.optimizer/ (the update loops, as far as XLA
left them unfused) and train_step.grad_accum/ (the K>1 fold), in ms."""
import scopes


def read(run):
    return scopes.scope_ms(run["trace"],
                           (scopes.OPTIMIZER, scopes.GRAD_ACCUM))
