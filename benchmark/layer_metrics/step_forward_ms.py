"""Layer: compiled step.  Device trace, first device: time per step in the
operations that the program names train_step.grad/... without transpose(
(the forward pass as JAX names it), in ms.  Operations as named: a fusion
counts where its root's op path lies."""
import scopes


def read(run):
    return scopes.scope_ms(run["trace"], (scopes.GRAD,), transposed=False)
