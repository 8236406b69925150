"""Layer: compiled step.  Device trace, first device: time per step in the
operations that the program names train_step.grad/...transpose(...) (the
backward pass; an optimizer update that XLA fuses into a weight-gradient
dot carries the dot's name and counts here), in ms."""
import scopes


def read(run):
    return scopes.scope_ms(run["trace"], (scopes.GRAD,), transposed=True)
