"""Layer: compiled step.  Device trace, first device: time per step in the
operations under train_step.fingerprint/ (the SDC digest over the updated
parameters, tpu_mx/parallel/integrity.py), in ms."""
import scopes


def read(run):
    return scopes.scope_ms(run["trace"], (scopes.FINGERPRINT,))
