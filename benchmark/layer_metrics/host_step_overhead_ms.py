"""Layer: training entry.  Profiler trace, host plane: median over the
traced steps of the tpu_mx/train_step span less its dispatch child, in ms:
the program's own Python per step."""
import scopes


def read(run):
    return scopes.host_step_overhead_ms(run["trace"])
