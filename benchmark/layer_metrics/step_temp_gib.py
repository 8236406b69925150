"""Layer: compiled step.  The step program's temporaries (activations kept
for the backward pass, mostly), from the compiler:
aot_compiled(...).memory_analysis().temp_size_in_bytes, per chip, in GiB."""


def read(run):
    return run["step_temp_bytes"] / 2 ** 30
