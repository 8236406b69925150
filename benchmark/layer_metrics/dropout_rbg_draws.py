"""Layer: kernels.  How many dropout masks this process drew through the
one helper that takes their bits from XLA's rng_bit_generator (the TPU's
hardware generator) and not from threefry: tpu_mx.random.mask_draws["rbg"],
counted where the draw is traced, so once a site, forward and backward, a
compilation of the step (the reference comparison runs with dropout off and
adds nothing).  run.py hands a reader attention's dispatch counts only, so
this one asks the program itself; a program without the counter (the parent
of the PR that brought it) or a cell without a dropout site reports
nothing."""


def read(run):
    import tpu_mx.random
    draws = getattr(tpu_mx.random, "mask_draws", {}).get("rbg", 0)
    return draws or None
