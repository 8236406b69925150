"""Layer: kernels.  How many dropout sites hold their keep mask from the
forward to the backward pass and do not draw it a second time:
tpu_mx.random.mask_draws["held"], counted where such a site is traced, so
once a site a compilation of the step.  The dense attention site holds (12
in bert-base.mlm128: its mask takes the room of the row maximum's tie mask,
which the site no longer holds); the hidden sites and the flash kernel's do
not.  Made like dropout_rbg_draws: run.py hands a reader attention's
dispatch counts only, so this one asks the program itself; a program
without the counter (the parent of the PR that brought it) or a cell in
which no site holds reports nothing."""


def read(run):
    import tpu_mx.random
    held = getattr(tpu_mx.random, "mask_draws", {}).get("held", 0)
    return held or None
