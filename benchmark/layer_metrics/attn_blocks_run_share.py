"""Layer: attention dispatch.  Program counter: of the (q block, k block)
pairs in the grids of the flash calls that have a window, the share (%) that
run; the rest lie above the diagonal or wholly before every query's window
and are skipped.  tpu_mx.parallel.ring_attention.window_blocks, counted
where a call is traced, so it says without a trace whether the skip is
there: a kernel that ran the whole causal triangle of T 16,384 in blocks of
512 x 1,024 would read 53.1, one that skips before a window of 4,096 reads
27.3.  run.py hands a reader the dispatch counts only, so this one asks the
program itself, like dropout_rbg_draws; a program without the counter (the
parent of the PR that brought it), or a cell without a windowed flash call,
reports nothing."""


def read(run):
    try:
        from tpu_mx.parallel.ring_attention import window_blocks
    except ImportError:
        return None
    if not window_blocks["grid"]:
        return None
    return 100.0 * window_blocks["run"] / window_blocks["grid"]
