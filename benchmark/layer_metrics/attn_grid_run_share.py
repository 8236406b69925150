"""Layer: attention dispatch.  Program counter: of the steps that the forward
grids of the flash calls with a window walk, the share (%) that run.  Under a
window the kernels' innermost grid axis is the band of key blocks that a query
block's windows can touch, not the whole row of the square, so all but a few
steps run: 31 of 32 at T 8,192 under W 512 in blocks of 512 x 512 (96.9), 140
of 160 at T 16,384 under W 4,096 in 512 x 1,024 (87.5); a grid that walked
the square would read attn_blocks_run_share's number (12.1, 27.3).  It says
without a trace whether the band engaged.
tpu_mx.parallel.ring_attention.window_blocks["walked"], counted where a call
is traced; like attn_blocks_run_share this reader asks the program itself.  A
program whose counter has no such kind (the parent of the PR that brought
it), or a cell without a windowed flash call, reports nothing."""


def read(run):
    try:
        from tpu_mx.parallel.ring_attention import window_blocks
    except ImportError:
        return None
    if not window_blocks.get("walked"):
        return None
    return 100.0 * window_blocks["run"] / window_blocks["walked"]
