"""Layer: routing.  Program counter: share (%) of the (expert layer, step)
pairs in the layers' kept history (census(): the last 64 training steps a
layer) whose rows routed here exceeded the layer's `head_rows`, the rows of
one slab of the sorted order: in those steps the loop over slabs ran more
than once (moe_head_rows_share says how many rows in all).  0 where the
router is balanced to within twice the mean; a program whose census names
no `head_rows` (one that runs the whole worst case every step) reports
nothing."""
import decoder_scopes


def read(run):
    census = decoder_scopes.census(run) or []
    pairs = [rows > c["head_rows"] for c in census if "head_rows" in c
             for rows in c["rows_routed_here_history"]]
    return 100.0 * sum(pairs) / len(pairs) if pairs else None
