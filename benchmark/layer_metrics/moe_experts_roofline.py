"""Layer: kernels.  Share (%) of its roofline that the expert layers'
grouped work reaches: the least time the REAL rows' work needs on this
chip, over `moe_experts_ms`, all the time the step spends under
`moe.experts`, its recomputed forward products with it (recomputation is the
program's choice, and its cost is the program's).

Work, from shapes and from the rows the program counted, a layer: FLOPs 6 x
rows x 3 x hidden x expert width (three matrices, forward 2 and backward 4
a MAC); bytes: the held experts' three matrices read forward and backward
and their gradients written once (bf16), each row's input, two
intermediates, their product and the output written and read once forward
and twice backward.  The need is the larger of FLOPs over the bf16 peak and
bytes over the HBM peak, summed over the expert layers: the same work
whatever implements the product.

The rows are those of the TRACED steps: a router that learns its one batch
moves its load from step to step (in this cell the rows routed here double
within a window), so the layers' `expert_load` history is read back through
census() and the steps that the trace holds are found in it by counting
back over the steps the window ran after them."""
import decoder_scopes


def traced_rows(run, census):
    """Mean rows a step routed to the held experts in the traced steps, a
    layer; None where the history does not reach back to them."""
    traced = len(decoder_scopes.step_ops(run["trace"])[0])
    after = len(run["first_enqueue_s"]) * run["mix"]["block_steps"]
    rows = []
    for c in census:
        history = c["rows_routed_here_history"]
        if len(history) < after + traced:
            return None
        first = len(history) - after - traced
        rows.append(sum(history[first:first + traced]) / traced)
    return rows


def layer_need_s(rows, held, hidden, width, peaks):
    flops = 6 * rows * 3 * hidden * width
    weights = 3 * held * hidden * width * 2
    per_row = 2 * (2 * hidden + 3 * width)
    bytes_ = 3 * weights + 3 * rows * per_row
    return max(flops / peaks["bf16_flops_per_s"],
               bytes_ / peaks["hbm_bytes_per_s"])


def read(run):
    took = decoder_scopes.scope_ms(run["trace"],
                                   (decoder_scopes.MOE_EXPERTS,), grouped=True)
    census = took and run["peaks"] and decoder_scopes.census(run)
    rows = census and traced_rows(run, census)
    if not rows:
        return None
    cfg = run["cfg"]
    need = sum(layer_need_s(r, c["held"][1] - c["held"][0],
                            cfg["hidden_size"], cfg["moe_intermediate_size"],
                            run["peaks"]) for r, c in zip(rows, census))
    return 100.0 * need / (took / 1e3)
