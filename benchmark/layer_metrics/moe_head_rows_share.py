"""Layer: routing.  Program counter: the rows of the expert layers' sorted
order that really ran, over all the (token, choice) pairs of a step, the
worst case that every buffer held before (%): one slab of `head_rows`
(census()) always, and one more for each `head_rows` that the rows routed
here reached past it, mean over the (layer, step) pairs of the layers' kept
history.  It is what the gathers, the clearing of unowned rows, the gated
activation's passes and the adding back cost against the worst case:
`head_rows` over the pairs (50 and 25 in the two decoder cells) while no
step passes the first slab, more where some do.  A program whose census
names no `head_rows` reports nothing."""
import decoder_scopes


def read(run):
    census = decoder_scopes.census(run) or []
    shares = []
    for c in census:
        head, pairs = c.get("head_rows"), sum(c["expert_load"])
        if head and pairs:
            shares += [min(pairs, max(1, -(-rows // head)) * head) / pairs
                       for rows in c["rows_routed_here_history"]]
    return 100.0 * sum(shares) / len(shares) if shares else None
