"""Layer: routing.  Program counter: the fullest held expert's rows over
the mean of the held experts' rows, the largest over the expert layers, in
the last step run (census()).  1 is perfect balance; the grouped
products' tiles and, in a deployment, the slowest chip follow it."""
import decoder_scopes


def read(run):
    census = decoder_scopes.census(run) or []
    ratios = [c["max_expert_load"] * (c["held"][1] - c["held"][0])
              / c["rows_routed_here"] for c in census if c["rows_routed_here"]]
    return max(ratios) if ratios else None
