"""Layer: kernels.  Share (%) of its roofline that the flash kernels of the
window layers reach where the layers' query heads differ from layer to
layer: the least time their scores' work needs on this chip, summed over
the window layers kept, each at its OWN head count, over the time of the
`pallas_call` operations under `attn.window`, the recomputed forward kernel
with them (recomputation is the program's choice, and its cost is the
program's).

Work, from shapes, a window layer and a sequence: the (query, key) pairs
that the mask lets through, W(W+1)/2 + (T - W)·W for a window W < T, exactly,
so it is the same work whatever implements it and whatever blocks it runs
in; FLOPs 12 x pairs x head size x query heads (two products, q·k and p·v,
forward 2 and backward 4 a MAC); bytes, in bf16: the forward pass reads q,
k, v and writes o, the backward pass reads q, k, v, o, do and writes dq, dk,
dv, each once: six passes over a head's rows, query and key/value heads
alike.  The need is the larger of FLOPs over the bf16 peak and bytes over
the HBM peak.

The layers and their heads come from the configuration's published
per-layer lists (`layer_types`, `num_attention_heads_per_layer`), read as
far as the layers kept; a configuration without them reports nothing."""
import attention_scopes

WINDOW = "sliding_attention"


def pairs(t, window):
    """(query, key) pairs of a causal layer over t positions whose queries
    see the last `window` keys, themselves among them."""
    if window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def layer_need_s(t, window, head_dim, heads, kv_heads, peaks):
    flops = 12 * pairs(t, window) * head_dim * heads
    bytes_ = 6 * t * head_dim * 2 * (heads + kv_heads)
    return max(flops / peaks["bf16_flops_per_s"],
               bytes_ / peaks["hbm_bytes_per_s"])


def window_heads(cfg):
    """The query heads of each window layer kept."""
    n = cfg["num_hidden_layers"]
    return [h for h, kind in zip(cfg["num_attention_heads_per_layer"][:n],
                                 cfg["layer_types"][:n]) if kind == WINDOW]


def need_s(cfg, mix, peaks):
    return mix["batch"] * sum(
        layer_need_s(mix["seq_len"], cfg["sliding_window"], cfg["head_dim"],
                     heads, cfg["num_key_value_heads"], peaks)
        for heads in window_heads(cfg))


def read(run):
    took = attention_scopes.scope_ms(
        run["trace"], (attention_scopes.ATTN_WINDOW,), kernels=True)
    cfg = run["cfg"]
    if not took or not run["peaks"] \
            or "num_attention_heads_per_layer" not in cfg:
        return None
    return 100.0 * need_s(cfg, run["mix"], run["peaks"]) / (took / 1e3)
