"""Layer: token mixer.  Share (%) of its roofline that the chunked delta
rule reaches: the least time its work needs on this chip, summed over the
KDA layers kept, over all the time the step spends under `kda.scan`
(`kda_scan_ms`, its recomputed forward with it: recomputation is the
program's choice, and its cost is the program's).

Work, from shapes, at chunks of C = 64 whatever chunk the program takes,
for each (sequence, head, chunk) of head size d: forward 4 C^2 d (the
decayed q k^T and k k^T) + 4 C^2 d (their products with the values) + 2/3
C^3 (the triangular solve) + 6 C d^2 (the state's three products) FLOPs,
the backward twice that and the recomputed forward once more; bytes, in
each of the three passes (forward, recomputed, backward), q, k, v and o in
bf16 and g in f32 read or written once, and the chunk states in f32
written once and read once.  The need is the larger of FLOPs over the bf16
peak and bytes over the HBM peak.  The KDA layers are the configuration's
published `linear_attn_config.kda_layers` (counted from 1), read from
`first_layer_kept` as far as the layers kept; a configuration without them
reports nothing."""
import kda_scopes
import pass_scopes

CHUNK = 64


def layer_flops(sequences, tokens, heads, d, c=CHUNK):
    chunks = sequences * heads * tokens / c
    return 4 * chunks * (8 * c * c * d + 2 * c ** 3 / 3 + 6 * c * d * d)


def layer_bytes(sequences, tokens, heads, d, c=CHUNK):
    n = sequences * tokens * heads * d
    states = sequences * heads * tokens / c * d * d
    return 3 * n * (4 * 2 + 4) + 2 * 4 * states


def kda_layers(cfg):
    """How many of the layers kept are KDA layers."""
    first = cfg.get("deployment", {}).get("first_layer_kept", 0)
    listed = cfg["linear_attn_config"]["kda_layers"]
    return sum(i + 1 in listed
               for i in range(first, first + cfg["num_hidden_layers"]))


def need_s(cfg, mix, peaks):
    lin = cfg["linear_attn_config"]
    shape = (mix["batch"], mix["seq_len"], lin["num_heads"], lin["head_dim"])
    return kda_layers(cfg) * max(
        layer_flops(*shape) / peaks["bf16_flops_per_s"],
        layer_bytes(*shape) / peaks["hbm_bytes_per_s"])


def read(run):
    took = pass_scopes.scope_ms(run["trace"], (kda_scopes.KDA_SCAN,))
    cfg = run["cfg"]
    if not took or not run["peaks"] or "linear_attn_config" not in cfg:
        return None
    return 100.0 * need_s(cfg, run["mix"], run["peaks"]) / (took / 1e3)
