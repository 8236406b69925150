"""laguna-s-2.1: how the cell builds the system under test, its batch and
its FLOPs.  Sizes come from laguna-s-2.1.json (the published config.json's
keys, cut as its `reduced`, `published` and `deployment` say) and from the
traffic file.  One chip of the 32 that share each layer of an
expert-parallel job: the model is tpu_mx.models.decoder.CausalLM with
head-gated grouped-query attention whose query heads differ by layer (48 on
a full layer, which turns half of each head by YaRN-scaled rotary positions;
72 on a layer with a window of 512, which turns the whole head), a leading
dense layer, sigmoid-routed SwiGLU experts beside a shared one, and its
head's loss in chunks; trained by CompiledTrainStep."""
import math

import numpy as np


def depth(cfg):
    return cfg["num_hidden_layers"]


def hyper(cfg):
    """What references/laguna-s-2.1.py takes as `hp`, and the experts held.
    The per-layer lists are the published ones, read as far as the layers
    kept."""
    dep, n, d = cfg["deployment"], depth(cfg), cfg["head_dim"]
    rope = {}
    for kind, p in cfg["rope_parameters"].items():
        rope[kind.split("_")[0]] = dict(
            theta=float(p["rope_theta"]),
            rotary_dim=int(d * p["partial_rotary_factor"]),
            yarn=None if p["rope_type"] == "default" else dict(
                factor=p["factor"],
                original_length=p["original_max_position_embeddings"],
                beta_fast=p["beta_fast"], beta_slow=p["beta_slow"],
                attention_factor=p["attention_factor"]))
    assert set(cfg["gating_types"][:n]) == {"per_head"}
    return dict(heads=tuple(cfg["num_attention_heads_per_layer"][:n]),
                kv_heads=cfg["num_key_value_heads"], head_dim=d,
                sliding=tuple(t == "sliding_attention"
                              for t in cfg["layer_types"][:n]),
                sparse=tuple(t == "sparse"
                             for t in cfg["mlp_layer_types"][:n]),
                window=cfg["sliding_window"], rope=rope,
                eps=cfg["rms_norm_eps"], top_k=cfg["num_experts_per_tok"],
                scaling=cfg["moe_routed_scaling_factor"],
                n_experts=dep["experts_routed_over"],
                logit_stride=cfg["reference_comparison"]["logit_stride"]), \
        tuple(dep["held_experts"])


def model_config(cfg):
    """The published keys under the names CausalLM takes."""
    hp, held = hyper(cfg)
    assert held[1] - held[0] == cfg["num_experts"]
    assert cfg["norm_topk_prob"] and not cfg["attention_bias"] \
        and not cfg["moe_apply_router_weight_on_input"] \
        and not cfg["tie_word_embeddings"] \
        and not cfg["moe_router_logit_softcapping"]
    # the leading dense layers, then an expert layer at every step
    assert hp["sparse"] == tuple(i not in cfg["mlp_only_layers"]
                                 for i in range(depth(cfg)))
    dense = len(cfg["mlp_only_layers"])
    assert hp["sparse"] == (False,) * dense + (True,) * (depth(cfg) - dense)

    def attention(heads, sliding):
        rope = hp["rope"]["sliding" if sliding else "full"]
        return dict(kind="grouped_query", num_heads=heads,
                    num_kv_heads=hp["kv_heads"], head_dim=hp["head_dim"],
                    rope_theta=rope["theta"], rotary_dim=rope["rotary_dim"],
                    yarn=rope["yarn"],
                    window=hp["window"] if sliding else None,
                    rotary_pairs=cfg["system"]["rotary_pairs"], gate=True)
    return dict(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        num_layers=depth(cfg), num_dense_layers=dense,
        dense_hidden=cfg["intermediate_size"], epsilon=hp["eps"],
        attention=[attention(h, s)
                   for h, s in zip(hp["heads"], hp["sliding"])],
        moe=dict(hidden_size=cfg["moe_intermediate_size"],
                 num_experts=hp["n_experts"], top_k=hp["top_k"],
                 held_experts=held, scaling=hp["scaling"],
                 shared_hidden=cfg["shared_expert_intermediate_size"],
                 scoring="sigmoid"),
        loss_chunk=cfg["system"]["loss_chunk"],
        logits_stride=hp["logit_stride"])


def window_pairs(t, window):
    """(query, key) pairs a causal layer scores over t positions: all of the
    past, or the last `window` keys of it."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def flops_parts(cfg, mix):
    """Training FLOPs per sequence by part: matrix multiplications only,
    once each (forward 2 FLOPs a MAC, backward twice that): a layer's four
    projections and its gate's at the layer's own head count, its scores
    and their product with v over exactly the pairs its mask lets through,
    the dense layer's MLP, the routers, the routed experts at the expected
    T·k·held/E rows, the shared experts, the head over every position.
    Embedding look-ups are gathers and are left out; nothing recomputed is
    counted."""
    hp, held = hyper(cfg)
    u, t, d = cfg["hidden_size"], mix["seq_len"], hp["head_dim"]
    sparse = sum(hp["sparse"])
    expert = 3 * u * cfg["moe_intermediate_size"]
    rows = t * hp["top_k"] * (held[1] - held[0]) / hp["n_experts"]
    scores = lambda windowed: sum(
        2 * window_pairs(t, hp["window"] if s else None) * d * h
        for h, s in zip(hp["heads"], hp["sliding"]) if s == windowed)
    macs = dict(
        project=sum(t * u * (2 * d * (h + hp["kv_heads"]) + h)
                    for h in hp["heads"]),
        scores_full=scores(False), scores_window=scores(True),
        dense_mlp=(depth(cfg) - sparse) * t * 3 * u
        * cfg["intermediate_size"],
        router=sparse * t * u * hp["n_experts"],
        routed=sparse * rows * expert,
        shared=sparse * t * 3 * u * cfg["shared_expert_intermediate_size"],
        head=t * u * cfg["vocab_size"])
    return {k: 3 * 2 * v for k, v in macs.items()}


def flops_per_sample(cfg, mix):
    return sum(flops_parts(cfg, mix).values())


def build(cfg, mix, seed, mesh=None):
    import tpu_mx as mx
    from tpu_mx import gluon
    # a program without the gate stops here, by name
    from tpu_mx.models.decoder import ATTENTION_GATE_SCOPES, CausalLM  # noqa: F401
    from tpu_mx.parallel import CompiledTrainStep
    sys_cfg = cfg["system"]
    mx.random.seed(seed % (2 ** 31))
    net = CausalLM(model_config(cfg), mesh=mesh, dtype=sys_cfg["dtype"],
                   remat=sys_cfg["remat"])
    net.initialize(mx.init.Normal(sys_cfg["init_sigma"]))
    # compare() is handed the net and not the configuration
    net._bench_cfg = cfg
    opt = mx.optimizer.create(
        sys_cfg["optimizer"], learning_rate=sys_cfg["learning_rate"],
        beta1=sys_cfg["beta1"], beta2=sys_cfg["beta2"],
        wd=sys_cfg["weight_decay"],
        multi_precision=sys_cfg["multi_precision"])

    def make_step():
        # the objective is computed in the forward: the net's first output
        # is the loss, the batch's last argument is not read
        step = CompiledTrainStep(net, gluon.loss.PassThrough(), opt,
                                 mesh=mesh)
        # a reader is handed the configuration and neither of these
        # (decoder_scopes.census)
        cfg["live"] = {"net": net, "step": step}
        return step
    return net, make_step


def make_batch(cfg, mix, seed, mesh=None):
    """One fixed batch of uniform random ids in [0, vocab) made on the
    device from the seed in one jitted call, every position valid, one
    document a sequence: (tokens, tokens).  The labels are the tokens
    shifted by one, taken inside the forward; the second entry is the
    argument CompiledTrainStep hands to the loss, which PassThrough
    ignores."""
    import jax
    import jax.numpy as jnp
    tokens = jax.jit(lambda key: jax.random.randint(
        key, (mix["batch"], mix["seq_len"]), 0, cfg["vocab_size"],
        jnp.int32))(jax.random.key(seed % (2 ** 31)))
    return tokens, tokens


def loss_center(cfg, mix):
    """ln(vocab) plus half the variance of the random-init logits: a
    normal(0, sigma) head on a unit-RMS hidden state of `hidden_size`."""
    return math.log(cfg["vocab_size"]) \
        + 0.5 * cfg["system"]["init_sigma"] ** 2 * cfg["hidden_size"]


def _params(block, names):
    return {k: getattr(block, v).data()._data for k, v in names.items()}


def _mlp(block):
    return _params(block, {"gate": "gate_proj_weight", "up": "up_proj_weight",
                           "down": "down_proj_weight"})


def _layer(layer):
    out = {"ln1": layer.ln1.gamma.data()._data,
           "ln2": layer.ln2.gamma.data()._data,
           "attn": _params(layer.attention, {
               "q": "q_weight", "k": "k_weight", "v": "v_weight",
               "o": "o_weight", "g": "gate_weight"})}
    if hasattr(layer.ffn, "expert_w1"):
        out["moe"] = dict(_params(layer.ffn, {
            "router": "gate_weight", "bias": "select_bias",
            "w1": "expert_w1", "w3": "expert_w3", "w2": "expert_w2"}),
            shared=_mlp(layer.ffn.shared))
    else:
        out["mlp"] = _mlp(layer.ffn)
    return out


def _layers(net):
    return list(net.layers._children.values())


def weights(net):
    """The system's parameters, as they lie on the device, in the plain
    nested dict that references/laguna-s-2.1.py takes (and casts to float32
    inside its one jitted program)."""
    return {"embed": net.embed_weight.data()._data,
            "head": net.head_weight.data()._data,
            "final_norm": net.final_norm.gamma.data()._data,
            "layers": [_layer(l) for l in _layers(net)]}


def compared_layers(hp):
    """(a full layer, a layer with a window): the first of each kind; the
    full one's W_q and the windowed one's W_g are the ones compared."""
    return hp["sliding"].index(False), hp["sliding"].index(True)


def reference_grads(grads, expert, hp):
    """The five compared gradients, from the reference's gradient tree: the
    last layer's router and there one held expert's down projection, the
    first full layer's W_q (its rotary turn covers half a head, by YaRN's
    frequencies), the first window layer's W_g (the gate of its 72 heads),
    the embedding."""
    full, window = compared_layers(hp)
    return {"grad_router": grads["layers"][-1]["moe"]["router"],
            "grad_expert_down": grads["layers"][-1]["moe"]["w2"][expert],
            "grad_q": grads["layers"][full]["attn"]["q"],
            "grad_gate": grads["layers"][window]["attn"]["g"],
            "grad_embed": grads["embed"]}


def system_grads(net, expert, hp):
    layers = _layers(net)
    full, window = compared_layers(hp)
    return {"grad_router": layers[-1].ffn.gate_weight.grad,
            "grad_expert_down": layers[-1].ffn.expert_w2.grad[expert],
            "grad_q": layers[full].attention.q_weight.grad,
            "grad_gate": layers[window].attention.gate_weight.grad,
            "grad_embed": net.embed_weight.grad}


def _f32(a):
    return np.asarray(getattr(a, "_data", a).astype("float32"))


def _one_hot(chosen, n_experts):
    """(layers, S, k) expert ids as a 0/1 matrix (layers·S, E): the form in
    which a relative RMS error counts the tokens that chose otherwise."""
    flat = np.asarray(chosen).reshape(-1, np.shape(chosen)[-1])
    out = np.zeros((flat.shape[0], n_experts), np.float32)
    np.put_along_axis(out, flat, 1.0, axis=1)
    return out


def window_qkv(tokens, hp, dtype):
    """q (1, heads, T, d), k and v (1, kv_heads, T, d) of a window layer's
    shapes and the model's own type, standard normal, seeded by the batch's
    first ids (so by the run's seed): what the attention call alone is
    compared on."""
    import jax
    import jax.numpy as jnp
    t, d = tokens.shape[1], hp["head_dim"]
    heads = hp["heads"][compared_layers(hp)[1]]
    keys = jax.random.split(jax.random.key(
        int(tokens[0, 0]) * 65536 + int(tokens[0, 1])), 3)
    return tuple(jax.random.normal(key, (1, n, t, d), jnp.dtype(dtype))
                 for key, n in zip(keys, (heads, hp["kv_heads"],
                                          hp["kv_heads"])))


def system_outputs(net, batch, n):
    """What the system gives for the first n sequences of the batch: (the
    compared outputs, what the reference's side needs of them).  One pass
    of its own autograd in training mode through hybridize(), one compiled
    program forward and one backward.  Each expert layer's input leaves
    that program through a forward hook (a host callback, traced into the
    program like the layer itself), and the program's own routing function
    is asked for its choice and weights on exactly those rows.  The
    program's own attention dispatch (`parallel.attention`, on the chip the
    flash kernel with the window and the grouped heads) is asked for its
    output alone, on seeded standard-normal q, k, v of a window layer's
    shapes (72 query heads over 8): one key more or less in a window of 512
    is what nothing end to end shows past its rounding."""
    import functools
    import jax
    from tpu_mx import autograd
    from tpu_mx.ndarray import NDArray
    from tpu_mx.parallel import attention, dropless_route
    cfg = net._bench_cfg
    hp, held = hyper(cfg)
    tokens = np.asarray(batch[0])[:n]
    moes = [l.ffn for l in _layers(net) if hasattr(l.ffn, "select_bias")]
    inputs = {}

    def tap(i):
        def hook(block, args):
            x = args[0]
            jax.debug.callback(
                lambda v: inputs.__setitem__(i, np.asarray(v)),
                getattr(x, "_data", x))
        return hook
    hooks = [m.register_forward_pre_hook(tap(i)) for i, m in enumerate(moes)]
    net.hybridize()
    with autograd.record():
        out = net(NDArray(tokens))
    out[0].backward()
    net.hybridize(False)
    jax.effects_barrier()
    for h in hooks:
        h.detach()

    route = jax.jit(functools.partial(
        dropless_route, top_k=hp["top_k"], scaling=hp["scaling"],
        scoring="sigmoid"))
    xs = [inputs[i].reshape(-1, inputs[i].shape[-1])
          for i in range(len(moes))]
    routed = [route(x, m.gate_weight.data()._data, m.select_bias.data()._data)
              for x, m in zip(xs, moes)]
    chosen = [np.asarray(c) for c, _ in routed]
    # the held expert of the last layer with the most rows: its down
    # projection's gradient is the one compared
    rows = np.bincount(chosen[-1].reshape(-1),
                       minlength=hp["n_experts"])[held[0]:held[1]]
    expert = int(np.argmax(rows))
    # the model's own logits are those of every logit_stride-th position
    got = {"logits": _f32(out[1]), "loss": _f32(out[0])}
    got.update({k: _f32(v) for k, v in system_grads(net, expert, hp).items()})
    got["route_choice"] = _one_hot(chosen, hp["n_experts"])
    got["route_weights"] = np.concatenate([_f32(w) for _, w in routed])
    qkv = window_qkv(tokens, hp, cfg["system"]["dtype"])
    got["attend_window"] = _f32(jax.jit(functools.partial(
        attention, causal=True, window=hp["window"]))(*qkv))
    return got, {"tokens": tokens, "expert": expert, "inputs": xs,
                 "chosen": chosen, "window_qkv": qkv}


def _reference_program(reference, cfg, low, expert):
    """One compiled program for the reference's side, whatever the wrong
    variant (a traced index into reference.WRONG, -1 for none)."""
    import jax
    import jax.numpy as jnp
    hp, held = hyper(cfg)
    dtype = jnp.bfloat16 if low == "all" else jnp.float32

    def program(weights, tokens, inputs, chosen, window_qkv, wrong):
        out, grads = reference.loss_and_grads(
            weights, tokens, hp=hp, held=held, wrong=wrong, low=low,
            forced=chosen)
        want = {"logits": out["logits"], "loss": out["loss"]}
        want.update(reference_grads(grads, expert, hp))
        # the routing alone, on the rows the system's routers read: its
        # free choice, and its weights for the choice the system made
        moes = [p["moe"] for p in weights["layers"] if "moe" in p]
        free, weight = [], []
        with jax.default_matmul_precision(
                "default" if low == "all" else "highest"):
            for p, x, c in zip(moes, inputs, chosen):
                p = {k: p[k].astype(dtype) for k in ("router", "bias")}
                free.append(reference.route(x.astype(dtype), p, hp, held,
                                            wrong, low)[0])
                weight.append(reference.route(x.astype(dtype), p, hp, held,
                                              wrong, low, forced=c)[1])
            # and a window layer's attention alone, on the same q, k, v
            want["attend_window"] = reference.attend(
                *(a.astype(dtype) for a in window_qkv), hp, True, wrong)
        want["route_choice"] = jnp.stack(free)
        want["route_weights"] = jnp.concatenate(weight)
        return want
    return jax.jit(program)


def reference_outputs(reference, net, aux, wrong=None, low=None,
                      programs=None):
    """The reference's side of the same outputs, on the system's weights
    and with the system's choice of experts in place of its own (the choice
    is a step function of the scores: a bf16 program moves a few tokens in
    a hundred across its boundary, and an error made of such flips says
    nothing of the mathematics); the choice itself is held to the
    reference's routing on the rows the system's own routers read.
    `programs`, a dict, keeps the compiled program between calls (a script
    that reads every wrong variant compiles once)."""
    hp, _ = hyper(net._bench_cfg)
    programs = {} if programs is None else programs
    key = (low, aux["expert"])
    if key not in programs:
        programs[key] = _reference_program(reference, net._bench_cfg, *key)
    index = -1 if wrong is None else reference.WRONG.index(wrong)
    want = programs[key](weights(net), aux["tokens"], aux["inputs"],
                         aux["chosen"], aux["window_qkv"], np.int32(index))
    return {k: _one_hot(v, hp["n_experts"]) if k == "route_choice"
            else np.asarray(v, np.float32) for k, v in want.items()}


def compare(reference, net, batch, n, wrong=None, low=None):
    """(system, reference) for the first n sequences of the batch: the
    logits at every `logit_stride`-th position, the loss, five gradients of
    it (reference_grads says which), the routing of every expert layer on
    the rows the system's own routers read (who is chosen, and the chosen's
    weights), and a window layer's attention alone on seeded q, k, v."""
    got, aux = system_outputs(net, batch, n)
    return got, reference_outputs(reference, net, aux, wrong, low)
