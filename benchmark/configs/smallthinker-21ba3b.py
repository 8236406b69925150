"""smallthinker-21ba3b: how the cell builds the system under test, its batch
and its FLOPs.  Sizes come from smallthinker-21ba3b.json (the published
config.json's keys, cut as its `reduced`, `published` and `deployment` say)
and from the traffic file.  One chip of the four that share each layer of
an expert-parallel job: the model is tpu_mx.models.decoder.CausalLM with
grouped-query attention (a global layer without positions, then three
rotary layers over a window), ReGLU experts behind a softmax router fed
from before the attention, and its head's loss in chunks; trained by
CompiledTrainStep."""
import math

import numpy as np


def depth(cfg):
    return cfg["num_hidden_layers"]


def hyper(cfg):
    """What references/smallthinker-21ba3b.py takes as `hp`, and the experts
    held.  The two layouts are the published lists, read as far as the
    layers kept."""
    dep, n = cfg["deployment"], depth(cfg)
    return dict(heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"], theta=float(cfg["rope_theta"]),
                window=cfg["sliding_window_size"],
                rope_layout=tuple(cfg["rope_layout"][:n]),
                window_layout=tuple(cfg["sliding_window_layout"][:n]),
                eps=cfg["rms_norm_eps"],
                top_k=cfg["moe_num_active_primary_experts"],
                n_experts=dep["experts_routed_over"],
                logit_stride=cfg["reference_comparison"]["logit_stride"]), \
        tuple(dep["held_experts"])


def model_config(cfg):
    """The published keys under the names CausalLM takes."""
    hp, held = hyper(cfg)
    assert held[1] - held[0] == cfg["moe_num_primary_experts"]
    assert cfg["moe_primary_router_apply_softmax"] and cfg["norm_topk_prob"]
    return dict(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        num_layers=depth(cfg), num_dense_layers=0, epsilon=hp["eps"],
        # a layer each, by the two published layouts: positions or none,
        # the window or the whole past
        attention=[dict(kind="grouped_query", num_heads=hp["heads"],
                        num_kv_heads=hp["kv_heads"], head_dim=hp["head_dim"],
                        rope_theta=hp["theta"] if turned else None,
                        window=hp["window"] if windowed else None,
                        rotary_pairs=cfg["system"]["rotary_pairs"])
                   for turned, windowed in zip(hp["rope_layout"],
                                               hp["window_layout"])],
        moe=dict(hidden_size=cfg["moe_ffn_hidden_size"],
                 num_experts=hp["n_experts"], top_k=hp["top_k"],
                 held_experts=held, scoring="softmax", activation="relu",
                 router_before_attention=True),
        loss_chunk=cfg["system"]["loss_chunk"],
        logits_stride=hp["logit_stride"])


def window_pairs(t, window):
    """(query, key) pairs a causal layer scores over t positions: all of the
    past, or the last `window` keys of it."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def flops_per_sample(cfg, mix):
    """Training FLOPs per sequence: matrix multiplications only, once each
    (forward 2 FLOPs a MAC, backward twice that): a layer's four
    projections and its router, its scores and their product with v over
    exactly the pairs its mask lets through, the routed experts at the
    expected T·k·held/E rows, the head over every position.  Embedding
    look-ups are gathers and are left out; nothing recomputed is counted."""
    hp, held = hyper(cfg)
    u, t, d = cfg["hidden_size"], mix["seq_len"], hp["head_dim"]
    project = 2 * u * d * (hp["heads"] + hp["kv_heads"]) + u * hp["n_experts"]
    pairs = sum(window_pairs(t, hp["window"] if w else None)
                for w in hp["window_layout"])
    rows = t * hp["top_k"] * (held[1] - held[0]) / hp["n_experts"]
    macs = depth(cfg) * (project * t + rows * 3 * u
                         * cfg["moe_ffn_hidden_size"]) \
        + 2 * pairs * d * hp["heads"] + t * u * cfg["vocab_size"]
    return 3 * 2 * macs


def build(cfg, mix, seed, mesh=None):
    import tpu_mx as mx
    from tpu_mx import gluon
    # a program without the grouped-query block stops here, by name
    from tpu_mx.models.decoder import CausalLM, GroupedQueryAttention  # noqa: F401
    from tpu_mx.parallel import CompiledTrainStep
    sys_cfg = cfg["system"]
    mx.random.seed(seed % (2 ** 31))
    net = CausalLM(model_config(cfg), mesh=mesh, dtype=sys_cfg["dtype"],
                   remat=sys_cfg["remat"])
    net.initialize(mx.init.Normal(sys_cfg["init_sigma"]))
    # `assumed`: the embedding and the projections that write into the
    # residual stream have spreads of their own, each one factor on the draw
    def rescale(weight, sigma):
        weight.set_data(weight.data() * (sigma / sys_cfg["init_sigma"]))
    rescale(net.embed_weight, sys_cfg["init_sigma_embedding"])
    for layer in net.layers._children.values():
        for weight in (layer.attention.o_weight, layer.ffn.expert_w2):
            rescale(weight, sys_cfg["init_sigma_residual"])
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


def _layer(layer):
    return {"ln1": layer.ln1.gamma.data()._data,
            "ln2": layer.ln2.gamma.data()._data,
            "attn": _params(layer.attention, {
                "q": "q_weight", "k": "k_weight", "v": "v_weight",
                "o": "o_weight"}),
            "moe": _params(layer.ffn, {
                "router": "gate_weight", "bias": "select_bias",
                "w1": "expert_w1", "w3": "expert_w3", "w2": "expert_w2"})}


def weights(net):
    """The system's parameters, as they lie on the device, in the plain
    nested dict that references/smallthinker-21ba3b.py takes (and casts to
    float32 inside its one jitted program)."""
    return {"embed": net.embed_weight.data()._data,
            "head": net.head_weight.data()._data,
            "final_norm": net.final_norm.gamma.data()._data,
            "layers": [_layer(l) for l in net.layers._children.values()]}


def _layers(net):
    return list(net.layers._children.values())


def reference_grads(grads, expert):
    """The five compared gradients, from the reference's gradient tree:
    the last layer's router and there one held expert's down projection, a
    window layer's W_k (layer 1: each of its heads' gradient is a sum over
    the query heads that read it), the global layer's W_q, the embedding."""
    return {"grad_router": grads["layers"][-1]["moe"]["router"],
            "grad_expert_down": grads["layers"][-1]["moe"]["w2"][expert],
            "grad_k": grads["layers"][1]["attn"]["k"],
            "grad_q": grads["layers"][0]["attn"]["q"],
            "grad_embed": grads["embed"]}


def system_grads(net, expert):
    layers = _layers(net)
    return {"grad_router": layers[-1].ffn.gate_weight.grad,
            "grad_expert_down": layers[-1].ffn.expert_w2.grad[expert],
            "grad_k": layers[1].attention.k_weight.grad,
            "grad_q": layers[0].attention.q_weight.grad,
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
    """q (1, heads, T, d), k and v (1, kv_heads, T, d) of the model's own
    type, standard normal, seeded by the batch's first ids (so by the run's
    seed): what the attention call alone is compared on."""
    import jax
    import jax.numpy as jnp
    t, d = tokens.shape[1], hp["head_dim"]
    keys = jax.random.split(jax.random.key(
        int(tokens[0, 0]) * 65536 + int(tokens[0, 1])), 3)
    return tuple(jax.random.normal(key, (1, n, t, d), jnp.dtype(dtype))
                 for key, n in zip(keys, (hp["heads"], hp["kv_heads"],
                                          hp["kv_heads"])))


def system_outputs(net, batch, n):
    """What the system gives for the first n sequences of the batch: (the
    compared outputs, what the reference's side needs of them).  One pass
    of its own autograd in training mode through hybridize(), one compiled
    program forward and one backward.  What each layer's ROUTER reads (the
    layer's raw input: the expert layer's second argument) leaves that
    program through a forward hook (a host callback, traced into the
    program like the layer itself), and the program's own routing function
    is asked for its choice and weights on exactly those rows.  The
    program's own attention dispatch (`parallel.attention`, on the chip the
    flash kernel with the window and the grouped heads) is asked for its
    output alone, on seeded standard-normal q, k, v of a window layer's
    shapes: one key more or less in a window of 4,096 is below the rounding
    of anything end to end (and of the layer's own q, k, v at random
    weights, whose values a global layer without positions before it has
    made nearly alike: 0.00217 against the honest 0.00168, my chip run, PR
    31, call 2b), and several times the kernel's own rounding here."""
    import functools
    import jax
    from tpu_mx import autograd
    from tpu_mx.ndarray import NDArray
    from tpu_mx.parallel import attention, dropless_route
    cfg = net._bench_cfg
    hp, held = hyper(cfg)
    tokens = np.asarray(batch[0])[:n]
    moes, inputs = [l.ffn for l in _layers(net)], {}

    def tap(i):
        def hook(block, args):
            x = args[1]
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
        dropless_route, top_k=hp["top_k"], scoring="softmax"))
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
    got.update({k: _f32(v) for k, v in system_grads(net, expert).items()})
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
        want.update(reference_grads(grads, expert))
        # the routing alone, on the rows the system's routers read: its
        # free choice, and its weights for the choice the system made
        free, weight = [], []
        with jax.default_matmul_precision(
                "default" if low == "all" else "highest"):
            for p, x, c in zip(weights["layers"], inputs, chosen):
                p = {k: p["moe"][k].astype(dtype) for k in ("router", "bias")}
                free.append(reference.route(x.astype(dtype), p, hp, held,
                                            wrong, low)[0])
                weight.append(reference.route(x.astype(dtype), p, hp, held,
                                              wrong, low, forced=c)[1])
            # and a window layer's attention alone, on the same q, k, v
            want["attend_window"] = reference.attend(
                *(a.astype(dtype) for a in window_qkv), hp, 1, wrong)
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
    it (reference_grads says which), the routing of every layer on the
    rows the system's own routers read (who is chosen, and the chosen's
    weights), and a window layer's attention alone on seeded q, k, v."""
    got, aux = system_outputs(net, batch, n)
    return got, reference_outputs(reference, net, aux, wrong, low)
