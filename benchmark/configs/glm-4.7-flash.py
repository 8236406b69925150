"""glm-4.7-flash: how the cell builds the system under test, its batch and
its FLOPs.  Sizes come from glm-4.7-flash.json (the published config.json's
keys, cut as its `reduced`, `published` and `deployment` say) and from the
traffic file.  One chip's stage of an 8-way expert-parallel job: the model
is tpu_mx.models.decoder.CausalLM, trained by CompiledTrainStep."""
import math

import numpy as np


def hyper(cfg):
    """What references/glm-4.7-flash.py takes as `hp`, and the experts held."""
    dep = cfg["deployment"]
    return dict(heads=cfg["num_attention_heads"],
                nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
                v_dim=cfg["v_head_dim"], theta=float(cfg["rope_theta"]),
                eps=cfg["rms_norm_eps"], top_k=cfg["num_experts_per_tok"],
                scaling=cfg["routed_scaling_factor"],
                n_experts=dep["experts_routed_over"],
                mtp_lambda=cfg["system"]["mtp_weight"]), \
        tuple(dep["held_experts"])


def model_config(cfg):
    """The published keys under the names CausalLM takes."""
    hp, held = hyper(cfg)
    assert held[1] - held[0] == cfg["n_routed_experts"]
    return dict(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_dense_layers=cfg["first_k_dense_replace"],
        dense_hidden=cfg["intermediate_size"], epsilon=cfg["rms_norm_eps"],
        attention=dict(num_heads=hp["heads"], q_rank=cfg["q_lora_rank"],
                       kv_rank=cfg["kv_lora_rank"], nope_dim=hp["nope"],
                       rope_dim=hp["rope"], v_dim=hp["v_dim"],
                       rope_theta=hp["theta"]),
        moe=dict(hidden_size=cfg["moe_intermediate_size"],
                 num_experts=hp["n_experts"], top_k=hp["top_k"],
                 held_experts=held, scaling=hp["scaling"],
                 shared_hidden=cfg["n_shared_experts"]
                 * cfg["moe_intermediate_size"]),
        mtp_depth=cfg["num_nextn_predict_layers"],
        mtp_weight=hp["mtp_lambda"])


def flops_per_sample(cfg, mix):
    """Training FLOPs per sequence: matrix multiplications only, once each
    (forward 2 FLOPs a MAC, backward twice that), the causal scores at half
    of T^2, the routed experts at the expected T·k·held/E rows, the head
    over every position; the multi-token module is one more expert layer,
    its joining projection and a second pass of the head.  Embedding
    look-ups are gathers and are left out; nothing recomputed is counted."""
    hp, held = hyper(cfg)
    u, t = cfg["hidden_size"], mix["seq_len"]
    h, dn, dr, dv = hp["heads"], hp["nope"], hp["rope"], hp["v_dim"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    mtp = cfg["num_nextn_predict_layers"]
    layers = cfg["num_hidden_layers"] + mtp
    sparse = layers - cfg["first_k_dense_replace"]
    project = u * rq + rq * h * (dn + dr) + u * (rkv + dr) \
        + rkv * h * (dn + dv) + h * dv * u
    scores = h * (t / 2) * (dn + dr + dv)
    expert = 3 * u * cfg["moe_intermediate_size"]
    routed = hp["top_k"] * (held[1] - held[0]) / hp["n_experts"] * expert \
        + u * hp["n_experts"]
    macs = layers * (project + scores) \
        + cfg["first_k_dense_replace"] * 3 * u * cfg["intermediate_size"] \
        + sparse * (cfg["n_shared_experts"] * expert + routed) \
        + (1 + mtp) * u * cfg["vocab_size"] + mtp * 2 * u * u
    return 3 * 2 * macs * t


def build(cfg, mix, seed, mesh=None):
    import tpu_mx as mx
    from tpu_mx import gluon
    from tpu_mx.models.decoder import CausalLM
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
    device from the seed in one jitted call, every position valid: (tokens,
    tokens).  The labels are the tokens shifted by one (main) and by two
    (multi-token), taken inside the forward; the second entry is the
    argument CompiledTrainStep hands to the loss, which PassThrough
    ignores."""
    import jax
    import jax.numpy as jnp
    tokens = jax.jit(lambda key: jax.random.randint(
        key, (mix["batch"], mix["seq_len"]), 0, cfg["vocab_size"],
        jnp.int32))(jax.random.key(seed % (2 ** 31)))
    return tokens, tokens


def loss_center(cfg, mix):
    return (1 + cfg["num_nextn_predict_layers"]
            * cfg["system"]["mtp_weight"]) * math.log(cfg["vocab_size"])


def _params(block, names):
    return {k: getattr(block, v).data()._data for k, v in names.items()}


def _mlp(block):
    return _params(block, {"gate": "gate_proj_weight", "up": "up_proj_weight",
                           "down": "down_proj_weight"})


def _layer(layer):
    att = layer.attention
    out = {"ln1": layer.ln1.gamma.data()._data,
           "ln2": layer.ln2.gamma.data()._data,
           "attn": dict(_params(att, {
               "q_a": "q_a_weight", "q_b": "q_b_weight", "kv_a": "kv_a_weight",
               "kv_b": "kv_b_weight", "o": "o_weight"}),
               q_a_norm=att.q_a_norm.gamma.data()._data,
               kv_a_norm=att.kv_a_norm.gamma.data()._data)}
    if hasattr(layer.ffn, "expert_w1"):
        out["moe"] = dict(_params(layer.ffn, {
            "router": "gate_weight", "bias": "select_bias",
            "w1": "expert_w1", "w3": "expert_w3", "w2": "expert_w2"}),
            shared=_mlp(layer.ffn.shared))
    else:
        out["mlp"] = _mlp(layer.ffn)
    return out


def weights(net):
    """The system's parameters, as they lie on the device, in the plain
    nested dict that references/glm-4.7-flash.py takes (and casts to
    float32 inside its one jitted program)."""
    out = {"embed": net.embed_weight.data()._data,
           "head": net.head_weight.data()._data,
           "final_norm": net.final_norm.gamma.data()._data,
           "layers": [_layer(l) for l in net.layers._children.values()]}
    if "mtp" in net._children:
        m = net.mtp
        out["mtp"] = {"hnorm": m.hnorm.gamma.data()._data,
                      "enorm": m.enorm.gamma.data()._data,
                      "eh_proj": m.eh_proj_weight.data()._data,
                      "layer": _layer(m.layer),
                      "final_norm": m.final_norm.gamma.data()._data}
    return out


def expert_layers(net):
    """The DroplessMoE blocks, the multi-token module's last."""
    return [l.ffn for l in net.decoder_layers()
            if hasattr(l.ffn, "select_bias")]


def set_selection_bias(net, scale):
    """Seeded non-zero values for the comparison (so that a bias that
    leaks into the weights shows), zeros for the job (`assumed`: the
    balancing rule that moves it is the job's, and is not published)."""
    import jax
    for i, moe in enumerate(expert_layers(net)):
        shape = moe.select_bias.shape
        moe.select_bias.set_data(scale * np.asarray(
            jax.random.normal(jax.random.key(1000 + i), shape)))


def compared_expert(net):
    """Which held expert of the last expert layer has its down projection's
    gradient compared: the one the selection bias favours most, so that it
    has rows under any bias."""
    last = list(net.layers._children.values())[-1].ffn
    held = last.held_experts
    bias = np.asarray(last.select_bias.data()._data)[held.start:held.stop]
    return int(np.argmax(bias))


def reference_grads(grads, expert):
    """The four compared gradients, from the reference's gradient tree."""
    return {"grad_router": grads["layers"][-1]["moe"]["router"],
            "grad_expert_down": grads["layers"][-1]["moe"]["w2"][expert],
            "grad_kv_a": grads["layers"][0]["attn"]["kv_a"],
            "grad_embed": grads["embed"]}


def system_grads(net, expert):
    last = list(net.layers._children.values())[-1].ffn
    first = list(net.layers._children.values())[0].attention
    return {"grad_router": last.gate_weight.grad,
            "grad_expert_down": last.expert_w2.grad[expert],
            "grad_kv_a": first.kv_a_weight.grad,
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


def system_outputs(net, batch, n):
    """What the system gives for the first n sequences of the batch, with
    the selection bias set to seeded non-zero values: (the compared
    outputs, what the reference's side needs of them).  One pass of its own
    autograd in training mode through hybridize(), one compiled program
    forward and one backward.  Each expert layer's input leaves that
    program through a forward hook (a host callback, traced into the
    program like the layer itself), and the program's own routing function
    is asked for its choice and weights on exactly those inputs."""
    import functools
    import jax
    from tpu_mx import autograd
    from tpu_mx.ndarray import NDArray
    from tpu_mx.parallel import dropless_route
    cfg = net._bench_cfg
    spec = cfg["reference_comparison"]
    stride = spec["logit_stride"]
    hp, _ = hyper(cfg)
    tokens = np.asarray(batch[0])[:n]
    set_selection_bias(net, spec["selection_bias_scale"])
    expert = compared_expert(net)
    layers, inputs = expert_layers(net), {}

    def tap(i):
        def hook(block, args):
            x = args[0]
            jax.debug.callback(
                lambda v: inputs.__setitem__(i, np.asarray(v)),
                getattr(x, "_data", x))
        return hook
    hooks = [m.register_forward_pre_hook(tap(i))
             for i, m in enumerate(layers)]
    net.hybridize()
    with autograd.record():
        out = net(NDArray(tokens))
    out[0].backward()
    net.hybridize(False)
    jax.effects_barrier()
    for h in hooks:
        h.detach()

    got = {"logits": _f32(out[1])[:, ::stride], "loss": _f32(out[0])}
    if len(out) > 2:
        got["mtp_logits"] = _f32(out[2])[:, ::stride]
    got.update({k: _f32(v) for k, v in system_grads(net, expert).items()})
    route = jax.jit(functools.partial(
        dropless_route, top_k=hp["top_k"], scaling=hp["scaling"]))
    xs = [inputs[i].reshape(-1, inputs[i].shape[-1])
          for i in range(len(layers))]
    routed = [route(x, m.gate_weight.data()._data, m.select_bias.data()._data)
              for x, m in zip(xs, layers)]
    chosen = [np.asarray(c) for c, _ in routed]
    got["route_choice"] = _one_hot(chosen, hp["n_experts"])
    got["route_weights"] = np.concatenate([_f32(w) for _, w in routed])
    return got, {"tokens": tokens, "expert": expert, "inputs": xs,
                 "chosen": chosen}


def _reference_program(reference, cfg, low, expert):
    """One compiled program for the reference's side, whatever the wrong
    variant (a traced index into reference.WRONG, -1 for none)."""
    import jax
    import jax.numpy as jnp
    hp, held = hyper(cfg)
    stride = cfg["reference_comparison"]["logit_stride"]
    dtype = jnp.bfloat16 if low == "all" else jnp.float32

    def program(weights, tokens, inputs, chosen, wrong):
        out, grads = reference.loss_and_grads(
            weights, tokens, hp=hp, held=held, wrong=wrong, low=low,
            forced=chosen)
        want = {"logits": out["logits"][:, ::stride], "loss": out["loss"]}
        if "mtp_logits" in out:
            want["mtp_logits"] = out["mtp_logits"][:, ::stride]
        want.update(reference_grads(grads, expert))
        # the routing alone, on the system's own layer inputs: its free
        # choice, and its weights for the choice the system made
        moes = [p["moe"] for p in weights["layers"] if "moe" in p]
        if "mtp" in weights:
            moes.append(weights["mtp"]["layer"]["moe"])
        free, weight = [], []
        with jax.default_matmul_precision(
                "default" if low == "all" else "highest"):
            for p, x, c in zip(moes, inputs, chosen):
                p = {k: p[k].astype(dtype) for k in ("router", "bias")}
                free.append(reference.route(x.astype(dtype), p, hp, held,
                                            wrong, low)[0])
                weight.append(reference.route(x.astype(dtype), p, hp, held,
                                              wrong, low, forced=c)[1])
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
    reference's routing on the system's own layer inputs.  `programs`, a
    dict, keeps the compiled program between calls (a script that reads
    every wrong variant compiles once)."""
    hp, _ = hyper(net._bench_cfg)
    programs = {} if programs is None else programs
    key = (low, aux["expert"])
    if key not in programs:
        programs[key] = _reference_program(reference, net._bench_cfg, *key)
    index = -1 if wrong is None else reference.WRONG.index(wrong)
    want = programs[key](weights(net), aux["tokens"], aux["inputs"],
                         aux["chosen"], np.int32(index))
    return {k: _one_hot(v, hp["n_experts"]) if k == "route_choice"
            else np.asarray(v, np.float32) for k, v in want.items()}


def compare(reference, net, batch, n, wrong=None, low=None):
    """(system, reference) for the first n sequences of the batch, with the
    selection bias set to seeded non-zero values on both sides: the logits
    and the multi-token logits at every `stride`-th position, the loss,
    four gradients of it (last expert layer's router, there the down
    projection of the held expert that the bias favours most, layer 0's
    kv_a, the embedding), and the routing of every expert layer on the
    system's own layer inputs (who is chosen, and the chosen's weights)."""
    got, aux = system_outputs(net, batch, n)
    want = reference_outputs(reference, net, aux, wrong, low)
    # the job runs with the bias at zero
    set_selection_bias(net, 0.0)
    return got, want
