"""kimi-linear-48b-a3b: how the cell builds the system under test, its batch
and its FLOPs.  Sizes come from kimi-linear-48b-a3b.json (the published
config.json's keys, cut as its `reduced`, `published` and `deployment`
say) and from the traffic file.  One chip of the 32 that share each layer
of an expert-parallel job: the model is tpu_mx.models.decoder.CausalLM
with Kimi Delta Attention layers (a gated delta rule with a decay per key
channel, in chunks) beside a latent-attention layer without a query rank
or positions, a leading dense layer, sigmoid-routed SwiGLU experts beside
a shared one, a head of its own and its loss in chunks; trained by
CompiledTrainStep."""
import math

import numpy as np

KDA, LATENT = "kda", "latent"


def depth(cfg):
    return cfg["num_hidden_layers"]


def hyper(cfg):
    """What references/kimi-linear-48b-a3b.py takes as `hp`, and the experts
    held.  The layer kinds are the published lists' (`kda_layers` and
    `full_attn_layers`, counted from 1), read from the first layer kept as
    far as the layers kept."""
    dep, lin = cfg["deployment"], cfg["linear_attn_config"]
    first = dep["first_layer_kept"]
    kinds = tuple(KDA if i + 1 in lin["kda_layers"] else LATENT
                  for i in range(first, first + depth(cfg)))
    assert all(i + 1 in lin["kda_layers"] + lin["full_attn_layers"]
               for i in range(first, first + depth(cfg)))
    return dict(kinds=kinds,
                kda=dict(heads=lin["num_heads"], head_dim=lin["head_dim"],
                         taps=lin["short_conv_kernel_size"]),
                mla=dict(heads=cfg["num_attention_heads"],
                         nope=cfg["qk_nope_head_dim"],
                         rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"],
                         kv_rank=cfg["kv_lora_rank"],
                         theta=float(cfg["rope_theta"])),
                eps=cfg["rms_norm_eps"], top_k=cfg["num_experts_per_token"],
                scaling=float(cfg["routed_scaling_factor"]),
                n_experts=dep["experts_routed_over"],
                logit_stride=cfg["reference_comparison"]["logit_stride"]), \
        tuple(dep["held_experts"])


def model_config(cfg):
    """The published keys under the names CausalLM takes."""
    hp, held = hyper(cfg)
    assert held[1] - held[0] == cfg["num_experts"]
    assert cfg["q_lora_rank"] is None and cfg["mla_use_nope"] \
        and cfg["moe_renormalize"] and not cfg["tie_word_embeddings"] \
        and cfg["moe_router_activation_func"] == "sigmoid" \
        and cfg["num_expert_group"] == cfg["topk_group"] == 1 \
        and cfg["moe_layer_freq"] == 1 and cfg["hidden_act"] == "silu" \
        and cfg["num_nextn_predict_layers"] == 0 \
        and cfg["rope_scaling"] is None
    kda, mla = hp["kda"], hp["mla"]

    def mixer(kind):
        if kind == KDA:
            return dict(kind="kda", num_heads=kda["heads"],
                        head_dim=kda["head_dim"], taps=kda["taps"])
        return dict(kind="latent", num_heads=mla["heads"], q_rank=None,
                    kv_rank=mla["kv_rank"], nope_dim=mla["nope"],
                    rope_dim=mla["rope"], v_dim=mla["v"],
                    rope_theta=mla["theta"], rotary=False)
    return dict(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        num_layers=depth(cfg), num_dense_layers=cfg["first_k_dense_replace"],
        dense_hidden=cfg["intermediate_size"], epsilon=hp["eps"],
        attention=[mixer(k) for k in hp["kinds"]],
        moe=dict(hidden_size=cfg["moe_intermediate_size"],
                 num_experts=hp["n_experts"], top_k=hp["top_k"],
                 held_experts=held, scaling=hp["scaling"],
                 shared_hidden=cfg["num_shared_experts"]
                 * cfg["moe_intermediate_size"], scoring="sigmoid"),
        loss_chunk=cfg["system"]["loss_chunk"],
        logits_stride=hp["logit_stride"])


def scan_flops(tokens, heads, d, chunk=64):
    """The chunked delta rule's forward FLOPs over `tokens` positions of
    `heads` heads of d, at chunks of `chunk` whatever the program takes:
    a (sequence, head, chunk)'s 4 C^2 d (the decayed k k^T and q k^T) + 4
    C^2 d (their products with the values) + 2/3 C^3 (the triangular
    solve) + 6 C d^2 (the state's three products)."""
    c = chunk
    return tokens / c * heads * (8 * c * c * d + 2 * c ** 3 / 3
                                 + 6 * c * d * d)


def flops_parts(cfg, mix):
    """Training FLOPs per sequence by part: matrix multiplications once
    each (forward 2 FLOPs a MAC, backward twice that): the KDA layers' five
    projections, two low-rank paths and output projection, and their
    chunked rule (scan_flops); the latent layer's projections, its scores
    and their product with v over the causal pairs; the dense layer's MLP,
    the routers, the routed experts at the expected T·k·held/E rows, the
    shared experts, the head over every position.  Filters, norms, gates
    and embedding look-ups are left out; nothing recomputed is counted."""
    hp, held = hyper(cfg)
    u, t = cfg["hidden_size"], mix["seq_len"]
    kda, mla = hp["kda"], hp["mla"]
    w, r = kda["heads"] * kda["head_dim"], kda["head_dim"]
    kdas = sum(k == KDA for k in hp["kinds"])
    latents = len(hp["kinds"]) - kdas
    sparse = depth(cfg) - cfg["first_k_dense_replace"]
    rows = t * hp["top_k"] * (held[1] - held[0]) / hp["n_experts"]
    qk = mla["nope"] + mla["rope"]
    macs = dict(
        kda_project=kdas * t * (4 * u * w + 2 * (u * r + r * w)
                                + u * kda["heads"]),
        kda_scan=kdas * scan_flops(t, kda["heads"], kda["head_dim"]) / 2,
        mla_project=latents * t * (
            u * mla["heads"] * qk + u * (mla["kv_rank"] + mla["rope"])
            + mla["kv_rank"] * mla["heads"] * (mla["nope"] + mla["v"])
            + mla["heads"] * mla["v"] * u),
        scores=latents * (t * (t + 1) // 2) * mla["heads"] * (qk + mla["v"]),
        dense_mlp=cfg["first_k_dense_replace"] * t * 3 * u
        * cfg["intermediate_size"],
        router=sparse * t * u * hp["n_experts"],
        routed=sparse * rows * 3 * u * cfg["moe_intermediate_size"],
        shared=sparse * t * 3 * u * cfg["num_shared_experts"]
        * cfg["moe_intermediate_size"],
        head=t * u * cfg["vocab_size"])
    return {k: 3 * 2 * v for k, v in macs.items()}


def flops_per_sample(cfg, mix):
    return sum(flops_parts(cfg, mix).values())


def build(cfg, mix, seed, mesh=None):
    import tpu_mx as mx
    from tpu_mx import gluon
    # a program without the mixer stops here, by name
    from tpu_mx.models.decoder import KDA_SCOPES, CausalLM  # noqa: F401
    from tpu_mx.parallel import CompiledTrainStep
    sys_cfg = cfg["system"]
    mx.random.seed(seed % (2 ** 31))
    net = CausalLM(model_config(cfg), mesh=mesh, dtype=sys_cfg["dtype"],
                   remat=sys_cfg["remat"])
    net.initialize(mx.init.Normal(sys_cfg["init_sigma"]))
    opt = mx.optimizer.create(
        sys_cfg["optimizer"], learning_rate=sys_cfg["learning_rate"],
        beta1=sys_cfg["beta1"], beta2=sys_cfg["beta2"],
        epsilon=sys_cfg["epsilon"], wd=sys_cfg["weight_decay"],
        multi_precision=sys_cfg["multi_precision"])
    made = []

    def make_step():
        """The one step of this net: compare() takes its first step, and
        run.py times the same object from there."""
        if not made:
            # the objective is computed in the forward: the net's first
            # output is the loss, the batch's last argument is not read
            made.append(CompiledTrainStep(net, gluon.loss.PassThrough(), opt,
                                          mesh=mesh))
            # a reader is handed the configuration and neither of these
            # (decoder_scopes.census)
            cfg["live"] = {"net": net, "step": made[0]}
        return made[0]
    # compare() is handed the net and not the configuration
    net._bench_cfg, net._bench_make_step = cfg, make_step
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


KDA_PARAMS = {"q": "q_weight", "k": "k_weight", "v": "v_weight",
              "q_taps": "q_conv_weight", "k_taps": "k_conv_weight",
              "v_taps": "v_conv_weight", "f_a": "f_a_weight",
              "f_b": "f_b_weight", "A_log": "A_log", "dt_bias": "dt_bias",
              "b": "b_weight", "g_a": "g_a_weight", "g_b": "g_b_weight",
              "g_bias": "g_b_bias", "o": "o_weight"}


def _layer(layer):
    out = {"ln1": layer.ln1.gamma.data()._data,
           "ln2": layer.ln2.gamma.data()._data}
    mixer = layer.attention
    if hasattr(mixer, "A_log"):
        out["kda"] = dict(_params(mixer, KDA_PARAMS),
                          o_norm=mixer.o_norm.gamma.data()._data)
    else:
        out["mla"] = dict(
            _params(mixer, {"q": "q_weight", "kv_a": "kv_a_weight",
                            "kv_b": "kv_b_weight", "o": "o_weight"}),
            kv_a_norm=mixer.kv_a_norm.gamma.data()._data)
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
    nested dict that references/kimi-linear-48b-a3b.py takes (and casts to
    float32 inside its one jitted program)."""
    return {"embed": net.embed_weight.data()._data,
            "head": net.head_weight.data()._data,
            "final_norm": net.final_norm.gamma.data()._data,
            "layers": [_layer(l) for l in _layers(net)]}


def compared_layers(hp):
    """(a KDA layer, the latent layer): the first of each kind."""
    return hp["kinds"].index(KDA), hp["kinds"].index(LATENT)


LEAVES = ("kda_q", "kda_decay", "kda_beta", "mla_q", "router", "expert_down",
          "embed", "head")


def reference_leaves(tree, expert, hp):
    """The eight compared leaves of a tree in the reference's form (its
    weights or their gradients): the first KDA layer's W_q (through its
    filter and L2 norm), the up-projection of its decay's low-rank path
    and its W_b (the dense layer: their gradients pass every later layer),
    the latent layer's W_q, the last layer's router and there the down
    projection of the held expert `expert`, the embedding and the head."""
    kda, latent = compared_layers(hp)
    k = tree["layers"][kda]["kda"]
    return {"kda_q": k["q"], "kda_decay": k["f_b"], "kda_beta": k["b"],
            "mla_q": tree["layers"][latent]["mla"]["q"],
            "router": tree["layers"][-1]["moe"]["router"],
            "expert_down": tree["layers"][-1]["moe"]["w2"][expert],
            "embed": tree["embed"], "head": tree["head"]}


def system_leaves(net, hp):
    """The same eight as the net's parameters (the down projections of every
    held expert of the last layer: the step keeps them as one)."""
    layers = _layers(net)
    kda, latent = compared_layers(hp)
    k = layers[kda].attention
    return {"kda_q": k.q_weight, "kda_decay": k.f_b_weight,
            "kda_beta": k.b_weight, "mla_q": layers[latent].attention.q_weight,
            "router": layers[-1].ffn.gate_weight,
            "expert_down": layers[-1].ffn.expert_w2,
            "embed": net.embed_weight, "head": net.head_weight}


def _f32(a):
    return np.asarray(getattr(a, "_data", a).astype("float32"))


def _one_hot(chosen, n_experts):
    """(layers, S, k) expert ids as a 0/1 matrix (layers·S, E): the form in
    which a relative RMS error counts the tokens that chose otherwise."""
    flat = np.asarray(chosen).reshape(-1, np.shape(chosen)[-1])
    out = np.zeros((flat.shape[0], n_experts), np.float32)
    np.put_along_axis(out, flat, 1.0, axis=1)
    return out


def scan_inputs(tokens, cfg):
    """q, k, v (1, T, heads, head_dim) in the model's type and g (1, T,
    heads, head_dim), beta (1, T, heads) in f32, seeded by the batch's first
    ids (so by the run's seed): what the chunked rule alone is compared on.
    q and k standard normal, L2-normalised a head (q then times
    head_dim^-1/2), v standard normal; the decays through the layer's own
    parametrisation, g = -exp(A_log) softplus(f + dt_bias), A_log = log U(1,
    16) a head, dt_bias the inverse softplus of dt log-uniform in [1e-3,
    0.1] a channel and f standard normal; beta = sigmoid of a standard
    normal."""
    import jax
    import jax.numpy as jnp
    lin = cfg["linear_attn_config"]
    t, h, d = tokens.shape[1], lin["num_heads"], lin["head_dim"]
    dtype = jnp.dtype(cfg["system"]["dtype"])
    keys = iter(jax.random.split(jax.random.key(
        int(tokens[0, 0]) * 65536 + int(tokens[0, 1])), 7))
    normal = lambda *shape: jax.random.normal(next(keys), shape)
    l2 = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True)
                                     + 1e-6)
    q = (l2(normal(1, t, h, d)) * d ** -0.5).astype(dtype)
    k = l2(normal(1, t, h, d)).astype(dtype)
    v = normal(1, t, h, d).astype(dtype)
    a_log = jnp.log(jax.random.uniform(next(keys), (h,), minval=1.0,
                                       maxval=16.0))
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        next(keys), (h, d), minval=math.log(1e-3), maxval=math.log(0.1))),
        1e-4)
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(normal(1, t, h, d)
                                                   + dt_bias)
    beta = jax.nn.sigmoid(normal(1, t, h))
    return q, k, v, g, beta


def _moes(net):
    return [l.ffn for l in _layers(net) if hasattr(l.ffn, "select_bias")]


def tap_expert_inputs(net):
    """Forward pre-hooks on each expert layer of the net that copy the
    layer's input to the host when a program that runs the net runs (a
    host callback, traced into the program like the layer itself): (a dict
    that gets each layer's first input by its index, the hooks, to be
    detached when done)."""
    import jax
    inputs = {}

    def tap(i):
        def hook(block, args):
            x = args[0]
            jax.debug.callback(
                lambda v: inputs.setdefault(i, np.asarray(v)),
                getattr(x, "_data", x))
        return hook
    return inputs, [m.register_forward_pre_hook(tap(i))
                    for i, m in enumerate(_moes(net))]


def route_rows(xs, routers, hp):
    """The program's own routing function on each expert layer's rows xs
    (S, U), with that layer's (router, selection bias): [(chosen (S, k),
    weights (S, k))] a layer."""
    import functools
    import jax
    from tpu_mx.parallel import dropless_route
    route = jax.jit(functools.partial(
        dropless_route, top_k=hp["top_k"], scaling=hp["scaling"],
        scoring="sigmoid"))
    return [route(x, *r) for x, r in zip(xs, routers)]


def system_forward(net, batch, n):
    """What the system's forward gives for the batch, whose n sequences are
    all of it (the timed step trains on them all): (the compared outputs,
    what the reference's side needs of them).  One compiled program of the
    net through hybridize(), forward only.  Each expert layer's input
    leaves that program through a forward hook (a host callback, traced
    into the program like the layer itself), and the program's own routing
    function is asked for its choice and weights on exactly those rows.
    The program's own chunked rule (`kda_chunked`, what runs under
    `kda.scan`) is asked for its output alone on seeded inputs of the
    published shape: a chunk that forgets the state it was handed, or a
    decay taken a head at a time, reads off there at once."""
    import jax
    from tpu_mx.models.decoder import kda_chunked
    from tpu_mx.ndarray import NDArray
    cfg = net._bench_cfg
    hp, held = hyper(cfg)
    tokens = np.asarray(batch[0])[:n]
    assert len(tokens) == len(batch[0]), "compare the whole batch"
    inputs, hooks = tap_expert_inputs(net)
    net.hybridize()
    out = net(NDArray(tokens))
    net.hybridize(False)
    jax.effects_barrier()
    for h in hooks:
        h.detach()
    moes = _moes(net)
    xs = [inputs[i].reshape(-1, inputs[i].shape[-1])
          for i in range(len(moes))]
    routed = route_rows(xs, [(m.gate_weight.data()._data,
                              m.select_bias.data()._data) for m in moes], hp)
    chosen = [np.asarray(c) for c, _ in routed]
    # the held expert of the last layer with the most rows: its down
    # projection is the one compared
    rows = np.bincount(chosen[-1].reshape(-1),
                       minlength=hp["n_experts"])[held[0]:held[1]]
    expert = int(np.argmax(rows))
    # the model's own logits are those of every logit_stride-th position
    got = {"logits": _f32(out[1])}
    got["route_choice"] = _one_hot(chosen, hp["n_experts"])
    got["route_weights"] = np.concatenate([_f32(w) for _, w in routed])
    scanned = scan_inputs(tokens, cfg)
    got["kda_scan"] = _f32(jax.jit(kda_chunked)(*scanned))
    return got, {"tokens": tokens, "expert": expert, "inputs": xs,
                 "chosen": chosen, "scan_inputs": scanned}


def system_step(net, batch, aux):
    """One step of the timed program, make_step()'s CompiledTrainStep (the
    very object run.py then times), on the whole batch: its loss, and for
    each compared leaf the gradient as it landed in AdamW's first moment
    (m = (1 - beta1) g after a first step) and the change of its f32 master
    weight.  The step's state goes on from there: the window times later
    steps of the same training."""
    cfg = net._bench_cfg
    hp, _ = hyper(cfg)
    step = net._bench_make_step()
    names = {id(p): k for k, p in net.collect_params().items()}
    params = system_leaves(net, hp)
    assert all(p.lr_mult == p.wd_mult == 1 for p in params.values())
    keys = {leaf: names[id(p)] for leaf, p in params.items()}

    def pick(leaf, a):
        return a[aux["expert"]] if leaf == "expert_down" else a
    before = {leaf: _f32(pick(leaf, step.values[k]))
              for leaf, k in keys.items()}
    got = {"loss": _f32(step.step(*batch))}
    for leaf, k in keys.items():
        after = step.masters.get(k, step.values[k])
        got["grad_" + leaf] = _f32(pick(leaf, step.opt_states[k][0])) \
            / (1 - cfg["system"]["beta1"])
        got["update_" + leaf] = _f32(pick(leaf, after)) - before[leaf]
    return got


def _reference_programs(reference, cfg, low, expert):
    """Two compiled programs for the reference's side, whatever the wrong
    variant (a traced index into reference.WRONG, -1 for none): one
    sequence's logits, loss and compared gradients, with the system's
    choice of experts; and the routing of every expert layer on the rows
    the system's routers read, with the recurrence alone."""
    import jax
    import jax.numpy as jnp
    hp, held = hyper(cfg)
    dtype = jnp.bfloat16 if low == "all" else jnp.float32

    def model(weights, tokens, chosen, wrong):
        out, grads = reference.loss_and_grads(
            weights, tokens, hp=hp, held=held, wrong=wrong, low=low,
            forced=chosen)
        return out["logits"], out["loss"], reference_leaves(grads, expert,
                                                            hp)

    def routing(weights, inputs, chosen, scan_inputs, wrong):
        # its free choice, and its weights for the choice the system made
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
        # and the recurrence alone, on the same inputs
        return {"route_choice": jnp.stack(free),
                "route_weights": jnp.concatenate(weight),
                "kda_scan": reference.scan(*scan_inputs, wrong=wrong,
                                           low=low)}
    return jax.jit(model), jax.jit(routing)


def reference_outputs(reference, net, aux, wrong=None, low=None,
                      programs=None, first=None, params=None):
    """The reference's side of the same outputs, on the system's weights
    (as they were before its step) and with the system's choice of experts
    in place of its own (the choice is a step function of the scores: a
    bf16 program moves a few tokens in a hundred across its boundary, and
    an error made of such flips says nothing of the mathematics); the
    choice itself is held to the reference's routing on the rows the
    system's own routers read.  The loss and the gradients are the means
    over the batch's sequences, one sequence at a time (the first `first`
    of them: a planted fault that leaves the rest out), and the update is
    the stated AdamW's first step from that gradient:
    -lr (g / (|g| + eps) + wd w).  `programs`, a dict, keeps the compiled
    programs between calls (a script that reads every wrong variant
    compiles once).  `params`: the weights in weights()'s form, where the
    net's own have moved on since (a copy taken before a step)."""
    cfg = net._bench_cfg
    hp, _ = hyper(cfg)
    sys_cfg = cfg["system"]
    programs = {} if programs is None else programs
    key = (low, aux["expert"])
    if key not in programs:
        programs[key] = _reference_programs(reference, cfg, *key)
    model, routing = programs[key]
    index = np.int32(-1 if wrong is None else reference.WRONG.index(wrong))
    weights_ = weights(net) if params is None else params
    tokens = aux["tokens"]
    t, first = tokens.shape[1], first or len(tokens)
    logits, loss, grads = [], 0.0, {}
    for i in range(len(tokens)):
        rows = slice(i * t, (i + 1) * t)
        got = model(weights_, tokens[i:i + 1],
                    [c[rows] for c in aux["chosen"]], index)
        logits.append(np.asarray(got[0], np.float32))
        if i < first:
            loss += float(got[1]) / first
            for leaf, g in got[2].items():
                grads[leaf] = grads.get(leaf, 0.0) \
                    + np.asarray(g, np.float64) / first
    want = {"logits": np.concatenate(logits), "loss": np.float32(loss)}
    before = reference_leaves(weights_, aux["expert"], hp)
    for leaf, g in grads.items():
        want["grad_" + leaf] = g.astype(np.float32)
        # AdamW's first step: m-hat is g and v-hat is g^2
        want["update_" + leaf] = (-sys_cfg["learning_rate"] * (
            g / (np.abs(g) + sys_cfg["epsilon"])
            + sys_cfg["weight_decay"] * _f32(before[leaf]))).astype(
                np.float32)
    routed = routing(weights_, aux["inputs"], aux["chosen"],
                     aux["scan_inputs"], index)
    want["route_choice"] = _one_hot(routed["route_choice"], hp["n_experts"])
    want.update({k: np.asarray(routed[k], np.float32)
                 for k in ("route_weights", "kda_scan")})
    return want


def compare(reference, net, batch, n, wrong=None, low=None):
    """(system, reference) for the whole batch (its n sequences): the
    logits at every `logit_stride`-th position and the routing of every
    expert layer on the rows the system's own routers read (who is chosen,
    and the chosen's weights), from one forward of the net; the loss, the
    gradient of eight leaves (reference_leaves says which) and the change
    of each under the optimizer, from one step of the timed program; and
    the chunked rule alone against the per-token recurrence on seeded
    inputs.  The reference runs before the step, on the weights the step
    starts from."""
    got, aux = system_forward(net, batch, n)
    want = reference_outputs(reference, net, aux, wrong, low)
    got.update(system_step(net, batch, aux))
    return got, want
