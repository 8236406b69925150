"""bert-base-uncased: how the cell builds the system under test, its batch
and its FLOPs.  Sizes come from bert-base-uncased.json (the published
config.json's keys) and from the traffic file."""
import math

import numpy as np


def n_masked(cfg, mix):
    return max(1, int(cfg["system"]["mask_share"] * mix["seq_len"]))


def flops_per_sample(cfg, mix):
    """Training FLOPs per sequence, copied from bench.py
    (bert_train_flops_per_seq): matrix multiplications only, counted once
    per executed matmul (forward 2 FLOPs per MAC, backward twice that), the
    T^2 score and AV terms over all positions, the MLM dense and tied
    vocabulary head over the masked positions only.  Embedding look-ups are
    gathers and are left out; nothing recomputed is counted."""
    units, hidden = cfg["hidden_size"], cfg["intermediate_size"]
    seq_len = mix["seq_len"]
    per_tok_layer = 2 * units * (3 * units) + 2 * units * units \
        + 2 * 2 * units * hidden
    body = cfg["num_hidden_layers"] * seq_len * (
        per_tok_layer + 4 * seq_len * units)
    head = n_masked(cfg, mix) * (2 * units * units
                                 + 2 * cfg["vocab_size"] * units)
    return 3 * (body + head)


def model_config(cfg):
    """The published keys under the names BERTModel takes."""
    return dict(num_layers=cfg["num_hidden_layers"], units=cfg["hidden_size"],
                hidden_size=cfg["intermediate_size"],
                num_heads=cfg["num_attention_heads"],
                vocab_size=cfg["vocab_size"],
                max_length=cfg["max_position_embeddings"],
                dropout=cfg["hidden_dropout_prob"])


def build(cfg, mix, seed, mesh=None):
    import tpu_mx as mx
    from tpu_mx import gluon
    from tpu_mx.models.bert import (BERTModel, bert_data_specs,
                                    bert_sharding_rules)
    from tpu_mx.parallel import CompiledTrainStep, P
    sys_cfg = cfg["system"]

    class MLMLoss(gluon.loss.Loss):
        """Mean cross-entropy over the gathered masked positions, as
        chip_smoke.py and examples/bert/pretrain.py have it."""

        def __init__(self, **kw):
            super().__init__(weight=None, batch_axis=0, **kw)
            self._ce = gluon.loss.SoftmaxCrossEntropyLoss()

        def hybrid_forward(self, F, logits, labels):
            vocab = logits.shape[-1]
            return F.mean(self._ce(F.reshape(logits, shape=(-1, vocab)),
                                   F.reshape(labels, shape=(-1,))))

    mx.random.seed(seed % (2 ** 31))
    net = BERTModel(model_config(cfg), dtype=sys_cfg["dtype"],
                    remat=sys_cfg["remat"])
    net.initialize()
    opt = mx.optimizer.create(sys_cfg["optimizer"],
                              learning_rate=sys_cfg["learning_rate"],
                              multi_precision=sys_cfg["multi_precision"])
    rules = data_specs = None
    if mesh is not None:
        # bert_data_specs() names (tokens, token_types, labels); the MLM
        # batch adds valid_length (None: no leaves) and the masked
        # positions, which shard like the labels they select
        rules = bert_sharding_rules()
        tok, typ, lab = bert_data_specs()
        data_specs = (tok, typ, P(), lab, lab)

    def make_step():
        return CompiledTrainStep(net, MLMLoss(), opt, mesh=mesh, rules=rules,
                                 data_specs=data_specs)
    return net, make_step


def make_batch(cfg, mix, seed, mesh=None):
    """One fixed MLM batch made on the device from the seed in one jitted
    call: (tokens, token_types, valid_length=None, masked_positions,
    labels).  With a mesh the batch is made already sharded over `dp`."""
    import jax
    import jax.numpy as jnp
    batch, seq_len, m = mix["batch"], mix["seq_len"], n_masked(cfg, mix)

    def make(key):
        k1, k2 = jax.random.split(key)
        tokens = jax.random.randint(k1, (batch, seq_len), 4,
                                    cfg["vocab_size"], jnp.int32)
        # m distinct positions per row: the m smallest of T random keys
        order = jnp.argsort(jax.random.uniform(k2, (batch, seq_len)), axis=1)
        positions = jnp.sort(order[:, :m], axis=1).astype(jnp.int32)
        labels = jnp.take_along_axis(tokens, positions, axis=1)
        return tokens, jnp.zeros_like(tokens), positions, labels
    out = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        out = NamedSharding(mesh, PartitionSpec("dp"))
    tokens, types, positions, labels = jax.jit(make, out_shardings=out)(
        jax.random.key(seed % (2 ** 31)))
    return tokens, types, None, positions, labels


def loss_center(cfg, mix):
    return math.log(cfg["vocab_size"])


def weights(net):
    """The system's parameters, as they lie on the device, in the plain
    nested dict that references/bert-base-uncased.py takes (and casts to
    float32 inside its one jitted program)."""
    def arr(p):
        return p.data()._data

    def ln(block):
        return {"gamma": arr(block.gamma), "beta": arr(block.beta)}
    enc = net.encoder
    layers = []
    for layer in enc.layers._children.values():
        att = layer.attention
        layers.append({
            "qkv_weight": arr(att.qkv_weight), "qkv_bias": arr(att.qkv_bias),
            "out_weight": arr(att.attnout_weight),
            "out_bias": arr(att.attnout_bias),
            "ln1": ln(layer.ln1), "ln2": ln(layer.ln2),
            "ffn1_weight": arr(layer.ffn1_weight),
            "ffn1_bias": arr(layer.ffn1_bias),
            "ffn2_weight": arr(layer.ffn2_weight),
            "ffn2_bias": arr(layer.ffn2_bias)})
    return {"word_embed": arr(enc.word_embed_weight),
            "pos_embed": arr(enc.pos_embed_weight),
            "type_embed": arr(enc.type_embed_weight),
            "embed_ln": ln(enc.ln), "layers": layers,
            "mlm_dense_weight": arr(net.mlm_dense.weight),
            "mlm_dense_bias": arr(net.mlm_dense.bias),
            "mlm_ln": ln(net.mlm_ln), "mlm_bias": arr(net.mlm_bias)}


def compare(reference, net, batch, n, wrong=None):
    """(system, reference) logits at the masked positions of the first n
    sequences of the batch, dropout off (prediction mode).  One compiled
    program each (the system's through hybridize(), as a user would), so
    that a second run finds both in the compile cache."""
    import jax
    from tpu_mx.ndarray import NDArray
    tokens, types, _, positions, _ = (
        None if a is None else np.asarray(a)[:n] for a in batch)
    net.hybridize()
    logits = net(NDArray(tokens), NDArray(types), None, NDArray(positions))
    net.hybridize(False)
    ref = jax.jit(reference.forward, static_argnames=("heads", "wrong"))(
        weights(net), tokens, types, positions,
        heads=net._cfg["num_heads"], wrong=wrong)
    return ({"logits": np.asarray(logits._data.astype("float32"))},
            {"logits": np.asarray(ref)})
