"""HLO inspection for compiled train steps (VERDICT r2 ask#1: "nobody has
looked at the steady-state HLO yet").

Builds a workload's CompiledTrainStep, lowers+compiles it for the
current backend, and prints an op histogram with the layout-change smells
called out: `transpose`, `copy`, `pad`, `reshape`, `convert` counts, the
fusion count, and every convolution's shapes/layout line.  Run on the real
TPU (plain `python tools/hlo_inspect.py resnet`) to see what XLA actually
made of the step; `--smoke` uses tiny shapes for a CPU sanity pass.

Usage: python tools/hlo_inspect.py {resnet|bert|lstm|ssd} [--smoke] [--batch N]
"""
import argparse
import collections
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_resnet_step(smoke, batch, layout="NHWC", stem="s2d"):
    import numpy as np
    import tpu_mx as mx
    from tpu_mx import gluon, nd
    from tpu_mx.gluon.model_zoo import vision
    from tpu_mx.layout import default_layout
    from tpu_mx.parallel import CompiledTrainStep

    size = 64 if smoke else 224
    classes = 100 if smoke else 1000
    factory = "resnet18_v1" if smoke else "resnet50_v1"
    shape = (batch, size, size, 3) if layout == "NHWC" else (batch, 3, size,
                                                             size)
    with default_layout(layout):
        net = getattr(vision, factory)(classes=classes, stem=stem)
    net.initialize(init="xavier")
    # tiny on-device finalize + on-device data: a lean cold start
    net.finalize_shapes(nd.random.uniform(shape=(2,) + shape[1:]))
    net.cast("bfloat16")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    opt = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9,
                              wd=1e-4, multi_precision=True)
    step = CompiledTrainStep(net, loss_fn, opt, mesh=None)
    data = nd.cast(nd.random.uniform(shape=shape), "bfloat16")
    label = nd.random.randint(0, classes, (batch,), dtype="float32")
    return step, (data, label)


def build_bert_step(smoke, batch):
    import numpy as np
    import tpu_mx as mx
    from tpu_mx import gluon, nd
    from tpu_mx.models.bert import BERTModel, bert_base_config
    from tpu_mx.parallel import CompiledTrainStep

    seq_len = 128
    cfg = bert_base_config(vocab_size=1000 if smoke else 30522,
                           max_len=seq_len)
    if smoke:
        cfg.update(num_layers=2, units=128, hidden_size=512, num_heads=2)
    net = BERTModel(cfg, dtype="bfloat16", remat=not smoke)
    net.initialize()
    rng = np.random.RandomState(0)
    tokens = rng.randint(4, cfg["vocab_size"], (batch, seq_len)).astype(
        np.int32)
    types = np.zeros((batch, seq_len), np.int32)
    n_masked = max(1, int(0.15 * seq_len))
    positions = np.stack([rng.choice(seq_len, n_masked, replace=False)
                          for _ in range(batch)]).astype(np.int32)
    labels = np.take_along_axis(tokens, positions, axis=1)
    net.finalize_shapes(nd.array(tokens[:1]), nd.array(types[:1]), None,
                        nd.array(positions[:1]))
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    class MLMLoss(gluon.loss.Loss):
        def __init__(self):
            super().__init__(weight=None, batch_axis=0)

        def hybrid_forward(self, F, logits, labels):
            vocab = logits.shape[-1]
            return F.mean(ce(F.reshape(logits, shape=(-1, vocab)),
                             F.reshape(labels, shape=(-1,))))

    opt = mx.optimizer.create("lamb", learning_rate=1e-4,
                              multi_precision=True)
    step = CompiledTrainStep(net, MLMLoss(), opt)
    return step, (nd.array(tokens), nd.array(types), None,
                  nd.array(positions), nd.array(labels))


def build_lstm_step(smoke, batch):
    """The PTB LSTM train step (2x650, bptt 35; bf16 weights, f32 CE
    logits): the one home of this recipe (ROADMAP W3 wants it as a
    cell)."""
    import numpy as np
    import tpu_mx as mx
    from tpu_mx import gluon, nd
    from tpu_mx.models.lstm_lm import RNNModel
    from tpu_mx.parallel import CompiledTrainStep

    vocab, emb, hid, layers, bptt = (1000, 64, 64, 1, 8) if smoke else \
        (10000, 650, 650, 2, 35)
    model = RNNModel(mode="lstm", vocab_size=vocab, num_embed=emb,
                     num_hidden=hid, num_layers=layers, dropout=0.0)
    model.initialize(init="xavier")

    class FlatCE(gluon.loss.Loss):
        def __init__(self, **kw):
            super().__init__(weight=None, batch_axis=0, **kw)
            self._ce = gluon.loss.SoftmaxCrossEntropyLoss()

        def hybrid_forward(self, F, logits, labels):
            v = logits.shape[-1]
            return self._ce(
                F.cast(F.reshape(logits, shape=(-1, v)), dtype="float32"),
                F.reshape(labels, shape=(-1,)))

    rng = np.random.RandomState(0)
    x = nd.array(rng.randint(0, vocab, (bptt, batch)), dtype="float32")
    y = nd.array(rng.randint(0, vocab, (bptt * batch,)), dtype="float32")
    model.finalize_shapes(x)  # no-op: RNNModel declares every dim
    model.cast("bfloat16")
    opt = mx.optimizer.create("sgd", learning_rate=1.0,
                              multi_precision=True)
    step = CompiledTrainStep(model, FlatCE(), opt)
    return step, (x, y)


def build_ssd_step(smoke, batch):
    """The SSD-512 train step (vgg16_reduced; bf16 backbone, f32
    heads/targets/losses): the one home of this recipe (ROADMAP W3)."""
    import numpy as np
    import tpu_mx as mx
    from tpu_mx import autograd, gluon, nd
    from tpu_mx.gluon.block import HybridBlock
    from tpu_mx.models.ssd import SSD, SSDTrainingTargets, ssd_512
    from tpu_mx.parallel import CompiledTrainStep

    if smoke:
        size, classes = 64, 3
        net = SSD(classes, sizes=[[0.2, 0.35], [0.5, 0.7]],
                  ratios=[[1, 2, 0.5]] * 2, base_filters=(8, 16))
    else:
        size, classes = 512, 20
        net = ssd_512(classes, backbone="vgg16_reduced")
    targets = SSDTrainingTargets()

    class SSDTrain(HybridBlock):
        def __init__(self, ssd_net, **kw):
            super().__init__(**kw)
            self.net = ssd_net
            self._cls = gluon.loss.SoftmaxCrossEntropyLoss()
            self._box = gluon.loss.HuberLoss()

        def forward(self, x, labels):
            anchors, cls_preds, box_preds = self.net(x)
            anchors = nd.cast(anchors, "float32")
            cls_preds = nd.cast(cls_preds, "float32")
            box_preds = nd.cast(box_preds, "float32")
            with autograd.pause():
                loc_t, loc_m, cls_t = targets(anchors, labels, cls_preds)
            return self._cls(cls_preds, cls_t) + \
                self._box(box_preds * loc_m, loc_t * loc_m)

    wrapper = SSDTrain(net)
    wrapper.initialize(init="xavier")
    rng = np.random.RandomState(0)
    labels = np.full((batch, 2, 5), -1.0, np.float32)
    for b in range(batch):
        cls = rng.randint(0, classes)
        x0, y0 = rng.uniform(0.05, 0.5, 2)
        labels[b, 0] = [cls, x0, y0, min(x0 + 0.3, 0.95),
                        min(y0 + 0.3, 0.95)]
    x_nd = nd.random.uniform(high=0.1, shape=(batch, 3, size, size))
    l_nd = nd.array(labels)
    wrapper.finalize_shapes(x_nd[:2], l_nd[:2])
    wrapper.cast("bfloat16")
    x_nd = nd.cast(x_nd, "bfloat16")
    dummy = nd.array(np.zeros((1,), np.float32))
    opt = mx.optimizer.create("sgd", learning_rate=0.01, momentum=0.9,
                              wd=5e-4, multi_precision=True)
    step = CompiledTrainStep(wrapper, gluon.loss.PassThrough(), opt)
    return step, (x_nd, l_nd, dummy)


SMELLS = ("transpose", "copy", "pad", "reshape", "convert", "bitcast",
          "all-reduce", "dynamic-slice", "dynamic-update-slice", "gather",
          "scatter")


def analyze(hlo_text):
    ops = collections.Counter()
    convs = []
    fusions = 0
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*[^ ]+\s+([\w\-]+)\(",
                     line)
        if not m:
            continue
        op = m.group(1)
        ops[op] += 1
        if op == "fusion":
            fusions += 1
        if op == "convolution":
            convs.append(line.strip()[:180])
    return ops, convs, fusions


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("model", choices=["resnet", "bert", "lstm", "ssd"])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--dump", help="write full HLO text here")
    args = ap.parse_args()

    batch = args.batch or (8 if args.smoke else 256)
    builders = {"resnet": build_resnet_step, "bert": build_bert_step,
                "lstm": build_lstm_step, "ssd": build_ssd_step}
    step, batch_args = builders[args.model](args.smoke, batch)

    # trigger the build without running a step, then compile the jitted fn
    raw = tuple(b._data if b is not None and hasattr(b, "_data") else b
                for b in batch_args)
    if step._jitted is None:
        step._build(len(raw))
        step.place()
    import jax
    import jax.numpy as jnp
    from tpu_mx import random as _random
    key = _random.take_key()
    gacc = step._gacc if step._accum > 1 else {}
    compiled = step._jitted.lower(
        step.values, step.masters, step.opt_states, step._efs, gacc,
        jnp.asarray(1.0, jnp.float32), jnp.asarray(0.1, jnp.float32),
        key, *raw).compile()
    txt = compiled.as_text()
    if args.dump:
        with open(args.dump, "w") as f:
            f.write(txt)
    ops, convs, fusions = analyze(txt)
    print(f"== {args.model} train-step HLO ({len(txt.splitlines())} lines, "
          f"{fusions} fusions) ==")
    print("-- op histogram (top 25) --")
    for op, n in ops.most_common(25):
        mark = "  <-- layout/copy smell" if op in SMELLS else ""
        print(f"  {op:28s} {n}{mark}")
    print("-- convolutions --")
    for c in convs:
        print("  " + c)
    try:
        mem = compiled.memory_analysis()
        print(f"-- memory: {mem}")
    except Exception:
        pass
    cost = None
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops = cost.get("flops") if hasattr(cost, "get") else None
        if flops:
            print(f"-- cost_analysis flops/step: {flops:.3e}")
    except Exception:
        pass


if __name__ == "__main__":
    main()
