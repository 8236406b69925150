"""resnet50-v1: how the cell builds the system under test, its batch and its
FLOPs.  Everything architectural comes from resnet50-v1.json."""
import math

import numpy as np


def flops_per_sample(cfg, mix):
    """Training FLOPs per image, copied from bench.py
    (RESNET50_TRAIN_FLOPS_PER_IMG): 4.09 GMACs forward at 224x224 (He et
    al. Table 1 gives 3.8e9 multiply-adds for the convolutions alone; 4.09
    counts the stem, shortcuts and classifier), 2 FLOPs per MAC, 3x for
    forward plus backward.  Scales with the image area for the rehearsal."""
    size = mix["image_size"]
    return 3 * 2 * 4.09e9 * (size * size) / (224 * 224)


def build(cfg, mix, seed, mesh=None):
    import tpu_mx as mx
    from tpu_mx import gluon
    from tpu_mx.gluon.model_zoo import vision
    from tpu_mx.layout import default_layout
    from tpu_mx.parallel import CompiledTrainStep
    sys_cfg, pub = cfg["system"], cfg["published"]
    mx.random.seed(seed % (2 ** 31))
    with default_layout(sys_cfg["layout"]):
        net = vision.resnet50_v1(classes=mix["classes"],
                                 stem=sys_cfg["stem"])
    net.initialize(init=sys_cfg["initializer"])
    net.cast(sys_cfg["dtype"])
    opt = mx.optimizer.create(
        pub["optimizer"], learning_rate=pub["learning_rate"],
        momentum=pub["momentum"], wd=pub["weight_decay"],
        multi_precision=sys_cfg["multi_precision"])

    def make_step():
        return CompiledTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                 opt, mesh=mesh)
    return net, make_step


def make_batch(cfg, mix, seed, mesh=None):
    """One fixed batch made on the device from the seed in one jitted call:
    (images NHWC bf16 in [0, 1), labels f32 class indices)."""
    import jax
    import jax.numpy as jnp
    shape = (mix["batch"], mix["image_size"], mix["image_size"], 3)

    @jax.jit
    def make(key):
        k1, k2 = jax.random.split(key)
        return (jax.random.uniform(k1, shape, jnp.bfloat16),
                jax.random.randint(k2, shape[:1], 0, mix["classes"])
                .astype(jnp.float32))
    return make(jax.random.key(seed % (2 ** 31)))


def loss_center(cfg, mix):
    return math.log(mix["classes"])


def weights(net):
    """The system's parameters, as they lie on the device, in the plain
    nested dict that references/resnet50-v1.py takes (and casts to float32
    inside its one jitted program)."""
    def arr(p):
        return p.data()._data

    def conv(c):
        d = {"weight": arr(c.weight)}
        if c.bias is not None:
            d["bias"] = arr(c.bias)
        return d

    def bn(b):
        return {"gamma": arr(b.gamma), "beta": arr(b.beta)}

    f = list(net.features._children.values())
    out = {"stem": {"conv": conv(f[0]), "bn": bn(f[1])}, "stages": [],
           "fc": {"weight": arr(net.output.weight),
                  "bias": arr(net.output.bias)}}
    for stage in f[4:8]:
        blocks = []
        for blk in stage._children.values():
            b = list(blk.body._children.values())
            d = {"conv1": conv(b[0]), "bn1": bn(b[1]), "conv2": conv(b[3]),
                 "bn2": bn(b[4]), "conv3": conv(b[6]), "bn3": bn(b[7])}
            if blk.downsample is not None:
                ds = list(blk.downsample._children.values())
                d["down_conv"], d["down_bn"] = conv(ds[0]), bn(ds[1])
            blocks.append(d)
        out["stages"].append(blocks)
    return out


def compare(reference, net, batch, n, wrong=None):
    """(system, reference) outputs on the first n images of the batch, in
    training mode (BatchNorm on the sample's own statistics).  "logits" is
    the whole network, end to end.  Every other entry feeds ONE part of the
    system (the stem, a stage, the head) the reference's own input to that
    part, so that fifty bf16 layers do not compound: a fault inside a stage
    then stands out against that stage's rounding alone.

    The reference is one jitted program; the system runs hybridized, as a
    user would run it: one program for the whole network and one for each
    stage (the stem's and the head's few layers are a program each), so
    that a second run finds them in the compile cache."""
    import jax
    from tpu_mx import autograd
    from tpu_mx.ndarray import NDArray
    images = batch[0][:n]
    ref = jax.jit(reference.forward, static_argnames="wrong")(
        weights(net), images, wrong=wrong)
    children = list(net.features._children.values())
    parts = {"stem": children[:4], "stage1": children[4:5],
             "stage2": children[5:6], "stage3": children[6:7],
             "stage4": children[7:8], "head": [children[8], net.output]}
    inputs = dict(zip(parts, [images] + [ref[k] for k in list(parts)[:5]]))
    ref["head"] = ref["logits"]

    def run(blocks, x):
        x = NDArray(x.astype(images.dtype))
        for block in blocks:
            x = block(x)
        return x._data.astype("float32")
    net.hybridize()
    with autograd.train_mode():
        out = {k: run(blocks, inputs[k]) for k, blocks in parts.items()}
        out["logits"] = run([net], images)
    net.hybridize(False)
    return ({k: np.asarray(v) for k, v in out.items()},
            {k: np.asarray(v) for k, v in ref.items()})
