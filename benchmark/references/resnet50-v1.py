"""Plain reference: ResNet-50 v1 forward pass (He et al.,
arXiv:1512.03385, Table 1, 50-layer; bottleneck blocks of Fig. 5).

Straightforward jax.numpy in float32 under matmul precision "highest": no
kernels, no layout tricks, no fused BatchNorm.  Independent of tpu_mx: it
is handed the system's seeded weights as a plain nested dict (see
configs/resnet50-v1.py `weights`) and a batch of NHWC images.

BatchNorm normalises with the statistics of the batch it is given (training
mode): at initialisation the running statistics are 0 and 1 and normalise
nothing, so inference mode would compare fifty unnormalised layers.

Departures from the paper, taken from the system so that the same function
is compared: the stride of a down-sampling block sits on its first 1x1
convolution (as in the paper's v1; "v1.5" moves it to the 3x3); the first
and third convolution of a bottleneck carry a bias (Gluon's model zoo has
one there; BatchNorm's mean subtraction cancels it exactly).

`wrong` selects a deliberately wrong variant, used only to place the
tolerance: "bn_eps_x100" (epsilon 1e-3), "no_residual" (the shortcut of the
last block of every stage is dropped), "stride_on_3x3" (v1.5).
"""
import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5


def conv(x, w, stride, pad, bias=None):
    """x NHWC, w OHWI (the system's channels-last weight layout)."""
    y = lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "OHWI", "NHWC"))
    return y if bias is None else y + bias


def batch_norm(x, p, eps):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + eps) * p["gamma"] + p["beta"]


def bottleneck(x, p, stride, eps, residual=True, stride_on_3x3=False):
    s1, s2 = (1, stride) if stride_on_3x3 else (stride, 1)
    y = conv(x, p["conv1"]["weight"], s1, 0, p["conv1"].get("bias"))
    y = jax.nn.relu(batch_norm(y, p["bn1"], eps))
    y = conv(y, p["conv2"]["weight"], s2, 1)
    y = jax.nn.relu(batch_norm(y, p["bn2"], eps))
    y = conv(y, p["conv3"]["weight"], 1, 0, p["conv3"].get("bias"))
    y = batch_norm(y, p["bn3"], eps)
    if "down_conv" in p:
        x = batch_norm(conv(x, p["down_conv"]["weight"], stride, 0),
                       p["down_bn"], eps)
    return jax.nn.relu(y + x if residual else y)


def forward(weights, images, wrong=None):
    """Returns {"stem", "stage1".."stage4", "logits"}: every stage's output,
    so that the comparison can hold the early stages tight.  `weights` and
    `images` may come in the system's type: they are taken to float32."""
    weights = jax.tree.map(lambda w: w.astype(jnp.float32), weights)
    eps = BN_EPS * 100 if wrong == "bn_eps_x100" else BN_EPS
    out = {}
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(images, jnp.float32)
        x = conv(x, weights["stem"]["conv"]["weight"], 2, 3)
        x = jax.nn.relu(batch_norm(x, weights["stem"]["bn"], eps))
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)])
        out["stem"] = x
        for i, stage in enumerate(weights["stages"]):
            for j, block in enumerate(stage):
                stride = 2 if (i > 0 and j == 0) else 1
                last = j == len(stage) - 1
                x = bottleneck(
                    x, block, stride, eps,
                    residual=not (wrong == "no_residual" and last),
                    stride_on_3x3=wrong == "stride_on_3x3")
            out[f"stage{i + 1}"] = x
        x = jnp.mean(x, axis=(1, 2))
        out["logits"] = x @ weights["fc"]["weight"].T + weights["fc"]["bias"]
    return out
