"""Layer: kernels.  Device trace, first device: share (%) of the operations'
device time spent in operations that hold a convolution or a dot (XLA's
convolution fusions, dot custom-calls); the rest is element-wise,
reductions, copies."""
import re

import xplane

MXU_OPS = ("dot_general", "conv_general_dilated")


def on_mxu(name, meta):
    """Does the operation hold a convolution or a dot?"""
    info = meta.get(name, {})
    return ("convolution" in info.get("hlo_category", "")
            or any(op in info.get("tf_op", "") for op in MXU_OPS)
            or bool(re.match(r"%?(convolution|dot)", name)))


def read(run):
    if not run["trace"]:
        return None
    dev = xplane.first_device(run["trace"])
    _, ops = xplane.stretch(dev)
    total = sum(d for _, _, d in ops)
    return 100.0 * sum(d for n, _, d in ops if on_mxu(n, dev["meta"])) \
        / total if total else None
