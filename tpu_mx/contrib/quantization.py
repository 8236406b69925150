"""INT8 quantization (REF:python/mxnet/contrib/quantization.py,
REF:src/operator/quantization/**).

The reference rewrites symbols to quantized ops with min/max calibration.
TPU-natively int8 matmuls run on the MXU with int32 accumulation —
``lax.dot_general(preferred_element_type=int32)`` — so the same three
pieces exist here: the quantize/dequantize ops (affine, symmetric int8 as
in the reference's `quantize` with `out_type='int8'`), a calibration pass
(min/max or entropy-free percentile over a calibration iterator), and
``quantize_net``, which swaps Gluon Dense layers for int8 versions.
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError
from ..ndarray import NDArray

__all__ = ["quantize", "dequantize", "calib_minmax", "QuantizedDense",
           "QuantizedConv", "quantize_net"]


def quantize(data, min_range=None, max_range=None, out_type="int8"):
    """Affine-symmetric int8 quantization (REF quantize op): scale =
    max(|min|,|max|)/127.  Returns (q, min_range, max_range)."""
    import jax.numpy as jnp
    if out_type != "int8":
        raise MXNetError("only int8 quantization is supported on TPU")
    x = data._data if isinstance(data, NDArray) else jnp.asarray(data)
    if min_range is None:
        min_range = float(jnp.min(x))
    if max_range is None:
        max_range = float(jnp.max(x))
    amax = max(abs(min_range), abs(max_range), 1e-8)
    scale = 127.0 / amax
    q = jnp.clip(jnp.round(x.astype(jnp.float32) * scale), -127, 127
                 ).astype(jnp.int8)
    return NDArray(q), min_range, max_range


def dequantize(q, min_range, max_range):
    """Inverse of :func:`quantize` (REF dequantize op)."""
    import jax.numpy as jnp
    x = q._data if isinstance(q, NDArray) else jnp.asarray(q)
    amax = max(abs(min_range), abs(max_range), 1e-8)
    return NDArray(x.astype(jnp.float32) * (amax / 127.0))


import contextlib


@contextlib.contextmanager
def _forced_eager(net):
    """Temporarily de-hybridize every block: both calibration (leaf
    forward hooks) and the int8 leaf patching only take effect on the
    eager path — a cached jit program was traced with the float leaves
    and would silently bypass them."""
    saved = [blk for blk in _all_blocks(net)
             if getattr(blk, "_active", False)]
    for blk in saved:
        blk._active = False
    try:
        yield
    finally:
        for blk in saved:
            blk._active = True


def calib_minmax(net, calib_iter, num_batches=10):
    """Min/max calibration (REF calib_mode='naive'): run the iterator
    through the net recording per-layer input ranges via forward hooks."""
    ranges = {}
    handles = []

    def make_hook(name):
        def hook(blk, inputs, output):
            x = inputs[0]
            if isinstance(x, NDArray):
                lo, hi = float(x.min().asnumpy()), float(x.max().asnumpy())
                old = ranges.get(name, (lo, hi))
                ranges[name] = (min(old[0], lo), max(old[1], hi))
        return hook

    for name, blk in _named_quantizable(net):
        handles.append(blk.register_forward_hook(make_hook(name)))
    with _forced_eager(net):
        for i, batch in enumerate(calib_iter):
            if i >= num_batches:
                break
            data = batch.data[0] if hasattr(batch, "data") else batch
            net(data)
    for h in handles:
        h.detach()
    return ranges


def _is_quantizable_conv(block):
    """Forward (non-transpose) convs of any spatial rank with initialized
    weights quantize; transpose convs stay float (the reference's int8
    coverage is conv/pool/fc too — REF:src/operator/subgraph/mkldnn/)."""
    from ..gluon.nn.conv_layers import _Conv
    return isinstance(block, _Conv) and not block._transpose


def _named_quantizable(block, prefix=""):
    """(name, block) for every quantizable leaf: Dense + forward convs."""
    from ..gluon import nn
    if isinstance(block, nn.Dense):
        yield prefix or "dense", block
        return
    if _is_quantizable_conv(block):
        yield prefix or "conv", block
        return
    children = getattr(block, "_children", {})
    items = children.items() if isinstance(children, dict) \
        else enumerate(children)
    for key, child in items:
        sub = f"{prefix}.{key}" if prefix else str(key)
        yield from _named_quantizable(child, sub)


def _named_dense(block, prefix=""):
    """Back-compat: Dense-only view of _named_quantizable."""
    from ..gluon import nn
    for name, blk in _named_quantizable(block, prefix):
        if isinstance(blk, nn.Dense):
            yield name, blk


class QuantizedDense:
    """Int8 inference Dense: int8×int8 → int32 on the MXU, rescaled to
    float (REF quantized_fully_connected)."""

    def __init__(self, dense, input_range):
        import jax.numpy as jnp
        w = dense.weight.data()
        self._wq, self._wmin, self._wmax = quantize(w)
        self._bias = dense.bias.data()._data \
            if getattr(dense, "bias", None) is not None else None
        self._act = dense.act  # activation fused in Dense stays applied
        self._flatten = getattr(dense, "_flatten", True)
        self._in_range = input_range

    def __call__(self, x):
        import jax.numpy as jnp
        from jax import lax
        xq, xmin, xmax = quantize(x, *self._in_range)
        xd = xq._data
        # Dense's input contract: flatten trailing dims (default) or
        # contract the last axis only
        xd = xd.reshape(xd.shape[0], -1) if self._flatten \
            else xd.reshape(-1, xd.shape[-1])
        acc = lax.dot_general(
            xd, self._wq._data,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)
        if not self._flatten and len(xq.shape) > 2:
            acc = acc.reshape(xq.shape[:-1] + (acc.shape[-1],))
        x_amax = max(abs(xmin), abs(xmax), 1e-8)
        w_amax = max(abs(self._wmin), abs(self._wmax), 1e-8)
        out = acc.astype(jnp.float32) * (x_amax / 127.0) * (w_amax / 127.0)
        if self._bias is not None:
            out = out + self._bias
        out = NDArray(out)
        return self._act(out) if self._act is not None else out


class QuantizedConv:
    """Int8 inference conv: int8×int8 → int32 on the MXU via
    nd.quantized_conv, rescaled to float (REF quantized_conv +
    subgraph/mkldnn conv int8 path).  Weights quantized once at build;
    inputs quantized per call with the calibrated range.  Pooling and
    activations around it pass through float — both are range-preserving,
    so the reference's conv→pool int8 chains lose nothing by rescaling at
    the conv boundary."""

    def __init__(self, conv, input_range):
        w = conv.weight.data()
        self._wq, self._wmin, self._wmax = quantize(w)
        self._bias = conv.bias.data() \
            if getattr(conv, "bias", None) is not None else None
        self._act = conv.act
        self._in_range = input_range
        self._conv = conv

    def __call__(self, x):
        import jax.numpy as jnp
        from ..ndarray import quantized_ops as Q
        c = self._conv
        xq, xmin, xmax = quantize(x, *self._in_range)
        out, mn, mx = Q.quantized_conv(
            xq, self._wq, None,
            NDArray(jnp.float32(xmin)), NDArray(jnp.float32(xmax)),
            NDArray(jnp.float32(self._wmin)),
            NDArray(jnp.float32(self._wmax)),
            kernel=c._kernel, stride=c._strides, pad=c._padding,
            dilate=c._dilation, num_filter=c._channels,
            num_group=c._groups, no_bias=True, layout=c._layout)
        x_amax = max(abs(xmin), abs(xmax), 1e-8)
        w_amax = max(abs(self._wmin), abs(self._wmax), 1e-8)
        y = out._data.astype(jnp.float32) * \
            ((x_amax / 127.0) * (w_amax / 127.0))
        if self._bias is not None:
            b = self._bias._data.astype(jnp.float32)
            if not c._channels_last:
                b = b.reshape((1, -1) + (1,) * len(c._kernel))
            y = y + b
        y = NDArray(y)
        return self._act(y) if self._act is not None else y


class _QuantizedNet:
    """Inference wrapper produced by quantize_net.  Structure-agnostic:
    for the duration of a call, each quantizable leaf's `forward` is
    shadowed by its int8 version (instance attribute over the class
    method), then the ORIGINAL net forward runs — residual/branchy
    architectures (ResNet blocks) keep their exact control flow, only the
    leaf compute is swapped.  The wrapped net itself is left untouched
    between calls.

    Calls are jit-compiled by default with the wrapper's OWN jax.jit —
    never the float net's `_cached_fns` (a cached float program was
    traced with the float leaves and would silently bypass the int8
    patching; that is why hybridize is force-disabled during the trace).
    The eager path measured 16 img/s — pure per-op dispatch — where the
    jitted program runs the same int8 ops as one XLA program at 146
    img/s, same config (measured 2026-07-31 on an earlier installation,
    not reproduced on today's code).
    TPUMX_QUANT_JIT=0 restores the eager behavior (debugging).

    The traced program freezes ALL live params — the int8 leaves' ranges
    AND every non-quantized leaf's float weights — as constants at first
    call.  This is an inference-only snapshot: after ANY weight change,
    call `quantize_net` again for a fresh wrapper (the eager path would
    pick up new values, the jitted one will not)."""

    def __init__(self, net, qmap):
        self._net = net
        self._qmap = qmap
        self._jit = None

    def _run_patched(self, x):
        patched = []
        patched_ids = set()
        with _forced_eager(self._net):
            try:
                for name, blk in _named_quantizable(self._net):
                    q = self._qmap.get(name)
                    # a SHARED layer appears under several names — patch
                    # (and later unpatch) each instance exactly once
                    if q is not None and id(blk) not in patched_ids:
                        blk.forward = q  # instance attr shadows the method
                        patched.append(blk)
                        patched_ids.add(id(blk))
                return self._net(x)
            finally:
                for blk in patched:
                    del blk.forward

    def __call__(self, x):
        import os
        if os.environ.get("TPUMX_QUANT_JIT", "1") != "1":
            return self._run_patched(x)
        import jax
        xd = x._data if isinstance(x, NDArray) else x
        if self._jit is None:
            def raw(xj):
                out = self._run_patched(NDArray(xj))
                # multi-output nets return tuples/lists of NDArray
                return jax.tree.map(
                    lambda o: o._data if isinstance(o, NDArray) else o,
                    out, is_leaf=lambda o: isinstance(o, NDArray))

            # one jax.jit: its own signature cache retraces per
            # shape/dtype; no hand-rolled key dict needed
            self._jit = jax.jit(raw)
        out = self._jit(xd)
        return jax.tree.map(NDArray, out)


def _all_blocks(block):
    yield block
    children = getattr(block, "_children", {})
    items = children.values() if isinstance(children, dict) else children
    for child in items:
        yield from _all_blocks(child)


def quantize_net(net, calib_iter=None, calib_data=None, num_batches=10,
                 quantize_convs=True):
    """Swap every Dense — and, by default, every forward conv — for its
    int8 version using calibrated input ranges (REF quantize_model /
    quantize_net; conv coverage per REF:src/operator/subgraph/mkldnn/).
    Pooling/activation layers pass through float (range-preserving)."""
    from ..gluon import nn
    if calib_iter is None:
        if calib_data is None:
            raise MXNetError("need calib_iter or calib_data")
        calib_iter = [calib_data]
    ranges = calib_minmax(net, calib_iter, num_batches)
    qmap = {}
    for name, blk in _named_quantizable(net):
        if name not in ranges:
            continue
        if isinstance(blk, nn.Dense):
            qmap[name] = QuantizedDense(blk, ranges[name])
        elif quantize_convs:
            qmap[name] = QuantizedConv(blk, ranges[name])
    return _QuantizedNet(net, qmap)
