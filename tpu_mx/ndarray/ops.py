"""Operator library: `mx.nd.*` over jax.numpy / lax, with tape recording.

TPU-native analog of the reference operator library (REF:src/operator/** —
mshadow/cuDNN/MKLDNN kernels registered via NNVM).  Design (SURVEY §7.1):
every op has a *pure functional core* on raw `jax.Array`s, compiled by XLA
(which supplies the fusion/memory-planning the reference got from NNVM passes
and hand-written kernels).  The `_apply` wrapper gives the imperative face:
it unwraps NDArray handles, records a `jax.vjp` pullback on the autograd tape
when needed (the FGradient analog), and re-wraps outputs.  Called with raw
arrays (inside a `hybridize()` trace) it is a zero-overhead passthrough, so
one namespace serves both `F=mx.nd` and the traced path — the reference got
the same duality from its nd/sym twin stubs.
"""
from __future__ import annotations

import builtins
import functools
import math as _math

import jax
import jax.numpy as jnp
import numpy as _np
from jax import lax

from .. import autograd
from .. import _functional
from .. import fusion as _fusion
from .. import layout as _layout_mod
from .ndarray import NDArray, array, concatenate, load, save, waitall
from ..context import current_context

_abs = builtins.abs
_sum = builtins.sum
_max = builtins.max
_min = builtins.min


# ----------------------------------------------------------------------------
# imperative invoke (analog of REF:src/imperative/imperative.cc Imperative::Invoke)
# ----------------------------------------------------------------------------
def _is_traced(x):
    return isinstance(x, jax.core.Tracer)


def _raw(a):
    if isinstance(a, NDArray):
        return a._data
    if isinstance(a, (jax.Array, _np.ndarray)) or _is_traced(a):
        return a
    return a  # python scalar — kept as-is so jnp broadcasting rules apply


def _scalar_key(*vals):
    """Type-tagged scalars for fuse keys.  1 == 1.0 == True in Python, so
    bare values would collide across spellings — but each bakes a
    DIFFERENT trace constant into the op's closure (int vs weak-float
    promotion), and a key collision replays the wrong cached program with
    the wrong output dtype vs eager."""
    return tuple((type(v).__name__, v) for v in vals)


def _apply(fn, args, name="op", nondiff=False, fuse=None):
    """Dispatch one op: args = tensor positionals (NDArray | array | scalar).

    `fuse` marks the op fusible for engine bulking: a hashable key naming
    the op AND every static parameter its `fn` closes over (the fusion
    cache replays a previously traced chain on key match, so anything that
    changes the math must be in the key).  None = non-fusible; reading the
    args below is then the flush barrier for any lazy inputs."""
    if _functional.active() or not any(isinstance(a, NDArray) for a in args):
        # functional mode: inside a hybridize/apply trace (even if an NDArray
        # leaked in via a creation op), or a pure-array call — no wrapping,
        # no tape
        return fn(*[_raw(a) for a in args])
    if fuse is not None and _fusion.enabled():
        res = _fusion.append(fn, args, name, fuse, nondiff)
        if res is not None:
            return res
    datas = [_raw(a) for a in args]

    diff_idx = [
        i for i, a in enumerate(args)
        # inexact = floats AND complex: fft chains produce complex64
        # intermediates whose cotangents must keep flowing
        if isinstance(a, NDArray) and jnp.issubdtype(a.dtype, jnp.inexact)
    ]
    diff_inputs = [args[i] for i in diff_idx]

    if not nondiff and diff_idx and autograd._needs_tape(diff_inputs):
        def closed(*diff_datas):
            full = list(datas)
            for i, d in zip(diff_idx, diff_datas):
                full[i] = d
            out = fn(*full)
            # normalize list outputs (jnp.split family) to tuples so the
            # pullback's expected cotangent pytree matches what backward
            # builds (a tuple)
            return tuple(out) if isinstance(out, list) else out

        out_data, vjp_fn = jax.vjp(closed, *[datas[i] for i in diff_idx])
        multi = isinstance(out_data, (tuple, list))
        outs_raw = list(out_data) if multi else [out_data]
        if any(jnp.issubdtype(o.dtype, jnp.inexact) for o in outs_raw):
            # record even MIXED-dtype outputs (frexp's mantissa/exponent):
            # backward supplies float0 cotangents for the integer ones —
            # dropping the whole op would silently zero real gradients
            outs = [NDArray(o) for o in outs_raw]
            autograd._record_op(vjp_fn, diff_inputs, outs, name=name)
            return outs if multi else outs[0]
        # all-integer output: fall through unrecorded
        out_data = tuple(outs_raw) if multi else outs_raw[0]
    else:
        out_data = fn(*datas)

    if isinstance(out_data, (tuple, list)):
        return [NDArray(o) for o in out_data]
    return NDArray(out_data)


def _index(a, key):
    return _apply(lambda x: x[key], [a], name="index")


# ----------------------------------------------------------------------------
# creation ops
# ----------------------------------------------------------------------------
def _place(data, ctx):
    if _functional.active():
        return data  # raw inside a functional trace
    return NDArray(data, ctx=ctx or current_context())


def zeros(shape, ctx=None, dtype="float32", **kw):
    return _place(jnp.zeros(shape, dtype=dtype), ctx)


def ones(shape, ctx=None, dtype="float32", **kw):
    return _place(jnp.ones(shape, dtype=dtype), ctx)


def full(shape, val, ctx=None, dtype="float32", **kw):
    return _place(jnp.full(shape, val, dtype=dtype), ctx)


def empty(shape, ctx=None, dtype="float32"):
    return zeros(shape, ctx=ctx, dtype=dtype)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype="float32"):
    a = jnp.arange(start, stop, step, dtype=dtype)
    if repeat != 1:
        a = jnp.repeat(a, repeat)
    return _place(a, ctx)


def linspace(start, stop, num, endpoint=True, ctx=None, dtype="float32"):
    return _place(jnp.linspace(start, stop, num, endpoint=endpoint, dtype=dtype), ctx)


def eye(N, M=0, k=0, ctx=None, dtype="float32"):
    return _place(jnp.eye(N, M if M else N, k=k, dtype=dtype), ctx)


def zeros_like(a, **kw):
    return _apply(jnp.zeros_like, [a], "zeros_like", nondiff=True,
                  fuse="zeros_like")


def ones_like(a, **kw):
    return _apply(jnp.ones_like, [a], "ones_like", nondiff=True,
                  fuse="ones_like")


def full_like(a, fill_value, **kw):
    fuse = ("full_like",) + _scalar_key(fill_value) \
        if isinstance(fill_value, (int, float)) else None
    return _apply(lambda x: jnp.full_like(x, fill_value), [a], "full_like",
                  nondiff=True, fuse=fuse)


# ----------------------------------------------------------------------------
# unary elementwise
# ----------------------------------------------------------------------------
def _unary(jfn, name):
    def op(data, out=None, **kw):
        # fusible: jfn is a module-level pure function, the name alone is
        # a complete chain-cache key.  The out= path realizes immediately
        # (res._data is a flush barrier) — in-place targets keep strict
        # eager rebind semantics.
        res = _apply(jfn, [data], name, fuse=name)
        if out is not None:
            out._rebind(res._data if isinstance(res, NDArray) else res)
            return out
        return res
    op.__name__ = name
    return op


abs = _unary(jnp.abs, "abs")
sign = _unary(jnp.sign, "sign")
ceil = _unary(jnp.ceil, "ceil")
floor = _unary(jnp.floor, "floor")
trunc = _unary(jnp.trunc, "trunc")
round = _unary(jnp.round, "round")
rint = _unary(jnp.rint, "rint")
fix = _unary(jnp.trunc, "fix")
exp = _unary(jnp.exp, "exp")
expm1 = _unary(jnp.expm1, "expm1")
log = _unary(jnp.log, "log")
log2 = _unary(jnp.log2, "log2")
log10 = _unary(jnp.log10, "log10")
log1p = _unary(jnp.log1p, "log1p")
sqrt = _unary(jnp.sqrt, "sqrt")
rsqrt = _unary(lambda x: lax.rsqrt(x), "rsqrt")
cbrt = _unary(jnp.cbrt, "cbrt")
rcbrt = _unary(lambda x: 1.0 / jnp.cbrt(x), "rcbrt")
square = _unary(jnp.square, "square")
reciprocal = _unary(lambda x: 1.0 / x, "reciprocal")
negative = _unary(jnp.negative, "negative")
sin = _unary(jnp.sin, "sin")
cos = _unary(jnp.cos, "cos")
tan = _unary(jnp.tan, "tan")
arcsin = _unary(jnp.arcsin, "arcsin")
arccos = _unary(jnp.arccos, "arccos")
arctan = _unary(jnp.arctan, "arctan")
sinh = _unary(jnp.sinh, "sinh")
cosh = _unary(jnp.cosh, "cosh")
tanh = _unary(jnp.tanh, "tanh")
arcsinh = _unary(jnp.arcsinh, "arcsinh")
arccosh = _unary(jnp.arccosh, "arccosh")
arctanh = _unary(jnp.arctanh, "arctanh")
degrees = _unary(jnp.degrees, "degrees")
radians = _unary(jnp.radians, "radians")
sigmoid = _unary(jax.nn.sigmoid, "sigmoid")
softsign = _unary(jax.nn.soft_sign, "softsign")
relu = _unary(jax.nn.relu, "relu")
erf = _unary(jax.scipy.special.erf, "erf")
erfinv = _unary(jax.scipy.special.erfinv, "erfinv")
gammaln = _unary(jax.scipy.special.gammaln, "gammaln")
gamma = _unary(lambda x: jnp.exp(jax.scipy.special.gammaln(x)), "gamma")
logical_not = _unary(lambda x: (x == 0).astype(x.dtype), "logical_not")
isnan = _unary(jnp.isnan, "isnan")
isinf = _unary(jnp.isinf, "isinf")
isfinite = _unary(jnp.isfinite, "isfinite")


def cast(data, dtype, **kw):
    return _apply(lambda x: x.astype(dtype), [data], "cast",
                  fuse=("cast", jnp.dtype(dtype).name))


Cast = cast


def amp_cast(data, dtype):
    """AMP cast op (reference [ver>=1.5] REF:src/operator/tensor/amp_cast.cc)."""
    return cast(data, dtype)


def amp_multicast(*data, num_outputs=None):
    widest = jnp.result_type(*[d.dtype for d in data])
    return [cast(d, widest) for d in data]


def BlockGrad(data, **kw):
    # fusible: lax.stop_gradient inside the composite blocks the
    # cotangent in the segment's single vjp exactly as not-recording
    # blocks it eagerly
    return _apply(lax.stop_gradient, [data], "BlockGrad", nondiff=True,
                  fuse="BlockGrad")


stop_gradient = BlockGrad


def identity(data, **kw):
    return _apply(lambda x: x, [data], "identity", fuse="identity")


def shape_array(data):
    return _apply(lambda x: jnp.array(x.shape, dtype=jnp.int64), [data], "shape_array",
                  nondiff=True)


def size_array(data):
    return _apply(lambda x: jnp.array([x.size], dtype=jnp.int64), [data], "size_array",
                  nondiff=True)


# ----------------------------------------------------------------------------
# binary elementwise (+ broadcast_* aliases for reference API parity)
# ----------------------------------------------------------------------------
def _binary(jfn, name):
    def op(lhs, rhs, out=None, **kw):
        res = _apply(jfn, [lhs, rhs], name, fuse=name)
        if out is not None:
            out._rebind(res._data)
            return out
        return res
    op.__name__ = name
    return op


add = _binary(jnp.add, "add")
subtract = _binary(jnp.subtract, "subtract")
multiply = _binary(jnp.multiply, "multiply")
divide = _binary(jnp.divide, "divide")
mod = _binary(jnp.mod, "mod")
power = _binary(jnp.power, "power")
maximum = _binary(jnp.maximum, "maximum")
minimum = _binary(jnp.minimum, "minimum")
hypot = _binary(jnp.hypot, "hypot")
arctan2 = _binary(jnp.arctan2, "arctan2")
equal = _binary(lambda a, b: (a == b).astype(jnp.result_type(a, b)), "equal")
not_equal = _binary(lambda a, b: (a != b).astype(jnp.result_type(a, b)), "not_equal")
greater = _binary(lambda a, b: (a > b).astype(jnp.result_type(a, b)), "greater")
greater_equal = _binary(lambda a, b: (a >= b).astype(jnp.result_type(a, b)), "greater_equal")
lesser = _binary(lambda a, b: (a < b).astype(jnp.result_type(a, b)), "lesser")
lesser_equal = _binary(lambda a, b: (a <= b).astype(jnp.result_type(a, b)), "lesser_equal")
logical_and = _binary(lambda a, b: ((a != 0) & (b != 0)).astype(jnp.result_type(a, b)), "logical_and")
logical_or = _binary(lambda a, b: ((a != 0) | (b != 0)).astype(jnp.result_type(a, b)), "logical_or")
logical_xor = _binary(lambda a, b: ((a != 0) ^ (b != 0)).astype(jnp.result_type(a, b)), "logical_xor")

# the reference distinguishes elemwise_* (same-shape) from broadcast_* ops;
# jnp broadcasts everywhere so these are exact aliases
for _nm, _op in [
    ("broadcast_add", add), ("broadcast_plus", add),
    ("broadcast_sub", subtract), ("broadcast_minus", subtract),
    ("broadcast_mul", multiply), ("broadcast_div", divide),
    ("broadcast_mod", mod), ("broadcast_power", power),
    ("broadcast_maximum", maximum), ("broadcast_minimum", minimum),
    ("broadcast_hypot", hypot),
    ("broadcast_equal", equal), ("broadcast_not_equal", not_equal),
    ("broadcast_greater", greater), ("broadcast_greater_equal", greater_equal),
    ("broadcast_lesser", lesser), ("broadcast_lesser_equal", lesser_equal),
    ("broadcast_logical_and", logical_and), ("broadcast_logical_or", logical_or),
    ("broadcast_logical_xor", logical_xor),
    ("elemwise_add", add), ("elemwise_sub", subtract),
    ("elemwise_mul", multiply), ("elemwise_div", divide),
]:
    globals()[_nm] = _op


def add_n(*args, **kw):
    return _apply(lambda *xs: functools.reduce(jnp.add, xs), list(args),
                  "add_n", fuse=("add_n", len(args)))


ElementWiseSum = add_n


# ----------------------------------------------------------------------------
# reductions
# ----------------------------------------------------------------------------
def _norm_axis(axis):
    if isinstance(axis, list):
        return tuple(axis)
    return axis


def _reduce(jfn, name):
    def op(data, axis=None, keepdims=False, exclude=False, **kw):
        ax = _norm_axis(axis)
        if exclude and ax is not None:
            nd_ = data.ndim if hasattr(data, "ndim") else jnp.asarray(data).ndim
            axset = {a % nd_ for a in (ax if isinstance(ax, tuple) else (ax,))}
            ax = tuple(i for i in range(nd_) if i not in axset)
        # the "reduce tail" of a fusible chain; resolved axis/keepdims are
        # the closure's only state, so they complete the key
        return _apply(lambda x: jfn(x, axis=ax, keepdims=keepdims), [data],
                      name, fuse=(name, ax, keepdims))
    op.__name__ = name
    return op


sum = _reduce(jnp.sum, "sum")
mean = _reduce(jnp.mean, "mean")
prod = _reduce(jnp.prod, "prod")
max = _reduce(jnp.max, "max")
min = _reduce(jnp.min, "min")
nansum = _reduce(jnp.nansum, "nansum")
nanprod = _reduce(jnp.nanprod, "nanprod")
sum_axis = sum
max_axis = max
min_axis = min


def argmax(data, axis=None, keepdims=False, **kw):
    return _apply(lambda x: jnp.argmax(x, axis=axis, keepdims=keepdims).astype(jnp.float32),
                  [data], "argmax", nondiff=True,
                  fuse=("argmax", axis, keepdims))


def argmin(data, axis=None, keepdims=False, **kw):
    return _apply(lambda x: jnp.argmin(x, axis=axis, keepdims=keepdims).astype(jnp.float32),
                  [data], "argmin", nondiff=True,
                  fuse=("argmin", axis, keepdims))


def argmax_channel(data, **kw):
    """Argmax over the channel axis (axis 1), returned as float
    (REF:src/operator/tensor/broadcast_reduce_op_index.cc
    argmax_channel — the metric/accuracy helper)."""
    return _apply(lambda x: jnp.argmax(x, axis=1).astype(jnp.float32),
                  [data], "argmax_channel", nondiff=True)


def norm(data, ord=2, axis=None, keepdims=False, **kw):
    ax = _norm_axis(axis)

    def f(x):
        if ord == 1:
            return jnp.sum(jnp.abs(x), axis=ax, keepdims=keepdims)
        return jnp.sqrt(jnp.sum(jnp.square(x), axis=ax, keepdims=keepdims))

    return _apply(f, [data], "norm",
                  fuse=("norm",) + _scalar_key(ord) + (ax, keepdims))


def cumsum(data, axis=None, dtype=None):
    return _apply(lambda x: jnp.cumsum(x, axis=axis, dtype=dtype), [data], "cumsum")


# ----------------------------------------------------------------------------
# shape manipulation
# ----------------------------------------------------------------------------
def reshape(data, shape=None, reverse=False, **kw):
    """MXNet reshape with special codes 0 (keep), -1 (infer), -2.. subset."""
    target = tuple(shape)

    def f(x):
        out, src = [], list(x.shape)
        i = 0
        for s in target:
            if s == 0:
                out.append(src[i]); i += 1
            elif s == -1:
                out.append(-1); i += 1
            elif s == -2:
                out.extend(src[i:]); i = len(src)
            elif s == -3:
                out.append(src[i] * src[i + 1]); i += 2
            elif s == -4:
                continue  # handled by following explicit dims
            else:
                out.append(s); i += 1
        return jnp.reshape(x, tuple(out))

    return _apply(f, [data], "reshape")


def reshape_like(lhs, rhs, **kw):
    return _apply(lambda x, y: jnp.reshape(x, y.shape), [lhs, rhs], "reshape_like")


def flatten(data, **kw):
    return _apply(lambda x: jnp.reshape(x, (x.shape[0], -1)), [data], "flatten")


Flatten = flatten


def transpose(data, axes=None, **kw):
    ax = tuple(axes) if axes else None
    return _apply(lambda x: jnp.transpose(x, ax), [data], "transpose")


def swapaxes(data, dim1=0, dim2=0, **kw):
    return _apply(lambda x: jnp.swapaxes(x, dim1, dim2), [data], "swapaxes")


SwapAxis = swapaxes


def expand_dims(data, axis, **kw):
    return _apply(lambda x: jnp.expand_dims(x, axis), [data], "expand_dims")


def space_to_depth(data, block_size, **kw):
    """REF:src/operator/tensor/matrix_op.cc space_to_depth — NCHW:
    (N,C,H,W) -> (N, b²C, H/b, W/b), block offsets leading the channels."""
    b = int(block_size)

    def f(x):
        n, c, h, w = x.shape
        y = jnp.reshape(x, (n, c, h // b, b, w // b, b))
        y = jnp.transpose(y, (0, 3, 5, 1, 2, 4))
        return jnp.reshape(y, (n, b * b * c, h // b, w // b))

    return _apply(f, [data], "space_to_depth")


def depth_to_space(data, block_size, **kw):
    """Inverse of space_to_depth (REF:src/operator/tensor/matrix_op.cc)."""
    b = int(block_size)

    def f(x):
        n, c, h, w = x.shape
        y = jnp.reshape(x, (n, b, b, c // (b * b), h, w))
        y = jnp.transpose(y, (0, 3, 4, 1, 5, 2))
        return jnp.reshape(y, (n, c // (b * b), h * b, w * b))

    return _apply(f, [data], "depth_to_space")


def squeeze(data, axis=None, **kw):
    return _apply(lambda x: jnp.squeeze(x, axis=axis), [data], "squeeze")


def broadcast_to(data, shape, **kw):
    tgt = tuple(shape)

    def f(x):
        # MXNet allows 0 meaning "keep this dim"
        full = tuple(x.shape[i] if s == 0 else s for i, s in enumerate(tgt))
        return jnp.broadcast_to(x, full)

    return _apply(f, [data], "broadcast_to")


def broadcast_axis(data, axis=0, size=1, **kw):
    axes = axis if isinstance(axis, (list, tuple)) else (axis,)
    sizes = size if isinstance(size, (list, tuple)) else (size,)

    def f(x):
        shp = list(x.shape)
        for a, s in zip(axes, sizes):
            shp[a] = s
        return jnp.broadcast_to(x, tuple(shp))

    return _apply(f, [data], "broadcast_axis")


def broadcast_like(lhs, rhs, **kw):
    return _apply(lambda x, y: jnp.broadcast_to(x, y.shape), [lhs, rhs], "broadcast_like")


def flip(data, axis, **kw):
    return _apply(lambda x: jnp.flip(x, axis=axis), [data], "flip")


reverse = flip


def tile(data, reps, **kw):
    return _apply(lambda x: jnp.tile(x, reps), [data], "tile")


def repeat(data, repeats, axis=None, **kw):
    return _apply(lambda x: jnp.repeat(x, repeats, axis=axis), [data], "repeat")


def pad(data, mode="constant", pad_width=None, constant_value=0, **kw):
    """Reference pad op: pad_width is the flat (before,after) per-dim tuple."""
    pw = list(zip(pad_width[::2], pad_width[1::2]))
    jmode = {"constant": "constant", "edge": "edge", "reflect": "reflect"}[mode]

    def f(x):
        if jmode == "constant":
            return jnp.pad(x, pw, mode="constant", constant_values=constant_value)
        return jnp.pad(x, pw, mode=jmode)

    return _apply(f, [data], "pad")


Pad = pad


def concat(*data, dim=1, **kw):
    if len(data) == 1 and isinstance(data[0], (list, tuple)):
        data = tuple(data[0])
    return _apply(lambda *xs: jnp.concatenate(xs, axis=dim), list(data), "concat")


Concat = concat


def stack(*data, axis=0, **kw):
    if len(data) == 1 and isinstance(data[0], (list, tuple)):
        data = tuple(data[0])
    return _apply(lambda *xs: jnp.stack(xs, axis=axis), list(data), "stack")


def split(data, num_outputs, axis=1, squeeze_axis=False, **kw):
    def f(x):
        parts = jnp.split(x, num_outputs, axis=axis)
        if squeeze_axis:
            parts = [jnp.squeeze(p, axis=axis) for p in parts]
        return tuple(parts)

    out = _apply(f, [data], "split")
    return out


SliceChannel = split


def slice(data, begin, end, step=None, **kw):
    def f(x):
        idx = []
        for i in range(len(begin)):
            b = begin[i]
            e = end[i] if end[i] is not None else x.shape[i]
            s = (step[i] if step else None) or 1
            idx.append(builtins.slice(b, e, s))
        return x[tuple(idx)]

    return _apply(f, [data], "slice")


def slice_axis(data, axis, begin, end, **kw):
    def f(x):
        e = end if end is not None else x.shape[axis]
        return lax.slice_in_dim(x, begin, e, axis=axis)

    return _apply(f, [data], "slice_axis")


def slice_like(data, shape_like, axes=None, **kw):
    def f(x, y):
        idx = [builtins.slice(None)] * x.ndim
        dims = axes if axes is not None else range(y.ndim)
        for a in dims:
            idx[a] = builtins.slice(0, y.shape[a])
        return x[tuple(idx)]

    return _apply(f, [data, shape_like], "slice_like")


def clip(data, a_min, a_max, **kw):
    fuse = ("clip",) + _scalar_key(a_min, a_max) \
        if isinstance(a_min, (int, float)) and isinstance(a_max, (int, float)) \
        else None
    return _apply(lambda x: jnp.clip(x, a_min, a_max), [data], "clip",
                  fuse=fuse)


def where(condition, x, y, **kw):
    return _apply(lambda c, a, b: jnp.where(c != 0, a, b), [condition, x, y],
                  "where", fuse="where")


# ----------------------------------------------------------------------------
# indexing ops
# ----------------------------------------------------------------------------
def take(a, indices, axis=0, mode="clip", **kw):
    jmode = {"clip": "clip", "wrap": "wrap", "raise": "clip"}[mode]
    return _apply(
        lambda x, i: jnp.take(x, i.astype(jnp.int32), axis=axis, mode=jmode),
        [a, indices], "take")


def pick(data, index, axis=-1, keepdims=False, **kw):
    def f(x, i):
        out = jnp.take_along_axis(
            x, jnp.expand_dims(i.astype(jnp.int32), axis=axis), axis=axis)
        return out if keepdims else jnp.squeeze(out, axis=axis)

    return _apply(f, [data, index], "pick")


def gather_nd(data, indices, **kw):
    def f(x, i):
        i = i.astype(jnp.int32)
        return x[tuple(i[k] for k in range(i.shape[0]))]

    return _apply(f, [data, indices], "gather_nd")


def scatter_nd(data, indices, shape, **kw):
    def f(d, i):
        i = i.astype(jnp.int32)
        out = jnp.zeros(tuple(shape), d.dtype)
        return out.at[tuple(i[k] for k in range(i.shape[0]))].add(d)

    return _apply(f, [data, indices], "scatter_nd")


def one_hot(indices, depth, on_value=1.0, off_value=0.0, dtype="float32", **kw):
    def f(i):
        oh = jax.nn.one_hot(i.astype(jnp.int32), depth, dtype=jnp.dtype(dtype))
        return oh * (on_value - off_value) + off_value

    return _apply(f, [indices], "one_hot", nondiff=True)


def Embedding(data, weight, input_dim=None, output_dim=None, dtype="float32",
              sparse_grad=False, **kw):
    """Embedding lookup (REF:src/operator/tensor/indexing_op.cc).  `sparse_grad`
    (row_sparse in the reference) has no TPU analog; gradients are dense —
    XLA turns the gather-vjp into an efficient scatter-add (SURVEY §7.3.4)."""
    return _apply(lambda i, w: jnp.take(w, i.astype(jnp.int32), axis=0),
                  [data, weight], "Embedding")


def SequenceMask(data, sequence_length=None, use_sequence_length=False, value=0.0,
                 axis=0, **kw):
    if not use_sequence_length or sequence_length is None:
        return identity(data)

    def f(x, sl):
        steps = jnp.arange(x.shape[axis])
        mask = steps[:, None] < sl[None, :]  # (T, B)
        if axis == 1:
            mask = mask.T
        mask = mask.reshape(mask.shape + (1,) * (x.ndim - 2))
        return jnp.where(mask, x, jnp.asarray(value, x.dtype))

    return _apply(f, [data, sequence_length], "SequenceMask")


def SequenceReverse(data, sequence_length=None, use_sequence_length=False, axis=0, **kw):
    if not use_sequence_length or sequence_length is None:
        return flip(data, axis=axis)

    def f(x, sl):
        T = x.shape[axis]
        idx = jnp.arange(T)[:, None]  # (T,1)
        rev = sl[None, :].astype(jnp.int32) - 1 - idx
        gather_idx = jnp.where(idx < sl[None, :], rev, idx)  # (T,B)
        return jnp.take_along_axis(
            x, gather_idx.reshape(gather_idx.shape + (1,) * (x.ndim - 2)), axis=0)

    return _apply(f, [data, sequence_length], "SequenceReverse")


def SequenceLast(data, sequence_length=None, use_sequence_length=False, axis=0, **kw):
    def f(x, *sl):
        if sl:
            idx = sl[0].astype(jnp.int32) - 1
        else:
            idx = jnp.full((x.shape[1],), x.shape[axis] - 1, jnp.int32)
        return jnp.take_along_axis(
            x, idx.reshape((1, -1) + (1,) * (x.ndim - 2)), axis=0)[0]

    args = [data] + ([sequence_length] if use_sequence_length else [])
    return _apply(f, args, "SequenceLast")


# ----------------------------------------------------------------------------
# ordering
# ----------------------------------------------------------------------------
def sort(data, axis=-1, is_ascend=True, **kw):
    def f(x):
        s = jnp.sort(x, axis=axis)
        return s if is_ascend else jnp.flip(s, axis=axis)

    return _apply(f, [data], "sort")


def argsort(data, axis=-1, is_ascend=True, dtype="float32", **kw):
    def f(x):
        s = jnp.argsort(x, axis=axis)
        if not is_ascend:
            s = jnp.flip(s, axis=axis)
        return s.astype(jnp.dtype(dtype))

    return _apply(f, [data], "argsort", nondiff=True)


def topk(data, axis=-1, k=1, ret_typ="indices", is_ascend=False, dtype="float32", **kw):
    def f(x):
        xm = jnp.moveaxis(x, axis, -1)
        vals, idx = lax.top_k(-xm if is_ascend else xm, k)
        if is_ascend:
            vals = -vals
        vals = jnp.moveaxis(vals, -1, axis)
        idx = jnp.moveaxis(idx, -1, axis)
        if ret_typ == "value":
            return vals
        if ret_typ == "indices":
            return idx.astype(jnp.dtype(dtype))
        if ret_typ == "both":
            return (vals, idx.astype(jnp.dtype(dtype)))
        if ret_typ == "mask":
            m = jnp.zeros_like(xm, dtype=jnp.dtype(dtype))
            m = m.at[..., :].set(0)
            oh = jax.nn.one_hot(jnp.moveaxis(idx, axis, -1), x.shape[axis],
                                dtype=jnp.dtype(dtype)).sum(-2)
            return jnp.moveaxis(oh, -1, axis)
        raise ValueError(ret_typ)

    nondiff = ret_typ != "value"
    return _apply(f, [data], "topk", nondiff=nondiff)


# ----------------------------------------------------------------------------
# linear algebra
# ----------------------------------------------------------------------------
def dot(lhs, rhs, transpose_a=False, transpose_b=False, **kw):
    """Reference `dot`: contracts last axis of lhs with first of rhs; the
    transpose flags apply matrix-transpose semantics (2-D fast path hits the
    MXU as a single matmul)."""

    def f(a, b):
        if transpose_a:
            a = jnp.swapaxes(a, -1, -2) if a.ndim > 1 else a
        if transpose_b:
            b = jnp.swapaxes(b, -1, -2) if b.ndim > 1 else b
        if a.ndim == 2 and b.ndim == 2:
            return a @ b
        return jnp.tensordot(a, b, axes=([-1], [0]))

    return _apply(f, [lhs, rhs], "dot")


def batch_dot(lhs, rhs, transpose_a=False, transpose_b=False, **kw):
    def f(a, b):
        if transpose_a:
            a = jnp.swapaxes(a, -1, -2)
        if transpose_b:
            b = jnp.swapaxes(b, -1, -2)
        return jnp.matmul(a, b)

    return _apply(f, [lhs, rhs], "batch_dot")


def linalg_gemm2(A, B, transpose_a=False, transpose_b=False, alpha=1.0, **kw):
    def f(a, b):
        if transpose_a:
            a = jnp.swapaxes(a, -1, -2)
        if transpose_b:
            b = jnp.swapaxes(b, -1, -2)
        return alpha * jnp.matmul(a, b)

    return _apply(f, [A, B], "linalg_gemm2")


def linalg_potrf(A, **kw):
    return _apply(lambda a: jnp.linalg.cholesky(a), [A], "linalg_potrf")


def linalg_syrk(A, transpose=False, alpha=1.0, **kw):
    def f(a):
        at = jnp.swapaxes(a, -1, -2)
        return alpha * (jnp.matmul(at, a) if transpose else jnp.matmul(a, at))

    return _apply(f, [A], "linalg_syrk")


# ----------------------------------------------------------------------------
# neural-net ops (REF:src/operator/nn/**) — XLA-native forms
# ----------------------------------------------------------------------------
def _pair(v, n):
    if v is None:
        return (0,) * n if n else ()
    if isinstance(v, int):
        return (v,) * n
    t = tuple(v)
    return t if len(t) == n else t + t[-1:] * (n - len(t))


def FullyConnected(data, weight, bias=None, num_hidden=None, no_bias=False,
                   flatten=True, **kw):
    """y = x·Wᵀ + b (REF:src/operator/nn/fully_connected.cc).  Contracted as a
    single MXU matmul; `flatten` collapses trailing dims like the reference."""

    def f(x, w, *b):
        if flatten and x.ndim > 2:
            x = x.reshape(x.shape[0], -1)
        y = jnp.matmul(x, w.T) if x.ndim <= 2 else jnp.einsum("...i,oi->...o", x, w)
        if b:
            y = y + b[0]
        return y

    args = [data, weight] + ([] if (no_bias or bias is None) else [bias])
    return _apply(f, args, "FullyConnected")


def Convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                pad=None, num_filter=None, num_group=1, no_bias=False,
                layout=None, **kw):
    """N-D convolution (REF:src/operator/nn/convolution.cc; cuDNN path replaced
    by `lax.conv_general_dilated`, which XLA tiles onto the MXU).

    `layout` selects the data layout as in the reference ("NCHW", "NHWC",
    "NCW", "NWC", "NCDHW", "NDHWC"; default channels-first).  Channels-last
    puts C in the TPU lane dimension, so prefer NHWC for the image path
    (weight layout is then O<spatial>I, matching the reference's NHWC
    convention)."""
    nd_ = len(kernel)
    strides = _pair(stride, nd_) if stride else (1,) * nd_
    dilation = _pair(dilate, nd_) if dilate else (1,) * nd_
    padding = [(p, p) for p in (_pair(pad, nd_) if pad else (0,) * nd_)]
    spatial = "DHW"[-nd_:]
    if layout is None:
        layout = "NC" + spatial
    channels_last = _layout_mod.is_channels_last(layout)
    wspec = ("O" + spatial + "I") if channels_last else ("OI" + spatial)
    dn = (layout, wspec, layout)
    bshape = ((1,) * (nd_ + 1) + (-1,)) if channels_last \
        else ((1, -1) + (1,) * nd_)

    def f(x, w, *b):
        # NOTE: no preferred_element_type — jax 0.9's conv transpose rule
        # emits mismatched-dtype convs under grad with it; XLA:TPU already
        # accumulates bf16 convs in f32 on the MXU
        y = lax.conv_general_dilated(
            x, w, window_strides=strides, padding=padding,
            rhs_dilation=dilation, dimension_numbers=dn,
            feature_group_count=num_group)
        if b:
            y = y + b[0].reshape(bshape)
        return y

    args = [data, weight] + ([] if (no_bias or bias is None) else [bias])
    return _apply(f, args, "Convolution")


def Deconvolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                  pad=None, adj=None, num_filter=None, num_group=1, no_bias=True,
                  layout=None, **kw):
    """Transposed conv (REF:src/operator/nn/deconvolution.cc).  `adj` (the
    output_padding) extends the trailing pad so out = (i-1)*s - 2p + d*(k-1)
    + 1 + adj, matching the reference's output-size formula.  `layout` as in
    Convolution; channels-last weights are I<spatial>O."""
    nd_ = len(kernel)
    strides = _pair(stride, nd_) if stride else (1,) * nd_
    dilation = _pair(dilate, nd_) if dilate else (1,) * nd_
    padding = _pair(pad, nd_) if pad else (0,) * nd_
    adjust = _pair(adj, nd_) if adj else (0,) * nd_
    spatial = "DHW"[-nd_:]
    if layout is None:
        layout = "NC" + spatial
    channels_last = _layout_mod.is_channels_last(layout)
    wspec = ("I" + spatial + "O") if channels_last else ("IO" + spatial)
    dn = (layout, wspec, layout)
    bshape = ((1,) * (nd_ + 1) + (-1,)) if channels_last \
        else ((1, -1) + (1,) * nd_)

    def f(x, w, *b):
        pads = [(d * (k - 1) - p, d * (k - 1) - p + a)
                for k, p, a, d in zip(kernel, padding, adjust, dilation)]
        y = lax.conv_general_dilated(
            x, w, window_strides=(1,) * nd_, padding=pads,
            lhs_dilation=strides, rhs_dilation=dilation,
            dimension_numbers=dn, feature_group_count=num_group)
        if b:
            y = y + b[0].reshape(bshape)
        return y

    args = [data, weight] + ([] if (no_bias or bias is None) else [bias])
    return _apply(f, args, "Deconvolution")


def Pooling(data, kernel=None, pool_type="max", global_pool=False, stride=None,
            pad=None, pooling_convention="valid", count_include_pad=True,
            layout=None, **kw):
    """Max/avg/sum pooling via `lax.reduce_window`
    (REF:src/operator/nn/pooling.cc).  `layout` as in Convolution."""
    channels_last = _layout_mod.is_channels_last(layout)

    def f(x):
        nd_ = x.ndim - 2
        spatial_axes = tuple(range(1, x.ndim - 1)) if channels_last \
            else tuple(range(2, x.ndim))
        if global_pool:
            return x.mean(axis=spatial_axes, keepdims=True) \
                if pool_type == "avg" else (
                    x.max(axis=spatial_axes, keepdims=True)
                    if pool_type == "max"
                    else x.sum(axis=spatial_axes, keepdims=True))
        k = _pair(kernel, nd_)
        s = _pair(stride, nd_) if stride else k
        p = _pair(pad, nd_) if pad else (0,) * nd_
        if pooling_convention == "full":
            # ceil-mode: extend right/bottom padding so no element is dropped
            spad = [(pp, pp + st - 1) for pp, st in zip(p, s)]
        else:
            spad = [(pp, pp) for pp in p]
        if channels_last:
            window, strides = (1,) + k + (1,), (1,) + s + (1,)
            padding = [(0, 0)] + spad + [(0, 0)]
        else:
            window, strides = (1, 1) + k, (1, 1) + s
            padding = [(0, 0), (0, 0)] + spad
        if pool_type == "max":
            init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
            return lax.reduce_window(x, init, lax.max, window, strides, padding)
        ssum = lax.reduce_window(x, 0.0, lax.add, window, strides, padding)
        if pool_type == "sum":
            return ssum
        if count_include_pad:
            return ssum / _np.prod(k)
        ones_ = jnp.ones_like(x)
        cnt = lax.reduce_window(ones_, 0.0, lax.add, window, strides, padding)
        return ssum / cnt

    return _apply(f, [data], "Pooling")


def Activation(data, act_type="relu", **kw):
    fns = {
        "relu": jax.nn.relu,
        "sigmoid": jax.nn.sigmoid,
        "tanh": jnp.tanh,
        "softrelu": jax.nn.softplus,
        "softsign": jax.nn.soft_sign,
    }
    return _apply(fns[act_type], [data], f"Activation[{act_type}]")


def LeakyReLU(data, gamma=None, act_type="leaky", slope=0.25, lower_bound=0.125,
              upper_bound=0.334, **kw):
    if act_type == "leaky":
        return _apply(lambda x: jax.nn.leaky_relu(x, slope), [data], "LeakyReLU")
    if act_type == "elu":
        return _apply(lambda x: jax.nn.elu(x, slope), [data], "elu")
    if act_type == "selu":
        return _apply(jax.nn.selu, [data], "selu")
    if act_type == "gelu":
        return _apply(lambda x: jax.nn.gelu(x, approximate=False), [data], "gelu")
    if act_type == "prelu":
        return _apply(lambda x, g: jnp.where(x >= 0, x, g * x), [data, gamma], "prelu")
    raise ValueError(act_type)


def gelu(data, **kw):
    return _apply(lambda x: jax.nn.gelu(x, approximate=False), [data], "gelu")


def gelu_tanh(data, **kw):
    return _apply(lambda x: jax.nn.gelu(x, approximate=True), [data], "gelu_tanh")


def softmax(data, axis=-1, temperature=None, length=None, **kw):
    def f(x, *ln):
        z = x / temperature if temperature else x
        if ln:
            steps = jnp.arange(x.shape[axis])
            shape = [1] * x.ndim
            shape[axis] = x.shape[axis]
            mask = steps.reshape(shape) < ln[0].reshape(
                ln[0].shape + (1,) * (x.ndim - ln[0].ndim))
            z = jnp.where(mask, z, -jnp.inf)
        return jax.nn.softmax(z, axis=axis)

    args = [data] + ([length] if length is not None else [])
    fuse = ("softmax", axis) + _scalar_key(temperature) if length is None \
        and isinstance(temperature, (int, float, type(None))) else None
    return _apply(f, args, "softmax", fuse=fuse)


def log_softmax(data, axis=-1, temperature=None, **kw):
    def f(x):
        z = x / temperature if temperature else x
        return jax.nn.log_softmax(z, axis=axis)

    fuse = ("log_softmax", axis) + _scalar_key(temperature) \
        if isinstance(temperature, (int, float, type(None))) else None
    return _apply(f, [data], "log_softmax", fuse=fuse)


def softmin(data, axis=-1, **kw):
    return _apply(lambda x: jax.nn.softmax(-x, axis=axis), [data], "softmin")


def softmax_cross_entropy(data, label, **kw):
    def f(x, y):
        logp = jax.nn.log_softmax(x, axis=-1)
        oh = jax.nn.one_hot(y.astype(jnp.int32), x.shape[-1], dtype=x.dtype)
        return -jnp.sum(oh * logp)

    return _apply(f, [data, label], "softmax_cross_entropy")


def SoftmaxActivation(data, mode="instance", **kw):
    axis = 1 if mode == "channel" else -1
    return softmax(data, axis=axis)


def _onepass_stats(xf, axis, keepdims=True):
    """(mean, var) via sum / sum-of-squares in ONE pass.  ONLY for
    reductions that span non-minor axes over more data than a VMEM tile
    (BatchNorm's (N, *S) reduce): there the classic mean->var chain
    forces two real HBM reads, while sibling sums fuse into one.  For
    ROW-LOCAL norms (LayerNorm & friends, minor-axis reduce) XLA already
    fuses the whole chain into one pass per row — use the two-pass
    mean/var there: it costs nothing and is cancellation-safe, whereas
    E[x^2]-E[x]^2 in f32 collapses for |mean|/std ≳ 1e3 (var rounds to
    the 0-clamp and rsqrt(eps) amplifies garbage).  BatchNorm inputs are
    post-conv/near-zero-mean, where the cancellation is benign."""
    n = 1
    ax = axis if isinstance(axis, tuple) else (axis,)
    for a in ax:
        n *= xf.shape[a]
    s1 = xf.sum(axis=axis, keepdims=keepdims)
    s2 = jnp.square(xf).sum(axis=axis, keepdims=keepdims)
    mu = s1 / n
    return mu, jnp.maximum(s2 / n - jnp.square(mu), 0.0)


def LayerNorm(data, gamma=None, beta=None, axis=-1, eps=1e-5, **kw):
    """REF:src/operator/nn/layer_norm.cc — fp32 statistics for bf16
    inputs.  Two-pass mean/var on purpose: the reduce is row-local
    (minor axis), which XLA fuses into one HBM pass anyway, and the
    two-pass form is cancellation-safe (see _onepass_stats)."""

    def f(x, g, b):
        xf = x.astype(jnp.float32)
        mu = xf.mean(axis=axis, keepdims=True)
        var = jnp.square(xf - mu).mean(axis=axis, keepdims=True)
        y = (xf - mu) * lax.rsqrt(var + eps)
        shape = [1] * x.ndim
        shape[axis] = x.shape[axis]
        return (y * g.reshape(shape) + b.reshape(shape)).astype(x.dtype)

    return _apply(f, [data, gamma, beta], "LayerNorm")


def RMSNorm(data, gamma=None, axis=-1, eps=1e-6, **kw):
    def f(x, g):
        xf = x.astype(jnp.float32)
        ms = jnp.square(xf).mean(axis=axis, keepdims=True)
        shape = [1] * x.ndim
        shape[axis] = x.shape[axis]
        return (xf * lax.rsqrt(ms + eps) * g.reshape(shape)).astype(x.dtype)

    return _apply(f, [data, gamma], "RMSNorm")


def InstanceNorm(data, gamma, beta, eps=1e-3, **kw):
    def f(x, g, b):
        ax = tuple(range(2, x.ndim))
        xf = x.astype(jnp.float32)
        mu = xf.mean(axis=ax, keepdims=True)
        var = jnp.square(xf - mu).mean(axis=ax, keepdims=True)
        shape = (1, -1) + (1,) * (x.ndim - 2)
        gf = g.reshape(shape).astype(jnp.float32)
        bf = b.reshape(shape).astype(jnp.float32)
        return ((xf - mu) * lax.rsqrt(var + eps) * gf + bf).astype(x.dtype)

    return _apply(f, [data, gamma, beta], "InstanceNorm")


def GroupNorm(data, gamma, beta, num_groups=1, eps=1e-5, **kw):
    """REF:src/operator/nn/group_norm.cc — (N, C, *S) input, C split into
    num_groups; f32 statistics for low-precision inputs."""
    def f(x, g, b):
        n = x.shape[0]
        xf = x.astype(jnp.float32).reshape((n, num_groups, -1))
        mu = xf.mean(axis=2, keepdims=True)
        var = jnp.square(xf - mu).mean(axis=2, keepdims=True)
        yf = (xf - mu) * lax.rsqrt(var + eps)
        # affine is PER GROUP, matching the reference's (num_groups,)
        # gamma/beta (REF:src/operator/nn/group_norm.cc)
        yf = yf * g.reshape((1, -1, 1)).astype(jnp.float32) + \
            b.reshape((1, -1, 1)).astype(jnp.float32)
        return yf.reshape(x.shape).astype(x.dtype)

    return _apply(f, [data, gamma, beta], "GroupNorm")


def L2Normalization(data, eps=1e-10, mode="instance", **kw):
    def f(x):
        if mode == "channel":
            ax = (1,)
        elif mode == "spatial":
            ax = tuple(range(2, x.ndim))
        else:
            ax = tuple(range(1, x.ndim))
        # norm-op precision policy (docs r5): the sum-of-squares
        # accumulates in f32 (XLA fuses the convert into the reduce
        # read), result back in x.dtype — a bf16 accumulation over 512
        # channels costs ~1% on the denominator
        xf = x.astype(jnp.float32)
        nrm = jnp.sqrt(jnp.sum(jnp.square(xf), axis=ax, keepdims=True)
                       + eps)
        return (xf / nrm).astype(x.dtype)

    return _apply(f, [data], "L2Normalization")


def batch_norm_core(x, gamma, beta, moving_mean, moving_var, eps, use_batch_stats,
                    axis=1, fix_gamma=False):
    """Pure BN forward; returns (out, batch_mean, batch_var).  Gluon's
    BatchNorm layer owns the running-stat update (the reference did it via
    FMutateInputs on aux states — here state flows functionally, SURVEY §7.1).
    One-pass sum/sum-of-squares statistics (no mean->var reduce dependency,
    so XLA sibling-fuses both into a single read of x) and a folded
    per-channel scale/bias applied in x.dtype — the r5 HBM byte diet;
    same formulation as gluon.nn.BatchNorm."""
    axis = axis % x.ndim
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    if use_batch_stats:
        red = tuple(i for i in range(x.ndim) if i != axis)
        mu, var = _onepass_stats(x.astype(jnp.float32), red,
                                 keepdims=False)
    else:
        mu = moving_mean.astype(jnp.float32)
        var = moving_var.astype(jnp.float32)
    scale = lax.rsqrt(var + eps) * g.astype(jnp.float32)
    bias = beta.astype(jnp.float32) - mu * scale
    y = x * scale.reshape(shape).astype(x.dtype) + \
        bias.reshape(shape).astype(x.dtype)
    return y, mu, var


def BatchNorm(data, gamma, beta, moving_mean, moving_var, eps=1e-5, momentum=0.9,
              fix_gamma=True, use_global_stats=False, axis=1, **kw):
    """Op-level BatchNorm (inference-style unless recording; Gluon layer drives
    the training path with running-stat updates)."""
    training = autograd.is_training() and not use_global_stats

    def f(x, g, b, mm, mv):
        y, _, _ = batch_norm_core(x, g, b, mm, mv, eps, training, axis, fix_gamma)
        return y

    return _apply(f, [data, gamma, beta, moving_mean, moving_var], "BatchNorm")


def Dropout(data, p=0.5, mode="training", axes=None, **kw):
    """REF:src/operator/nn/dropout.cc — inverted dropout; key from the RNG
    stream (traced key inside hybridize, eager split otherwise)."""
    if not (autograd.is_training() or mode == "always") or p <= 0:
        return identity(data)
    from .. import random as _random
    key = _random.take_key()

    def f(x):
        shape = None
        if axes:
            shape = tuple(1 if i in axes else s for i, s in enumerate(x.shape))
        return _random.dropout(x, key, p, shape)

    return _apply(f, [data], "Dropout")


# ----------------------------------------------------------------------------
# optimizer update ops (REF:src/operator/optimizer_op.cc fused updates).
# Pure cores used by both the imperative optimizer and jitted train steps.
# ----------------------------------------------------------------------------
def sgd_update_core(weight, grad, lr, wd, rescale_grad=1.0, clip_gradient=None):
    g = grad * rescale_grad
    if clip_gradient is not None and clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    return weight - lr * (g + wd * weight)


def sgd_mom_update_core(weight, grad, mom, lr, momentum, wd, rescale_grad=1.0,
                        clip_gradient=None):
    g = grad * rescale_grad
    if clip_gradient is not None and clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    new_mom = momentum * mom - lr * (g + wd * weight)
    return weight + new_mom, new_mom


def adam_update_core(weight, grad, mean, var, lr, beta1, beta2, epsilon, wd, t,
                     rescale_grad=1.0, clip_gradient=None, lazy_update=False):
    g = grad * rescale_grad
    if clip_gradient is not None and clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    g = g + wd * weight
    m = beta1 * mean + (1 - beta1) * g
    v = beta2 * var + (1 - beta2) * jnp.square(g)
    mhat = m / (1 - beta1 ** t)
    vhat = v / (1 - beta2 ** t)
    return weight - lr * mhat / (jnp.sqrt(vhat) + epsilon), m, v


def sgd_update(weight, grad, lr, wd=0.0, rescale_grad=1.0, clip_gradient=-1,
               out=None, **kw):
    cg = clip_gradient if clip_gradient and clip_gradient > 0 else None
    # fusible elementwise update (an engine.bulk() around a parameter loop
    # bulks the whole sweep); all hyper-params ride the key — a schedule
    # changing lr compiles a fresh chain, same as the reference re-bulking
    fuse = ("sgd_update",) + _scalar_key(lr, wd, rescale_grad, cg) \
        if all(isinstance(v, (int, float, type(None)))
               for v in (lr, wd, rescale_grad, cg)) else None
    res = _apply(lambda w, g: sgd_update_core(w, g, lr, wd, rescale_grad, cg),
                 [weight, grad], "sgd_update", nondiff=True, fuse=fuse)
    if out is not None:
        out._rebind(res._data)
        return out
    return res


# ----------------------------------------------------------------------------
# random samplers (REF:src/operator/random/**) — see tpu_mx.random for state
# ----------------------------------------------------------------------------
def _rand(shape, sampler, dtype, ctx):
    from .. import random as _random
    key = _random.take_key()
    data = sampler(key, tuple(shape) if shape else ())
    return _place(data.astype(dtype), ctx)


def random_uniform(low=0.0, high=1.0, shape=(1,), dtype="float32", ctx=None, **kw):
    return _rand(shape, lambda k, s: jax.random.uniform(k, s, minval=low, maxval=high),
                 dtype, ctx)


def random_normal(loc=0.0, scale=1.0, shape=(1,), dtype="float32", ctx=None, **kw):
    return _rand(shape, lambda k, s: loc + scale * jax.random.normal(k, s), dtype, ctx)


def random_randint(low, high, shape=(1,), dtype="int32", ctx=None, **kw):
    return _rand(shape, lambda k, s: jax.random.randint(k, s, low, high), dtype, ctx)


def random_gamma(alpha=1.0, beta=1.0, shape=(1,), dtype="float32", ctx=None, **kw):
    return _rand(shape, lambda k, s: jax.random.gamma(k, alpha, s) * beta, dtype, ctx)


def random_exponential(scale=1.0, shape=(1,), dtype="float32", ctx=None, **kw):
    return _rand(shape, lambda k, s: jax.random.exponential(k, s) * scale, dtype, ctx)


def random_poisson(lam=1.0, shape=(1,), dtype="float32", ctx=None, **kw):
    return _rand(shape, lambda k, s: jax.random.poisson(k, lam, s), dtype, ctx)


def random_bernoulli(prob=0.5, shape=(1,), dtype="float32", ctx=None, **kw):
    return _rand(shape, lambda k, s: jax.random.bernoulli(k, prob, s), dtype, ctx)


def sample_multinomial(data, shape=1, get_prob=False, dtype="int32", **kw):
    from .. import random as _random
    key = _random.take_key()
    n = shape if isinstance(shape, int) else int(_np.prod(shape))

    def f(p):
        logits = jnp.log(jnp.maximum(p, 1e-30))
        return jax.random.categorical(key, logits, axis=-1,
                                      shape=(n,) + p.shape[:-1]).astype(jnp.dtype(dtype))

    res = _apply(lambda p: jnp.moveaxis(f(p), 0, -1).squeeze(-1) if n == 1
                 else jnp.moveaxis(f(p), 0, -1), [data], "sample_multinomial",
                 nondiff=True)
    return res


def shuffle(data, **kw):
    from .. import random as _random
    key = _random.take_key()
    return _apply(lambda x: jax.random.permutation(key, x, axis=0), [data], "shuffle",
                  nondiff=True)


# ---------------------------------------------------------------------------
# legacy output heads (REF:src/operator/softmax_output.cc,
# REF:src/operator/regression_output-inl.h, REF:src/operator/make_loss.cc).
# These are loss layers: forward is the prediction, backward *injects* the
# loss gradient regardless of the incoming head gradient — realized here with
# `jax.custom_vjp` so the same semantics hold under the symbolic executor.
# ---------------------------------------------------------------------------

def _output_head(fwd_fn, grad_fn, name):
    @jax.custom_vjp
    def head(x, y):
        return fwd_fn(x, y)

    def head_fwd(x, y):
        out = fwd_fn(x, y)
        return out, (out, x, y)

    def head_bwd(res, g):
        out, x, y = res
        del g  # loss layer: incoming head grad ignored (reference semantics)
        ylike = jnp.zeros_like(y) if isinstance(y, jnp.ndarray) else 0.0
        return grad_fn(out, x, y), ylike

    head.defvjp(head_fwd, head_bwd)
    head.__name__ = name
    return head


def SoftmaxOutput(data, label, grad_scale=1.0, ignore_label=-1.0,
                  multi_output=False, use_ignore=False, preserve_shape=False,
                  normalization="null", out_grad=False, smooth_alpha=0.0, **kw):
    """Softmax forward + injected cross-entropy gradient
    (REF:src/operator/softmax_output.cc)."""
    axis = 1 if multi_output else -1

    def fwd(x, y):
        return jax.nn.softmax(x, axis=axis)

    def grad(p, x, y):
        n_class = x.shape[axis]
        yi = y.astype(jnp.int32)
        oh = jax.nn.one_hot(yi, n_class, axis=axis, dtype=x.dtype)
        if smooth_alpha:
            oh = oh * (1.0 - smooth_alpha) + smooth_alpha / n_class
        g = p - oh
        if use_ignore:
            valid = (y != ignore_label).astype(x.dtype)
            g = g * jnp.expand_dims(valid, axis if axis != -1 else x.ndim - 1)
        if normalization == "batch":
            g = g / x.shape[0]
        elif normalization == "valid":
            if use_ignore:
                cnt = jnp.maximum(jnp.sum(y != ignore_label), 1).astype(x.dtype)
            else:
                cnt = jnp.asarray(float(_np.prod(y.shape)), x.dtype)
            g = g / cnt
        return g * grad_scale

    return _apply(_output_head(fwd, grad, "SoftmaxOutput"), [data, label],
                  "SoftmaxOutput")


def SVMOutput(data, label, margin=1.0, regularization_coefficient=1.0,
              use_linear=False, **kw):
    """Hinge-loss output layer (REF:src/operator/svm_output.cc): forward
    is identity (scores pass through), backward injects the L2-SVM (or
    L1 with use_linear) subgradient — for j≠y: λ·h (L1) or 2λ·h (L2)
    with h = max(0, margin + x_j − x_y); for j=y the negative sum."""

    def fwd(x, y):
        return x

    def grad(out, x, y):
        yi = y.astype(jnp.int32)
        n_class = x.shape[-1]
        xy = jnp.take_along_axis(x, yi[..., None], axis=-1)     # (..., 1)
        h = jnp.maximum(0.0, margin + x - xy)                   # (..., C)
        lam = regularization_coefficient
        g = jnp.where(h > 0, lam, 0.0) if use_linear else 2.0 * lam * h
        oh = jax.nn.one_hot(yi, n_class, dtype=x.dtype)
        g = g * (1 - oh)                       # j≠y terms
        g = g - oh * jnp.sum(g, axis=-1, keepdims=True)  # j=y pulls down
        return g.astype(x.dtype)

    return _apply(_output_head(fwd, grad, "SVMOutput"), [data, label],
                  "SVMOutput")


def _regression_head(link, residual, name):
    def make(data, label, grad_scale=1.0, **kw):
        def fwd(x, y):
            return link(x)

        def grad(out, x, y):
            yb = y.reshape(out.shape)
            return residual(out, yb) * (grad_scale / out.shape[0])

        return _apply(_output_head(fwd, grad, name), [data, label], name)

    make.__name__ = name
    return make


LinearRegressionOutput = _regression_head(
    lambda x: x, lambda o, y: o - y, "LinearRegressionOutput")
MAERegressionOutput = _regression_head(
    lambda x: x, lambda o, y: jnp.sign(o - y), "MAERegressionOutput")
LogisticRegressionOutput = _regression_head(
    jax.nn.sigmoid, lambda o, y: o - y, "LogisticRegressionOutput")


def MakeLoss(data, grad_scale=1.0, valid_thresh=0.0, normalization="null", **kw):
    """REF:src/operator/make_loss.cc — treat `data` as a loss value; backward
    injects `grad_scale` (normalized) into it."""

    def fwd(x, y):
        return x

    def grad(out, x, y):
        g = jnp.full_like(x, grad_scale)
        if normalization == "batch":
            g = g / x.shape[0]
        elif normalization == "valid":
            cnt = jnp.maximum(jnp.sum(x > valid_thresh), 1).astype(x.dtype)
            g = g / cnt
        return g

    return _apply(_output_head(fwd, grad, "MakeLoss"), [data, 0.0], "MakeLoss")


# namespace-style aliases matching mx.nd.random.* / mx.random.*
class _RandomNS:
    uniform = staticmethod(random_uniform)
    normal = staticmethod(random_normal)
    randint = staticmethod(random_randint)
    gamma = staticmethod(random_gamma)
    exponential = staticmethod(random_exponential)
    poisson = staticmethod(random_poisson)
    bernoulli = staticmethod(random_bernoulli)
    multinomial = staticmethod(sample_multinomial)
    shuffle = staticmethod(shuffle)


random = _RandomNS()
uniform = random_uniform
normal = random_normal
randn = lambda *shape, **kw: random_normal(shape=shape, **kw)


def Custom(*args, op_type=None, **op_params):
    """User-registered custom op (REF:src/operator/custom/custom.cc);
    register with @mx.operator.register(name), invoke as
    nd.Custom(x, ..., op_type=name, **params)."""
    from .. import operator as _op_mod
    if op_type is None:
        raise ValueError("Custom requires op_type=")
    return _op_mod._invoke_custom(args, op_type, **op_params)


# ----------------------------------------------------------------------------
# extended operator families (separate modules, one public namespace — the
# reference's registry likewise flattens src/operator/** into mx.nd.*)
# ----------------------------------------------------------------------------
from .linalg_ops import *      # noqa: F401,F403,E402
from .vision_ops import *      # noqa: F401,F403,E402
from .ctc import *             # noqa: F401,F403,E402
from .rnn_op import *          # noqa: F401,F403,E402
from .quantized_ops import *   # noqa: F401,F403,E402
from .sample_ops import *      # noqa: F401,F403,E402


# ----------------------------------------------------------------------------
# long-tail parity ops (REF:src/operator/tensor/*, src/operator/*.cc)
# ----------------------------------------------------------------------------
def linalg_gemm(A, B, C, transpose_a=False, transpose_b=False, alpha=1.0,
                beta=1.0, **kw):
    """C' = alpha·op(A)·op(B) + beta·C (REF:src/operator/tensor/la_op.cc)."""

    def f(a, b, c):
        if transpose_a:
            a = jnp.swapaxes(a, -1, -2)
        if transpose_b:
            b = jnp.swapaxes(b, -1, -2)
        return alpha * jnp.matmul(a, b) + beta * c

    return _apply(f, [A, B, C], "linalg_gemm")


def batch_take(a, indices, **kw):
    """out[i] = a[i, indices[i]] (REF:src/operator/tensor/indexing_op.cc)."""
    return _apply(
        lambda x, idx: jnp.take_along_axis(
            x, idx.astype(jnp.int32)[:, None], axis=1)[:, 0],
        [a, indices], "batch_take")


def diag(data, k=0, axis1=0, axis2=1, **kw):
    """1-D in: build a k-diagonal matrix; N-D in: extract the k-diagonal
    over (axis1, axis2) — reference defaults (0, 1), NOT numpy's last-two
    (REF:src/operator/tensor/diag_op.cc)."""

    def f(x):
        if x.ndim == 1:
            return jnp.diag(x, k)
        return jnp.diagonal(x, offset=k, axis1=axis1, axis2=axis2)

    return _apply(f, [data], "diag")


def smooth_l1(data, scalar=1.0, **kw):
    """Huber-style loss elementwise (REF:src/operator/tensor/
    elemwise_unary_op_basic.cc smooth_l1): 0.5(σx)²/σ² if |x|<1/σ² else
    |x|-0.5/σ²."""
    s2 = float(scalar) ** 2

    def f(x):
        ax = jnp.abs(x)
        return jnp.where(ax < 1.0 / s2, 0.5 * s2 * x * x, ax - 0.5 / s2)

    return _apply(f, [data], "smooth_l1")


def make_loss(data, **kw):
    """Mark a symbol/array as a loss output (REF:src/operator/
    make_loss.cc) — identity forward; gradient of ones flows from it."""
    return _apply(lambda x: x, [data], "make_loss")


def unravel_index(data, shape=None, **kw):
    """Flat indices -> coordinate rows (REF:src/operator/tensor/
    ravel.cc): out is (ndim, N) like the reference."""
    dims = tuple(int(s) for s in shape)

    def f(x):
        return jnp.stack(jnp.unravel_index(x.astype(jnp.int32), dims))

    return _apply(f, [data], "unravel_index")


def ravel_multi_index(data, shape=None, **kw):
    """Coordinate rows (ndim, N) -> flat indices (REF:src/operator/tensor/
    ravel.cc)."""
    dims = tuple(int(s) for s in shape)

    def f(x):
        coords = tuple(x[i].astype(jnp.int32) for i in range(len(dims)))
        return jnp.ravel_multi_index(coords, dims, mode="clip")

    return _apply(f, [data], "ravel_multi_index")


def hard_sigmoid(data, alpha=0.2, beta=0.5, **kw):
    """clip(alpha·x + beta, 0, 1) (REF:src/operator/tensor/
    elemwise_unary_op_basic.cc)."""
    return _apply(lambda x: jnp.clip(alpha * x + beta, 0.0, 1.0), [data],
                  "hard_sigmoid")


def softrelu(data, **kw):
    """log(1+exp(x)) — softplus (Activation('softrelu') as a free op)."""
    return _apply(lambda x: jax.nn.softplus(x), [data], "softrelu")


def Crop(data, *like, offset=(0, 0), h_w=(0, 0), center_crop=False, **kw):
    """Spatial crop (REF:src/operator/crop.cc, NCHW): to `h_w`, or to the
    second input's spatial size; offset or center anchoring."""

    if not like and (int(h_w[0]) <= 0 or int(h_w[1]) <= 0):
        raise ValueError("Crop: pass a crop_like second input or a "
                         "positive h_w target size")

    def f(x, *rest):
        th, tw = (rest[0].shape[2:4] if rest else
                  (int(h_w[0]), int(h_w[1])))
        H, W = x.shape[2], x.shape[3]
        if center_crop:
            oy, ox = (H - th) // 2, (W - tw) // 2
        else:
            oy, ox = int(offset[0]), int(offset[1])
        return x[:, :, oy:oy + th, ox:ox + tw]

    return _apply(f, [data] + list(like), "Crop")


Reshape = reshape
astype = cast


# ----------------------------------------------------------------------------
# round-3 long tail (REF:src/operator/{tensor,nn,contrib}/** families)
# ----------------------------------------------------------------------------
log_sigmoid = _unary(jax.nn.log_sigmoid, "log_sigmoid")
mish = _unary(lambda x: x * jnp.tanh(jax.nn.softplus(x)), "mish")
hard_swish = _unary(lambda x: x * jnp.clip(x + 3.0, 0.0, 6.0) / 6.0,
                    "hard_swish")
digamma = _unary(jax.scipy.special.digamma, "digamma")
# erfcinv via ndtri, NOT erfinv(1-x): the subtraction cancels
# catastrophically in f32 for small x (erfcinv(1e-8) would return inf)
erfcinv = _unary(
    lambda x: -jax.scipy.special.ndtri(x.astype(jnp.float32) / 2.0)
    / jnp.sqrt(2.0).astype(jnp.float32), "erfcinv")


def polygamma(n, data, **kw):
    """REF:src/operator/tensor/elemwise_unary_op: polygamma(n, x)."""
    return _apply(lambda x: jax.scipy.special.polygamma(int(n), x), [data],
                  "polygamma")


def gammainc(a, x, **kw):
    """Regularized lower incomplete gamma (REF unary family)."""
    return _apply(jax.scipy.special.gammainc, [a, x], "gammainc")


def nextafter(lhs, rhs, **kw):
    return _apply(jnp.nextafter, [lhs, rhs], "nextafter", nondiff=True)


def moments(data, axes=None, keepdims=False, **kw):
    """(mean, variance) in one pass (REF:src/operator/nn/moments.cc)."""
    def f(x):
        ax = tuple(axes) if axes is not None else tuple(range(x.ndim))
        mu = jnp.mean(x, axis=ax, keepdims=keepdims)
        mu_b = mu if keepdims else jnp.expand_dims(
            mu, ax) if ax else mu
        var = jnp.mean(jnp.square(x - mu_b), axis=ax, keepdims=keepdims)
        return mu, var

    return _apply(f, [data], "moments")


def khatri_rao(*matrices, **kw):
    """Column-wise Kronecker product (REF:src/operator/contrib/krprod.cc):
    inputs (r, c_i) … -> (r? no: prod over rows) — reference semantics:
    for matrices with the SAME number of columns k, output has
    prod(rows_i) rows and k columns."""
    def f(*ms):
        out = ms[0]
        for m in ms[1:]:
            out = jnp.einsum("ik,jk->ijk", out, m).reshape(
                -1, out.shape[-1])
        return out

    return _apply(f, list(matrices), "khatri_rao")


def multi_all_finite(*arrays, num_arrays=None, init_output=True, **kw):
    """1 iff every element of every input is finite
    (REF:src/operator/contrib/all_finite.cc — the AMP overflow probe)."""
    def f(*xs):
        ok = jnp.ones((1,), jnp.float32)
        for x in xs:
            ok = ok * jnp.isfinite(x.astype(jnp.float32)).all().astype(
                jnp.float32)
        return ok

    return _apply(f, list(arrays), "multi_all_finite", nondiff=True)


all_finite = multi_all_finite


def masked_softmax(data, mask, axis=-1, temperature=1.0, **kw):
    """softmax over positions where mask!=0; fully-masked rows -> 0
    (REF:src/operator/nn/softmax.cc masked_softmax [ver>=1.8-era])."""
    def f(x, m):
        neg = jnp.finfo(jnp.float32).min
        z = jnp.where(m != 0, x.astype(jnp.float32) / temperature, neg)
        p = jax.nn.softmax(z, axis=axis)
        return jnp.where(m != 0, p, 0.0).astype(x.dtype)

    return _apply(f, [data, mask], "masked_softmax")


def masked_log_softmax(data, mask, axis=-1, temperature=1.0, **kw):
    def f(x, m):
        neg = jnp.finfo(jnp.float32).min
        z = jnp.where(m != 0, x.astype(jnp.float32) / temperature, neg)
        p = jax.nn.log_softmax(z, axis=axis)
        return jnp.where(m != 0, p, -jnp.inf).astype(x.dtype)

    return _apply(f, [data, mask], "masked_log_softmax")


def _im2col_params(kernel, stride, dilate, pad):
    kh, kw_ = (kernel, kernel) if isinstance(kernel, int) else tuple(kernel)
    sh, sw = _pair(stride, 2) if stride else (1, 1)
    dh, dw = _pair(dilate, 2) if dilate else (1, 1)
    ph, pw = _pair(pad, 2) if pad else (0, 0)
    return kh, kw_, sh, sw, dh, dw, ph, pw


def _patches(x, kh, kw_, sh, sw, dh, dw, ph, pw):
    """The ONE patch-extraction both im2col and col2im's vjp use —
    col2im is exact only while they share this code."""
    p = lax.conv_general_dilated_patches(
        x, (kh, kw_), (sh, sw), [(ph, ph), (pw, pw)],
        rhs_dilation=(dh, dw),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return p.reshape(x.shape[0], x.shape[1] * kh * kw_, -1)


def im2col(data, kernel, stride=None, dilate=None, pad=None, **kw):
    """Sliding-window patches as columns (REF:src/operator/nn/im2col.h):
    (N, C, H, W) -> (N, C*kh*kw, L) with L output positions."""
    prm = _im2col_params(kernel, stride, dilate, pad)
    return _apply(lambda x: _patches(x, *prm), [data], "im2col")


def col2im(data, output_size, kernel, stride=None, dilate=None, pad=None,
           **kw):
    """Inverse of im2col: scatter-add columns back to the image
    (REF:src/operator/nn/im2col.h col2im) — implemented as the exact vjp
    of the im2col patch extraction, which IS the scatter-add."""
    prm = _im2col_params(kernel, stride, dilate, pad)
    kh, kw_ = prm[0], prm[1]
    oh, ow = tuple(output_size)

    def f(cols):
        n = cols.shape[0]
        c = cols.shape[1] // (kh * kw_)
        zeros = jnp.zeros((n, c, oh, ow), cols.dtype)
        _, vjp = jax.vjp(lambda img: _patches(img, *prm), zeros)
        return vjp(cols)[0]

    return _apply(f, [data], "col2im")


def fill_element_0index(lhs, mhs, rhs, **kw):
    """lhs[i, rhs[i]] = mhs[i] (REF:src/operator/tensor/
    fill_element_0index — the bucketing trick for masking outputs)."""
    def f(l, m, r):
        idx = r.astype(jnp.int32)
        return l.at[jnp.arange(l.shape[0]), idx].set(m)

    return _apply(f, [lhs, mhs, rhs], "fill_element_0index")


def choose_element_0index(lhs, rhs, **kw):
    """out[i] = lhs[i, rhs[i]] (REF tensor family; pick's ancestor)."""
    def f(l, r):
        return l[jnp.arange(l.shape[0]), r.astype(jnp.int32)]

    return _apply(f, [lhs, rhs], "choose_element_0index")


def LRN(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5, **kw):
    """Local response normalization across channels
    (REF:src/operator/nn/lrn.cc — AlexNet-era)."""
    def f(x):
        sq = jnp.square(x.astype(jnp.float32))
        half = nsize // 2
        # windowed channel sum via padding + cumulative slicing
        padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
        # NB: module-level `sum` is the reduction OP; use the builtin
        acc = _sum(padded[:, i:i + x.shape[1]] for i in range(nsize))
        norm = (knorm + alpha * acc / nsize) ** beta
        return (x.astype(jnp.float32) / norm).astype(x.dtype)

    return _apply(f, [data], "LRN")


broadcast_axes = broadcast_axis


# ----------------------------------------------------------------------------
# deprecated 0.x-era aliases (REF:src/operator/{batch_norm_v1,convolution_v1,
# pooling_v1}.cc — upstream kept them registered for old symbol JSON; here
# they forward to the current ops with a DeprecationWarning)
# ----------------------------------------------------------------------------
def _deprecated_v1(new_fn, old_name, ref_file):
    import warnings as _warnings

    @functools.wraps(new_fn)  # real signature: the symbol autogen stubs
    def op(*args, **kw):      # classify by inspect.signature, and a bare
        # (*args, **kw) would take the variadic path and skip the
        # auto-created weight/bias/gamma Variables
        _warnings.warn(
            f"{old_name} is the deprecated 0.x alias of "
            f"{new_fn.__name__}; it forwards with identical semantics",
            DeprecationWarning, stacklevel=2)
        return new_fn(*args, **kw)

    op.__name__ = old_name
    op.__qualname__ = old_name
    op.__doc__ = (f"Deprecated alias of :func:`{new_fn.__name__}` "
                  f"(REF:src/operator/{ref_file} kept old symbol JSON "
                  "loadable).")
    return op


BatchNorm_v1 = _deprecated_v1(BatchNorm, "BatchNorm_v1",
                              "batch_norm_v1.cc")
# upstream: NNVM_REGISTER_OP(SoftmaxOutput).add_alias("Softmax") — the 0.x
# name is the SAME OP (softmax fwd + injected CE grad), not nd.softmax
Softmax = _deprecated_v1(SoftmaxOutput, "Softmax", "softmax_output.cc")
Convolution_v1 = _deprecated_v1(Convolution, "Convolution_v1",
                                "convolution_v1.cc")
Pooling_v1 = _deprecated_v1(Pooling, "Pooling_v1", "pooling_v1.cc")


def IdentityAttachKLSparseReg(data, sparseness_target=0.1, penalty=0.001,
                              momentum=0.9, moving_avg=None, **kw):
    """Identity forward + KL sparsity-regularization gradient
    (REF:src/operator/identity_attach_KL_sparse_reg.cc — the sparse-
    autoencoder penalty).  The forward passes `data` through unchanged;
    the backward ADDS penalty·KL'(ρ‖ρ̂) per hidden unit, where ρ is
    `sparseness_target` and ρ̂ the (moving-average) mean activation of
    that unit over the batch: d/da = penalty·(−ρ/ρ̂ + (1−ρ)/(1−ρ̂)).

    `moving_avg` (units,) carries ρ̂ across calls with `momentum` and is
    REBOUND in place (the op's aux state upstream — the FMutateInputs
    idiom used by the raw optimizer kernels here); omit it to use the
    current batch mean alone.  Activations are expected in (0, 1)
    (post-sigmoid), as upstream assumes; ρ̂ is clamped away from {0, 1}."""
    rho = float(sparseness_target)
    pen = float(penalty)
    mom = float(momentum)
    use_ma = moving_avg is not None
    if use_ma and _functional.active():
        from ..base import MXNetError
        raise MXNetError(
            "IdentityAttachKLSparseReg: the moving_avg aux cannot be "
            "updated inside a hybridize/compiled trace (the rebind would "
            "silently freeze at the trace-time value); use the batch-mean "
            "mode (moving_avg=None) under hybridize, or train this block "
            "eagerly")
    from .. import autograd as _ag
    # aux semantics match upstream: ρ̂ updates only on TRAINING forwards
    # (inference passes must not corrupt the training statistics), and
    # the blend is computed exactly once
    rho_hat_const = None
    if use_ma:
        x_now = _raw(data)
        batch_mean = x_now.reshape(x_now.shape[0], -1).mean(axis=0)
        ma_val = _raw(moving_avg)
        new_ma = mom * ma_val.reshape(-1) + (1 - mom) * batch_mean
        rho_hat_const = jnp.clip(new_ma, 1e-6, 1.0 - 1e-6)
        if _ag.is_recording():
            moving_avg._rebind(
                new_ma.reshape(ma_val.shape).astype(moving_avg.dtype))

    @jax.custom_vjp
    def head(x):
        return x

    def head_fwd(x):
        if rho_hat_const is not None:
            rho_hat = rho_hat_const
        else:
            rho_hat = jnp.clip(x.reshape(x.shape[0], -1).mean(axis=0),
                               1e-6, 1.0 - 1e-6)
        return x, (x.shape, rho_hat)

    def head_bwd(res, g):
        shape, rho_hat = res
        kl_grad = pen * (-rho / rho_hat + (1 - rho) / (1 - rho_hat))
        return (g + kl_grad.reshape((1,) + shape[1:]),)

    head.defvjp(head_fwd, head_bwd)
    return _apply(head, [data], "IdentityAttachKLSparseReg")
