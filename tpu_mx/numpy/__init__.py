"""`mx.np` — the NumPy-compatible array namespace (MXNet ≥1.6,
REF:python/mxnet/numpy/ — ~50k LoC of C++-backed wrappers upstream).

TPU-native design: every function wraps the matching `jax.numpy` routine
through `ops._apply`, so results are framework NDArrays that participate
in autograd recording and in functional (hybridize/CompiledTrainStep)
traces exactly like the classic `nd` ops — one dispatch layer, not a
parallel engine.  Upstream keeps a separate np ndarray type; here the
unified NDArray already has numpy semantics (a documented divergence).

Default dtype is float32 (the upstream mx.np contract, and the only
sensible default on TPU).
"""
from __future__ import annotations

import builtins as _builtins

import numpy as _onp
import jax.numpy as _jnp

from ..ndarray import NDArray
from ..ndarray import ops as _ops

newaxis = None
pi = _onp.pi
e = _onp.e
inf = _onp.inf
nan = _onp.nan
euler_gamma = _onp.euler_gamma
float32, float64, float16 = "float32", "float64", "float16"
int32, int64, int8, uint8 = "int32", "int64", "int8", "uint8"
bool_ = "bool"
ndarray = NDArray


def _to_f32(dtype, obj):
    if dtype is not None:
        return dtype
    a = _onp.asarray(obj)
    if a.dtype == _onp.float64:
        return _onp.float32  # mx.np default-dtype contract
    return None


def array(object, dtype=None, ctx=None):
    a = _onp.asarray(object)
    return NDArray(_jnp.asarray(a, _to_f32(dtype, a)))


def zeros(shape, dtype=None, ctx=None, **kw):
    return NDArray(_jnp.zeros(shape, dtype or "float32"))


def ones(shape, dtype=None, ctx=None, **kw):
    return NDArray(_jnp.ones(shape, dtype or "float32"))


def full(shape, fill_value, dtype=None, ctx=None, **kw):
    return NDArray(_jnp.full(shape, fill_value,
                             dtype or _to_f32(None, fill_value) or None))


def arange(start, stop=None, step=1, dtype=None, ctx=None):
    out = _jnp.arange(start, stop, step, dtype)
    if dtype is None and out.dtype == _jnp.float64:
        out = out.astype(_jnp.float32)
    return NDArray(out)


def linspace(start, stop, num=50, endpoint=True, dtype=None, ctx=None,
             **kw):
    return NDArray(_jnp.linspace(start, stop, num, endpoint=endpoint,
                                 dtype=dtype or "float32"))


def eye(N, M=None, k=0, dtype=None, ctx=None, **kw):
    return NDArray(_jnp.eye(N, M, k, dtype or "float32"))


def identity(n, dtype=None, ctx=None):
    return NDArray(_jnp.identity(n, dtype or "float32"))


def _wrap(jnp_name, public=None):
    jfn = getattr(_jnp, jnp_name)

    def op(*args, **kwargs):
        # sequence-taking routines (concatenate, stack, …) receive a list
        # of arrays as ONE argument; flatten it through the dispatch layer
        # so every element participates in autograd, rebuild inside
        # NB: module globals shadow builtins like any/all/sum with wrapped
        # np ops — reach for the real builtins in here
        # NDArray kwargs (e.g. average(..., weights=w)) are unwrapped to
        # raw values: they compute correctly but are CONSTANTS to autograd
        # — pass arrays positionally when their gradient matters
        kwargs = {k: (v._data if isinstance(v, NDArray) else v)
                  for k, v in kwargs.items()}
        flat, spec = [], []
        for a in args:
            if isinstance(a, (list, tuple)) and _builtins.any(
                    isinstance(x, NDArray) for x in a):
                spec.append(len(a))
                flat.extend(a)
            else:
                spec.append(None)
                flat.append(a)

        def call(*raw):
            it = iter(raw)
            rebuilt = [[next(it) for _ in range(n)] if n is not None
                       else next(it) for n in spec]
            return jfn(*rebuilt, **kwargs)

        return _ops._apply(call, flat, public or jnp_name)

    op.__name__ = public or jnp_name
    op.__doc__ = (f"mx.np.{public or jnp_name} — jax.numpy.{jnp_name} "
                  "through the autograd-aware dispatch layer "
                  "(REF:python/mxnet/numpy)")
    return op


# one generated wrapper per jnp routine; names follow numpy.  Keep sorted.
_WRAPPED = [
    "abs", "absolute", "add", "all", "allclose", "amax", "amin", "any",
    "append",
    "arccos", "arccosh", "arcsin", "arcsinh", "arctan", "arctan2",
    "arctanh", "argmax", "argmin", "argsort", "around", "array_split",
    "atleast_1d",
    "atleast_2d", "atleast_3d", "average", "bincount", "bitwise_and",
    "bitwise_or", "bitwise_xor", "broadcast_arrays", "broadcast_to",
    "cbrt", "ceil", "clip", "column_stack", "concatenate", "copysign",
    "cos", "cosh", "cross", "cumprod", "cumsum", "deg2rad", "degrees",
    "delete", "diag", "diagflat", "diagonal", "diff", "divide", "divmod",
    "dot", "dsplit", "dstack",
    "ediff1d", "einsum", "equal", "exp", "exp2", "expand_dims", "expm1",
    "flatnonzero", "flip", "fliplr", "flipud", "floor", "floor_divide",
    "fmax", "fmin", "fmod", "gcd", "greater", "greater_equal",
    "histogram", "hsplit",
    "hstack", "hypot", "inner", "insert", "interp", "invert", "isclose",
    "isfinite", "isinf",
    "isnan", "isneginf", "isposinf", "kron", "lcm", "ldexp", "less",
    "less_equal", "log", "log10", "log1p", "log2", "logaddexp",
    "logaddexp2",
    "logical_and", "logical_not", "logical_or", "logical_xor", "matmul",
    "max", "maximum", "mean", "median", "meshgrid", "min", "minimum",
    "mod", "moveaxis", "multiply", "nan_to_num", "nanmax", "nanmean",
    "nanmin", "nansum", "negative", "nonzero",
    "not_equal", "outer", "pad", "percentile", "polyval", "power", "prod",
    "ptp", "quantile", "rad2deg", "radians", "ravel", "reciprocal",
    "remainder",
    "repeat", "reshape", "resize", "roll", "rot90", "searchsorted",
    "sign", "sin",
    "sinh", "sort", "split", "sqrt", "square", "squeeze", "stack", "std",
    "subtract", "sum", "swapaxes", "take", "take_along_axis", "tan",
    "tanh", "tensordot",
    "tile", "trace", "transpose", "tril", "triu", "true_divide", "trunc",
    "unique", "unravel_index", "vander", "var", "vdot", "vsplit",
    "vstack", "where",
]
for _name in _WRAPPED:
    globals()[_name] = _wrap(_name)
round = globals()["around"]
concat = globals()["concatenate"]
fix = globals()["trunc"]  # numpy fix == round toward zero (jnp.fix removed)


def frexp(x):
    """Mantissa/exponent decomposition with a DIFFERENTIABLE mantissa.

    The exponent is piecewise constant in x, so the true derivative is
    ``d(m)/dx = 2**-e``; it is attached STRAIGHT-THROUGH: the returned
    VALUES are exactly jnp.frexp's bits (the gradient path contributes an
    exact zero, clamped so inf/nan inputs cannot leak a nan through
    ``inf - inf``), while the gradient flows via ``x * 2**-e`` computed
    as two half-power scalings so neither factor overflows across the
    full exponent range.  jnp.frexp's own mantissa derivative is cut
    (jax 0.9 gives it one, with a derivative of 1 at inf; earlier
    versions gave none), so the straight-through term is the only
    gradient.  Subnormal
    inputs follow the backend's flush-to-zero arithmetic — divergence
    #26 in docs/DIVERGENCES.md."""
    import jax as _jax

    def call(v):
        if not _jnp.issubdtype(v.dtype, _jnp.floating):
            v = v.astype(_jnp.result_type(float))
        m_exact, e = _jnp.frexp(v)
        e_sg = _jax.lax.stop_gradient(e)
        h = (-e_sg) // 2
        scaled = (v * _jnp.exp2(h.astype(v.dtype))) \
            * _jnp.exp2((-e_sg - h).astype(v.dtype))
        # zero (not nan) straight-through delta for inf/nan inputs: the
        # value must stay m_exact's bits there, with no gradient
        scaled = _jnp.where(_jnp.isfinite(scaled), scaled, 0)
        m = _jax.lax.stop_gradient(m_exact) \
            + (scaled - _jax.lax.stop_gradient(scaled))
        return m, e
    return _ops._apply(call, [x], "frexp")


def zeros_like(a, dtype=None, **kw):
    return _ops._apply(lambda x: _jnp.zeros_like(x, dtype), [a],
                       "zeros_like")


def ones_like(a, dtype=None, **kw):
    return _ops._apply(lambda x: _jnp.ones_like(x, dtype), [a],
                       "ones_like")


def full_like(a, fill_value, dtype=None, **kw):
    return _ops._apply(lambda x: _jnp.full_like(x, fill_value, dtype), [a],
                       "full_like")


def empty(shape, dtype=None, ctx=None, **kw):
    # functional arrays are never uninitialized; zeros is the honest analog
    return zeros(shape, dtype, ctx)


def empty_like(a, dtype=None, **kw):
    return zeros_like(a, dtype)


def logspace(start, stop, num=50, endpoint=True, base=10.0, dtype=None,
             ctx=None, **kw):
    return NDArray(_jnp.logspace(start, stop, num, endpoint=endpoint,
                                 base=base, dtype=dtype or "float32"))


def indices(dimensions, dtype="int32", ctx=None):
    # numpy contract: ONE stacked array of shape (ndim, *dimensions)
    return NDArray(_jnp.indices(tuple(dimensions), dtype=dtype))


def diag_indices(n, ndim=2):
    return tuple(NDArray(a) for a in _jnp.diag_indices(n, ndim))


def may_share_memory(a, b):
    return False  # functional arrays never alias


def shape(a):
    return tuple(a.shape)


def ndim(a):
    return len(a.shape)


def size(a):
    return int(_onp.prod(a.shape)) if a.shape else 1


from . import fft         # noqa: E402
from . import linalg      # noqa: E402
from . import random      # noqa: E402

__all__ = (["array", "zeros", "ones", "full", "arange", "linspace", "eye",
            "identity", "zeros_like", "ones_like", "full_like", "ndarray", "fix",
            "newaxis", "pi", "e", "inf", "nan", "fft", "linalg", "random",
            "shape", "ndim", "size", "round", "concat", "empty", "frexp",
            "empty_like", "logspace", "indices", "diag_indices"] + _WRAPPED)
