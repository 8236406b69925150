"""NDArray: the imperative tensor handle over an immutable `jax.Array`.

TPU-native analog of the reference NDArray (REF:include/mxnet/ndarray.h,
REF:src/ndarray/ndarray.cc).  Design (SURVEY §7.1): the reference pairs a
mutable buffer with an async-engine variable; here the buffer is an immutable
`jax.Array` whose dispatch is already async, so the handle provides
*mutation semantics* (``x[:]=v``, ``+=``, slice-assign) by functional rebind
(`.at[].set()`) plus a version counter, and ``wait_to_read`` maps to
``block_until_ready``.  The engine's read/write ordering is inherited from
XLA program order — no thread pool to manage.
"""
from __future__ import annotations

import functools
import struct

import jax
import jax.numpy as jnp
import numpy as np

from .. import autograd
from .. import fusion as _fusion
from ..context import Context, current_context, default_context

__all__ = ["NDArray", "array", "save", "load", "waitall", "concatenate", "from_numpy"]

_FLOAT_DTYPES = (jnp.float16, jnp.bfloat16, jnp.float32, jnp.float64)


def _is_float(dtype):
    return jnp.issubdtype(jnp.dtype(dtype), jnp.floating)


def _ctx_of(data):
    try:
        dev = list(data.devices())[0]
        kind = "cpu" if dev.platform == "cpu" else "tpu"
        # Context ids are indices into this process's local device list, not
        # raw jax device ids (under jax.distributed a worker's only local CPU
        # device can carry a global id like 2048).
        locals_ = [d for d in jax.local_devices() if d.platform == dev.platform]
        try:
            return Context(kind, locals_.index(dev))
        except ValueError:
            return Context(kind, 0)  # non-addressable/global array
    except Exception:
        return default_context()


def _to_ctx_device(data, ctx):
    """Place `data` on ctx's device if it isn't already there."""
    if ctx is None:
        return data
    dev = ctx.jax_device()
    try:
        cur = list(data.devices())
        if len(cur) == 1 and cur[0] == dev:
            return data
    except Exception:
        pass
    return jax.device_put(data, dev)


class NDArray:
    """Mutable tensor handle; wraps an immutable jax.Array + autograd hooks.

    The buffer lives behind the ``_data`` property: ``_buf`` is the
    concrete jax.Array, or None while ``_lazy`` points at a pending
    fusion-segment node (engine bulking, see tpu_mx/fusion.py).  Every
    read path goes through the property, so ANY buffer access is a flush
    barrier that realizes the lazy thunk; shape/dtype queries answer from
    the segment's abstract eval without forcing execution."""

    __slots__ = ("_buf", "_lazy", "_grad", "_grad_req", "_tape_node",
                 "_version", "__weakref__")

    def __init__(self, data, ctx=None):
        if isinstance(data, NDArray):
            data = data._data
        elif not isinstance(data, jax.Array):
            data = jnp.asarray(data)
        self._buf = _to_ctx_device(data, ctx)
        self._lazy = None
        self._grad = None
        self._grad_req = "write"
        self._tape_node = None
        self._version = 0

    @property
    def _data(self):
        if self._lazy is not None:
            _fusion.realize(self)
        return self._buf

    @_data.setter
    def _data(self, value):
        self._buf = value
        self._lazy = None

    # ------------------------------------------------------------------ meta
    @property
    def shape(self):
        if self._lazy is not None:
            return tuple(_fusion.aval_of(self._lazy).shape)
        return tuple(self._buf.shape)

    @property
    def dtype(self):
        if self._lazy is not None:
            return _fusion.aval_of(self._lazy).dtype
        return self._buf.dtype

    @property
    def size(self):
        shape = self.shape
        return int(np.prod(shape)) if shape else 1

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def context(self):
        return _ctx_of(self._data)

    ctx = context

    @property
    def stype(self):
        return "default"

    def tostype(self, stype):
        """Convert to another storage type (csr / row_sparse / default)."""
        if stype == "default":
            return self
        from . import sparse as _sparse
        return _sparse.cast_storage(self, stype)

    @property
    def grad(self):
        return self._grad

    @property
    def handle(self):
        return self._data  # "handle" = the underlying buffer in this stack

    def attach_grad(self, grad_req="write", stype=None):
        """Allocate a gradient buffer so backward() deposits into ``.grad``.

        Divergence (SURVEY §7.3.4): ``stype='row_sparse'`` gradients are
        DENSE here — XLA:TPU has no sparse gradient storage; the request is
        honored numerically (same values, dense layout) and warned about.
        """
        if stype not in (None, "default"):
            import warnings
            warnings.warn(
                f"attach_grad(stype={stype!r}): TPU gradients are always "
                "dense; storing dense values (documented divergence, "
                "SURVEY §7.3.4)", stacklevel=2)
        self._grad = NDArray(jnp.zeros(self.shape, self.dtype))
        self._grad_req = grad_req

    def drop_grad(self):
        self._grad = None
        self._grad_req = "null"

    # -------------------------------------------------------------- transfer
    def asnumpy(self):
        return np.asarray(jax.device_get(self._data))

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def copy(self):
        return NDArray(self._data)

    def copyto(self, other):
        if isinstance(other, NDArray):
            if other.shape != self.shape:
                raise ValueError(f"copyto shape mismatch {self.shape} vs {other.shape}")
            other._rebind(_to_ctx_device(self._data.astype(other.dtype), other.context))
            return other
        if isinstance(other, Context):
            return NDArray(self._data, ctx=other)
        raise TypeError(f"copyto: unsupported target {type(other)}")

    def as_in_context(self, ctx):
        if ctx == self.context:
            return self
        return NDArray(self._data, ctx=ctx)

    as_in_ctx = as_in_context

    def astype(self, dtype, copy=True):
        from . import ops
        return ops.cast(self, dtype=dtype)

    def as_nd_ndarray(self):
        return self

    def tolist(self):
        return self.asnumpy().tolist()

    # --------------------------------------------------------- sync / engine
    def wait_to_read(self):
        """Engine WaitForVar analog: block until this buffer is computed."""
        self._data.block_until_ready()
        return self

    wait_to_write = wait_to_read

    def detach(self):
        out = NDArray(self._data)
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        """Run autograd back-prop from this array (reference: NDArray.backward)."""
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    # ----------------------------------------------------------- mutation
    def _rebind(self, new_data):
        """In-place semantics: swap the underlying buffer, bump the version.
        (The reference bumps the engine var version on each write.)"""
        self._data = new_data
        self._version += 1
        self._tape_node = None

    def __setitem__(self, key, value):
        v = value._data if isinstance(value, NDArray) else value
        if isinstance(key, slice) and key == slice(None):
            if hasattr(v, "shape") and tuple(getattr(v, "shape", ())) == self.shape:
                self._rebind(jnp.asarray(v).astype(self.dtype))
            else:
                self._rebind(jnp.broadcast_to(jnp.asarray(v, self.dtype), self.shape))
            return
        key = _canonical_index(key)
        self._rebind(self._data.at[key].set(jnp.asarray(v, dtype=self.dtype)))

    def __getitem__(self, key):
        from . import ops
        if isinstance(key, NDArray):
            key = key._data
        return ops._index(self, _canonical_index(key))

    # ----------------------------------------------------------- arithmetic
    def _binop(self, other, name):
        from . import ops
        return getattr(ops, name)(self, other)

    def __add__(self, o): return self._binop(o, "add")
    def __radd__(self, o): return self._binop(o, "add")
    def __sub__(self, o): return self._binop(o, "subtract")
    def __rsub__(self, o):
        from . import ops
        return ops.subtract(o, self)
    def __mul__(self, o): return self._binop(o, "multiply")
    def __rmul__(self, o): return self._binop(o, "multiply")
    def __truediv__(self, o): return self._binop(o, "divide")
    def __rtruediv__(self, o):
        from . import ops
        return ops.divide(o, self)
    def __mod__(self, o): return self._binop(o, "mod")
    def __pow__(self, o): return self._binop(o, "power")
    def __neg__(self):
        from . import ops
        return ops.negative(self)
    def __abs__(self):
        from . import ops
        return ops.abs(self)

    def __iadd__(self, o):
        from . import ops
        res = ops.add(self, o)
        self._rebind(res._data)
        self._tape_node = res._tape_node
        return self

    def __isub__(self, o):
        from . import ops
        res = ops.subtract(self, o)
        self._rebind(res._data)
        self._tape_node = res._tape_node
        return self

    def __imul__(self, o):
        from . import ops
        res = ops.multiply(self, o)
        self._rebind(res._data)
        self._tape_node = res._tape_node
        return self

    def __itruediv__(self, o):
        from . import ops
        res = ops.divide(self, o)
        self._rebind(res._data)
        self._tape_node = res._tape_node
        return self

    # comparisons return 0/1 arrays like the reference
    def __eq__(self, o): return self._binop(o, "equal")
    def __ne__(self, o): return self._binop(o, "not_equal")
    def __gt__(self, o): return self._binop(o, "greater")
    def __ge__(self, o): return self._binop(o, "greater_equal")
    def __lt__(self, o): return self._binop(o, "lesser")
    def __le__(self, o): return self._binop(o, "lesser_equal")
    __hash__ = object.__hash__

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of a 0-d NDArray")
        return self.shape[0]

    def __bool__(self):
        if self.size != 1:
            raise ValueError("ambiguous truth value of multi-element NDArray")
        return bool(self.asscalar())

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __repr__(self):
        return f"\n{self.asnumpy()}\n<NDArray {self.shape} @{self.context}>"

    # ------------------------------------------------------- method mirrors
    def reshape(self, *shape, **kwargs):
        from . import ops
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        return ops.reshape(self, shape=shape)

    def reshape_like(self, other):
        from . import ops
        return ops.reshape(self, shape=other.shape)

    def transpose(self, axes=None):
        from . import ops
        return ops.transpose(self, axes=axes)

    @property
    def T(self):
        return self.transpose()

    def flatten(self):
        from . import ops
        return ops.flatten(self)

    def expand_dims(self, axis):
        from . import ops
        return ops.expand_dims(self, axis=axis)

    def squeeze(self, axis=None):
        from . import ops
        return ops.squeeze(self, axis=axis)

    def broadcast_to(self, shape):
        from . import ops
        return ops.broadcast_to(self, shape=shape)

    def broadcast_like(self, other):
        from . import ops
        return ops.broadcast_to(self, shape=other.shape)

    def slice_axis(self, axis, begin, end):
        from . import ops
        return ops.slice_axis(self, axis=axis, begin=begin, end=end)

    def clip(self, a_min, a_max):
        from . import ops
        return ops.clip(self, a_min=a_min, a_max=a_max)

    def abs(self):
        from . import ops
        return ops.abs(self)

    def sqrt(self):
        from . import ops
        return ops.sqrt(self)

    def square(self):
        from . import ops
        return ops.square(self)

    def exp(self):
        from . import ops
        return ops.exp(self)

    def log(self):
        from . import ops
        return ops.log(self)

    def sum(self, axis=None, keepdims=False):
        from . import ops
        return ops.sum(self, axis=axis, keepdims=keepdims)

    def mean(self, axis=None, keepdims=False):
        from . import ops
        return ops.mean(self, axis=axis, keepdims=keepdims)

    def max(self, axis=None, keepdims=False):
        from . import ops
        return ops.max(self, axis=axis, keepdims=keepdims)

    def min(self, axis=None, keepdims=False):
        from . import ops
        return ops.min(self, axis=axis, keepdims=keepdims)

    def prod(self, axis=None, keepdims=False):
        from . import ops
        return ops.prod(self, axis=axis, keepdims=keepdims)

    def argmax(self, axis=None):
        from . import ops
        return ops.argmax(self, axis=axis)

    def argmin(self, axis=None):
        from . import ops
        return ops.argmin(self, axis=axis)

    def norm(self, ord=2, axis=None, keepdims=False):
        from . import ops
        return ops.norm(self, ord=ord, axis=axis, keepdims=keepdims)


    def softmax(self, axis=-1):
        from . import ops
        return ops.softmax(self, axis=axis)

    def log_softmax(self, axis=-1):
        from . import ops
        return ops.log_softmax(self, axis=axis)




    def one_hot(self, depth, on_value=1.0, off_value=0.0):
        from . import ops
        return ops.one_hot(self, depth=depth, on_value=on_value, off_value=off_value)

    def take(self, indices, axis=0):
        from . import ops
        return ops.take(self, indices, axis=axis)

    def flip(self, axis):
        from . import ops
        return ops.flip(self, axis=axis)

    def repeat(self, repeats, axis=None):
        from . import ops
        return ops.repeat(self, repeats=repeats, axis=axis)

    def tile(self, reps):
        from . import ops
        return ops.tile(self, reps=reps)

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        from . import ops
        return ops.split(self, num_outputs=num_outputs, axis=axis, squeeze_axis=squeeze_axis)



    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    # DLPack interop (reference: NDArray::ToDLPack / FromDLPack)
    def to_dlpack_for_read(self):
        return self._data.__dlpack__()

    to_dlpack_for_write = to_dlpack_for_read


def _canonical_index(key):
    """Convert NDArray indices inside fancy-index tuples to raw arrays."""
    if isinstance(key, NDArray):
        return key._data
    if isinstance(key, tuple):
        return tuple(k._data if isinstance(k, NDArray) else k for k in key)
    return key


# ----------------------------------------------------------------------------
# free functions
# ----------------------------------------------------------------------------
def array(source_array, ctx=None, dtype=None):
    """mx.nd.array — create from any array-like (reference: ndarray.py:array)."""
    if isinstance(source_array, NDArray):
        data = source_array._data
    else:
        data = np.asarray(source_array)
    if dtype is None:
        dtype = data.dtype if data.dtype != np.float64 else np.float32
    return NDArray(jnp.asarray(data, dtype=dtype), ctx=ctx or current_context())


def from_numpy(a, zero_copy=False):
    return array(a)


def waitall():
    """Engine WaitForAll analog (REF:include/mxnet/engine.h WaitForAll).

    Blocks until every live jax.Array in the process is ready — a real sync
    of all previously dispatched device work, not just a fresh dummy
    computation (which would only bound the dispatch queue, not completion
    on every device).  A pending fused op segment flushes first: waitall
    is a full engine barrier."""
    _fusion.flush("waitall")
    for a in jax.live_arrays():
        try:
            a.block_until_ready()
        except RuntimeError as e:
            # deleted/donated buffers are expected flotsam; real async
            # computation failures must surface (WaitForAll semantics)
            if "deleted" in str(e).lower() or "donated" in str(e).lower():
                continue
            raise
    try:
        jax.effects_barrier()
    except Exception:
        pass


def concatenate(arrays, axis=0):
    from . import ops
    return ops.concat(*arrays, dim=axis)


# -- save/load: reference-compatible capability (REF:src/ndarray/ndarray.cc
#    Save/Load) realized with the .npz container --------------------------------
def save(fname, data):
    """Save list/dict of NDArray (mx.nd.save)."""
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, (list, tuple)):
        payload = {f"arr_{i}": a.asnumpy() for i, a in enumerate(data)}
        meta = "list"
    elif isinstance(data, dict):
        payload = {k: v.asnumpy() for k, v in data.items()}
        meta = "dict"
    else:
        raise TypeError("save: need NDArray, list or dict of NDArray")
    # serialize to memory first, then one atomic_write: (a) np.savez on a
    # bare path appends .npz, breaking `<prefix>-NNNN.params` parity; (b) a
    # single linear write keeps the durability layer's intended-bytes
    # digest exact (zipfile seeks would invalidate it); (c) a crash mid-save
    # can then never leave a truncated destination (docs/robustness.md)
    import io as _io
    from ..checkpoint import atomic_write
    bio = _io.BytesIO()
    np.savez(bio, __layout__=np.array(meta), **payload)
    with atomic_write(fname) as f:
        f.write(bio.getbuffer())


def load(fname):
    """Load what `save` wrote (mx.nd.load)."""
    import os
    if not os.path.exists(fname) and os.path.exists(str(fname) + ".npz"):
        fname = str(fname) + ".npz"   # files written by older revisions
    with np.load(fname, allow_pickle=False) as z:
        layout = str(z["__layout__"]) if "__layout__" in z else "dict"
        items = {k: NDArray(jnp.asarray(v)) for k, v in z.items() if k != "__layout__"}
    if layout == "list":
        return [items[f"arr_{i}"] for i in range(len(items))]
    return items


# ---------------------------------------------------------------------------
# remaining method-form op delegators (REF:python/mxnet/ndarray/ndarray.py
# exposes most ops as methods; the explicit ones above carry custom
# signatures, these are straight passthroughs)
# ---------------------------------------------------------------------------
def _delegate_method(name):
    def method(self, *args, **kwargs):
        from . import ops
        return getattr(ops, name)(self, *args, **kwargs)
    method.__name__ = name
    method.__doc__ = f"Method form of mx.nd.{name} (self as first input)."
    setattr(NDArray, name, method)


for _m in ("round", "floor", "ceil", "pick", "pad", "sort", "argsort",
           "topk", "slice", "slice_like", "swapaxes", "sign", "rint",
           "log2", "log10", "log1p", "expm1", "rsqrt", "cbrt",
           "reciprocal", "diag", "relu", "sigmoid", "tanh", "dot",
           "zeros_like", "ones_like"):
    _delegate_method(_m)
del _m
