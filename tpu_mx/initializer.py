"""Weight initializers (REF:python/mxnet/initializer.py).

String-registered like the reference (`init='xavier'`); produce numpy arrays
so Parameter can place them on any context. Name-based aux handling matches
the reference convention (running_mean→0, running_var→1, bias→0, gamma→1).
"""
from __future__ import annotations

import math
import os

import numpy as np

from .base import Registry
from .random import host_rng as _host_rng

__all__ = ["Initializer", "Uniform", "Normal", "Zero", "One", "Constant",
           "Xavier", "MSRAPrelu", "Orthogonal", "Bilinear", "LSTMBias", "registry"]

registry = Registry("initializer")


def _aux_value(name):
    """Name-convention constant for aux/affine params, or None for weights."""
    if name.endswith(("running_mean", "moving_mean")):
        return 0.0
    if name.endswith(("running_var", "moving_var")):
        return 1.0
    if name.endswith("gamma"):
        return 1.0
    if name.endswith(("beta", "bias")):
        return 0.0
    return None


class Initializer:
    """Base: dispatch on parameter-name convention, like the reference's
    InitDesc-driven `__call__`."""

    def __call__(self, name, shape, dtype="float32"):
        aux = _aux_value(name)
        if aux is not None:
            return np.full(shape, aux, dtype)
        return self._init_weight(name, shape).astype(dtype)

    def _init_weight(self, name, shape):
        raise NotImplementedError

    def device_sample(self, name, shape, dtype="float32"):
        """Sample this parameter ON DEVICE, or return None for the
        host-numpy path.

        No reference analog — the reference fills host buffers and copies
        (REF:python/mxnet/initializer.py), which means ~100 MB
        (ResNet-50) to ~440 MB (BERT-base) of host→device parameter
        traffic before the first step.  Standard initializers instead
        sample with the chip's own PRNG (seeded by `mx.random.seed`).
        Falls back to host (None) when:
        - TPUMX_HOST_INIT=1 (global revert knob),
        - the subclass overrides __call__ (its name-dispatch semantics
          are unknown here, e.g. LSTMBias),
        - the active PRNG key is traced (deferred init firing inside a
          jit trace must not capture a tracer in Parameter._data),
        - the subclass has no closed-form device rule (Orthogonal's SVD,
          Bilinear's loop)."""
        if os.environ.get("TPUMX_HOST_INIT") == "1":
            return None
        if type(self).__call__ is not Initializer.__call__:
            return None
        import jax.numpy as jnp
        from . import random as _random
        # the trace guard must come BEFORE any jnp call: inside a trace
        # (hybridize-before-first-forward, eval_shape) even jnp.full
        # stages into the jaxpr, and a tracer stored in Parameter._data
        # outlives the trace
        from jax._src.core import trace_state_clean
        if not trace_state_clean():
            return None
        aux = _aux_value(name)
        if aux is not None:
            return jnp.full(shape, aux, dtype)
        if self._device_weight.__func__ is Initializer._device_weight:
            return None  # no device rule; skip the key split
        key = _random.take_key() if self._needs_key else None
        out = self._device_weight(key, shape)
        return None if out is None else out.astype(dtype)

    _needs_key = True  # Zero/One/Constant ignore the PRNG: no key split

    def _device_weight(self, key, shape):
        return None


@registry.register
class Uniform(Initializer):
    def __init__(self, scale=0.07):
        self.scale = scale

    def _init_weight(self, name, shape):
        return _host_rng().uniform(-self.scale, self.scale, size=shape)

    def _device_weight(self, key, shape):
        import jax
        return jax.random.uniform(key, shape, minval=-self.scale,
                                  maxval=self.scale)


@registry.register
class Normal(Initializer):
    def __init__(self, sigma=0.01):
        self.sigma = sigma

    def _init_weight(self, name, shape):
        return _host_rng().normal(0, self.sigma, size=shape)

    def _device_weight(self, key, shape):
        import jax
        return self.sigma * jax.random.normal(key, shape)


@registry.register(aliases=("zeros",))
class Zero(Initializer):
    _needs_key = False

    def _init_weight(self, name, shape):
        return np.zeros(shape)

    def _device_weight(self, key, shape):
        import jax.numpy as jnp
        return jnp.zeros(shape)


@registry.register(aliases=("ones",))
class One(Initializer):
    _needs_key = False

    def _init_weight(self, name, shape):
        return np.ones(shape)

    def _device_weight(self, key, shape):
        import jax.numpy as jnp
        return jnp.ones(shape)


@registry.register
class Constant(Initializer):
    _needs_key = False

    def __init__(self, value=0.0):
        self.value = value

    def _init_weight(self, name, shape):
        return np.full(shape, self.value)

    def _device_weight(self, key, shape):
        import jax.numpy as jnp
        # no dtype pin: device_sample's astype(dtype) converts exactly
        # like the host np.full path (a float32 detour would round large
        # ints differently per path)
        return jnp.full(shape, self.value)


class Mixed:
    """Pattern-routed initializer (REF initializer.py:Mixed): first regex
    matching the parameter name picks the initializer."""

    def __init__(self, patterns, initializers):
        import re as _re
        if len(patterns) != len(initializers):
            raise ValueError("patterns and initializers must pair up")
        self._map = [(_re.compile(p), i if not isinstance(i, str)
                      else registry.create(i))
                     for p, i in zip(patterns, initializers)]

    def __call__(self, name, shape, dtype="float32"):
        for pat, init in self._map:
            if pat.search(name):
                return init(name, shape, dtype)
        raise ValueError(f"no initializer pattern matches {name!r}; "
                         "add a '.*' catch-all")


class Load:
    """Initialize from saved arrays (REF initializer.py:Load): dict or
    .npz/.params path; falls back to default_init for absent names."""

    def __init__(self, param, default_init=None, verbose=False):
        if isinstance(param, str):
            from .ndarray import load as nd_load
            param = nd_load(param)
        self._param = {k.split(":", 1)[-1]: v for k, v in param.items()}
        self._default = default_init
        self._verbose = verbose

    def __call__(self, name, shape, dtype="float32"):
        if name in self._param:
            arr = self._param[name]
            arr = arr.asnumpy() if hasattr(arr, "asnumpy") else np.asarray(arr)
            if tuple(arr.shape) != tuple(shape):
                raise ValueError(
                    f"Load: shape mismatch for {name}: saved "
                    f"{arr.shape} vs wanted {tuple(shape)}")
            return arr.astype(dtype)
        if self._default is None:
            raise ValueError(f"Load: {name!r} not in saved params and no "
                             "default_init given")
        return self._default(name, shape, dtype)


def _fan(shape, factor_type):
    hw = int(np.prod(shape[2:])) if len(shape) > 2 else 1
    fan_in = shape[1] * hw if len(shape) > 1 else shape[0]
    fan_out = shape[0] * hw
    if factor_type == "in":
        return fan_in
    if factor_type == "out":
        return fan_out
    return (fan_in + fan_out) / 2.0


@registry.register
class Xavier(Initializer):
    def __init__(self, rnd_type="uniform", factor_type="avg", magnitude=3):
        self.rnd_type = rnd_type
        self.factor_type = factor_type
        self.magnitude = magnitude

    def _init_weight(self, name, shape):
        factor = _fan(shape, self.factor_type)
        scale = math.sqrt(self.magnitude / factor)
        if self.rnd_type == "uniform":
            return _host_rng().uniform(-scale, scale, size=shape)
        return _host_rng().normal(0, scale, size=shape)

    def _device_weight(self, key, shape):
        import jax
        factor = _fan(shape, self.factor_type)
        scale = math.sqrt(self.magnitude / factor)
        if self.rnd_type == "uniform":
            return jax.random.uniform(key, shape, minval=-scale,
                                      maxval=scale)
        return scale * jax.random.normal(key, shape)


@registry.register(name="msraprelu")
class MSRAPrelu(Xavier):
    def __init__(self, factor_type="avg", slope=0.25):
        super().__init__("gaussian", factor_type, 2.0 / (1 + slope ** 2))


@registry.register
class Orthogonal(Initializer):
    def __init__(self, scale=1.414, rand_type="uniform"):
        self.scale = scale
        self.rand_type = rand_type

    def _init_weight(self, name, shape):
        rows = shape[0]
        cols = int(np.prod(shape[1:]))
        if self.rand_type == "uniform":
            tmp = _host_rng().uniform(-1.0, 1.0, (rows, cols))
        else:
            tmp = _host_rng().normal(0.0, 1.0, (rows, cols))
        u, _, v = np.linalg.svd(tmp, full_matrices=False)
        q = u if u.shape == (rows, cols) else v
        return (self.scale * q).reshape(shape)


@registry.register
class Bilinear(Initializer):
    def _init_weight(self, name, shape):
        weight = np.zeros(int(np.prod(shape)))
        f = np.ceil(shape[3] / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        for i in range(int(np.prod(shape))):
            x = i % shape[3]
            y = (i // shape[3]) % shape[2]
            weight[i] = (1 - abs(x / f - c)) * (1 - abs(y / f - c))
        return weight.reshape(shape)


@registry.register(name="lstmbias")
class LSTMBias(Initializer):
    """Forget-gate bias = 1 (reference: initializer.LSTMBias)."""

    def __init__(self, forget_bias=1.0):
        self.forget_bias = forget_bias

    def __call__(self, name, shape, dtype="float32"):
        b = np.zeros(shape, dtype)
        n = shape[0] // 4
        b[n:2 * n] = self.forget_bias
        return b

    def _init_weight(self, name, shape):
        return np.zeros(shape)


def create(name, **kwargs):
    if isinstance(name, Initializer):
        return name
    return registry.create(name, **kwargs)
