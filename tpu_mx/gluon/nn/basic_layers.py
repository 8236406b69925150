"""Gluon basic layers (REF:python/mxnet/gluon/nn/basic_layers.py)."""
from __future__ import annotations

import os

import numpy as np

from ... import autograd
from ... import layout as _layout_mod
from ..block import Block, HybridBlock

__all__ = ["Sequential", "HybridSequential", "Dense", "Dropout", "BatchNorm", "GroupNorm", "ReflectionPad2D",
           "LayerNorm", "RMSNorm", "InstanceNorm", "Embedding", "Flatten", "Activation",
           "LeakyReLU", "PReLU", "ELU", "SELU", "GELU", "Swish", "Lambda",
           "HybridLambda"]


class Sequential(Block):
    """Stack of blocks run sequentially."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for b in blocks:
            self.register_child(b)

    def forward(self, x, *args):
        for block in self._children.values():
            x = block(x)
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        items = list(self._children.values())
        if isinstance(key, slice):
            net = type(self)()
            net.add(*items[key])
            return net
        return items[key]

    def __iter__(self):
        return iter(self._children.values())


class HybridSequential(HybridBlock):
    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for b in blocks:
            self.register_child(b)

    def forward(self, x, *args):
        # containers route through children directly (each child resolves its
        # own deferred params); works identically on NDArray and traced values
        for block in self._children.values():
            x = block(x)
        return x

    def hybrid_forward(self, F, x):
        return self.forward(x)

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        items = list(self._children.values())
        if isinstance(key, slice):
            net = type(self)()
            net.add(*items[key])
            return net
        return items[key]

    def __iter__(self):
        return iter(self._children.values())


class Dense(HybridBlock):
    """y = act(x·Wᵀ + b) (REF:gluon/nn/basic_layers.py:Dense), MXU matmul."""

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype="float32", weight_initializer=None,
                 bias_initializer="zeros", in_units=0, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._flatten = flatten
        self._use_bias = use_bias
        self.weight = self.params.get("weight", shape=(units, in_units),
                                      dtype=dtype, init=weight_initializer,
                                      allow_deferred_init=True)
        if use_bias:
            self.bias = self.params.get("bias", shape=(units,), dtype=dtype,
                                        init=bias_initializer,
                                        allow_deferred_init=True)
        self.act = Activation(activation) if activation else None

    def infer_shape(self, x, *args):
        in_units = int(np.prod(x.shape[1:])) if self._flatten else x.shape[-1]
        self.weight.shape_hint((self._units, in_units))

    def hybrid_forward(self, F, x, weight, bias=None):
        out = F.FullyConnected(x, weight, bias, num_hidden=self._units,
                               no_bias=bias is None, flatten=self._flatten)
        return self.act(out) if self.act else out

    def __repr__(self):
        return f"Dense({self.weight.shape[1] or None} -> {self._units})"


class Dropout(HybridBlock):
    def __init__(self, rate, axes=(), **kwargs):
        super().__init__(**kwargs)
        self._rate = rate
        self._axes = axes

    def hybrid_forward(self, F, x):
        return F.Dropout(x, p=self._rate, axes=self._axes)

    def __repr__(self):
        return f"Dropout(p = {self._rate})"


class BatchNorm(HybridBlock):
    """BatchNorm with running-stat aux state
    (REF:gluon/nn/basic_layers.py:BatchNorm + src/operator/nn/batch_norm.cc).
    Aux mutation flows through the apply-scope updates dict under hybridize —
    the functional replacement for the reference's FMutateInputs."""

    def __init__(self, axis=None, momentum=0.9, epsilon=1e-5, center=True,
                 scale=True, use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones", running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0, **kwargs):
        super().__init__(**kwargs)
        # axis=None (the default) resolves against the active
        # tpu_mx.layout.default_layout: 1 for channels-first (the reference's
        # default), -1 under a channels-last block.
        self._axis = _layout_mod.bn_axis() if axis is None else axis
        self._momentum = momentum
        self._eps = epsilon
        self._center = center
        self._scale = scale
        self._use_global_stats = use_global_stats
        shape = (in_channels,) if in_channels else (0,)
        self.gamma = self.params.get("gamma", shape=shape,
                                     init=gamma_initializer,
                                     allow_deferred_init=True,
                                     grad_req="write" if scale else "null")
        self.beta = self.params.get("beta", shape=shape, init=beta_initializer,
                                    allow_deferred_init=True,
                                    grad_req="write" if center else "null")
        self.running_mean = self.params.get("running_mean", shape=shape,
                                            init=running_mean_initializer,
                                            allow_deferred_init=True,
                                            grad_req="null")
        self.running_var = self.params.get("running_var", shape=shape,
                                           init=running_variance_initializer,
                                           allow_deferred_init=True,
                                           grad_req="null")

    def infer_shape(self, x, *args):
        c = x.shape[self._axis]
        for p in (self.gamma, self.beta, self.running_mean, self.running_var):
            p.shape_hint((c,))

    def hybrid_forward(self, F, x, gamma, beta, running_mean, running_var):
        ndim = len(x.shape)
        axis = self._axis % ndim
        shape = [1] * ndim
        shape[axis] = x.shape[axis]
        red = tuple(i for i in range(ndim) if i != axis)
        g = gamma if self._scale else F.ones_like(gamma)
        b = beta if self._center else F.zeros_like(beta)
        training = autograd.is_training() and not self._use_global_stats
        if os.environ.get("TPUMX_BN_ONEPASS", "1") != "1":
            return self._legacy_forward(F, x, g, b, running_mean,
                                        running_var, red, shape, training)
        # One-pass f32 statistics + folded scale/bias (r5 byte diet; the
        # r4 roofline showed the bf16 ResNet step HBM-bound with 20.5 ms
        # of convert_reduce fusions).  The legacy two-pass form computes
        # var = mean(square(x - mean)), whose reduce DEPENDS on the mean
        # reduce — two sequential full reads of the activation.  The
        # sum/sum-of-squares form has no such dependency, so XLA sibling-
        # fuses both reductions into ONE read of x.  Stats stay f32
        # end-to-end (the legacy path round-tripped them through bf16 via
        # jnp.mean's upcast-and-cast-back); the normalize applies as a
        # single per-channel scale/bias folded in f32, cast once to
        # x.dtype — so no activation-sized f32 appears anywhere.
        n = 1
        for i in red:
            n *= x.shape[i]
        if training:
            xf = F.cast(x, dtype="float32")
            s1 = F.sum(xf, axis=red)
            s2 = F.sum(F.square(xf), axis=red)
            mean = s1 * (1.0 / n)
            # E[x^2]-E[x]^2 cancellation is benign here (f32 accumulation,
            # post-conv activations are near zero-mean); clamp guards the
            # var>=0 invariant against rounding
            var = F.maximum(s2 * (1.0 / n) - F.square(mean), 0.0)
            m = self._momentum
            with autograd.pause():
                rdt = str(running_mean.dtype)
                new_mean = m * running_mean + \
                    (1 - m) * F.cast(F.BlockGrad(mean), dtype=rdt)
                new_var = m * running_var + \
                    (1 - m) * F.cast(F.BlockGrad(var), dtype=rdt)
                self.running_mean._register_mutation(
                    new_mean._data if hasattr(new_mean, "_data") else new_mean)
                self.running_var._register_mutation(
                    new_var._data if hasattr(new_var, "_data") else new_var)
        else:
            mean = F.cast(running_mean, dtype="float32")
            var = F.cast(running_var, dtype="float32")
        inv = F.rsqrt(var + self._eps)
        scale = inv * F.cast(g, dtype="float32")
        bias = F.cast(b, dtype="float32") - mean * scale
        dt = str(x.dtype)
        return x * F.reshape(F.cast(scale, dtype=dt), shape=shape) + \
            F.reshape(F.cast(bias, dtype=dt), shape=shape)

    def _legacy_forward(self, F, x, g, b, running_mean, running_var, red,
                        shape, training):
        """Pre-r5 two-pass form (TPUMX_BN_ONEPASS=0): kept for the
        on-chip A/B of the one-pass byte diet."""
        if training:
            mean = F.mean(x, axis=red)
            var = F.mean(F.square(x - F.reshape(mean, shape=shape)), axis=red)
            m = self._momentum
            with autograd.pause():
                new_mean = m * running_mean + (1 - m) * F.BlockGrad(mean)
                new_var = m * running_var + (1 - m) * F.BlockGrad(var)
                self.running_mean._register_mutation(
                    new_mean._data if hasattr(new_mean, "_data") else new_mean)
                self.running_var._register_mutation(
                    new_var._data if hasattr(new_var, "_data") else new_var)
        else:
            mean, var = running_mean, running_var
        inv = F.rsqrt(F.reshape(var, shape=shape) + self._eps)
        return (x - F.reshape(mean, shape=shape)) * inv * \
            F.reshape(g, shape=shape) + F.reshape(b, shape=shape)

    def __repr__(self):
        return f"BatchNorm(axis={self._axis}, eps={self._eps}, " \
               f"momentum={self._momentum})"


class LayerNorm(HybridBlock):
    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._eps = epsilon
        shape = (in_channels,) if in_channels else (0,)
        self.gamma = self.params.get("gamma", shape=shape,
                                     init=gamma_initializer,
                                     allow_deferred_init=True,
                                     grad_req="write" if scale else "null")
        self.beta = self.params.get("beta", shape=shape, init=beta_initializer,
                                    allow_deferred_init=True,
                                    grad_req="write" if center else "null")

    def infer_shape(self, x, *args):
        c = x.shape[self._axis]
        self.gamma.shape_hint((c,))
        self.beta.shape_hint((c,))

    def hybrid_forward(self, F, x, gamma, beta):
        return F.LayerNorm(x, gamma, beta, axis=self._axis, eps=self._eps)


class RMSNorm(HybridBlock):
    """Root-mean-square norm over `axis` with a learned scale and no
    centring (Zhang & Sennrich, arXiv:1910.07467): the decoder families'
    norm.  Statistics in f32 whatever the input's type (ops.RMSNorm)."""

    def __init__(self, axis=-1, epsilon=1e-6, gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._eps = epsilon
        self.gamma = self.params.get(
            "gamma", shape=(in_channels,) if in_channels else (0,),
            init=gamma_initializer, allow_deferred_init=True)

    def infer_shape(self, x, *args):
        self.gamma.shape_hint((x.shape[self._axis],))

    def hybrid_forward(self, F, x, gamma):
        return F.RMSNorm(x, gamma, axis=self._axis, eps=self._eps)


class InstanceNorm(HybridBlock):
    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._eps = epsilon
        shape = (in_channels,) if in_channels else (0,)
        self.gamma = self.params.get("gamma", shape=shape,
                                     init=gamma_initializer,
                                     allow_deferred_init=True)
        self.beta = self.params.get("beta", shape=shape, init=beta_initializer,
                                    allow_deferred_init=True)

    def infer_shape(self, x, *args):
        c = x.shape[1]
        self.gamma.shape_hint((c,))
        self.beta.shape_hint((c,))

    def hybrid_forward(self, F, x, gamma, beta):
        return F.InstanceNorm(x, gamma, beta, eps=self._eps)


class GroupNorm(HybridBlock):
    """Group normalization over channel groups (REF:gluon/nn/basic_layers.py
    GroupNorm [ver>=1.6], src/operator/nn/group_norm.cc): NCHW-style input,
    channels split into num_groups, normalized over (group, *spatial) with
    f32 statistics.  gamma/beta are PER GROUP, shape (num_groups,), exactly
    the reference contract — so reference GroupNorm weights load
    unchanged."""

    def __init__(self, num_groups=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 **kwargs):
        super().__init__(**kwargs)
        self._ng = int(num_groups)
        self._eps = epsilon
        shape = (self._ng,)
        self.gamma = self.params.get("gamma", shape=shape,
                                     init=gamma_initializer,
                                     grad_req="write" if scale else "null")
        self.beta = self.params.get("beta", shape=shape, init=beta_initializer,
                                    grad_req="write" if center else "null")

    def hybrid_forward(self, F, x, gamma, beta):
        if x.shape[1] % self._ng:
            # shape known here even when in_channels was given up front
            # (infer_shape only runs for deferred params)
            from ...base import MXNetError
            raise MXNetError(f"GroupNorm: channels {x.shape[1]} not "
                             f"divisible by num_groups {self._ng}")
        return F.GroupNorm(x, gamma, beta, num_groups=self._ng,
                           eps=self._eps)


class Embedding(HybridBlock):
    """Lookup table (REF:gluon/nn/basic_layers.py:Embedding).  `sparse_grad`
    accepted for API parity; gradients are dense scatter-adds on TPU."""

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, sparse_grad=False, **kwargs):
        super().__init__(**kwargs)
        self._input_dim = input_dim
        self._output_dim = output_dim
        self.weight = self.params.get("weight", shape=(input_dim, output_dim),
                                      dtype=dtype, init=weight_initializer)

    def hybrid_forward(self, F, x, weight):
        return F.Embedding(x, weight, input_dim=self._input_dim,
                           output_dim=self._output_dim)

    def __repr__(self):
        return f"Embedding({self._input_dim} -> {self._output_dim})"


class Flatten(HybridBlock):
    def hybrid_forward(self, F, x):
        return F.flatten(x)

    def __repr__(self):
        return "Flatten"


class Activation(HybridBlock):
    def __init__(self, activation, **kwargs):
        super().__init__(**kwargs)
        self._act_type = activation

    def hybrid_forward(self, F, x):
        return F.Activation(x, act_type=self._act_type)

    def __repr__(self):
        return f"Activation({self._act_type})"


class LeakyReLU(HybridBlock):
    def __init__(self, alpha=0.01, **kwargs):
        super().__init__(**kwargs)
        self._alpha = alpha

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="leaky", slope=self._alpha)


class PReLU(HybridBlock):
    def __init__(self, alpha_initializer=None, **kwargs):
        super().__init__(**kwargs)
        from ... import initializer
        self.alpha = self.params.get("alpha", shape=(1,),
                                     init=alpha_initializer or
                                     initializer.Constant(0.25))

    def hybrid_forward(self, F, x, alpha):
        return F.LeakyReLU(x, gamma=alpha, act_type="prelu")


class ELU(HybridBlock):
    def __init__(self, alpha=1.0, **kwargs):
        super().__init__(**kwargs)
        self._alpha = alpha

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="elu", slope=self._alpha)


class SELU(HybridBlock):
    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="selu")


class GELU(HybridBlock):
    def hybrid_forward(self, F, x):
        return F.gelu(x)


class Swish(HybridBlock):
    def __init__(self, beta=1.0, **kwargs):
        super().__init__(**kwargs)
        self._beta = beta

    def hybrid_forward(self, F, x):
        return x * F.sigmoid(self._beta * x)


class ReflectionPad2D(HybridBlock):
    """Reflection padding on H/W of NCHW input
    (REF basic_layers.py:ReflectionPad2D)."""

    def __init__(self, padding=0, **kwargs):
        super().__init__(**kwargs)
        if isinstance(padding, int):
            padding = (padding,) * 4  # (left, right, top, bottom)
        self._pad = tuple(int(p) for p in padding)

    def hybrid_forward(self, F, x):
        import jax.numpy as jnp
        from ...ndarray import ops as O
        l, r, t, b = self._pad
        return O._apply(
            lambda a: jnp.pad(a, ((0, 0), (0, 0), (t, b), (l, r)),
                              mode="reflect"),
            [x], "ReflectionPad2D")


class Lambda(Block):
    def __init__(self, function, **kwargs):
        super().__init__(**kwargs)
        self._fn = function

    def forward(self, *args):
        return self._fn(*args)


class HybridLambda(HybridBlock):
    def __init__(self, function, **kwargs):
        super().__init__(**kwargs)
        self._fn = function

    def hybrid_forward(self, F, *args):
        return self._fn(F, *args)
