"""Optimizers (REF:python/mxnet/optimizer/optimizer.py + the fused update
kernels in REF:src/operator/optimizer_op.cc).

Design: every optimizer exposes a *pure functional core*
``update_core(weight, grad, state, lr, wd, t) -> (new_weight, new_state)`` on
raw jax arrays — the analog of the reference's fused sgd_update/adam_update
kernels, jit-able inside a compiled train step — plus the reference's
imperative face (`update(index, weight, grad, state)`) used by Trainer/KVStore.
Mixed precision: `multi_precision` keeps fp32 master weights for fp16/bf16
params, matching the reference's mp_* kernel family.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..base import Registry
from ..ndarray import NDArray
from ..ndarray.ops import (adam_update_core, sgd_mom_update_core,
                           sgd_update_core)

__all__ = ["Optimizer", "SGD", "NAG", "Adam", "AdamW", "RMSProp", "AdaGrad",
           "AdaDelta", "Ftrl", "Signum", "LAMB", "LBSGD", "create", "register", "Updater",
           "get_updater", "registry"]

registry = Registry("optimizer")
register = registry.register


def create(name, **kwargs):
    if isinstance(name, Optimizer):
        return name
    return registry.create(name, **kwargs)


class Optimizer:
    """Base optimizer: lr scheduling, wd/lr multipliers, grad rescale/clip,
    per-index state, mixed-precision master weights."""

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0, multi_precision=False,
                 param_dict=None):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.clip_gradient = clip_gradient
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count = {}
        self.multi_precision = multi_precision
        self.idx2name = param_idx2name or {}
        self.param_dict = param_dict or {}
        self.lr_mult = {}
        self.wd_mult = {}

    # -- reference API --------------------------------------------------------
    def set_learning_rate(self, lr):
        self.lr = lr

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = dict(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = dict(args_wd_mult)

    def _update_count(self, index):
        if index not in self._index_update_count:
            self._index_update_count[index] = self.begin_num_update
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index], self.num_update)

    def _get_lr(self, index):
        lr = self.lr_scheduler(self.num_update) if self.lr_scheduler else self.lr
        if index in self.param_dict:
            lr *= self.param_dict[index].lr_mult
        elif index in self.lr_mult:
            lr *= self.lr_mult[index]
        elif index in self.idx2name:
            lr *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lr

    def _get_wd(self, index):
        wd = self.wd
        if index in self.param_dict:
            wd *= self.param_dict[index].wd_mult
        elif index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif index in self.idx2name:
            wd *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wd

    # -- state ---------------------------------------------------------------
    def create_state(self, index, weight):
        """Return opaque per-weight state (raw jax arrays / tuples / None)."""
        return None

    def create_state_multi_precision(self, index, weight):
        if self.multi_precision and weight.dtype in (jnp.float16, jnp.bfloat16):
            master = weight._data.astype(jnp.float32)
            return (master, self.create_state(index, NDArray(master)))
        return self.create_state(index, weight)

    # -- updates --------------------------------------------------------------
    def update_core(self, weight, grad, state, lr, wd, t):
        raise NotImplementedError

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        t = self._index_update_count[index]
        new_w, new_state = self.update_core(weight._data, grad._data, state,
                                            lr, wd, t)
        weight._rebind(new_w.astype(weight.dtype))
        return new_state

    def update_multi_precision(self, index, weight, grad, state):
        if self.multi_precision and weight.dtype in (jnp.float16, jnp.bfloat16):
            self._update_count(index)
            lr, wd = self._get_lr(index), self._get_wd(index)
            t = self._index_update_count[index]
            master, inner = state
            new_master, new_inner = self.update_core(
                master, grad._data.astype(jnp.float32), inner, lr, wd, t)
            weight._rebind(new_master.astype(weight.dtype))
            return (new_master, new_inner)
        return self.update(index, weight, grad, state)

    def _preprocess(self, grad, weight, wd):
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        return g


@register
class SGD(Optimizer):
    """SGD (+momentum) — fused form of REF sgd_update/sgd_mom_update."""

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum != 0.0:
            return jnp.zeros(weight.shape, jnp.float32 if weight.dtype in
                             (jnp.float16, jnp.bfloat16) else weight.dtype)
        return None

    def update_core(self, weight, grad, state, lr, wd, t):
        if self.momentum == 0.0:
            return sgd_update_core(weight, grad, lr, wd, self.rescale_grad,
                                   self.clip_gradient), None
        return sgd_mom_update_core(weight, grad, state, lr, self.momentum, wd,
                                   self.rescale_grad, self.clip_gradient)


@register
class LBSGD(Optimizer):
    """Large-batch SGD: momentum SGD with LARS layer-wise adaptive rates
    and warmup (REF optimizer.py LBSGD — You et al., "Large Batch Training
    of Convolutional Networks")."""

    def __init__(self, momentum=0.0,
                 warmup_strategy="linear", warmup_epochs=5, batch_scale=1,
                 updates_per_epoch=32, begin_epoch=0, num_epochs=60,
                 eta=0.001, epsilon=1e-9, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.eta = eta          # LARS trust coefficient
        self.epsilon = epsilon
        self.warmup_updates = max(1, int(warmup_epochs * updates_per_epoch))
        self.warmup_strategy = warmup_strategy

    def create_state(self, index, weight):
        if self.momentum != 0.0:
            return jnp.zeros(weight.shape, jnp.float32 if weight.dtype in
                             (jnp.float16, jnp.bfloat16) else weight.dtype)
        return None

    def update_core(self, weight, grad, state, lr, wd, t):
        # linear warmup on top of the scheduler-provided lr
        warm = jnp.minimum(1.0, t / self.warmup_updates) \
            if self.warmup_strategy == "linear" else 1.0
        g = self._preprocess(grad, weight, wd)
        # LARS: scale lr by ||w|| / (||g|| + wd*||w|| + eps) per layer
        wnorm = jnp.sqrt(jnp.sum(weight.astype(jnp.float32) ** 2))
        gnorm = jnp.sqrt(jnp.sum(g.astype(jnp.float32) ** 2))
        trust = jnp.where(
            (wnorm > 0) & (gnorm > 0),
            self.eta * wnorm / (gnorm + wd * wnorm + self.epsilon), 1.0)
        eff_lr = (lr * warm * trust).astype(weight.dtype)
        g = g + wd * weight
        if self.momentum == 0.0:
            return weight - eff_lr * g, None
        new_mom = self.momentum * state + g
        return weight - eff_lr * new_mom, new_mom


@register
class NAG(Optimizer):
    """Nesterov accelerated SGD (REF nag_mom_update)."""

    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        return jnp.zeros(weight.shape, weight.dtype)

    def update_core(self, weight, grad, state, lr, wd, t):
        g = self._preprocess(grad, weight, wd) + wd * weight
        new_mom = self.momentum * state + g
        new_w = weight - lr * (g + self.momentum * new_mom)
        return new_w, new_mom


@register
class Adam(Optimizer):
    """REF adam_update fused kernel."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def create_state(self, index, weight):
        dt = jnp.float32 if weight.dtype in (jnp.float16, jnp.bfloat16) \
            else weight.dtype
        return (jnp.zeros(weight.shape, dt), jnp.zeros(weight.shape, dt))

    def update_core(self, weight, grad, state, lr, wd, t):
        mean, var = state
        new_w, m, v = adam_update_core(weight, grad, mean, var, lr, self.beta1,
                                       self.beta2, self.epsilon, wd, t,
                                       self.rescale_grad, self.clip_gradient)
        return new_w, (m, v)


@register
class AdamW(Optimizer):
    """Decoupled weight decay (REF contrib adamw [ver>=1.6])."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def create_state(self, index, weight):
        dt = jnp.float32 if weight.dtype in (jnp.float16, jnp.bfloat16) \
            else weight.dtype
        return (jnp.zeros(weight.shape, dt), jnp.zeros(weight.shape, dt))

    def update_core(self, weight, grad, state, lr, wd, t):
        mean, var = state
        g = self._preprocess(grad, weight, wd)
        m = self.beta1 * mean + (1 - self.beta1) * g
        v = self.beta2 * var + (1 - self.beta2) * jnp.square(g)
        mhat = m / (1 - self.beta1 ** t)
        vhat = v / (1 - self.beta2 ** t)
        new_w = weight - lr * (mhat / (jnp.sqrt(vhat) + self.epsilon) +
                               wd * weight)
        return new_w, (m, v)


@register
class RMSProp(Optimizer):
    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1, self.gamma2 = gamma1, gamma2
        self.epsilon = epsilon
        self.centered = centered

    def create_state(self, index, weight):
        z = jnp.zeros(weight.shape, weight.dtype)
        return (z, z, z) if self.centered else z

    def update_core(self, weight, grad, state, lr, wd, t):
        g = self._preprocess(grad, weight, wd) + wd * weight
        if self.centered:
            n, mg, delta = state
            n = (1 - self.gamma1) * jnp.square(g) + self.gamma1 * n
            mg = (1 - self.gamma1) * g + self.gamma1 * mg
            delta = self.gamma2 * delta - lr * g / jnp.sqrt(
                n - jnp.square(mg) + self.epsilon)
            return weight + delta, (n, mg, delta)
        n = (1 - self.gamma1) * jnp.square(g) + self.gamma1 * state
        return weight - lr * g / jnp.sqrt(n + self.epsilon), n


@register
class AdaGrad(Optimizer):
    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return jnp.zeros(weight.shape, weight.dtype)

    def update_core(self, weight, grad, state, lr, wd, t):
        g = self._preprocess(grad, weight, wd) + wd * weight
        hist = state + jnp.square(g)
        return weight - lr * g / jnp.sqrt(hist + self.float_stable_eps), hist


@register
class AdaDelta(Optimizer):
    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho, self.epsilon = rho, epsilon

    def create_state(self, index, weight):
        z = jnp.zeros(weight.shape, weight.dtype)
        return (z, z)

    def update_core(self, weight, grad, state, lr, wd, t):
        acc_g, acc_delta = state
        g = self._preprocess(grad, weight, wd) + wd * weight
        acc_g = self.rho * acc_g + (1 - self.rho) * jnp.square(g)
        delta = jnp.sqrt(acc_delta + self.epsilon) / \
            jnp.sqrt(acc_g + self.epsilon) * g
        acc_delta = self.rho * acc_delta + (1 - self.rho) * jnp.square(delta)
        return weight - delta, (acc_g, acc_delta)


@register
class Ftrl(Optimizer):
    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1, self.beta = lamda1, beta

    def create_state(self, index, weight):
        z = jnp.zeros(weight.shape, weight.dtype)
        return (z, z)  # z, n

    def update_core(self, weight, grad, state, lr, wd, t):
        z, n = state
        g = self._preprocess(grad, weight, wd)
        sigma = (jnp.sqrt(n + jnp.square(g)) - jnp.sqrt(n)) / lr
        z = z + g - sigma * weight
        n = n + jnp.square(g)
        new_w = jnp.where(
            jnp.abs(z) > self.lamda1,
            -(z - jnp.sign(z) * self.lamda1) /
            ((self.beta + jnp.sqrt(n)) / lr + wd),
            0.0)
        return new_w.astype(weight.dtype), (z, n)


@register
class Signum(Optimizer):
    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        return jnp.zeros(weight.shape, weight.dtype) if self.momentum else None

    def update_core(self, weight, grad, state, lr, wd, t):
        g = self._preprocess(grad, weight, wd)
        if self.momentum:
            mom = self.momentum * state - (1 - self.momentum) * g
            new_w = (1 - lr * self.wd_lh) * weight + lr * jnp.sign(mom)
            return new_w, mom
        return (1 - lr * self.wd_lh) * weight - lr * jnp.sign(g), None


@register
class LAMB(Optimizer):
    """Layer-wise adaptive large-batch optimizer (REF lamb_update [ver>=1.6];
    the BERT path)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-6, lower_bound=None, upper_bound=None,
                 bias_correction=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.lower_bound, self.upper_bound = lower_bound, upper_bound
        self.bias_correction = bias_correction

    def create_state(self, index, weight):
        dt = jnp.float32 if weight.dtype in (jnp.float16, jnp.bfloat16) \
            else weight.dtype
        return (jnp.zeros(weight.shape, dt), jnp.zeros(weight.shape, dt))

    def update_core(self, weight, grad, state, lr, wd, t):
        mean, var = state
        g = self._preprocess(grad, weight, wd)
        m = self.beta1 * mean + (1 - self.beta1) * g
        v = self.beta2 * var + (1 - self.beta2) * jnp.square(g)
        if self.bias_correction:
            mhat = m / (1 - self.beta1 ** t)
            vhat = v / (1 - self.beta2 ** t)
        else:
            mhat, vhat = m, v
        update = mhat / (jnp.sqrt(vhat) + self.epsilon) + wd * weight
        wnorm = jnp.linalg.norm(weight)
        unorm = jnp.linalg.norm(update)
        ratio = jnp.where(
            (wnorm > 0) & (unorm > 0),
            wnorm / unorm, 1.0)
        if self.lower_bound is not None:
            ratio = jnp.maximum(ratio, self.lower_bound)
        if self.upper_bound is not None:
            ratio = jnp.minimum(ratio, self.upper_bound)
        return weight - lr * ratio * update, (m, v)


class Updater:
    """KVStore server-side updater (REF optimizer.py:Updater / get_updater):
    applies optimizer updates keyed by parameter index."""

    def __init__(self, optimizer):
        self.optimizer = optimizer
        self.states = {}

    def __call__(self, index, grad, weight):
        if index not in self.states:
            self.states[index] = \
                self.optimizer.create_state_multi_precision(index, weight)
        self.states[index] = self.optimizer.update_multi_precision(
            index, weight, grad, self.states[index])

    def set_states(self, states):
        self.states = states

    def get_states(self):
        return self.states


def get_updater(optimizer):
    return Updater(optimizer)


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (REF:src/operator/optimizer_op
    dcasgd; Zheng et al. 2016): the reference's async-worker staleness
    compensation — kept for API parity (our dist is bulk-synchronous, so
    the previous-weight term sees a 1-step-old copy)."""

    def __init__(self, learning_rate=0.01, momentum=0.0, lamda=0.04,
                 **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.lamda = lamda

    def create_state(self, index, weight):
        w = weight._data if hasattr(weight, "_data") else weight
        return (jnp.zeros(w.shape, w.dtype), jnp.asarray(w))

    def update_core(self, weight, grad, state, lr, wd, t):
        g = self._preprocess(grad, weight, wd)
        mom, prev_w = state
        comp = g + self.lamda * g * g * (weight - prev_w)
        mom = self.momentum * mom - lr * comp
        new_w = weight + mom
        return new_w, (mom, new_w)


@register
class SGLD(Optimizer):
    """Stochastic gradient Langevin dynamics (REF optimizer.py:SGLD):
    SGD + sqrt(lr) gaussian noise — Bayesian posterior sampling."""

    def __init__(self, learning_rate=0.01, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)

    def create_state(self, index, weight):
        return None

    def update_core(self, weight, grad, state, lr, wd, t):
        g = self._preprocess(grad, weight, wd)
        # deterministic per-(t, shape) draw keyed off the framework stream
        # contract: traced inside the step, keyed on the step counter
        # tpumx-lint: disable=determinism -- traced constant key folded with
        # t: the noise is a pure function of the step counter, so a resume
        # capsule replays it exactly without carrying any stream state
        key = jax.random.fold_in(jax.random.PRNGKey(0),
                                 jnp.asarray(t, jnp.int32))
        noise = jax.random.normal(key, weight.shape, jnp.float32)
        return (weight - 0.5 * lr * g +
                jnp.sqrt(lr).astype(weight.dtype) *
                noise.astype(weight.dtype)), None


@register
class Adamax(Optimizer):
    """Adam with infinity norm (REF optimizer.py:Adamax)."""

    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999,
                 **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2 = beta1, beta2

    def create_state(self, index, weight):
        w = weight._data if hasattr(weight, "_data") else weight
        return (jnp.zeros(w.shape, jnp.float32),
                jnp.zeros(w.shape, jnp.float32))

    def update_core(self, weight, grad, state, lr, wd, t):
        g = self._preprocess(grad, weight, wd).astype(jnp.float32)
        m, u = state
        m = self.beta1 * m + (1 - self.beta1) * g
        u = jnp.maximum(self.beta2 * u, jnp.abs(g))
        lr_t = lr / (1 - self.beta1 ** t)
        new_w = weight - (lr_t * m / (u + 1e-8)).astype(weight.dtype)
        return new_w, (m, u)


@register
class Nadam(Optimizer):
    """Nesterov Adam (REF optimizer.py:Nadam; Dozat 2016)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2 = beta1, beta2
        self.epsilon = epsilon
        self.schedule_decay = schedule_decay

    def create_state(self, index, weight):
        w = weight._data if hasattr(weight, "_data") else weight
        return (jnp.zeros(w.shape, jnp.float32),
                jnp.zeros(w.shape, jnp.float32),
                jnp.ones((), jnp.float32))  # m_schedule product

    def update_core(self, weight, grad, state, lr, wd, t):
        g = self._preprocess(grad, weight, wd).astype(jnp.float32)
        m, v, m_sched = state
        mu_t = self.beta1 * (1 - 0.5 * 0.96 ** (t * self.schedule_decay))
        mu_t1 = self.beta1 * (1 - 0.5 * 0.96 **
                              ((t + 1) * self.schedule_decay))
        m_sched_new = m_sched * mu_t
        g_prime = g / (1 - m_sched_new)
        m = self.beta1 * m + (1 - self.beta1) * g
        m_prime = m / (1 - m_sched_new * mu_t1)
        v = self.beta2 * v + (1 - self.beta2) * g * g
        v_prime = v / (1 - self.beta2 ** t)
        m_bar = (1 - mu_t) * g_prime + mu_t1 * m_prime
        new_w = weight - (lr * m_bar /
                          (jnp.sqrt(v_prime) + self.epsilon)).astype(
                              weight.dtype)
        return new_w, (m, v, m_sched_new)


@register
class FTML(Optimizer):
    """Follow the moving leader (REF ftml_update; Zheng & Kwok 2017)."""

    def __init__(self, learning_rate=0.0025, beta1=0.6, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2 = beta1, beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        w = weight._data if hasattr(weight, "_data") else weight
        # three DISTINCT buffers: donation rejects one buffer bound to
        # several arguments (f(donate(a), donate(a)))
        return (jnp.zeros(w.shape, jnp.float32),
                jnp.zeros(w.shape, jnp.float32),
                jnp.zeros(w.shape, jnp.float32))  # d, v, z

    def update_core(self, weight, grad, state, lr, wd, t):
        g = self._preprocess(grad, weight, wd).astype(jnp.float32)
        d_prev, v_prev, z_prev = state
        v = self.beta2 * v_prev + (1 - self.beta2) * g * g
        d = (1 - self.beta1 ** t) / lr * (
            jnp.sqrt(v / (1 - self.beta2 ** t)) + self.epsilon)
        sigma = d - self.beta1 * d_prev
        z = self.beta1 * z_prev + (1 - self.beta1) * g - sigma * weight
        new_w = (-z / d).astype(weight.dtype)
        return new_w, (d, v, z)
