"""Fused multi-layer RNN/LSTM/GRU (REF:python/mxnet/gluon/rnn/rnn_layer.py over
the fused RNN op REF:src/operator/rnn.cc / cudnn_rnn-inl.h — the PTB path).

TPU-native design (SURVEY §7.3.6): instead of a cuDNN descriptor, each layer
is `lax.scan` over time with the input projection hoisted OUT of the scan —
x·W_i2hᵀ for all T timesteps is one large (T·N, G·H) MXU matmul; the scan body
only carries the (N, G·H) recurrent matmul + gate math, which XLA fuses into
a single per-step kernel.  Memory stays linear in T like the reference's
streaming cuDNN path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..block import HybridBlock
from ...ndarray import NDArray
from ...ndarray.ops import _apply
from ... import random as _random

__all__ = ["RNN", "LSTM", "GRU"]

_GATES = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}


def _layer_scan_core(mode, x_tnc, states, wi, wh, bi, bh):
    """One direction of one layer. x_tnc: (T, N, C); states: tuple of (N, H).
    Returns (out (T, N, H), final states)."""
    T, N, _ = x_tnc.shape
    H = wh.shape[1]

    if mode == "lstm":
        # hoisted input projection: one big (T·N, 4H) MXU matmul
        xproj = jnp.einsum("tnc,gc->tng", x_tnc, wi) + bi + bh
        def step(carry, xp):
            h, c = carry
            gates = xp + h @ wh.T
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            c_new = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
            return (h_new, c_new), h_new

        (h_f, c_f), out = lax.scan(step, (states[0], states[1]), xproj)
        return out, (h_f, c_f)

    if mode == "gru":
        # GRU needs the reset gate applied to h2h of the candidate, so the
        # h2h projection can't be fully merged; split wh by gate.
        # bh is per-gate here (not merged into xproj like lstm/rnn).
        wh_rz, wh_n = wh[:2 * H], wh[2 * H:]
        bh_n = bh[2 * H:]
        xproj = jnp.einsum("tnc,gc->tng", x_tnc, wi) + bi

        def step(h, xp):
            x_rz, x_n = xp[:, :2 * H], xp[:, 2 * H:]
            rz = jax.nn.sigmoid(x_rz + h @ wh_rz.T + bh[:2 * H])
            r, z = jnp.split(rz, 2, axis=-1)
            n = jnp.tanh(x_n + r * (h @ wh_n.T + bh_n))
            h_new = (1 - z) * n + z * h
            return h_new, h_new

        h_f, out = lax.scan(step, states[0], xproj)
        return out, (h_f,)

    act = jax.nn.relu if mode == "rnn_relu" else jnp.tanh
    xproj = jnp.einsum("tnc,gc->tng", x_tnc, wi) + bi + bh

    def step(h, xp):
        h_new = act(xp + h @ wh.T)
        return h_new, h_new

    h_f, out = lax.scan(step, states[0], xproj)
    return out, (h_f,)


def rnn_fused_core(mode, num_layers, bidirectional, dropout, x, init_states,
                   params, rng_key=None, training=False):
    """Full stacked (optionally bidirectional) RNN. x: (T, N, C).
    params: flat list per (layer, dir): [wi, wh, bi, bh, ...].
    init_states: tuple of (L*D, N, H) arrays (h, and c for lstm)."""
    dirs = 2 if bidirectional else 1
    outs = x
    h_finals, c_finals = [], []
    p = 0
    for layer in range(num_layers):
        layer_outs = []
        for d in range(dirs):
            wi, wh, bi, bh = params[p:p + 4]
            p += 4
            idx = layer * dirs + d
            st = tuple(s[idx] for s in init_states)
            inp = jnp.flip(outs, 0) if d == 1 else outs
            out, finals = _layer_scan_core(mode, inp, st, wi, wh, bi, bh)
            if d == 1:
                out = jnp.flip(out, 0)
            layer_outs.append(out)
            h_finals.append(finals[0])
            if mode == "lstm":
                c_finals.append(finals[1])
        outs = layer_outs[0] if dirs == 1 else \
            jnp.concatenate(layer_outs, axis=-1)
        if dropout > 0 and training and layer < num_layers - 1 and \
                rng_key is not None:
            rng_key, sub = jax.random.split(rng_key)
            outs = _random.dropout(outs, sub, dropout)
    h_out = jnp.stack(h_finals)
    if mode == "lstm":
        return outs, h_out, jnp.stack(c_finals)
    return outs, h_out


class _RNNLayer(HybridBlock):
    def __init__(self, mode, hidden_size, num_layers, layout, dropout,
                 bidirectional, input_size, i2h_weight_initializer=None,
                 h2h_weight_initializer=None, i2h_bias_initializer="zeros",
                 h2h_bias_initializer="zeros", dtype="float32", **kwargs):
        super().__init__(**kwargs)
        assert layout in ("TNC", "NTC")
        self._mode = mode
        self._hidden_size = hidden_size
        self._num_layers = num_layers
        self._layout = layout
        self._dropout = dropout
        self._dir = 2 if bidirectional else 1
        self._input_size = input_size
        self._dtype = dtype
        ng = _GATES[mode]
        self._param_names = []
        for layer in range(num_layers):
            for d in range(self._dir):
                suffix = "_l" if d == 0 else "_r"
                in_sz = input_size if layer == 0 else hidden_size * self._dir
                for name, shape, init in [
                        (f"{suffix}{layer}_i2h_weight",
                         (ng * hidden_size, in_sz), i2h_weight_initializer),
                        (f"{suffix}{layer}_h2h_weight",
                         (ng * hidden_size, hidden_size),
                         h2h_weight_initializer),
                        (f"{suffix}{layer}_i2h_bias",
                         (ng * hidden_size,), i2h_bias_initializer),
                        (f"{suffix}{layer}_h2h_bias",
                         (ng * hidden_size,), h2h_bias_initializer)]:
                    p = self.params.get(name, shape=shape, init=init,
                                        allow_deferred_init=True, dtype=dtype)
                    setattr(self, name.lstrip("_"), p)
                    self._param_names.append(name)

    def state_info(self, batch_size=0):
        infos = [{"shape": (self._num_layers * self._dir, batch_size,
                            self._hidden_size), "__layout__": "LNC"}]
        if self._mode == "lstm":
            infos.append(dict(infos[0]))
        return infos

    def cast(self, dtype):
        """Track the compute dtype: the implicit zero states must follow
        the cast or a bf16 net recurs in f32 (the r5 dtype audit caught
        exactly this — f32 states promoted every scan step of the 'bf16'
        PTB leg)."""
        super().cast(dtype)
        self._dtype = dtype

    def begin_state(self, batch_size=0, func=None, **kwargs):
        from ...ndarray import ops as F
        return [F.zeros(info["shape"], dtype=self._dtype)
                for info in self.state_info(batch_size)]

    def infer_shape(self, x, *args):
        in_sz = x.shape[-1]
        ng = _GATES[self._mode]
        for layer in range(self._num_layers):
            for d in range(self._dir):
                suffix = "_l" if d == 0 else "_r"
                sz = in_sz if layer == 0 else self._hidden_size * self._dir
                p = self.params[self.prefix +
                                f"{suffix}{layer}_i2h_weight"]
                p.shape_hint((ng * self._hidden_size, sz))

    def forward(self, inputs, states=None):
        for name, p in self._reg_params.items():
            if p._data is None and p._shape_incomplete():
                self.infer_shape(inputs)
        # base class finishes deferred init + substitution lookup
        return super().forward(inputs, states)

    def hybrid_forward(self, F, inputs, states=None, **params):
        from ... import autograd
        skip_states = states is None
        if self._layout == "NTC":
            inputs = F.swapaxes(inputs, 0, 1)
        batch = inputs.shape[1]
        # states follow the PROMOTED compute dtype: a bf16 net on bf16
        # input must not recur in f32 via f32 states (r5 dtype audit),
        # while any mixed call (f32 net on bf16 input, f32 states after
        # cast, ...) recurs in the promoted f32 the dots produce —
        # anything else mismatches the scan carry
        if skip_states:
            sdt = jnp.result_type(inputs.dtype, jnp.dtype(self._dtype))
            states = [F.zeros(info["shape"], dtype=sdt)
                      for info in self.state_info(batch)]
        else:
            sdt = jnp.result_type(inputs.dtype, jnp.dtype(self._dtype),
                                  *[s.dtype for s in states])
            states = [s if s.dtype == sdt else F.cast(s, dtype=sdt)
                      for s in states]
        ordered = [params[n.lstrip("_")] for n in self._param_names]
        training = autograd.is_training()
        key = _random.take_key() if (self._dropout > 0 and training) else None

        mode, nl, bd, dp = self._mode, self._num_layers, self._dir == 2, \
            self._dropout

        def core(x, *flat):
            ns = 2 if mode == "lstm" else 1
            init_states = tuple(flat[:ns])
            ps = list(flat[ns:])
            return rnn_fused_core(mode, nl, bd, dp, x, init_states, ps,
                                  rng_key=key, training=training)

        out = _apply(core, [inputs] + list(states) + ordered,
                     f"RNN[{mode}]")
        outputs, state_outs = out[0], out[1:]
        if self._layout == "NTC":
            outputs = F.swapaxes(outputs, 0, 1)
        if skip_states:
            return outputs
        return outputs, list(state_outs)

    def __repr__(self):
        return (f"{type(self).__name__}({self._hidden_size}, "
                f"num_layers={self._num_layers}, layout={self._layout!r}, "
                f"bidirectional={self._dir == 2})")


class RNN(_RNNLayer):
    def __init__(self, hidden_size, num_layers=1, activation="relu",
                 layout="TNC", dropout=0, bidirectional=False, input_size=0,
                 **kwargs):
        super().__init__(f"rnn_{activation}", hidden_size, num_layers, layout,
                         dropout, bidirectional, input_size, **kwargs)


class LSTM(_RNNLayer):
    def __init__(self, hidden_size, num_layers=1, layout="TNC", dropout=0,
                 bidirectional=False, input_size=0, **kwargs):
        super().__init__("lstm", hidden_size, num_layers, layout, dropout,
                         bidirectional, input_size, **kwargs)


class GRU(_RNNLayer):
    def __init__(self, hidden_size, num_layers=1, layout="TNC", dropout=0,
                 bidirectional=False, input_size=0, **kwargs):
        super().__init__("gru", hidden_size, num_layers, layout, dropout,
                         bidirectional, input_size, **kwargs)
