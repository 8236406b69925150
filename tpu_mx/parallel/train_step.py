"""Compiled SPMD train step — the performance path (SURVEY §3.2's hot loop,
fused into ONE XLA program).

The reference's step is: CachedOp forward → autograd backward → KVStore
push/pull (NCCL/PS) → fused optimizer kernels, four engine-scheduled phases.
Here the entire step — forward, backward, gradient reduction (psum inserted
by XLA from the shardings), optimizer update, BN-stat update — is a single
jitted function with donated buffers, so weights never leave device and XLA
overlaps the collectives with the backward pass (the same overlap the
reference engineered via per-parameter engine ordering).

Sharding: parameters get PartitionSpecs from regex rules (default replicated
= pure DP; rules give Megatron-style TP or fsdp), batch enters sharded over
`dp` (and `sp` for sequence-parallel models).  Works mesh-less too (single
device jit).
"""
from __future__ import annotations

import functools
import logging
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import random as _random
from .. import telemetry as _telemetry
from .. import tracing as _tracing
from ..ndarray import NDArray

__all__ = ["CompiledTrainStep", "fsdp_rules", "sharding_for", "apply_rules"]

_logger = logging.getLogger(__name__)

# jax.named_scope names inside the one XLA program (HLO metadata only): a
# device trace splits the step by them (docs/observability.md; read by
# benchmark/scopes.py).  Forward is train_step.grad/jvp(...), backward
# train_step.grad/transpose(jvp(...)).
STEP_SCOPES = ("train_step.grad", "train_step.grad_sync",
               "train_step.grad_accum", "train_step.optimizer",
               "train_step.fingerprint")
_GRAD, _GRAD_SYNC, _GRAD_ACCUM, _OPTIMIZER, _FINGERPRINT = STEP_SCOPES


def _fingerprint_on():
    """``TPUMX_FINGERPRINT`` gates the device-side SDC fingerprint
    (ISSUE 20, parallel/integrity.py; default ON).  Read at trace time:
    flipping it changes the program, which the overhead-receipt A/B does
    by construction (one fresh process per arm)."""
    return os.environ.get("TPUMX_FINGERPRINT", "1").lower() \
        not in ("0", "false", "off")


def _shape_signature(raw):
    """The batch's shape signature (``"float32[16,4];float32[16]"``) —
    the label the per-shape compile metrics key on (ISSUE 14): jax
    retraces/compiles once per distinct operand signature even when the
    jit wrapper itself survives, so "how many programs did this run
    compile, for which shapes, costing how long" needs the signature as
    the series key, not just the build count."""
    parts = []
    for b in raw:
        if b is None:
            parts.append("none")
            continue
        dt = np.dtype(getattr(b, "dtype", np.float32)).name
        shape = ",".join(str(int(d)) for d in getattr(b, "shape", ()))
        parts.append(f"{dt}[{shape}]")
    return ";".join(parts)


def _drop_absent_axes(spec, mesh):
    """`spec` without the axes `mesh` lacks, so one set of rules or data
    specs written for dp×tp×sp serves every smaller mesh."""
    def keep(ax):
        if isinstance(ax, tuple):
            return tuple(a for a in ax if a in mesh.axis_names) or None
        return ax if ax in mesh.axis_names else None
    return tuple(keep(ax) for ax in spec)


def apply_rules(name, shape, rules, mesh):
    """First matching (regex → PartitionSpec) rule wins; axes not in the mesh
    are dropped from the spec; default replicated."""
    if rules:
        for pattern, spec in rules:
            if re.search(pattern, name):
                cleaned = _drop_absent_axes(spec, mesh) \
                    if mesh is not None else ()
                # drop trailing Nones beyond rank
                cleaned = cleaned[:len(shape)]
                return P(*cleaned)
    return P()


def sharding_for(mesh, spec):
    return NamedSharding(mesh, spec) if mesh is not None else None


class CompiledTrainStep:
    """One-program train step over an (optional) mesh.

    net        — an initialized HybridBlock (run one forward first)
    loss_fn    — gluon Loss block (operates on raw arrays through F ops)
    optimizer  — tpu_mx optimizer (its pure update_core is traced in)
    mesh       — jax.sharding.Mesh or None
    rules      — [(regex, PartitionSpec)] parameter sharding rules
    data_specs — PartitionSpecs for the batch inputs (default P('dp') on
                 axis0); axes the mesh lacks are dropped, as for rules
    n_loss_args — how many TRAILING step() args go to the loss instead of
                  the network forward (default 1: the label; 2 for e.g.
                  (label, sample_weight) losses)
    gradient_compression — None, or {"type": "2bit", "threshold": t} /
                  {"type": "int8"}: the in-step quantized gradient
                  allreduce (SURVEY §2.3 stretch; the reference compressed
                  only on the kvstore push wire,
                  REF:src/kvstore/gradient_compression.cc).  Per-device
                  partial gradients are quantized with per-device error
                  feedback (carried in the train state, dp-sharded), summed
                  with a psum over `dp`, and dequantized into the optimizer.
                  Requires a mesh with dp>1 and pure-DP (replicated) params.
    accum_steps — gradient accumulation: every K-th step() applies the
                  optimizer with the MEAN of the last K microbatch
                  gradients (the reference's grad_req='add' + delayed
                  Trainer.step pattern, REF:python/mxnet/gluon/trainer.py).
                  Two compiled programs (accumulate / apply) — static
                  control flow stays outside jit.  BN stats still update
                  every microbatch.
    """

    def __init__(self, net, loss_fn, optimizer, mesh=None, rules=None,
                 data_specs=None, donate=True, n_loss_args=1,
                 gradient_compression=None, accum_steps=1):
        self.net = net
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh
        params = {k: p for k, p in net.collect_params().items()
                  if p._data is not None}
        if not params:
            raise ValueError("net has no initialized parameters; run one "
                             "forward pass before compiling the step")
        self._params = params
        self._diff_keys = [
            k for k, p in params.items()
            if p.grad_req != "null" and jnp.issubdtype(p.data().dtype,
                                                       jnp.floating)]
        self._lr_mults = {k: params[k].lr_mult for k in self._diff_keys}
        self._wd_mults = {k: params[k].wd_mult for k in self._diff_keys}
        self.values = {k: p.data()._data for k, p in params.items()}
        # mixed precision: f32 master copies for low-precision diff params
        # (the reference's mp_* kernel family; optimizer.multi_precision)
        self._mp_keys = set()
        if getattr(optimizer, "multi_precision", False):
            self._mp_keys = {
                k for k in self._diff_keys
                if self.values[k].dtype in (jnp.float16, jnp.bfloat16)}
        self.masters = {k: self.values[k].astype(jnp.float32)
                        for k in self._mp_keys}
        self.opt_states = {
            k: optimizer.create_state(
                i, NDArray(self.masters[k]) if k in self._mp_keys
                else params[k].data())
            for i, k in enumerate(self._diff_keys)}
        self._t = 0
        self._specs = {k: apply_rules(k, v.shape, rules, mesh)
                       for k, v in self.values.items()}
        # like the rules: axes the mesh lacks are dropped, so
        # bert_data_specs() (dp × sp) serves a dp or dp × tp mesh
        if data_specs and mesh is not None:
            data_specs = tuple(P(*_drop_absent_axes(s, mesh))
                               for s in data_specs)
        self._data_specs = data_specs
        self._donate = donate
        if n_loss_args < 1:
            raise ValueError("n_loss_args must be >= 1 (the label)")
        self._n_loss_args = n_loss_args
        if accum_steps < 1:
            raise ValueError("accum_steps must be >= 1")
        # accum × compression composes as compress-ONCE-per-applied-update:
        # microbatch grads accumulate per-device (dp-sharded local buffers,
        # no collective), and the single quantized psum happens in the
        # apply step on the accumulated mean — one quantization error per
        # update, exactly one compressed reduction (closes DIVERGENCES'
        # former #12 rejection)
        self._accum = int(accum_steps)
        self._micro = 0
        self._last_fp = None  # last committed step's device fingerprint
        self._gacc = None     # lazy f32 grad-accumulation buffers
        self._accum_jit = None
        self._compression = None
        self._efs = {}
        if gradient_compression:
            ctype = gradient_compression.get("type", "2bit")
            if ctype not in ("2bit", "int8", "fp8"):
                raise ValueError(f"unsupported compression type {ctype!r} "
                                 "(have: 2bit, int8, fp8)")
            if mesh is None or "dp" not in mesh.axis_names or \
                    mesh.shape["dp"] < 2:
                raise ValueError(
                    "gradient_compression needs a mesh with a dp axis >1 "
                    "(it compresses the dp gradient reduction)")
            sharded = [k for k in self._diff_keys
                       if any(ax is not None for ax in self._specs[k])]
            if sharded:
                raise ValueError(
                    "gradient_compression supports pure-DP (replicated) "
                    f"params; these are sharded: {sharded[:3]}...")
            # the compressed reduce psums over 'dp' only; batch sharding
            # over any other axis would silently drop those contributions
            bad_axes = set()
            for spec in (data_specs or ()):
                for ax in spec:
                    for a in (ax if isinstance(ax, tuple) else (ax,)):
                        if a is not None and a != "dp":
                            bad_axes.add(a)
            if bad_axes:
                raise ValueError(
                    "gradient_compression reduces over 'dp' only, but "
                    f"data_specs shard the batch over {sorted(bad_axes)}")
            self._compression = dict(gradient_compression, type=ctype)
            ndp = mesh.shape["dp"]
            # per-device quantization error feedback, dp-sharded on axis 0;
            # allocated ALREADY sharded (out_shardings) so a big model never
            # materializes ndp full copies on one device — one compile for
            # the whole dict, not one per tensor
            ef_sh = sharding_for(mesh, P("dp"))
            shapes = {k: self.values[k].shape for k in self._diff_keys}
            alloc = jax.jit(
                lambda: {k: jnp.zeros((ndp,) + s, jnp.float32)
                         for k, s in shapes.items()},
                out_shardings={k: ef_sh for k in shapes})
            self._efs = alloc()
        self._jitted = None
        self._build_count = 0
        # batch shape-signatures already traced/compiled: the first step
        # at a NEW signature pays the retrace+XLA-compile inside its jit
        # call, so that call's wall clock is observed as compile_seconds
        # under the signature label (ISSUE 14 capacity twins)
        self._seen_signatures = set()
        # zombie-step guard: a watchdog-abandoned step that later finishes
        # must not apply its (stale) result over restored state.  Restores
        # bump _generation under _state_lock; _step commits its new state
        # only if the generation it started under is still current.
        self._state_lock = threading.Lock()
        self._generation = 0

    # -- sharding helpers -----------------------------------------------------
    def _value_shardings(self):
        return {k: sharding_for(self.mesh, self._specs[k])
                for k in self.values}

    def _state_shardings(self):
        return {
            k: jax.tree_util.tree_map(
                lambda _: sharding_for(self.mesh, self._specs[k]),
                self.opt_states[k])
            for k in self._diff_keys}

    def place(self):
        """Device_put params/opt state onto their mesh shardings."""
        if self.mesh is None:
            return
        vs = self._value_shardings()
        values = {k: jax.device_put(v, vs[k])
                  for k, v in self.values.items()}
        masters = {k: jax.device_put(v, vs[k])
                   for k, v in self.masters.items()}
        ss = self._state_shardings()
        opt_states = {k: jax.device_put(s, ss[k])
                      for k, s in self.opt_states.items()}
        ef_sh = sharding_for(self.mesh, P("dp"))
        efs = {k: jax.device_put(v, ef_sh)
               for k, v in self._efs.items()}
        # publish under the state lock: a watchdog-abandoned step's late
        # result application (gated by _stale under this lock) must never
        # interleave with re-placement of restored weights
        with self._state_lock:
            self.values, self.masters = values, masters
            self.opt_states, self._efs = opt_states, efs

    # -- the compiled program -------------------------------------------------
    def _build(self, n_batch_args):
        # every _build is a fresh jit program (first compile, or a batch-
        # arity change invalidating the old one) — the recompile-storm
        # signal ops dashboards watch (docs/observability.md).  Counted at
        # ENTRY so a watchdog that times out during a long compile sees
        # the counter already moved and grants compile grace
        # (supervisor.run_with_deadline's grace_signal).
        self._build_count += 1
        _telemetry.counter("train_step.recompiles").inc()
        net, loss_fn, opt = self.net, self.loss_fn, self.optimizer
        diff_keys = list(self._diff_keys)
        lr_mults, wd_mults = self._lr_mults, self._wd_mults
        base_wd = opt.wd

        mp_keys = set(self._mp_keys)

        n_loss = self._n_loss_args
        compression = self._compression
        mesh = self.mesh

        def make_lfn(const_vals, key, data_args, loss_args):
            def lfn(dv):
                pm = dict(const_vals)
                pm.update(dv)
                with _random.partitioned_draws(mesh is not None
                                               and mesh.size > 1):
                    out, updates = net._functional_call(pm, key, True,
                                                        data_args)
                if isinstance(out, (tuple, list)):
                    # multi-output nets: the step trains on the FIRST
                    # output only.  That silently drops e.g. an MoE aux
                    # loss unless the net folds it into output[0] (the
                    # loss-in-forward + PassThrough pattern) — warn once
                    # per build so the dropped term is never invisible.
                    from ..gluon.loss import PassThrough
                    if not isinstance(loss_fn, PassThrough):
                        _logger.warning(
                            "CompiledTrainStep: net returned %d outputs; "
                            "training on output[0] and DROPPING the rest "
                            "(an MoE aux loss would be lost — fold extra "
                            "terms into the objective in forward() and "
                            "use gluon.loss.PassThrough)", len(out))
                    out = out[0]
                l = loss_fn(out, *loss_args)
                return jnp.mean(l), updates
            return lfn

        def shard_dspecs(batch):
            return self._data_specs or tuple(P("dp")
                                             for _ in range(len(batch)))

        def shard_fwd_grads(dv, cv, key, b_local):
            """Shared per-shard preamble of the compressed accumulate AND
            apply programs: per-device key fold, forward+grad on the local
            batch shard, loss/BN-updates pmean'd.  Keeping it single-copy
            keeps the two programs numerically in lockstep (the compress-
            once equivalence depends on it)."""
            key = jax.random.fold_in(key, jax.lax.axis_index("dp"))
            dat, lar = b_local[:-n_loss], b_local[-n_loss:]
            with jax.named_scope(_GRAD):
                (loss, updates), grads = jax.value_and_grad(
                    make_lfn(cv, key, dat, lar), has_aux=True)(dv)
            with jax.named_scope(_GRAD_SYNC):
                loss = jax.lax.pmean(loss, "dp")
                updates = {uk: jax.lax.pmean(uv, "dp")
                           for uk, uv in updates.items()}
            return loss, updates, grads

        def compressed_grads(diff_vals, const_vals, efs, key, batch,
                             gacc=None):
            """shard_map over dp: each device takes partial grads on its
            batch shard, quantizes them with its own error feedback, and
            the reduction is a psum of the QUANTIZED values (the EQuARX-
            style in-collective compression the reference could only do on
            the kvstore wire)."""
            from ..contrib.compression import (quantize_2bit_core,
                                               quantize_fp8_core,
                                               quantize_int8_core)

            ndp = mesh.shape["dp"]
            ctype = compression["type"]
            threshold = float(compression.get("threshold", 0.5))
            dspecs = shard_dspecs(batch)

            def per_shard(dv, cv, efs_l, gacc_l, key, *b_local):
                loss, updates, grads = shard_fwd_grads(dv, cv, key, b_local)
                red, new_efs = {}, {}
                for k in diff_keys:
                    g = grads[k].astype(jnp.float32)
                    if gacc is not None:
                        # compress-once-per-update: fold the final
                        # microbatch into the LOCAL accumulated mean; the
                        # quantized psum below is the update's only
                        # collective and only quantization
                        with jax.named_scope(_GRAD_ACCUM):
                            g = g / K + gacc_l[k][0]
                    ef = efs_l[k][0]
                    if ctype == "2bit":
                        deq, new_ef = quantize_2bit_core(g, ef, threshold)
                    elif ctype == "fp8":
                        deq, new_ef = quantize_fp8_core(g, ef)
                    else:
                        deq, new_ef = quantize_int8_core(g, ef)
                    with jax.named_scope(_GRAD_SYNC):
                        red[k] = jax.lax.psum(deq, "dp") / ndp
                    new_efs[k] = new_ef[None]
                return loss, red, new_efs, updates

            gacc_arg = gacc if gacc is not None else \
                {k: jnp.zeros((ndp,) + (1,) * diff_vals[k].ndim,
                              jnp.float32) for k in diff_keys}
            fn = jax.shard_map(
                per_shard, mesh=mesh,
                in_specs=(P(), P(), P("dp"), P("dp"), P()) + tuple(dspecs),
                out_specs=(P(), P(), P("dp"), P()), check_vma=False)
            return fn(diff_vals, const_vals, efs, gacc_arg, key, *batch)

        K = self._accum

        def grads_and_updates(values, key, batch):
            """Shared by the apply and accumulate programs: forward+grad
            over the diff params, plus the BN-stat aux updates applied to
            a copy of `values`."""
            data_args, loss_args = batch[:-n_loss], batch[-n_loss:]
            diff_vals = {k: values[k] for k in diff_keys}
            const_vals = {k: v for k, v in values.items()
                          if k not in set(diff_keys)}
            with jax.named_scope(_GRAD):
                (loss, updates), grads = jax.value_and_grad(
                    make_lfn(const_vals, key, data_args, loss_args),
                    has_aux=True)(diff_vals)
            new_vals = dict(values)
            for k, v in updates.items():
                if k in new_vals:
                    new_vals[k] = v.astype(new_vals[k].dtype)
            return loss, grads, new_vals

        def fn(values, masters, opt_states, efs, gacc, t, lr, key, *batch):
            if compression:
                diff_vals = {k: values[k] for k in diff_keys}
                const_vals = {k: v for k, v in values.items()
                              if k not in set(diff_keys)}
                loss, grads, new_efs, updates = compressed_grads(
                    diff_vals, const_vals, efs, key, batch,
                    gacc=gacc if K > 1 else None)
                aux_vals = dict(values)
                for k, v in updates.items():
                    if k in aux_vals:
                        aux_vals[k] = v.astype(aux_vals[k].dtype)
            else:
                loss, grads, aux_vals = grads_and_updates(values, key, batch)
                new_efs = efs
            if K > 1 and not compression:
                # fold the final microbatch into the accumulated mean
                with jax.named_scope(_GRAD_ACCUM):
                    grads = {k: grads[k].astype(jnp.float32) / K + gacc[k]
                             for k in diff_keys}
                new_gacc = {k: jnp.zeros_like(v) for k, v in gacc.items()}
            elif K > 1:
                # compression already folded gacc inside the shard_map
                new_gacc = {k: jnp.zeros_like(v) for k, v in gacc.items()}
            else:
                new_gacc = gacc
            new_vals = aux_vals  # starts from the BN-stat-updated copy
            new_masters = {}
            new_states = {}
            with jax.named_scope(_OPTIMIZER):
                for k in diff_keys:
                    if k in mp_keys:
                        # update in f32 master space; forward weight is a cast
                        w, s = opt.update_core(
                            masters[k], grads[k].astype(jnp.float32),
                            opt_states[k], lr * lr_mults[k],
                            base_wd * wd_mults[k], t)
                        new_masters[k] = w
                        new_vals[k] = w.astype(values[k].dtype)
                    else:
                        # match the param dtype regardless of path (the K>1
                        # fold and compression accumulate in f32)
                        w, s = opt.update_core(
                            values[k], grads[k].astype(values[k].dtype),
                            opt_states[k], lr * lr_mults[k],
                            base_wd * wd_mults[k], t)
                        new_vals[k] = w.astype(values[k].dtype)
                    new_states[k] = s
            # device-side SDC fingerprint (ISSUE 20, parallel/integrity.py):
            # folded over the POST-UPDATE parameter tree INSIDE the same
            # program that applied it, read back beside the loss — the hot
            # path stays one program, and dp replicas (bit-identical
            # post-AllReduce) must produce the same digest.  Off → a
            # constant uint32(0): same output arity, XLA folds it away
            # (the overhead A/B's baseline arm).
            if _fingerprint_on():
                from .integrity import device_fingerprint
                with jax.named_scope(_FINGERPRINT):
                    fp = device_fingerprint(new_vals)
            else:
                fp = jnp.uint32(0)
            return (new_vals, new_masters, new_states, new_efs, new_gacc,
                    loss, fp)

        def accum_fn(values, gacc, key, *batch):
            """Microbatch accumulate: grads/K into the f32 buffers, BN-stat
            aux updates applied, NO optimizer step."""
            loss, grads, new_vals = grads_and_updates(values, key, batch)
            with jax.named_scope(_GRAD_ACCUM):
                new_gacc = {k: gacc[k] + grads[k].astype(jnp.float32) / K
                            for k in diff_keys}
            return new_vals, new_gacc, loss

        def compressed_accum_fn(values, gacc, key, *batch):
            """Microbatch accumulate under compression: per-shard LOCAL
            grads/K into dp-sharded (ndp, ...) buffers — NO collective and
            NO quantization here; both happen exactly once in the apply
            step (compress-once-per-update).  BN aux updates are pmean'd
            and applied every microbatch as usual."""
            diff_vals = {k: values[k] for k in diff_keys}
            const_vals = {k: v for k, v in values.items()
                          if k not in set(diff_keys)}
            dspecs = shard_dspecs(batch)

            def per_shard(dv, cv, gacc_l, key, *b_local):
                loss, updates, grads = shard_fwd_grads(dv, cv, key, b_local)
                with jax.named_scope(_GRAD_ACCUM):
                    new_gacc = {
                        k: gacc_l[k]
                        + grads[k].astype(jnp.float32)[None] / K
                        for k in diff_keys}
                return loss, new_gacc, updates

            sm = jax.shard_map(
                per_shard, mesh=mesh,
                in_specs=(P(), P(), P("dp"), P()) + tuple(dspecs),
                out_specs=(P(), P("dp"), P()), check_vma=False)
            loss, new_gacc, updates = sm(diff_vals, const_vals, gacc, key,
                                         *batch)
            new_vals = dict(values)
            for k, v in updates.items():
                if k in new_vals:
                    new_vals[k] = v.astype(new_vals[k].dtype)
            return new_vals, new_gacc, loss

        def alloc_gacc(shardings=None):
            if K <= 1 or self._gacc is not None:
                return
            lead = (mesh.shape["dp"],) if (compression and mesh is not None) \
                else ()
            shapes = {k: lead + self.values[k].shape
                      for k in self._diff_keys}
            # tpumx-lint: disable=concurrency -- first-build-only init:
            # runs before any step result exists that a restore could
            # race, and fresh zeros are the correct post-restore value
            self._gacc = jax.jit(
                lambda: {k: jnp.zeros(s, jnp.float32)
                         for k, s in shapes.items()},
                **({"out_shardings": shardings} if shardings else {}))()

        # on a trace's `XLA Modules` line: jit_tpumx_train_step, ..._accum_step
        fn.__name__ = "tpumx_train_step"
        accum_fn.__name__ = compressed_accum_fn.__name__ = "tpumx_accum_step"
        donate = (0, 1, 2, 3, 4) if self._donate else ()
        if self.mesh is None:
            self._jitted = jax.jit(fn, donate_argnums=donate)
            if K > 1:
                self._accum_jit = jax.jit(
                    accum_fn, donate_argnums=(0, 1) if self._donate else ())
                alloc_gacc()
            return
        repl = sharding_for(self.mesh, P())
        dspecs = self._data_specs or tuple(P("dp") for _ in range(n_batch_args))
        batch_sh = tuple(sharding_for(self.mesh, s) for s in dspecs)
        master_sh = {k: sharding_for(self.mesh, self._specs[k])
                     for k in self._mp_keys}
        efs_sh = {k: sharding_for(self.mesh, P("dp")) for k in self._efs}
        # under compression the accumulation buffers are per-device LOCAL
        # rows, dp-sharded on their leading axis (like the error feedback)
        gacc_spec = P("dp") if compression else None
        gacc_sh = {k: sharding_for(self.mesh,
                                   gacc_spec or self._specs[k])
                   for k in (self._diff_keys if K > 1 else [])}
        in_sh = (self._value_shardings(), master_sh, self._state_shardings(),
                 efs_sh, gacc_sh, repl, repl, repl) + batch_sh
        out_sh = (self._value_shardings(), master_sh, self._state_shardings(),
                  efs_sh, gacc_sh, repl, repl)
        self._jitted = jax.jit(
            fn, in_shardings=in_sh, out_shardings=out_sh,
            donate_argnums=donate)
        if K > 1:
            self._accum_jit = jax.jit(
                compressed_accum_fn if compression else accum_fn,
                in_shardings=(self._value_shardings(), gacc_sh, repl)
                + batch_sh,
                out_shardings=(self._value_shardings(), gacc_sh, repl),
                donate_argnums=(0, 1) if self._donate else ())
            alloc_gacc(gacc_sh)

    @property
    def recompiles(self):
        """How many jit programs THIS instance has built (the global
        recompile-storm counter is `train_step.recompiles` in telemetry)."""
        return self._build_count

    def fingerprint(self):
        """The last committed step's post-update parameter fingerprint as
        a Python int (ISSUE 20, parallel/integrity.py), or None before
        the first applied update or with ``TPUMX_FINGERPRINT=0``.

        The uint32 digest was computed INSIDE the fused step and committed
        as a lazy device scalar; the conversion here rides on a program
        that already completed (the sentinel read its loss), so this is a
        cheap transfer at the step boundary — the IntegrityMonitor's
        ``fingerprint_fn`` seam."""
        if not _fingerprint_on():
            return None
        fp = self._last_fp
        if fp is None:
            return None
        # tpumx-lint: disable=sync-point -- the digest readback rides a
        # step whose loss was already read (program complete); called at
        # the K-step vote cadence, never inside the dispatch path
        return int(jax.device_get(fp))

    def step(self, *batch, lr=None, deadline=None, compile_grace=120.0):
        """Run one step; batch = (*data_args, label) as NDArray/array.

        ``deadline=`` arms the hung-step watchdog (tpu_mx/supervisor.py):
        the dispatch AND the loss readback run on a daemon thread joined
        with the deadline, so a stalled collective — which jax's async
        dispatch would otherwise surface as an eternal hang at the first
        device read — raises a catchable ``WatchdogTimeout``
        (a ``WorkerFailure``) instead.  The deadline is recompile-aware:
        when a jit (re)build starts during the step, the watchdog grants
        ``compile_grace`` extra seconds once rather than killing a
        legitimate compile."""
        if deadline is not None:
            from ..supervisor import run_with_deadline
            gen0 = self._generation

            def call():
                loss = self._step(batch, lr, expect_gen=gen0)
                # force the async dispatch to completion INSIDE the
                # watchdog thread — a hung collective parks here
                with _tracing.phase("loss_readback"):
                    jax.block_until_ready(loss._data)
                return loss

            count0 = self._build_count
            return run_with_deadline(
                call, deadline, name="train_step",
                grace=compile_grace or 0.0,
                grace_signal=lambda: self._build_count - count0,
                message=f"train_step hung past its {deadline:.1f}s "
                        "deadline (stalled collective or device); restart "
                        "from the last checkpoint")
        return self._step(batch, lr)

    def _step(self, batch, lr, expect_gen=None):
        if expect_gen is None:
            # capture at entry: un-watchdogged calls too (the supervisor
            # runs THIS method on its watchdog thread) discard their result
            # if a restore supersedes them mid-flight
            expect_gen = self._generation
        # the parent span on the profiler's timeline; the phases tile it in
        # order (tracing.TRAIN_STEP_PHASES), each an annotation there AND a
        # train_step.phase event.  The device side is ONE XLA program, so
        # "dispatch" is its (async) enqueue and "loss_readback" (at the
        # read sites) the block on its result.
        with jax.profiler.StepTraceAnnotation("tpu_mx/train_step",
                                              step_num=self._t + 1):
            return self._step_phases(batch, lr, expect_gen)

    def _step_phases(self, batch, lr, expect_gen):
        from ..contrib import chaos as _chaos
        with _tracing.phase("data_wait") as waited:
            # chaos straggler injection (ISSUE 18) lands INSIDE data_wait:
            # outside every phase it would be invisible to the cross-rank
            # attribution it exists to exercise (parallel/fleet_obs.py)
            _chaos.maybe_slow_worker()
            # None batch args pass through (optional inputs like
            # valid_length: no leaves in the jitted signature).  Non-NDArray
            # operands stay RAW (numpy/python): the jit boundary commits
            # them on the C++ fast path — an eager jnp.asarray here costs a
            # dispatch per operand per step (hot-path-purity flags it)
            raw = tuple(b._data if isinstance(b, NDArray) else b
                        for b in batch)
            # per-shape-signature compile accounting (ISSUE 14): the first
            # step at a new operand signature pays jax's retrace + XLA
            # compile inside the jit call — counted under the signature
            # label, with the dispatch phase's seconds as the compile cost
            sig = _shape_signature(raw)
            fresh_sig = sig not in self._seen_signatures
            if fresh_sig:
                self._seen_signatures.add(sig)
                _telemetry.counter("train_step.compiles", signature=sig).inc()
            # microbatch: accumulate grads, no optimizer application
            micro = self._accum > 1 and self._micro < self._accum - 1
            t_next = self._t + 1
            if lr is None and not micro:
                sched = self.optimizer.lr_scheduler
                lr = sched(t_next) if sched else self.optimizer.lr
        if self._jitted is None:
            with _tracing.phase("recompile"):
                self._build(len(raw))
                self.place()
        with _tracing.phase("rng_key"):
            key = _random.take_key()
        with _tracing.phase("dispatch") as dispatched:
            if micro:
                new_vals, new_gacc, loss = self._accum_jit(
                    self.values, self._gacc, key, *raw)
            else:
                # np scalars: the jit boundary places them (no eager commit)
                (new_vals, new_masters, new_states, new_efs, new_gacc,
                 loss, fp) = self._jitted(
                    self.values, self.masters, self.opt_states, self._efs,
                    self._gacc if self._accum > 1 else {},
                    np.float32(t_next), np.float32(lr), key, *raw)
        if fresh_sig:
            _telemetry.histogram(
                "train_step.compile_seconds", signature=sig).observe(
                    dispatched.seconds)
        # the host-side commit of the program's result (the new train
        # state becoming THE state, under the zombie-step lock)
        with _tracing.phase("optimizer_update"):
            with self._state_lock:
                if self._stale(expect_gen):
                    return NDArray(loss)
                if micro:
                    self.values, self._gacc = new_vals, new_gacc
                    self._micro += 1
                else:
                    (self.values, self.masters, self.opt_states,
                     self._efs) = new_vals, new_masters, new_states, new_efs
                    # the device fingerprint commits WITH the state it
                    # digests (a lazy device scalar until fingerprint())
                    self._last_fp = fp
                    self._t = t_next
                    self._micro = 0
                    if self._accum > 1:
                        self._gacc = new_gacc
            # chaos SDC injection (ISSUE 20): flip one bit of the COMMITTED
            # state, after this step's fingerprint — silent until the NEXT
            # published fingerprint disagrees (the promised ≤ K steps)
            bit = None if micro else _chaos.maybe_bitflip()
            if bit is not None:
                self._apply_bitflip(bit)
        with _tracing.phase("record"):
            self._record_step(raw, waited.t0)
        return NDArray(loss)

    def _apply_bitflip(self, bit):
        """Flip bit ``bit`` of element 0 of the first diff param — the
        chaos ``bitflip_param_at_step`` / ``bitflip_grad_rank`` payload.
        Mixed-precision keys flip the f32 MASTER: the forward weight is
        recast from it on every update, so flipping only the cast copy
        would silently self-heal one step later."""
        key = self._diff_keys[0]
        with self._state_lock:
            use_master = key in self._mp_keys
            tree = self.masters if use_master else self.values
            # tpumx-lint: disable=sync-point,hot-path-purity -- chaos
            # fault INJECTION (test-only, armed by TPUMX_CHAOS): the
            # whole point is to corrupt committed state; the roundtrip
            # fires at most once per run and never in production
            host = np.array(jax.device_get(tree[key]))
            flat = host.reshape(-1)
            view = flat.view(np.uint32) if flat.dtype == np.float32 \
                else flat.view(np.uint8)
            nbits = view.dtype.itemsize * 8
            view[0] ^= view.dtype.type(1 << (int(bit) % nbits))
            if self.mesh is not None:
                tree[key] = jax.device_put(
                    host, sharding_for(self.mesh, self._specs[key]))
            else:
                tree[key] = jnp.asarray(host)
        _logger.debug("train_step: chaos bit-flip applied to %r bit %d "
                      "(%s)", key, int(bit),
                      "master" if use_master else "value")

    def _stale(self, expect_gen):
        """True when the train state was restored (generation bumped) while
        this step ran past its watchdog deadline on an abandoned thread —
        the stale result must be DISCARDED, not applied over the restored
        weights (call with _state_lock held)."""
        if expect_gen is not None and self._generation != expect_gen:
            _logger.warning(
                "train_step: discarding a stale step result — the train "
                "state was restored while this step ran past its watchdog "
                "deadline")
            return True
        return False

    @staticmethod
    def _record_step(raw, t_start):
        """Per-step telemetry: host-side dispatch latency (jax dispatch is
        async, so this is queue latency — steady-state it converges to the
        device step time because the dispatch queue applies backpressure),
        step count, and the examples/sec gauge from the batch leading dim."""
        dt = time.perf_counter() - t_start
        _telemetry.counter("train_step.steps").inc()
        _telemetry.histogram("train_step.seconds").observe(dt)
        n = next((b.shape[0] for b in raw
                  if b is not None and getattr(b, "ndim", 0)), None)
        if n and dt > 0:
            _telemetry.gauge("train_step.examples_per_sec").set(n / dt)

    def sync_to_net(self):
        """Write device weights back into the Gluon parameters (for eval,
        checkpointing through net.save_parameters, etc.)."""
        for k, p in self._params.items():
            p._data._rebind(self.values[k])

    def sync_from_net(self):
        """Inverse of `sync_to_net`: reload the device weights from the
        Gluon parameters — the rollback path after `elastic.auto_resume`
        restored `net` from a checkpoint, without rebuilding the jit
        program.  Values are COPIED (donation would otherwise delete the
        params' live buffers on the next step), masters re-derived from
        the restored values, and in-flight gradient accumulation dropped
        (partial grads against the old weights are invalid).  Optimizer
        state is deliberately kept: the Gluon net carries none — restore
        it via `load_state_dict`/`load_checkpoint` when exactness
        matters."""
        values = {k: jnp.copy(p.data()._data)
                  for k, p in self._params.items()}
        masters = {k: values[k].astype(jnp.float32)
                   for k in self._mp_keys}
        if self.mesh is not None:
            vs = self._value_shardings()
            values = {k: jax.device_put(v, vs[k])
                      for k, v in values.items()}
            masters = {k: jax.device_put(v, vs[k])
                       for k, v in masters.items()}
        with self._state_lock:
            self._generation += 1  # invalidate any watchdog-abandoned step
            self.values = values
            self.masters = masters
            self._reset_accumulation()

    def aot_compiled(self, *batch):
        """Lower + compile the step WITHOUT executing it and return the
        jax Compiled object (for cost_analysis / memory_analysis / HLO
        text).  Shares the jit/persistent compile cache with step(), so
        after a step() has run this is cache-hit cheap.  benchmark/run.py
        reads the step's memory_analysis() through it."""
        raw = tuple(b._data if isinstance(b, NDArray)
                    else (None if b is None else jnp.asarray(b))
                    for b in batch)
        if self._jitted is None:
            self._build(len(raw))
            self.place()
        # a constant key: lowering only needs the shape/dtype, and an
        # introspection helper must not advance the global RNG stream
        # (that would silently change later dropout masks)
        # tpumx-lint: disable=determinism -- lowering only needs shape/dtype
        key = jax.random.PRNGKey(0)
        gacc = self._gacc if self._accum > 1 else {}
        lowered = self._jitted.lower(
            self.values, self.masters, self.opt_states, self._efs, gacc,
            jnp.asarray(float(self._t or 1), jnp.float32),
            jnp.asarray(self.optimizer.lr or 0.1, jnp.float32),
            key, *raw)
        return lowered.compile()

    def state_dict(self):
        """Snapshot of the train state.  Leaves are COPIED: with buffer
        donation active (the default), later step() calls delete the live
        arrays — a snapshot that aliased them would die with them."""
        copy = functools.partial(jax.tree_util.tree_map, jnp.copy)
        sd = {"values": copy(self.values), "masters": copy(self.masters),
              "opt_states": copy(self.opt_states), "t": self._t}
        if self._efs:
            sd["efs"] = copy(self._efs)
        return sd

    def load_state_dict(self, sd):
        """Restore a `state_dict` snapshot.  Host-numpy leaves (a snapshot
        that round-tripped through a resume capsule's pickled sidecar,
        tpu_mx/resume.py) are accepted and placed back on device —
        deterministic resume depends on this path restoring t, optimizer
        state and weights bit-exactly."""
        def dev(tree):
            return jax.tree_util.tree_map(
                lambda x: jnp.asarray(x) if isinstance(x, np.ndarray)
                else x, tree)

        with self._state_lock:
            self._generation += 1  # invalidate any watchdog-abandoned step
            self.values = dev(sd["values"])
            self.masters = dev(sd.get("masters", {}))
            self.opt_states = dev(sd["opt_states"])
            efs = dev(sd.get("efs") or {})
            if self._efs and efs and all(k in efs and efs[k].shape == v.shape
                                         for k, v in self._efs.items()):
                self._efs = efs  # same dp topology; else keep fresh zeros
            self._t = int(sd["t"])
            self._reset_accumulation()
        if self.mesh is not None:
            self.place()  # host-restored leaves need their mesh shardings

    def _reset_accumulation(self):
        """Discard in-flight microbatch state: restored weights invalidate
        partial gradients accumulated against the previous weights (the
        silent-corruption alternative is worse than dropping ≤K-1
        microbatches).  Caller MUST hold _state_lock — every call site
        does, and tpumx-lint's interprocedural concurrency pass PROVES it
        (lock context propagates through the call graph since ISSUE 10;
        the suppressions that used to sit here are gone because a new
        lock-free caller would be a lint error, not a silent race)."""
        self._micro = 0
        if self._gacc is not None:
            self._gacc = jax.tree_util.tree_map(
                lambda a: jnp.zeros_like(a), self._gacc)

    # -- sharded checkpointing (SURVEY §5.4) ----------------------------------
    def _abstract_state(self):
        """ShapeDtypeStructs of the full train state with CURRENT mesh
        shardings — the restore target, so a checkpoint saved on one mesh
        (e.g. dp=2×tp=2) reshards onto this one (e.g. dp=4) at load."""
        def leaf(spec):
            def f(v):
                sh = sharding_for(self.mesh, spec)
                if sh is None:
                    return jax.ShapeDtypeStruct(jnp.shape(v),
                                                jnp.result_type(v))
                return jax.ShapeDtypeStruct(jnp.shape(v), jnp.result_type(v),
                                            sharding=sh)
            return f

        return {
            "values": {k: leaf(self._specs[k])(v)
                       for k, v in self.values.items()},
            "masters": {k: leaf(self._specs[k])(v)
                        for k, v in self.masters.items()},
            "opt_states": {
                k: jax.tree_util.tree_map(leaf(self._specs[k]),
                                          self.opt_states[k])
                for k in self._diff_keys},
            # efs (compression error feedback) is deliberately NOT part of
            # the checkpoint: it is per-DEVICE residual state whose global
            # shape bakes in the dp size, which would break the
            # reshard-on-restore contract below.  Losing it on restore
            # costs one transient quantization error — acceptable.
            "t": jax.ShapeDtypeStruct((), jnp.int32),
        }

    @property
    def _checkpointer(self):
        """One orbax StandardCheckpointer per step instance — its async
        machinery (background tensorstore commit threads) is reused across
        saves instead of being rebuilt per call."""
        if getattr(self, "_ckpt", None) is None:
            import orbax.checkpoint as ocp
            self._ckpt = ocp.StandardCheckpointer()
        return self._ckpt

    def save_checkpoint(self, path, block=True):
        """Sharded checkpoint: every host writes only its own parameter
        shards, in parallel, via orbax/tensorstore — no gather through host
        memory (the reference gathered to rank 0 and wrote one file;
        REF:python/mxnet/module/module.py save_checkpoint).

        block=False returns as soon as the device→host copy is done (orbax
        async save guarantees source buffers are copied out before save()
        returns), so training continues — and may donate/overwrite the live
        buffers — while tensorstore commits in the background.  Call
        `wait_for_checkpoint()` (or any later save/load, which waits
        internally) before reading the files.

        Durability (docs/robustness.md): after the orbax commit completes, a
        `<path>.commit.json` marker is written atomically NEXT TO the
        checkpoint directory (never inside it — orbax owns that layout).
        The marker is the verified-commit point: a preemption between
        tensorstore's partial writes and the marker leaves a directory that
        `load_checkpoint` treats as suspect, not as the newest state.  For
        async saves the marker lands in `wait_for_checkpoint()`."""
        state = dict(self.state_dict())
        state.pop("efs", None)  # per-device; see _abstract_state
        state["t"] = jnp.asarray(state["t"], jnp.int32)
        ck = self._checkpointer
        if getattr(self, "_pending_commit", None) is not None:
            # an earlier async save is still marker-less: finish and stamp
            # it before its slot is overwritten, or a fully-committed
            # checkpoint would stay permanently unverified
            ck.wait_until_finished()
            self._write_commit_marker()
        ap = os.path.abspath(str(path))
        ck.save(ap, state, force=True)
        self._pending_commit = (ap, int(self._t))  # t of the SAVED state
        if block:
            ck.wait_until_finished()
            self._write_commit_marker()

    @staticmethod
    def commit_marker_path(path):
        return os.path.abspath(str(path)) + ".commit.json"

    def _write_commit_marker(self):
        """Stamp the verified-commit marker for the save that just finished
        (multi-host: every host replace()s the same content onto a shared
        filesystem — idempotent and atomic either way)."""
        import json
        import time
        pending = getattr(self, "_pending_commit", None)
        if pending is None:
            return
        self._pending_commit = None
        p, saved_t = pending
        from ..checkpoint import atomic_write
        with atomic_write(self.commit_marker_path(p), "w") as f:
            f.write(json.dumps({"format": "tpu_mx-orbax-commit-v1",
                                "path": os.path.basename(p),
                                "t": saved_t,
                                "wall_time": time.time()}))

    def wait_for_checkpoint(self):
        """Block until any in-flight async save has committed to disk, then
        stamp its verified-commit marker."""
        if getattr(self, "_ckpt", None) is not None:
            self._ckpt.wait_until_finished()
        self._write_commit_marker()

    def load_checkpoint(self, path, fallback_paths=()):
        """Restore a sharded checkpoint onto THIS step's mesh — the saved
        mesh/layout may differ (dp=2×tp=2 → dp=4 etc.); every host reads
        only the shards its devices need.

        Robustness: a path without its `.commit.json` marker (interrupted
        save) is skipped when `fallback_paths` remain — pass older
        checkpoints newest-first to get elastic-style fall-back.  A
        marker-less path is still *attempted* as legacy (with a warning)
        when it is the last resort; restore errors also advance to the next
        fallback.  Raises MXNetError when no candidate restores."""
        from ..base import MXNetError
        ck = self._checkpointer
        ck.wait_until_finished()  # an async save may still be committing
        self._write_commit_marker()
        logger = logging.getLogger(__name__)
        candidates = [os.path.abspath(str(p))
                      for p in (path, *tuple(fallback_paths))]
        errors = []
        for i, ap in enumerate(candidates):
            last_resort = i == len(candidates) - 1
            if not os.path.exists(ap):
                errors.append(f"{ap}: does not exist")
                continue
            if not os.path.exists(self.commit_marker_path(ap)):
                if not last_resort:
                    logger.warning(
                        "checkpoint %s has no commit marker (interrupted "
                        "or pre-durability save): falling back", ap)
                    errors.append(f"{ap}: no commit marker")
                    continue
                logger.warning(
                    "checkpoint %s has no commit marker: attempting "
                    "unverified restore (legacy/last resort)", ap)
            try:
                state = ck.restore(ap, self._abstract_state())
            except Exception as e:
                logger.warning("checkpoint %s failed to restore (%s: %s)%s",
                               ap, type(e).__name__, e,
                               "" if last_resort else " — falling back")
                errors.append(f"{ap}: {type(e).__name__}: {e}")
                continue
            with self._state_lock:
                self._generation += 1  # invalidate abandoned steps
                self.values = state["values"]
                self.masters = state.get("masters", {})
                self.opt_states = state["opt_states"]
                self._t = int(state["t"])
                self._reset_accumulation()
            return ap
        raise MXNetError("load_checkpoint: no restorable checkpoint among "
                         + "; ".join(errors))


def fsdp_rules(params, axis="dp", min_size=1024, axis_size=None):
    """ZeRO-3/FSDP-style parameter sharding rules (SURVEY §2.3; the
    reference had no analog — its params were replicated per GPU with
    KVStore aggregation).

    Returns [(regex, PartitionSpec)] sharding every parameter whose size
    is >= min_size along its largest axis DIVISIBLE by `axis_size` (pass
    the mesh's dp size; with axis_size=None any largest axis is taken and
    jit will reject non-divisible dims loudly).  Params with no divisible
    axis stay replicated.  Under the compiled step this is textbook
    GSPMD-FSDP: XLA all-gathers each weight just before its matmul and
    reduce-scatters its gradient — per-device parameter+optimizer memory
    drops ~axis-fold, at the cost of those collectives (they overlap with
    compute on ICI)."""
    rules = []
    for name, v in params.items():
        shape = tuple(v.shape)
        if not shape or int(np.prod(shape)) < min_size:
            continue
        dims = sorted(range(len(shape)), key=lambda d: -shape[d])
        dim = None
        for d in dims:  # largest divisible axis; ties -> earliest
            if axis_size is None or shape[d] % axis_size == 0:
                dim = d
                break
        if dim is None:
            continue  # no divisible axis: leave replicated
        spec = [None] * len(shape)
        spec[dim] = axis
        rules.append((f"^{re.escape(name)}$", P(*spec)))
    return rules
