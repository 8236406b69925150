"""Gluon Block / HybridBlock (REF:python/mxnet/gluon/block.py).

Capabilities kept: define-by-run `Block`, `HybridBlock.hybridize()` graph
capture, deferred shape init, parameter collection/scoping, save/load,
`export()`.  TPU-native design (SURVEY §7.1): hybridize wraps the block's
*functionalized* forward in `jax.jit` — parameters enter as a traced pytree
(via the Parameter substitution scope), RNG enters as an explicit key, and
BatchNorm-style aux mutations leave as an updates pytree (`has_aux` vjp).
That replaces the reference's CachedOp + NNVM passes + static memory planning:
XLA does the fusion/planning; buffer donation plays the role of
`static_alloc`.
"""
from __future__ import annotations

import itertools
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np

from .. import autograd
from .. import random as _random
from ..base import MXNetError
from ..context import current_context
from ..ndarray import NDArray, array
from ..ndarray import ops as F
from .parameter import Parameter, ParameterDict, param_substitution

__all__ = ["Block", "HybridBlock", "SymbolBlock", "nn"]

_NAME_COUNTER = {}
_NAME_LOCK = threading.Lock()


def _gen_prefix(hint):
    with _NAME_LOCK:
        idx = _NAME_COUNTER.get(hint, 0)
        _NAME_COUNTER[hint] = idx + 1
    return f"{hint}{idx}_"


class _BlockScope:
    """Placeholder for reference name_scope() compatibility."""

    def __init__(self, block):
        self._block = block

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class HookHandle:
    """Removable handle for a registered hook (reference: gluon.utils
    HookHandle)."""

    def __init__(self, hooks_list, hook):
        self._hooks_list = hooks_list
        self._hook = hook

    def detach(self):
        if self._hooks_list is not None and self._hook in self._hooks_list:
            self._hooks_list.remove(self._hook)
        self._hooks_list = None

    remove = detach

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.detach()


class Block:
    """Define-by-run module. Subclasses implement `forward(self, *args)`."""

    def __init__(self, prefix=None, params=None):
        hint = re.sub(r"(?<!^)(?=[A-Z])", "", type(self).__name__).lower()
        self._prefix = prefix if prefix is not None else _gen_prefix(hint)
        self._params = ParameterDict(self._prefix, shared=params)
        self._children = {}
        self._reg_params = {}
        self._scope = _BlockScope(self)

    # -- attribute registration ----------------------------------------------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            self.__dict__.setdefault("_children", {})[name] = value
        elif isinstance(value, Parameter):
            self.__dict__.setdefault("_reg_params", {})[name] = value
        super().__setattr__(name, value)

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._prefix.rstrip("_")

    def name_scope(self):
        return self._scope

    @property
    def params(self):
        return self._params

    def collect_params(self, select=None):
        """All params of self + descendants as one ParameterDict (full names)."""
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(dict(self._params.items()))
        else:
            pat = re.compile(select)
            ret.update({k: v for k, v in self._params.items() if pat.match(k)})
        for child in self._children.values():
            ret.update(dict(child.collect_params(select).items()))
        return ret

    def register_child(self, block, name=None):
        self._children[name or str(len(self._children))] = block

    def register_forward_hook(self, hook):
        hooks = self.__dict__.setdefault("_fwd_hooks", [])
        hooks.append(hook)
        return HookHandle(hooks, hook)

    def register_forward_pre_hook(self, hook):
        hooks = self.__dict__.setdefault("_fwd_pre_hooks", [])
        hooks.append(hook)
        return HookHandle(hooks, hook)

    def apply_fn(self, fn):
        """Reference Block.apply: run fn on self and all children."""
        for child in self._children.values():
            child.apply_fn(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        self.collect_params().initialize(init=init, ctx=ctx,
                                         force_reinit=force_reinit)
        return self

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for p in self._params.values():
            p.cast(dtype)

    # -- save / load (attribute-path naming, reference save_parameters) ------
    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + n: p for n, p in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def save_parameters(self, filename):
        params = self._collect_params_with_prefix()
        payload = {k: p.data() for k, p in params.items() if p._data is not None}
        from ..ndarray import save as nd_save
        nd_save(filename, payload)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False):
        from ..ndarray import load as nd_load
        loaded = nd_load(filename)
        params = self._collect_params_with_prefix()
        for k, p in params.items():
            if k in loaded:
                p.set_data(loaded[k])
            elif not allow_missing:
                raise MXNetError(f"Parameter {k} missing in {filename}")
        if not ignore_extra:
            extra = set(loaded) - set(params)
            if extra:
                raise MXNetError(f"Extra params in file: {sorted(extra)}")

    save_params = save_parameters
    load_params = load_parameters

    # -- call ----------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        for hook in self.__dict__.get("_fwd_pre_hooks", ()):
            hook(self, args)
        out = self.forward(*args, **kwargs)
        for hook in self.__dict__.get("_fwd_hooks", ()):
            hook(self, args, out)
        return out

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def summary(self, *inputs):
        out = self(*inputs)
        lines = [f"{type(self).__name__}: params="
                 f"{sum(int(np.prod(p.shape)) for p in self.collect_params().values() if p.shape)}"]
        return "\n".join(lines)

    def __repr__(self):
        s = f"{type(self).__name__}(\n"
        for name, child in self._children.items():
            child_repr = repr(child).replace("\n", "\n  ")
            s += f"  ({name}): {child_repr}\n"
        return s + ")"


class HybridBlock(Block):
    """Block whose forward is functionally traceable → `hybridize()` compiles
    it with XLA (the CachedOp analog, REF:src/imperative/cached_op.cc)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._flags = {}
        self._cached_fns = {}          # (train, arg_struct) -> jitted fn
        self._param_order = None
        self._last_input_avals = None  # recorded for export()
        self._remat = False
        self._remat_policy = None

    def remat(self, active=True, policy=None):
        """Gradient rematerialization for this block's forward segment.

        When this block runs inside an enclosing compiled trace (a
        hybridized parent or `CompiledTrainStep`), its forward is wrapped
        in `jax.checkpoint`: activations inside the segment are recomputed
        during backward instead of stored, trading ~1 extra forward of
        FLOPs for the segment's activation HBM (SURVEY §7.1 — the TPU
        answer to big-batch training; no reference analog, MXNet 1.x
        mirrored memory via `mirror_stage` graph attrs).  Mark the
        repeated unit (e.g. each transformer layer), not the whole model.
        `policy` is forwarded to `jax.checkpoint` (a
        `jax.checkpoint_policies` entry) to keep select intermediates.
        Eager (non-traced) execution ignores the flag.  Returns self."""
        self._remat = bool(active)
        self._remat_policy = policy
        return self

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  inline_limit=None, **kwargs):
        self._active = active
        self._flags = dict(static_alloc=static_alloc, static_shape=static_shape)
        self._cached_fns = {}
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)

    def infer_shape(self, *args):
        """Finalize deferred-init parameter shapes from example inputs
        (REF:python/mxnet/gluon/block.py HybridBlock.infer_shape).

        Leaf layers override this with closed-form rules (Dense, Conv,
        RNN cells, …).  The base implementation covers the two remaining
        cases:

        - a container whose CHILDREN hold the deferred params: one
          predict-mode forward over the example inputs finalizes every
          child (TPU-native divergence: the reference runs symbolic
          inference over the NNVM graph; here the eager forward IS the
          shape-inference pass — each layer's own infer_shape fires as
          the data reaches it);
        - a custom block with its OWN deferred params and no override:
          an explicit error (arbitrary Python forwards have no
          closed-form shape rule; the silent no-op this used to be
          surfaced later as a confusing uninitialized-parameter error).
        """
        own_incomplete = [p.name for p in self._reg_params.values()
                          if p._data is None and p._shape_incomplete()]
        if own_incomplete:
            raise MXNetError(
                f"{type(self).__name__} has deferred-shape parameters "
                f"{own_incomplete} but no infer_shape override; declare "
                "full shapes (in_units/in_channels/...) or override "
                "infer_shape(self, *args) with the block's shape rule")
        with autograd.predict_mode():
            self.forward(*args)

    def _uninitialized(self):
        return [p for p in self.collect_params().values() if p._data is None]

    def finalize_shapes(self, *args):
        """Finalize any deferred-shape parameters with ONE predict-mode
        forward over example inputs — and no-op (no device work) when the
        model declares every dim.  The public cold-start helper for
        benches/tools: `net.finalize_shapes(tiny_batch)` replaces the
        unconditional eager forward that costs an extra compile+transfer
        round-trip per model build.  Returns self."""
        if self._uninitialized():
            with autograd.predict_mode():
                self(*args)
        return self

    # -- the functional core --------------------------------------------------
    def _functional_call(self, param_map, key, train, raw_args):
        """Pure: (params, key, *inputs) -> (outputs, aux_updates)."""
        scope = autograd.train_mode() if train else autograd.predict_mode()
        with param_substitution(param_map) as updates, \
                _random.key_scope(key), scope:
            out = self.forward(*raw_args)
        return out, updates

    def _remat_segment(self, args, kwargs):
        """Run this block's forward as a `jax.checkpoint` segment inside
        the enclosing functional trace (see `remat()`).  The segment is a
        pure function of (own params, rng key, positional array args);
        None/scalar args, kwargs, and any unused outer-scope values ride
        in the closure (jax.checkpoint differentiates closed-over tracers
        correctly — they just stay checkpoint residuals).  Aux updates
        (BatchNorm stats) recorded inside the segment are merged into the
        enclosing updates dict so they still reach the caller."""
        from .parameter import _active_substitution
        mapping, outer_updates = _active_substitution()
        own = {k: mapping[k] for k in self.collect_params() if k in mapping}
        key = _random.take_key()
        arr_idx = [i for i, a in enumerate(args)
                   if isinstance(a, (NDArray, jnp.ndarray, np.ndarray))]
        arrs = [args[i]._data if isinstance(args[i], NDArray) else args[i]
                for i in arr_idx]

        def seg(own_map, key, *arrs):
            m = dict(mapping)
            m.update(own_map)
            full = list(args)
            for i, a in zip(arr_idx, arrs):
                full[i] = a
            with param_substitution(m) as upd, _random.key_scope(key):
                out = Block.__call__(self, *full, **kwargs)
            return out, upd

        out, upd = jax.checkpoint(seg, policy=self._remat_policy)(
            own, key, *arrs)
        outer_updates.update(upd)
        return out

    def _ensure_cached(self, train):
        if train not in self._cached_fns:
            def pure_fn(param_map, key, *raw_args):
                return self._functional_call(param_map, key, train, raw_args)

            self._cached_fns[train] = jax.jit(pure_fn)
        return self._cached_fns[train]

    def __call__(self, *args, **kwargs):
        from .parameter import _active_substitution
        if _active_substitution() is None and not kwargs and args and \
                all(isinstance(a, (NDArray, jnp.ndarray, np.ndarray))
                    for a in args):
            # remember concrete input shapes for export() (works even if the
            # call below takes the eager path)
            self._last_input_avals = [
                jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a))
                for a in args]
        inside = _active_substitution() is not None
        if inside and self._remat and not self._uninitialized():
            return self._remat_segment(args, kwargs)
        if not self._active or inside:
            # plain path: not hybridized, OR already inside an enclosing
            # block's functional trace (children trace inline — one compiled
            # graph per outermost hybridized block, like CachedOp inlining)
            return super().__call__(*args, **kwargs)
        if self._uninitialized() or kwargs:
            # first call: eager to resolve deferred shapes (reference: the
            # first hybrid call performs the trace/shape-inference).
            # kwargs also take the eager path — they aren't part of the
            # cached-signature key, so compiling with them would silently
            # bake in defaults
            return super().__call__(*args, **kwargs)
        return self._call_cached(*args)

    def _call_cached(self, *args):
        params = {k: v for k, v in self.collect_params().items()
                  if v._data is not None}
        param_map = {k: p.data()._data for k, p in params.items()}
        raw_args = [a._data if isinstance(a, NDArray) else a for a in args]
        train = autograd.is_training() or autograd.is_recording()
        fn = self._ensure_cached(train)
        key = _random.take_key()

        nd_args = [a for a in args if isinstance(a, NDArray)]
        diff_params = {k: p for k, p in params.items()
                       if p.grad_req != "null" and
                       jnp.issubdtype(p.data().dtype, jnp.floating)}
        record = autograd._needs_tape(
            [p.data() for p in diff_params.values()] + nd_args)

        if record:
            const_map = {k: param_map[k] for k in param_map if k not in diff_params}
            diff_keys = list(diff_params)
            diff_arg_idx = [i for i, a in enumerate(args)
                            if isinstance(a, NDArray)
                            and jnp.issubdtype(a.dtype, jnp.floating)]

            def closed(diff_vals, *diff_raw):
                pm = dict(const_map)
                pm.update(dict(zip(diff_keys, diff_vals)))
                full = list(raw_args)
                for i, d in zip(diff_arg_idx, diff_raw):
                    full[i] = d
                return fn(pm, key, *full)

            out, vjp_fn, updates = jax.vjp(
                closed, [param_map[k] for k in diff_keys],
                *[raw_args[i] for i in diff_arg_idx], has_aux=True)

            multi = isinstance(out, (tuple, list))
            outs_raw = list(out) if multi else [out]
            outs = [NDArray(o) for o in outs_raw]
            tape_inputs = [diff_params[k].data() for k in diff_keys] + \
                          [args[i] for i in diff_arg_idx]

            def wrapped_vjp(out_ct):
                # rebuild the structure `closed` returned: backward() hands a
                # bare array for single-output nodes, a tuple otherwise
                cts = out_ct if isinstance(out_ct, tuple) else (out_ct,)
                # a forward that returned a tuple wants a tuple back
                in_cts = vjp_fn(type(out)(cts) if multi else cts[0])
                param_cts, arg_cts = in_cts[0], in_cts[1:]
                return tuple(param_cts) + tuple(arg_cts)

            autograd._record_op(wrapped_vjp, tape_inputs, outs,
                                name=f"CachedOp[{self.name}]")
            result = outs if multi else outs[0]
        else:
            out, updates = fn(param_map, key, *raw_args)
            if isinstance(out, (tuple, list)):
                result = [NDArray(o) for o in out]
            else:
                result = NDArray(out)

        # apply aux mutations (BatchNorm running stats) post-hoc
        all_params = dict(params)
        for name, val in updates.items():
            if name in all_params:
                all_params[name]._data._rebind(val)
        return result

    # -- imperative face ------------------------------------------------------
    def forward(self, *args, **kwargs):
        kwparams = {}
        for name, p in self._reg_params.items():
            if p._data is None and p._shape_incomplete():
                self.infer_shape(*args)
            if p._data is None and not p._shape_incomplete():
                if p._deferred_init_args is None:
                    raise MXNetError(
                        f"Parameter {p.name} has not been initialized. Call "
                        ".initialize() on the block before the first forward "
                        "pass (reference semantics)")
                p._finish_deferred_init(p.shape)
        for name, p in self._reg_params.items():
            kwparams[name] = p.data()
        return self.hybrid_forward(F, *args, **kwparams, **kwargs)

    def hybrid_forward(self, F, *args, **kwargs):
        raise NotImplementedError

    def export(self, path, epoch=0, example_inputs=None):
        """Serialize the compiled inference graph + params
        (REF:python/mxnet/gluon/block.py export — symbol JSON + params file).

        TPU-native artifact set:
          ``{path}-symbol.json``          manifest (format, input specs)
          ``{path}-{epoch:04d}.params.npz``  parameters
          ``{path}-{epoch:04d}.stablehlo``   serialized `jax.export` program

        The StableHLO program is the inference (predict-mode) forward with
        static input shapes.  Shapes come from ``example_inputs`` or, if
        omitted, from the most recent call to this block.  Load it back with
        `SymbolBlock.imports` — forward results are bit-identical to the
        exporting block's.
        """
        import json

        import numpy as _np
        from jax import export as jexport

        params = self._collect_params_with_prefix()
        payload = {k: p.data() for k, p in params.items() if p._data is not None}
        from ..ndarray import save as nd_save
        nd_save(f"{path}-{epoch:04d}.params.npz", payload)

        if example_inputs is not None:
            in_avals = [
                jax.ShapeDtypeStruct(a.shape, a.dtype) for a in example_inputs]
        elif self._last_input_avals is not None:
            in_avals = self._last_input_avals
        else:
            raise MXNetError(
                "export() needs input shapes: call the block once (after "
                "hybridize()) or pass example_inputs=")

        # exported signature: (params_by_prefixed_name, key, *inputs);
        # prefixed names match the .params.npz keys so a loader needs no
        # other name mapping
        global_of = {k: p.name for k, p in params.items()
                     if p._data is not None}

        def infer_fn(pmap, key, *inputs):
            gmap = {global_of[k]: v for k, v in pmap.items()}
            out, _updates = self._functional_call(gmap, key, False, inputs)
            return out

        key0 = _random.take_key()
        param_avals = {k: jax.ShapeDtypeStruct(p.data().shape, p.data().dtype)
                       for k, p in params.items() if p._data is not None}
        exported = jexport.export(jax.jit(infer_fn))(
            param_avals, jax.ShapeDtypeStruct(key0.shape, key0.dtype),
            *in_avals)
        from ..checkpoint import atomic_write, write_manifest
        hlo_path = f"{path}-{epoch:04d}.stablehlo"
        with atomic_write(hlo_path) as f:
            f.write(exported.serialize())

        with atomic_write(f"{path}-symbol.json", "w") as f:
            f.write(json.dumps({
                "format": "tpu_mx-stablehlo-v1",
                "name": self.name,
                "params": sorted(payload),
                "inputs": [{"shape": list(a.shape),
                            "dtype": _np.dtype(a.dtype).name}
                           for a in in_avals],
                "artifact": f"{path.split('/')[-1]}-{epoch:04d}.stablehlo",
            }))
        # export is a checkpoint too: commit a manifest over the per-epoch
        # artifacts so a torn export can't be mistaken for a loadable
        # model.  {path}-symbol.json is deliberately NOT listed: it is
        # rewritten by every export with an epoch-dependent "artifact"
        # pointer, so digesting it would mark every OLDER epoch corrupt
        # the moment a newer one is exported
        write_manifest(path, epoch, [f"{path}-{epoch:04d}.params.npz",
                                     hlo_path])

    def optimize_for(self, *args, **kwargs):
        self.hybridize(True)


class SymbolBlock(HybridBlock):
    """Reference SymbolBlock wraps a saved symbol; here a saved compiled
    program (REF:python/mxnet/gluon/block.py SymbolBlock).  Build one from
    an `export()` artifact with `SymbolBlock.imports`."""

    def __init__(self, fn, params=None, prefix=None):
        super().__init__(prefix=prefix)
        self._fn = fn

    def hybrid_forward(self, F, *args, **params):
        return self._fn(*args)

    @staticmethod
    def imports(symbol_file, input_names=None, param_file=None, ctx=None):
        """Load an `export()`ed model: returns a callable block whose forward
        runs the deserialized StableHLO program (bit-identical to the
        exporter's inference forward).  Mirrors the reference's
        SymbolBlock.imports(symbol_file, input_names, param_file)."""
        import json
        import os

        import numpy as _np
        from jax import export as jexport

        with open(symbol_file) as f:
            manifest = json.load(f)
        if manifest.get("format") != "tpu_mx-stablehlo-v1":
            raise MXNetError(f"unsupported export format in {symbol_file}")
        art = os.path.join(os.path.dirname(symbol_file) or ".",
                           manifest["artifact"])
        with open(art, "rb") as f:
            exported = jexport.deserialize(f.read())
        from ..ndarray import load as nd_load
        if param_file is None:
            raise MXNetError("param_file is required")
        payload = {k: v._data for k, v in nd_load(param_file).items()}
        key0 = _random.take_key()

        def fn(*inputs):
            raw = [a._data if isinstance(a, NDArray) else jnp.asarray(a)
                   for a in inputs]
            out = exported.call(payload, key0, *raw)
            if isinstance(out, (tuple, list)):
                return [NDArray(o) for o in out]
            return NDArray(out)

        blk = SymbolBlock(fn)
        blk._export_manifest = manifest
        return blk
