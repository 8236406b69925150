"""Pipeline parallelism: GPipe-style microbatch pipelining over a `pp` mesh
axis (SURVEY §2.3's last parallelism row; the reference had no pipeline
support — MXNet model-parallel was manual ctx placement per layer,
REF:example/model-parallel).

TPU-native design: all `pp` stages run the SAME program under `shard_map`
(SPMD, like everything else on the mesh) instead of the reference-era
one-process-per-stage scheme.  Stage parameters are stacked along a leading
stage axis sharded over `pp`, activations rotate stage→stage+1 with
`lax.ppermute`, and a `lax.scan` over M + S - 1 ticks drives the classic
GPipe schedule (stage s computes microbatch t−s at tick t; the first/last
S−1 ticks are the pipeline bubble).  Gradients flow through the transpose
of the same scan/ppermute program — no separate backward schedule to write.

Composes with `dp` (microbatch batch axis sharded over dp) and the other
mesh axes: specs are PartitionSpecs on the same mesh the rest of the train
step uses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_apply", "stack_stage_params"]


def stack_stage_params(per_stage_params):
    """[params_pytree per stage] -> one pytree with a leading stage axis
    (the layout pipeline_apply shards over `pp`).  All stages must share a
    structure and per-leaf shape (uniform stages, the GPipe contract)."""
    return jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves),
                                  *per_stage_params)


def pipeline_apply(stage_fn, stacked_params, x, mesh, axis_name="pp",
                   num_microbatches=None, data_spec=None):
    """Run `x` through S pipeline stages of `stage_fn`, microbatched.

    stage_fn(params, x_mb) -> y_mb — one stage's computation; activations
        must keep the same shape/dtype across stages (uniform stages).
    stacked_params — pytree whose leaves have a leading stage axis of size
        S == mesh.shape[axis_name] (see stack_stage_params).
    x — (B, ...) global batch; B must divide into `num_microbatches`
        (default S) microbatches.
    data_spec — PartitionSpec for one microbatch's dims starting at the
        batch axis, e.g. P('dp') to shard each microbatch's batch over dp
        (default: replicated).

    Returns (B, ...) outputs replicated over `axis_name` (broadcast from
    the last stage), sharded per `data_spec` elsewhere.
    """
    S = mesh.shape[axis_name]
    M = num_microbatches or S
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible into {M} microbatches")

    xs = x.reshape((M, B // M) + x.shape[1:])
    dspec = tuple(data_spec) if data_spec is not None else ()
    x_spec = P(*((None,) + dspec))               # (M, mb, ...): pp-replicated
    p_spec = jax.tree_util.tree_map(lambda _: P(axis_name), stacked_params)

    body = functools.partial(_pipeline_body, stage_fn=stage_fn,
                             axis_name=axis_name, n_stages=S, n_micro=M)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(p_spec, x_spec),
                       out_specs=x_spec, check_vma=False)
    out = fn(stacked_params, xs)
    return out.reshape((B,) + out.shape[2:])


def _pipeline_body(params_local, xs, *, stage_fn, axis_name, n_stages,
                   n_micro):
    """Inside shard_map: params_local leaves are (1, ...) — this stage's
    slice; xs is (M, mb_local, ...) with every microbatch present."""
    p = jax.tree_util.tree_map(lambda a: a[0], params_local)
    s_idx = lax.axis_index(axis_name)
    S, M = n_stages, n_micro
    perm = [(i, (i + 1) % S) for i in range(S)]
    state = jnp.zeros(xs.shape[1:], xs.dtype)    # activation arriving here
    out = jnp.zeros_like(xs)                     # filled on the last stage

    def tick(carry, t):
        state, out = carry
        # stage 0 feeds itself from the input queue; later stages consume
        # what the previous stage permuted over last tick
        x_t = lax.dynamic_index_in_dim(xs, jnp.clip(t, 0, M - 1), 0,
                                       keepdims=False)
        inp = jnp.where(s_idx == 0, x_t, state)
        y = stage_fn(p, inp)
        # the microbatch completing at the last stage this tick
        m_out = t - (S - 1)
        idx = jnp.clip(m_out, 0, M - 1)
        cur = lax.dynamic_index_in_dim(out, idx, 0, keepdims=False)
        valid = (s_idx == S - 1) & (m_out >= 0)
        out = lax.dynamic_update_index_in_dim(
            out, jnp.where(valid, y, cur), idx, 0)
        state = lax.ppermute(y, axis_name, perm)
        return (state, out), None

    (state, out), _ = lax.scan(tick, (state, out), jnp.arange(M + S - 1))
    # broadcast the last stage's buffer to every pp rank (others hold zeros)
    return lax.psum(jnp.where(s_idx == S - 1, out, jnp.zeros_like(out)),
                    axis_name)
