"""Ulysses sequence parallelism: all-to-all head-sharded attention
(SURVEY §5.7 — the second long-context strategy next to ring attention;
DeepSpeed-Ulysses, PAPERS.md).

Design: activations arrive sequence-sharded (each of the n `sp` devices
holds T/n positions of every head).  One tiled `lax.all_to_all` per q/k/v
re-shards to HEAD-sharded (each device holds H/n heads over the FULL
sequence), attention for those heads runs entirely locally — which means
the Pallas flash kernel (full-T blockwise, MXU-sized matmuls) instead of
ring's n-step streamed blocks — and one all-to-all brings the output back
to sequence-sharded.  Communication is 4 activation-sized all-to-alls per
layer vs ring's n K/V ppermute hops; compute is one big local attention vs
n small ones.  Ring wins when T/n is still large and H < n; Ulysses wins
on MXU efficiency when H % n == 0 (the usual case: 12-128 heads, sp ≤ 8).

Trade-off table (pick with `set_sp_strategy` / the `sp_strategy` arg):
  ring    — no head-count constraint, K/V memory O(T/n) per device
  ulysses — needs H % n == 0, local flash kernel, fewer comm hops
"""
from __future__ import annotations

import functools


import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .ring_attention import (_count, _refuse_window, _sp_valid_seed,
                             local_flash_attention)

__all__ = ["ulysses_attention", "set_sp_strategy", "get_sp_strategy"]

_SP_STRATEGY = "ring"  # module default: no head-divisibility constraint


def set_sp_strategy(strategy):
    """Select the sequence-parallel attention strategy ('ring' or
    'ulysses') used by `parallel.attention` when the mesh has an `sp`
    axis.  Returns the previous value."""
    global _SP_STRATEGY
    if strategy not in ("ring", "ulysses"):
        raise ValueError("sp strategy must be 'ring' or 'ulysses'")
    prev, _SP_STRATEGY = _SP_STRATEGY, strategy
    return prev


def get_sp_strategy():
    return _SP_STRATEGY


def _ulysses_body(q, k, v, valid, seed, bias, *, axis_name, causal,
                  rate, masked, dropped, biased, key_axes=()):
    """Runs inside shard_map.  q/k/v: LOCAL sequence blocks (B, H, Tb, D).
    all_to_all → (B, H/n, T, D) head shards → one full-T local attention →
    all_to_all back."""
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    # tiled all_to_all: split the head axis n ways, concat sequence axis
    qh = lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2,
                        tiled=True)                       # (B, H/n, T, D)
    kh = lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2,
                        tiled=True)
    vh = lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2,
                        tiled=True)
    key = None
    if dropped:
        # tpumx-lint: disable=determinism -- key is a pure function of the
        # caller-provided seed input (traced), not a hidden fresh stream
        key = jax.random.PRNGKey(seed[0])
        for ax in key_axes:
            key = jax.random.fold_in(key, lax.axis_index(ax))
        key = jax.random.fold_in(key, my_idx)
    b_blk = None
    if biased:
        # bias arrives with full rows/cols; slice MY head group when it
        # carries a head axis
        hb = bias.shape[1]
        if hb > 1:
            hn = hb // n
            b_blk = lax.dynamic_slice_in_dim(bias, my_idx * hn, hn, axis=1)
        else:
            b_blk = bias
    # the local full-T attention goes through local_flash_attention: on
    # TPU with tile-friendly shapes that is the Pallas flash kernel
    # (blockwise, O(T) score memory — the reason ulysses wins on MXU
    # efficiency); off-TPU / unsupported shapes take the dense path.
    # NB keys: local_flash_attention derives its kernel seed from the
    # already per-device-folded key, so head groups draw independent masks
    out = local_flash_attention(
        qh, kh, vh, causal=causal,
        valid_length=valid if masked else None,
        dropout_rate=rate if dropped else 0.0,
        dropout_key=key, bias=b_blk)                      # (B, H/n, T, D)
    # back to sequence-sharded: split T, concat heads
    return lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)                     # (B, H, Tb, D)


def ulysses_attention(q, k, v, mesh, axis_name="sp", causal=False,
                      q_spec=None, valid_length=None, dropout_rate=0.0,
                      dropout_key=None, bias=None, batch_axes=("dp", "tp"),
                      window=None):
    """All-to-all sequence-parallel attention.  Same contract as
    `ring_attention`: q/k/v are GLOBAL (B, H, T, D) arrays with T sharded
    over `axis_name`; returns output with the same sharding.  Requires
    H % mesh.shape[axis_name] == 0 (raises otherwise — `attention()`
    falls back to ring for such models).  A window is refused by name, as
    on the ring."""
    _refuse_window(window, "ulysses_attention")

    def present(ax):
        # size-1 axes shard nothing — treat as absent so e.g. tp=1 meshes
        # don't poison the head slot of the spec
        return ax in mesh.axis_names and mesh.shape[ax] > 1

    if not present(axis_name):
        return local_flash_attention(q, k, v, causal=causal,
                                     valid_length=valid_length,
                                     dropout_rate=dropout_rate,
                                     dropout_key=dropout_key, bias=bias)
    n = mesh.shape[axis_name]
    H = q.shape[1]
    if H % n:
        raise ValueError(
            f"ulysses_attention: heads ({H}) must divide by sp ({n}); "
            "use ring attention for this model")
    bax, hax = (tuple(batch_axes) + (None, None))[:2]
    spec = q_spec or P(bax if bax and present(bax) else None,
                       hax if hax and present(hax) else None,
                       axis_name, None)
    if spec[1] is not None:
        raise ValueError(
            "ulysses_attention: the head axis cannot also be mesh-sharded "
            f"(spec {spec}); all-to-all re-shards heads over {axis_name}")
    dropped = dropout_rate > 0.0 and dropout_key is not None
    masked = valid_length is not None
    biased = bias is not None
    _count("ulysses", f"sp={n} shape={q.shape}")
    valid, seed, vspec = _sp_valid_seed(q, masked, dropped, valid_length,
                                        dropout_key, spec)
    bias_arr = bias if biased else jnp.zeros((1, 1, 1, 1), q.dtype)
    # bias: rows and columns stay WHOLE (each device attends over full T
    # after the all-to-all); batch follows q's batch axis when present
    bspec = P(spec[0] if biased and bias_arr.shape[0] > 1 else None,
              None, None, None)
    key_axes = tuple(ax for ax in (spec[0],) if ax is not None)
    fn = jax.shard_map(
        functools.partial(_ulysses_body, axis_name=axis_name, causal=causal,
                          rate=float(dropout_rate),
                          masked=masked, dropped=dropped, biased=biased,
                          key_axes=key_axes),
        mesh=mesh, in_specs=(spec, spec, spec, vspec, P(None), bspec),
        out_specs=spec, check_vma=False)
    return fn(q, k, v, valid, seed, bias_arr)
