"""`_block_attn`, the one block of attention behind the dense, ring and
ulysses arms, stops the gradient at the row maximum (ISSUE 30): the maximum
is a shift that cancels in o / l, so its gradient is zero in exact
arithmetic, and autodiff paid for it with a tie indicator the size of the
scores, held from forward to backward.  The dense site's dropout mask is
held in that room.  What must stay true: the gradients are attention's."""
import inspect
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from tpu_mx.parallel import local_flash_attention, make_mesh, ring_attention
from tpu_mx.parallel.ring_attention import _block_attn

B, H, T, D = 2, 2, 32, 4


def _qkv(seed):
    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(B, H, T, D).astype(np.float32))
                 for _ in range(3))


def _plain(q, k, v, valid=None, causal=False):
    """Softmax attention in f32 as the textbook has it; a row with no key
    to attend to gives zeros (and passes no gradient)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    mask = jnp.ones((B, 1, T, T), bool)
    if causal:
        mask = mask & (jnp.arange(T)[:, None] >= jnp.arange(T)[None, :])
    if valid is not None:
        mask = mask & (jnp.arange(T)[None, None, None, :]
                       < jnp.asarray(valid)[:, None, None, None])
    some = mask.any(-1, keepdims=True)
    p = jax.nn.softmax(jnp.where(mask | ~some, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", jnp.where(some, p, 0.0), v)


def _grads(attend, q, k, v):
    # a nonlinear scalarizer, as tests/test_parallel.py has it
    return jax.grad(lambda q, k, v: jnp.sum(jnp.sin(attend(q, k, v))),
                    argnums=(0, 1, 2))(q, k, v)


# -- the dense arm against plain autodiff ------------------------------------------
@pytest.mark.parametrize("valid", [None, (32, 5), (7, 0)],
                         ids=["every_key", "valid_length",
                              "a_fully_masked_row"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_dense_gradients_are_plain_softmax_attentions(causal, valid):
    q, k, v = _qkv(3)
    length = None if valid is None else jnp.asarray(valid, jnp.int32)
    out = local_flash_attention(q, k, v, causal=causal, valid_length=length)
    np.testing.assert_allclose(out, _plain(q, k, v, valid, causal),
                               rtol=1e-5, atol=1e-6)
    got = _grads(lambda q, k, v: local_flash_attention(
        q, k, v, causal=causal, valid_length=length), q, k, v)
    want = _grads(lambda q, k, v: _plain(q, k, v, valid, causal), q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6,
                                   err_msg=f"d{name}")


class _LaxThatStopsNothing:
    stop_gradient = staticmethod(lambda x: x)

    def __getattr__(self, name):
        return getattr(jax.lax, name)


def test_the_value_does_not_know_that_the_gradient_stops(monkeypatch):
    # stop_gradient is the identity in the forward pass: the same bits
    q, k, v = _qkv(4)
    length = jnp.asarray((32, 5), jnp.int32)
    now = local_flash_attention(q, k, v, valid_length=length)
    # (parallel/__init__ names the function `ring_attention` too)
    module = sys.modules["tpu_mx.parallel.ring_attention"]
    monkeypatch.setattr(module, "lax", _LaxThatStopsNothing())
    np.testing.assert_array_equal(
        now, local_flash_attention(q, k, v, valid_length=length))


# -- the ring arm: every block's shift cancels through _merge -------------------------
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_ring_gradients_match_dense_under_a_valid_length(causal):
    mesh = make_mesh({"sp": 8})
    q, k, v = _qkv(7)
    length = jnp.asarray((32, 11), jnp.int32)
    want = _grads(lambda q, k, v: local_flash_attention(
        q, k, v, causal=causal, valid_length=length), q, k, v)
    got = _grads(lambda q, k, v: ring_attention(
        q, k, v, mesh, causal=causal, valid_length=length), q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg=f"d{name}")


# -- what the site holds from forward to backward -------------------------------------
def _held(valid):
    q, k, v = _qkv(5)
    length = None if valid is None else jnp.asarray(valid, jnp.int32)

    def loss(q, k, v):
        return jnp.sum(jnp.sin(local_flash_attention(
            q, k, v, valid_length=length, dropout_rate=0.1,
            dropout_key=jax.random.PRNGKey(1))))
    return [(aval.str_short(), what)
            for aval, what in saved_residuals(loss, q, k, v)]


@pytest.mark.parametrize("valid", [None, (32, 5)],
                         ids=["every_key", "valid_length"])
def test_nothing_is_held_for_the_row_maximum(valid):
    source, first = inspect.getsourcelines(_block_attn)
    line = first + next(i for i, l in enumerate(source) if "jnp.max(" in l)
    held = _held(valid)
    assert held and not [what for _, what in held
                         if f"ring_attention.py:{line}:" in what]
    # the probabilities are, so the reading is of the right function
    assert [what for _, what in held
            if "output of exp" in what and "(_block_attn)" in what]


def test_the_dense_site_holds_one_mask_the_keep_mask():
    scores = f"bool[{B},{H},{T},{T}]"
    masks = [what for shape, what in _held(None) if shape == scores]
    assert len(masks) == 1 and "(dropout_keep)" in masks[0]
