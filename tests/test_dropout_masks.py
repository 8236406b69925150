"""Every dropout mask comes from one helper (`tpu_mx.random.dropout_keep`)
whose bits are XLA's `rng_bit_generator`, drawn again in the backward pass
from the same key (ISSUE 28; `tpu_mx.random.dropped` says why) unless the
site holds it (ISSUE 30: the dense attention site does).  What a mask
must be: kept with probability 1 - rate, the same for the same key, independent for a split's two halves,
the same in forward and backward, and replayed bit for bit from a restored
RNG state."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpu_mx as mx
from tpu_mx import autograd, gluon, nd
from tpu_mx import random as R
from tpu_mx.gluon import nn
from tpu_mx.models.bert import BERTModel, TransformerLayer
from tpu_mx.ndarray import NDArray
from tpu_mx.parallel import CompiledTrainStep


@pytest.fixture(autouse=True)
def _stream():
    token = mx.random.seed(28)
    yield
    mx.random.set_state(token)


# -- the keep share ---------------------------------------------------------------
@pytest.mark.parametrize("axes", [None, (0,)], ids=["full", "axes0"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rate", [0.1, 0.5])
def test_keep_share(rate, dtype, axes):
    # 4 M elements; with axes=(0,) one draw serves four of them
    x = nd.ones((4, 1024, 1024), dtype=dtype)
    with autograd.train_mode():
        y = nd.Dropout(x, p=rate, axes=axes)
    assert str(y.dtype) == dtype
    y = np.asarray(y._data.astype(jnp.float32))
    kept = y != 0
    assert abs(kept.mean() - (1 - rate)) < 0.002
    scale = np.float32(jnp.asarray(1 / (1 - rate), dtype).astype(jnp.float32))
    np.testing.assert_allclose(y[kept], scale, rtol=1e-6)
    if axes:
        assert (kept == kept[:1]).all()


# -- a function of the key, and of nothing else ---------------------------------------
def test_same_key_same_mask():
    key = R.take_key()
    a = R.dropout_keep(key, 0.1, (512, 512))
    b = jax.jit(lambda k: R.dropout_keep(k, 0.1, (512, 512)))(key)
    assert a.dtype == jnp.bool_ and bool((a == b).all())


def test_a_splits_halves_are_independent():
    left, right = jax.random.split(R.take_key())
    a = R.dropout_keep(left, 0.1, (2048, 2048))
    b = R.dropout_keep(right, 0.1, (2048, 2048))
    # 0.9^2 + 0.1^2
    assert abs(float((a == b).mean()) - 0.82) < 0.01


@pytest.mark.parametrize("impl", ["threefry2x32", "rbg"])
def test_typed_and_raw_keys_of_either_width(impl):
    typed = jax.random.key(7, impl=impl)
    a = R.dropout_keep(typed, 0.5, (64, 64))
    b = R.dropout_keep(jax.random.key_data(typed), 0.5, (64, 64))
    assert bool((a == b).all()) and 0.4 < float(a.mean()) < 0.6


# -- forward and backward share the mask ---------------------------------------------
@pytest.mark.parametrize("hybridized", [False, True], ids=["eager", "hybridized"])
def test_dropout_gradient_is_the_mask(hybridized):
    net = nn.Dropout(0.1)
    if hybridized:
        net.hybridize()
    x = nd.array(np.random.RandomState(0).rand(256, 384).astype(np.float32) + 1)
    x.attach_grad()
    with autograd.record():
        y = net(x)
    y.backward()
    y, g = y.asnumpy(), x.grad.asnumpy()
    assert 0.88 < (y != 0).mean() < 0.92
    assert ((g == 0) == (y == 0)).all()
    np.testing.assert_array_equal(g[g != 0], np.float32(1 / 0.9))


def _product(keep, p, v):
    return jnp.einsum("bhqk,bhkd->bhqd", R.scaled(keep, p, 0.1), v)


def _probabilities_and_values():
    return (jnp.asarray(np.random.RandomState(1).rand(2, 3, 16, 16), jnp.float32),
            jnp.asarray(np.random.RandomState(2).rand(2, 3, 16, 8), jnp.float32))


def _site_gradients(how, key, p, v):
    """Gradients of the product attention wraps through one site: "latest"
    is the latest site traced, "drawn_again" has another traced after it,
    "hold" has one too and says that it holds its mask.  With them, how
    many masks the trace drew and how many sites said they hold."""
    def site(p, v):
        out = (R.dropped(_product, key, 0.1, p.shape, p, v,
                         hold=how == "hold") ** 2).sum()
        if how != "latest":
            out = out + 0 * R.dropout(p, jax.random.PRNGKey(5), 0.5).sum()
        return out
    before = dict(R.mask_draws)
    got = jax.jit(jax.grad(site, argnums=(0, 1)))(p, v)
    return got, {k: n - before[k] for k, n in R.mask_draws.items()}


@pytest.mark.parametrize("how", ["latest", "drawn_again", "hold"])
def test_a_sites_backward_matches_plain_autodiff(how):
    # the custom backward pass against jax's own, on the product attention
    # wraps: the latest site holds its mask, every other draws it again,
    # unless it says that it holds it
    key = R.take_key()
    p, v = _probabilities_and_values()

    def plain(p, v):
        return (_product(R.dropout_keep(key, 0.1, p.shape), p, v) ** 2).sum()

    want = jax.grad(plain, argnums=(0, 1))(p, v)
    got, counted = _site_gradients(how, key, p, v)
    # the other site is the latest and holds: one draw; this one's second
    # draw is the third
    assert counted == {"latest": {"rbg": 1, "held": 0},
                       "drawn_again": {"rbg": 3, "held": 0},
                       "hold": {"rbg": 2, "held": 1}}[how]
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_a_site_that_holds_gives_the_gradients_of_one_that_draws_again():
    # same key, same 32-bit draws, same comparison: the same mask, held or
    # drawn a second time, and the same arithmetic on it
    key = R.take_key()
    p, v = _probabilities_and_values()
    again, _ = _site_gradients("drawn_again", key, p, v)
    held, _ = _site_gradients("hold", key, p, v)
    for a, b in zip(held, again):
        np.testing.assert_array_equal(a, b)


def test_rnn_inter_layer_dropout_draws_through_the_helper():
    net = gluon.rnn.LSTM(8, num_layers=3, dropout=0.5)
    net.initialize()
    before = R.mask_draws["rbg"]
    with autograd.record():
        out = net(nd.ones((5, 3, 4)))
    assert out.shape == (5, 3, 8)
    assert R.mask_draws["rbg"] - before == 2


# -- what the compiled program holds ------------------------------------------------
def _transformer_layer_text():
    net = TransformerLayer(64, 128, 4, dropout=0.1)
    net.initialize()
    x = np.random.RandomState(0).rand(8, 32, 64).astype(np.float32)
    with autograd.predict_mode():
        net(nd.array(x))
    params = {n: p.data()._data for n, p in net.collect_params().items()}

    def loss(params, key, x):
        out, _ = net._functional_call(params, key, True, (NDArray(x),))
        return (out ** 2).sum()
    return jax.jit(jax.grad(loss)).lower(
        params, jax.random.PRNGKey(0), jnp.asarray(x)).as_text()


def test_transformer_layer_lowers_to_rng_bit_generator():
    before = dict(R.mask_draws)
    text = _transformer_layer_text()
    # three sites (attention probabilities, two hidden) in the forward
    # pass; in the backward pass one: the attention site holds its mask
    # and so does the latest site
    assert R.mask_draws["rbg"] - before["rbg"] == 3 + 1
    assert R.mask_draws["held"] - before["held"] == 1
    drawn = re.findall(r"stablehlo.rng_bit_generator.*-> \(tensor<2xui64>, "
                       r"tensor<([0-9x]+)xui32>\)", text)
    assert sorted(set(drawn)) == ["8x32x64", "8x4x32x32"]
    # threefry stays for the key splits, and makes nothing mask-sized
    assert "threefry" in text
    fry = [l for l in text.split("\n") if "threefry" in l and "call @" in l]
    assert fry and not [l for l in fry if re.search(r"tensor<8x(32x64|4x32x32)x", l)]


def test_the_dense_attention_site_draws_once_and_a_hidden_site_twice():
    # forward and backward in one lowered text: the scores' shape is drawn
    # in the forward pass alone; of the two hidden sites the earlier draws
    # again in the backward pass (the later is the latest site)
    # (a mask draw is a call of a private function that holds the
    # generator; draws of one shape may share the function)
    drawn = re.findall(r"call @_bernoulli.*-> tensor<([0-9x]+)xi1>",
                       _transformer_layer_text())
    assert drawn.count("8x4x32x32") == 1
    assert drawn.count("8x32x64") == 2 + 1


def test_a_site_that_does_not_hold_lowers_as_before_issue_30():
    # the cells whose sites do not hold (bert-base.mlm512's 25 hidden
    # sites, the RNN layers) run the program they ran: the site below is
    # ISSUE 28's, copied, and lowers to the same text as today's
    def fwd(fn, n, key, rate, shape, *operands):
        keep = R.dropout_keep(R._after(key, operands), rate, shape)
        return fn(keep, *operands), (key, keep, operands)

    def bwd(fn, n, rate, shape, held, g):
        key, keep, operands = held
        if n != R._sites[0]:
            keep = R.dropout_keep(R._after(key, g), rate, shape)
        _, pull = jax.vjp(functools.partial(fn, keep), *operands)
        return (None, *pull(g))

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 3, 4))
    def site(fn, n, key, rate, shape, *operands):
        return fwd(fn, n, key, rate, shape, *operands)[0]
    site.defvjp(fwd, bwd)

    def before_issue_30(fn, key, rate, shape, *operands):
        R._sites[0] += 1
        return site(fn, R._sites[0], R._raw(key), rate, tuple(shape), *operands)

    def text(dropped):
        def loss(x, w, key):
            k1, k2 = jax.random.split(key)
            h = dropped(lambda keep, x: R.scaled(keep, x, 0.1), k1, 0.1,
                        x.shape, x) @ w
            return dropped(lambda keep, h: R.scaled(keep, h, 0.1), k2, 0.1,
                           h.shape, h).sum()
        return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            jnp.ones((8, 32)), jnp.ones((32, 32)), jax.random.PRNGKey(0)
        ).as_text()
    now = text(R.dropped)
    assert len(re.findall(r"call @_bernoulli", now)) == 3
    assert now == text(before_issue_30)


@pytest.mark.parametrize("arm, sites, held", [("dense", 25 + 12, 12),
                                              ("flash", 25, 0)])
def test_one_trace_of_berts_step_counts_its_sites(monkeypatch, arm, sites,
                                                  held):
    # BERT-base's depth at a toy width: embedding + two a layer, and on the
    # dense arm the twelve attention sites too (the flash kernel draws
    # inside itself).  The backward pass draws every mask again but the
    # latest site's and those of the attention sites, which hold theirs:
    # 61 and 49.  A trace alone: the kernel's dropout needs a TPU to run
    monkeypatch.setenv("TPUMX_ATTENTION", arm)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    net = BERTModel(dict(num_layers=12, units=128, hidden_size=256,
                         num_heads=2, vocab_size=97, max_length=128,
                         dropout=0.1))
    net.initialize()
    rs = np.random.RandomState(0)
    tokens = rs.randint(4, 97, (2, 128)).astype(np.int32)
    positions = np.tile(np.arange(5, dtype=np.int32), (2, 1))
    with autograd.predict_mode(), monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "cpu")
        net(nd.array(tokens, dtype="int32"), nd.zeros((2, 128), dtype="int32"),
            None, nd.array(positions, dtype="int32"))
    params = {n: p.data()._data for n, p in net.collect_params().items()}

    def loss(params, key):
        out, _ = net._functional_call(
            params, key, True, (NDArray(tokens), NDArray(np.zeros_like(tokens)),
                                None, NDArray(positions)))
        return (out.astype(jnp.float32) ** 2).sum()
    before = dict(R.mask_draws)
    jax.make_jaxpr(jax.grad(loss))(params, jax.random.PRNGKey(0))
    assert R.mask_draws["rbg"] - before["rbg"] == 2 * sites - 1 - held
    assert R.mask_draws["held"] - before["held"] == held


# -- determinism: the same key, program and backend give the same mask -------------------
def _dropout_step():
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dropout(0.1), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    step = CompiledTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                             mx.optimizer.create("sgd", learning_rate=0.1))
    rs = np.random.RandomState(3)
    return step, (nd.array(rs.rand(16, 8).astype(np.float32)),
                  nd.array(rs.randint(0, 4, (16,)), dtype="float32"))


def _snapshot(step):
    # on the host: the step donates the buffers a loaded snapshot hands it
    return jax.tree_util.tree_map(np.asarray, step.state_dict())


def _five_losses(step, batch):
    return [step.step(*batch).asnumpy() for _ in range(5)]


def test_train_step_replays_from_one_state_token():
    step, batch = _dropout_step()
    start, token = _snapshot(step), mx.random.get_state()
    first = _five_losses(step, batch)
    step.load_state_dict(start)
    mx.random.set_state(token)
    again = _five_losses(step, batch)
    for a, b in zip(first, again):
        np.testing.assert_array_equal(a, b)
    mx.random.set_state(token)
    mx.random.take_key()
    step.load_state_dict(start)
    assert not np.array_equal(_five_losses(step, batch)[0], first[0])


def test_resume_capsule_replays_a_dropout_step(tmp_path):
    from tpu_mx import resume
    from tpu_mx import elastic
    step, batch = _dropout_step()
    prefix = str(tmp_path / "ck")
    mgr = resume.CapsuleManager(prefix, iters=[])
    _five_losses(step, batch)
    start = _snapshot(step)
    saved = nn.Dense(2, in_units=2)      # the capsule rides on a checkpoint
    saved.initialize()
    elastic.save_checkpoint(prefix, 0, net=saved, capsule=mgr)
    expect = _five_losses(step, batch)

    # a "fresh process": the stream somewhere else
    mx.random.seed(999)
    assert resume.CapsuleManager(prefix, iters=[]).restore(resume_from=1) == 1
    step.load_state_dict(start)
    for a, b in zip(expect, _five_losses(step, batch)):
        np.testing.assert_array_equal(a, b)


def test_shadow_audit_reexecutes_a_dropout_step_bit_for_bit():
    from tpu_mx.parallel.integrity import ShadowAuditor
    step, batch = _dropout_step()
    start, token = _snapshot(step), mx.random.get_state()
    first = step.step(*batch).asnumpy()

    def again():
        step.load_state_dict(start)
        mx.random.set_state(token)
        return step.step(*batch).asnumpy()
    # raises DataCorruption on any differing bit
    ShadowAuditor(rate=1.0, seed=0).audit(first, again, step=1)


def test_a_mesh_step_keeps_threefrys_partitionable_masks():
    # GSPMD does not split rng_bit_generator: every chip would draw the
    # global batch's mask.  Under a mesh the step draws as it did before
    from tpu_mx.parallel import make_mesh
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dropout(0.1), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    step = CompiledTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                             mx.optimizer.create("sgd", learning_rate=0.1),
                             mesh=mesh)
    rs = np.random.RandomState(3)
    batch = (nd.array(rs.rand(16, 8).astype(np.float32)),
             nd.array(rs.randint(0, 4, (16,)), dtype="float32"))
    before = R.mask_draws["rbg"]
    text = step.aot_compiled(*batch).as_text()
    assert R.mask_draws["rbg"] == before
    assert "rng-bit-generator" not in text and "rng_bit_generator" not in text
    assert np.isfinite(step.step(*batch).asnumpy())
