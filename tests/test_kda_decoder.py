"""The Kimi Delta Attention decoder at toy sizes on the CPU: the
chunked delta rule `kda_chunked` against the per-token recurrence of the
plain reference tests/references/kda_decoder.py, forward and in the
gradient of every input, across chunk boundaries and under decays strong
enough that a split exp(G_i) exp(-G_j) would overflow; the mixer
KimiDeltaAttention and the latent layer without a query rank or positions
against the reference; the whole model (a KDA-dense layer, KDA and latent
expert layers, sigmoid-routed experts beside a shared one, a head of its
own) against the reference through the configuration module the benchmark
uses (benchmark/configs/kimi-linear-48b-a3b.py: its `weights`, `compare`
and `hyper` decide `correct` on the chip); the parameters the cell's chip
holds; and the scope names and the counter.
"""
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_mx.models import decoder
from tpu_mx.models.decoder import (KDA_SCOPES, OWNER_SCOPES,
                                   KimiDeltaAttention, LatentAttention,
                                   kda_chunked)
from tpu_mx.parallel.ring_attention import dispatch_counts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
NAME = "kimi-linear-48b-a3b"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load(os.path.join(ROOT, "tests", "references", "kda_decoder.py"),
                  "kda_decoder_ref")
config_mod = _load(os.path.join(BENCH, "configs", NAME + ".py"),
                   "kimi_config_mod")
TOLERANCE = 2e-3    # f32 against f32 "highest"; the toy's honest error is 1e-6


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean(np.square(a - b)))
                 / np.sqrt(np.mean(np.square(b))))


def system_call(block, params, *xs):
    return block._functional_call(params, jax.random.PRNGKey(0), True, xs)[0]


# -- the chunked rule --------------------------------------------------------------------
def rule_inputs(t, strength, seed=0, b=2, h=2, dk=16, dv=8, aligned=False):
    """q, k L2-normalised, v, g = -strength softplus(normal) (strength 0: a
    decay of 1 everywhere), beta in (0, 1).  `aligned`: the keys of a head
    share one direction up to noise of 0.05 and beta lies in (0.9, 1), so
    that I + A is as far from I as a trained model's can be."""
    keys = jax.random.split(jax.random.key(seed), 6)
    l2 = lambda a: a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True))
    q = l2(jax.random.normal(keys[0], (b, t, h, dk)))
    k = l2(jax.random.normal(keys[1], (b, t, h, dk)))
    v = jax.random.normal(keys[2], (b, t, h, dv))
    g = -strength * jax.nn.softplus(jax.random.normal(keys[3], (b, t, h, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, t, h)))
    if aligned:
        k = l2(l2(jax.random.normal(keys[5], (b, 1, h, dk))) + 0.05 * k)
        beta = 0.9 + 0.1 * beta
    return q, k, v, g, beta


@pytest.mark.parametrize("t,chunk,strength,aligned", [
    (64, 64, 1.0, False),      # one whole chunk
    (192, 64, 1.0, False),     # three, the state carried over two boundaries
    (256, 64, 12.0, False),    # decays summing below -100 inside a chunk
    (100, 64, 4.0, False),     # a chunk and a part: padded at the end
    (96, 32, 0.0, False),      # no decay at all, chunks of 32
    (256, 64, 0.05, False),    # a decay that hardly decays
    (1100, 64, 2.0, False),    # 18 chunks: two whole groups of 8 and a part
    (192, 64, 0.05, True)],    # keys that point alike, beta near 1
    ids=["t64", "t192", "t256-strong", "t100-padded", "t96-c32-nodecay",
         "t256-weak", "t1100-groups", "t192-aligned"])
def test_the_chunked_rule_is_the_recurrence(t, chunk, strength, aligned):
    q, k, v, g, beta = rule_inputs(t, strength, seed=t, aligned=aligned)
    if strength > 10:
        # the sum of a chunk's decays from its start: exp of its negation,
        # a split form's factor, is past float32's range
        cum = np.cumsum(np.asarray(g[:, :chunk]), axis=1)
        with np.errstate(over="ignore"):
            assert cum.min() < -100 and not np.isfinite(
                np.exp(-cum.astype(np.float32))).all()
    f = lambda *a: kda_chunked(*a, chunk=chunk)
    plain = reference.recurrence
    with jax.default_matmul_precision("highest"):
        got, want = jax.jit(f)(q, k, v, g, beta), plain(q, k, v, g, beta)
        loss = lambda fn: lambda *a: jnp.sum(jnp.sin(3 * fn(*a)))
        grads, ref_grads = (jax.jit(jax.grad(loss(fn), argnums=range(5)))(
            q, k, v, g, beta) for fn in (f, plain))
    assert got.shape == want.shape == (2, t, 2, 8)
    assert np.isfinite(np.asarray(got)).all()
    assert rel(got, want) < 1e-5
    for name, a, b in zip("qkvgb", grads, ref_grads):
        assert np.isfinite(np.asarray(a)).all(), name
        assert rel(a, b) < 1e-4, name


def chunk_system(c, strength, seed=0, heads=6):
    """A = beta k_i k_j exp(G_i - G_j) (j < i) of one chunk of c positions a
    head, keys aligned as `rule_inputs` draws them: (A, I + A in float64)."""
    _, k, _, g, beta = rule_inputs(c, strength, seed=seed, b=1, h=heads,
                                   aligned=True)
    k, g = (jnp.moveaxis(x[0], 1, 0) for x in (k, g))        # (H, c, d)
    a = beta[0].T[..., None] * decoder._decayed_products(
        k, k, jnp.cumsum(g, axis=-2), min(16, c))[0]
    return a, np.eye(c) + np.asarray(a, np.float64)


@pytest.mark.parametrize("c", [16, 32, 64])
@pytest.mark.parametrize("strength", [0.0, 4.0], ids=["nodecay", "strong"])
def test_the_chunk_inverse_is_float64s(c, strength):
    """The chunk's (I + A)^-1, by forward substitution in f32, against
    NumPy's in float64, on keys that point alike: A's entries near 1 with
    no decay, where a product form's powers of A would grow into the
    thousands before they cancel."""
    a, system = chunk_system(c, strength)
    if strength == 0.0:
        assert np.asarray(a)[:, -1, 0].min() > 0.8
    t = np.asarray(decoder._unit_lower_inverse(a), np.float64)
    eye = np.eye(c)
    for ti, mi in zip(t, system):
        assert np.linalg.norm(ti @ mi - eye) / np.linalg.norm(eye) < 1e-5
        inv = np.linalg.inv(mi)
        assert np.linalg.norm(ti - inv) / np.linalg.norm(inv) < 1e-5


def test_the_solves_gradient_is_the_triangular_solves():
    """(I + A)^-1 [beta v | beta k e^G] by the inverse and its two products
    a step back: the same values and gradients as XLA's triangular solve."""
    a, _ = chunk_system(64, 1.0, seed=3)
    rhs = jax.random.normal(jax.random.key(4), a.shape[:-1] + (24,))
    w = jax.random.normal(jax.random.key(5), rhs.shape)
    eye = jnp.eye(64)
    loss = lambda solve: lambda a, r: jnp.sum(w * jnp.sin(solve(a, r)))
    solver = lambda a, r: jax.scipy.linalg.solve_triangular(
        eye + jnp.tril(a, -1), r, lower=True, unit_diagonal=True)
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(loss(decoder._unit_lower_solve), (0, 1))(
            a, rhs)
        want = jax.value_and_grad(loss(solver), (0, 1))(a, rhs)
    assert abs(float(got[0]) - float(want[0])) < 1e-5 * abs(float(want[0]))
    for mine, xla in zip(got[1], want[1]):
        assert rel(mine, xla) < 1e-5
    # nothing flows to the entries the solve does not read
    assert not np.asarray(jnp.triu(got[1][0])).any()


def test_no_triangular_solve_is_left_in_the_gradient():
    """The gradient of the rule under a checkpoint, as a layer's remat
    takes it, holds no triangular solve: neither XLA's operation nor the
    LAPACK call the CPU lowers it to (the solve form held four)."""
    inputs = rule_inputs(192, 1.0)
    rule = jax.checkpoint(lambda *a: jnp.sum(kda_chunked(*a)))
    text = jax.jit(jax.grad(rule, argnums=range(5))).lower(*inputs).as_text()
    assert "while" in text
    assert "triangular_solve" not in text
    assert not re.search(r"lapack_\w*trsm", text)


# -- the within-sub-chunk pairs -----------------------------------------------------------
def xla_pairs(a, b, cum, diagonal):
    """The pairwise form the Pallas calls replaced: sum_d a_i b_j
    exp(cum_i - cum_j) over (..., n, s, d) for j < i (j <= i with
    `diagonal`), a (..., n, s, s, d) exp of its own, gradient by autodiff."""
    pos = np.arange(a.shape[-2])
    keep = pos[:, None] >= pos[None, :] if diagonal \
        else pos[:, None] > pos[None, :]
    pair = jnp.exp(jnp.where(keep[:, :, None],
                             cum[..., :, None, :] - cum[..., None, :, :],
                             -jnp.inf))
    return jnp.sum(a[..., :, None, :] * b[..., None, :, :] * pair, axis=-1)


def numpy_pairs(a, b, cum, diagonal):
    a, b, cum = (np.asarray(x, np.float64) for x in (a, b, cum))
    s = a.shape[-2]
    out = np.zeros(a.shape[:-1] + (s,))
    for i in range(s):
        for j in range(i + 1 if diagonal else i):
            out[..., i, j] = np.sum(a[..., i, :] * b[..., j, :] * np.exp(
                cum[..., i, :] - cum[..., j, :]), axis=-1)
    return out


def pair_inputs(case, n=6, d=16, seed=0):
    """q, k, cum (2, n, 16, d) f32 from `rule_inputs`' draws, the decays
    summed along each sub-chunk's positions: `nodecay`, `strong` (sums
    below -100 inside a sub-chunk), `aligned` keys, `padded` (the last
    sub-chunks zero, as kda_chunked pads its last group), `blocks` (more
    sub-chunks than one program's block, and no whole number of them)."""
    strength = {"nodecay": 0.0, "strong": 40.0}.get(case, 1.0)
    n = 140 if case == "blocks" else n
    q, k, _, g, _ = rule_inputs(16 * n, strength, seed=seed, b=1, h=2,
                                dk=d, aligned=case == "aligned")
    at = lambda x: jnp.moveaxis(x[0], 1, 0).reshape(2, n, 16, d)
    q, k, g = at(q), at(k), at(g)
    if case == "padded":
        live = (jnp.arange(n) < n - 2)[:, None, None]
        q, k, g = (jnp.where(live, x, 0.0) for x in (q, k, g))
    return q, k, jnp.cumsum(g, axis=-2)


PAIR_CASES = ["nodecay", "strong", "aligned", "padded", "blocks"]


def pairwise_forms(q, k, cum):
    return xla_pairs(k, k, cum, False), xla_pairs(q, k, cum, True)


@jax.jit
def pairs_and_grads(q, k, cum, ca, cp):
    """(A, P) by the Pallas calls and by the pairwise form, and the
    gradients of sum(ca A) + sum(cp P) by q, k and cum by each; one
    program a shape for all the cases of that shape."""
    loss = lambda fn: lambda *x: jnp.sum(ca * fn(*x)[0]) \
        + jnp.sum(cp * fn(*x)[1])
    return [(fn(q, k, cum), jax.grad(loss(fn), (0, 1, 2))(q, k, cum))
            for fn in (decoder._sub_chunk_pairs, pairwise_forms)]


@pytest.mark.parametrize("case", PAIR_CASES)
def test_the_pairs_are_the_pairwise_forms_and_float64s(case):
    """Both products of one Pallas call, values and the gradient by q, k
    and the summed decays, against the pairwise form the call replaced
    (autodiff, f32) and against float64 NumPy (values, and the gradient
    along a random direction by central differences)."""
    q, k, cum = pair_inputs(case)
    if case == "strong":
        span = np.asarray(cum[..., -1, :] - cum[..., 0, :])
        assert span.min() < -100
    keys = jax.random.split(jax.random.key(7), 5)
    ca, cp = (jax.random.normal(key, q.shape[:-1] + (16,))
              for key in keys[:2])
    (got, grads), (want, ref_grads) = pairs_and_grads(q, k, cum, ca, cp)
    exact = (numpy_pairs(k, k, cum, False), numpy_pairs(q, k, cum, True))
    for mine, theirs, f64 in zip(got, want, exact):
        assert mine.shape == q.shape[:-1] + (16,)
        assert np.isfinite(np.asarray(mine)).all()
        assert rel(mine, theirs) < 1e-6 and rel(mine, f64) < 1e-6
    # nothing on or above the diagonal of A, nothing above it in P
    upper = np.triu(np.ones((16, 16), bool), 1)
    assert not np.asarray(got[0])[..., upper | np.eye(16, dtype=bool)].any()
    assert not np.asarray(got[1])[..., upper].any()
    for name, mine, theirs in zip("qkG", grads, ref_grads):
        assert np.isfinite(np.asarray(mine)).all(), name
        assert rel(mine, theirs) < 1e-5, name
    f64 = lambda *x: np.sum(np.asarray(ca, np.float64) * numpy_pairs(
        x[1], x[1], x[2], False)) + np.sum(np.asarray(cp, np.float64)
                                         * numpy_pairs(*x, True))
    base = [np.asarray(x, np.float64) for x in (q, k, cum)]
    for at, key in zip(range(3), keys[2:]):
        v = np.asarray(jax.random.normal(key, q.shape), np.float64)
        h = 1e-4
        up, down = list(base), list(base)
        up[at], down[at] = base[at] + h * v, base[at] - h * v
        slope = (f64(*up) - f64(*down)) / (2 * h)
        mine = np.asarray(grads[at], np.float64)
        scale = np.linalg.norm(mine) * np.linalg.norm(v)
        assert abs(np.sum(mine * v) - slope) < 1e-6 * scale, "qkG"[at]


@pytest.mark.parametrize("diagonal", [False, True], ids=["strict", "diag"])
def test_the_decays_gradient_is_a_da_minus_b_db(diagonal):
    """dW_ij / dG_i = a_i b_j E_ij = -dW_ij / dG_j, so the pairwise form's
    gradient by the summed decays is a * da - b * db, by autodiff."""
    q, k, cum = pair_inputs("aligned", seed=3)
    c = jax.random.normal(jax.random.key(8), q.shape[:-1] + (16,))
    loss = lambda a, b, g: jnp.sum(c * xla_pairs(a, b, g, diagonal))
    da, db, dg = jax.grad(loss, (0, 1, 2))(q, k, cum)
    assert rel(q * da - k * db, dg) < 1e-5


def test_the_pairs_take_channels_eight_at_a_time():
    """A head of 12 channels would leave 4 out of the kernels' sums."""
    q, k, cum = pair_inputs("aligned", d=12)
    with pytest.raises(ValueError, match="head_dim 12"):
        decoder._sub_chunk_pairs(q, k, cum)


def test_no_pairwise_decay_is_left_in_the_gradient():
    """The gradient of the rule under a checkpoint, as a layer's remat
    takes it, holds no value of (..., 16, 16, d): every pairwise decay is
    formed inside the Pallas calls (the pairwise form held several)."""
    inputs = rule_inputs(192, 1.0, dk=24)
    rule = jax.checkpoint(lambda *a: jnp.sum(kda_chunked(*a)))
    text = jax.jit(jax.grad(rule, argnums=range(5))).lower(
        *inputs).as_text()
    # the kernels' turned blocks, (16, d, 128) a program, are there
    assert "16x24x128xf32" in text
    assert not re.search(r"x16x16x24x", text)


def test_the_pairs_lie_under_their_scope_inside_the_scan():
    """`kda.pairs` inside `kda.scan`, forward, recomputed and backward."""
    block, params, _, _ = kda_block()
    f = lambda pm, xx: jnp.sum(system_call(block, pm, xx))
    hlo = jax.jit(jax.grad(f)).lower(params, jnp.ones((2, 32, 16))).compile(
    ).as_text()
    paths = set(re.findall(r'op_name="([^"]*kda\.pairs)/', hlo))
    assert any("jvp(kda.scan)/" in p and "transpose" not in p
               for p in paths)
    assert any("transpose(jvp(kda.scan))/" in p
               and "rematted_computation" not in p for p in paths)
    assert any("transpose(jvp(kda.scan))/" in p
               and "rematted_computation" in p for p in paths)


def test_the_pairs_literal_is_the_programs(monkeypatch):
    """A name of its own, no owner's and none of the benchmark's three."""
    monkeypatch.syspath_prepend(BENCH)
    literals = _load(os.path.join(BENCH, "kda_scopes.py"),
                     "kda_scopes_literals")
    assert decoder.KDA_PAIRS == "kda.pairs"
    assert decoder.KDA_PAIRS not in KDA_SCOPES + OWNER_SCOPES
    assert decoder.KDA_PAIRS not in literals.SCOPES


def pairs_step():
    """Two steps of 1000 ns.  In each: the scan's body 30, of which its
    pairs 8 (a call inside the loop); the projections 40; the scan again
    under the checkpoint 30, its pairs 6; the scan transposed 60, its
    pairs' gradient 14; the optimizer 45."""
    g = "jit(tpumx_train_step)/train_step.grad/"
    back = g + "transpose(jvp(train_step.grad))/jvp()/checkpoint/"
    body = "while/body/closed_call/"
    ops = {"scan": ("convolution fusion", g + "jvp(kda.scan)/" + body
                    + "dot_general:"),
           "pairs": ("custom-call", g + "jvp(kda.scan)/" + body
                     + "kda.pairs/pallas_call:"),
           "proj": ("convolution fusion", g + "jvp(kda.project)/"
                    "dot_general:"),
           "again": ("convolution fusion", back + "rematted_computation/"
                     "kda.scan/" + body + "dot_general:"),
           "pairs_again": ("custom-call", back + "rematted_computation/"
                           "kda.scan/" + body + "kda.pairs/pallas_call:"),
           "scan_back": ("convolution fusion", back + "transpose(jvp("
                         "kda.scan))/" + body + "dot_general:"),
           "pairs_back": ("custom-call", back + "transpose(jvp(kda.scan))/"
                          + body + "checkpoint/kda.pairs/pallas_call:"),
           "opt": ("loop fusion", "jit(tpumx_train_step)/"
                   "train_step.optimizer/add:")}
    name = {k: f"%{k} = f32[8] fusion(f32[8] %p)" for k in ops}
    meta = {name[k]: {"hlo_category": c, "tf_op": t}
            for k, (c, t) in ops.items()}
    lines = {"XLA Modules": [], "XLA Ops": []}
    for t0 in (0, 1000):
        lines["XLA Modules"].append(("jit_tpumx_train_step(1)", t0, 1000))
        at = t0
        for k, d in (("scan", 22), ("pairs", 8), ("proj", 40),
                     ("again", 24), ("pairs_again", 6), ("scan_back", 46),
                     ("pairs_back", 14), ("opt", 45)):
            lines["XLA Ops"].append((name[k], at, d))
            at += d
    return {"devices": {"/device:TPU:0": {"lines": lines, "meta": meta}},
            "host": []}


def test_the_pairs_scope_reads_its_calls_and_nothing_else(monkeypatch):
    """The name splits out the pairs' calls, forward, recomputed and
    backward, and the scan's own reader still takes them in."""
    monkeypatch.syspath_prepend(BENCH)
    import pass_scopes
    import xplane
    pairs = lambda trace: pass_scopes.scope_ms(trace, (decoder.KDA_PAIRS,))
    assert pairs(pairs_step()) == pytest.approx(28e-6)
    scan = _load(os.path.join(BENCH, "layer_metrics", "kda_scan_ms.py"),
                 "kda_scan_ms_reader")
    assert scan.read({"trace": pairs_step()}) == pytest.approx(120e-6)
    # no trace, an empty one, and programs without the name: None, never 0
    for trace in [None, {"devices": {}, "host": []}] + [
            xplane.load(os.path.join(BENCH, "fixtures", f + ".xplane.pb.gz"))
            for f in ("decoder_step", "conv_step")]:
        assert pairs(trace) is None


def test_the_state_is_f32_and_the_output_in_vs_type():
    q, k, v, g, beta = rule_inputs(128, 1.0)
    low = lambda a: a.astype(jnp.bfloat16)
    got = kda_chunked(low(q), low(k), low(v), g, beta)
    assert got.dtype == jnp.bfloat16
    with jax.default_matmul_precision("highest"):
        want = reference.recurrence(*(low(a).astype(jnp.float32)
                                      for a in (q, k, v)), g, beta)
    # the f32 rule on the same bf16 inputs, rounded once at the end
    assert rel(got.astype(jnp.float32), want) < 4e-3


def test_the_counter_counts_every_trace():
    before = dispatch_counts["kda_chunked"]
    inputs = rule_inputs(64, 1.0)
    jax.jit(kda_chunked).lower(*inputs)
    jax.jit(lambda *a: kda_chunked(*a, chunk=32)).lower(*inputs)
    assert dispatch_counts["kda_chunked"] == before + 2


# -- the mixers --------------------------------------------------------------------------
def drawn(block, seed):
    """Every parameter of the block at normal(0, 0.3), A_log at log U(1, 16)
    and dt_bias its init: (system params, the reference's plain dict)."""
    block.initialize()
    keys = iter(jax.random.split(jax.random.key(seed), 40))
    for name, p in block.collect_params().items():
        if not name.endswith(("A_log", "dt_bias")):
            p.set_data(np.asarray(0.3 * jax.random.normal(next(keys),
                                                          p.shape)))
    return {k: p.data()._data for k, p in block.collect_params().items()}


def by_end(params, end):
    """The block's own parameter `end` (its name after the block's
    prefix), or its one norm's scale for "gamma"."""
    return next(v for k, v in params.items()
                if k.split("_", 1)[1] == end
                or end == "gamma" and k.endswith("_gamma"))


def kda_block(seed=0):
    block = KimiDeltaAttention(16, num_heads=2, head_dim=8, taps=4,
                               epsilon=1e-5)
    params = drawn(block, seed)
    plain = {k: by_end(params, v)
             for k, v in config_mod.KDA_PARAMS.items()}
    plain["o_norm"] = by_end(params, "gamma")
    hp = dict(kda=dict(heads=2, head_dim=8, taps=4), eps=1e-5)
    return block, params, plain, hp


def test_the_mixer_is_the_reference_forward_and_in_its_gradients():
    block, params, plain, hp = kda_block()
    assert float(by_end(params, "A_log").min()) >= 0.0
    assert float(by_end(params, "dt_bias").max()) < 0.0   # dt <= 0.1
    # a chunk of 64 and a part: the state crosses a boundary, the end padded
    x = jax.random.normal(jax.random.key(1), (2, 100, 16))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda pm: system_call(block, pm, x))(params)
        want = reference.kda(x, plain, hp)
        assert got.shape == (2, 100, 16)
        assert rel(got, want) < 1e-5
        grads = jax.jit(jax.grad(lambda pm: jnp.sum(jnp.sin(system_call(
            block, pm, x)))))(params)
        ref_grads = jax.jit(jax.grad(lambda p: jnp.sum(jnp.sin(
            reference.kda(x, p, hp)))))(plain)
    names = dict(config_mod.KDA_PARAMS, o_norm="gamma")
    assert len(names) == len(params) == 16
    for k, end in names.items():
        g = by_end(grads, end)
        assert g.shape == ref_grads[k].shape
        assert rel(g, ref_grads[k]) < 1e-4, k


@pytest.mark.parametrize("at", [0, 9, 30, 70])
def test_the_mixer_is_causal(at):
    """A change of the input at position s moves no output before s (70:
    in the second chunk of 64)."""
    block, params, _, _ = kda_block(seed=3)
    x = jax.random.normal(jax.random.key(2), (1, 100, 16))
    run = jax.jit(lambda xx: system_call(block, params, xx))
    moved = np.abs(np.asarray(run(x.at[0, at].add(1.0)) - run(x))).max(-1)[0]
    assert (moved[:at] == 0).all() and moved[at] > 1e-4


def test_the_mixer_lies_under_its_three_scopes_in_the_program():
    block, params, _, _ = kda_block()
    text = jax.jit(lambda pm, xx: system_call(block, pm, xx)).lower(
        params, jnp.ones((2, 32, 16))).as_text(debug_info=True)
    assert re.search(r"kda\.project/.*dot_general", text)
    assert re.search(r"kda\.conv/.*(logistic|rsqrt)", text)
    assert re.search(r"kda\.scan/.*while", text)


def test_scope_names_are_the_benchmarks_literals(monkeypatch):
    monkeypatch.syspath_prepend(BENCH)
    literals = _load(os.path.join(BENCH, "kda_scopes.py"),
                     "kda_scopes_literals")
    assert literals.SCOPES == KDA_SCOPES == ("kda.project", "kda.conv",
                                             "kda.scan")
    # kept out of the owners, which test_named_passes.py holds equal to the
    # benchmark's accepted literals (ROADMAP B1)
    assert not set(KDA_SCOPES) & set(OWNER_SCOPES)


@pytest.mark.parametrize("rotary,wrong", [(False, None),
                                          (True, "mla_rotary")],
                         ids=["no-positions", "turned"])
def test_the_latent_layer_without_a_query_rank(rotary, wrong):
    """LatentAttention(q_rank=None): q = x W_q; with rotary=False nothing
    turns (the reference's MLA), with rotary the rope parts turn by
    interleaved pairs (the reference's `mla_rotary` variant)."""
    block = LatentAttention(16, 2, None, 8, 8, 4, 6, rope_theta=1e4,
                            epsilon=1e-5, rotary=rotary)
    params = drawn(block, 5)
    assert not any("q_a" in k or "q_b" in k for k in params)
    plain = {k: by_end(params, k + "_weight") for k in ("q", "kv_a", "kv_b",
                                                         "o")}
    plain["kv_a_norm"] = by_end(params, "gamma")
    hp = dict(mla=dict(heads=2, nope=8, rope=4, v=6, kv_rank=8, theta=1e4),
              eps=1e-5)
    x = jax.random.normal(jax.random.key(6), (2, 24, 16))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda pm: system_call(block, pm, x))(params)
        want = reference.mla(x, plain, hp, wrong)
        assert rel(got, want) < 1e-5
        grads = jax.jit(jax.grad(lambda pm: jnp.sum(jnp.sin(system_call(
            block, pm, x)))))(params)
        ref_grads = jax.jit(jax.grad(lambda p: jnp.sum(jnp.sin(
            reference.mla(x, p, hp, wrong)))))(plain)
    for k in ("q", "kv_a", "kv_b", "o"):
        assert rel(by_end(grads, k + "_weight"), ref_grads[k]) < 1e-4, k
    # the other setting is another layer
    assert rel(got, reference.mla(x, plain, hp, None if wrong else
                                  "mla_rotary")) > 1e-3


# -- the model against the reference ---------------------------------------------------
def toy_cfg(chunk):
    """The configuration file at its rehearsal sizes, f32 so that the
    comparison is of the equations and not of bf16's rounding; the head's
    loss whole (chunk None: every position's logits) or in chunks; the
    rule's 128 positions cross one boundary of its chunks of 64."""
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearse"])
    # at a thirtieth of the width, five times the published initializer's
    # spread gives the mixers and the routers values of a size
    cfg["system"] = dict(cfg["system"], dtype="float32", init_sigma=0.1,
                         loss_chunk=chunk)
    cfg["reference_comparison"] = dict(cfg["reference_comparison"],
                                       logit_stride=4 if chunk else 1)
    return cfg


MIX = {"batch": 2, "seq_len": 128, "block_steps": 2}
OUTPUTS = ["logits", "loss", "route_choice", "route_weights", "kda_scan"] \
    + [f"{kind}_{leaf}" for leaf in config_mod.LEAVES
       for kind in ("grad", "update")]


@pytest.fixture(scope="module", params=[32], ids=["chunked"])
def sides(request):
    """The system's side once, and a function for the reference's side
    that keeps its compiled programs, as the readings tool does.  The
    timed step takes its first step on a twin of the net, drawn from the
    same seed: a step donates the weights that the reference reads."""
    cfg = toy_cfg(request.param)
    net, _ = config_mod.build(cfg, MIX, seed=7)
    batch = config_mod.make_batch(cfg, MIX, seed=7)
    got, aux = config_mod.system_forward(net, batch, 2)
    programs = {}

    def want(**kw):
        return config_mod.reference_outputs(reference, net, aux,
                                            programs=programs, **kw)
    twin, _ = config_mod.build(cfg, MIX, seed=7)
    assert all(np.array_equal(a.data()._data, b.data()._data) for a, b in zip(
        net.collect_params().values(), twin.collect_params().values()))
    got.update(config_mod.system_step(twin, batch, aux))
    return got, aux, want, programs, net, twin


@pytest.mark.parametrize("key", OUTPUTS)
def test_system_matches_reference(sides, key):
    got, _, want, _, _, _ = sides
    honest = want()
    assert set(got) == set(honest) == set(OUTPUTS)
    assert got[key].shape == honest[key].shape
    assert rel(got[key], honest[key]) < TOLERANCE, key


@pytest.mark.parametrize("wrong", reference.WRONG)
def test_each_wrong_variant_fails_the_tolerance(sides, wrong):
    got, _, want, programs, _, _ = sides
    other = want(wrong=wrong)
    with np.errstate(invalid="ignore", over="ignore"):
        errors = {k: rel(got[k], other[k]) for k in OUTPUTS}
    assert not max(errors.values()) <= 10 * TOLERANCE, errors
    assert len(programs) == 1       # a traced index: one program for all


def test_the_rule_alone_shows_only_what_touches_it(sides):
    """The rule alone, on seeded inputs: only the variants of the rule read
    off there, and the all-bf16 control's state besides."""
    got, aux, want, _, _, _ = sides
    assert got["kda_scan"].shape == (1, MIX["seq_len"], 2, 16)
    for wrong in reference.WRONG:
        error = rel(got["kda_scan"], want(wrong=wrong)["kda_scan"])
        if wrong in ("decay_per_head", "no_delta", "chunk_state_dropped"):
            assert error > 10 * TOLERANCE, wrong
        else:
            assert error < TOLERANCE, wrong
    assert rel(got["kda_scan"], want(low="all")["kda_scan"]) > TOLERANCE


def test_the_model_holds_what_the_configuration_says(sides):
    got, aux, _, _, net, _ = sides
    layers = list(net.layers._children.values())
    assert [type(l.attention).__name__ for l in layers] == [
        "KimiDeltaAttention"] * 3 + ["LatentAttention", "KimiDeltaAttention"]
    assert [l.attention.q_conv_weight.shape for l in layers
            if isinstance(l.attention, KimiDeltaAttention)] == [(32, 4)] * 4
    latent = layers[3].attention
    assert not latent._rotary and "q_a_norm" not in latent._children
    assert not hasattr(layers[0].ffn, "expert_w1")
    assert all("shared" in l.ffn._children for l in layers[1:])
    assert "head_weight" in net._reg_params
    assert len(aux["chosen"]) == len(aux["inputs"]) == 4
    assert all(c.shape == (2 * MIX["seq_len"], 2) for c in aux["chosen"])


def test_the_tests_reference_is_the_benchmarks():
    """One copy: tests/references/kda_decoder.py loads the benchmark's
    reference by path."""
    assert os.path.samefile(reference.PATH, os.path.join(
        BENCH, "references", NAME + ".py"))
    assert reference.recurrence.__module__ == "kimi_linear_reference"


# -- the chip's share --------------------------------------------------------------------
def test_the_parameters_are_the_issues_count():
    """The published widths at the cut's depth, experts and vocabulary:
    602,449,792 trainable parameters, part by part as the configuration's
    `deployment` lists them (shapes only: nothing is drawn)."""
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        cfg = json.load(f)
    net = decoder.CausalLM(config_mod.model_config(cfg))
    count = lambda block: sum(
        int(np.prod(p.shape)) for p in block.collect_params().values()
        if p.grad_req != "null")
    parts = cfg["deployment"]["parameters"]
    layers = list(net.layers._children.values())
    assert count(net) == parts["total"] == 602449792
    assert count(layers[0].attention) == parts["kda_mixer"]
    assert count(layers[3].attention) == parts["mla_mixer"]
    assert count(layers[0].ffn) == parts["dense_swiglu"]
    assert count(layers[4].ffn) == parts["expert_ffn"]
    assert [count(l) for l in layers] == [
        parts["kda_dense_layer"]] + [parts["kda_expert_layer"]] * 2 + [
        parts["mla_expert_layer"], parts["kda_expert_layer"]]
    # 8.98 GiB at 14 + 2 bytes a parameter
    assert round(16 * parts["total"] / 2 ** 30, 2) == 8.98


def test_flops_per_sample_is_the_issues_reckoning():
    """About 777 M model FLOPs a token forward, the four KDA
    mixers 44% of them, MLA's scores 84 M at T 8,192."""
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", "pretrain8k-b2.json")) as f:
        mix = json.load(f)
    t = mix["seq_len"]
    parts = config_mod.flops_parts(cfg, mix)
    total = config_mod.flops_per_sample(cfg, mix)
    assert total / (3 * t) / 1e6 == pytest.approx(777, abs=1)
    share = lambda *names: 100 * sum(parts[n] for n in names) / total
    assert share("kda_project", "kda_scan") == pytest.approx(43.5, abs=0.5)
    assert parts["scores"] / (3 * t) / 1e6 == pytest.approx(83.9, abs=0.1)
