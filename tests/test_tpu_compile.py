"""Compile the Pallas kernels for a TPU v5e without having one.

libtpu can describe a chip topology that is not attached
(`jax.experimental.topologies.get_topology_desc`) and compile for its
devices: the real XLA:TPU and Mosaic compilers run, nothing executes.  So a
kernel Mosaic refuses is caught here on the CPU, before chip time is spent;
what only execution shows (numerics, placement) stays with the on-chip tier
(tests/test_tpu_chip.py) and chip_smoke.py.

The kernels pick interpret mode from `jax.default_backend()` at trace time;
the fixture turns that off for the kernels under test, so what is lowered is
what a TPU process lowers.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.sharding import SingleDeviceSharding

from tpu_mx.kernels import flash_attention as fa
from tpu_mx.kernels import paged_attention as pa


@pytest.fixture(scope="module")
def v5e():
    """One compile-only device of a v5e 2x2 host."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or one that cannot do this
        pytest.skip("libtpu cannot make a compile-only v5e:2x2 topology: "
                    f"{type(e).__name__}: {e}"[:300])
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices[0]


@pytest.fixture
def mosaic(monkeypatch):
    """Lower the real kernels, not their interpret-mode stand-ins."""
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(pa, "_interpret", lambda: False)
    pa._kernel_call.cache_clear()
    yield
    pa._kernel_call.cache_clear()


def _compile(fn, device, *shapes_dtypes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=SingleDeviceSharding(device))
            for s, d in shapes_dtypes]
    return jax.jit(fn).lower(*args).compile()


def _mosaic_calls(compiled):
    return compiled.as_text().count("tpu_custom_call")


def test_mosaic_really_runs(v5e):
    """A kernel Mosaic is known to refuse must fail here too — otherwise a
    green run below proves nothing."""
    def bad_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...].reshape(128, 8)

    bad = pl.pallas_call(
        bad_kernel, out_shape=jax.ShapeDtypeStruct((128, 8), jnp.float32))
    with pytest.raises(Exception, match="Mosaic failed to compile"):
        _compile(bad, v5e, ((8, 128), jnp.float32))


@pytest.mark.parametrize("masked_dropout", [False, True])
def test_flash_fwd_bwd_compiles_for_v5e(v5e, mosaic, masked_dropout):
    """BERT-base's flash shape (12 heads of 64, T=512, bf16), forward and
    backward; the second case adds the key-padding mask and the in-kernel
    dropout (TPU PRNG — no interpret lowering, so never traced on CPU)."""
    b, h, t, d = 8, 12, 512, 64
    qkv = ((b, h, t, d), jnp.bfloat16)
    assert fa.supported(qkv[0], qkv[1], kv_len=t,
                        dropout_rate=0.1 if masked_dropout else 0.0)

    def loss(q, k, v, valid_length, seed):
        kw = dict(valid_length=valid_length, dropout_rate=0.1,
                  dropout_seed=seed) if masked_dropout else {}
        return fa.mha_flash_attention(q, k, v, **kw).astype(
            jnp.float32).sum()

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), v5e, qkv, qkv,
                        qkv, ((b,), jnp.int32), ((1,), jnp.int32))
    assert _mosaic_calls(compiled) >= 3  # forward, dq, dk/dv


@pytest.mark.parametrize("window", [None, 4096])
def test_windowed_grouped_flash_compiles_for_v5e(v5e, mosaic, window):
    """smallthinker-21ba3b.extend16k's attention (28 query heads over 4
    key/value heads of 128, T = 16,384, bf16, causal), forward and backward:
    the global layers' kernels and, with the window, those whose index maps
    clamp a skipped step to the nearest block that runs."""
    q, kv = ((1, 28, 16384, 128), jnp.bfloat16), \
        ((1, 4, 16384, 128), jnp.bfloat16)
    assert fa.supported(q[0], q[1], kv_heads=4, window=window)

    def loss(q, k, v):
        return fa.mha_flash_attention(q, k, v, causal=True,
                                      window=window).astype(jnp.float32).sum()

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), v5e, q, kv, kv)
    assert _mosaic_calls(compiled) >= 3  # forward, dq, dk/dv
    assert [tuple(a.shape) for a in jax.eval_shape(
        jax.grad(loss, argnums=(0, 1, 2)),
        *(jax.ShapeDtypeStruct(*x) for x in (q, kv, kv)))] \
        == [q[0], kv[0], kv[0]]     # dk, dv per key/value head


@pytest.mark.parametrize("tq", [1, 4])
def test_paged_decode_compiles_for_v5e(v5e, mosaic, tq):
    """The paged decode kernel at a decoder-sized geometry (16 heads of
    128, bf16 pool): one-token decode and a 4-wide draft window."""
    b, h, d, nb, block_size, num_blocks = 8, 16, 128, 8, 16, 64
    assert pa.supported(d, jnp.bfloat16, block_size)
    pool = ((num_blocks, block_size, h, d), jnp.bfloat16)
    compiled = _compile(pa.paged_attention, v5e,
                        ((b, tq, h, d), jnp.bfloat16), pool, pool,
                        ((b, nb), jnp.int32), ((b,), jnp.int32))
    assert _mosaic_calls(compiled) >= 1


@pytest.mark.parametrize("cell", ["glm-4.7-flash.pretrain4k",
                                  "smallthinker-21ba3b.extend16k"])
def test_the_expert_layers_loop_over_slabs_compiles_for_v5e(v5e, cell):
    """The dropless expert layer at the two decoder cells' shapes (bf16,
    under a checkpoint, every gradient): XLA:TPU takes the grouped products
    inside a loop with a dynamic trip count, forward and in the layer's own
    backward rule, and builds the slab once for the loop, not once a slab."""
    from tpu_mx.parallel import moe
    (S, k, H, U, F), kw = {
        "glm-4.7-flash.pretrain4k": (
            (8192, 4, 8, 2048, 1536), dict(scoring="sigmoid")),
        "smallthinker-21ba3b.extend16k": (
            (16384, 6, 16, 2560, 768),
            dict(scoring="softmax", activation="relu"))}[cell]
    E, bf16 = 64, jnp.bfloat16

    @jax.checkpoint
    def layer(x, gw, w1, w3, w2):
        return moe._dropless_forward(x, x, gw, jnp.zeros(E), w1, w3, w2,
                                     top_k=k, lo=0, scaling=1.8, **kw)[0]

    def loss(*a):
        return layer(*a).astype(jnp.float32).sum()
    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4)), v5e, ((S, U), bf16),
        ((E, U), bf16), ((H, U, F), bf16), ((H, U, F), bf16),
        ((H, F, U), bf16))
    text = compiled.as_text()
    assert S * k // moe.head_rows(S * k, H, E) in (2, 4)
    # the loop is there, and XLA:TPU's kernel stands in it once a product
    # of ONE slab: the rule's 3 computed again and 6 transposed (nothing
    # reads the forward pass of a program that returns gradients alone)
    assert " while(" in text and " conditional(" not in text
    assert len(re.findall(r"^\s*%?ragged-dot-none[.\d]* = ", text,
                          re.M)) == 9


@pytest.mark.parametrize("heads,window", [(48, None), (72, 512)])
def test_narrow_window_flash_compiles_for_v5e(v5e, mosaic, heads, window):
    """laguna-s-2.1.pretrain8k's attention (bf16, causal, T = 8,192, heads
    of 128): 48 query heads over 8 on the full layers (groups of 6), 72
    over 8 with a window of 512, half a key block, on the others (groups of
    9), forward and backward."""
    q, kv = ((1, heads, 8192, 128), jnp.bfloat16), \
        ((1, 8, 8192, 128), jnp.bfloat16)
    assert fa.supported(q[0], q[1], kv_heads=8, window=window)

    def loss(q, k, v):
        return fa.mha_flash_attention(q, k, v, causal=True,
                                      window=window).astype(jnp.float32).sum()

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), v5e, q, kv, kv)
    assert _mosaic_calls(compiled) >= 3  # forward, dq, dk/dv


def test_the_gated_mixed_decoders_step_compiles_for_v5e(v5e, mosaic,
                                                        monkeypatch):
    """The whole train step of a small head-gated mixed decoder (ISSUE 34:
    a full layer of 12 gated heads over 2 that turns half of each head by
    YaRN's frequencies, two layers of 18 with a window of 256, a quarter of
    a key block; a dense layer, then sigmoid-routed experts with a shared
    one; the head's loss in chunks; bf16, AdamW with f32 masters, one
    checkpoint a layer) as a TPU process builds it: the dispatch takes the
    flash kernels, and XLA:TPU and Mosaic take the step."""
    import tpu_mx as mx
    from tpu_mx import gluon, nd
    from tpu_mx.models.decoder import CausalLM
    from tpu_mx.parallel import CompiledTrainStep
    gated = lambda heads, **kw: dict(
        kind="grouped_query", num_heads=heads, num_kv_heads=2, head_dim=64,
        gate=True, **kw)
    full = gated(12, rope_theta=5e5, rotary_dim=32, yarn=dict(
        factor=128, original_length=1024, attention_factor=1.485))
    window = gated(18, rope_theta=1e4, window=256)
    net = CausalLM(dict(
        vocab_size=1024, units=256, num_layers=3, num_dense_layers=1,
        dense_hidden=512, attention=[full, window, window],
        moe=dict(hidden_size=128, num_experts=16, top_k=3,
                 held_experts=(0, 2), scaling=2.5, shared_hidden=128,
                 scoring="sigmoid"), loss_chunk=256, logits_stride=16),
        dtype="bfloat16", remat=True)
    net.initialize(mx.init.Normal(0.02))
    step = CompiledTrainStep(
        net, gluon.loss.PassThrough(), mx.optimizer.create(
            "adamw", learning_rate=3e-6, multi_precision=True))
    tokens = nd.zeros((1, 1024), dtype="int32")._data
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step._build(2)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=SingleDeviceSharding(v5e)), tree)
    compiled = step._jitted.lower(*on_chip((
        step.values, step.masters, step.opt_states, step._efs, {},
        jnp.float32(1), jnp.float32(3e-6), jax.random.PRNGKey(0),
        tokens, tokens))).compile()
    # a layer's forward kernel, the same again under its checkpoint, and
    # its two backward kernels, jitted once a signature: the full layer's
    # and the window layers'
    assert _mosaic_calls(compiled) >= 8
    assert compiled.memory_analysis().temp_size_in_bytes > 0
    # ISSUE 36: XLA:TPU keeps the scope round a Mosaic call: every kernel
    # of the step stands under its own name, the forward one again under
    # the checkpoint (chip_smoke.py phase D reads the same from a trace)
    paths = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    # (XLA:TPU's own grouped product is such a call too, without a path)
    paths = [p for p in paths if "pallas_call" in p]
    assert len(paths) >= 8 and all(p.endswith("/pallas_call")
                                   and p.split("/")[-2].startswith("flash.")
                                   for p in paths)
    for scope in ("attn.full", "attn.window"):
        for inside in (f"({scope})/jit(_fwd)/flash.fwd",
                       f"rematted_computation/{scope}/jit(_fwd)/flash.fwd",
                       f"/{scope}/jit(_bwd_call)/flash.dq",
                       f"/{scope}/jit(_bwd_call)/flash.dkv"):
            assert [p for p in paths if inside + "/pallas_call" in p], inside
