"""The names ISSUE 36 gives the step's passes: the three flash kernels each
under a jax.named_scope of its own inside the jitted `_fwd` / `_bwd_call`
(`kernels.flash_attention.FLASH_SCOPES`), the dense gated MLP under
`mlp.dense`, and `models.decoder.OWNER_SCOPES`, the union of the decoder
blocks' names.  They are what `benchmark/pass_scopes.py` holds as literals;
they stand in the program's op paths in every caller, forward, backward and
under a layer's checkpoint; and they are metadata and nothing else: the
lowered program without its debug information is the same text with the
scopes and without.

The op paths are read from the compiled program's `op_name`s, as a trace
shows them: the lowered module holds a jitted `_fwd`'s inside relative to
its own name stack, and XLA joins the two where it inlines the call.  The
kernels run in interpret mode here, which lowers a `pallas_call` to the
loops it stands for (the component `pallas_call` itself is Mosaic's);
`tests/test_tpu_compile.py` lowers them through Mosaic and `chip_smoke.py`
phase D reads a chip's trace.  Under Mosaic the kernel's serialised module
in the custom call's `backend_config` carries its locations, the name stack
among them, so there the text does differ, by that payload alone: jax's
persistent compile cache, whose key leaves debug information out, serves a
program that differs only in names the OLD executable and its op paths,
but not one that holds a kernel (PERF.md section 7).
"""
import contextlib
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest

import tpu_mx as mx
from tpu_mx.kernels import flash_attention as fa
from tpu_mx.kernels.flash_attention import FLASH_SCOPES
from tpu_mx.models.bert import SelfAttention
from tpu_mx.models.decoder import (ATTENTION_GATE_SCOPES, ATTENTION_SCOPES,
                                   DECODER_SCOPES, OWNER_SCOPES, CausalLM,
                                   LatentAttention)
from tpu_mx.parallel.train_step import STEP_SCOPES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
FWD, DQ, DKV = FLASH_SCOPES
REMAT = "rematted_computation"


@contextlib.contextmanager
def flash_arm():
    """What a TPU process dispatches, with the kernels in interpret mode."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        patch.setattr(fa, "_interpret", lambda: True)
        patch.setenv("TPUMX_ATTENTION", "flash")
        jax.clear_caches()
        yield
    jax.clear_caches()


@pytest.fixture
def flash():
    with flash_arm():
        yield


def test_the_names_are_the_benchmarks_literals(monkeypatch):
    monkeypatch.syspath_prepend(BENCH)      # it imports its three neighbours
    spec = importlib.util.spec_from_file_location(
        "pass_scopes_literals", os.path.join(BENCH, "pass_scopes.py"))
    literals = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(literals)
    assert literals.FLASH == FLASH_SCOPES == ("flash.fwd", "flash.dq",
                                              "flash.dkv")
    assert literals.MLP_DENSE == OWNER_SCOPES[-1] == "mlp.dense"
    # the union, in the three tuples' order, and one name more
    assert OWNER_SCOPES == DECODER_SCOPES + ATTENTION_SCOPES \
        + ATTENTION_GATE_SCOPES + ("mlp.dense",)
    assert len(set(OWNER_SCOPES)) == len(OWNER_SCOPES)
    assert literals.MODEL == OWNER_SCOPES
    assert literals.OWNERS == OWNER_SCOPES + tuple(
        s for s in STEP_SCOPES if s != "train_step.grad")
    assert not set(FLASH_SCOPES) & set(literals.OWNERS)
    assert set(literals.ATTEND + literals.PROJECT) <= set(OWNER_SCOPES)


def lower(block, *inputs):
    """The gradient program of the block's first output's sum, lowered."""
    params = {k: p.data()._data for k, p in block.collect_params().items()}

    def loss(pm, *xs):
        out = block._functional_call(pm, jax.random.PRNGKey(0), True, xs)[0]
        return jnp.sum(out[0].astype(jnp.float32))
    return jax.jit(jax.grad(loss)).lower(params, *inputs)


def paths(lowered):
    """Every op path of the compiled program."""
    return set(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))


def holds(found, *parts):
    """Does one name stack hold all the parts, as whole components (bare or
    wrapped in jvp( ) and transpose( )), in this order?"""
    def components(path):
        return [re.sub(r"transpose\(|jvp\(|\)", "", c)
                for c in path.split("/")]
    parts = components("/".join(parts))
    for path in found:
        at, comps = 0, components(path)
        for part in parts:
            if part not in comps[at:]:
                break
            at = comps.index(part, at) + 1
        else:
            return True
    return False


def toy_lm():
    """A dense layer 0 with a window layer, then an expert layer (with a
    shared expert) whose queries see the whole past; one checkpoint a
    layer."""
    window = dict(kind="grouped_query", num_heads=4, num_kv_heads=2,
                  head_dim=64, rope_theta=1e4, window=128)
    full = dict(kind="grouped_query", num_heads=2, num_kv_heads=2,
                head_dim=64, rope_theta=1e4, gate=True)
    net = CausalLM(dict(
        vocab_size=64, units=64, num_layers=2, num_dense_layers=1,
        dense_hidden=128, attention=[window, full],
        moe=dict(hidden_size=32, num_experts=4, top_k=2, held_experts=(0, 2),
                 shared_hidden=32)), remat=True)
    net.initialize(mx.init.Normal(0.02))
    return net


@pytest.fixture(scope="module")
def lm_paths():
    with flash_arm():
        return paths(lower(toy_lm(), jnp.zeros((1, 256), jnp.int32)))


@pytest.mark.parametrize("scope", ["attn.window", "attn.full"])
@pytest.mark.parametrize("kernel", FLASH_SCOPES)
def test_a_decoder_step_names_each_kernel_under_each_attention_scope(
        lm_paths, scope, kernel):
    # inside the jitted call, so that the layers share one traced copy
    inner = "jit(_fwd)" if kernel == FWD else "jit(_bwd_call)"
    assert holds(lm_paths, scope, inner, kernel)


def test_the_forward_kernel_runs_again_under_the_checkpoint(lm_paths):
    for scope in ("attn.window", "attn.full"):
        assert holds(lm_paths, REMAT, scope, "jit(_fwd)", FWD)
        # the first forward pass is not under it
        assert [p for p in lm_paths if holds([p], scope, FWD)
                and REMAT not in p]
    # and the backward kernels do not
    assert not holds(lm_paths, REMAT, DQ)
    assert not holds(lm_paths, REMAT, DKV)


def test_the_dense_mlp_has_a_name_and_the_shared_expert_keeps_its_outer_one(
        lm_paths):
    assert holds(lm_paths, "mlp.dense", "dot_general")
    assert holds(lm_paths, REMAT, "mlp.dense")
    assert holds(lm_paths, "moe.shared", "mlp.dense", "dot_general")
    # layer 0's is under no expert layer's name
    assert [p for p in lm_paths if "mlp.dense" in p and "moe.shared" not in p]


def test_latent_attention_names_the_kernels_under_mla_attend(flash):
    block = LatentAttention(64, num_heads=2, q_rank=32, kv_rank=32,
                            nope_dim=48, rope_dim=16, v_dim=64,
                            rope_theta=1e4)
    block.initialize(mx.init.Normal(0.02))
    found = paths(lower(block, jnp.ones((1, 256, 64))))
    for kernel in FLASH_SCOPES:
        assert holds(found, "mla.attend", kernel), kernel
        assert not holds(found, "mla.project", kernel)


def test_berts_attention_names_the_kernels_at_kv_512(flash):
    block = SelfAttention(128, 2)
    block.initialize(mx.init.Normal(0.02))
    found = paths(lower(block, jnp.ones((2, 512, 128)),
                        jnp.array([512, 384], jnp.int32)))
    for kernel in FLASH_SCOPES:
        assert holds(found, kernel), kernel
    assert holds(found, "jit(_fwd)", FWD) and holds(found, "jit(_bwd_call)",
                                                    DKV)


@pytest.mark.parametrize("build, inputs", [
    (toy_lm, lambda: (jnp.zeros((1, 256), jnp.int32),)),
    (lambda: SelfAttention(128, 2), lambda: (jnp.ones((1, 512, 128)),)),
], ids=["decoder", "bert_attention"])
def test_the_names_are_metadata_and_nothing_else(flash, monkeypatch, build,
                                                 inputs):
    """The program without its debug information is one text, with the
    scopes and with every jax.named_scope a null context; the jitted `_fwd`
    and `_bwd_call` are traced again for the second lowering."""
    mx.random.seed(3)
    net = build()
    net.initialize(mx.init.Normal(0.02))
    named = lower(net, *inputs())
    assert holds(paths(named), "jit(_fwd)", FWD)
    jax.clear_caches()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = lower(net, *inputs())
    found = paths(bare)
    assert not [p for p in found if any(
        s in p.split("/") for s in FLASH_SCOPES + OWNER_SCOPES)]
    assert holds(found, "jit(_fwd)") and holds(found, "jit(_bwd_call)")
    assert named.as_text() == bare.as_text()
