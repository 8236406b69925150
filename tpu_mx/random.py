"""RNG state: the `mx.random.seed()` layer over JAX's splittable PRNG.

The reference keeps per-device sampler states inside the ResourceManager
(REF:src/resource.cc kRandom).  Here a process-global key is split per draw in
eager mode; inside a `hybridize()` trace the active `KeyHolder` (installed by
Block.apply) supplies *traced* subkeys so compiled graphs stay pure and
reproducible — keys become explicit step-function inputs, the XLA-correct way.

State is DATA (docs/robustness.md "Deterministic resume"): the stream is
observable and restorable, not just reseedable.  :func:`get_state` returns an
opaque token covering BOTH generators the framework draws from — the global
JAX key and numpy's global state — and :func:`set_state` restores them
bit-exactly, which is what lets a training-state capsule (`tpu_mx/resume.py`)
make a crash-recovered run replay the exact RNG stream of the run that died.
:func:`seed` returns the prior token so tests (and capsule writers) can
save/restore the stream around themselves.

Dropout masks (:func:`dropout_keep`, and :func:`dropped` / :func:`dropout`
that apply one) are the one draw that does not come from threefry.  A mask
is a function of the sub-key its site took from this stream, of the mask's
shape and the rate, **and of the compiled program and the backend** (under
`shard_map`, of the device's mesh coordinates too, through the key): the
same key, program and hardware give the same mask every time (what the resume capsule's
bit-exact replay, `ShadowAuditor`'s re-execution and the cross-replica
fingerprint rest on), forward and backward of one step use the same one,
and a split's two halves give independent ones.  A site either draws its
mask a second time in the backward pass or holds it, a byte an element
(:func:`dropped`): the dense attention site holds, because the row
maximum's tie mask, as large, is no longer held there (ISSUE 30), and so
does the latest site traced; every other site (the hidden ones of
`nd.Dropout`, `gluon.rnn`'s between layers) draws again, for want of the
memory.  It is NOT portable: a CPU
and a TPU, or two jax versions, drop different (equally random) elements
for one seed.  A step that GSPMD partitions over a mesh keeps threefry's
masks, which do not depend on the mesh (:func:`partitioned_draws`).  The
key stream itself (splits, `get_state`/`set_state`, the step's `key` input)
is threefry as before and is portable.

The global key is genuinely process-global (one lock-guarded stream): a step
function running on a watchdog daemon thread (`supervisor.run_with_deadline`)
draws from the SAME stream the main thread would — a thread-local key would
silently hand every watchdog thread its own fresh `PRNGKey(0)` replay.
"""
from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp

__all__ = ["seed", "get_state", "set_state", "take_key", "host_rng",
           "KeyHolder", "key_scope", "dropout", "dropout_keep", "dropped",
           "scaled", "partitioned_draws", "mask_draws"]

# "rbg": dropout masks drawn through dropout_keep(); "held": sites that hold
# their mask for the backward pass (dropped(hold=True)).  Both counted where
# the call is traced (once a compilation under jit, once a call in eager
# mode), like ring_attention.dispatch_counts.  The benchmark's
# `dropout_rbg_draws` and `dropout_masks_held` read them.
mask_draws = {"rbg": 0, "held": 0}


class _GlobalRNG:
    def __init__(self):
        self.lock = threading.Lock()
        self.key = jax.random.PRNGKey(0)


_GLOBAL = _GlobalRNG()
_HOLDER = threading.local()


class KeyHolder:
    """Mutable holder threading one traced key through a functional forward."""

    def __init__(self, key):
        self.key = key

    def take(self):
        self.key, sub = jax.random.split(self.key)
        return sub


@contextlib.contextmanager
def key_scope(key):
    """Route `take_key()` to splits of `key` (used during functional apply)."""
    holder = KeyHolder(key)
    prev = getattr(_HOLDER, "holder", None)
    _HOLDER.holder = holder
    try:
        yield holder
    finally:
        _HOLDER.holder = prev


def take_key():
    holder = getattr(_HOLDER, "holder", None)
    if holder is not None:
        return holder.take()
    with _GLOBAL.lock:
        _GLOBAL.key, sub = jax.random.split(_GLOBAL.key)
    return sub


def _raw(key):
    """The words of a key, typed or already raw."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        return jax.random.key_data(key)
    return key


def dropout_keep(key, rate, shape):
    """The keep mask of every dropout site: bool, True with probability
    ``1 - rate``, independently an element, from 32 fresh bits each.

    `key` is the sub-key the site took from the stream (`take_key()`, or
    a fold of the ring's seed), typed or raw.  Its words seed an ``rbg``
    key, so the bits lower to XLA's ``rng_bit_generator`` (on a TPU the
    core's hardware generator) and not to twenty rounds of threefry2x32
    an element on the VPU; the comparison is `jax.random.bernoulli`'s
    own.  The module text says what the mask is a function of."""
    words = _raw(key).astype(jnp.uint32).reshape(-1)
    # impl="rbg" wants four words: the key's own, repeated, as jax seeds
    # its rbg keys from a threefry half-key
    rbg_key = jax.random.wrap_key_data(jnp.tile(words, 4 // words.size),
                                       impl="rbg")
    mask_draws["rbg"] += 1
    return jax.random.bernoulli(rbg_key, 1.0 - rate, shape)


def _after(key, x):
    """`key`, unchanged, but not before `x` exists.  The comparison is
    False for every number (True only for a NaN, which has poisoned the
    step anyway) and XLA cannot fold it, so the draw that takes this key
    is scheduled where its mask is used.  Without it every draw of a
    step, a function of the step's key alone, is hoisted to the
    program's start, and the generator's words (four bytes an element)
    wait there for their consumers."""
    x = jax.tree_util.tree_leaves(x)[0]
    if not x.size:
        return key
    first = x.reshape(-1)[0]
    return key ^ (first != first).astype(key.dtype)


# Dropout sites in the order they were traced; `_site_bwd` asks whether
# its site is the latest one.
_sites = [0]


@contextlib.contextmanager
def partitioned_draws(on=True):
    """Inside, a dropout mask is `jax.random.bernoulli` on the site's
    threefry key, as before ISSUE 28: for a program that GSPMD partitions
    over a mesh (`CompiledTrainStep(mesh=...)` enters this around the
    net's forward).  XLA's partitioner does not split
    ``rng_bit_generator``: every chip draws the mask of the global batch
    (`u32[768,128,768]` on each of four chips in the compiled text of a
    dp=4 step for a described v5e:2x2, PERF.md section 6, PR 28), and the
    tie of `_after` becomes an all-reduce a site.  Threefry is elementwise
    and partitionable: each chip draws its shard, and the mask does not
    depend on the mesh.  A `shard_map` body sees local shapes and needs
    none of this."""
    prev = getattr(_HOLDER, "partitioned", False)
    _HOLDER.partitioned = bool(on)
    try:
        yield
    finally:
        _HOLDER.partitioned = prev


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 4, 5))
def _site(fn, n, hold, key, rate, shape, *operands):
    return _site_fwd(fn, n, hold, key, rate, shape, *operands)[0]


def _site_fwd(fn, n, hold, key, rate, shape, *operands):
    keep = dropout_keep(_after(key, operands), rate, shape)
    return fn(keep, *operands), (key, keep, operands)


def _site_bwd(fn, n, hold, rate, shape, held, g):
    key, keep, operands = held
    if not hold and n != _sites[0]:
        # the same key, so the same mask, drawn when the cotangent is
        # there; the forward's `keep` is then dead code to XLA
        keep = dropout_keep(_after(key, g), rate, shape)
    _, pull = jax.vjp(functools.partial(fn, keep), *operands)
    return (None, *pull(g))


_site.defvjp(_site_fwd, _site_bwd)


def dropped(fn, key, rate, shape, *operands, hold=False):
    """``fn(keep, *operands)`` at a dropout site, `keep` being
    ``dropout_keep(key, rate, shape)``.

    Without `hold` the backward pass does not hold the mask: it draws it
    again from the same key (as XLA recomputes an elementwise threefry
    mask inside its backward fusions) and runs `fn` again under `jax.vjp`.
    `rng_bit_generator` is an operation of its own to XLA:TPU, so a mask
    that autodiff holds costs its 32-bit words from forward to backward
    (+2.5 GiB in BERT-base's step at 24,576 tokens), or a byte an element
    as a bool (+0.8 GiB).  Give `fn` everything that would otherwise hold
    a masked tensor: attention passes the probabilities-times-V product
    with its mask.

    With `hold` the site keeps the bool `keep` of its forward pass and the
    backward pass uses it: no second generator call, no second copy of the
    words into the operand's layout, no second comparison.  That is the
    faster form wherever the byte an element is there to spend, and the
    site's code says so, not a user.  One site does: dense attention
    (`parallel.ring_attention._block_attn`), whose row maximum no longer
    holds a tie mask of the same shape, dtype and lifetime (432 MiB for
    432 MiB in BERT-base's step at sequence 128; PERF.md section 6, PR
    30).  The same 32-bit draws, comparison and key as the other form:
    the forward mask is the one it would draw.

    One more site keeps its bool mask: the latest one traced, which the
    backward pass reaches first.  A generator call there opens the
    backward pass, XLA's scheduler fills the wait with the loss head's
    largest temporaries, and the step's peak rises by 0.25 GiB
    (`bert-base.mlm512`; PERF.md section 6, PR 28).  Under jit the
    forward masks of the sites that draw again are dead code; in eager
    mode each is held, a byte an element, until its backward pass."""
    if getattr(_HOLDER, "partitioned", False):
        return fn(jax.random.bernoulli(key, 1.0 - rate, shape), *operands)
    _sites[0] += 1
    mask_draws["held"] += hold
    return _site(fn, _sites[0], hold, _raw(key), rate, tuple(shape),
                 *operands)


def scaled(keep, x, rate):
    """Inverted dropout's arithmetic on a mask that is there."""
    return jnp.where(keep, x / (1.0 - rate),
                     jnp.zeros((), x.dtype)).astype(x.dtype)


def dropout(x, key, rate, mask_shape=None):
    """Inverted dropout of `x`: kept elements scaled by ``1 / (1 - rate)``,
    the rest zero, in `x`'s dtype.  `mask_shape` (default `x`'s) may hold
    1s to share one draw along those axes."""
    return dropped(lambda keep, x: scaled(keep, x, rate), key, rate,
                   mask_shape or x.shape, x)


def host_rng():
    """The framework's blessed HOST-side RNG: numpy's global generator.

    Library code that samples on the host (data-augmentation transforms,
    host-path initializers, shufflers) must draw through this accessor
    rather than calling ``np.random.*`` directly — same stream, but the
    dependence on the capsule-covered state becomes explicit and
    statically checkable (tools/tpumx_lint.py's determinism pass flags
    direct global draws).  The returned generator is exactly what
    :func:`seed` seeds and :func:`get_state`/:func:`set_state` snapshot
    and restore, so every draw through it replays bit-exactly under a
    resume capsule.  Iterators with their OWN ``RandomState(seed)`` plus
    ``state_dict()`` coverage should keep it — a private stream is
    stronger isolation, not a violation."""
    import numpy as _np
    # the module-level singleton behind np.random.* — NOT a new stream
    return _np.random.mtrand._rand


def get_state():
    """Snapshot BOTH framework RNG streams as an opaque, picklable token.

    Covers the global JAX key (device sampling — ``nd.random.*``, on-device
    init, the compiled train step's per-step subkeys) and numpy's global
    state (host-path initializers and any ``np.random``-backed iterator).
    Per-iterator private ``RandomState``s are NOT included — each
    ``DataIter.state_dict()`` carries its own.  Pass the token to
    :func:`set_state` to restore the streams bit-exactly."""
    import numpy as _np
    with _GLOBAL.lock:
        key = _np.asarray(_GLOBAL.key)
    return {"jax_key": key, "numpy": _np.random.get_state()}


def set_state(state):
    """Restore a :func:`get_state` / :func:`seed` token.

    Tolerant of JSON round-trips (lists where the token had arrays/tuples):
    a capsule that serialized the token can hand it straight back."""
    import numpy as _np
    key = _np.asarray(state["jax_key"], dtype=_np.uint32)
    st = state["numpy"]
    np_state = (str(st[0]), _np.asarray(st[1], dtype=_np.uint32),
                int(st[2]), int(st[3]), float(st[4]))
    with _GLOBAL.lock:
        _GLOBAL.key = jax.numpy.asarray(key)
    _np.random.set_state(np_state)


def seed(seed_state, ctx="all"):
    """mx.random.seed (REF:python/mxnet/random.py).

    Seeds BOTH generators the framework draws from: the JAX key (device
    sampling — `nd.random.*`, on-device parameter init) and numpy's
    global state (the host-path initializers, e.g. Orthogonal/Bilinear,
    sample from np.random the way the reference's initializers sample
    from its own engine RNG — one seed call must make either path
    deterministic).

    Returns the PRIOR state token (see :func:`get_state`) so a caller can
    save/restore the streams around itself::

        tok = mx.random.seed(7)
        ... deterministic block ...
        mx.random.set_state(tok)        # outer stream continues untouched
    """
    import numpy as _np
    prior = get_state()
    with _GLOBAL.lock:
        _GLOBAL.key = jax.random.PRNGKey(int(seed_state))
    _np.random.seed(int(seed_state) % (2 ** 32))
    return prior
