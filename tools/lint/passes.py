"""tpumx-lint phase 2: the rule passes.

Every pass runs per file with the shared :class:`~lint.core.FileCtx`
plus (optionally) the phase-1 :class:`~lint.index.ProjectIndex`.  With
no index the passes degrade to the PR-6 lexical behavior — a single
fixture file still lints exactly as before; with the index the
concurrency pass *proves or refutes* caller-holds-lock helpers, the
sync-point and durability passes follow one level of helper
indirection, the telemetry pass sees re-exported emitter aliases, and
the ``hot-path-purity`` pass walks the whole call graph from the
decode/train/fusion roots.  See docs/static_analysis.md for the rule
catalog and the add-a-pass recipe.
"""
from __future__ import annotations

import ast
import re

from .core import (SYNC_ATTRS, SYNC_REDUCTIONS, call_name, const_str,
                   dotted, expr_text, flat_targets, jnp_names, numpy_names,
                   strings_in)
from .index import HOT_ROOTS  # noqa: F401 — re-exported for the CLI/tests

_GUARD_TEST_RE = re.compile(r"isinstance|hasattr|is (not )?None\b")


def func_qual(ctx, node):
    """Qualname of the function enclosing `node` (None at module level)."""
    fn = ctx.func_of.get(id(node))
    if fn is None:
        return None
    parent = ctx.qualname(fn)
    return f"{parent}.{fn.name}" if parent else fn.name


# ---------------------------------------------------------------------------
class Pass:
    """One rule pass.  Subclasses set `name` and implement
    `run(ctx, index=None)` yielding Findings.  Adding a pass = subclass +
    append to build_passes() (docs/static_analysis.md walks through an
    example)."""

    name = None

    def run(self, ctx, index=None):  # pragma: no cover — interface
        raise NotImplementedError


class DurabilityPass(Pass):
    """Raw state writes that bypass checkpoint.atomic_write.

    Flags, in library code (``tpu_mx/``): any ``open(path, "w"/"wb")``,
    any ``pickle.dump(obj, file)``, and ``np.save/np.savez`` to anything
    not provably an in-memory buffer.  In ``tools/`` only
    *state-shaped* paths are flagged (ones whose expression mentions
    checkpoints/params/states/manifests) — report files there are not
    recovery state.  ``atomic_write``'s own internal ``open`` is the one
    structural allowlist: it IS the durability layer.

    With the project index the pass additionally follows ONE helper hop:
    a call that hands a state-shaped path to a function whose body
    raw-opens its path parameter for write is flagged at the call site —
    the wrapper-around-``open`` blind spot (ISSUE 10).  Helpers named
    like the durability layer itself (``atomic_write``/``write_atomic``,
    i.e. tmp+rename commit layers) are exempt, as are helper sites that
    carry their own justified suppression.
    """

    name = "durability"

    STATE_HINTS = ("params", "states", "checkpoint", "ckpt", "manifest",
                   "capsule")

    def _is_library(self, ctx):
        return ctx.path.startswith("tpu_mx/")

    def _state_shaped(self, arg):
        text = expr_text(arg).lower()
        return any(h in text for h in self.STATE_HINTS)

    def _in_scope(self, ctx, path_arg):
        return self._is_library(ctx) or self._state_shaped(path_arg)

    def _bytesio_fed(self, ctx, call, arg):
        """True when `arg` is (or is assigned from) an io.BytesIO — an
        in-memory sink, no durability contract applies."""
        if any("BytesIO" in (dotted(n) or "")
               for n in ast.walk(arg) if isinstance(n, (ast.Name, ast.Attribute))):
            return True
        if isinstance(arg, ast.Name):
            func = ctx.func_of.get(id(call))
            search = func if func is not None else ctx.tree
            for node in ast.walk(search):
                if isinstance(node, ast.Assign) and any(
                        isinstance(t, ast.Name) and t.id == arg.id
                        for t in node.targets):
                    if "BytesIO" in expr_text(node.value):
                        return True
        return False

    def run(self, ctx, index=None):
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = call_name(node)
            # --- open(path, "w"/"wb") --------------------------------
            if fn == "open" and node.args:
                func = ctx.func_of.get(id(node))
                if func is not None and func.name == "atomic_write":
                    continue  # the durability layer's own tmp-file open
                mode = None
                if len(node.args) >= 2:
                    mode = node.args[1]
                for kw in node.keywords:
                    if kw.arg == "mode":
                        mode = kw.value
                if mode is None:
                    continue  # default "r"
                modes = strings_in(mode)
                if not any(m.startswith("w") for m in modes):
                    continue
                if not self._in_scope(ctx, node.args[0]):
                    continue
                yield ctx.finding(
                    self.name, node,
                    f"raw open({expr_text(node.args[0])}, "
                    f"{'/'.join(sorted(set(modes)))}) write bypasses "
                    "checkpoint.atomic_write — a crash mid-write leaves a "
                    "truncated destination (docs/robustness.md)")
            # --- pickle.dump(obj, file) ------------------------------
            elif fn is not None and fn.endswith("pickle.dump"):
                if not self._is_library(ctx) and not (
                        len(node.args) >= 2
                        and self._state_shaped(node.args[1])):
                    continue
                yield ctx.finding(
                    self.name, node,
                    "pickle.dump to a raw file handle bypasses "
                    "checkpoint.atomic_write — use pickle.dumps + "
                    "atomic_write so the commit is all-or-nothing")
            # --- np.save / np.savez(path, ...) -----------------------
            elif fn is not None and node.args and any(
                    fn == f"{alias}.{save}"
                    for alias in numpy_names(ctx)
                    for save in ("save", "savez", "savez_compressed")):
                sink = node.args[0]
                if self._bytesio_fed(ctx, node, sink):
                    continue  # in-memory serialize-then-atomic_write idiom
                if not self._in_scope(ctx, sink):
                    continue
                yield ctx.finding(
                    self.name, node,
                    f"{fn}({expr_text(sink)}, ...) writes state in place — "
                    "serialize to BytesIO and commit via "
                    "checkpoint.atomic_write")
            # --- one helper hop: f(state_path) where f raw-opens -----
            elif fn is not None and index is not None and node.args:
                got = index.callee_summary(ctx.path, func_qual(ctx, node), fn)
                if got is None:
                    continue
                rel2, qual2, fs = got
                writes = [w for w in fs.get("raw_writes", ())
                          if not w[2]]  # unsuppressed helper sites only
                if not writes:
                    continue
                if rel2.startswith("tpu_mx/"):
                    continue  # the helper's own open is flagged directly
                if not any(self._state_shaped(a) for a in node.args):
                    continue
                kind, line2, _ = writes[0]
                yield ctx.finding(
                    self.name, node,
                    f"passes a state-shaped path to {qual2} ({rel2}:"
                    f"{line2}) whose body raw-{kind}s its path parameter "
                    "— a wrapper does not make the write atomic; route "
                    "the commit through checkpoint.atomic_write")


class DeterminismPass(Pass):
    """Library RNG outside the tpu_mx.random process-global state.

    Flags, in ``tpu_mx/`` (the framework's own ``random.py`` excepted):
    draws/seeds on numpy's global stream (``np.random.rand`` etc. —
    route through ``tpu_mx.random.host_rng()`` so the dependence on the
    capsule-covered stream is explicit), fresh ``jax.random.PRNGKey``
    streams (escape the capsule entirely), entropy-seeded
    ``RandomState()``/``default_rng()`` (irreproducible by
    construction), a dropout site that draws its own mask with
    ``jax.random.bernoulli`` (every keep mask comes from
    ``tpu_mx.random.dropout_keep``: one generator, one place that says
    what a mask is a function of), and time-seeded RNG anywhere.  A *seeded* private
    ``RandomState(seed)`` is NOT flagged — that is the blessed pattern
    for iterators that snapshot their own stream via ``state_dict()``.
    """

    name = "determinism"

    GLOBAL_DRAWS = frozenset({
        "seed", "rand", "randn", "randint", "random", "random_sample",
        "ranf", "sample", "uniform", "normal", "standard_normal",
        "shuffle", "permutation", "choice", "beta", "gamma", "binomial",
        "multinomial", "poisson", "exponential", "laplace", "bytes",
    })
    SEEDED_CTORS = ("RandomState", "default_rng")

    def _library(self, ctx):
        return (ctx.path.startswith("tpu_mx/")
                and ctx.path != "tpu_mx/random.py")

    @staticmethod
    def _has_seed_arg(call):
        """True when the RNG constructor receives a non-None seed, either
        positionally or as a keyword (RandomState(seed=7))."""
        if call.args and not (isinstance(call.args[0], ast.Constant)
                              and call.args[0].value is None):
            return True
        return any(not (isinstance(kw.value, ast.Constant)
                        and kw.value.value is None)
                   for kw in call.keywords if kw.arg is not None)

    @staticmethod
    def _dropout_site(ctx, call):
        """A ``bernoulli`` call whose enclosing function or arguments name
        dropout: the sampling API (``random_bernoulli``) does neither."""
        where = (func_qual(ctx, call) or "") + " ".join(
            expr_text(a) for a in
            list(call.args) + [kw.value for kw in call.keywords])
        return "drop" in where.lower()

    def _time_seeded(self, node):
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                d = call_name(sub) or ""
                if d in ("time.time", "time.time_ns", "time.monotonic",
                         "time.perf_counter"):
                    return True
        return False

    def run(self, ctx, index=None):
        lib = self._library(ctx)
        np_names = numpy_names(ctx)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = call_name(node)
            if fn is None:
                continue
            parts = fn.split(".")
            # time-seeded RNG is wrong EVERYWHERE (tools included): the
            # run is irreproducible and the seed is unrecorded.  Both
            # positional and keyword (seed=time.time()) spellings count.
            seedish = list(node.args) + [kw.value for kw in node.keywords]
            if (parts[-1] in ("seed", "PRNGKey", "key", "Random")
                    + self.SEEDED_CTORS
                    and any(self._time_seeded(a) for a in seedish)):
                yield ctx.finding(
                    self.name, node,
                    f"{fn} seeded from wall-clock time — the stream is "
                    "unrecorded and can never be replayed by a resume "
                    "capsule; derive the seed from tpu_mx.random or config")
                continue
            if not lib:
                continue
            # np.random.<draw> on the GLOBAL numpy stream
            if (len(parts) >= 3 and parts[-2] == "random"
                    and parts[-3] in np_names
                    and parts[-1] in self.GLOBAL_DRAWS):
                yield ctx.finding(
                    self.name, node,
                    f"direct {fn} draws from numpy's global stream — "
                    "route through tpu_mx.random.host_rng() (the "
                    "capsule-covered stream) or a private seeded "
                    "RandomState with state_dict coverage")
            # fresh jax PRNGKey/typed-key stream outside tpu_mx/random.py
            # (jax.random.key is the current recommended constructor —
            # same capsule-escape as the legacy PRNGKey)
            elif parts[-1] == "PRNGKey" or (
                    len(parts) >= 2 and parts[-2] == "random"
                    and parts[-1] == "key"):
                yield ctx.finding(
                    self.name, node,
                    f"fresh {parts[-1]} stream escapes the "
                    "process-global tpu_mx.random state — resume capsules "
                    "cannot replay it; use tpu_mx.random.take_key()")
            # a dropout site drawing its own keep mask
            elif (".".join([ctx.mod_alias.get(parts[0], parts[0])]
                           + parts[1:]) == "jax.random.bernoulli"
                    and self._dropout_site(ctx, node)):
                yield ctx.finding(
                    self.name, node,
                    f"{fn} at a dropout site — draw the keep mask through "
                    "tpu_mx.random.dropout_keep (or apply it with "
                    "tpu_mx.random.dropout): its bits come from the "
                    "chip's generator, and its module says what a mask "
                    "is a function of")
            # entropy-seeded private streams (a seed passed positionally
            # OR as seed=/... keyword makes the stream reproducible)
            elif parts[-1] in self.SEEDED_CTORS and (
                    len(parts) < 3 or parts[-2] == "random") and (
                    not self._has_seed_arg(node)):
                yield ctx.finding(
                    self.name, node,
                    f"{fn} with no seed draws OS entropy — the stream is "
                    "irreproducible; seed it from config or "
                    "tpu_mx.random")


class SyncPointPass(Pass):
    """Implicit device→host syncs inside the hot paths.

    Hot scopes: ``tpu_mx/fusion.py`` and ``tpu_mx/parallel/train_step.py``
    (whole files — segment construction and the step dispatch path), and
    optimizer ``update*``/``create_state*`` bodies.  Flags ``.asnumpy()``
    / ``.item()`` / ``.tolist()`` / ``jax.device_get`` /
    host-``np.asarray(...)`` calls, and ``float()/bool()/int()`` applied
    to a call or subscript result (an array reduction like
    ``float(loss.mean())`` blocks dispatch; ``float(self.lr)`` on plain
    attributes stays silent).  Explicit syncs (``wait_to_read``,
    ``block_until_ready``) are allowed — the contract is that a sync must
    be *visible*, not that it never happens.

    With the project index, a call FROM a hot scope to a helper whose
    body contains an (unsuppressed) implicit sync is flagged at the call
    site — one level of indirection, so hiding the ``.item()`` in a
    same-file or imported helper no longer evades the rule.  Helpers
    that live in a hot scope themselves are skipped (their sites are
    flagged directly), and a justified suppression at the helper site
    covers its callers too.
    """

    name = "sync-point"

    HOT_FILES = ("tpu_mx/fusion.py", "tpu_mx/parallel/train_step.py")
    HOT_FUNC_FILES = ("tpu_mx/optimizer/", )
    HOT_FUNC_PREFIXES = ("update", "_update", "create_state", "step")
    IMPLICIT = SYNC_ATTRS
    # method-style array reductions: float(loss.mean()) blocks on device.
    # Module-level host calls (np.prod(shape)) and dict methods (.get)
    # are host work — the nearest legitimate look-alikes, left silent.
    REDUCTIONS = SYNC_REDUCTIONS

    def _hot(self, ctx, node):
        if ctx.path in self.HOT_FILES:
            return True
        if any(ctx.path.startswith(p) for p in self.HOT_FUNC_FILES):
            func = ctx.func_of.get(id(node))
            while func is not None:
                if any(func.name.startswith(p)
                       for p in self.HOT_FUNC_PREFIXES):
                    return True
                func = ctx.func_of.get(id(func))
        return False

    def run(self, ctx, index=None):
        hot_possible = (ctx.path in self.HOT_FILES
                        or any(ctx.path.startswith(p)
                               for p in self.HOT_FUNC_FILES))
        if not hot_possible:
            return
        np_names = numpy_names(ctx)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call) or not self._hot(ctx, node):
                continue
            fn = call_name(node)
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr in self.IMPLICIT
                    and not node.args and not node.keywords):
                yield ctx.finding(
                    self.name, node,
                    f".{node.func.attr}() forces a device→host sync on the "
                    "hot path — it stalls dispatch and flushes/splits any "
                    "fusion segment; hoist it out or make the sync "
                    "explicit at the loop level")
            elif fn == "jax.device_get" or (
                    fn is not None and "." in fn
                    and fn.split(".")[0] in np_names
                    and fn.split(".")[-1] in ("asarray", "array")
                    and ctx.path in self.HOT_FILES):
                yield ctx.finding(
                    self.name, node,
                    f"{fn}(...) copies device memory to host on the hot "
                    "path — an implicit sync; keep data on device "
                    "(jnp.asarray) or sync explicitly outside the step")
            elif (isinstance(node.func, ast.Name)
                  and node.func.id in ("float", "bool", "int")
                  and node.args
                  and isinstance(node.args[0], ast.Call)
                  and isinstance(node.args[0].func, ast.Attribute)
                  and node.args[0].func.attr in self.REDUCTIONS
                  and not (isinstance(node.args[0].func.value, ast.Name)
                           and node.args[0].func.value.id in np_names)):
                yield ctx.finding(
                    self.name, node,
                    f"{node.func.id}({expr_text(node.args[0])}) on the hot "
                    "path blocks until the device value materializes — an "
                    "implicit sync point; read it back outside the step "
                    "or keep the value on device")
            elif fn is not None and index is not None:
                got = index.callee_summary(ctx.path, func_qual(ctx, node), fn)
                if got is None:
                    continue
                rel2, qual2, fs = got
                if rel2 in self.HOT_FILES:
                    continue  # the helper's own sites are flagged directly
                syncs = [s for s in fs.get("syncs", ()) if not s[2]]
                if not syncs:
                    continue
                desc, line2, _ = syncs[0]
                yield ctx.finding(
                    self.name, node,
                    f"calls {qual2} ({rel2}:{line2}) whose body forces a "
                    f"device→host sync ({desc}) — one helper hop does not "
                    "hide the stall; hoist the sync out of the hot path "
                    "or justify it at the helper site")


class ConcurrencyPass(Pass):
    """Thread-lifetime and lock-discipline contracts.

    (a) ``threading.Thread(...)`` must pass an explicit ``daemon=``; a
    non-daemon thread must additionally be ``.join()``-ed somewhere in
    the file (otherwise interpreter shutdown can hang on it — the
    watchdog/generation discipline from PR 4).
    (b) Per class: a ``self.X`` attribute that is assigned under a
    ``with self.<lock>:`` block at ANY site must not be assigned
    lock-free at another site (``__init__`` excepted — before the object
    escapes, no thread can see it).  Mixed discipline is exactly the
    zombie-step class of race.
    (c) Per MODULE: a module-level global that is assigned/mutated under
    a ``with <module_lock>:`` block at ANY site must not be mutated
    lock-free in another function (module top level — import time,
    single-threaded — excepted).  Covered mutations: ``global X;
    X = ...``, ``X[...] = ...`` and ``X.attr = ...`` where X is a
    module-level name (plus their aug/annotated forms); method CALLS
    (``X.append(...)``) are not assignments and stay out of scope.

    With the project index, rules (b) and (c) propagate lock context
    through the call graph: a lock-free mutation inside a helper is
    **proven safe** when every project call chain reaching the helper
    holds a lock at the boundary (``ProjectIndex.always_locked`` — the
    caller-holds-lock shape that previously needed a suppression), and
    otherwise the finding names one lock-free entry chain, so a
    transitively-reachable unlocked mutation is a finding with its
    witness path attached.
    """

    name = "concurrency"

    def run(self, ctx, index=None):
        yield from self._threads(ctx)
        yield from self._lock_discipline(ctx, index)
        yield from self._module_lock_discipline(ctx, index)

    @staticmethod
    def _thread_joins(ctx):
        """Receiver texts of `<expr>.join(...)` calls that can plausibly
        be thread joins — string `", ".join` and `os.path.join` (any
        path-module join) are excluded, so they cannot satisfy the
        non-daemon rule vacuously."""
        joins = set()
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "join"):
                recv = node.func.value
                if isinstance(recv, ast.Constant):
                    continue  # ", ".join(...)
                text = expr_text(recv)
                if text.endswith("path") or ".path" in text:
                    continue  # os.path.join / posixpath.join
                joins.add(text)
        return joins

    def _threads(self, ctx):
        joins = self._thread_joins(ctx)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = call_name(node)
            if fn is None:
                continue
            if fn.endswith("threading.Thread"):
                pass
            elif isinstance(node.func, ast.Name):
                # `from threading import Thread [as T]` — resolve the
                # alias; a class merely NAMED Thread from elsewhere is
                # not ours
                mod, orig = ctx.from_imports.get(node.func.id, ("", ""))
                if orig != "Thread" or mod.split(".")[-1] != "threading":
                    continue
            else:
                continue
            daemon = None
            for kw in node.keywords:
                if kw.arg == "daemon":
                    daemon = kw.value
            if daemon is None:
                yield ctx.finding(
                    self.name, node,
                    "threading.Thread without an explicit daemon= — "
                    "decide the lifetime: daemon=True (watchdog-style, "
                    "may die mid-write) or daemon=False with a join")
            elif (isinstance(daemon, ast.Constant)
                  and daemon.value is False and not joins):
                yield ctx.finding(
                    self.name, node,
                    "non-daemon Thread with no .join() anywhere in this "
                    "file — interpreter shutdown will hang on it")

    def _is_lock_with(self, item):
        d = dotted(item.context_expr) or ""
        return d.startswith("self.") and "lock" in d.lower()

    def _discharged(self, ctx, index, site):
        """Caller-holds-lock proof for a lock-free mutation site: every
        project call chain reaching its enclosing function holds a lock
        at the boundary."""
        if index is None:
            return False
        qual = func_qual(ctx, site)
        return qual is not None and index.always_locked(ctx.path, qual)

    def _entry_note(self, ctx, index, site):
        if index is None:
            return ""
        qual = func_qual(ctx, site)
        if qual is None:
            return ""
        chain = index.unlocked_entry_chain(ctx.path, qual)
        if chain:
            return (" — reached lock-free from "
                    f"{' -> '.join(chain + [qual])}")
        return ""

    def _lock_discipline(self, ctx, index):
        for klass in ast.walk(ctx.tree):
            if not isinstance(klass, ast.ClassDef):
                continue
            guarded = {}    # attr -> first guarded-assign node
            unguarded = {}  # attr -> [unguarded-assign nodes]

            def visit(node, locked, in_init):
                for child in ast.iter_child_nodes(node):
                    if isinstance(child, ast.ClassDef):
                        continue  # nested class: analyzed on its own
                    if isinstance(child, (ast.FunctionDef,
                                          ast.AsyncFunctionDef)):
                        # a direct method's nearest enclosing function is
                        # the class's own (None at module level); anything
                        # deeper is a closure inside a method
                        direct = (ctx.class_of.get(id(child)) is klass
                                  and ctx.func_of.get(id(child))
                                  is ctx.func_of.get(id(klass)))
                        # a function DEFINED under a lock does not RUN
                        # under it; a closure inside __init__ still runs
                        # during construction (keeps in_init)
                        visit(child, False,
                              child.name == "__init__" if direct
                              else in_init)
                        continue
                    child_locked = locked
                    if isinstance(child, ast.With) and any(
                            self._is_lock_with(i) for i in child.items):
                        child_locked = True
                    if isinstance(child, (ast.Assign, ast.AugAssign,
                                          ast.AnnAssign)) and not (
                            isinstance(child, ast.AnnAssign)
                            and child.value is None):  # bare annotation
                        for t in flat_targets(child):
                            d = dotted(t) or ""
                            if not d.startswith("self.") or d.count(".") != 1:
                                continue
                            attr = d.split(".", 1)[1]
                            if locked:
                                guarded.setdefault(attr, child)
                            elif not in_init:
                                unguarded.setdefault(attr, []).append(child)
                    visit(child, child_locked, in_init)

            visit(klass, False, False)
            for attr, sites in unguarded.items():
                if attr not in guarded:
                    continue
                g = guarded[attr]
                for site in sites:
                    if self._discharged(ctx, index, site):
                        continue  # every caller provably holds the lock
                    yield ctx.finding(
                        self.name, site,
                        f"self.{attr} is assigned under a lock at "
                        f"{ctx.path}:{g.lineno} but lock-free here"
                        f"{self._entry_note(ctx, index, site)} — mixed "
                        "discipline races exactly like the PR-4 "
                        "zombie-step bug; take the lock (or document why "
                        "this site is single-threaded)")

    # -- (c) module-level lock/global discipline -----------------------------
    def _is_module_lock_with(self, item):
        d = dotted(item.context_expr) or ""
        return d and not d.startswith("self.") and "lock" in d.lower()

    @staticmethod
    def _locals_of(fn):
        """(local names, declared globals) of a function: parameters plus
        bare-Name assignment/loop targets anywhere inside (nested scopes
        included — over-approximating locals under-approximates findings,
        the safe direction for a lexical rule)."""
        if fn is None:
            return frozenset(), frozenset()
        args = fn.args
        params = {a.arg for a in (args.args + args.kwonlyargs
                                  + getattr(args, "posonlyargs", []))}
        if args.vararg:
            params.add(args.vararg.arg)
        if args.kwarg:
            params.add(args.kwarg.arg)
        declared_global, assigned = set(), set()
        for n in ast.walk(fn):
            if isinstance(n, ast.Global):
                declared_global.update(n.names)
            elif isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                for t in flat_targets(n):
                    if isinstance(t, ast.Name):
                        assigned.add(t.id)
            elif isinstance(n, (ast.For, ast.AsyncFor)):
                for t in ast.walk(n.target):
                    if isinstance(t, ast.Name):
                        assigned.add(t.id)
            elif isinstance(n, ast.comprehension):
                for t in ast.walk(n.target):
                    if isinstance(t, ast.Name):
                        assigned.add(t.id)
            elif isinstance(n, (ast.With, ast.AsyncWith)):
                for item in n.items:
                    if item.optional_vars is not None:
                        for t in ast.walk(item.optional_vars):
                            if isinstance(t, ast.Name):
                                assigned.add(t.id)
        return params | (assigned - declared_global), declared_global

    def _module_lock_discipline(self, ctx, index):
        mod_globals = set()
        for node in ctx.tree.body:
            if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                for t in flat_targets(node):
                    if isinstance(t, ast.Name):
                        mod_globals.add(t.id)
        # names declared `global` anywhere also count (first assignment
        # may happen inside a function)
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Global):
                mod_globals.update(node.names)
        if not mod_globals:
            return
        guarded = {}    # global name -> first guarded-mutation node
        unguarded = {}  # global name -> [unguarded-mutation nodes]
        locals_cache = {}

        def target_global(t, fn):
            """The module-global name this target mutates, or None."""
            if id(fn) not in locals_cache:
                locals_cache[id(fn)] = self._locals_of(fn)
            local_names, declared_global = locals_cache[id(fn)]
            if isinstance(t, ast.Name):
                # a bare-name rebind targets the module global only
                # under an explicit `global` declaration
                return t.id if (t.id in declared_global
                                and t.id in mod_globals) else None
            node = t
            while isinstance(node, (ast.Subscript, ast.Attribute)):
                node = node.value
            if isinstance(node, ast.Name) and node.id in mod_globals \
                    and node.id not in local_names:
                return node.id
            return None

        def visit(node, locked, exempt, fn):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    # function bodies run post-import (not exempt); a
                    # function DEFINED under a lock does not RUN under it
                    visit(child, False, False, child)
                    continue
                if isinstance(child, ast.ClassDef):
                    # a class BODY executes at import time (exempt like
                    # module level); its methods hit the branch above
                    visit(child, False, exempt, fn)
                    continue
                child_locked = locked
                if isinstance(child, (ast.With, ast.AsyncWith)) and any(
                        self._is_module_lock_with(i) for i in child.items):
                    child_locked = True
                if isinstance(child, (ast.Assign, ast.AugAssign,
                                      ast.AnnAssign)) and not (
                        isinstance(child, ast.AnnAssign)
                        and child.value is None):  # bare annotation
                    for t in flat_targets(child):
                        name = target_global(t, fn)
                        if name is None:
                            continue
                        if locked:
                            guarded.setdefault(name, child)
                        elif not exempt:
                            unguarded.setdefault(name, []).append(child)
                visit(child, child_locked, exempt, fn)

        visit(ctx.tree, False, True, None)
        for name, sites in unguarded.items():
            if name not in guarded:
                continue
            g = guarded[name]
            for site in sites:
                if self._discharged(ctx, index, site):
                    continue  # every caller provably holds the lock
                yield ctx.finding(
                    self.name, site,
                    f"module global {name!r} is mutated under a lock at "
                    f"{ctx.path}:{g.lineno} but lock-free here"
                    f"{self._entry_note(ctx, index, site)} — mixed "
                    "discipline on module-level shared state (the "
                    "checkpoint._intended shape); take the lock (or "
                    "document why this site is single-threaded)")


class TelemetryCatalogPass(Pass):
    """Names at emission sites must be in their static catalog.

    Two catalogs, one discipline (stable names are an API,
    docs/observability.md): metric names at
    ``<telemetry>.counter/gauge/histogram/span(...)`` call sites are
    checked against ``telemetry.KNOWN_METRICS``, and flight-recorder
    event names at ``<tracing>.emit(...)`` call sites against
    ``tracing.KNOWN_EVENTS`` (any alias whose import resolves to the
    respective module, or functions imported from it — with the project
    index the resolution follows re-export chains across modules, so an
    emitter re-exported under another name is still checked).  A literal
    name outside the catalog — even in a branch the obs CI tier never
    executes — fails; a non-literal name is flagged as unverifiable.
    Each catalog's home module is exempt (it manipulates records
    generically).
    """

    name = "telemetry-catalog"

    EMITTERS = frozenset({"counter", "gauge", "histogram", "span"})
    TRACE_EMITTERS = frozenset({"emit"})

    def __init__(self, known_metrics, known_events=None):
        self.known = known_metrics
        self.known_events = known_events

    @staticmethod
    def _aliases(ctx, module, emitters):
        mods = {alias for alias, mod in ctx.mod_alias.items()
                if mod.split(".")[-1] == module}
        # `from tpu_mx import telemetry [as _telemetry]` — the module is
        # the imported NAME here, not the from-module path
        mods |= {alias for alias, (_, name) in ctx.from_imports.items()
                 if name == module}
        funcs = {alias for alias, (mod, name) in ctx.from_imports.items()
                 if name in emitters and mod.split(".")[-1] == module}
        return mods, funcs

    def _check(self, ctx, module, emitters, known, catalog_name, index):
        if ctx.path == f"tpu_mx/{module}.py" or known is None:
            return
        mods, funcs = self._aliases(ctx, module, emitters)
        if index is not None:
            imods, ifuncs = index.emitter_aliases(
                ctx.path, f"tpu_mx/{module}.py", emitters)
            mods, funcs = mods | imods, funcs | ifuncs
        if not mods and not funcs:
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            is_emit = False
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr in emitters
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in mods):
                is_emit = True
            elif isinstance(node.func, ast.Name) and node.func.id in funcs:
                is_emit = True
            if not is_emit or not node.args:
                continue
            name = const_str(node.args[0])
            if name is None:
                yield ctx.finding(
                    self.name, node,
                    f"name {expr_text(node.args[0])!r} is not a string "
                    f"literal — {catalog_name} cannot verify it "
                    "statically; emit a literal name (labels/payload "
                    "fields carry the dynamic part)")
            elif name not in known:
                yield ctx.finding(
                    self.name, node,
                    f'name "{name}" is not in {catalog_name} — '
                    "dashboards and the black-box schema will never see "
                    "it; add it to the catalog (and "
                    "docs/observability.md) or fix the typo")

    def run(self, ctx, index=None):
        yield from self._check(ctx, "telemetry", self.EMITTERS,
                               self.known, "telemetry.KNOWN_METRICS", index)
        yield from self._check(ctx, "tracing", self.TRACE_EMITTERS,
                               self.known_events, "tracing.KNOWN_EVENTS",
                               index)


class HotPathPurityPass(Pass):
    """No eager host↔device traffic reachable from a hot-path root.

    The decode/train/fusion inner loops (``lint.index.HOT_ROOTS``: the
    serving engine's decode step, ``decode_attention``, the compiled
    train step, the fusion flush) run per token / per step; an eager
    conversion hiding ANY number of helper hops below them is a per-call
    dispatch cliff — the exact shape PR 9 had to find empirically
    (~73 µs per eager ``jnp.asarray`` operand on the decode path).  The
    pass walks every function the project call graph reaches from a
    root and flags:

    - ``jnp.asarray``/``jnp.array`` outside a jit boundary (an eager
      device commit; inside a jitted function it is a trace-time no-op);
    - ``np.asarray``/``np.array`` applied to a device value (a call
      into ``tpu_mx/kernels/`` or a jitted function, or a local assigned
      from one) — a blocking device→host readback;
    - ``.item()``/``.tolist()``/``.asnumpy()`` — the same readback,
      scalar-shaped;
    - ``jax.device_get``;
    - ``jax.jit(...)`` construction inside the hot region — a fresh jit
      wrapper per call retraces every call.

    Stays silent on: jitted functions and lambdas passed to
    ``jax.jit``/``pallas_call`` (the jit boundary IS the commit point —
    operands cross on the C++ fast path); conversions — eager commits
    AND device readbacks alike — inside an ``isinstance``/``hasattr``-
    tested branch (the guarded-fallback idiom: a guarded fast path
    exists, only foreign inputs pay) or an ``is None`` branch /
    ``lru_cache`` function (memoized construction, runs once); and
    everything not reachable from a root.  Findings carry the witness
    call chain from the root.
    """

    name = "hot-path-purity"

    def _jit_lambda_ids(self, ctx):
        """Lambda nodes passed (possibly nested) to jax.jit/pallas_call —
        their bodies are traced, not executed eagerly."""
        out = set()
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            base = (call_name(node) or "").split(".")[-1]
            if base in ("jit", "pjit", "pallas_call"):
                for arg in node.args[:1]:
                    for sub in ast.walk(arg):
                        if isinstance(sub, ast.Lambda):
                            out.add(id(sub))
        return out

    def _device_taint(self, ctx, index, fn_node, qual):
        """(value names assigned from device-producing calls, callable
        names bound to kernel/jitted functions) inside one function."""
        vals, fns = set(), set()

        def producing(call):
            d = call_name(call)
            if d is None:
                return False
            head = d.split(".")[0]
            if head in jnp_names(ctx) or head == "jax":
                return True
            if isinstance(call.func, ast.Name) and call.func.id in fns:
                return True
            tgt = index.resolve_call(ctx.path, qual, d)
            if tgt is None:
                return False
            rel2, qual2 = tgt
            fs = index.files[rel2]["functions"].get(qual2, {})
            return "/kernels/" in rel2 or fs.get("jitted", False)

        def kernel_ref(expr):
            for sub in ast.walk(expr):
                d = dotted(sub) if isinstance(
                    sub, (ast.Name, ast.Attribute)) else None
                if d is None or isinstance(sub, ast.Call):
                    continue
                tgt = index.resolve_call(ctx.path, qual, d)
                if tgt is not None and ("/kernels/" in tgt[0]
                                        or index.files[tgt[0]]["functions"]
                                        .get(tgt[1], {}).get("jitted")):
                    return True
            return False

        for node in ast.walk(fn_node):
            if not isinstance(node, ast.Assign):
                continue
            names = [t.id for t in node.targets if isinstance(t, ast.Name)]
            if not names:
                continue
            if isinstance(node.value, ast.Call):
                if producing(node.value):
                    vals.update(names)
            elif kernel_ref(node.value):
                fns.update(names)
        return vals, fns

    def run(self, ctx, index=None):
        if index is None:
            return
        jit_lambdas = self._jit_lambda_ids(ctx)
        jnp_aliases = jnp_names(ctx)
        np_aliases = numpy_names(ctx)
        info = index.files.get(ctx.path, {"functions": {}})

        for fn_node in ast.walk(ctx.tree):
            if not isinstance(fn_node, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                continue
            parent = ctx.qualname(fn_node)
            qual = f"{parent}.{fn_node.name}" if parent else fn_node.name
            chain = index.hot_chain(ctx.path, qual)
            if chain is None:
                continue
            summary = info["functions"].get(qual, {})
            if summary.get("jitted"):
                continue  # the jit boundary IS the hot path's commit point
            where = f" [hot path: {' -> '.join(chain)}]"
            taint_vals, taint_fns = self._device_taint(
                ctx, index, fn_node, qual)
            yield from self._walk(ctx, index, fn_node, fn_node, qual,
                                  jit_lambdas, jnp_aliases, np_aliases,
                                  taint_vals, taint_fns, summary, where,
                                  guarded=False)

    def _walk(self, ctx, index, fn_node, node, qual, jit_lambdas,
              jnp_aliases, np_aliases, taint_vals, taint_fns, summary,
              where, guarded):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # separate function: checked via its own chain
            if isinstance(child, ast.Lambda) and id(child) in jit_lambdas:
                continue  # traced body, not eager execution
            child_guarded = guarded
            if isinstance(child, ast.If) and _GUARD_TEST_RE.search(
                    expr_text(child.test)):
                child_guarded = True
            if isinstance(child, ast.Call):
                yield from self._check_call(
                    ctx, index, child, qual, jnp_aliases, np_aliases,
                    taint_vals, taint_fns, summary, where, guarded)
            yield from self._walk(ctx, index, fn_node, child, qual,
                                  jit_lambdas, jnp_aliases, np_aliases,
                                  taint_vals, taint_fns, summary, where,
                                  child_guarded)

    def _check_call(self, ctx, index, node, qual, jnp_aliases, np_aliases,
                    taint_vals, taint_fns, summary, where, guarded):
        fn = call_name(node)
        parts = fn.split(".") if fn else []
        # scalar/host readbacks
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in SYNC_ATTRS
                and not node.args and not node.keywords):
            yield ctx.finding(
                self.name, node,
                f".{node.func.attr}() forces a device→host readback on "
                f"a hot-path helper chain{where}")
            return
        if fn == "jax.device_get":
            yield ctx.finding(
                self.name, node,
                f"jax.device_get copies device memory to host inside the "
                f"hot region{where}")
            return
        # eager device commit: jnp.asarray/jnp.array outside a jit
        if (len(parts) == 2 and parts[0] in jnp_aliases
                and parts[1] in ("asarray", "array") and not guarded):
            yield ctx.finding(
                self.name, node,
                f"eager {fn}(...) commits a host value to device per call "
                "(~tens of µs of dispatch each — the PR-9 decode cliff); "
                "pass the raw operand through the jit boundary instead "
                f"(C++ fast path){where}")
            return
        # host readback of a device value: np.asarray(kernel_call(...)).
        # `guarded` exempts the guarded-fallback idiom exactly like the
        # eager-commit check above: `if not isinstance(out, np.ndarray):
        # out = np.asarray(out)` is the documented shape for a helper
        # that serves both host- and device-valued callers — the numpy
        # fast path pays nothing, only genuinely device-valued results
        # pay the (deliberate, branch-visible) readback
        if (len(parts) == 2 and parts[0] in np_aliases
                and parts[1] in ("asarray", "array") and node.args
                and not guarded):
            arg = node.args[0]
            tainted = False
            if isinstance(arg, ast.Call):
                d = call_name(arg)
                head = d.split(".")[0] if d else ""
                if head in jnp_aliases or head == "jax" or (
                        isinstance(arg.func, ast.Name)
                        and arg.func.id in taint_fns):
                    tainted = True
                elif d is not None:
                    tgt = index.resolve_call(ctx.path, qual, d)
                    if tgt is not None and (
                            "/kernels/" in tgt[0]
                            or index.files[tgt[0]]["functions"]
                            .get(tgt[1], {}).get("jitted")):
                        tainted = True
            else:
                base = arg
                while isinstance(base, (ast.Subscript, ast.Attribute)):
                    base = base.value
                if isinstance(base, ast.Name) and base.id in taint_vals:
                    tainted = True
            if tainted:
                yield ctx.finding(
                    self.name, node,
                    f"{fn}({expr_text(node.args[0])}) reads a device "
                    "value back to host — a blocking sync inside the hot "
                    f"region; keep the value on device{where}")
            return
        # uncached jit construction per call
        if parts and parts[-1] in ("jit", "pjit") and (
                fn in ("jax.jit", "jax.pjit")
                or (isinstance(node.func, ast.Name) and ctx.from_imports
                    .get(node.func.id, ("", ""))[1] in ("jit", "pjit"))):
            if not summary.get("memo_guard"):
                yield ctx.finding(
                    self.name, node,
                    "jax.jit(...) constructed inside the hot region with "
                    "no memoization guard — a fresh wrapper retraces on "
                    "every call; build it once (module-level, lru_cache, "
                    f"or an `is None` guard){where}")


# ---------------------------------------------------------------------------
def build_passes(known_metrics, known_events=None):
    return [DurabilityPass(), DeterminismPass(), SyncPointPass(),
            ConcurrencyPass(),
            TelemetryCatalogPass(known_metrics, known_events),
            HotPathPurityPass()]
