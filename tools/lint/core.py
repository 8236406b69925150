"""tpumx-lint core: findings, the per-file context, suppressions,
baseline I/O, and static catalog extraction.

Everything here is shared between phase 1 (the project index,
``tools/lint/index.py``) and phase 2 (the rule passes,
``tools/lint/passes.py``).  Pure stdlib; the linter never imports
``tpu_mx`` (catalogs are extracted by *parsing* their home modules).
"""
from __future__ import annotations

import ast
import hashlib
import json
import os
import re

LINT_FORMAT = "tpumx-lint-baseline-v1"

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the default scan set (ISSUE 6): the library and the tools
DEFAULT_TARGETS = ("tpu_mx", "tools")

_SUPPRESS_RE = re.compile(
    r"#\s*tpumx-lint:\s*disable="
    r"([A-Za-z0-9_\-]+(?:\s*,\s*[A-Za-z0-9_\-]+)*)")


# ---------------------------------------------------------------------------
# findings
# ---------------------------------------------------------------------------
class Finding:
    """One rule violation at a source location."""

    __slots__ = ("rule", "path", "line", "col", "message", "context",
                 "line_text")

    def __init__(self, rule, path, line, col, message, context="",
                 line_text=""):
        self.rule = rule
        self.path = path            # repo-relative, forward slashes
        self.line = line            # 1-based
        self.col = col              # 0-based
        self.message = message
        self.context = context      # enclosing Class.def qualname ("" = module)
        self.line_text = line_text

    def fingerprint(self):
        """Stable identity for baselining: hashes the rule, file, enclosing
        scope and the normalized source line — NOT the line number, so
        unrelated edits above a baselined finding don't resurrect it."""
        norm = " ".join(self.line_text.split())
        raw = "|".join((self.rule, self.path, self.context, norm))
        return hashlib.sha256(raw.encode("utf-8")).hexdigest()[:16]

    def as_dict(self):
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "col": self.col, "message": self.message,
                "context": self.context, "fingerprint": self.fingerprint()}

    def render(self):
        return (f"{self.path}:{self.line}:{self.col + 1}: "
                f"[{self.rule}] {self.message}")


# ---------------------------------------------------------------------------
# per-file context shared by every pass
# ---------------------------------------------------------------------------
class FileCtx:
    """Parsed file + the lookups the passes share: source lines, a
    node→enclosing-scope map, and the module's import aliases."""

    def __init__(self, path, source):
        self.path = path.replace(os.sep, "/")
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self.scope = {}        # id(node) -> "Class.method" qualname
        self.func_of = {}      # id(node) -> nearest FunctionDef node (or None)
        self.class_of = {}     # id(node) -> nearest ClassDef node (or None)
        self._index_scopes()
        # import aliases: local name -> dotted module it refers to
        self.mod_alias = {}    # e.g. {"np": "numpy", "_telemetry": "...telemetry"}
        self.from_imports = {} # local name -> (module, original name)
        self._index_imports()

    def _index_scopes(self):
        def walk(node, qual, func, klass):
            for child in ast.iter_child_nodes(node):
                q, f, k = qual, func, klass
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    q = f"{qual}.{child.name}" if qual else child.name
                    f = child
                elif isinstance(child, ast.ClassDef):
                    q = f"{qual}.{child.name}" if qual else child.name
                    k = child
                self.scope[id(child)] = qual
                self.func_of[id(child)] = func
                self.class_of[id(child)] = klass
                walk(child, q, f, k)
        walk(self.tree, "", None, None)

    def _index_imports(self):
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.mod_alias[a.asname or a.name.split(".")[0]] = a.name
            elif isinstance(node, ast.ImportFrom):
                mod = ("." * node.level) + (node.module or "")
                for a in node.names:
                    self.from_imports[a.asname or a.name] = (mod, a.name)

    def qualname(self, node):
        return self.scope.get(id(node), "")

    def line_text(self, lineno):
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1]
        return ""

    def finding(self, rule, node, message):
        return Finding(rule, self.path, node.lineno, node.col_offset,
                       message, context=self.qualname(node),
                       line_text=self.line_text(node.lineno))


# ---------------------------------------------------------------------------
# small AST helpers
# ---------------------------------------------------------------------------
def dotted(node):
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def call_name(call):
    return dotted(call.func)


def const_str(node):
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def strings_in(node):
    """Every string constant anywhere inside `node` (e.g. both arms of a
    conditional mode expression)."""
    return [n.value for n in ast.walk(node)
            if isinstance(n, ast.Constant) and isinstance(n.value, str)]


def expr_text(node):
    try:
        return ast.unparse(node)
    except Exception:  # pragma: no cover — unparse handles all real exprs
        return ""


def numpy_names(ctx):
    """Local aliases that refer to the host numpy module."""
    return {alias for alias, mod in ctx.mod_alias.items()
            if mod in ("numpy", "numpy.random")} | {"np", "onp", "_np"}


def jnp_names(ctx):
    """Local aliases that refer to jax.numpy (the device-array module)."""
    return {alias for alias, mod in ctx.mod_alias.items()
            if mod == "jax.numpy"} | {"jnp"}


# Implicit device→host sync markers, shared by phase 1 (summaries) and
# phase 2 (sync-point, hot-path-purity): ONE list, so a new sync attr
# can never make the summaries and the passes disagree on what counts.
SYNC_ATTRS = ("asnumpy", "item", "tolist", "asscalar")
SYNC_REDUCTIONS = frozenset({"mean", "sum", "max", "min", "norm", "prod",
                             "all", "any", "dot"})


def flat_targets(node):
    """Assignment targets of Assign/AugAssign/AnnAssign, tuples flattened."""
    targets = node.targets if isinstance(node, ast.Assign) else [node.target]
    flat = []
    for t in targets:
        if isinstance(t, (ast.Tuple, ast.List)):
            flat.extend(t.elts)
        else:
            flat.append(t)
    return flat


# ---------------------------------------------------------------------------
# catalog extraction (static — never imports tpu_mx)
# ---------------------------------------------------------------------------
def _load_catalog(repo, module, var):
    """Extract a literal catalog assignment from tpu_mx/<module>.py by
    parsing it — no package import, so the linter needs no jax and runs
    anywhere.  Dict literals yield their key set."""
    path = os.path.join(repo, "tpu_mx", f"{module}.py")
    try:
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), filename=path)
    except (OSError, SyntaxError):
        return None
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == var
                for t in node.targets):
            value = node.value
            if (isinstance(value, ast.Call)
                    and (dotted(value.func) == "frozenset")
                    and value.args):
                value = value.args[0]
            try:
                return frozenset(ast.literal_eval(value))
            except ValueError:
                return None
    return None


def load_known_metrics(repo=REPO):
    """KNOWN_METRICS from tpu_mx/telemetry.py (statically parsed)."""
    return _load_catalog(repo, "telemetry", "KNOWN_METRICS")


def load_known_events(repo=REPO):
    """KNOWN_EVENTS names from tpu_mx/tracing.py (statically parsed;
    the catalog is a dict of name -> typed payload fields — the event
    NAMES are what emit() call sites are checked against)."""
    return _load_catalog(repo, "tracing", "KNOWN_EVENTS")


# ---------------------------------------------------------------------------
# suppression + baseline
# ---------------------------------------------------------------------------
def suppressed_rules(ctx, lineno):
    """Rules disabled for `lineno` via an inline comment on the line, or
    anywhere in the contiguous comment-only block directly above it (so a
    multi-line justification can lead with the directive)."""
    rules = set()

    def collect(text):
        m = _SUPPRESS_RE.search(text)
        if m:
            rules.update(r.strip() for r in m.group(1).split(",")
                         if r.strip())

    collect(ctx.line_text(lineno))
    ln = lineno - 1
    while ln >= 1 and ctx.line_text(ln).lstrip().startswith("#"):
        collect(ctx.line_text(ln))
        ln -= 1
    return rules


def read_baseline(path):
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    except OSError:
        return set()
    except ValueError as e:
        raise SystemExit(f"tpumx-lint: baseline {path} unreadable: {e}")
    if data.get("format") != LINT_FORMAT:
        raise SystemExit(f"tpumx-lint: baseline {path}: unknown format "
                         f"{data.get('format')!r}")
    return {e["fingerprint"] for e in data.get("findings", [])}


def write_baseline(path, findings):
    entries = [{"fingerprint": f.fingerprint(), "rule": f.rule,
                "path": f.path, "context": f.context,
                "line": f.line, "message": f.message}
               for f in findings]
    entries.sort(key=lambda e: (e["path"], e["line"], e["rule"]))
    payload = {"format": LINT_FORMAT,
               "note": "Accepted pre-existing findings; regenerate with "
                       "tools/tpumx_lint.py --write-baseline.  Keep this "
                       "EMPTY: prefer a fix, or an inline justified "
                       "'# tpumx-lint: disable=<rule> -- why'.",
               "findings": entries}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
