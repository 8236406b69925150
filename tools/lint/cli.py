"""tpumx-lint driver: the two-phase analyzer CLI.

Phase 1 parses every target file once and builds the project index
(``tools/lint/index.py``); phase 2 re-uses the same parsed trees to run
the rule passes (``tools/lint/passes.py``) with the index in hand.  The
index is serialized next to the baseline
(``tools/tpumx_lint_index.json``) so ``--changed-only`` can re-summarize
just the files git reports dirty and re-analyze their call-graph region
— the pre-commit fast path; the full run stays the CI truth.

Exit status: 0 when every finding is suppressed or baselined, 1
otherwise, 2 on usage/internal error (missing targets, unparsable
catalogs, git failure under ``--changed-only`` — the tool fails CLOSED).
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
import time

from .core import (DEFAULT_TARGETS, REPO, FileCtx, load_known_events,
                   load_known_metrics, read_baseline, suppressed_rules,
                   write_baseline)
from .index import (ProjectIndex, build_index, read_index, summarize_file,
                    write_index)
from .passes import build_passes

DEFAULT_INDEX = os.path.join(REPO, "tools", "tpumx_lint_index.json")


def _run_passes(ctx, known_metrics, rules, known_events, index):
    findings, suppressed = [], []
    for p in build_passes(known_metrics, known_events):
        if rules and p.name not in rules:
            continue
        for f in p.run(ctx, index):
            sup = suppressed_rules(ctx, f.line)
            if p.name in sup or "all" in sup:
                suppressed.append(f)
            else:
                findings.append(f)
    return findings, suppressed


def lint_source(source, relpath, known_metrics=None, rules=None,
                known_events=None, index=None):
    """Lint one in-memory file; returns (findings, suppressed) lists.
    `relpath` decides scoping (library vs tools vs hot path), so tests
    can exercise any scope with fixture paths.  A single-file index is
    built when none is passed — same-file interprocedural facts
    (caller-holds-lock proofs, hot-path chains) work on lone fixtures."""
    ctx = FileCtx(relpath, source)
    if index is None:
        index = build_index({ctx.path: ctx})
    return _run_passes(ctx, known_metrics, rules, known_events, index)


def lint_sources(sources, known_metrics=None, rules=None, known_events=None):
    """Lint a dict of {relpath: source} as ONE project: the index spans
    the whole set, so cross-module fixtures (helper chains, re-exported
    emitters) resolve.  Returns (findings, suppressed)."""
    ctxs = {}
    for rel, src in sources.items():
        ctx = FileCtx(rel, src)
        ctxs[ctx.path] = ctx
    index = build_index(ctxs)
    findings, suppressed = [], []
    for rel in sorted(ctxs):
        found, sup = _run_passes(ctxs[rel], known_metrics, rules,
                                 known_events, index)
        findings.extend(found)
        suppressed.extend(sup)
    return findings, suppressed


def iter_files(targets, repo=REPO, missing=None):
    for t in targets:
        full = t if os.path.isabs(t) else os.path.join(repo, t)
        if not os.path.isfile(full) and not os.path.isdir(full) \
                and os.path.exists(t):
            full = os.path.abspath(t)  # relative to CWD, not the repo
        if os.path.isfile(full):
            yield full
        elif not os.path.isdir(full):
            # a typo'd target must NOT read as a clean lint
            if missing is not None:
                missing.append(t)
        elif os.path.isdir(full):
            for dirpath, dirnames, filenames in os.walk(full):
                dirnames[:] = [d for d in dirnames
                               if d not in ("__pycache__", ".git")]
                for fname in sorted(filenames):
                    if fname.endswith(".py"):
                        yield os.path.join(dirpath, fname)


def _parse_targets(targets, repo, errors):
    """Phase 0: read + parse every target file -> {rel: FileCtx}."""
    ctxs, missing = {}, []
    for path in iter_files(targets, repo, missing=missing):
        rel = os.path.relpath(os.path.abspath(path), repo)
        try:
            with open(path, encoding="utf-8") as f:
                source = f.read()
            ctx = FileCtx(rel, source)
        except SyntaxError as e:
            errors.append(f"{rel.replace(os.sep, '/')}: syntax error: {e}")
            continue
        except OSError as e:
            errors.append(f"{rel.replace(os.sep, '/')}: unreadable: {e}")
            continue
        ctxs[ctx.path] = ctx
    errors.extend(f"target not found: {t}" for t in missing)
    return ctxs


def lint_paths(targets, repo=REPO, known_metrics=None, rules=None,
               known_events=None, index=None):
    """Two-phase lint of files/dirs: returns (findings, suppressed,
    errors).  Pass a prebuilt `index` to skip phase 1 — phase 2 then
    runs only over `targets` while the index facts span the whole
    project (the --changed-only shape)."""
    errors = []
    ctxs = _parse_targets(targets, repo, errors)
    if index is None:
        index = build_index(ctxs)
    all_findings, all_suppressed = [], []
    for rel in sorted(ctxs):
        found, sup = _run_passes(ctxs[rel], known_metrics, rules,
                                 known_events, index)
        all_findings.extend(found)
        all_suppressed.extend(sup)
    return all_findings, all_suppressed, errors


def git_changed_files(repo=REPO):
    """Repo-relative paths of files git reports modified/added/renamed
    (staged, unstaged and untracked).  Raises SystemExit on git failure
    — --changed-only must fail closed, not lint nothing."""
    try:
        # --untracked-files=all: 'normal' reports a brand-new package as
        # one '?? dir/' line, and dir/ fails the .py filter — every file
        # inside an untracked directory would silently skip the lint
        run = subprocess.run(
            ["git", "-C", repo, "status", "--porcelain",
             "--untracked-files=all"],
            capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.TimeoutExpired) as e:
        raise SystemExit(f"tpumx-lint: --changed-only needs git: {e}")
    if run.returncode != 0:
        raise SystemExit("tpumx-lint: git status failed: "
                         + (run.stderr or "").strip())
    changed = set()
    for line in run.stdout.splitlines():
        if len(line) < 4:
            continue
        path = line[3:]
        if " -> " in path:  # rename: lint the new name
            path = path.split(" -> ", 1)[1]
        path = path.strip().strip('"')
        if path.endswith(".py"):
            changed.add(path.replace(os.sep, "/"))
    return changed


def _changed_only_lint(opts, known, known_events, rules):
    """The pre-commit fast path: sha-validate the cached index against
    the tree (git's dirty bit alone is not enough — a pull or branch
    switch rewrites files git then calls clean), re-summarize only the
    stale files, and parse + analyze only the dirty call-graph region."""
    changed = git_changed_files(opts.repo)
    in_scope = {c for c in changed
                if any(c == t or c.startswith(t.rstrip("/") + "/")
                       for t in opts.targets)}
    deleted = {c for c in in_scope
               if not os.path.isfile(os.path.join(opts.repo, c))}
    errors = []
    cached = read_index(opts.index)
    if cached is None:
        # no usable cache: full phase 1 builds it; the region still
        # restricts phase 2 to the git-dirty files' neighborhood
        ctxs = _parse_targets(opts.targets, opts.repo, errors)
        index = build_index(ctxs)
        stale = (in_scope - deleted) & set(ctxs)
    else:
        # a deleted file's callers/callees need re-analysis (its lock
        # contributions and reachability are gone): collect the
        # neighborhood from the OLD graph, then drop the entry so the
        # stale summary cannot keep discharging proofs
        stale = set()
        if deleted & set(cached.files):
            fwd = cached.file_edges()
            for rel, tgts in fwd.items():
                if tgts & deleted:
                    stale.add(rel)
            for d in deleted:
                stale |= fwd.get(d, set())
                cached.remove_file(d)
        # sha-validate EVERY scanned file against the cache
        seen, missing = set(), []
        for path in iter_files(opts.targets, opts.repo, missing=missing):
            rel = os.path.relpath(
                os.path.abspath(path), opts.repo).replace(os.sep, "/")
            seen.add(rel)
            try:
                with open(path, encoding="utf-8") as f:
                    sha = hashlib.sha256(
                        f.read().encode("utf-8")).hexdigest()
            except OSError as e:
                errors.append(f"{rel}: unreadable: {e}")
                continue
            entry = cached.files.get(rel)
            if entry is None or entry.get("sha") != sha:
                stale.add(rel)
        errors.extend(f"target not found: {t}" for t in missing)
        for rel in set(cached.files) - seen:
            cached.remove_file(rel)  # left the scan set, however it went
        # git-dirty files stay seeded EVERY run (not just the run that
        # refreshes their cache entry): a finding in your working set
        # must keep re-appearing until the file is committed or fixed
        stale |= in_scope - deleted
        stale &= seen
        stale_ctxs = _parse_targets(sorted(stale), opts.repo, errors)
        for rel, ctx in stale_ctxs.items():
            cached.add_file(rel, summarize_file(ctx))
        index = cached.link()
    region = index.dirty_region(stale)
    # phase 2 reads and parses ONLY the region files — the point of the
    # cache (the full default run stays the CI truth)
    findings, suppressed, more = lint_paths(
        sorted(region), repo=opts.repo, known_metrics=known, rules=rules,
        known_events=known_events, index=index)
    errors.extend(more)
    write_index(opts.index, index)
    return findings, suppressed, errors, sorted(region | deleted)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="tpumx_lint",
        description="framework-aware static analysis for tpu-mx contracts")
    ap.add_argument("targets", nargs="*", default=list(DEFAULT_TARGETS),
                    help="files/dirs to lint (default: tpu_mx tools)")
    ap.add_argument("--format", choices=("human", "json"), default="human")
    ap.add_argument("--rules", default=None,
                    help="comma-separated subset of rules to run")
    ap.add_argument("--baseline", default=None,
                    help="findings baseline path (default: "
                         "<repo>/tools/tpumx_lint_baseline.json)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report baselined findings too")
    ap.add_argument("--write-baseline", action="store_true",
                    help="accept all current findings into the baseline")
    ap.add_argument("--changed-only", action="store_true",
                    help="re-analyze only git-dirty files and their "
                         "call-graph region (pre-commit fast path; the "
                         "full run is the CI truth)")
    ap.add_argument("--index", default=None,
                    help="project-index cache path (phase 1 output; "
                         "default: <repo>/tools/tpumx_lint_index.json)")
    ap.add_argument("--repo", default=REPO,
                    help="repository root relative targets resolve "
                         "against (tests use a scratch checkout)")
    opts = ap.parse_args(argv)

    # everything repo-relative derives from --repo: linting another
    # checkout must use ITS catalogs/baseline/index, not the host's
    # (and never clobber the host's warm cache)
    if opts.baseline is None:
        opts.baseline = os.path.join(opts.repo, "tools",
                                     "tpumx_lint_baseline.json")
    if opts.index is None:
        opts.index = os.path.join(opts.repo, "tools",
                                  "tpumx_lint_index.json")

    if opts.write_baseline and opts.changed_only:
        # a dirty-region run sees only a slice of the findings; writing
        # it as THE baseline would drop every fingerprint outside the
        # region and turn the next full CI run red
        ap.error("--write-baseline needs the full run, not --changed-only")

    rules = None
    if opts.rules:
        rules = {r.strip() for r in opts.rules.split(",") if r.strip()}
        valid = {p.name for p in build_passes(frozenset())}
        unknown = rules - valid
        if unknown:
            ap.error(f"unknown rules: {sorted(unknown)} "
                     f"(valid: {sorted(valid)})")

    known = load_known_metrics(repo=opts.repo)
    known_events = load_known_events(repo=opts.repo)
    if (known is None or known_events is None) \
            and (rules is None or "telemetry-catalog" in rules):
        # failing OPEN here would silently disable the whole catalog
        # pass (e.g. after a refactor that makes KNOWN_METRICS /
        # KNOWN_EVENTS a computed expression the static extractor can't
        # evaluate)
        missing = "KNOWN_METRICS from tpu_mx/telemetry.py" \
            if known is None else "KNOWN_EVENTS from tpu_mx/tracing.py"
        print(f"tpumx-lint: could not extract {missing} — the "
              "telemetry-catalog pass cannot run; keep the catalog a "
              "literal frozenset({...}) / dict and update "
              "load_known_metrics()/load_known_events()", file=sys.stderr)
        return 2

    t0 = time.perf_counter()
    region = None
    if opts.changed_only:
        findings, suppressed, errors, region = _changed_only_lint(
            opts, known, known_events, rules)
    else:
        ctxs_errors = []
        ctxs = _parse_targets(opts.targets, opts.repo, ctxs_errors)
        t_index0 = time.perf_counter()
        index = build_index(ctxs)
        t_index = time.perf_counter() - t_index0
        findings, suppressed, errors = [], [], ctxs_errors
        for rel in sorted(ctxs):
            found, sup = _run_passes(ctxs[rel], known, rules, known_events,
                                     index)
            findings.extend(found)
            suppressed.extend(sup)
        # refresh the serialized index so --changed-only starts warm
        if opts.targets == list(DEFAULT_TARGETS):
            try:
                write_index(opts.index, index)
            except OSError:
                pass  # a read-only checkout still lints
    elapsed = time.perf_counter() - t0

    if opts.write_baseline:
        write_baseline(opts.baseline, findings)
        print(f"tpumx-lint: baselined {len(findings)} finding(s) -> "
              f"{opts.baseline}")
        return 0

    baseline = set() if opts.no_baseline else read_baseline(opts.baseline)
    fresh = [f for f in findings if f.fingerprint() not in baseline]
    baselined = len(findings) - len(fresh)

    if opts.format == "json":
        payload = {
            "findings": [f.as_dict() for f in fresh],
            "baselined": baselined,
            "suppressed": len(suppressed),
            "errors": errors,
            "known_metrics_loaded": known is not None,
            "known_events_loaded": known_events is not None,
            "elapsed_seconds": round(elapsed, 3),
        }
        if region is not None:
            payload["changed_region"] = region
        print(json.dumps(payload, indent=1, sort_keys=True))
    else:
        for f in fresh:
            print(f.render())
        for e in errors:
            print(f"error: {e}")
        scope = (f" over {len(region)} dirty-region file(s)"
                 if region is not None else "")
        print(f"tpumx-lint: {len(fresh)} finding(s), "
              f"{baselined} baselined, {len(suppressed)} suppressed"
              f" in {elapsed:.1f}s{scope}"
              + ("" if known is not None else
                 " [WARNING: KNOWN_METRICS catalog not loaded]"))
    if errors:
        return 2
    return 1 if fresh else 0
