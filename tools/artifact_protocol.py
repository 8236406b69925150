"""Shared write protocol for on-chip measurement artifacts
(MFU_PROBE_<round>.json, LONGCTX_<round>.json, ...).

The contract (see .claude/skills/verify/SKILL.md "hardware artifacts are
merge-on-write"):
- a partial rerun (--configs / --lens retry after a transport blip) MERGES
  into the existing artifact — this run's rows replace their own keys,
  sibling rows survive (a retry once clobbered a full sweep's rows);
- a TPU-less process REFUSES to overwrite a platform=tpu artifact (a
  chipless run or CPU smoke pointed at the default --out must not
  replace real rows with a skip/smoke record);
- writes are atomic (tmp+rename) and happen after every row, so a later
  hang cannot lose earlier results.

Rows should be self-describing (carry their own config/geometry fields):
merged rows may come from runs with different settings, and the row is
the only place that provenance survives.
"""
from __future__ import annotations

import json
import os

# Round stamp for every hardware artifact this tree produces.  Single
# source of truth: the watcher, validate sweep, MFU probe, long-context
# bench and chip profiler all derive their default --out from here, so a
# new round is one-line (or TPUMX_ROUND=rNN) instead of a five-file sweep.
ROUND = os.environ.get("TPUMX_ROUND", "r05")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def artifact(name, ext="json"):
    """Round-stamped artifact path at the repo root:
    artifact("MFU_PROBE") -> <repo>/MFU_PROBE_r05.json."""
    return os.path.join(_REPO, f"{name}_{ROUND}.{ext}")


def load_prior(path):
    """The existing artifact as a dict; {} if absent/corrupt."""
    try:
        with open(path) as f:
            prior = json.load(f)
        return prior if isinstance(prior, dict) else {}
    except (OSError, ValueError):
        return {}


def refuses_clobber(prior, platform):
    """True when THIS process (running on `platform`) must not overwrite
    the artifact `prior` (measured on real TPU)."""
    return platform != "tpu" and prior.get("platform") == "tpu"


def merge_prior_sections(record, prior, sections, require_platform=None):
    """Graft prior rows this run hasn't produced into record[section].
    This run's rows win on key collision.  require_platform: only merge
    from a prior artifact measured on that platform (pass the current
    platform so e.g. CPU-smoke rows never leak into a TPU artifact)."""
    if require_platform is not None and \
            prior.get("platform") != require_platform:
        return record
    for sect in sections:
        if isinstance(prior.get(sect), dict) and \
                isinstance(record.get(sect), dict):
            merged = dict(prior[sect])
            merged.update(record[sect])
            record[sect] = merged
    return record


def write_atomic(path, record):
    with open(path + ".tmp", "w") as f:
        json.dump(record, f, indent=1)
    os.replace(path + ".tmp", path)
