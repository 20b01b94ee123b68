"""Artifact rules: committed JSON artifacts validate against the
versioned contracts in ``obs/schema.py``.

``telemetry-schema`` covers the files ``tools/check_telemetry_schema.py``
(now a thin shim over this module) historically linted:

* ``*.jsonl``          — telemetry event streams (``--telemetry-out``)
* ``BENCH_*.json``     — bench round artifacts (raw line or round
                         wrapper; failed-round wrappers with
                         ``parsed: null`` pass)
* ``bench_*.json``     — provisional/salvage side files from bench.py

Import-light on purpose (obs/schema.py is jax/numpy-free): this runs in
the --fast gate.
"""

from __future__ import annotations

import glob
import json
import os
from typing import List

from pcg_mpi_solver_tpu.analysis.engine import REPO, Finding, rule


def default_paths() -> list:
    """The committed artifacts the tier-1 check covers: round artifacts
    at the repo root, and the fixtures of each shape under tests/data."""
    return sorted(glob.glob(os.path.join(REPO, "BENCH_*.json"))
                  + glob.glob(os.path.join(REPO, "tests", "data",
                                           "bench_rounds", "BENCH_*.json")))


def check_file(path: str) -> list:
    """Validate one artifact; returns error strings prefixed with path."""
    from pcg_mpi_solver_tpu.obs.schema import (
        validate_bench_text, validate_jsonl_text)

    try:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    except OSError as e:
        return [f"{path}: unreadable ({e})"]
    name = os.path.basename(path)
    if name.endswith(".jsonl"):
        errs = validate_jsonl_text(text)
    elif name.endswith(".json"):
        if name.startswith("bench_salvage"):
            # salvage wrapper: {"lines": [{"line": <bench json str>}]}
            errs = []
            try:
                doc = json.loads(text)
            except ValueError as e:
                errs = [f"not JSON ({e})"]
            else:
                for i, entry in enumerate(doc.get("lines", [])):
                    errs.extend(
                        f"lines[{i}]: {e}"
                        for e in validate_bench_text(entry.get("line", "")))
        else:
            errs = validate_bench_text(text)
    else:
        errs = ["unrecognized artifact type (expected .json/.jsonl)"]
    return [f"{path}: {e}" for e in errs]


@rule("telemetry-schema", kind="artifact", fast=True,
      doc="committed BENCH_*.json artifacts (and any telemetry JSONL) "
          "validate against the versioned obs/schema.py contracts")
def telemetry_schema_rule(ctx) -> List[Finding]:
    findings = []
    for p in default_paths():
        for err in check_file(p):
            loc, _, msg = err.partition(": ")
            findings.append(Finding(
                rule="telemetry-schema",
                loc=os.path.relpath(loc, REPO),
                message=msg or err))
    return findings


# -- doc-schema sync (ISSUE 16) -----------------------------------------

EVENT_TABLE_DOC = os.path.join("docs", "OBSERVABILITY.md")


def documented_event_kinds(doc_text: str) -> set:
    """Event kinds documented in OBSERVABILITY.md's event table: the
    first backticked token of each table row (``| `kind` | ... |``)."""
    import re

    kinds = set()
    for line in doc_text.splitlines():
        m = re.match(r"^\|\s*`([a-z0-9_]+)`\s*\|", line)
        if m:
            kinds.add(m.group(1))
    return kinds


def check_doc_schema_sync(doc_text: str, kinds=None) -> List[str]:
    """Every event kind in obs/schema.py EVENT_KINDS must have a row in
    the doc's event table — an event a consumer cannot look up is
    undocumented telemetry.  Returns one error string per missing kind
    (testable directly on synthetic doc text)."""
    if kinds is None:
        from pcg_mpi_solver_tpu.obs.schema import EVENT_KINDS

        kinds = EVENT_KINDS
    documented = documented_event_kinds(doc_text)
    return [f"event kind `{k}` (obs/schema.py EVENT_KINDS) has no row "
            f"in the event table"
            for k in kinds if k not in documented]


@rule("doc-schema-sync", kind="artifact", fast=True,
      doc="every event kind in obs/schema.py EVENT_KINDS has a row in "
          "docs/OBSERVABILITY.md's event table (schema without doc is "
          "telemetry nobody can read back)")
def doc_schema_sync_rule(ctx) -> List[Finding]:
    path = os.path.join(REPO, EVENT_TABLE_DOC)
    try:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    except OSError as e:
        return [Finding(rule="doc-schema-sync", loc=EVENT_TABLE_DOC,
                        message=f"unreadable ({e})")]
    return [Finding(rule="doc-schema-sync", loc=EVENT_TABLE_DOC,
                    message=msg)
            for msg in check_doc_schema_sync(text)]
