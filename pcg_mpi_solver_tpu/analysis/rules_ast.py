"""AST-level source rules.

``recovery-paths``: no silently-swallowed broad exception handlers in
the solve/cache/recovery/ops/parallel/obs layers.  The resilience
posture only works if every broad ``except`` either **re-raises**
(possibly after cleanup), **records** what happened (a metrics call —
``.event``/``.inc``/``.note``/``.gauge`` — a ``warnings.warn``, or the
bench's ``_log``), or carries an explicit ``# noqa: BLE001``
justification on the handler line (the repo convention for best-effort
cache/IO paths where a failure legitimately degrades to a miss).

This is the engine-native home of the logic ``tools/
check_recovery_paths.py`` exposes as a standalone CLI (that script is
now a thin shim over this module).  Scope extension (ISSUE 7): ``ops/``,
``parallel/`` and ``obs/`` joined the historical
solver/cache/resilience/validate set — a swallowed matvec/mesh/telemetry
failure hides a wrong answer exactly as effectively as a swallowed
recovery failure.
"""

from __future__ import annotations

import ast
import os
from typing import List

from pcg_mpi_solver_tpu.analysis.engine import REPO, Finding, rule

PKG = os.path.join(REPO, "pcg_mpi_solver_tpu")

#: scanned packages: the historical recovery scope + the ISSUE-7
#: extension (ops/parallel/obs) + the solve service (ISSUE 19 — a
#: swallowed daemon failure silently loses a tenant's job).
DEFAULT_SCOPE = (
    os.path.join(PKG, "solver"),
    os.path.join(PKG, "cache"),
    os.path.join(PKG, "resilience"),
    os.path.join(PKG, "validate"),
    os.path.join(PKG, "ops"),
    os.path.join(PKG, "parallel"),
    os.path.join(PKG, "obs"),
    os.path.join(PKG, "serve"),
)

# Exception names considered "broad" when caught: anything narrower
# (OSError, ValueError, ...) expresses an expectation and is exempt.
_BROAD = {"Exception", "BaseException"}

# A call to any of these names (bare or attribute) inside the handler
# counts as recording the failure.
_LOG_CALLS = {"event", "inc", "note", "gauge", "warn", "warning",
              "exception", "_log"}


def _is_broad(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:                            # bare `except:`
        return True
    names = []
    if isinstance(t, ast.Tuple):
        names = [e.id for e in t.elts if isinstance(e, ast.Name)]
    elif isinstance(t, ast.Name):
        names = [t.id]
    return any(n in _BROAD for n in names)


def _handler_ok(handler: ast.ExceptHandler, lines: List[str]) -> bool:
    # explicit justification on the `except` line (repo convention)
    line = lines[handler.lineno - 1]
    if "noqa" in line and "BLE001" in line:
        return True
    for node in ast.walk(handler):
        if isinstance(node, ast.Raise):
            return True
        if isinstance(node, ast.Call):
            f = node.func
            name = (f.attr if isinstance(f, ast.Attribute)
                    else getattr(f, "id", ""))
            if name in _LOG_CALLS:
                return True
    return False


def check_source(source: str, path: str = "<source>") -> List[str]:
    """Violations in one python source blob (path used for labels)."""
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [f"{path}: unparseable ({e})"]
    lines = source.splitlines()
    errs = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler) and _is_broad(node) \
                and not _handler_ok(node, lines):
            errs.append(
                f"{path}:{node.lineno}: broad `except` neither re-raises, "
                "logs a metrics/warning event, nor carries a "
                "`# noqa: BLE001` justification")
    return errs


def check_file(path: str) -> List[str]:
    try:
        with open(path, encoding="utf-8") as f:
            source = f.read()
    except OSError as e:
        return [f"{path}: unreadable ({e})"]
    return check_source(source, path)


def iter_py_files(paths) -> List[str]:
    out = []
    for p in paths:
        if os.path.isdir(p):
            for root, _dirs, files in os.walk(p):
                if "__pycache__" in root:
                    continue
                out.extend(os.path.join(root, fn) for fn in sorted(files)
                           if fn.endswith(".py"))
        elif p.endswith(".py"):
            out.append(p)
    return out


@rule("recovery-paths", kind="ast", fast=True,
      doc="every broad `except` in solver/cache/resilience/validate/ops/"
          "parallel/obs re-raises, records, or carries # noqa: BLE001")
def recovery_paths_rule(ctx) -> List[Finding]:
    findings = []
    for f in iter_py_files(DEFAULT_SCOPE):
        for err in check_file(f):
            # err is "path:line: message" — split the anchor off for the
            # baseline-stable loc
            loc, _, msg = err.partition(": ")
            findings.append(Finding(
                rule="recovery-paths",
                loc=os.path.relpath(loc, REPO),
                message=msg))
    return findings


# ----------------------------------------------------------------------
# recovery-coverage: every Krylov dispatch surface of the drivers is
# wrapped by the resilience harness or carries a documented exemption
# (ISSUE 9).
# ----------------------------------------------------------------------

#: Files whose top-level functions/methods are swept for dispatch
#: surfaces.  ``solver/chunked.py`` (ChunkedEngine) and
#: ``resilience/engine.py`` are harness-INTERNAL — their dispatches are
#: only ever reached through a wrapped caller below.
COVERAGE_FILES = ("pcg_mpi_solver_tpu/solver/driver.py",
                  "pcg_mpi_solver_tpu/solver/newmark.py",
                  "pcg_mpi_solver_tpu/serve/daemon.py")

#: Krylov-TERMINAL dispatch-span names: a swept function whose subtree
#: opens ``<recorder>.dispatch("<one of these>")`` — or calls the
#: one-shot ``_step_fn`` program — runs a solve to (partial)
#: termination and is therefore a dispatch surface.  Setup/finalize
#: spans (start, restart, many_start, many_final, fallback_prec, ...)
#: are not surfaces: they hold no Krylov iterations to lose.
SOLVE_DISPATCH_NAMES = frozenset(
    {"step", "solve_many", "cycle", "inner_cycle", "many_cycle"})

#: (file, function) -> coverage requirement.  ``calls:<name>`` — the
#: function must invoke that recovery-harness entry (the positive proof
#: that the surface is wrapped); ``exempt`` — the function must carry a
#: ``recovery-exempt:`` comment documenting WHY no harness applies
#: (e.g. a donated one-shot operand that must never be re-dispatched).
#: A swept surface missing from this registry is itself a finding, so a
#: new dispatch path cannot ship silently unprotected.
RECOVERY_SURFACES = {
    ("pcg_mpi_solver_tpu/solver/driver.py", "_step_chunked"):
        "calls:run_with_recovery",
    ("pcg_mpi_solver_tpu/solver/driver.py", "_solve_many_chunked"):
        "calls:run_many_with_recovery",
    ("pcg_mpi_solver_tpu/solver/driver.py", "solve_many"):
        "calls:_dispatch_with_retry",
    ("pcg_mpi_solver_tpu/solver/driver.py", "step"): "exempt",
    ("pcg_mpi_solver_tpu/solver/newmark.py", "_step_chunked"):
        "calls:run_with_recovery",
    ("pcg_mpi_solver_tpu/solver/newmark.py", "step"): "exempt",
    # solve-service dispatch (ISSUE 19): jobs reach the solver ONLY
    # through Solver.solve_many — the per-column recovery/quarantine
    # path — so a poisoned tenant cannot fail its co-batched block
    ("pcg_mpi_solver_tpu/serve/daemon.py", "_dispatch_block"):
        "calls:solve_many",
}


def _top_level_functions(tree: ast.Module):
    """Module-level functions and class methods (nested closures belong
    to — and are walked with — their enclosing definition)."""
    out = []
    for node in tree.body:
        if isinstance(node, ast.ClassDef):
            out.extend(n for n in node.body
                       if isinstance(n, ast.FunctionDef))
        elif isinstance(node, ast.FunctionDef):
            out.append(node)
    return out


def _is_dispatch_surface(fn: ast.FunctionDef) -> bool:
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr == "dispatch" \
                and node.args:
            a = node.args[0]
            if isinstance(a, ast.Constant) \
                    and a.value in SOLVE_DISPATCH_NAMES:
                return True
        if isinstance(f, ast.Attribute) and f.attr == "_step_fn":
            return True
    return False


def _calls_name(fn: ast.FunctionDef, name: str) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            f = node.func
            got = (f.attr if isinstance(f, ast.Attribute)
                   else getattr(f, "id", ""))
            if got == name:
                return True
    return False


def check_recovery_coverage(sources) -> List[str]:
    """Coverage violations for ``{relpath: source}`` (the rule feeds the
    real files; tests feed seeded-violation sources)."""
    errs: List[str] = []
    for rel, source in sources.items():
        try:
            tree = ast.parse(source)
        except SyntaxError as e:
            errs.append(f"{rel}:0: unparseable ({e})")
            continue
        lines = source.splitlines()
        seen = set()
        for fn in _top_level_functions(tree):
            key = (rel, fn.name)
            req = RECOVERY_SURFACES.get(key)
            if _is_dispatch_surface(fn):
                seen.add(key)
                if req is None:
                    errs.append(
                        f"{rel}:{fn.lineno}: `{fn.name}` opens a "
                        "Krylov-terminal dispatch but is not registered "
                        "in RECOVERY_SURFACES — wrap it in the recovery "
                        "harness (run_with_recovery / "
                        "run_many_with_recovery / _dispatch_with_retry) "
                        "and register it, or register a documented "
                        "exemption")
                    continue
            if req is None:
                continue
            if req.startswith("calls:"):
                want = req.split(":", 1)[1]
                if not _calls_name(fn, want):
                    errs.append(
                        f"{rel}:{fn.lineno}: dispatch surface "
                        f"`{fn.name}` no longer calls its registered "
                        f"recovery harness `{want}` — the surface runs "
                        "unprotected")
            elif req == "exempt":
                seg = "\n".join(
                    lines[fn.lineno - 1:fn.end_lineno or fn.lineno])
                if "recovery-exempt:" not in seg:
                    errs.append(
                        f"{rel}:{fn.lineno}: dispatch surface "
                        f"`{fn.name}` is registered exempt but carries "
                        "no `recovery-exempt:` comment — document why "
                        "no recovery harness applies, or wrap it")
        # stale registry entries: the function moved/renamed, so the
        # registry would silently vouch for nothing
        names = {fn.name for fn in _top_level_functions(tree)}
        for (f, name), _req in RECOVERY_SURFACES.items():
            if f == rel and name not in names:
                errs.append(
                    f"{rel}:0: RECOVERY_SURFACES registers "
                    f"`{name}` but no such function exists — update "
                    "the registry")
    return errs


@rule("recovery-coverage", kind="ast", fast=True,
      doc="every Krylov dispatch surface in driver.py/newmark.py "
          "(one-shot, chunked scalar, chunked blocked, mixed inner) is "
          "wrapped by the recovery harness or carries a documented "
          "`recovery-exempt:` justification")
def recovery_coverage_rule(ctx) -> List[Finding]:
    sources = {}
    for rel in COVERAGE_FILES:
        path = os.path.join(REPO, rel)
        try:
            with open(path, encoding="utf-8") as f:
                sources[rel] = f.read()
        except OSError as e:
            return [Finding(rule="recovery-coverage", loc=rel,
                            message=f"unreadable ({e})")]
    findings = []
    for err in check_recovery_coverage(sources):
        loc, _, msg = err.partition(": ")
        findings.append(Finding(rule="recovery-coverage", loc=loc,
                                message=msg))
    return findings


# ----------------------------------------------------------------------
# consensus-coverage: every host-side collective on the dispatch path
# routes its verdict through parallel/consensus or carries a documented
# exemption (ISSUE 18).
# ----------------------------------------------------------------------

#: Files swept for host-side collective call sites.  The dispatch path
#: only: setup-layer collectives (``parallel/partition.py`` glue
#: exchanges, ``cache/partition_cache.py``) run once before any Krylov
#: loop and already route their gate verdicts through the consensus
#: module by construction.
CONSENSUS_COVERAGE_FILES = (
    "pcg_mpi_solver_tpu/solver/driver.py",
    "pcg_mpi_solver_tpu/solver/chunked.py",
    "pcg_mpi_solver_tpu/resilience/engine.py",
)

#: Host-collective call names that pair blocking rounds across
#: processes: a divergent branch around ANY of these wedges the fleet.
#: Deliberately NOT ``warmup`` — ``ChunkedEngine.warmup`` is the
#: unrelated compile-warmup method and would shadow every sweep.
COLLECTIVE_CALL_NAMES = frozenset(
    {"allreduce", "allreduce_many", "allreduce_groups",
     "process_allgather", "sync_global_devices"})

#: (file, function) -> coverage requirement, the RECOVERY_SURFACES
#: shape: ``calls:<name>`` — the function must invoke that
#: ``parallel/consensus`` primitive (or the chunk-boundary liveness
#: sync), the positive proof its group verdict cannot diverge;
#: ``exempt`` — the function must carry a ``consensus-exempt:`` comment
#: documenting why no verdict needs agreement (an unconditional data
#: gather or plain barrier that every process reaches).
CONSENSUS_SITES = {
    # engage decision gates collective code paths -> agree_flag
    ("pcg_mpi_solver_tpu/solver/driver.py", "__init__"):
        "calls:agree_flag",
    # export-glue layout exchange: unconditional data movement
    ("pcg_mpi_solver_tpu/solver/driver.py", "_exchange_export_glue"):
        "exempt",
    # runstore-prepared barrier: no verdict, every process reaches it
    ("pcg_mpi_solver_tpu/solver/driver.py", "solve"): "exempt",
    # chunk loops open every iteration with the guarded liveness sync
    ("pcg_mpi_solver_tpu/solver/chunked.py", "run"):
        "calls:sync_boundary",
    # scalar ladder triggers are group-agreed before branching
    ("pcg_mpi_solver_tpu/resilience/engine.py", "run_with_recovery"):
        "calls:agree_trigger",
    # per-column triggers (quarantine/ladder masks) likewise
    ("pcg_mpi_solver_tpu/resilience/engine.py",
     "run_many_with_recovery"): "calls:agree_triggers",
}


def _has_collective_call(fn: ast.FunctionDef) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            f = node.func
            got = (f.attr if isinstance(f, ast.Attribute)
                   else getattr(f, "id", ""))
            if got in COLLECTIVE_CALL_NAMES:
                return True
    return False


def check_consensus_coverage(sources) -> List[str]:
    """Coverage violations for ``{relpath: source}`` (the rule feeds the
    real files; tests feed seeded-violation sources)."""
    errs: List[str] = []
    for rel, source in sources.items():
        try:
            tree = ast.parse(source)
        except SyntaxError as e:
            errs.append(f"{rel}:0: unparseable ({e})")
            continue
        lines = source.splitlines()
        for fn in _top_level_functions(tree):
            key = (rel, fn.name)
            req = CONSENSUS_SITES.get(key)
            if _has_collective_call(fn):
                if req is None:
                    errs.append(
                        f"{rel}:{fn.lineno}: `{fn.name}` calls a "
                        "host-side collective but is not registered in "
                        "CONSENSUS_SITES — route its verdict through "
                        "parallel/consensus (agree / agree_flag / "
                        "agree_trigger / agree_triggers) and register "
                        "it, or register a documented exemption")
                    continue
            if req is None:
                continue
            if req.startswith("calls:"):
                want = req.split(":", 1)[1]
                if not _calls_name(fn, want):
                    errs.append(
                        f"{rel}:{fn.lineno}: collective site "
                        f"`{fn.name}` no longer calls its registered "
                        f"consensus primitive `{want}` — a divergent "
                        "group verdict wedges the fleet")
            elif req == "exempt":
                seg = "\n".join(
                    lines[fn.lineno - 1:fn.end_lineno or fn.lineno])
                if "consensus-exempt:" not in seg:
                    errs.append(
                        f"{rel}:{fn.lineno}: collective site "
                        f"`{fn.name}` is registered exempt but carries "
                        "no `consensus-exempt:` comment — document why "
                        "the verdict needs no agreement, or route it "
                        "through parallel/consensus")
        names = {fn.name for fn in _top_level_functions(tree)}
        for (f, name), _req in CONSENSUS_SITES.items():
            if f == rel and name not in names:
                errs.append(
                    f"{rel}:0: CONSENSUS_SITES registers `{name}` but "
                    "no such function exists — update the registry")
    return errs


@rule("consensus-coverage", kind="ast", fast=True,
      doc="every host-side collective call site on the dispatch path "
          "(driver.py / chunked.py / resilience engine) routes its "
          "group verdict through parallel/consensus or carries a "
          "documented `consensus-exempt:` justification")
def consensus_coverage_rule(ctx) -> List[Finding]:
    sources = {}
    for rel in CONSENSUS_COVERAGE_FILES:
        path = os.path.join(REPO, rel)
        try:
            with open(path, encoding="utf-8") as f:
                sources[rel] = f.read()
        except OSError as e:
            return [Finding(rule="consensus-coverage", loc=rel,
                            message=f"unreadable ({e})")]
    findings = []
    for err in check_consensus_coverage(sources):
        loc, _, msg = err.partition(": ")
        findings.append(Finding(rule="consensus-coverage", loc=loc,
                                message=msg))
    return findings


# ----------------------------------------------------------------------
# serve-admission-events: every admission-decision outcome of the solve
# service emits its schema-versioned telemetry event (ISSUE 19) — the
# no-silent-drops contract, proven statically.
# ----------------------------------------------------------------------

#: Files swept for admission/lifecycle decision sites.
ADMISSION_COVERAGE_FILES = (
    "pcg_mpi_solver_tpu/serve/admission.py",
    "pcg_mpi_solver_tpu/serve/daemon.py",
)

#: (file, function) -> the event kinds the function MUST emit via a
#: constant-first-arg ``.event("<kind>", ...)`` call.  Each kind must
#: also exist in obs/schema.EVENT_KINDS (a registered typo would vouch
#: for an event no consumer can validate).  A registered function that
#: disappears is itself a finding — the registry cannot go stale
#: silently.
ADMISSION_EVENT_SITES = {
    ("pcg_mpi_solver_tpu/serve/admission.py", "admit"):
        ("job_admit",),
    ("pcg_mpi_solver_tpu/serve/admission.py", "_reject"):
        ("job_reject",),
    ("pcg_mpi_solver_tpu/serve/admission.py", "shed_past_deadline"):
        ("job_shed",),
    ("pcg_mpi_solver_tpu/serve/daemon.py", "_dispatch_block"):
        ("job_done", "job_quarantine"),
    ("pcg_mpi_solver_tpu/serve/daemon.py", "_finish_failed"):
        ("job_done",),
    ("pcg_mpi_solver_tpu/serve/daemon.py", "run"):
        ("serve_drain",),
}


def _emits_event(fn: ast.FunctionDef, kind: str) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Call) and node.args:
            f = node.func
            name = (f.attr if isinstance(f, ast.Attribute)
                    else getattr(f, "id", ""))
            a = node.args[0]
            if name == "event" and isinstance(a, ast.Constant) \
                    and a.value == kind:
                return True
    return False


def check_admission_events(sources) -> List[str]:
    """Violations for ``{relpath: source}`` (the rule feeds the real
    files; tests feed seeded-violation sources)."""
    from pcg_mpi_solver_tpu.obs.schema import EVENT_KINDS

    errs: List[str] = []
    for rel, source in sources.items():
        try:
            tree = ast.parse(source)
        except SyntaxError as e:
            errs.append(f"{rel}:0: unparseable ({e})")
            continue
        fns = {fn.name: fn for fn in _top_level_functions(tree)}
        for (f, name), kinds in sorted(ADMISSION_EVENT_SITES.items()):
            if f != rel:
                continue
            fn = fns.get(name)
            if fn is None:
                errs.append(
                    f"{rel}:0: ADMISSION_EVENT_SITES registers "
                    f"`{name}` but no such function exists — update "
                    "the registry")
                continue
            for kind in kinds:
                if kind not in EVENT_KINDS:
                    errs.append(
                        f"{rel}:{fn.lineno}: ADMISSION_EVENT_SITES "
                        f"requires `{name}` to emit `{kind}`, which is "
                        "not a schema EVENT_KINDS kind — fix the "
                        "registry or add the kind to obs/schema.py")
                    continue
                if not _emits_event(fn, kind):
                    errs.append(
                        f"{rel}:{fn.lineno}: admission-decision site "
                        f"`{name}` no longer emits the "
                        f"schema-versioned `{kind}` event — a service "
                        "outcome would go silent")
    return errs


@rule("serve-admission-events", kind="ast", fast=True,
      doc="every solve-service admission/lifecycle outcome (admit, "
          "reject, shed, done, quarantine, drain) emits its "
          "schema-versioned telemetry event — decisions are never "
          "silent")
def serve_admission_events_rule(ctx) -> List[Finding]:
    sources = {}
    for rel in ADMISSION_COVERAGE_FILES:
        path = os.path.join(REPO, rel)
        try:
            with open(path, encoding="utf-8") as f:
                sources[rel] = f.read()
        except OSError as e:
            return [Finding(rule="serve-admission-events", loc=rel,
                            message=f"unreadable ({e})")]
    findings = []
    for err in check_admission_events(sources):
        loc, _, msg = err.partition(": ")
        findings.append(Finding(rule="serve-admission-events", loc=loc,
                                message=msg))
    return findings
