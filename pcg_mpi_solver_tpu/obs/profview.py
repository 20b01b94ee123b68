"""Profiler-trace consumer: parse device traces into per-phase
attribution and a MEASURED collective-overlap verdict.

The repo has predicted per-iteration phase costs since ISSUE 12
(obs/perf.py roofline model) and recorded them with compiled probe
programs (obs/phases.py) — but until this module nothing ever READ a
captured ``jax.profiler`` trace back, so the round-5 question ("where do
the unattributed ~45% of 24.994 ms/iter go?") and the pipelined-CG
overlap claim (PR 10 proved it STATICALLY on the jaxpr; the premise of
arXiv:2105.06176 is that the psum *measurably* hides behind the stencil)
had no mechanical answer.  This closes the loop:

* :func:`capture_solve_profile` — a bounded one-shot capture around any
  warm solver dispatch (``jax.profiler.start_trace``/``stop_trace``),
  multi-process-safe (per-process dir suffix, like the telemetry
  shards), writing a ``profview_meta.json`` sidecar next to the trace
  so the artifact is SELF-DESCRIBING offline: committed iterations, the
  whole-solve anchor, the engaged variant/precond/nrhs/backend shape,
  and the HLO-instruction -> phase ``scope_map`` derived from the
  compiled program's ``op_name`` metadata.

* a TOLERANT reader for the trace-viewer JSON(.gz) the profiler emits:
  gz or plain, a truncated/unreadable file or a trace with no device-op
  events degrades to a NAMED verdict (``degraded: <reason>``), never a
  crash — the artifact a killed process leaves behind must still parse.

* :func:`bucket_phases` — buckets device-op wall time per phase via the
  ``pcg/*`` ``jax.named_scope`` labels threaded through the
  solver/pcg.py loop bodies (all three variants, scalar + blocked).  On
  TPU the labels ride the event metadata directly; on CPU the events
  carry bare HLO instruction names (``dot.1``, ``multiply_add_fusion``)
  and the sidecar scope_map restores the mapping.  Events matching no
  phase are COUNTED and their time reported (``other``); a ``pcg/<x>``
  label that is not one of the four known phases is counted under
  ``unknown_scopes`` — never silently dropped (the analysis/
  ``scope-labels`` rule holds both contracts).

* :func:`collective_overlap` — the measured twin of PR 10's static
  psum-overlap rule: per device lane, the wall-clock intersection of
  collective-op spans with concurrent compute-op spans on OTHER
  threads of the same lane, as a fraction of total collective time.
  Contract: the traced pipelined program must compute a fraction where
  classic's serialized reductions report ~0 (on a 1-core host both may
  be ~0 — the parse/bucket/reconcile pipeline is what CPU proves; the
  number is the hardware window's to confirm).

The report is emitted as a schema-versioned ``prof_report`` event +
``prof.*`` gauges, reconciled against ``obs/perf.cost_model()`` by the
extended ``pcg-tpu perf-report`` (predicted | recorded | measured) and
readable offline from any artifact via ``pcg-tpu prof-report PATH``.

Import-light by contract (no jax, no numpy at import): jax is imported
only inside :func:`capture_solve_profile` / :func:`scope_map_from_solver`.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import time
from typing import Any, Dict, List, Optional, Tuple

#: named-scope label -> attribution phase (the obs/perf.PHASES rows).
#: solver/pcg.py threads exactly these labels through every loop body;
#: the analysis/ ``scope-labels`` rule proves each appears in the traced
#: hot loop of every variant (scalar + blocked).
PHASE_SCOPES: Dict[str, str] = {
    "pcg/matvec": "matvec",
    "pcg/precond": "precond",
    "pcg/reduce": "reduction",
    "pcg/axpy": "axpy",
}

#: substrings identifying a collective device op (XLA instruction
#: naming; the -start/-done halves of async collectives match too).
COLLECTIVE_MARKERS = ("all-reduce", "all-gather", "reduce-scatter",
                      "collective-permute", "all-to-all")

#: container ops whose span WRAPS other ops on the same thread — they
#: must join neither the phase buckets nor the overlap compute set (a
#: ``while`` span intersecting its own body's collective would read as
#: fake 100% overlap).
CONTAINER_OPS = frozenset({"while", "call", "conditional", "cond",
                           "tuple", "parameter", "get-tuple-element"})

#: sidecar filename written next to the trace by capture_solve_profile.
PROFVIEW_META = "profview_meta.json"
PROFVIEW_META_SCHEMA = "pcg-tpu-profview-meta/1"

_SCOPE_RE = re.compile(r"pcg/([A-Za-z0-9_]+)")


# ----------------------------------------------------------------------
# interval math (unit-tested on synthetic timelines)
# ----------------------------------------------------------------------

def merge_intervals(spans: List[Tuple[float, float]]
                    ) -> List[Tuple[float, float]]:
    """Sorted union of half-open [s, e) intervals (degenerate/negative
    spans dropped)."""
    spans = sorted((s, e) for s, e in spans if e > s)
    out: List[Tuple[float, float]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def intersect_len(span: Tuple[float, float],
                  merged: List[Tuple[float, float]]) -> float:
    """Length of ``span``'s intersection with a merged interval union."""
    s, e = span
    total = 0.0
    for ms, me in merged:
        if me <= s:
            continue
        if ms >= e:
            break
        total += min(e, me) - max(s, ms)
    return total


# ----------------------------------------------------------------------
# tolerant trace reading
# ----------------------------------------------------------------------

def find_trace_files(path: str) -> List[str]:
    """Every ``*.trace.json(.gz)`` under ``path`` (a file, a profile run
    dir, or a capture root containing ``plugins/profile/<run>/``),
    newest run first."""
    if os.path.isfile(path):
        return [path]
    if not os.path.isdir(path):
        return []
    hits: List[str] = []
    for root, _dirs, files in os.walk(path):
        for fn in files:
            if fn.endswith((".trace.json", ".trace.json.gz")):
                hits.append(os.path.join(root, fn))
    hits.sort(key=lambda p: os.path.getmtime(p), reverse=True)
    return hits


def read_trace_events(path: str) -> Tuple[List[dict], List[str]]:
    """(traceEvents, problems) of one trace-viewer JSON(.gz) file.
    A truncated/unreadable file returns ([], [named reason]) — the
    killed-process artifact must degrade, never crash."""
    try:
        if path.endswith(".gz"):
            with gzip.open(path, "rt", encoding="utf-8",
                           errors="replace") as f:
                text = f.read()
        else:
            with open(path, encoding="utf-8", errors="replace") as f:
                text = f.read()
    except (OSError, EOFError) as e:
        return [], [f"unreadable trace file ({type(e).__name__}: {e})"]
    try:
        doc = json.loads(text)
    except ValueError as e:
        return [], [f"truncated/invalid trace JSON ({e})"]
    evs = doc.get("traceEvents") if isinstance(doc, dict) else None
    if not isinstance(evs, list):
        return [], ["no traceEvents array in trace JSON"]
    return evs, []


def _base_name(name: str) -> str:
    """HLO instruction base name: strip ``.clone``/``.N`` suffixes
    (``multiply_add_fusion.clone`` -> ``multiply_add_fusion``,
    ``all-reduce.0`` -> ``all-reduce``)."""
    while True:
        if name.endswith(".clone"):
            name = name[:-6]
            continue
        head, dot, tail = name.rpartition(".")
        if dot and tail.isdigit():
            name = head
            continue
        return name


def device_ops(events: List[dict]) -> List[dict]:
    """Normalized device-op records from raw trace events.

    A device op is a complete ("ph" == "X") event that names an XLA op:
    its args carry hlo metadata (``hlo_op``/``hlo_module``/
    ``hlo_category``/``tf_op``/``long_name`` — the CPU and TPU trace
    flavors between them), and it is not a container op.  Host-side
    python/runtime events (``$builtins ...``, ``TfrtCpuExecutable::*``)
    never qualify."""
    out = []
    for e in events:
        if e.get("ph") != "X":
            continue
        args = e.get("args")
        if not isinstance(args, dict):
            continue
        if not any(k in args for k in ("hlo_op", "hlo_module",
                                       "hlo_category", "tf_op",
                                       "long_name")):
            continue
        name = str(e.get("name", ""))
        base = _base_name(name)
        if base in CONTAINER_OPS:
            continue
        try:
            ts = float(e.get("ts", 0.0))
            dur = float(e.get("dur", 0.0))
        except (TypeError, ValueError):
            continue
        # searchable text: the name plus every string arg (TPU events
        # carry the full op_name stack in tf_op/long_name)
        text = " ".join([name] + [str(v) for v in args.values()
                                  if isinstance(v, str)])
        out.append({"name": name, "base": base, "ts": ts, "dur": dur,
                    "pid": e.get("pid", 0), "tid": e.get("tid", 0),
                    "text": text})
    return out


def is_collective(base: str) -> bool:
    return any(m in base for m in COLLECTIVE_MARKERS)


# ----------------------------------------------------------------------
# scope map (HLO instruction name -> phase)
# ----------------------------------------------------------------------

_METADATA_RE = re.compile(
    r"%?([A-Za-z0-9_.\-]+)\s*=\s*.*?op_name=\"([^\"]+)\"")
_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?([A-Za-z0-9_.\-]+)\s.*\{\s*$")
_CALLS_RE = re.compile(
    r"%?([A-Za-z0-9_.\-]+)\s*=\s*.*?\bfusion\(([^)]*)\).*"
    r"calls=%?([A-Za-z0-9_.\-]+)")
_OPERAND_RE = re.compile(r"%([A-Za-z0-9_.\-]+)")


def scope_map_from_hlo_text(text: str) -> Dict[str, str]:
    """{instruction name: phase} for every instruction whose ``op_name``
    metadata carries a ``pcg/*`` named-scope label (the optimized-HLO
    ``as_text()`` of the profiled executable).  A fusion whose own line
    carries no label (the installed XLA puts the metadata on the fused
    computation's instructions only) takes the phase its fused
    computation's labeled instructions agree on; failing that (XLA's
    split of a labeled reduction into an unlabeled reduce-window), the
    phase its labeled operands agree on.  A label OUTSIDE the
    known phase set maps to the marker ``"?<label>"`` — the parser then
    counts it into ``unknown_scopes`` instead of silently folding a
    future phase into 'other' (the scope-labels loudness contract must
    hold on the sidecar path too, not just on TPU event text)."""
    out: Dict[str, str] = {}
    comp_phases: Dict[str, set] = {}
    calls: List[Tuple[str, str, List[str]]] = []
    comp = None
    for line in text.splitlines():
        h = _COMPUTATION_RE.match(line)
        if h is not None:
            comp = h.group(1)
            continue
        m = _METADATA_RE.search(line)
        sm = _SCOPE_RE.search(m.group(2)) if m is not None else None
        if sm is not None:
            phase = PHASE_SCOPES.get(f"pcg/{sm.group(1)}")
            out[m.group(1)] = (phase if phase is not None
                               else "?" + sm.group(1))
            if comp is not None:
                comp_phases.setdefault(comp, set()).add(out[m.group(1)])
            continue
        c = _CALLS_RE.search(line)
        if c is not None:
            calls.append((c.group(1), c.group(3),
                          _OPERAND_RE.findall(c.group(2))))
    for name, callee, operands in calls:      # operands precede users
        phases = comp_phases.get(callee) or {
            out[o] for o in operands if o in out}
        if len(phases) == 1:
            out[name] = next(iter(phases))
    return out


def scope_map_from_solver(solver, nrhs: int = 1) -> Dict[str, str]:
    """Best-effort scope map from the live solver's own compiled solve
    program (the one-shot step, or the blocked ``solve`` program at
    ``nrhs`` > 1).  Returns {} when the program cannot be re-lowered
    (e.g. an AOT-deserialized executable) — the parser then degrades to
    metadata-only bucketing and says so."""
    import jax
    import jax.numpy as jnp

    texts: List[str] = []
    try:
        if nrhs > 1:
            progs = solver._ensure_many_programs(int(nrhs))
            rdt = jnp.float64 if solver.mixed else solver.dtype
            fb = jax.ShapeDtypeStruct(
                (solver.pm.n_parts, solver.pm.n_loc, int(nrhs)), rdt)
            texts.append(
                progs["solve"].lower(solver.data, fb).compile().as_text())
        else:
            # _step_fn_jit is the plain jitted step kept lowerable even
            # when the AOT warm path replaced _step_fn (driver.py)
            fn = getattr(solver, "_step_fn_jit", None) or solver._step_fn
            delta = jnp.asarray(1.0, solver.dtype)
            texts.append(
                fn.lower(solver.data, solver.un, delta).compile().as_text())
    except Exception:                                   # noqa: BLE001
        return {}
    out: Dict[str, str] = {}
    for t in texts:
        out.update(scope_map_from_hlo_text(t))
    return out


def _base_scope_map(scope_map: Dict[str, str]) -> Dict[str, Optional[str]]:
    """Base-name fallback: a trace event name whose numeric suffix
    differs from the compiled text's (two lowerings of one program) maps
    through its base WHEN the base is unambiguous; an ambiguous base
    (two phases share it) maps to None — never a guess."""
    out: Dict[str, Optional[str]] = {}
    for name, phase in scope_map.items():
        b = _base_name(name)
        if b in out and out[b] != phase:
            out[b] = None
        else:
            out[b] = phase
    return out


# ----------------------------------------------------------------------
# bucketing + overlap
# ----------------------------------------------------------------------

def phase_of(op: dict, scope_map: Dict[str, str],
             base_map: Optional[Dict[str, Optional[str]]] = None,
             unknown_scopes: Optional[Dict[str, int]] = None,
             ) -> Optional[str]:
    """Phase of one device op: (1) a ``pcg/<label>`` substring in the
    event text (TPU metadata flavor) — an unrecognized label is COUNTED
    into ``unknown_scopes``; (2) the sidecar scope map by exact
    instruction name, then by unambiguous base name.  None = no phase
    (the ``other`` bucket)."""
    sm = _SCOPE_RE.search(op["text"])
    if sm is not None:
        label = f"pcg/{sm.group(1)}"
        phase = PHASE_SCOPES.get(label)
        if phase is not None:
            return phase
        if unknown_scopes is not None:
            unknown_scopes[sm.group(1)] = \
                unknown_scopes.get(sm.group(1), 0) + 1
    if scope_map:
        phase = scope_map.get(op["name"])
        if phase is None:
            if base_map is None:
                base_map = _base_scope_map(scope_map)
            phase = base_map.get(op["base"])
        if isinstance(phase, str) and phase.startswith("?"):
            # a sidecar-mapped label outside the known phase set:
            # counted, never silently dropped (see scope_map_from_hlo_text)
            if unknown_scopes is not None:
                label = phase[1:]
                unknown_scopes[label] = unknown_scopes.get(label, 0) + 1
            return None
        return phase
    return None


def bucket_phases(ops: List[dict], scope_map: Dict[str, str]
                  ) -> Dict[str, Any]:
    """Bucket device-op wall time per phase.  Nothing is dropped: time
    that matches no phase lands in ``other_ms``/``other_events``, and
    ``pcg/<x>`` labels outside the known four are counted in
    ``unknown_scopes`` — the scope-labels rule's loudness contract."""
    from pcg_mpi_solver_tpu.obs.perf import PHASES

    phases = {ph: {"us": 0.0, "events": 0} for ph in PHASES}
    other_us = 0.0
    other_events = 0
    unknown_scopes: Dict[str, int] = {}
    base_map = _base_scope_map(scope_map) if scope_map else {}
    for op in ops:
        ph = phase_of(op, scope_map, base_map, unknown_scopes)
        if ph in phases:
            phases[ph]["us"] += op["dur"]
            phases[ph]["events"] += 1
        else:
            other_us += op["dur"]
            other_events += 1
    return {"phases": phases, "other_us": other_us,
            "other_events": other_events,
            "unknown_scopes": unknown_scopes}


def collective_overlap(ops: List[dict]) -> Dict[str, Any]:
    """Measured collective-overlap: per device lane (trace pid), the
    wall-clock intersection of each collective op's span with the union
    of compute-op spans on OTHER threads of the same lane, as a
    fraction of total collective time.  Same-thread events are excluded
    (they are serialized with the collective by construction, and a
    parent span would fake overlap).  ``overlap_frac`` is None when the
    trace carries no collectives (single-device capture)."""
    colls = [o for o in ops if is_collective(o["base"])]
    if not colls:
        return {"n_collectives": 0, "coll_us": 0.0, "overlap_us": 0.0,
                "overlap_frac": None}
    computes = [o for o in ops if not is_collective(o["base"])]
    by_pid: Dict[Any, List[dict]] = {}
    for o in computes:
        by_pid.setdefault(o["pid"], []).append(o)
    coll_us = 0.0
    overlap_us = 0.0
    merged_cache: Dict[Tuple[Any, Any], List[Tuple[float, float]]] = {}
    for c in colls:
        span = (c["ts"], c["ts"] + c["dur"])
        coll_us += c["dur"]
        key = (c["pid"], c["tid"])
        if key not in merged_cache:
            merged_cache[key] = merge_intervals(
                [(o["ts"], o["ts"] + o["dur"])
                 for o in by_pid.get(c["pid"], ())
                 if o["tid"] != c["tid"]])
        overlap_us += intersect_len(span, merged_cache[key])
    return {"n_collectives": len(colls), "coll_us": coll_us,
            "overlap_us": overlap_us,
            "overlap_frac": (overlap_us / coll_us) if coll_us else None}


# ----------------------------------------------------------------------
# meta sidecar + capture
# ----------------------------------------------------------------------

def load_meta(trace_file: str) -> Optional[dict]:
    """The ``profview_meta.json`` sidecar next to (or up to two levels
    above) a trace file; None when absent/unreadable."""
    d = os.path.dirname(os.path.abspath(trace_file))
    for _ in range(3):
        p = os.path.join(d, PROFVIEW_META)
        if os.path.exists(p):
            try:
                with open(p, encoding="utf-8") as f:
                    return json.load(f)
            except (OSError, ValueError):
                return None
        nd = os.path.dirname(d)
        if nd == d:
            break
        d = nd
    return None


def newest_profile_artifact(root: str) -> Optional[str]:
    """The newest ``plugins/profile/<run>`` dir under a capture root (or
    the root itself when it directly holds trace files)."""
    files = find_trace_files(root)
    return os.path.dirname(files[0]) if files else None


def capture_solve_profile(solver, out_dir: str, nrhs: int = 1,
                          recorder=None, fn=None,
                          scope_map: Optional[Dict[str, str]] = None,
                          ) -> Dict[str, Any]:
    """Bounded one-shot profile capture around a warm solver dispatch.

    Runs one UNPROFILED dispatch first (compile + warm), then brackets a
    second one with ``jax.profiler.start_trace``/``stop_trace``, and
    writes the ``profview_meta.json`` sidecar (shape, committed
    iterations, whole-solve anchor, HLO scope map) into the run dir so
    the artifact parses offline.  Multi-process safe: each process
    captures into ``out_dir/p<idx>`` (two hosts must not race one trace
    directory — the same rule the telemetry shards follow).

    ``fn``: optional override dispatch, returning ``(iters, wall_s)``
    (default: ``solver.step(1.0)`` scalar / ``solver.solve_many`` at
    ``nrhs`` > 1, state reset around the measurement).  Emits a
    ``profile_capture`` telemetry event with the artifact path."""
    import jax

    pdir = out_dir
    if jax.process_count() > 1:
        pdir = os.path.join(out_dir, f"p{jax.process_index()}")
    os.makedirs(pdir, exist_ok=True)

    if fn is None:
        if nrhs > 1:
            import numpy as np

            F = np.repeat(np.asarray(solver._model.F)[:, None],
                          int(nrhs), axis=1)

            def fn():
                res = solver.solve_many(F)
                return int(res.iters.max(initial=1)), \
                    float(res.solve_wall_s)
        else:
            def fn():
                r = solver.step(1.0)
                solver.reset_state()
                return int(r.iters), float(r.wall_s)

    fn()                                    # warm: compile outside the trace
    jax.profiler.start_trace(pdir)
    try:
        iters, wall_s = fn()
    finally:
        jax.profiler.stop_trace()
    iters = max(1, int(iters))

    run_dir = newest_profile_artifact(pdir) or pdir
    if scope_map is None:
        scope_map = scope_map_from_solver(solver, nrhs=nrhs)
    scfg = solver.config.solver
    # lane count for per-iteration normalization: the mesh devices LOCAL
    # to this process — this process's trace carries only their events
    # (a multi-process capture divided by the GLOBAL device count would
    # undercount every phase by process_count)
    local_lanes = sum(
        1 for d in solver.mesh.devices.flat
        if getattr(d, "process_index", 0) == jax.process_index())
    meta = {
        "schema": PROFVIEW_META_SCHEMA,
        "pcg_variant": scfg.pcg_variant,
        "precond": scfg.precond,
        "nrhs": int(nrhs),
        "backend": str(solver.backend),
        "n_dof": int(solver.pm.glob_n_dof),
        "n_parts": int(solver.pm.n_parts),
        "n_devices": max(1, int(local_lanes)),
        "n_devices_global": int(solver.mesh.devices.size),
        "dtype": str(scfg.dtype),
        "mode": str(scfg.precision_mode),
        "platform": str(solver.mesh.devices.flat[0].platform),
        "device_kind": str(solver.mesh.devices.flat[0].device_kind),
        "iters": iters,
        "anchor_ms_per_iter": round(wall_s / iters * 1e3, 6),
        "wall_s": round(wall_s, 6),
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "scope_map": scope_map,
    }
    meta_path = os.path.join(run_dir, PROFVIEW_META)
    try:
        with open(meta_path, "w", encoding="utf-8") as f:
            json.dump(meta, f, indent=1)
    except OSError:
        meta_path = None                    # artifact still parses degraded
    rec = recorder if recorder is not None else getattr(
        solver, "recorder", None)
    if rec is not None:
        rec.event("profile_capture", path=run_dir, source="capture",
                  iters=iters, wall_s=round(wall_s, 6),
                  scope_map_ops=len(scope_map))
    return {"artifact": run_dir, "meta": meta, "meta_path": meta_path,
            "iters": iters, "wall_s": wall_s}


# ----------------------------------------------------------------------
# the report
# ----------------------------------------------------------------------

def profile_report(path: str, meta: Optional[dict] = None,
                   iters: Optional[int] = None) -> Dict[str, Any]:
    """Parse a captured trace artifact into the ``prof_report`` payload:
    per-phase device-op wall time (ms, and ms/iter when the iteration
    count is known), the unbucketed remainder, unknown-scope counts, and
    the measured collective-overlap verdict.  Degrades to a NAMED
    verdict on every tolerated failure mode (missing file, truncated
    JSON, no device lanes, no sidecar) — never a crash."""
    problems: List[str] = []
    files = find_trace_files(path)
    events: List[dict] = []
    src = str(path)
    if not files:
        problems.append(f"no trace artifact under {path}")
    else:
        src = files[0]
        events, probs = read_trace_events(src)
        problems.extend(probs)
    if meta is None and files:
        meta = load_meta(src)
    meta = meta or {}
    if iters is None:
        iters = meta.get("iters")
    n_devices = int(meta.get("n_devices", 1) or 1)
    scope_map = meta.get("scope_map") or {}

    ops = device_ops(events)
    if events and not ops:
        problems.append("no device-op events in trace (device lanes "
                        "missing — host-only capture?)")
    buckets = bucket_phases(ops, scope_map)
    overlap = collective_overlap(ops)

    phases: Dict[str, Any] = {}
    sum_ms = 0.0
    sum_ms_per_iter = 0.0
    denom = (int(iters) * n_devices) if iters else None
    for ph, b in buckets["phases"].items():
        ms = b["us"] / 1e3
        sum_ms += ms
        per = round(ms / denom, 6) if denom else None
        if per is not None:
            sum_ms_per_iter += per
        phases[ph] = {"ms": round(ms, 6), "ms_per_iter": per,
                      "events": b["events"]}
    anchor = meta.get("anchor_ms_per_iter")
    attribution = (round(sum_ms_per_iter / anchor, 4)
                   if denom and anchor else None)
    # the trace-derived anchor: total device-op time per iteration —
    # what the trace can possibly attribute.  The wall anchor minus
    # this is the RUNTIME GAP (thunk scheduling, host dispatch,
    # transfers): reported explicitly, never silently absorbed into a
    # phase.  device_attribution is the four phases' share of it.
    other_per_iter = (round(buckets["other_us"] / 1e3 / denom, 6)
                      if denom else None)
    device_ms_per_iter = (round(sum_ms_per_iter + other_per_iter, 6)
                          if denom else None)
    device_attribution = (round(sum_ms_per_iter / device_ms_per_iter, 4)
                          if device_ms_per_iter else None)
    if not meta:
        problems.append("no profview_meta.json sidecar (per-iteration "
                        "normalization and the predicted column are "
                        "unavailable)")
    elif not scope_map and ops and buckets["other_events"] == len(ops):
        problems.append("empty scope map and no pcg/* labels in event "
                        "metadata — attribution is all 'other'")

    verdict = "ok" if not problems else "degraded: " + "; ".join(problems)
    return {
        "source": src,
        "verdict": verdict,
        "n_events": len(events),
        "n_device_ops": len(ops),
        "phases": phases,
        "sum_ms": round(sum_ms, 6),
        "sum_ms_per_iter": (round(sum_ms_per_iter, 6) if denom else None),
        "other_ms": round(buckets["other_us"] / 1e3, 6),
        "other_events": buckets["other_events"],
        "other_ms_per_iter": other_per_iter,
        "unknown_scopes": buckets["unknown_scopes"],
        "iters": iters,
        "n_devices": n_devices,
        "anchor_ms_per_iter": anchor,
        "attribution": attribution,
        "device_ms_per_iter": device_ms_per_iter,
        "device_attribution": device_attribution,
        "overlap_frac": overlap["overlap_frac"],
        "overlap": {k: (round(v, 6) if isinstance(v, float) else v)
                    for k, v in overlap.items()},
        "pcg_variant": meta.get("pcg_variant"),
        "precond": meta.get("precond"),
        "nrhs": meta.get("nrhs"),
        "backend": meta.get("backend"),
        "n_dof": meta.get("n_dof"),
        "platform": meta.get("platform"),
    }


def emit_prof_report(recorder, report: Dict[str, Any]) -> None:
    """Emit one parsed report as the schema-versioned ``prof_report``
    event plus the ``prof.*`` gauges."""
    recorder.event("prof_report", **report)
    for ph, d in report["phases"].items():
        if d.get("ms_per_iter") is not None:
            recorder.gauge(f"prof.{ph}_ms_per_iter", d["ms_per_iter"])
    if report.get("overlap_frac") is not None:
        recorder.gauge("prof.overlap_frac",
                       round(report["overlap_frac"], 6))
    if report.get("attribution") is not None:
        recorder.gauge("prof.attribution", report["attribution"])
    recorder.gauge("prof.other_ms", report["other_ms"])


def predicted_from_meta(meta: dict) -> Optional[dict]:
    """The obs/perf.py cost model rebuilt from a capture sidecar (the
    predicted column of the offline report); None when the meta carries
    no usable shape.  Unknown variant/precond names stay loud
    (KeyError — the single-source-table contract)."""
    from pcg_mpi_solver_tpu.obs import perf as _perf

    if not meta:
        return None
    try:
        shape = _perf.shape_from_detail(meta)
        if shape is None:
            return None
        return _perf.cost_model(
            shape, str(meta.get("pcg_variant", "classic")),
            str(meta.get("precond", "jacobi")),
            int(meta.get("nrhs", 1) or 1),
            _perf.resolve_profile(str(meta.get(
                "device_kind", meta.get("platform", "cpu")))))
    except KeyError:
        raise
    except Exception:                                   # noqa: BLE001
        return None


def format_report(report: Dict[str, Any],
                  predicted: Optional[dict] = None,
                  recorded: Optional[dict] = None) -> str:
    """Human table of one parsed report: per-phase rows with the
    predicted (cost model) and recorded (phase probes) columns when
    available next to the trace-measured ms/iter, then the overlap
    verdict and the degraded-mode notes."""
    from pcg_mpi_solver_tpu.obs.perf import PHASES

    per_iter = report.get("sum_ms_per_iter") is not None
    lines = []
    lines.append(f"{'phase':<10} {'predicted':>10} {'recorded':>10} "
                 + (f"{'measured':>10} {'share':>7}" if per_iter
                    else f"{'measured_ms':>12} {'share':>7}"))
    total = report["sum_ms"] or 0.0
    pred_sum = 0.0
    for ph in PHASES:
        d = report["phases"].get(ph, {})
        meas = d.get("ms_per_iter") if per_iter else d.get("ms", 0.0)
        share = (d.get("ms", 0.0) / total) if total else 0.0
        pm = (predicted["phases"][ph]["model_ms"]
              if predicted is not None else None)
        pred_sum += pm or 0.0
        rm = (recorded or {}).get(ph)
        pm_s = f"{pm:>10.4f}" if pm is not None else f"{'-':>10}"
        rm_s = f"{rm:>10.4f}" if rm is not None else f"{'-':>10}"
        ms_s = (f"{meas:>10.4f}" if per_iter
                else f"{meas:>12.3f}")
        lines.append(f"{ph:<10} {pm_s} {rm_s} {ms_s} {share:>6.0%}")
    sum_meas = (report["sum_ms_per_iter"] if per_iter
                else report["sum_ms"])
    ps = f"{pred_sum:>10.4f}" if predicted is not None else f"{'-':>10}"
    lines.append(f"{'sum':<10} {ps} {'':>10} "
                 + (f"{sum_meas:>10.4f}" if per_iter
                    else f"{sum_meas:>12.3f}"))
    lines.append(f"other (unbucketed): {report['other_ms']:.3f} ms over "
                 f"{report['other_events']} event(s)")
    if report.get("unknown_scopes"):
        lines.append("UNKNOWN pcg/* scope labels (counted, not "
                     f"dropped): {report['unknown_scopes']}")
    if report.get("device_ms_per_iter") is not None:
        lines.append(
            f"device-op anchor: {report['device_ms_per_iter']:.4f} "
            f"ms/iter ({report.get('iters')} iters, "
            f"{report.get('n_devices')} device(s)); phase share of "
            f"device-op time: {report.get('device_attribution')}")
    if report.get("anchor_ms_per_iter"):
        gap = None
        if report.get("device_ms_per_iter") is not None:
            gap = (report["anchor_ms_per_iter"]
                   - report["device_ms_per_iter"])
        lines.append(
            f"wall anchor: {report['anchor_ms_per_iter']:.4f} ms/iter; "
            f"attribution (phase sum / wall): "
            f"{report.get('attribution')}"
            + (f"; runtime gap (scheduling/dispatch, outside every "
               f"device op): {gap:.4f} ms/iter" if gap is not None
               else ""))
    ov = report["overlap"]
    if report.get("overlap_frac") is not None:
        lines.append(
            f"collective overlap: {report['overlap_frac']:.3f} "
            f"({ov['overlap_us'] / 1e3:.3f} of {ov['coll_us'] / 1e3:.3f}"
            f" ms across {ov['n_collectives']} collective op(s) hidden "
            "behind concurrent compute)")
    elif ov["n_collectives"]:
        # collectives present but zero total duration (e.g. bare async
        # -start markers): a fraction of nothing is n/a, not a crash
        lines.append(f"collective overlap: n/a ({ov['n_collectives']} "
                     "collective op(s) carry zero duration)")
    else:
        lines.append("collective overlap: n/a (no collective ops in "
                     "trace — single-device capture?)")
    lines.append(f"verdict: {report['verdict']}")
    return "\n".join(lines)
