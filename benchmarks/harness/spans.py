"""The program's own labels in a profiler trace: matvec stages, the
scopes outside the PCG phases, and the program's host spans.

``harness/trace.py`` reads each device op's PCG phase and the
benchmark's host spans (``window``, ``reset``, ``dispatch``, ``fetch``).
The program labels more (solver/pcg.py, ops/matvec.py, obs/metrics.py):

- each op of a matvec by its stage, ``mv/gather|contract|scatter|halo``;
- each op outside the four phases by ``solve/residual64|start|track``;
- host spans ``pcg-tpu/<name>`` around every dispatch, the whole solve
  (with its ``step`` argument), each mixed refinement cycle, the reset
  and both halves of the answer's fetch.

:func:`read` returns what ``trace.read`` does with each op's stage and
scope appended, every host span with its arguments, and the program
launches per device.  :func:`reduce` returns ``trace.reduce``'s dict,
with the idle gaps named by the innermost span open at them (program
spans included), plus the keys listed in its docstring.  A label is
found the way ``trace.py`` finds a phase: op metadata, then the
program's HLO; where an op names several, the innermost of each name,
then the one named most.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from harness import trace, xplane

STAGES = ("gather", "contract", "scatter", "halo")
SCOPES = ("residual64", "start", "track")
_STAGE = re.compile(r"mv/(gather|contract|scatter|halo)\b")
_SOLVE = re.compile(r"solve/(residual64|start|track)\b")
PROGRAM_SPAN = "pcg-tpu/"
SOLVE_SPAN = "pcg-tpu/solve"


def label_of(pattern, text: Optional[str]):
    """The innermost label ``pattern`` finds in each op_name of ``text``
    (space-separated), then the one found most."""
    found = []
    for name in (text or "").split():
        m = pattern.findall(name)
        if m:
            found.append(m[-1])
    if not found:
        return None
    return collections.Counter(found).most_common(1)[0][0]


def _labels(meta, hlo, computation_scope=None):
    """(phase, stage, scope): each from the first of the op's metadata
    and its HLO op_name that names one.  An op that names no scope (a
    copy XLA inserted has no op_name) takes ``computation_scope``, the
    scope of its HLO computation: a loop body's copies are the loop's."""
    phase = trace.phase_of(meta) or trace.phase_of(hlo)
    stage = label_of(_STAGE, meta) or label_of(_STAGE, hlo)
    scope = (label_of(_SOLVE, meta) or label_of(_SOLVE, hlo)
             or computation_scope)
    return phase, stage, scope


def computation_scopes(blob: bytes) -> Dict[str, Optional[str]]:
    """{instruction name: the ``solve/*`` scope its HLO computation's
    op_names name most} of one program's serialized HloProto."""
    hlo = xplane._classes()["HloProto"]()
    hlo.ParseFromString(blob)
    out = {}
    for c in hlo.hlo_module.computations:
        scope = label_of(_SOLVE, " ".join(i.metadata.op_name
                                          for i in c.instructions))
        out.update((i.name, scope) for i in c.instructions)
    return out


def read(trace_dir: str):
    """(ops, spans, launches) of the one trace under ``trace_dir``.

    An op is (start_ns, end_ns, name, phase, program, stage, scope).  A
    span is (name, start_ns, end_ns, args) for the benchmark's host
    spans and every ``pcg-tpu/*`` one.  ``launches`` maps a device to
    the (start_ns, end_ns) of each program run on it: the ``XLA
    Modules`` events of a TPU plane, the op extent of each ``run_id``
    on the CPU."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(files)}")
    planes = xplane.planes(files[0])
    programs, computations = {}, {}
    for p in planes:
        if p.name == "/host:metadata":
            for mid in p.meta:
                blob = p.metadata_stats(mid).get("Hlo Proto")
                if isinstance(blob, bytes):
                    programs[p.event_name(mid)] = xplane.hlo_op_names(blob)
                    computations[p.event_name(mid)] = \
                        computation_scopes(blob)
    ops: Dict[str, list] = {}
    spans: List[Tuple[str, int, int, dict]] = []
    launches: Dict[str, list] = {}
    runs: Dict[Tuple[str, int], List[int]] = {}
    tpu = any(trace.TPU_PLANE.match(p.name) for p in planes)
    for p in planes:
        m = trace.TPU_PLANE.match(p.name)
        if m:
            dev = f"TPU:{m.group(1)}"
            ops[dev] = _tpu_ops(p, programs, computations)
            launches[dev] = [(s, e) for _, s, e, _, _ in
                             p.events(trace.TPU_MODULES_LINE)]
        elif p.name == "/host:CPU":
            for _, s, e, mid, ev in p.events():
                name = p.event_name(mid)
                if name in trace.HOST_SPANS or name.startswith(PROGRAM_SPAN):
                    spans.append((name, s, e, p.event_stats(ev)))
                    continue
                if tpu:
                    continue
                st = p.event_stats(ev)
                if "hlo_op" not in st:
                    continue
                mod = f"{st.get('hlo_module')}({st.get('program_id')})"
                dev = f"CPU:{st.get('device_ordinal', 0)}"
                ph, stage, scope = _labels(
                    None, programs.get(mod, {}).get(name),
                    computations.get(mod, {}).get(name))
                ops.setdefault(dev, []).append(
                    (s, e, name, ph, str(st.get("hlo_module")), stage,
                     scope))
                run = runs.setdefault((dev, st.get("run_id")), [s, e])
                run[0], run[1] = min(run[0], s), max(run[1], e)
    for (dev, _), (s, e) in runs.items():
        launches.setdefault(dev, []).append((s, e))
    return ops, spans, launches


def _tpu_ops(p, programs, computations) -> list:
    """``trace._tpu_ops`` with the stage and scope of each op."""
    mods = sorted((s, e, p.event_name(mid))
                  for _, s, e, mid, _ in p.events(trace.TPU_MODULES_LINE))
    starts = [m[0] for m in mods]
    cache = {}
    out = []
    for _, s, e, mid, _ in p.events(trace.TPU_OPS_LINE):
        i = bisect.bisect_right(starts, s) - 1
        mod = mods[i][2] if i >= 0 and s < mods[i][1] else ""
        key = (mid, mod)
        if key not in cache:
            full = p.event_name(mid)
            m = trace._INSTR.match(full)
            name = m.group(1) if m else full
            meta = " ".join(str(v) for v in p.metadata_stats(mid).values()
                            if isinstance(v, str))
            ph, stage, scope = _labels(
                meta, programs.get(mod, {}).get(name),
                computations.get(mod, {}).get(name))
            cache[key] = (name, ph, mod.split("(")[0], stage, scope)
        out.append((s, e) + cache[key])
    return out


def _overlap(ivs: List[Tuple[int, int]], ends: List[int], a: int,
             b: int) -> int:
    """Length of [a, b) covered by the sorted disjoint ``ivs`` (``ends``:
    their ends)."""
    n = 0
    for i in range(bisect.bisect_right(ends, a), len(ivs)):
        s, e = ivs[i]
        if s >= b:
            break
        n += min(e, b) - max(s, a)
    return n


def reduce(ops: Dict[str, list], spans, launches=None,
           devices=None) -> dict:
    """``trace.reduce`` of the same ops and the benchmark's spans, its
    ``idle_gaps`` named by the innermost span (program spans included),
    and:

    - ``matvec_stage_s``: ``pcg/matvec`` device time by stage, and
      ``unstaged``; sums to ``phase_s["matvec"]``;
    - ``solve_scope_s``: device time under no PCG phase by ``solve/*``
      scope, and ``none``; sums to ``other_s``;
    - ``spans``: per program span name, its ``count``, ``total_s``,
      ``self_s`` (time no shorter span inside it covers), ``idle_s``
      (device idle in its self time, mean over chips) and
      ``idle_total_s`` (device idle anywhere inside it); ``host_idle_s``
      is the idle under no program span, so the ``idle_s`` and
      ``host_idle_s`` sum to the window's idle time;
    - ``programs``: program runs starting in the window, mean over
      chips.

    Every figure is also kept per device in integer ns."""
    base_spans = [(n, s, e) for n, s, e, _ in spans
                  if n in trace.HOST_SPANS]
    out = trace.reduce({d: [op[:5] for op in v] for d, v in ops.items()},
                       base_spans, devices)
    (w0, w1), = [(s, e) for n, s, e in base_spans if n == "window"]
    devices = out["devices"]
    n = len(devices)

    prog = sorted((s, e, name) for name, s, e, _ in spans
                  if name.startswith(PROGRAM_SPAN))
    clipped = [(max(s, w0), min(e, w1), name) for s, e, name in prog]
    clipped = [c for c in clipped if c[1] > c[0]]
    edges = sorted({w0, w1} | {t for s, e, _ in clipped for t in (s, e)})
    segments = []          # (a, b, index of the innermost span or None)
    for a, b in zip(edges, edges[1:]):
        inner = None
        for i, (s, e, _) in enumerate(clipped):
            if s <= a and e >= b and (
                    inner is None
                    or e - s < clipped[inner][1] - clipped[inner][0]):
                inner = i
        segments.append((a, b, inner))

    names = sorted({name for _, _, name in clipped})
    gaps = []
    for dev in devices:
        d = out["per_device"][dev]
        stage_ns = dict.fromkeys(STAGES + ("unstaged",), 0)
        scope_ns = dict.fromkeys(SCOPES + ("none",), 0)
        ivs = []
        for s, e, name, ph, _, stage, scope in ops.get(dev, ()):
            if trace.base_name(name) in trace.CONTAINERS:
                continue
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            ivs.append((s, e))
            if ph == "matvec":
                stage_ns[stage or "unstaged"] += e - s
            elif ph is None:
                scope_ns[scope or "none"] += e - s
        union = trace.merge(ivs)
        edges_d = [w0] + [t for iv in union for t in iv] + [w1]
        idle = [(a, b) for a, b in zip(edges_d[0::2], edges_d[1::2])
                if b > a]
        gaps += [(b - a, (a + b) // 2, dev) for a, b in idle]
        ends = [e for _, e in idle]
        span_idle = dict.fromkeys(names + ["host"], 0)
        for a, b, inner in segments:
            owner = "host" if inner is None else clipped[inner][2]
            span_idle[owner] += _overlap(idle, ends, a, b)
        idle_total = {
            name: sum(_overlap(idle, ends, s, e) for s, e in trace.merge(
                [(s, e) for s, e, nm in clipped if nm == name]))
            for name in names}
        d.update(matvec_stage_ns=stage_ns, solve_scope_ns=scope_ns,
                 idle_ns=sum(b - a for a, b in idle),
                 span_idle_ns=span_idle, span_idle_total_ns=idle_total,
                 programs=sum(1 for s, _ in (launches or {}).get(dev, ())
                              if w0 <= s < w1))

    def mean_s(key, sub):
        return sum(out["per_device"][dv][key][sub]
                   for dv in devices) / n / 1e9

    self_ns = collections.Counter()
    for a, b, inner in segments:
        if inner is not None:
            self_ns[clipped[inner][2]] += b - a
    out["matvec_stage_s"] = {k: mean_s("matvec_stage_ns", k)
                             for k in STAGES + ("unstaged",)}
    out["solve_scope_s"] = {k: mean_s("solve_scope_ns", k)
                            for k in SCOPES + ("none",)}
    out["spans"] = {
        name: {"count": sum(1 for _, _, nm in clipped if nm == name),
               "total_s": sum(e - s for s, e, nm in clipped
                              if nm == name) / 1e9,
               "self_s": self_ns[name] / 1e9,
               "idle_s": mean_s("span_idle_ns", name),
               "idle_total_s": mean_s("span_idle_total_ns", name)}
        for name in names}
    out["host_idle_s"] = mean_s("span_idle_ns", "host")
    out["programs"] = sum(out["per_device"][dv]["programs"]
                          for dv in devices) / n
    # trace.reduce's ten longest gaps, named by every span but the window
    named = sorted((s, e, nm) for nm, s, e, _ in spans if nm != "window")
    gaps.sort(reverse=True)
    out["idle_gaps"] = [[f"{trace._span_at(named, mid)} ({dev})", g / 1e9]
                        for g, mid, dev in gaps[:10]]
    return out


def metrics(red: dict, iters: int, solves: int) -> dict:
    """The per-layer readings of one traced reduction: matvec stages and
    the solve scope per inner iteration or per solve, the device idle
    inside ``pcg-tpu/solve`` and the program runs per solve, and both
    halves of the answer's fetch per solve (ms, host clock)."""
    sp = red["spans"]

    def span_ms(name, key="total_s"):
        return sp[name][key] * 1e3 / solves if name in sp else None

    out = {f"matvec.{k}_ms_per_iter": red["matvec_stage_s"][k] * 1e3 / iters
           for k in STAGES}
    out["refine.residual64_ms_per_solve"] = (
        red["solve_scope_s"]["residual64"] * 1e3 / solves)
    out["refine.start_ms_per_solve"] = (
        red["solve_scope_s"]["start"] * 1e3 / solves)
    out["pcg.track_ms_per_iter"] = red["solve_scope_s"]["track"] * 1e3 / iters
    out["dispatch.idle_ms_per_solve"] = span_ms(SOLVE_SPAN, "idle_total_s")
    out["dispatch.programs_per_solve"] = red["programs"] / solves
    out["fetch.transfer_ms_per_solve"] = span_ms("pcg-tpu/fetch.transfer")
    out["fetch.assemble_ms_per_solve"] = span_ms("pcg-tpu/fetch.assemble")
    return out
