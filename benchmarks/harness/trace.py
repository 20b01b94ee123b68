"""Reduction of one ``jax.profiler`` trace to device busy time, per-scope
device time, collective time, the ops that took most time, and the
longest idle gaps named by the host span open in them.

The trace is read from its ``.xplane.pb`` (harness/xplane.py).  Device
ops are the events of each TPU plane's ``XLA Ops`` line (on the CPU,
where the tests record their traces, the events that carry an ``hlo_op``
stat, by ``device_ordinal``).  An op belongs to a PCG phase when its
``op_name`` names a ``pcg/<phase>`` scope (the program's
``jax.named_scope`` labels in solver/pcg.py).  The interval
union and the phase buckets follow the program's obs/profview.py, copied
so that the yardstick stays here.

Nothing is dropped: every op inside the window lands in exactly one
bucket, a phase or ``other``.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Tuple

from harness import xplane

PHASES = ("matvec", "precond", "reduce", "axpy")
_SCOPE = re.compile(r"pcg/(matvec|precond|reduce|axpy)\b")
# XLA's collective ops; psum and ppermute are their names on the CPU
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "psum", "ppermute")
# ops whose span wraps other ops on the same line
CONTAINERS = frozenset({"while", "call", "conditional", "cond", "tuple",
                        "parameter", "get-tuple-element"})
HOST_SPANS = ("window", "reset", "dispatch", "fetch")
TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
TPU_OPS_LINE = "XLA Ops"
TPU_MODULES_LINE = "XLA Modules"


def base_name(name: str) -> str:
    """``fusion.12`` -> ``fusion``, ``all-reduce-start.3`` ->
    ``all-reduce-start``."""
    while True:
        if name.endswith(".clone"):
            name = name[:-6]
            continue
        head, dot, tail = name.rpartition(".")
        if dot and tail.isdigit():
            name = head
            continue
        return name


def merge(spans: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Sorted union of half-open intervals."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(sp for sp in spans if sp[1] > sp[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def phase_of(text: str):
    """The PCG phase a scope text names; where a fused op names several,
    the one named most."""
    found = _SCOPE.findall(text or "")
    if not found:
        return None
    return collections.Counter(found).most_common(1)[0][0]


_INSTR = re.compile(r"^%?([A-Za-z0-9_.\-]+)")


def read(trace_dir: str):
    """(ops per device, host spans) of the one trace under ``trace_dir``.

    An op is (start_ns, end_ns, name, phase, program): its HLO
    instruction name, the PCG phase its scope names (None for none) and
    the program that ran it.  The scope is looked up in the op's
    metadata, then in that program's HLO (``/host:metadata``)."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(files)}")
    planes = xplane.planes(files[0])
    programs = {}
    for p in planes:
        if p.name == "/host:metadata":
            for mid in p.meta:
                blob = p.metadata_stats(mid).get("Hlo Proto")
                if isinstance(blob, bytes):
                    programs[p.event_name(mid)] = xplane.hlo_op_names(blob)
    ops: Dict[str, list] = {}
    spans: List[Tuple[str, int, int]] = []
    tpu = any(TPU_PLANE.match(p.name) for p in planes)
    for p in planes:
        m = TPU_PLANE.match(p.name)
        if m:
            ops[f"TPU:{m.group(1)}"] = _tpu_ops(p, programs)
        elif p.name == "/host:CPU":
            for _, s, e, mid, ev in p.events():
                name = p.event_name(mid)
                if name in HOST_SPANS:
                    spans.append((name, s, e))
                    continue
                if tpu:
                    continue
                st = p.event_stats(ev)
                if "hlo_op" not in st:
                    continue
                prog = programs.get(f"{st.get('hlo_module')}"
                                    f"({st.get('program_id')})", {})
                dev = f"CPU:{st.get('device_ordinal', 0)}"
                ops.setdefault(dev, []).append(
                    (s, e, name, phase_of(prog.get(name)),
                     str(st.get("hlo_module"))))
    return ops, spans


def _tpu_ops(p, programs) -> list:
    """A TPU plane's ops, each with the program that ran it: the
    ``XLA Modules`` event that holds it in time."""
    mods = sorted((s, e, p.event_name(mid))
                  for _, s, e, mid, _ in p.events(TPU_MODULES_LINE))
    starts = [m[0] for m in mods]
    cache = {}
    out = []
    for _, s, e, mid, _ in p.events(TPU_OPS_LINE):
        i = bisect.bisect_right(starts, s) - 1
        mod = mods[i][2] if i >= 0 and s < mods[i][1] else ""
        key = (mid, mod)
        if key not in cache:
            full = p.event_name(mid)
            m = _INSTR.match(full)
            name = m.group(1) if m else full
            meta = " ".join(str(v) for v in p.metadata_stats(mid).values()
                            if isinstance(v, str))
            ph = phase_of(meta)
            if ph is None:
                ph = phase_of(programs.get(mod, {}).get(name))
            cache[key] = (name, ph, mod.split("(")[0])
        out.append((s, e) + cache[key])
    return out


def is_collective(name: str) -> bool:
    b = base_name(name)
    return any(c in b for c in COLLECTIVES)


def reduce(ops: Dict[str, list], spans, devices=None) -> dict:
    """Reduce the ops of ``devices`` (default: every device in the trace)
    inside the host ``window`` span."""
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if len(windows) != 1:
        raise RuntimeError(f"expected one 'window' span, found {len(windows)}")
    w0, w1 = windows[0]
    devices = sorted(ops) if devices is None else devices
    if not devices:
        raise RuntimeError("the trace holds no device ops")
    per_dev = {}
    op_time: Dict[str, float] = {}
    gaps = []
    host = sorted((s, e, n) for n, s, e in spans if n != "window")
    for dev in devices:
        phase_ns = {p: 0 for p in PHASES}
        other_ns = coll_ns = n_ops = scoped = 0
        ivs = []
        for s, e, name, ph, prog in ops.get(dev, ()):
            if base_name(name) in CONTAINERS:
                continue
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            n_ops += 1
            ivs.append((s, e))
            if ph is None:
                other_ns += e - s
            else:
                phase_ns[ph] += e - s
                scoped += 1
            if is_collective(name):
                coll_ns += e - s
            key = f"{prog}/{name} [{ph or 'unscoped'}]"
            op_time[key] = op_time.get(key, 0) + (e - s)
        union = merge(ivs)
        busy = sum(e - s for s, e in union)
        edges = [w0] + [t for iv in union for t in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, (a + b) // 2, dev))
        per_dev[dev] = {"busy_ns": busy, "phase_ns": phase_ns,
                        "other_ns": other_ns, "collective_ns": coll_ns,
                        "n_ops": n_ops, "scoped_ops": scoped}
    n = len(devices)
    gaps.sort(reverse=True)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (w1 - w0) / 1e9,
        "devices": devices,
        "per_device": per_dev,
        "busy_s": sum(d["busy_ns"] for d in per_dev.values()) / n / 1e9,
        "phase_s": {p: sum(d["phase_ns"][p] for d in per_dev.values())
                    / n / 1e9 for p in PHASES},
        "other_s": sum(d["other_ns"] for d in per_dev.values()) / n / 1e9,
        "collective_s": sum(d["collective_ns"] for d in per_dev.values())
        / n / 1e9,
        "scoped_ops": sum(d["scoped_ops"] for d in per_dev.values()),
        "n_ops": sum(d["n_ops"] for d in per_dev.values()),
        "device_ops": [[name, t / n / 1e9] for name, t in top],
        "idle_gaps": [[f"{_span_at(host, mid)} ({dev})", g / 1e9]
                      for g, mid, dev in gaps[:10]],
    }


def _span_at(host, t) -> str:
    """The innermost host span open at time ``t``, or ``host`` when the
    host was between the benchmark's calls."""
    best = None
    for s, e, n in host:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best[2] if best else "host"
