"""Step metrics registry: counters, gauges, wall-time spans and structured
events, fanned out to pluggable sinks (stderr, JSONL), with every span and
dispatch also a ``jax.profiler`` trace annotation ``pcg-tpu/<name>``.

This is the ONE logging path of the framework: the old ``_vlog`` stderr
breadcrumbs of ``solver/driver.py`` are now ``note`` events through a
:class:`MetricsRecorder`, with ``PCG_TPU_VERBOSE=1`` kept as the alias
that enables the stderr sink on the default recorder.

Design constraints:

* Host-side only.  Nothing here touches device buffers; enabling telemetry
  adds zero device<->host transfers per PCG iteration (the in-graph
  residual trace lives in ``obs/trace.py`` and is fetched once per solve).
* Import-light.  ``bench.py`` imports this module before configuring the
  accelerator environment, so jax is never imported here: a span opens
  its trace annotation only once something else has loaded jax (before
  that no profiler trace can be collecting).
* Annotations are always on.  ``TraceAnnotation`` is a no-op while no
  trace is collecting (a few microseconds per span); under
  ``jax.profiler.start_trace`` each span is one named interval on the
  device trace's clock, the same interval its JSONL event and the
  ``span_stats()`` / ``dispatch_stats()`` tables time.
* A recorder with no sinks is a cheap null object: counters/spans still
  accumulate (for the ``--summary`` table) but nothing is formatted or
  written.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import Any, Dict, List, Optional

from pcg_mpi_solver_tpu.obs.schema import TELEMETRY_SCHEMA


def _jsonable(v):
    """Best-effort coercion for numpy scalars/arrays without importing
    numpy: anything with .item()/.tolist() degrades to builtins."""
    if hasattr(v, "tolist"):
        return v.tolist()
    if hasattr(v, "item"):
        return v.item()
    return str(v)


class StderrSink:
    """Human breadcrumbs on stderr.

    Every event gets the historical ``[pcg-tpu HH:MM:SS]`` prefix so
    a hung dispatch can be located from stderr alone (the original
    ``_vlog`` contract); note events print their message body verbatim
    after it (bench.py's ``# ...`` lines keep their shape).
    """

    def __init__(self, stream=None):
        self._stream = stream

    def emit(self, ev: Dict[str, Any]) -> None:
        stream = self._stream if self._stream is not None else sys.stderr
        kind = ev.get("kind", "?")
        if kind == "note":
            body = str(ev.get("msg", ""))
        else:
            skip = ("schema", "t", "kind")
            parts = []
            for k, v in ev.items():
                if k in skip:
                    continue
                if isinstance(v, (list, dict)):
                    v = f"<{len(v)} entries>"
                elif isinstance(v, float):
                    v = f"{v:.6g}"
                parts.append(f"{k}={v}")
            body = f"{kind}: " + " ".join(parts)
        print(f"[pcg-tpu {time.strftime('%H:%M:%S')}] {body}",
              file=stream, flush=True)

    def close(self) -> None:
        pass


class EnvGatedStderrSink(StderrSink):
    """StderrSink active only while ``PCG_TPU_VERBOSE=1``, sampled PER
    EVENT — matching the removed ``_vlog``'s per-call env check, so a
    long-lived process can turn breadcrumbs on after the Solver was
    constructed (the hung-dispatch forensics workflow)."""

    def emit(self, ev: Dict[str, Any]) -> None:
        if os.environ.get("PCG_TPU_VERBOSE") == "1":
            super().emit(ev)


class JsonlSink:
    """Schema-versioned JSONL event stream: one JSON object per line,
    flushed per event so a killed run still leaves a parseable file."""

    def __init__(self, path: str):
        self.path = path
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        self._f = open(path, "a", encoding="utf-8")

    def emit(self, ev: Dict[str, Any]) -> None:
        self._f.write(json.dumps(ev, default=_jsonable) + "\n")
        self._f.flush()

    def close(self) -> None:
        try:
            self._f.close()
        except ValueError:
            pass


def _annotation(name: str, args: Dict[str, Any]):
    """``jax.profiler.TraceAnnotation("pcg-tpu/<name>", **args)``, or a
    null context while jax is not loaded (no trace can be collecting
    then, and importing it here would initialise it before ``bench.py``
    configures the accelerator environment)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return nullcontext()
    return jax.profiler.TraceAnnotation(f"pcg-tpu/{name}", **args)


class MetricsRecorder:
    """Counters + gauges + monotonic wall-time spans + structured events.

    All mutation goes through a lock: the solver may be driven from a
    thread while exports run elsewhere.  Events are dicts with the base
    triplet ``schema``/``t``/``kind`` (see ``obs/schema.py``).
    """

    def __init__(self, sinks=(), clock=time.monotonic):
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, Any] = {}
        self.sinks: List[Any] = list(sinks)
        # Optional crash-durable flight recorder (obs/flight.py): when
        # attached, every dispatch span is bracketed by fsync'd
        # begin/end flight records so a SIGKILL mid-dispatch leaves a
        # parseable artifact naming the in-flight program.
        self.flight = None
        self._clock = clock
        self._spans: Dict[str, List[float]] = {}    # name -> [count, total_s]
        # per-dispatch-name: [calls, cold_s, warm_s] — the first call of a
        # jitted program pays XLA compile, so cold vs warm IS the
        # compile-time vs execute-time split per dispatch.
        self._dispatch: Dict[str, List[float]] = {}
        self.step_events: List[Dict[str, Any]] = []
        self._lock = threading.Lock()

    # -- construction ---------------------------------------------------
    @classmethod
    def default(cls, jsonl_path: Optional[str] = None) -> "MetricsRecorder":
        """The solver-facing factory: stderr breadcrumbs gated on
        ``PCG_TPU_VERBOSE=1`` per event (the historical knob, checked at
        every emit like the old ``_vlog`` so it can be flipped on a LIVE
        process), JSONL sink iff a path is given."""
        sinks: List[Any] = [EnvGatedStderrSink()]
        if jsonl_path:
            # Multi-process jax.distributed: each process appends to its
            # OWN shard (run.jsonl -> run.p<idx>.jsonl) — interleaved
            # appends from N processes would corrupt a shared file.
            # Single-process paths are untouched; `pcg-tpu
            # telemetry-merge` reassembles the shards.  Lazy import, no
            # jax side effects (shard_jsonl_path only consults an
            # already-imported jax).
            from pcg_mpi_solver_tpu.obs.flight import shard_jsonl_path

            sinks.append(JsonlSink(shard_jsonl_path(jsonl_path)))
        return cls(sinks=sinks)

    def add_sink(self, sink) -> None:
        with self._lock:
            self.sinks.append(sink)

    def remove_sink(self, sink) -> None:
        """Detach a sink added with :meth:`add_sink`; idempotent (the
        bench's one-shot first-dispatch sink detaches best-effort on
        every exit path)."""
        with self._lock:
            try:
                self.sinks.remove(sink)
            except ValueError:
                pass

    def close(self) -> None:
        fl = self.flight
        if fl is not None:
            fl.close()
        for s in self.sinks:
            close = getattr(s, "close", None)
            if close:
                close()

    # -- registry -------------------------------------------------------
    def inc(self, name: str, delta: float = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + delta

    def gauge(self, name: str, value) -> None:
        with self._lock:
            self.gauges[name] = value

    # -- events ---------------------------------------------------------
    def event(self, kind: str, **fields) -> Dict[str, Any]:
        ev = {"schema": TELEMETRY_SCHEMA, "t": time.time(), "kind": kind}
        ev.update(fields)
        # sink emission stays UNDER the lock: concurrent emitters (solver
        # thread + a watchdog note) must not interleave mid-line in a
        # shared JSONL stream
        with self._lock:
            if kind == "step":
                self.step_events.append(ev)
            for s in self.sinks:
                s.emit(ev)
        return ev

    def note(self, msg: str) -> None:
        self.event("note", msg=msg)

    # -- timing ---------------------------------------------------------
    @contextmanager
    def span(self, name: str, emit: bool = False, **args):
        """Accumulate monotonic wall time under ``name``; ``emit=True``
        additionally emits a ``bench_phase`` event on exit.  The span is
        also the trace annotation ``pcg-tpu/<name>``, carrying ``args``
        (e.g. ``step=3``) as its arguments."""
        t0 = self._clock()
        try:
            with _annotation(name, args):
                yield
        finally:
            dt = self._clock() - t0
            with self._lock:
                st = self._spans.setdefault(name, [0, 0.0])
                st[0] += 1
                st[1] += dt
            if emit:
                self.event("bench_phase", name=name, wall_s=round(dt, 6))

    @contextmanager
    def dispatch(self, name: str, emit: bool = True, **args):
        """Wrap one jitted device dispatch: cold/warm attribution (first
        call of a program = the call that paid XLA compile) and the trace
        annotation ``pcg-tpu/<name>`` (with ``args``), so the dispatch is
        a named region in profiler traces.

        Caller contract: jax dispatch is ASYNC — keep a blocking
        device->host fetch (``int(scalar)``, ``float(scalar)``,
        ``block_until_ready``) inside the span, otherwise wall_s measures
        enqueue time, not execution."""
        with self._lock:
            st = self._dispatch.setdefault(name, [0, 0.0, 0.0])
            cold = st[0] == 0
            st[0] += 1
        # Flight bracket (obs/flight.py): the begin record is fsync'd
        # BEFORE the dispatch runs, so a device loss / SIGKILL inside
        # the device call leaves "dispatch:<name> in flight" on disk.
        flight = self.flight
        seq = (flight.begin(f"dispatch:{name}", cold=cold)
               if flight is not None else None)
        t0 = self._clock()
        ok = True
        err = None
        try:
            with _annotation(name, args):
                yield
        except BaseException as e:
            ok = False
            err = f"{type(e).__name__}: {e}"
            raise
        finally:
            dt = self._clock() - t0
            with self._lock:
                st = self._dispatch[name]
                st[1 if cold else 2] += dt
            self.inc(f"dispatch.{name}.calls")
            if flight is not None:
                flight.end(seq, f"dispatch:{name}", ok=ok,
                           wall_s=round(dt, 6),
                           **({"error": err} if err else {}))
            if emit:
                self.event("dispatch", name=name, wall_s=round(dt, 6),
                           cold=cold)

    # -- snapshots ------------------------------------------------------
    def dispatch_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-program compile vs execute attribution: ``cold_s`` is the
        first call (compile + one execution), ``warm_s`` the rest."""
        with self._lock:
            return {k: {"calls": int(v[0]), "cold_s": v[1], "warm_s": v[2]}
                    for k, v in self._dispatch.items()}

    def span_stats(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {k: {"calls": int(v[0]), "total_s": v[1]}
                    for k, v in self._spans.items()}

    def reset_dispatch_attribution(self) -> None:
        """Forget per-program cold/warm state.  Call when the programs
        behind the dispatch names are REBUILT (e.g. a solver
        reconstruction after a failed kernel path): the next call of each
        name pays XLA compile again and must be booked as cold.  The
        ``dispatch.<name>.calls`` counters reset too, so snapshot() stays
        internally consistent."""
        with self._lock:
            self._dispatch.clear()
            for k in [k for k in self.counters
                      if k.startswith("dispatch.")]:
                del self.counters[k]

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            counters = dict(self.counters)
            gauges = dict(self.gauges)
        return {"counters": counters, "gauges": gauges,
                "spans": self.span_stats(),
                "dispatches": self.dispatch_stats()}

    def emit_run_summary(self) -> Dict[str, Any]:
        return self.event("run_summary", **self.snapshot())

    def summary(self) -> str:
        """Human-readable end-of-run table (the CLI ``--summary`` output)."""
        lines = []
        if self.step_events:
            lines.append(f"{'step':>5} {'flag':>4} {'iters':>7} "
                         f"{'relres':>10} {'wall_s':>9}")
            for ev in self.step_events:
                lines.append(
                    f"{ev.get('step', '?'):>5} {ev.get('flag', '?'):>4} "
                    f"{ev.get('iters', '?'):>7} "
                    f"{ev.get('relres', float('nan')):>10.3e} "
                    f"{ev.get('wall_s', float('nan')):>9.3f}")
        ds = self.dispatch_stats()
        if ds:
            lines.append("")
            lines.append(f"{'dispatch':<24} {'calls':>6} {'cold_s':>9} "
                         f"{'warm_s':>9}")
            for name in sorted(ds):
                d = ds[name]
                lines.append(f"{name:<24} {d['calls']:>6} "
                             f"{d['cold_s']:>9.3f} {d['warm_s']:>9.3f}")
        with self._lock:
            gauges = dict(self.gauges)
            counters = dict(self.counters)
        extra = {k: v for k, v in counters.items()
                 if not k.startswith("dispatch.")}
        if gauges:
            lines.append("")
            lines.extend(f"gauge {k} = {gauges[k]}" for k in sorted(gauges))
        if extra:
            lines.extend(f"counter {k} = {extra[k]}" for k in sorted(extra))
        return "\n".join(lines) if lines else "(no telemetry recorded)"


def summarize_jsonl(path: str) -> str:
    """Offline ``--summary`` of an on-disk telemetry/flight JSONL
    artifact — INCLUDING the exact artifact a killed process leaves: a
    truncated trailing line is skipped and counted (``truncated_lines``),
    never raised on (obs/flight.read_jsonl_tolerant).

    Rebuilds the live summary's tables from the event stream: the
    per-step table, per-dispatch cold/warm aggregation, per-kind event
    counts, the last run_summary's gauges, and — when flight records are
    present — the mechanical verdict (clean / failed / died-in-flight
    with the unclosed record names and last heartbeat)."""
    from pcg_mpi_solver_tpu.obs.flight import (
        flight_verdict_path, read_jsonl_tolerant)

    events, truncated = read_jsonl_tolerant(path)
    lines = [f"{path}: {len(events)} event(s), "
             f"truncated_lines = {truncated}"]
    kinds: Dict[str, int] = {}
    for ev in events:
        k = str(ev.get("kind", "?"))
        kinds[k] = kinds.get(k, 0) + 1
    if kinds:
        lines.append("  " + "  ".join(f"{k}={kinds[k]}"
                                      for k in sorted(kinds)))
    steps = [ev for ev in events if ev.get("kind") == "step"]
    if steps:
        lines.append("")
        lines.append(f"{'step':>5} {'flag':>4} {'iters':>7} "
                     f"{'relres':>10} {'wall_s':>9}")
        for ev in steps:
            try:
                relres = float(ev.get("relres", float("nan")))
                wall = float(ev.get("wall_s", float("nan")))
            except (TypeError, ValueError):
                relres = wall = float("nan")
            lines.append(
                f"{ev.get('step', '?'):>5} {ev.get('flag', '?'):>4} "
                f"{ev.get('iters', '?'):>7} {relres:>10.3e} "
                f"{wall:>9.3f}")
    disp: Dict[str, List[float]] = {}
    for ev in events:
        if ev.get("kind") != "dispatch":
            continue
        st = disp.setdefault(str(ev.get("name", "?")), [0, 0.0, 0.0])
        st[0] += 1
        try:
            w = float(ev.get("wall_s", 0.0) or 0.0)
        except (TypeError, ValueError):
            w = 0.0
        st[1 if ev.get("cold") else 2] += w
    if disp:
        lines.append("")
        lines.append(f"{'dispatch':<24} {'calls':>6} {'cold_s':>9} "
                     f"{'warm_s':>9}")
        for name in sorted(disp):
            d = disp[name]
            lines.append(f"{name:<24} {int(d[0]):>6} {d[1]:>9.3f} "
                         f"{d[2]:>9.3f}")
    caps = [ev for ev in events if ev.get("kind") == "profile_capture"]
    if caps:
        lines.append("")
        for ev in caps:
            lines.append(f"profile artifact: {ev.get('path')} "
                         f"(read it back with `pcg-tpu prof-report`)")
    summaries = [ev for ev in events if ev.get("kind") == "run_summary"]
    if summaries:
        gauges = summaries[-1].get("gauges") or {}
        if isinstance(gauges, dict) and gauges:
            lines.append("")
            lines.extend(f"gauge {k} = {gauges[k]}"
                         for k in sorted(gauges))
    if any(ev.get("kind") == "flight" for ev in events):
        # flight_verdict_path folds a final heartbeat cut mid-write back
        # into last_wall/last_mono (salvaged_tail): a shard killed while
        # writing its newest beat must read as alive until then, not as
        # having died a heartbeat interval earlier
        v = flight_verdict_path(path)
        lines.append("")
        lines.append(f"flight verdict: {v['verdict']} "
                     f"({v['records']} record(s))")
        if v["in_flight"]:
            lines.append("  in flight at death: "
                         + ", ".join(v["in_flight"]))
        for msg in v["fails"]:
            lines.append(f"  fail: {msg}")
        for msg in v.get("expected_fails", []):
            lines.append(f"  expected descent: {msg}")
        if v["last_wall"] is not None:
            lines.append(f"  last record at t={v['last_wall']:.3f} "
                         f"(mono {v['last_mono']})"
                         + (" [salvaged from the truncated final line]"
                            if v.get("salvaged_tail") else ""))
    if truncated:
        lines.append(f"({truncated} truncated line(s) skipped — the "
                     "partial write of a killed process)")
    return "\n".join(lines)
