"""Telemetry subsystem (obs/): in-graph convergence-trace ring buffer
(clamping, wrap-around, parity against the numpy reference), the metrics
recorder / JSONL event round-trip, and the no-extra-transfer contract."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from pcg_mpi_solver_tpu.config import RunConfig, SolverConfig, TimeHistoryConfig
from pcg_mpi_solver_tpu.models.synthetic import make_cube_model
from pcg_mpi_solver_tpu.obs.metrics import JsonlSink, MetricsRecorder, StderrSink
from pcg_mpi_solver_tpu.obs.schema import (
    TELEMETRY_SCHEMA, validate_event, validate_jsonl_text)
from pcg_mpi_solver_tpu.obs.trace import (
    clamp_trace_len, trace_init, trace_record, unpack_trace)
from pcg_mpi_solver_tpu.parallel.mesh import make_mesh
from pcg_mpi_solver_tpu.solver.driver import Solver
from pcg_mpi_solver_tpu.solver.numpy_ref import NumpyRefSolver


# ---------------------------------------------------------------- ring buffer
def test_clamp_trace_len():
    assert clamp_trace_len(100, 50) == 50      # clamped to max_iter
    assert clamp_trace_len(10, 50) == 10
    assert clamp_trace_len(0, 50) == 1         # floor (callers gate on > 0)
    assert clamp_trace_len(5, 0) == 1


def _record_n(tr, n):
    for i in range(1, n + 1):
        tr = trace_record(
            tr, normr=jnp.asarray(float(i)), rho=jnp.asarray(10.0 * i),
            stag=jnp.asarray(0, jnp.int32), flag=jnp.asarray(1, jnp.int32))
    return tr


def test_trace_no_wrap():
    tr = _record_n(trace_init(8), 5)
    out = unpack_trace(tr)
    assert out.n_recorded == 5 and not out.truncated
    np.testing.assert_allclose(out.normr, [1, 2, 3, 4, 5])
    np.testing.assert_allclose(out.rho, [10, 20, 30, 40, 50])


def test_trace_wrap_around_keeps_last_entries_in_order():
    tr = _record_n(trace_init(4), 7)
    out = unpack_trace(tr)
    assert out.n_recorded == 7 and out.truncated
    # ring holds the LAST 4 records, oldest -> newest
    np.testing.assert_allclose(out.normr, [4, 5, 6, 7])
    np.testing.assert_allclose(out.rho, [40, 50, 60, 70])


def test_trace_scale_restores_absolute_residuals():
    tr = trace_init(2)
    tr = trace_record(tr, normr=jnp.asarray(0.5), rho=jnp.asarray(1.0),
                      stag=jnp.asarray(0, jnp.int32),
                      flag=jnp.asarray(1, jnp.int32),
                      scale=jnp.asarray(8.0))
    out = unpack_trace(tr)
    np.testing.assert_allclose(out.normr, [4.0])


# ------------------------------------------------------------- normr parity
def test_traced_normr_matches_numpy_reference():
    """The in-graph trace must reproduce the host reference's per-iteration
    residual norms — same length, same values (f64 direct mode; both sides
    record the TRUE residual at tol-confirmation iterations)."""
    model = make_cube_model(4, 3, 3, h=0.5, nu=0.3, load="traction",
                            heterogeneous=True)
    cfg = RunConfig(
        solver=SolverConfig(tol=1e-8, max_iter=2000, trace_resid=2000),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 1.0]),
    )
    s = Solver(model, cfg, mesh=make_mesh(1), n_parts=1)
    r = s.step(1.0)
    assert r.flag == 0
    tr = s.last_trace
    assert tr is not None and not tr.truncated
    assert tr.n_recorded == r.iters
    ref = NumpyRefSolver(model).solve(1.0, tol=1e-8, max_iter=2000)
    assert ref.flag == 0
    # +-1 iteration (the parity .claude/skills/verify/SKILL.md grants):
    # the two f64 recurrences drift apart in low-order bits (different
    # summation orders), so a residual landing within round-off of
    # tol*||b|| crosses it one iteration apart — here the traced solve's
    # iteration 79 sits at 4.02e-8 against tol*||b|| = 4e-8, and the
    # reference's at 3.83e-8.
    n = min(len(ref.normr_hist), tr.n_recorded)
    assert abs(len(ref.normr_hist) - tr.n_recorded) <= 1
    # Early iterations: the two f64 implementations are numerically
    # indistinguishable.  Late iterations: the residual RECURRENCES drift
    # apart in low-order bits that compound, so the whole-trace contract
    # is log-space agreement — each recorded norm within a fraction of a
    # decade of the reference's — plus both endpoints under tol*||b||.
    np.testing.assert_allclose(tr.normr[:10], ref.normr_hist[:10],
                               rtol=1e-6)
    np.testing.assert_allclose(np.log10(tr.normr[:n]),
                               np.log10(ref.normr_hist[:n]), atol=0.5)
    tolb = 1e-8 * ref.normr_hist[-1] / ref.relres
    assert tr.normr[-1] <= tolb and ref.normr_hist[-1] <= tolb
    # the final recorded flag is the termination flag
    assert tr.flag[-1] == 0 and np.all(tr.flag[:-1] == 1)


def test_traced_chunked_identical_to_one_shot():
    """Dispatch chunking must not change the recorded trace (the ring rides
    the resumable carry across dispatch boundaries)."""
    model = make_cube_model(4, 3, 3, h=0.5, nu=0.3, load="traction",
                            heterogeneous=True)

    def run(iters_per_dispatch):
        cfg = RunConfig(
            solver=SolverConfig(tol=1e-8, max_iter=2000, trace_resid=2000,
                                iters_per_dispatch=iters_per_dispatch),
            time_history=TimeHistoryConfig(time_step_delta=[0.0, 1.0]),
        )
        s = Solver(model, cfg, mesh=make_mesh(1), n_parts=1)
        s.step(1.0)
        return s.last_trace

    one_shot, chunked = run(0), run(20)
    assert chunked.n_recorded == one_shot.n_recorded
    np.testing.assert_allclose(chunked.normr, one_shot.normr, rtol=1e-12)
    np.testing.assert_array_equal(chunked.flag, one_shot.flag)


def test_traced_mixed_mode_absolute_residuals():
    """Mixed-precision tracing: recorded norms are rescaled to absolute
    residuals, so the trace decays to ~tol*||b|| like the direct trace."""
    model = make_cube_model(4, 3, 3, h=0.5, nu=0.3, load="traction",
                            heterogeneous=True)
    cfg = RunConfig(
        solver=SolverConfig(tol=1e-8, max_iter=4000, trace_resid=4000,
                            dtype="float32", dot_dtype="float64",
                            precision_mode="mixed"),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 1.0]),
    )
    s = Solver(model, cfg, mesh=make_mesh(1), n_parts=1)
    r = s.step(1.0)
    assert r.flag == 0
    tr = s.last_trace
    assert tr.n_recorded == r.iters
    # absolute scale: starts near ||b|| magnitude, ends near tol*||b||
    ref = NumpyRefSolver(model).solve(1.0, tol=1e-8, max_iter=4000)
    n2b = np.linalg.norm(ref.normr_hist[0])
    assert tr.normr[0] > 1e3 * tr.normr[-1]
    assert tr.normr[-1] < 1e-6 * n2b


def test_trace_ring_shorter_than_solve_truncates():
    model = make_cube_model(4, 3, 3, h=0.5, nu=0.3, load="traction",
                            heterogeneous=True)
    cfg = RunConfig(
        solver=SolverConfig(tol=1e-8, max_iter=2000, trace_resid=10),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 1.0]),
    )
    s = Solver(model, cfg, mesh=make_mesh(1), n_parts=1)
    r = s.step(1.0)
    tr = s.last_trace
    assert tr.truncated and tr.n_recorded == r.iters
    assert len(tr.normr) == 10
    # the retained window is the LAST 10 iterations -> monotone-ish decay
    # into convergence, ending with the termination flag
    assert tr.flag[-1] == 0


# ------------------------------------------------------- recorder + JSONL
def test_recorder_jsonl_round_trip(tmp_path):
    path = str(tmp_path / "t.jsonl")
    rec = MetricsRecorder(sinks=[JsonlSink(path)])
    rec.event("step", step=1, flag=0, relres=1e-9, iters=42, wall_s=0.5)
    rec.note("hello")
    rec.inc("foo", 2)
    rec.gauge("bar", "baz")
    with rec.span("phase1", emit=True):
        pass
    rec.emit_run_summary()
    rec.close()

    text = open(path).read()
    assert validate_jsonl_text(text) == []
    events = [json.loads(ln) for ln in text.splitlines()]
    kinds = [e["kind"] for e in events]
    assert kinds == ["step", "note", "bench_phase", "run_summary"]
    assert all(e["schema"] == TELEMETRY_SCHEMA for e in events)
    step = events[0]
    assert step["iters"] == 42 and step["relres"] == 1e-9
    summary = events[-1]
    assert summary["counters"]["foo"] == 2
    assert summary["gauges"]["bar"] == "baz"
    assert summary["spans"]["phase1"]["calls"] == 1


def test_recorder_jsonl_appends_and_survives_kill(tmp_path):
    """Per-event flush: a half-finished run still leaves parseable lines."""
    path = str(tmp_path / "t.jsonl")
    rec = MetricsRecorder(sinks=[JsonlSink(path)])
    rec.note("one")
    # file is readable BEFORE close (flush-per-event)
    assert validate_jsonl_text(open(path).read()) == []
    rec.close()


def test_validate_event_rejects_missing_fields():
    assert validate_event({"schema": TELEMETRY_SCHEMA, "t": 0.0,
                           "kind": "step", "step": 1}) != []
    assert validate_event({"t": 0.0, "kind": "note", "msg": "x"}) != []
    ok = {"schema": TELEMETRY_SCHEMA, "t": 0.0, "kind": "note", "msg": "x"}
    assert validate_event(ok) == []
    # unknown kinds are forward-compatible (allowed)
    unk = {"schema": TELEMETRY_SCHEMA, "t": 0.0, "kind": "future_thing"}
    assert validate_event(unk) == []


def test_stderr_sink_verbose_alias(capsys, monkeypatch):
    """PCG_TPU_VERBOSE=1 is the alias that turns on the stderr
    breadcrumbs of the default recorder — checked PER EVENT like the
    historical _vlog, so it can be flipped on a live process."""
    monkeypatch.setenv("PCG_TPU_VERBOSE", "1")
    rec = MetricsRecorder.default()
    assert any(isinstance(snk, StderrSink) for snk in rec.sinks)
    rec.note("breadcrumb")
    err = capsys.readouterr().err
    assert "breadcrumb" in err and "[pcg-tpu " in err
    # flipping the env var OFF silences the SAME recorder mid-flight...
    monkeypatch.setenv("PCG_TPU_VERBOSE", "0")
    rec.note("muted")
    assert "muted" not in capsys.readouterr().err
    # ...and back ON re-enables it (the hung-dispatch forensics workflow)
    monkeypatch.setenv("PCG_TPU_VERBOSE", "1")
    rec.note("resumed")
    assert "resumed" in capsys.readouterr().err


def test_solver_step_events_and_dispatch_attribution(tmp_path):
    """Solver wiring end to end: a solve with telemetry_path set writes
    step + resid_trace + run_summary events, and dispatch stats split the
    compile-paying first call from warm calls."""
    path = str(tmp_path / "run.jsonl")
    model = make_cube_model(3, 3, 3)
    cfg = RunConfig(
        solver=SolverConfig(tol=1e-8, max_iter=2000, trace_resid=100),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 0.5, 1.0]),
        telemetry_path=path,
    )
    s = Solver(model, cfg, mesh=make_mesh(1), n_parts=1)
    res = s.solve()
    s.recorder.close()
    assert all(r.flag == 0 for r in res)
    text = open(path).read()
    assert validate_jsonl_text(text) == []
    events = [json.loads(ln) for ln in text.splitlines()]
    steps = [e for e in events if e["kind"] == "step"]
    traces = [e for e in events if e["kind"] == "resid_trace"]
    assert [e["step"] for e in steps] == [1, 2]
    assert len(traces) == 2
    assert traces[0]["n_recorded"] == steps[0]["iters"]
    assert len(traces[0]["normr"]) == min(steps[0]["iters"], 100)
    assert events[-1]["kind"] == "run_summary"
    ds = s.recorder.dispatch_stats()
    assert ds["step"]["calls"] == 2
    # first call paid the XLA compile: cold >> warm on this tiny model
    assert ds["step"]["cold_s"] > ds["step"]["warm_s"]
    gauges = events[-1]["gauges"]
    assert gauges["n_dof"] == model.n_dof
    assert "comm.psums_per_iter" in gauges


def test_cli_telemetry_end_to_end(tmp_path, capsys):
    """The acceptance surface: the CLI demo with --telemetry-out and
    --trace-resid writes schema-valid JSONL with per-step metrics and a
    residual trace matching the host reference within tolerance."""
    from pcg_mpi_solver_tpu.cli import main

    out = str(tmp_path / "out.jsonl")
    main(["demo", "--nx", "4", "--scratch", str(tmp_path / "s"),
          "--tol", "1e-8", "--precision", "direct",
          "--telemetry-out", out, "--trace-resid", "2000", "--summary"])
    stdout = capsys.readouterr().out
    assert ">success!" in stdout
    assert "dispatch" in stdout          # the --summary table
    text = open(out).read()
    assert validate_jsonl_text(text) == []
    events = [json.loads(ln) for ln in text.splitlines()]
    steps = [e for e in events if e["kind"] == "step"]
    traces = [e for e in events if e["kind"] == "resid_trace"]
    assert steps and traces and steps[0]["flag"] == 0
    # the demo model is make_cube_model(nx=4, heterogeneous=True): check
    # the traced residuals against the host-side reference on that model
    from pcg_mpi_solver_tpu.models.synthetic import make_cube_model as mk

    model = mk(4, 0, 0, E=30e9, nu=0.2, load="traction", load_value=1e6,
               heterogeneous=True)
    ref = NumpyRefSolver(model).solve(1.0, tol=1e-8, max_iter=10000)
    tn = np.asarray(traces[0]["normr"])
    assert len(tn) == len(ref.normr_hist)
    np.testing.assert_allclose(np.log10(tn), np.log10(ref.normr_hist),
                               atol=0.5)


def test_tracing_off_no_trace_in_carry():
    """With trace_resid=0 nothing is threaded: no trace output, and the
    carry schema (hence the compiled program) is unchanged."""
    from pcg_mpi_solver_tpu.solver.pcg import carry_part_specs, cold_carry

    model = make_cube_model(3, 3, 3)
    cfg = RunConfig(
        solver=SolverConfig(tol=1e-8, max_iter=2000),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 1.0]),
    )
    s = Solver(model, cfg, mesh=make_mesh(1), n_parts=1)
    s.step(1.0)
    assert s.last_trace is None and s.trace_len == 0
    import jax

    P, R = (jax.sharding.PartitionSpec("parts"),
            jax.sharding.PartitionSpec())
    assert "trace" not in carry_part_specs(P, R)
    assert "trace" not in cold_carry(jnp.zeros(4), jnp.zeros(4),
                                     jnp.asarray(1.0), jnp.float64)


# ---------------------------------------------------------- flight recorder
#
# ISSUE 12: crash-durable flight records (obs/flight.py), the tolerant
# JSONL ingest every killed-process artifact needs, per-process telemetry
# shards and their merge aggregator, and the SIGKILL-mid-solve
# acceptance path.

import os
import signal
import socket
import subprocess
import sys
import textwrap
import time

from pcg_mpi_solver_tpu.obs.flight import (
    FlightRecorder, find_shards, flight_verdict, flight_verdict_path,
    merge_shards, read_jsonl_tolerant, shard_jsonl_path)


def test_flight_brackets_and_verdicts(tmp_path):
    """begin/end -> clean, a fail bracket -> failed, an unclosed bracket
    -> died; every record is schema-valid and carries BOTH clocks."""
    p = str(tmp_path / "f.jsonl")
    fl = FlightRecorder(p, meta={"component": "test"}, heartbeat_s=30)
    with fl.record("solve:cube", nx=4):
        pass
    fl.close()
    assert validate_jsonl_text(open(p).read()) == []
    events, truncated = read_jsonl_tolerant(p)
    assert truncated == 0
    assert [e["op"] for e in events] == ["meta", "begin", "end"]
    assert all(e["kind"] == "flight" and "mono" in e and "t" in e
               for e in events)
    v = flight_verdict(events)
    assert v["verdict"] == "clean" and v["in_flight"] == []
    assert v["last_wall"] is not None and v["last_mono"] is not None

    # a bracket that raises closes as op=fail and the error survives
    fl2 = FlightRecorder(str(tmp_path / "g.jsonl"), heartbeat_s=30)
    with pytest.raises(RuntimeError):
        with fl2.record("solve:boom"):
            raise RuntimeError("device lost")
    fl2.close()
    v2 = flight_verdict_path(str(tmp_path / "g.jsonl"))
    assert v2["verdict"] == "failed"
    assert any("device lost" in f for f in v2["fails"])

    # an unclosed bracket is the kill signature: verdict died
    fl3 = FlightRecorder(str(tmp_path / "h.jsonl"), heartbeat_s=30)
    fl3.begin("dispatch:step")
    fl3.close()
    v3 = flight_verdict_path(str(tmp_path / "h.jsonl"))
    assert v3["verdict"] == "died"
    assert v3["in_flight"] == ["dispatch:step"]

    # a fail bracket stamped expected=True (the bench ladder descending
    # by design) must NOT fail the artifact — and neither must the
    # Solver's unmarked dispatch fail NESTED inside it (the solve raised
    # first; bench only stamps the rung).  A successful descent run
    # reads clean, with the descent still on record.
    fl4 = FlightRecorder(str(tmp_path / "i.jsonl"), heartbeat_s=30)
    seq = fl4.begin("rung:0", nx=160)
    dseq = fl4.begin("dispatch:step")
    fl4.end(dseq, "dispatch:step", ok=False, error="RuntimeError: OOM")
    fl4.end(seq, "rung:0", ok=False, error="RuntimeError: OOM",
            expected=True)
    with fl4.record("rung:1", nx=128):
        pass
    fl4.close()
    v4 = flight_verdict_path(str(tmp_path / "i.jsonl"))
    assert v4["verdict"] == "clean", v4
    assert v4["fails"] == []
    assert [f.split(":")[0] for f in v4["expected_fails"]] == \
        ["dispatch", "rung"]

    # ...but a fail OUTSIDE any expected span still fails the artifact
    fl5 = FlightRecorder(str(tmp_path / "j.jsonl"), heartbeat_s=30)
    seq = fl5.begin("rung:0")
    fl5.end(seq, "rung:0", ok=False, error="OOM", expected=True)
    with pytest.raises(RuntimeError):
        with fl5.record("dispatch:later"):
            raise RuntimeError("real failure")
    fl5.close()
    v5 = flight_verdict_path(str(tmp_path / "j.jsonl"))
    assert v5["verdict"] == "failed", v5
    assert any("real failure" in f for f in v5["fails"])


def test_flight_heartbeats_while_bracket_open(tmp_path):
    """Heartbeats tick only while a bracket is open, carry the in-flight
    names, and stop once the bracket closes."""
    p = str(tmp_path / "hb.jsonl")
    fl = FlightRecorder(p, heartbeat_s=0.06)
    seq = fl.begin("dispatch:long")
    time.sleep(0.4)
    fl.end(seq, "dispatch:long")
    events, _ = read_jsonl_tolerant(p)
    beats = [e for e in events if e["op"] == "heartbeat"]
    assert beats, "no heartbeat while the bracket was open"
    assert all(b["in_flight"] == ["dispatch:long"] for b in beats)
    n = len(beats)
    time.sleep(0.25)
    events, _ = read_jsonl_tolerant(p)
    assert len([e for e in events if e["op"] == "heartbeat"]) == n
    fl.close()


def test_read_jsonl_tolerant_skips_cut_line(tmp_path):
    """The killed-process artifact: a trailing line cut mid-object is
    skipped and counted, never raised on."""
    p = tmp_path / "cut.jsonl"
    good = json.dumps({"schema": TELEMETRY_SCHEMA, "t": 1.0,
                       "kind": "note", "msg": "ok"})
    p.write_text(good + "\n" + good + "\n"
                 + '{"schema": "pcg-tpu-telemetry/1", "kind": "st')
    events, truncated = read_jsonl_tolerant(str(p))
    assert len(events) == 2 and truncated == 1
    # non-object lines count as truncated too, blank lines are ignored
    p.write_text(good + "\n\n[1, 2]\n")
    events, truncated = read_jsonl_tolerant(str(p))
    assert len(events) == 1 and truncated == 1


def test_solver_flight_path_brackets_every_dispatch(tmp_path):
    """RunConfig.flight_path wires the recorder through the Solver: a
    dead previous run's artifact at the same path is rotated to .prev
    (never appended to — reused seq numbers would close its unclosed
    brackets), the solve dispatch lands between fsync'd begin/end flight
    records, and a completed run reads verdict=clean."""
    p = str(tmp_path / "solve_flight.jsonl")
    stale = FlightRecorder(p, heartbeat_s=30)
    stale.begin("dispatch:killed previous run")     # never closed
    stale.close()
    model = make_cube_model(4, 0, 0, E=30e9, nu=0.2, load="traction",
                            load_value=1e6, heterogeneous=True)
    cfg = RunConfig(flight_path=p,
                    solver=SolverConfig(tol=1e-8, max_iter=2000))
    s = Solver(model, cfg, mesh=make_mesh(1), n_parts=1)
    r = s.step(1.0)
    assert r.flag == 0
    s.recorder.close()
    prev = flight_verdict_path(p + ".prev")
    assert prev["verdict"] == "died"
    assert prev["in_flight"] == ["dispatch:killed previous run"]
    assert validate_jsonl_text(open(p).read()) == []
    events, truncated = read_jsonl_tolerant(p)
    assert truncated == 0
    begins = [e["name"] for e in events if e["op"] == "begin"]
    assert "dispatch:step" in begins
    v = flight_verdict(events)
    assert v["verdict"] == "clean", v


def test_flight_attach_survives_typod_heartbeat_env(tmp_path, monkeypatch):
    """A typo'd PCG_TPU_FLIGHT_HEARTBEAT_S must not cost the run:
    FlightRecorder falls back to the default cadence and attach_flight
    still wires up (its contract says observability never aborts the
    solve it observes)."""
    from pcg_mpi_solver_tpu.obs.flight import attach_flight

    monkeypatch.setenv("PCG_TPU_FLIGHT_HEARTBEAT_S", "5s")
    rec = MetricsRecorder()
    fl = attach_flight(rec, str(tmp_path / "f.jsonl"), "test")
    assert fl is not None and fl.heartbeat_s == 5.0
    fl.close()


def test_ingest_rotation_failure_diverts_to_fallback_path(
        tmp_path, monkeypatch):
    """When the leftover artifact can't be rotated (read-only dir, NFS
    hiccup) the new stream must NOT append to it — the fresh recorder's
    reused seq numbers would close the dead run's brackets and its
    'died' verdict would read clean.  ingest_and_rotate diverts the new
    stream to a unique .<pid> sibling instead."""
    import pcg_mpi_solver_tpu.obs.flight as flight_mod

    p = str(tmp_path / "wedged.jsonl")
    stale = FlightRecorder(p, heartbeat_s=30)
    stale.begin("dispatch:killed previous run")     # never closed
    stale.close()

    def deny_replace(src, dst):
        raise OSError("read-only directory")

    monkeypatch.setattr(flight_mod.os, "replace", deny_replace)
    notes = []
    safe = flight_mod.ingest_and_rotate(p, notes.append)
    assert safe == f"{p}.{os.getpid()}"
    assert any("could not be read/rotated" in m for m in notes), notes

    # the shared attach wiring uses the diverted path end-to-end
    rec = MetricsRecorder()
    fl = flight_mod.attach_flight(rec, p, "test")
    assert fl is not None and fl.path == safe
    with fl.record("dispatch:fresh"):
        pass
    fl.close()
    # the dead run's artifact is untouched and still reads died
    v_old = flight_verdict_path(p)
    assert v_old["verdict"] == "died"
    assert v_old["in_flight"] == ["dispatch:killed previous run"]
    assert flight_verdict_path(safe)["verdict"] == "clean"


def test_dynamics_driver_flight_path_wires_brackets(tmp_path):
    """--flight-out / RunConfig.flight_path must not be a silent no-op
    for the explicit-dynamics driver: its chunk dispatches land between
    flight brackets exactly like the quasi-static Solver's (a long time
    history is the run a device loss orphans)."""
    from pcg_mpi_solver_tpu.solver.dynamics import DynamicsSolver, stable_dt

    p = str(tmp_path / "dyn_flight.jsonl")
    model = make_cube_model(3, 3, 3, E=100.0, nu=0.25, rho=1.0,
                            load="traction", load_value=1.0)
    dyn = DynamicsSolver(model, RunConfig(flight_path=p),
                         mesh=make_mesh(1), n_parts=1,
                         dt=stable_dt(model, safety=0.5))
    dyn.run(n_steps=3)
    dyn.recorder.close()
    events, _ = read_jsonl_tolerant(p)
    begins = [e["name"] for e in events if e["op"] == "begin"]
    assert any(n.startswith("dispatch:") for n in begins), begins
    assert flight_verdict(events)["verdict"] == "clean"


def test_dispatch_failure_records_error_text(tmp_path):
    """A dispatch that raises must close its flight bracket with the
    exception text — `pcg-tpu summary` on the crash artifact prints the
    actual error, not 'dispatch:step: ?'."""
    p = str(tmp_path / "boom.jsonl")
    rec = MetricsRecorder(sinks=[])
    rec.flight = FlightRecorder(p, heartbeat_s=30)
    with pytest.raises(RuntimeError):
        with rec.dispatch("step"):
            raise RuntimeError("UNAVAILABLE: device lost")
    rec.close()
    v = flight_verdict_path(p)
    assert v["verdict"] == "failed"
    assert any("UNAVAILABLE: device lost" in f for f in v["fails"]), v


def test_flight_write_trouble_never_raises(tmp_path):
    """Disk trouble mid-run (handle gone, disk full) must never cost the
    run: emit swallows the write error and the brackets keep working."""
    p = str(tmp_path / "trouble.jsonl")
    fl = FlightRecorder(p, heartbeat_s=30)
    with fl.record("dispatch:ok"):
        pass
    fl._f.close()                   # simulate the handle dying mid-run
    with fl.record("dispatch:unrecorded"):
        pass                        # must not raise
    fl.close()
    v = flight_verdict_path(p)
    assert v["verdict"] == "clean"  # the pre-trouble records survive


_KILL_CHILD = textwrap.dedent("""\
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    from pcg_mpi_solver_tpu.config import RunConfig, SolverConfig
    from pcg_mpi_solver_tpu.models.synthetic import make_cube_model
    from pcg_mpi_solver_tpu.parallel.mesh import make_mesh
    from pcg_mpi_solver_tpu.solver.driver import Solver

    model = make_cube_model(10, 0, 0, E=30e9, nu=0.2, load="traction",
                            load_value=1e6, heterogeneous=True)
    cfg = RunConfig(flight_path=sys.argv[1],
                    solver=SolverConfig(tol=1e-30, max_iter=200000))
    s = Solver(model, cfg, mesh=make_mesh(1), n_parts=1)
    while True:                     # killed from outside mid-dispatch
        s.step(1.0)
        s.reset_state()
""")


def test_sigkill_mid_solve_leaves_parseable_flight_record(tmp_path,
                                                          capsys):
    """The acceptance path: SIGKILL a solve mid-dispatch; the flight
    JSONL on disk must read verdict=died with the in-flight dispatch
    named, `pcg-tpu summary` must parse it without error, and the bench
    salvage/startup path must ingest + rotate it mechanically."""
    p = str(tmp_path / "killed.jsonl")
    script = tmp_path / "child.py"
    script.write_text(_KILL_CHILD)
    env = dict(os.environ)
    env["PCG_TPU_FLIGHT_HEARTBEAT_S"] = "0.2"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    proc = subprocess.Popen([sys.executable, str(script), p],
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL, env=env)
    try:
        deadline = time.time() + 180
        while time.time() < deadline:
            if os.path.exists(p) and \
                    flight_verdict_path(p)["in_flight"]:
                break
            assert proc.poll() is None, "child exited before the kill"
            time.sleep(0.05)
        else:
            raise AssertionError("no in-flight bracket before timeout")
        time.sleep(0.5)             # let a heartbeat land mid-flight
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()

    v = flight_verdict_path(p)
    assert v["verdict"] == "died", v
    assert any(n.startswith("dispatch:") for n in v["in_flight"]), v
    assert v["last_mono"] is not None and v["last_wall"] is not None
    events, _ = read_jsonl_tolerant(p)
    assert any(e["op"] == "heartbeat" for e in events)

    # `pcg-tpu summary` parses the artifact without error
    from pcg_mpi_solver_tpu.cli import main

    main(["summary", p])
    out = capsys.readouterr().out
    assert "flight verdict: died" in out
    assert "in flight at death: dispatch:" in out

    # the bench startup/salvage path ingests the SAME artifact
    # mechanically: verdict logged, file rotated to .prev, fresh
    # recorder armed in its place
    from pcg_mpi_solver_tpu import bench

    bench_path = str(tmp_path / "bench_flight.jsonl")
    os.rename(p, bench_path)
    old_env = os.environ.get("BENCH_FLIGHT")
    os.environ["BENCH_FLIGHT"] = bench_path
    try:
        fl = bench._attach_flight()
        assert fl is not None
        fl.close()
    finally:
        bench._REC.flight = None
        if old_env is None:
            os.environ.pop("BENCH_FLIGHT", None)
        else:
            os.environ["BENCH_FLIGHT"] = old_env
    err = capsys.readouterr().err
    assert "verdict=died" in err
    assert os.path.exists(bench_path + ".prev")
    v_new = flight_verdict_path(bench_path)
    assert v_new["verdict"] == "clean"      # the fresh stream: meta only


def test_summary_cli_tolerates_truncated_artifact(tmp_path, capsys):
    """`pcg-tpu summary` on the exact artifact a killed process leaves:
    the cut trailing line is skipped and REPORTED, the intact events
    still build the tables."""
    from pcg_mpi_solver_tpu.cli import main

    p = tmp_path / "run.jsonl"
    lines = [
        json.dumps({"schema": TELEMETRY_SCHEMA, "t": 1.0, "kind": "step",
                    "step": 1, "flag": 0, "relres": 1e-9, "iters": 42,
                    "wall_s": 0.5}),
        json.dumps({"schema": TELEMETRY_SCHEMA, "t": 2.0,
                    "kind": "dispatch", "name": "step", "wall_s": 0.4,
                    "cold": True}),
    ]
    p.write_text("\n".join(lines)
                 + '\n{"schema": "pcg-tpu-telemetry/1", "kind": "ste')
    main(["summary", str(p)])
    out = capsys.readouterr().out
    assert "truncated_lines = 1" in out
    assert "42" in out                      # the step table survived
    assert "partial write of a killed process" in out
    with pytest.raises(SystemExit):
        main(["summary", str(tmp_path / "no_such.jsonl")])


def test_summary_cli_falls_back_to_shards(tmp_path, capsys):
    """A multi-process run shards run.jsonl away to run.p<idx>.jsonl;
    `pcg-tpu summary run.jsonl` (the documented invocation) must find
    and summarize the shards instead of hard-failing on the base name."""
    from pcg_mpi_solver_tpu.cli import main

    def ev(t, step):
        return json.dumps({"schema": TELEMETRY_SCHEMA, "t": t,
                           "kind": "step", "step": step, "flag": 0,
                           "relres": 1e-9, "iters": 7, "wall_s": 0.1})

    (tmp_path / "run.p0.jsonl").write_text(ev(1.0, 1) + "\n")
    (tmp_path / "run.p1.jsonl").write_text(ev(2.0, 2) + "\n")
    main(["summary", str(tmp_path / "run.jsonl")])
    out = capsys.readouterr().out
    assert "2 per-process shard(s)" in out
    assert "run.p0.jsonl" in out and "run.p1.jsonl" in out


def test_shard_path_and_find_shards(tmp_path):
    base = str(tmp_path / "run.jsonl")
    # single-process: the path is untouched (existing workflows keep
    # their exact filenames)
    assert shard_jsonl_path(base, 0, 1) == base
    assert shard_jsonl_path(base, 3, 4) == str(tmp_path / "run.p3.jsonl")
    for name in ("run.jsonl", "run.p0.jsonl", "run.p1.jsonl",
                 "run.p10.jsonl", "run.pX.jsonl", "other.p0.jsonl"):
        (tmp_path / name).write_text("")
    shards = find_shards(base)
    assert shards == [base, str(tmp_path / "run.p0.jsonl"),
                      str(tmp_path / "run.p1.jsonl"),
                      str(tmp_path / "run.p10.jsonl")]
    # an extension-less base path: shard_jsonl_path falls back to
    # .jsonl, so discovery must apply the SAME fallback
    bare = str(tmp_path / "bare")
    assert shard_jsonl_path(bare, 3, 4) == str(tmp_path / "bare.p3.jsonl")
    (tmp_path / "bare.p3.jsonl").write_text("")
    assert find_shards(bare) == [str(tmp_path / "bare.p3.jsonl")]


def test_merge_shards_time_orders_and_tags(tmp_path):
    """The aggregator: per-process shards merge into one time-ordered
    stream, every event tagged with its source shard, truncated lines
    skipped and counted per shard."""

    def ev(t, msg):
        return json.dumps({"schema": TELEMETRY_SCHEMA, "t": t,
                           "kind": "note", "msg": msg})

    p0 = tmp_path / "run.p0.jsonl"
    p1 = tmp_path / "run.p1.jsonl"
    p0.write_text(ev(1.0, "a") + "\n" + ev(3.0, "c") + "\n")
    p1.write_text(ev(2.0, "b") + "\n" + ev(4.0, "d") + "\n"
                  + '{"cut": ')
    out = str(tmp_path / "merged.jsonl")
    stats = merge_shards([str(p0), str(p1)], out)
    assert stats["events"] == 4 and stats["truncated_lines"] == 1
    assert stats["shards"]["run.p1.jsonl"]["truncated"] == 1
    merged = [json.loads(ln) for ln in open(out)]
    assert [e["msg"] for e in merged] == ["a", "b", "c", "d"]
    assert [e["shard"] for e in merged] == [
        "run.p0.jsonl", "run.p1.jsonl", "run.p0.jsonl", "run.p1.jsonl"]
    assert validate_jsonl_text(open(out).read()) == []


def test_merged_flight_verdict_pairs_brackets_per_shard(tmp_path):
    """Per-shard seq counters all start at 1, so a merged stream must
    pair begin/end PER SOURCE SHARD — process 1's end must not close
    process 0's unclosed begin (a died shard would read clean)."""
    f0 = FlightRecorder(str(tmp_path / "fl.p0.jsonl"), heartbeat_s=30)
    f0.begin("dispatch:p0-died-here")           # never closed
    f0.close()
    f1 = FlightRecorder(str(tmp_path / "fl.p1.jsonl"), heartbeat_s=30)
    with f1.record("dispatch:p1-fine"):         # same seq as p0's begin
        pass
    f1.close()
    out = str(tmp_path / "merged.jsonl")
    merge_shards([str(tmp_path / "fl.p0.jsonl"),
                  str(tmp_path / "fl.p1.jsonl")], out)
    v = flight_verdict_path(out)
    assert v["verdict"] == "died", v
    assert v["in_flight"] == ["dispatch:p0-died-here"]


def test_merge_shards_disambiguates_same_basename(tmp_path):
    """Cross-directory twins (two per-host collection dirs both holding
    run.p0.jsonl) must NOT collapse: stats keyed per input, and one
    run's end (same seq) must not close the other run's death."""
    da, db = tmp_path / "hostA", tmp_path / "hostB"
    fa = FlightRecorder(str(da / "run.p0.jsonl"), heartbeat_s=30)
    fa.begin("dispatch:hostA-died-here")        # never closed
    fa.close()
    fb = FlightRecorder(str(db / "run.p0.jsonl"), heartbeat_s=30)
    with fb.record("dispatch:hostB-fine"):      # same basename, same seq
        pass
    fb.close()
    out = str(tmp_path / "merged.jsonl")
    pa, pb = str(da / "run.p0.jsonl"), str(db / "run.p0.jsonl")
    stats = merge_shards([pa, pb], out)
    assert set(stats["shards"]) == {pa, pb}     # full paths, not basenames
    merged = [json.loads(ln) for ln in open(out)]
    assert {e["shard"] for e in merged} == {pa, pb}
    v = flight_verdict_path(out)
    assert v["verdict"] == "died", v
    assert v["in_flight"] == ["dispatch:hostA-died-here"]
    # the same file listed twice still yields two distinct stat keys
    stats2 = merge_shards([pa, pa], out)
    assert len(stats2["shards"]) == 2 and stats2["events"] > 0


def test_telemetry_merge_cli(tmp_path, capsys):
    from pcg_mpi_solver_tpu.cli import main

    base = tmp_path / "run.jsonl"
    ev = json.dumps({"schema": TELEMETRY_SCHEMA, "t": 1.0,
                     "kind": "note", "msg": "x"})
    base.write_text(ev + "\n")
    (tmp_path / "run.p1.jsonl").write_text(ev + "\n" + ev + "\n")
    out = str(tmp_path / "merged.jsonl")
    main(["telemetry-merge", str(base), "--out", out])
    stdout = capsys.readouterr().out
    assert ">merged 3 event(s) from 2 shard(s)" in stdout
    assert len(open(out).read().splitlines()) == 3
    with pytest.raises(SystemExit):
        main(["telemetry-merge", str(tmp_path / "ghost.jsonl"),
              "--out", out])


_SHARD_CHILD = textwrap.dedent("""\
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    import jax
    jax.config.update("jax_platforms", "cpu")

    from pcg_mpi_solver_tpu.parallel.distributed import init_distributed

    pid = init_distributed(coordinator_address=sys.argv[1],
                           num_processes=2, process_id=int(sys.argv[2]))
    assert jax.process_count() == 2

    from pcg_mpi_solver_tpu.obs.metrics import MetricsRecorder

    rec = MetricsRecorder.default(jsonl_path=sys.argv[3])
    rec.note(f"hello from process {pid}")
    rec.close()
    print(f"RESULT {pid} ok", flush=True)
""")


@pytest.mark.skipif(os.environ.get("PCG_TPU_SKIP_MULTIPROC") == "1",
                    reason="multi-process test disabled")
def test_two_process_telemetry_shards_merge_round_trip(tmp_path, capsys):
    """Under 2-process jax.distributed every process writes its OWN
    telemetry shard (run.p<idx>.jsonl — interleaved appends to one file
    would corrupt it) and `pcg-tpu telemetry-merge` reassembles one
    attributed stream.  No collective compute: sharding must work even
    where multi-process CPU computations don't."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    script = tmp_path / "child.py"
    script.write_text(_SHARD_CHILD)
    base = str(tmp_path / "run.jsonl")
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    procs = [subprocess.Popen(
                 [sys.executable, str(script), coord, str(i), base],
                 stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                 text=True, env=env)
             for i in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=180)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out}"

    # each process wrote its own shard; the unsharded base was NOT used
    assert not os.path.exists(base)
    assert os.path.exists(str(tmp_path / "run.p0.jsonl"))
    assert os.path.exists(str(tmp_path / "run.p1.jsonl"))

    from pcg_mpi_solver_tpu.cli import main

    merged = str(tmp_path / "merged.jsonl")
    main(["telemetry-merge", base, "--out", merged])
    capsys.readouterr()
    events = [json.loads(ln) for ln in open(merged)]
    notes = [e for e in events if e["kind"] == "note"]
    assert {n["msg"] for n in notes} == {"hello from process 0",
                                         "hello from process 1"}
    assert {n["shard"] for n in notes} == {"run.p0.jsonl",
                                           "run.p1.jsonl"}
