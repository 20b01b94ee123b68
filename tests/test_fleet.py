"""Fleet observability (ISSUE 16, obs/fleet.py + obs/watch.py): the
matched-anchor clock alignment and transport-vs-wait split on synthetic
per-process captures, the straggler naming on a REAL 2-process CPU
capture with an injected boundary delay, the live run monitor's stall /
ETA semantics, the clock-aligned telemetry merge, the summary CLI's
salvaged-final-heartbeat readback, the faultinject ``sleep`` straggler
simulator, the doc-schema sync rule, and the bench skew-detail
stamping."""

import gzip
import json
import os
import socket
import subprocess
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pcg_mpi_solver_tpu.obs import fleet, watch  # noqa: E402
from pcg_mpi_solver_tpu.obs.flight import (  # noqa: E402
    FlightRecorder, dispatch_anchors, flight_verdict_path, merge_shards,
    salvage_truncated_tail)
from pcg_mpi_solver_tpu.obs.schema import (  # noqa: E402
    TELEMETRY_SCHEMA, validate_bench_text, validate_event)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _CapturingRecorder:
    def __init__(self):
        self.events = []
        self.gauges = {}

    def event(self, kind, **fields):
        ev = {"schema": TELEMETRY_SCHEMA, "t": 0.0, "kind": kind}
        ev.update(fields)
        self.events.append(ev)
        return ev

    def gauge(self, name, value):
        self.gauges[name] = value


# ----------------------------------------------------------------------
# matched-anchor clock alignment (the shared helper)
# ----------------------------------------------------------------------

def test_align_offsets_median_and_degrades():
    # two streams, constant skew: the median recovers it exactly
    offs, n = fleet.align_offsets({
        0: {("a", 0): 10.0, ("a", 1): 20.0, ("b", 0): 30.0},
        1: {("a", 0): 110.0, ("a", 1): 120.0, ("b", 0): 130.0}})
    assert n == 3 and offs == {0: 0.0, 1: 100.0}
    # odd count with one outlier (a trace-boundary clip): median ignores it
    offs, _ = fleet.align_offsets({
        0: {("a", 0): 1.0, ("a", 1): 2.0, ("a", 2): 3.0},
        1: {("a", 0): 51.0, ("a", 1): 52.0, ("a", 2): 953.0}})
    assert offs[1] == 50.0
    # even count interpolates between the middle pair
    offs, _ = fleet.align_offsets({
        0: {("a", 0): 0.0, ("a", 1): 0.0},
        1: {("a", 0): 100.0, ("a", 1): 101.0}})
    assert offs[1] == pytest.approx(100.5)
    # anchors only match when present in ALL streams
    offs, n = fleet.align_offsets({
        0: {("a", 0): 1.0}, 1: {("b", 0): 2.0}})
    assert n == 0 and offs == {0: 0.0, 1: 0.0}
    # a single stream has nothing to align against
    offs, n = fleet.align_offsets({0: {("a", 0): 1.0}})
    assert n == 0 and offs == {0: 0.0}


def test_collective_occurrences_lane_aggregation():
    def op(name, ts, dur, pid=1, tid=1):
        return {"name": name, "base": name.rsplit(".", 1)[0], "ts": ts,
                "dur": dur, "pid": pid, "tid": tid, "text": ""}

    # two device lanes of ONE process see the same program collective:
    # the k-th per-lane occurrences aggregate (end=max, dur=max), and a
    # non-collective op contributes nothing
    reps = fleet.collective_occurrences([
        op("all-reduce.1", 1000, 300, pid=1, tid=1),
        op("all-reduce.5", 1010, 250, pid=2, tid=2),   # lane 2, k=0
        op("all-reduce.9", 2000, 100, pid=1, tid=1),   # lane 1, k=1
        op("fusion.2", 0, 9999)])
    assert set(reps) == {("all-reduce", 0), ("all-reduce", 1)}
    r0 = reps[("all-reduce", 0)]
    assert r0["dur"] == 300 and r0["end"] == 1300 and r0["lanes"] == 2
    assert r0["ts"] == 1000
    assert reps[("all-reduce", 1)]["lanes"] == 1


# ----------------------------------------------------------------------
# fleet_report over synthetic per-process captures
# ----------------------------------------------------------------------

def _write_capture(pdir, colls, meta=None):
    """One process's capture dir: a trace of collective events (name,
    ts, dur) plus the profview_meta.json sidecar."""
    os.makedirs(pdir, exist_ok=True)
    events = [{"ph": "X", "name": name, "ts": ts, "dur": dur,
               "pid": 1, "tid": 1, "args": {"hlo_op": name}}
              for name, ts, dur in colls]
    with gzip.open(os.path.join(pdir, "x.trace.json.gz"), "wb") as f:
        f.write(json.dumps({"traceEvents": events}).encode())
    if meta is not None:
        with open(os.path.join(pdir, "profview_meta.json"), "w",
                  encoding="utf-8") as f:
            json.dump(meta, f)


def _skewed_fleet_root(tmp_path):
    """p0 on the reference clock; p1's clock +100000us ahead and p1 the
    straggler (arrives last -> shortest durations) on the all-reduces."""
    meta = {"iters": 10, "scope_map": {"all-reduce.1": "reduce",
                                       "all-reduce.7": "reduce",
                                       "all-gather.3": "matvec"}}
    _write_capture(str(tmp_path / "p0"),
                   [("all-reduce.1", 1000, 300),
                    ("all-reduce.7", 2000, 400),
                    ("all-gather.3", 3000, 200)], meta=meta)
    _write_capture(str(tmp_path / "p1"),
                   [("all-reduce.1", 101200, 100),
                    ("all-reduce.7", 102250, 150),
                    ("all-gather.3", 103000, 200)], meta=meta)
    return str(tmp_path)


def test_fleet_report_synthetic_transport_wait_split(tmp_path):
    rep = fleet.fleet_report(_skewed_fleet_root(tmp_path))
    assert rep["verdict"] == "ok"
    assert rep["n_processes"] == 2 and rep["matched_collectives"] == 3
    # every matched end differs by exactly the baked-in clock skew
    assert rep["clock_offsets_ms"] == {"0": 0.0, "1": 100.0}
    # transport = per-collective min duration: 100 + 150 + 200 us
    assert rep["transport_ms"] == pytest.approx(0.45)
    # wait = p0's excess (200 + 250 + 0); p1 never waited
    assert rep["wait_ms"] == pytest.approx(0.45)
    p0, p1 = rep["processes"]["0"], rep["processes"]["1"]
    assert p0["wait_ms"] == pytest.approx(0.45)
    assert p1["wait_ms"] == pytest.approx(0.0)
    assert p0["skew_frac"] == pytest.approx(0.5)       # 450/900
    assert rep["skew_frac"] == pytest.approx(450 / 1350, abs=1e-4)
    # p1 arrived last and waited least: THE straggler, rank 0
    assert rep["straggler"] == "1"
    assert p1["straggler_rank"] == 0 and p0["straggler_rank"] == 1
    assert p1["caused_wait_ms"] == pytest.approx(0.45)
    # per-iteration normalization from the sidecar's iters
    assert p0["wait_ms_per_iter"] == pytest.approx(0.045)
    # phase attribution through the sidecar scope map: the skew lives in
    # the reduce-side collectives, the all-gather is balanced
    assert rep["phases"]["reduce"]["straggler"] == "1"
    assert rep["phases"]["reduce"]["wait_ms"] == pytest.approx(0.45)
    assert rep["phases"]["matvec"]["straggler"] is None
    # rendering carries the verdict lines an operator reads
    txt = fleet.format_fleet_report(rep)
    assert "straggler: p1" in txt and "skew_frac" in txt
    assert "clock offsets vs p0" in txt
    # the telemetry event validates against the schema contract
    rec = _CapturingRecorder()
    fleet.emit_fleet_report(rec, rep)
    assert validate_event(rec.events[0]) == []
    assert rec.gauges["fleet.skew_frac"] == rep["skew_frac"]


def test_fleet_report_degrades_by_name(tmp_path):
    # empty root: nothing to attribute
    rep = fleet.fleet_report(str(tmp_path / "nowhere"))
    assert rep["n_processes"] == 0
    assert rep["verdict"].startswith("degraded:")
    # single-process capture: a real artifact, but no cross-process skew
    _write_capture(str(tmp_path / "p0"), [("all-reduce.1", 0, 100)])
    rep = fleet.fleet_report(str(tmp_path))
    assert rep["n_processes"] == 1 and rep["skew_frac"] is None
    assert "single-process" in rep["verdict"]
    assert fleet.format_fleet_report(rep)          # renders, never raises
    # two processes with NO shared collective: alignment has no anchors
    _write_capture(str(tmp_path / "p1"), [("all-gather.9", 0, 100)])
    rep = fleet.fleet_report(str(tmp_path))
    assert rep["n_processes"] == 2
    assert "no matched collectives" in rep["verdict"]
    assert rep["skew_frac"] is None


def test_bench_detail_fields_never_fabricate(tmp_path):
    rep = fleet.fleet_report(_skewed_fleet_root(tmp_path))
    det = fleet.bench_detail_fields(rep, 0)
    assert det == {"skew_frac": rep["skew_frac"], "straggler_rank": 1}
    assert fleet.bench_detail_fields(rep, 1)["straggler_rank"] == 0
    # a process the report does not carry -> {}
    assert fleet.bench_detail_fields(rep, 7) == {}
    # an unmeasurable report -> {} (absent, not null — the ISSUE 15 rule)
    assert fleet.bench_detail_fields({"skew_frac": None}) == {}
    # and the stamped line validates against the bench schema
    line = {"schema": "pcg-tpu-bench/1", "metric": "dof_iter_per_s",
            "value": 1.0, "unit": "1/s", "vs_baseline": None,
            "detail": det}
    assert validate_bench_text(json.dumps(line)) == []


def test_trend_matches_legs_across_skew_stamped_rounds(tmp_path):
    """`pcg-tpu trend` must match a skew-stamped multi-controller line
    against an unstamped earlier round of the SAME leg: the ISSUE 16
    detail fields ride along without entering the matching identity."""
    from pcg_mpi_solver_tpu.obs import trend

    def line(value, extra_detail):
        d = {"model": "cube", "n_dof": 1000, "mode": "direct",
             "backend": "general", "pcg_variant": "classic",
             "precond": "jacobi", "nrhs": 1}
        d.update(extra_detail)
        return {"schema": "pcg-tpu-bench/1", "metric": "dof_iter_per_s",
                "value": value, "unit": "1/s", "vs_baseline": None,
                "detail": d}

    old = line(100.0, {})
    new = line(101.0, {"skew_frac": 0.37, "straggler_rank": 0})
    assert trend.leg_key(old) == trend.leg_key(new)
    a = str(tmp_path / "BENCH_r97.json")
    b = str(tmp_path / "BENCH_r98.json")
    json.dump(old, open(a, "w"))
    json.dump(new, open(b, "w"))
    rep = trend.trend_report([a, b])
    assert rep["flat"] == 1 and rep["regressed"] == 0
    assert rep["legs"][0]["rounds_seen"] == 2


def test_fleet_report_cli(tmp_path, capsys):
    from pcg_mpi_solver_tpu.cli import main

    root = _skewed_fleet_root(tmp_path)
    jpath = str(tmp_path / "fleet.json")
    tpath = str(tmp_path / "fleet.jsonl")
    main(["fleet-report", root, "--json", jpath,
          "--telemetry-out", tpath])
    out = capsys.readouterr().out
    assert "straggler: p1" in out and "verdict: ok" in out
    # the saved JSON round-trips through the loader
    rep = fleet.load_fleet_report(jpath)
    assert rep is not None and rep["straggler"] == "1"
    assert fleet.load_fleet_report(str(tmp_path / "ghost")) is None
    # the telemetry artifact carries a valid fleet_report event
    evs = [json.loads(ln) for ln in open(tpath)]
    assert any(e["kind"] == "fleet_report" for e in evs)
    # an empty root is a scripting failure: exit 2
    with pytest.raises(SystemExit) as ei:
        main(["fleet-report", str(tmp_path / "void")])
    assert ei.value.code == 2


# ----------------------------------------------------------------------
# REAL 2-process CPU capture: injected boundary delay -> named straggler
# ----------------------------------------------------------------------

_FLEET_CHILD = r"""
import os, sys
N_PROCS = 2
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ["PCG_TPU_FAULT_SLEEP_S"] = "0.05"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

from pcg_mpi_solver_tpu.parallel.distributed import (
    init_distributed, make_global_mesh)

pid = init_distributed(coordinator_address=sys.argv[1],
                       num_processes=N_PROCS, process_id=int(sys.argv[2]))
assert jax.process_count() == N_PROCS

from pcg_mpi_solver_tpu import RunConfig, SolverConfig
from pcg_mpi_solver_tpu.solver import Solver
from pcg_mpi_solver_tpu.resilience.faultinject import FaultPlan
from pcg_mpi_solver_tpu.obs.profview import capture_solve_profile

model = make_mh_test_model("general")
# small chunks => many host-side chunk boundaries for the delay to fire
cfg = RunConfig(solver=SolverConfig(tol=1e-8, max_iter=500,
                                    iters_per_dispatch=5))
s = Solver(model, cfg, mesh=make_global_mesh(), n_parts=8,
           backend="general")
if pid == 1:
    # rank 1 sleeps 50ms at EVERY chunk boundary (warm + traced solve
    # both consume boundary indices: cover plenty) — the deterministic
    # straggler every OTHER rank then waits for at its next collective
    s.fault_plan = FaultPlan(",".join(f"sleep@{i}" for i in range(400)))
cap = capture_solve_profile(s, sys.argv[3])
print(f"RESULT {pid} iters={cap['iters']} dir={cap['artifact']}",
      flush=True)
"""


@pytest.mark.skipif(os.environ.get("PCG_TPU_SKIP_MULTIPROC") == "1",
                    reason="multi-process test disabled")
def test_two_process_capture_names_delayed_rank_straggler(tmp_path,
                                                          capsys):
    """End to end on real gloo collectives: a 2-process CPU solve where
    rank 1 is artificially delayed at every chunk boundary
    (faultinject ``sleep``) must produce a fleet report that names rank
    1 the straggler, with the healthy rank carrying the matching wait."""
    from test_distributed import _run_multiproc

    root = str(tmp_path / "cap")
    results = _run_multiproc(tmp_path, _FLEET_CHILD, 2, [root])
    assert len(results) == 2
    # each process captured into its own p<idx>/ subdir
    assert os.path.isdir(os.path.join(root, "p0"))
    assert os.path.isdir(os.path.join(root, "p1"))

    rep = fleet.fleet_report(root)
    assert rep["n_processes"] == 2, rep["verdict"]
    assert rep["matched_collectives"] > 0, rep["verdict"]
    assert rep["skew_frac"] is not None and rep["skew_frac"] > 0
    # the delayed rank arrived last at every collective: THE straggler
    assert rep["straggler"] == "1", rep
    assert rep["processes"]["1"]["straggler_rank"] == 0
    # ... and the healthy rank is the one that paid the wait
    assert rep["processes"]["0"]["wait_ms"] > \
        rep["processes"]["1"]["wait_ms"]
    assert rep["processes"]["1"]["caused_wait_ms"] > \
        rep["processes"]["0"]["caused_wait_ms"]

    # the CLI reads the same capture back
    from pcg_mpi_solver_tpu.cli import main

    main(["fleet-report", root])
    out = capsys.readouterr().out
    assert "straggler: p1" in out


# ----------------------------------------------------------------------
# live run monitor: stall semantics, salvage, ETA
# ----------------------------------------------------------------------

def _ev(t, kind, **fields):
    d = {"schema": TELEMETRY_SCHEMA, "t": t, "kind": kind}
    d.update(fields)
    return json.dumps(d)


def test_watch_statuses_and_stall_needs_all_shards_silent(tmp_path):
    now = 1000.0
    base = str(tmp_path / "run.jsonl")
    # no shards on disk at all
    assert watch.watch_snapshot(base, now=now)["status"] == "empty"
    # one fresh shard: running
    (tmp_path / "run.p0.jsonl").write_text(
        _ev(now - 1.0, "note", msg="alive") + "\n")
    snap = watch.watch_snapshot(base, now=now, stall_after_s=5.0)
    assert snap["status"] == "running" and snap["n_shards"] == 1
    # a second, silent shard: NOT a stall — one slow host is skew, not a
    # wedged run
    (tmp_path / "run.p1.jsonl").write_text(
        _ev(now - 60.0, "note", msg="old") + "\n")
    snap = watch.watch_snapshot(base, now=now, stall_after_s=5.0)
    assert snap["status"] == "running"
    # ALL shards silent past the threshold: stall, detected within one
    # heartbeat-interval-sized threshold of the last record
    (tmp_path / "run.p0.jsonl").write_text(
        _ev(now - 6.0, "note", msg="stale") + "\n")
    snap = watch.watch_snapshot(base, now=now, stall_after_s=5.0)
    assert snap["status"] == "stalled"
    assert snap["silent_s"] == pytest.approx(6.0)
    txt = watch.format_watch(snap)
    assert "STALL" in txt and "STALLED" in txt
    rec = _CapturingRecorder()
    watch.emit_watch_events(rec, snap)
    kinds = [e["kind"] for e in rec.events]
    assert kinds == ["watch", "stall"]
    assert all(validate_event(e) == [] for e in rec.events)
    # done: a run_summary landed and nothing is in flight
    (tmp_path / "run.p0.jsonl").write_text(
        _ev(now - 6.0, "run_summary", counters={}, gauges={}) + "\n")
    (tmp_path / "run.p1.jsonl").write_text(
        _ev(now - 60.0, "run_summary", counters={}, gauges={}) + "\n")
    assert watch.watch_snapshot(base, now=now,
                                stall_after_s=5.0)["status"] == "done"


def test_watch_salvaged_heartbeat_defers_stall(tmp_path):
    """A final heartbeat cut mid-write is the run's last breath: the
    salvaged timestamp must keep the shard alive, not let the monitor
    flag a live run that was merely killed mid-write... of a line it
    wrote moments ago."""
    now = 1000.0
    p = tmp_path / "run.jsonl"
    cut = ('{"schema": "%s", "t": %s, "kind": "flight", '
           '"op": "heartbeat", "mono": 55.5, "se' % (TELEMETRY_SCHEMA,
                                                     now - 1.0))
    p.write_text(_ev(now - 30.0, "note", msg="old") + "\n" + cut)
    assert salvage_truncated_tail(str(p))["t"] == now - 1.0
    snap = watch.watch_snapshot(str(p), now=now, stall_after_s=5.0)
    assert snap["status"] == "running"
    assert snap["shards"][0]["salvaged_tail"]
    # without the salvaged tail the same stream would read stalled
    p.write_text(_ev(now - 30.0, "note", msg="old") + "\n")
    snap = watch.watch_snapshot(str(p), now=now, stall_after_s=5.0)
    assert snap["status"] == "stalled"


def test_watch_eta_cost_model_times_observed_rate(tmp_path):
    now = 1000.0
    p = tmp_path / "run.jsonl"
    lines = [
        _ev(now - 3.0, "cost_model", pcg_variant="classic",
            precond="jacobi", nrhs=1, backend="general", phases={},
            predicted_ms_per_iter=2.0),
        _ev(now - 2.0, "dispatch", name="cycle", wall_s=0.1, cold=True),
        _ev(now - 1.0, "resid_trace", step=1, n_recorded=4,
            truncated=False, normr=[1.0, 0.1, 0.01, 1e-3]),
    ]
    p.write_text("\n".join(lines) + "\n")
    snap = watch.watch_snapshot(str(p), now=now, stall_after_s=60.0,
                                tol=1e-8)
    # one decade per iteration observed; 5 decades left to tol; 2 ms/iter
    assert snap["rate_decades_per_iter"] == pytest.approx(-1.0)
    assert snap["last_relres"] == pytest.approx(1e-3)
    assert snap["eta_s"] == pytest.approx(0.01)
    assert snap["dispatches"] == {"cycle": 1}
    assert "ETA to tol" in watch.format_watch(snap)
    # remove the cost model: the ETA degrades to a NAMED reason
    p.write_text(lines[2] + "\n")
    snap = watch.watch_snapshot(str(p), now=now, stall_after_s=60.0)
    assert snap["eta_s"] is None
    assert "cost_model" in snap["eta_reason"]
    # steps-only stream: the rate falls back to relres over cumulative
    # iters
    p.write_text("\n".join([
        _ev(now - 2.0, "step", step=1, flag=0, relres=1e-2, iters=10,
            wall_s=0.1),
        _ev(now - 1.0, "step", step=2, flag=0, relres=1e-4, iters=10,
            wall_s=0.1)]) + "\n")
    snap = watch.watch_snapshot(str(p), now=now, stall_after_s=60.0)
    assert snap["rate_decades_per_iter"] == pytest.approx(-0.2)


def test_watch_cli_once_exit_codes(tmp_path, capsys):
    from pcg_mpi_solver_tpu.cli import main

    p = tmp_path / "run.jsonl"
    p.write_text(_ev(time.time(), "note", msg="alive") + "\n")
    # healthy snapshot: returns normally
    main(["watch", str(p), "--once"])
    assert "RUNNING" in capsys.readouterr().out
    # stalled snapshot: exit 3 (the scriptable probe)
    p.write_text(_ev(time.time() - 120.0, "note", msg="stale") + "\n")
    tout = str(tmp_path / "mon.jsonl")
    with pytest.raises(SystemExit) as ei:
        main(["watch", str(p), "--once", "--stall-after", "5",
              "--telemetry-out", tout])
    assert ei.value.code == 3
    evs = [json.loads(ln) for ln in open(tout)]
    assert [e["kind"] for e in evs if e["kind"] in ("watch", "stall")] \
        == ["watch", "stall"]


def test_stall_threshold_resolution(monkeypatch):
    assert watch.stall_threshold_s(7.5) == 7.5
    monkeypatch.setenv("PCG_TPU_FLIGHT_HEARTBEAT_S", "2.0")
    assert watch.stall_threshold_s() == pytest.approx(6.0)
    monkeypatch.setenv("PCG_TPU_FLIGHT_HEARTBEAT_S", "typo")
    assert watch.stall_threshold_s() == pytest.approx(
        watch.STALL_HEARTBEATS * 5.0)


# ----------------------------------------------------------------------
# telemetry-merge --align collectives over clock-skewed shards
# ----------------------------------------------------------------------

def test_merge_align_collectives_restores_true_order(tmp_path):
    """Two shards of one run whose host clocks disagree by 100.5s: the
    dispatch completions are the shared anchors, and alignment must
    interleave the events in TRUE order (raw-t ordering would sort every
    p1 event after every p0 event)."""
    p0 = tmp_path / "run.p0.jsonl"
    p1 = tmp_path / "run.p1.jsonl"
    p0.write_text("\n".join([
        _ev(10.0, "dispatch", name="cycle", wall_s=0.1, cold=True),
        _ev(15.0, "note", msg="mid0"),
        _ev(20.0, "dispatch", name="cycle", wall_s=0.1, cold=False),
    ]) + "\n")
    p1.write_text("\n".join([
        _ev(110.5, "dispatch", name="cycle", wall_s=0.1, cold=True),
        _ev(112.0, "note", msg="mid1"),
        _ev(120.5, "dispatch", name="cycle", wall_s=0.1, cold=False),
    ]) + "\n")
    out = str(tmp_path / "merged.jsonl")
    # without alignment: raw clocks, p1's note sorts last
    stats = merge_shards([str(p0), str(p1)], out)
    assert "align" not in stats
    msgs = [e["msg"] for e in map(json.loads, open(out))
            if e["kind"] == "note"]
    assert msgs == ["mid0", "mid1"]
    # with alignment: p1's offset (+100.5s) is recovered from the two
    # matched cycle completions and mid1 (true t=11.5) precedes mid0
    stats = merge_shards([str(p0), str(p1)], out, align="collectives")
    al = stats["align"]
    assert al["matched_anchors"] == 2
    assert al["offsets_s"]["run.p1.jsonl"] == pytest.approx(100.5)
    evs = [json.loads(ln) for ln in open(out)]
    msgs = [e["msg"] for e in evs if e["kind"] == "note"]
    assert msgs == ["mid1", "mid0"]
    # t_aligned stamped, raw t preserved
    mid1 = next(e for e in evs if e.get("msg") == "mid1")
    assert mid1["t"] == 112.0
    assert mid1["t_aligned"] == pytest.approx(11.5)


def test_dispatch_anchors_from_flight_and_telemetry():
    evs = [
        {"t": 1.0, "kind": "dispatch", "name": "cycle"},
        {"t": 2.0, "kind": "flight", "op": "end", "name": "dispatch:step"},
        {"t": 3.0, "kind": "dispatch", "name": "cycle"},
        {"t": 4.0, "kind": "flight", "op": "begin",
         "name": "dispatch:step"},            # begins are not completions
        {"t": 5.0, "kind": "note", "msg": "x"},
        {"kind": "dispatch", "name": "cycle"},  # no t: unusable
    ]
    a = dispatch_anchors(evs)
    assert a == {("cycle", 0): 1.0, ("dispatch:step", 0): 2.0,
                 ("cycle", 1): 3.0}


def test_merge_align_cli_prints_offsets(tmp_path, capsys):
    from pcg_mpi_solver_tpu.cli import main

    (tmp_path / "run.p0.jsonl").write_text(
        _ev(10.0, "dispatch", name="cycle", wall_s=0.1, cold=True) + "\n")
    (tmp_path / "run.p1.jsonl").write_text(
        _ev(110.0, "dispatch", name="cycle", wall_s=0.1, cold=True) + "\n")
    out = str(tmp_path / "m.jsonl")
    main(["telemetry-merge", str(tmp_path / "run.jsonl"), "--out", out,
          "--align", "collectives"])
    stdout = capsys.readouterr().out
    assert ">clock alignment (collectives): 1 matched anchor(s)" in stdout
    assert "+100.000000s" in stdout
    # no shared anchors: the mode degrades to raw-t ordering and says so
    (tmp_path / "run.p1.jsonl").write_text(
        _ev(110.0, "note", msg="no anchors here") + "\n")
    main(["telemetry-merge", str(tmp_path / "run.jsonl"), "--out", out,
          "--align", "collectives"])
    assert "no matched dispatch anchors" in capsys.readouterr().out


# ----------------------------------------------------------------------
# summary CLI: a truncated FINAL heartbeat still counts as the last one
# ----------------------------------------------------------------------

def test_summary_salvages_truncated_final_heartbeat(tmp_path, capsys):
    from pcg_mpi_solver_tpu.cli import main

    p = tmp_path / "run.jsonl"
    f = FlightRecorder(str(p), heartbeat_s=3600)
    f.begin("dispatch:cycle")
    f.close()
    # append the killed-process signature: a heartbeat cut mid-write with a
    # NEWER timestamp than any complete record
    with open(p, "a", encoding="utf-8") as fh:
        fh.write('{"schema": "%s", "t": 9e9, "kind": "flight", '
                 '"op": "heartbeat", "mono": 9e8, "hos'
                 % TELEMETRY_SCHEMA)
    v = flight_verdict_path(str(p))
    assert v["verdict"] == "died"               # the begin never closed
    assert v["salvaged_tail"] and v["last_wall"] == 9e9
    assert v["last_mono"] == 9e8
    main(["summary", str(p)])
    out = capsys.readouterr().out
    assert "[salvaged from the truncated final line]" in out
    assert "t=9000000000.000" in out
    # a complete final line must NOT claim salvage
    f2 = FlightRecorder(str(tmp_path / "ok.jsonl"), heartbeat_s=3600)
    with f2.record("dispatch:fine"):
        pass
    f2.close()
    v2 = flight_verdict_path(str(tmp_path / "ok.jsonl"))
    assert "salvaged_tail" not in v2
    assert salvage_truncated_tail(str(tmp_path / "ok.jsonl")) is None


# ----------------------------------------------------------------------
# faultinject: the ``sleep`` straggler simulator
# ----------------------------------------------------------------------

def test_fault_sleep_mode_boundary_semantics(monkeypatch):
    from pcg_mpi_solver_tpu.resilience.faultinject import FaultPlan

    monkeypatch.setenv("PCG_TPU_FAULT_SLEEP_S", "0.01")
    plan = FaultPlan("sleep@0,sleep@2*2")
    assert plan.sleep_s == pytest.approx(0.01)
    carry = {"r": None}
    t0 = time.monotonic()
    out = plan.at_boundary(dict(carry))       # boundary 0: fires
    assert out == carry                       # a delay, not a poison
    plan.at_boundary(dict(carry))             # boundary 1: no fault
    plan.at_boundary(dict(carry))             # boundary 2: fires
    plan.at_boundary(dict(carry))             # boundary 3: *2 consumed?
    assert time.monotonic() - t0 >= 0.02
    fired = [(f["mode"], f["point"], f["at"]) for f in plan.fired]
    # boundary indices advance per call, so each @idx fires at most once
    # per pass; the *count budget covers re-visits (a recovery replay)
    assert fired == [("sleep", "boundary", 0), ("sleep", "boundary", 2)]
    assert plan.armed                          # one firing of @2 left
    # recorder attribution: mode/point/at ride the fault event
    rec = _CapturingRecorder()
    plan3 = FaultPlan("sleep@0", recorder=rec)
    plan3.at_boundary(dict(carry))
    assert rec.events[0]["mode"] == "sleep"
    assert validate_event(rec.events[0]) == []
    # a typo'd duration env falls back to the default, never raises
    monkeypatch.setenv("PCG_TPU_FAULT_SLEEP_S", "oops")
    assert FaultPlan("sleep@0").sleep_s == pytest.approx(0.25)


def test_fault_sleep_parse_rejects_bad_domains():
    from pcg_mpi_solver_tpu.resilience.faultinject import FaultPlan

    # sleep is a boundary-domain mode: step/column triggers are refused
    with pytest.raises(ValueError):
        FaultPlan("sleep@s:1")
    with pytest.raises(ValueError):
        FaultPlan("sleep@col:0")


# ----------------------------------------------------------------------
# analysis: doc-schema sync rule
# ----------------------------------------------------------------------

def test_doc_schema_sync_seeded_violation():
    from pcg_mpi_solver_tpu.analysis.rules_artifacts import (
        check_doc_schema_sync, documented_event_kinds)

    doc = ("| kind | fields |\n"
           "| --- | --- |\n"
           "| `step` | step, flag |\n"
           "| `dispatch` | name |\n")
    assert documented_event_kinds(doc) == {"step", "dispatch"}
    errs = check_doc_schema_sync(doc, kinds=("step", "dispatch", "stall"))
    assert len(errs) == 1 and "`stall`" in errs[0]
    assert check_doc_schema_sync(doc, kinds=("step",)) == []


def test_doc_schema_sync_clean_on_current_tree():
    """Every kind in EVENT_KINDS has a row in OBSERVABILITY.md's event
    table — the rule the fast lint gate now enforces."""
    from pcg_mpi_solver_tpu.analysis.rules_artifacts import (
        EVENT_TABLE_DOC, check_doc_schema_sync)

    with open(os.path.join(REPO, EVENT_TABLE_DOC), encoding="utf-8") as f:
        assert check_doc_schema_sync(f.read()) == []
