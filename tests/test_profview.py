"""Profile harvest (ISSUE 15, obs/profview.py + obs/trend.py): the
capture -> parse -> attribute round trip on CPU, the tolerant-reader
degradation legs, the overlap interval math on synthetic timelines, and
the bench-trend regression sentinel over tests/data/bench_rounds/
artifacts."""

import glob
import gzip
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pcg_mpi_solver_tpu.config import RunConfig, SolverConfig  # noqa: E402
from pcg_mpi_solver_tpu.models.synthetic import make_cube_model  # noqa: E402
from pcg_mpi_solver_tpu.obs import profview, trend  # noqa: E402
from pcg_mpi_solver_tpu.obs.schema import validate_event  # noqa: E402
from pcg_mpi_solver_tpu.parallel.mesh import make_mesh  # noqa: E402
from pcg_mpi_solver_tpu.solver.driver import Solver  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _solver(nx=6, n_parts=1, variant="classic", max_iter=300):
    cfg = RunConfig(solver=SolverConfig(tol=1e-8, max_iter=max_iter,
                                        pcg_variant=variant))
    model = make_cube_model(nx, nx, nx, heterogeneous=True)
    return Solver(model, cfg, mesh=make_mesh(n_parts), n_parts=n_parts,
                  backend="general")


class _CapturingRecorder:
    """Minimal recorder double: records events/gauges for assertions."""

    def __init__(self):
        self.events = []
        self.gauges = {}

    def event(self, kind, **fields):
        ev = {"schema": "pcg-tpu-telemetry/1", "t": 0.0, "kind": kind}
        ev.update(fields)
        self.events.append(ev)
        return ev

    def gauge(self, name, value):
        self.gauges[name] = value


# ----------------------------------------------------------------------
# overlap interval math on synthetic timelines
# ----------------------------------------------------------------------

def test_merge_and_intersect_interval_math():
    merged = profview.merge_intervals([(5, 7), (0, 2), (1, 3), (9, 9)])
    assert merged == [(0, 3), (5, 7)]
    assert profview.intersect_len((0, 10), merged) == 5.0
    assert profview.intersect_len((3, 5), merged) == 0.0
    assert profview.intersect_len((2.5, 6), merged) == 1.5


def _op(name, ts, dur, pid=1, tid=1, text=""):
    return {"name": name, "base": profview._base_name(name), "ts": ts,
            "dur": dur, "pid": pid, "tid": tid, "text": text}


def test_overlap_disjoint_contained_partial_spans():
    # disjoint: the collective and all compute never coincide -> 0
    ops = [_op("all-reduce.0", 0, 10, tid=1),
           _op("dot.1", 20, 10, tid=2)]
    assert profview.collective_overlap(ops)["overlap_frac"] == 0.0
    # contained: compute fully covers the collective -> 1
    ops = [_op("all-reduce.0", 5, 10, tid=1),
           _op("dot.1", 0, 30, tid=2)]
    assert profview.collective_overlap(ops)["overlap_frac"] == 1.0
    # partial: half the collective span is covered -> 0.5
    ops = [_op("all-reduce.0", 0, 10, tid=1),
           _op("dot.1", 5, 20, tid=2)]
    r = profview.collective_overlap(ops)
    assert r["overlap_frac"] == pytest.approx(0.5)
    assert r["n_collectives"] == 1 and r["coll_us"] == 10.0


def test_overlap_excludes_same_thread_and_other_lanes():
    # same tid = serialized by construction (and a parent span would
    # fake 100%): contributes nothing
    ops = [_op("all-reduce.0", 0, 10, tid=1),
           _op("dot.1", 0, 10, tid=1)]
    assert profview.collective_overlap(ops)["overlap_frac"] == 0.0
    # a different pid is a different device lane: also excluded
    ops = [_op("all-reduce.0", 0, 10, pid=1, tid=1),
           _op("dot.1", 0, 10, pid=2, tid=2)]
    assert profview.collective_overlap(ops)["overlap_frac"] == 0.0
    # no collectives at all -> frac is None (n/a), not 0 (a
    # single-device capture must not read as "proven serialized")
    ops = [_op("dot.1", 0, 10)]
    assert profview.collective_overlap(ops)["overlap_frac"] is None


def test_overlap_merges_overlapping_compute_spans():
    # two compute spans covering the same window must not double-count
    ops = [_op("all-reduce.0", 0, 10, tid=1),
           _op("dot.1", 0, 8, tid=2),
           _op("fusion.2", 2, 6, tid=3)]
    r = profview.collective_overlap(ops)
    assert r["overlap_us"] == pytest.approx(8.0)


# ----------------------------------------------------------------------
# bucketing: labels, scope map, unknown counting
# ----------------------------------------------------------------------

def test_bucket_phases_via_text_labels_and_scope_map():
    scope_map = {"dot.7": "matvec", "reduce.3": "reduction"}
    ops = [
        # TPU flavor: the label rides the event metadata text
        _op("fusion.1", 0, 4, text="jit(f)/pcg/axpy/add"),
        # CPU flavor: bare instruction name through the sidecar map
        _op("dot.7", 0, 10),
        # base-name fallback (different lowering suffix)
        _op("reduce.9", 0, 2),
        # no phase anywhere -> other
        _op("copy.5", 0, 3),
    ]
    b = profview.bucket_phases(ops, scope_map)
    assert b["phases"]["axpy"]["us"] == 4.0
    assert b["phases"]["matvec"]["us"] == 10.0
    assert b["phases"]["reduction"]["us"] == 2.0
    assert b["other_us"] == 3.0 and b["other_events"] == 1
    # nothing dropped: bucketed + other == total
    total = sum(d["us"] for d in b["phases"].values()) + b["other_us"]
    assert total == pytest.approx(sum(o["dur"] for o in ops))


def test_bucket_phases_counts_unknown_scope_labels():
    ops = [_op("dot.1", 0, 5, text="jit(f)/pcg/halo/op"),
           _op("dot.2", 0, 5, text="jit(f)/pcg/halo/op2")]
    b = profview.bucket_phases(ops, {})
    assert b["unknown_scopes"] == {"halo": 2}
    assert b["other_events"] == 2          # counted, not dropped


def test_ambiguous_base_name_never_guesses():
    # two instructions share a base but bucket to different phases:
    # the base fallback must refuse, not pick one
    scope_map = {"fusion.1": "matvec", "fusion.2": "axpy"}
    bm = profview._base_scope_map(scope_map)
    assert bm["fusion"] is None
    assert profview.phase_of(_op("fusion.9", 0, 1), scope_map) is None


def test_scope_map_from_hlo_text():
    txt = '''
  %dot.0 = f32[8,8] dot(...), metadata={op_name="jit(f)/pcg/matvec/dot_general" source_file="x"}
  %add.2 = f32[8,8] add(...), metadata={op_name="jit(f)/pcg/axpy/add"}
  %mul.3 = f32[8,8] multiply(...), metadata={op_name="jit(f)/other/mul"}
'''
    m = profview.scope_map_from_hlo_text(txt)
    assert m == {"dot.0": "matvec", "add.2": "axpy"}


def test_scope_map_unlabeled_fusions_inherit_phase():
    """The installed XLA labels a fusion's fused computation, not the
    fusion line: the fusion takes its computation's agreed phase, and an
    unlabeled split (reduce-window) takes its labeled operand's; a
    computation of two phases, or loop control, maps to nothing."""
    txt = '''
%fused_computation.8 (p0: f64[1,8]) -> f64[1,8] {
  %mul.3 = f64[1,8] multiply(...), metadata={op_name="jit(f)/pcg/reduce/mul"}
}

%wrapped_rw_computation (p0: f64[1,8]) -> f64[1,1] {
  ROOT %reduce-window.2 = f64[1,1] reduce-window(%p0), window={size=1x8}
}

%mixed_computation (p0: f64[1,8]) -> f64[1,8] {
  %a = f64[1,8] add(...), metadata={op_name="jit(f)/pcg/axpy/add"}
  %b = f64[1,8] multiply(...), metadata={op_name="jit(f)/pcg/matvec/mul"}
}

ENTRY %main (p: f64[1,8]) -> f64[1,1] {
  %mm_fusion = f64[1,8] fusion(%gte.1, %gte.2), kind=kLoop, calls=%fused_computation.8
  %wrapped_reduce-window.6 = f64[1,1] fusion(%mm_fusion, %constant.21), kind=kLoop, calls=%wrapped_rw_computation
  %mixed_fusion = f64[1,8] fusion(%gte.3), kind=kLoop, calls=%mixed_computation
  %compare_and_fusion = pred[] fusion(%gte.4), kind=kLoop, calls=%cond_computation, metadata={op_name="jit(f)/while/cond/and"}
}
'''
    m = profview.scope_map_from_hlo_text(txt)
    assert m["mm_fusion"] == "reduction"
    assert m["wrapped_reduce-window.6"] == "reduction"
    assert "mixed_fusion" not in m and "compare_and_fusion" not in m


# ----------------------------------------------------------------------
# tolerant reader: gz + plain, truncated, missing lanes, missing file
# ----------------------------------------------------------------------

def _write_trace(path, events, gz=True):
    payload = json.dumps({"traceEvents": events}).encode()
    if gz:
        with gzip.open(path, "wb") as f:
            f.write(payload)
    else:
        with open(path, "wb") as f:
            f.write(payload)
    return path


def test_reader_gz_and_plain(tmp_path):
    evs = [{"ph": "X", "name": "dot.1", "ts": 0, "dur": 5,
            "pid": 1, "tid": 1, "args": {"hlo_op": "dot.1"}}]
    for fn, gz in (("a.trace.json.gz", True), ("b.trace.json", False)):
        p = _write_trace(str(tmp_path / fn), evs, gz=gz)
        got, probs = profview.read_trace_events(p)
        assert probs == [] and len(got) == 1
        assert len(profview.device_ops(got)) == 1


def test_reader_truncated_file_degrades_not_crashes(tmp_path):
    p = str(tmp_path / "t.trace.json.gz")
    _write_trace(p, [{"ph": "X", "name": "dot.1"}])
    with open(p, "rb") as f:
        blob = f.read()
    with open(p, "wb") as f:
        f.write(blob[: len(blob) // 2])     # the killed-process artifact
    evs, probs = profview.read_trace_events(p)
    assert evs == [] and probs, probs
    rep = profview.profile_report(p)
    assert rep["verdict"].startswith("degraded:")
    assert rep["phases"]["matvec"]["ms"] == 0.0
    # the report still validates as a prof_report event
    rec = _CapturingRecorder()
    profview.emit_prof_report(rec, rep)
    assert validate_event(rec.events[0]) == []


def test_reader_missing_device_lanes_named_verdict(tmp_path):
    # host-only events (no hlo args): parse succeeds, verdict names it
    p = _write_trace(str(tmp_path / "h.trace.json.gz"),
                     [{"ph": "X", "name": "$builtins len", "ts": 0,
                       "dur": 5, "pid": 1, "tid": 1}])
    rep = profview.profile_report(p)
    assert "device lanes" in rep["verdict"] or "device-op" in rep["verdict"]
    assert rep["verdict"].startswith("degraded:")


def test_reader_missing_artifact_named_verdict(tmp_path):
    rep = profview.profile_report(str(tmp_path / "nowhere"))
    assert rep["verdict"].startswith("degraded:")
    assert "no trace artifact" in rep["verdict"]


def test_container_ops_excluded_from_device_ops():
    evs = [{"ph": "X", "name": "call.3", "ts": 0, "dur": 50, "pid": 1,
            "tid": 1, "args": {"hlo_op": "call.3"}},
           {"ph": "X", "name": "while.1", "ts": 0, "dur": 50, "pid": 1,
            "tid": 1, "args": {"hlo_op": "while.1"}},
           {"ph": "X", "name": "dot.1", "ts": 0, "dur": 5, "pid": 1,
            "tid": 1, "args": {"hlo_op": "dot.1"}}]
    ops = profview.device_ops(evs)
    assert [o["name"] for o in ops] == ["dot.1"]


# ----------------------------------------------------------------------
# CPU end-to-end: capture -> parse -> attribute (classic + pipelined)
# ----------------------------------------------------------------------

def test_capture_parse_attribute_roundtrip_classic(tmp_path):
    s = _solver(nx=6, n_parts=1)
    rec = _CapturingRecorder()
    cap = profview.capture_solve_profile(s, str(tmp_path / "prof"),
                                         recorder=rec)
    # the sidecar makes the artifact self-describing offline
    assert cap["meta_path"] and os.path.exists(cap["meta_path"])
    meta = json.load(open(cap["meta_path"]))
    assert meta["schema"] == profview.PROFVIEW_META_SCHEMA
    assert meta["pcg_variant"] == "classic" and meta["iters"] >= 1
    assert len(meta["scope_map"]) > 0
    # the profile_capture event fired with the artifact path
    caps = [e for e in rec.events if e["kind"] == "profile_capture"]
    assert len(caps) == 1 and caps[0]["path"] == cap["artifact"]
    assert validate_event(caps[0]) == []

    rep = profview.profile_report(cap["artifact"])
    assert rep["verdict"] == "ok"
    # every phase attributed with real events and time
    for ph in ("matvec", "precond", "reduction", "axpy"):
        assert rep["phases"][ph]["events"] > 0, (ph, rep["phases"])
        assert rep["phases"][ph]["ms_per_iter"] > 0
    # acceptance: the per-phase attribution sums to within 20% of the
    # anchor iteration time the trace can attribute (the device-op
    # total; the wall anchor additionally carries the CPU runtime's
    # inter-thunk scheduling gap, reported separately as runtime gap)
    assert rep["device_attribution"] >= 0.8, rep
    assert rep["sum_ms_per_iter"] == pytest.approx(
        rep["device_ms_per_iter"], rel=0.2)
    # classic negative control: the measured overlap is ~0 (1 device:
    # the trivial collectives never hide behind concurrent compute)
    assert rep["overlap_frac"] in (None, 0.0) or rep["overlap_frac"] < 0.05
    # the prof_report event validates against obs/schema.py
    profview.emit_prof_report(rec, rep)
    ev = [e for e in rec.events if e["kind"] == "prof_report"][0]
    assert validate_event(ev) == []
    assert rec.gauges["prof.matvec_ms_per_iter"] > 0


def test_capture_parse_pipelined_overlap_computed(tmp_path):
    """The hardware twin of PR 10's static psum-overlap rule, chipless:
    the traced pipelined program's report COMPUTES an overlap fraction
    (collectives present, intersection measured).  On CPU the number
    itself may be small — forced-host virtual devices share one pid,
    and a 1-core box serializes everything — the contract here is the
    parse/bucket/reconcile pipeline; the fraction is the hardware
    window's to confirm (tools/hw_session.py logs it)."""
    s = _solver(nx=6, n_parts=2, variant="pipelined")
    cap = profview.capture_solve_profile(s, str(tmp_path / "prof"))
    rep = profview.profile_report(cap["artifact"])
    assert rep["verdict"] == "ok"
    assert rep["overlap"]["n_collectives"] > 0
    assert rep["overlap_frac"] is not None
    assert 0.0 <= rep["overlap_frac"] <= 1.0
    for ph in ("matvec", "precond", "reduction", "axpy"):
        assert rep["phases"][ph]["events"] > 0


def test_prof_report_cli_offline(tmp_path, capsys):
    from pcg_mpi_solver_tpu import cli

    s = _solver(nx=6, n_parts=1)
    cap = profview.capture_solve_profile(s, str(tmp_path / "prof"))
    out_jsonl = str(tmp_path / "prof.jsonl")
    cli.main(["prof-report", cap["artifact"],
              "--telemetry-out", out_jsonl])
    out = capsys.readouterr().out
    assert "matvec" in out and "verdict: ok" in out
    assert "predicted" in out        # rebuilt from the sidecar meta
    evs = [json.loads(l) for l in open(out_jsonl)]
    assert evs and evs[0]["kind"] == "prof_report"
    assert validate_event(evs[0]) == []
    # a missing artifact exits 2, after printing the degraded verdict
    with pytest.raises(SystemExit) as e:
        cli.main(["prof-report", str(tmp_path / "missing")])
    assert e.value.code == 2


def test_perf_report_cli_measured_column(tmp_path, capsys):
    """The extended perf-report: --profile-dir adds the trace-measured
    column next to predicted (cost model) and recorded (probes)."""
    from pcg_mpi_solver_tpu import cli

    cli.main(["perf-report", "--nx", "6", "--reps", "1", "--inner", "2",
              "--max-iter", "200",
              "--profile-dir", str(tmp_path / "prof")])
    out = capsys.readouterr().out
    assert "predicted" in out and "recorded" in out and "measured" in out
    assert "collective overlap" in out
    assert "verdict:" in out


# ----------------------------------------------------------------------
# capture respects the scope-map contract end to end
# ----------------------------------------------------------------------

def test_scope_map_from_solver_nonempty_all_variants():
    for variant in ("classic", "fused", "pipelined"):
        s = _solver(nx=4, n_parts=1, variant=variant)
        m = profview.scope_map_from_solver(s)
        phases = set(m.values())
        assert {"matvec", "reduction", "axpy"} <= phases, (variant, phases)


# ----------------------------------------------------------------------
# trend sentinel over round artifacts (synthetic fixtures)
# ----------------------------------------------------------------------

ROUNDS = os.path.join(REPO, "tests", "data", "bench_rounds")


def test_trend_parses_round_artifacts():
    arts = sorted(glob.glob(os.path.join(ROUNDS, "BENCH_r0*.json")))
    assert len(arts) == 4
    rep = trend.trend_report(arts)
    # r01 is a failed-round wrapper (rc != 0, parsed null): it
    # contributes zero lines but must parse without error
    by_label = {s["label"]: s["lines"] for s in rep["sources"]}
    assert by_label["BENCH_r01.json"] == 0
    assert by_label["BENCH_r02.json"] == 1
    assert by_label["BENCH_r04.json"] == 1
    # the pre-schema r02 line matches the schema r03 line of its shape
    # under the historical defaults; the 10.3M-dof r04 line is another
    # leg: matched pairs cannot be fabricated across shapes
    assert rep["regressed"] == 0
    assert rep["flat"] == 1 and rep["single"] == 1
    legs = {l["leg"] for l in rep["legs"]}
    assert any("10328853" in l for l in legs)


def test_trend_seeded_regression_exits_nonzero(tmp_path, capsys):
    arts = sorted(glob.glob(os.path.join(ROUNDS, "BENCH_r0*.json")))
    fresh_line = json.load(open(
        os.path.join(ROUNDS, "BENCH_r04.json")))["parsed"]
    fresh = dict(fresh_line, value=fresh_line["value"] * 0.5)
    fp = str(tmp_path / "fresh.json")
    json.dump(fresh, open(fp, "w"))
    rep = trend.trend_report(arts, fresh=fp)
    assert rep["regressed"] == 1
    reg = [l for l in rep["legs"] if l["verdict"] == "regressed"][0]
    assert reg["delta_pct"] == pytest.approx(-50.0)
    assert "REGRESSED" in trend.verdict_line(rep)
    # the CLI exit code reflects the regression
    from pcg_mpi_solver_tpu import cli

    with pytest.raises(SystemExit) as e:
        cli.main(["trend"] + arts + ["--fresh", fp])
    assert e.value.code == 1
    assert "REGRESSION" in capsys.readouterr().out


def test_trend_improved_and_flat_verdicts(tmp_path):
    base = {"metric": "m", "value": 100.0, "unit": "u",
            "vs_baseline": 1.0,
            "detail": {"model": "cube", "n_dof": 1000, "mode": "mixed",
                       "backend": "general"}}
    a, b = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    json.dump(base, open(a, "w"))
    json.dump(dict(base, value=130.0), open(b, "w"))
    rep = trend.trend_report([a, b])
    assert rep["improved"] == 1 and rep["regressed"] == 0
    json.dump(dict(base, value=103.0), open(b, "w"))
    rep = trend.trend_report([a, b])
    assert rep["flat"] == 1


def test_trend_matches_by_variant_precond_nrhs(tmp_path):
    """A fused leg must never compare against a classic leg of the same
    shape — the key includes variant/precond/nrhs; pre-schema lines
    (no fields) match under the historical defaults."""
    d = {"model": "cube", "n_dof": 1000, "mode": "mixed",
         "backend": "general"}
    base = {"metric": "m", "value": 100.0, "unit": "u",
            "vs_baseline": 1.0, "detail": d}
    a, b = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    json.dump(base, open(a, "w"))
    json.dump(dict(base, value=50.0,
                   detail=dict(d, pcg_variant="fused")), open(b, "w"))
    rep = trend.trend_report([a, b])
    assert rep["regressed"] == 0 and rep["single"] == 2
    # explicit classic/jacobi/nrhs=1 matches a pre-schema line
    json.dump(dict(base, value=50.0,
                   detail=dict(d, pcg_variant="classic",
                               precond="jacobi", nrhs=1)), open(b, "w"))
    rep = trend.trend_report([a, b])
    assert rep["regressed"] == 1


def test_trend_zero_value_sentinel_skipped(tmp_path):
    err = {"metric": "m", "value": 0.0, "unit": "u", "vs_baseline": 0.0,
           "detail": {"error": "no solve completed"}}
    p = str(tmp_path / "err.json")
    json.dump(err, open(p, "w"))
    assert trend.iter_bench_lines(p) == []


def test_trend_round_wrapper_tail_lines_deduped(tmp_path):
    """The committed round wrappers repeat the parsed line inside the
    tail — one leg, not two."""
    line = {"metric": "m", "value": 5.0, "unit": "u", "vs_baseline": 1.0,
            "detail": {"model": "cube", "n_dof": 10, "mode": "mixed",
                       "backend": "general"}}
    wrapper = {"n": 9, "cmd": "x", "rc": 0,
               "tail": "noise\n" + json.dumps(line) + "\n",
               "parsed": line}
    p = str(tmp_path / "w.json")
    json.dump(wrapper, open(p, "w"))
    assert len(trend.iter_bench_lines(p)) == 1


# ----------------------------------------------------------------------
# bench wiring (BENCH_PROFILE) + schema stamps
# ----------------------------------------------------------------------

def test_bench_profile_capture_stamps_detail(tmp_path, monkeypatch):
    """_capture_bench_profile returns the schema-typed detail fields on
    a live capture, {} when BENCH_PROFILE is off, and {} (with a log
    breadcrumb, never a raise) when the capture explodes."""
    from pcg_mpi_solver_tpu import bench
    from pcg_mpi_solver_tpu.obs.schema import BENCH_DETAIL_NUMERIC

    assert "measured_ms_per_iter_matvec" in BENCH_DETAIL_NUMERIC
    assert "overlap_frac" in BENCH_DETAIL_NUMERIC

    s = _solver(nx=5, n_parts=1, max_iter=150)
    monkeypatch.delenv("BENCH_PROFILE", raising=False)
    assert bench._capture_bench_profile(s, 1) == {}

    monkeypatch.setenv("BENCH_PROFILE", "1")
    monkeypatch.setenv("BENCH_PROFILE_DIR", str(tmp_path / "bp"))
    out = bench._capture_bench_profile(s, 1)
    assert out["measured_ms_per_iter_matvec"] > 0
    # single device: no collectives -> overlap_frac absent or a number
    if "overlap_frac" in out:
        assert 0.0 <= out["overlap_frac"] <= 1.0
    # the artifact is on disk for pcg-tpu prof-report
    assert profview.find_trace_files(str(tmp_path / "bp"))

    # a broken capture must not cost the bench its number
    def boom(*a, **k):
        raise OSError("disk full")

    monkeypatch.setattr(profview, "capture_solve_profile", boom)
    assert bench._capture_bench_profile(s, 1) == {}


def test_profile_capture_event_on_solve_path(tmp_path):
    """The driver's profile_dir bracket (the historical dynamics-path
    capture) now emits profile_capture with the artifact path, and the
    offline summary points at it."""
    from pcg_mpi_solver_tpu.config import TimeHistoryConfig
    from pcg_mpi_solver_tpu.obs.metrics import summarize_jsonl

    model = make_cube_model(3, 3, 3)
    cfg = RunConfig(
        scratch_path=str(tmp_path),
        profile_dir=str(tmp_path / "trace"),
        solver=SolverConfig(tol=1e-6, max_iter=100),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 1.0],
                                       export_flag=False),
    )
    cfg.telemetry_path = str(tmp_path / "run.jsonl")
    s = Solver(model, cfg, mesh=make_mesh(1), n_parts=1)
    s.solve()
    evs = [json.loads(l) for l in open(cfg.telemetry_path)]
    caps = [e for e in evs if e["kind"] == "profile_capture"]
    assert len(caps) == 1
    assert caps[0]["source"] == "solve"
    assert os.path.isdir(caps[0]["path"])
    assert validate_event(caps[0]) == []
    # the captured artifact parses (no sidecar on this path -> the
    # reader degrades by NAME, it does not crash)
    rep = profview.profile_report(caps[0]["path"])
    assert rep["n_device_ops"] > 0
    assert "summary" not in rep        # sanity: it's a report dict
    txt = summarize_jsonl(cfg.telemetry_path)
    assert "profile artifact:" in txt and "prof-report" in txt


# ----------------------------------------------------------------------
# review-hardening regressions (ISSUE 15 review pass)
# ----------------------------------------------------------------------

def test_trend_same_round_duplicates_cannot_shadow_regression(tmp_path):
    """A round whose artifact carries the final line NEXT TO an
    insurance near-duplicate (same leg, different value — dedup misses
    it) must still compare CROSS-round: the duplicate pair inside one
    round must not shadow a real cross-round regression."""
    d = {"model": "cube", "n_dof": 1000, "mode": "mixed",
         "backend": "general"}
    old = {"metric": "m", "value": 150.0, "unit": "u",
           "vs_baseline": 1.0, "detail": d}
    final = dict(old, value=98.0)
    insurance = dict(old, value=100.0)
    a, b = str(tmp_path / "r1.json"), str(tmp_path / "r2.json")
    json.dump(old, open(a, "w"))
    json.dump({"n": 2, "cmd": "x", "rc": 0,
               "tail": json.dumps(insurance) + "\n", "parsed": final},
              open(b, "w"))
    rep = trend.trend_report([a, b])
    reg = [l for l in rep["legs"] if l["verdict"] == "regressed"]
    assert len(reg) == 1, rep["legs"]
    # compared against the round's BEST value (100), across rounds
    assert reg[0]["old_value"] == 150.0
    assert reg[0]["new_value"] == 100.0


def test_trend_failed_round_tail_contributes_nothing(tmp_path):
    """rc != 0 wrapper: provisional/insurance lines stranded in its
    tail are not that round's measurement — they must not become the
    leg's newest value."""
    d = {"model": "cube", "n_dof": 1000, "mode": "mixed",
         "backend": "general"}
    line = {"metric": "m", "value": 50.0, "unit": "u",
            "vs_baseline": 1.0, "detail": d}
    p = str(tmp_path / "dead.json")
    json.dump({"n": 3, "cmd": "x", "rc": 124,
               "tail": json.dumps(line) + "\n", "parsed": None},
              open(p, "w"))
    assert trend.iter_bench_lines(p) == []


def test_trend_exit_2_when_no_bench_lines(tmp_path, capsys):
    p = str(tmp_path / "empty.json")
    json.dump({"n": 1, "cmd": "x", "rc": 1, "tail": "", "parsed": None},
              open(p, "w"))
    assert trend.main_cli([p]) == 2
    assert "nothing to compare" in capsys.readouterr().out
    rep = trend.trend_report([p])
    assert "no matched legs" in trend.verdict_line(rep)


def test_format_report_zero_duration_collectives_no_crash(tmp_path):
    """Collective ops with zero total duration (bare async markers):
    overlap_frac is None while n_collectives > 0 — format_report must
    render n/a, not crash on None formatting."""
    p = _write_trace(str(tmp_path / "z.trace.json.gz"),
                     [{"ph": "X", "name": "all-reduce.0", "ts": 0,
                       "dur": 0, "pid": 1, "tid": 1,
                       "args": {"hlo_op": "all-reduce.0"}},
                      {"ph": "X", "name": "dot.1", "ts": 0, "dur": 5,
                       "pid": 1, "tid": 2,
                       "args": {"hlo_op": "dot.1"}}])
    rep = profview.profile_report(p)
    assert rep["overlap_frac"] is None
    assert rep["overlap"]["n_collectives"] == 1
    txt = profview.format_report(rep)
    assert "zero duration" in txt


def test_sidecar_unknown_scope_label_counted():
    """The scope-labels loudness contract on the CPU sidecar path: a
    pcg/<x> label outside the known phases arriving via the compiled
    HLO scope map is counted into unknown_scopes, not silently folded
    into 'other' anonymously."""
    smap = profview.scope_map_from_hlo_text(
        '%ghost.1 = f32[2]{0} add(...), '
        'metadata={op_name="jit(f)/pcg/halo/add"}\n'
        '%dot.1 = f32[2]{0} dot(...), '
        'metadata={op_name="jit(f)/pcg/matvec/dot_general"}')
    assert smap == {"ghost.1": "?halo", "dot.1": "matvec"}
    b = profview.bucket_phases(
        [_op("ghost.1", 0, 5), _op("ghost.2", 10, 5), _op("dot.1", 0, 7)],
        smap)
    assert b["unknown_scopes"] == {"halo": 2}     # exact + base-name hit
    assert b["phases"]["matvec"]["us"] == 7.0
    assert b["other_events"] == 2                 # counted, not dropped
