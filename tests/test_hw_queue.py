"""Contract tests for the hardware-queue tooling (tools/hw_session.py,
tools/hw_v9_ab.py): the session-log format run_step writes is parsed by
the wave queues to make engage/skip decisions, so the coupling needs a
test.  Pure subprocess/log logic — no accelerator, no solver."""

import os
import sys
import textwrap

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_run_step_log_matches_ab_parser(tmp_path, monkeypatch):
    """run_step's start/done line format must stay parseable by
    tools/hw_v9_ab._parse_ab — the marker anchors on the START line and
    must not match the trailing 'done:' line."""
    from tools import hw_session
    from tools.hw_v9_ab import _parse_ab

    fake = tmp_path / "fake_ab.py"
    fake.write_text(textwrap.dedent("""\
        print("10328853 dofs on FakeDevice")
        print("xla (gse):      13.741 ms/matvec")
        print("pallas v9 C=8:    3.100 ms/matvec  (vs xla  4.43x, "
              "maxrelerr 1.2e-07)")
    """))
    log = tmp_path / "log.txt"
    monkeypatch.setattr(hw_session, "_last_step_ok", True)
    hw_session.run_step(str(log), "matvec A/B v9", [str(fake)],
                        timeout=60, gate_s=0)
    gse, v9 = _parse_ab(str(log), "=== matvec A/B v9: ")
    assert gse == 13.741 and v9 == 3.1

    # a failed variant yields None for v9 and the engage gate must stay
    # closed (tools/hw_v9_ab.maybe_engage_flagship's first branch)
    fake.write_text('print("xla (gse):      13.741 ms/matvec")\n'
                    'print("pallas v9 C=8: FAILED MosaicError: nope")\n')
    hw_session.run_step(str(log), "matvec A/B v9", [str(fake)],
                        timeout=60, gate_s=0)
    gse, v9 = _parse_ab(str(log), "=== matvec A/B v9: ")
    assert gse == 13.741 and v9 is None


def test_run_step_timeout_kills_group(tmp_path, monkeypatch):
    """A hung step must be killed at its timeout and logged as TIMEOUT,
    and the next step must see _last_step_ok False (the wedged-grant
    gate trigger)."""
    from tools import hw_session

    hang = tmp_path / "hang.py"
    hang.write_text("import time\ntime.sleep(60)\n")
    log = tmp_path / "log.txt"
    monkeypatch.setattr(hw_session, "_last_step_ok", True)
    hw_session.run_step(str(log), "hang step", [str(hang)],
                        timeout=2, gate_s=0)
    text = log.read_text()
    assert "TIMEOUT after 2s" in text
    assert hw_session._last_step_ok is False


def test_run_step_ok_rcs_verdict_exits(tmp_path, monkeypatch):
    """Steps whose nonzero exit is a VERDICT (cache_key_check rc=4 =
    determined MISMATCH) must not trip the next step's wedged-grant
    gate."""
    from tools import hw_session

    v = tmp_path / "verdict.py"
    v.write_text("import sys\nsys.exit(4)\n")
    log = tmp_path / "log.txt"
    monkeypatch.setattr(hw_session, "_last_step_ok", True)
    hw_session.run_step(str(log), "verdict step", [str(v)],
                        timeout=30, gate_s=0, ok_rcs=(0, 4))
    assert hw_session._last_step_ok is True
    hw_session.run_step(str(log), "verdict step strict", [str(v)],
                        timeout=30, gate_s=0)
    assert hw_session._last_step_ok is False


def test_priority_queue_step_order_has_pipelined_after_fused(tmp_path,
                                                             monkeypatch):
    """ISSUE 11: the priority preset's variant A/B must run classic ->
    fused -> pipelined as ADJACENT bench steps sharing one warm cache
    dir (three adjacent lines = the 3-way ms/iter A/B), with the lint
    gate still step 0 and Pallas v9 still in the queue.  Recorded by
    monkeypatching run_step — no accelerator, no subprocesses."""
    from tools import hw_session

    steps = []

    def fake_run_step(path, name, argv, env_extra=None, **kw):
        steps.append((name, dict(env_extra or {})))
        return "rc=0"

    monkeypatch.setattr(hw_session, "run_step", fake_run_step)
    hw_session.run_priority_queue(str(tmp_path / "log.txt"), quick=True)

    names = [n for n, _ in steps]
    assert names[0] == "contract lint (step 0)"
    # the overlap lint gates the pipelined leg (the fast lint can't:
    # psum-overlap is fast=False and --fast traces no pipelined
    # programs) — it runs on CPU, before any hardware step
    i_ov = names.index("overlap lint (step 0.2)")
    i_c = names.index("flagship classic")
    i_f = names.index("flagship fused")
    i_p = names.index("flagship pipelined")
    assert 0 < i_ov < i_c < i_f < i_p and i_p == i_f + 1, names
    env = dict(steps)
    assert env["overlap lint (step 0.2)"]["JAX_PLATFORMS"] == "cpu"
    assert env["flagship pipelined"]["BENCH_PCG_VARIANT"] == "pipelined"
    assert env["flagship fused"]["BENCH_PCG_VARIANT"] == "fused"
    assert "BENCH_PCG_VARIANT" not in env["flagship classic"]
    # the three variant legs share ONE warm cache dir (the A/B contract:
    # steps 2-3 reuse step 1's caches) and one pinned size
    for leg in ("flagship classic", "flagship fused", "flagship pipelined"):
        assert env[leg].get("BENCH_CACHE_DIR") == \
            env["flagship classic"]["BENCH_CACHE_DIR"]
        assert env[leg].get("BENCH_NX") == env["flagship classic"]["BENCH_NX"]
    # the rest of the queue survives the insertion
    assert any(n.startswith("mg A/B") for n in names)
    assert "matvec A/B v9" in names


def test_priority_queue_setup_ladder_after_lint_before_variants(
        tmp_path, monkeypatch):
    """ISSUE 14: the setup-ladder leg runs AFTER the lints (a broken
    structural claim aborts first), BEFORE the variant A/Bs, on CPU,
    sharing the warm cache dir, and writes the SETUP_LADDER.json
    artifact."""
    from tools import hw_session

    steps = []

    def fake_run_step(path, name, argv, env_extra=None, **kw):
        steps.append((name, dict(env_extra or {})))
        return "rc=0"

    monkeypatch.setattr(hw_session, "run_step", fake_run_step)
    hw_session.run_priority_queue(str(tmp_path / "log.txt"), quick=True)

    names = [n for n, _ in steps]
    i_lint = names.index("contract lint (step 0)")
    i_ladder = names.index("setup ladder")
    i_c = names.index("flagship classic")
    assert i_lint < i_ladder < i_c, names
    env = dict(steps)["setup ladder"]
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["BENCH_SETUP_LADDER"]
    assert env["BENCH_SETUP_OUT"].endswith("SETUP_LADDER.json")
    # shares the variant legs' warm cache dir (the A/B steps inherit
    # whatever the ladder already warmed)
    assert env["BENCH_CACHE_DIR"] == \
        dict(steps)["flagship classic"]["BENCH_CACHE_DIR"]


def test_priority_queue_serve_smoke_on_cpu_before_variants(
        tmp_path, monkeypatch):
    """ISSUE 19: the serve smoke (3 jobs through a live daemon, one
    ``exc@job:`` fault, 2 done + 1 failed with a named verdict) runs on
    CPU after the distributed-chaos smoke, before any hardware grant is
    spent on the flagship legs."""
    from tools import hw_session

    steps = []

    def fake_run_step(path, name, argv, env_extra=None, **kw):
        steps.append((name, dict(env_extra or {})))
        return "rc=0"

    monkeypatch.setattr(hw_session, "run_step", fake_run_step)
    hw_session.run_priority_queue(str(tmp_path / "log.txt"), quick=True)

    names = [n for n, _ in steps]
    i_chaos = names.index("distributed-chaos smoke")
    i_serve = names.index("serve smoke")
    i_c = names.index("flagship classic")
    assert i_chaos < i_serve < i_c, names
    assert dict(steps)["serve smoke"]["JAX_PLATFORMS"] == "cpu"


def test_priority_queue_aborts_on_lint_failure(tmp_path, monkeypatch):
    """A FAILED step-0 lint must abort before any hardware step — the
    pipelined leg's overlap claim is exactly what the lint proves, so
    measuring after a FAIL would benchmark a disproven claim."""
    from tools import hw_session

    steps = []

    def fake_run_step(path, name, argv, env_extra=None, **kw):
        steps.append(name)
        return "rc=1"

    monkeypatch.setattr(hw_session, "run_step", fake_run_step)
    hw_session.run_priority_queue(str(tmp_path / "log.txt"), quick=True)
    assert steps == ["contract lint (step 0)"]


def test_priority_queue_overlap_lint_failure_skips_pipelined_only(
        tmp_path, monkeypatch):
    """A FAILED step-0.2 overlap lint must skip ONLY the pipelined leg
    (its ms/iter number would measure a disproven latency-hiding claim)
    while the classic/fused/MG/nrhs/Pallas steps — none of which depend
    on the overlap claim — still run and use the window."""
    from tools import hw_session

    steps = []

    def fake_run_step(path, name, argv, env_extra=None, **kw):
        steps.append((name, list(argv)))
        if name == "overlap lint (step 0.2)":
            return "rc=1"
        return "rc=0"

    monkeypatch.setattr(hw_session, "run_step", fake_run_step)
    hw_session.run_priority_queue(str(tmp_path / "log.txt"), quick=True)

    names = [n for n, _ in steps]
    assert "flagship pipelined" not in names
    for kept in ("flagship classic", "flagship fused",
                 "mg A/B anchor (jacobi)", "matvec A/B v9"):
        assert any(n.startswith(kept) for n in names), (kept, names)
    # the step really invokes the psum-overlap rule alone (full tier)
    argv = dict(steps)["overlap lint (step 0.2)"]
    assert "--rules" in argv and "psum-overlap" in argv
    assert "--fast" not in argv
    log = (tmp_path / "log.txt").read_text()
    assert "SKIPPING the flagship pipelined leg" in log


def test_parse_ab_missing_marker_or_file_returns_none(tmp_path):
    """ADVICE r05 #3: a missing marker (step died before its section
    header) or an unreadable log must not raise out of _parse_ab — the
    remaining independent steps of a scarce hardware window continue,
    and maybe_engage_flagship's None gate keeps the engage closed."""
    from tools.hw_v9_ab import _parse_ab, maybe_engage_flagship

    log = tmp_path / "log.txt"
    log.write_text("window opened; step never started\n")
    assert _parse_ab(str(log), "=== matvec A/B v9: ") == (None, None)
    # the anomaly breadcrumb landed in the log instead of an exception
    assert "parse anomaly" in log.read_text()

    missing = tmp_path / "never_written.txt"
    assert _parse_ab(str(missing), "=== whatever: ") == (None, None)

    # downstream: no v9 number => no engaged flagship run (and no crash)
    assert maybe_engage_flagship(str(log), None, None) is False


# ----------------------------------------------------------------------
# flight records around queue steps (ISSUE 12, obs/flight.py): the
# crash-durable twin of the session log.
# ----------------------------------------------------------------------

def test_run_step_writes_flight_brackets_in_order(tmp_path, monkeypatch):
    """Every run_step is bracketed by begin/end flight records in the
    session log's .flight.jsonl twin — ordered, seq-matched, and
    verdict=clean after a clean queue."""
    from pcg_mpi_solver_tpu.obs.flight import (
        flight_verdict_path, read_jsonl_tolerant)
    from tools import hw_session

    ok = tmp_path / "ok.py"
    ok.write_text("print('fine')\n")
    log = tmp_path / "log.txt"
    monkeypatch.setattr(hw_session, "_last_step_ok", True)
    hw_session.run_step(str(log), "first step", [str(ok)],
                        timeout=60, gate_s=0)
    hw_session.run_step(str(log), "second step", [str(ok)],
                        timeout=60, gate_s=0)
    fpath = str(log) + ".flight.jsonl"
    assert os.path.exists(fpath)
    events, truncated = read_jsonl_tolerant(fpath)
    assert truncated == 0
    ops = [(e["op"], e.get("name")) for e in events
           if e["op"] != "heartbeat"]
    assert ops == [("meta", None),
                   ("begin", "step:first step"),
                   ("end", "step:first step"),
                   ("begin", "step:second step"),
                   ("end", "step:second step")]
    # the begin record is written BEFORE the subprocess result exists:
    # it must carry the argv for the post-mortem
    begins = [e for e in events if e["op"] == "begin"]
    assert begins[0]["argv"] == [str(ok)]
    assert flight_verdict_path(fpath)["verdict"] == "clean"


def test_run_step_failure_logs_flight_verdict(tmp_path, monkeypatch):
    """A failed step closes its bracket with op=fail AND prints the
    mechanical post-mortem pointer — flight-record path + verdict —
    into the session log itself."""
    from pcg_mpi_solver_tpu.obs.flight import flight_verdict_path
    from tools import hw_session

    bad = tmp_path / "bad.py"
    bad.write_text("import sys\nsys.exit(3)\n")
    log = tmp_path / "log.txt"
    monkeypatch.setattr(hw_session, "_last_step_ok", True)
    hw_session.run_step(str(log), "doomed step", [str(bad)],
                        timeout=60, gate_s=0)
    assert hw_session._last_step_ok is False
    fpath = str(log) + ".flight.jsonl"
    v = flight_verdict_path(fpath)
    assert v["verdict"] == "failed"
    assert any("doomed step" in f for f in v["fails"])
    text = log.read_text()
    assert f"flight record: {fpath} verdict=failed" in text
    # ...and an ok_rcs-listed verdict exit stays a CLEAN bracket (the
    # cache_key_check rc=4 MISMATCH is an answer, not a failure)
    log2 = tmp_path / "log2.txt"
    hw_session.run_step(str(log2), "verdict step", [str(bad)],
                        timeout=60, gate_s=0, ok_rcs=(0, 3))
    v2 = flight_verdict_path(str(log2) + ".flight.jsonl")
    assert v2["verdict"] == "clean", v2
    assert "flight record:" not in log2.read_text()


def test_stale_flight_artifact_rotated_not_inherited(tmp_path,
                                                     monkeypatch):
    """A leftover flight file from a DEAD previous session on the same
    log path is ingested (verdict logged) and rotated to .prev before
    this session records — otherwise this session's reused seq numbers
    would close the dead session's brackets (its death reads clean) and
    its stale unclosed brackets would poison this session's verdict."""
    from pcg_mpi_solver_tpu.obs.flight import (
        FlightRecorder, flight_verdict_path)
    from tools import hw_session

    log = tmp_path / "log.txt"
    fpath = str(log) + ".flight.jsonl"
    dead = FlightRecorder(fpath, heartbeat_s=30)
    dead.begin("step:killed by device loss")     # never closed
    dead.close()
    assert flight_verdict_path(fpath)["verdict"] == "died"

    ok = tmp_path / "ok.py"
    ok.write_text("print('fine')\n")
    monkeypatch.setattr(hw_session, "_last_step_ok", True)
    hw_session.run_step(str(log), "fresh step", [str(ok)],
                        timeout=60, gate_s=0)
    # the dead artifact moved aside intact; the fresh stream is clean
    prev = flight_verdict_path(fpath + ".prev")
    assert prev["verdict"] == "died"
    assert prev["in_flight"] == ["step:killed by device loss"]
    v = flight_verdict_path(fpath)
    assert v["verdict"] == "clean", v
    text = log.read_text()
    assert "verdict=died" in text
    assert "in flight at death: step:killed by device loss" in text


def test_run_step_survives_flight_recorder_trouble(tmp_path, monkeypatch):
    """Recorder trouble must never cost a hardware window a step:
    run_step logs the problem and runs the subprocess anyway."""
    from tools import hw_session

    def boom(path):
        raise OSError("read-only scratch")

    monkeypatch.setattr(hw_session, "_flight", boom)
    monkeypatch.setattr(hw_session, "_last_step_ok", True)
    ok = tmp_path / "ok.py"
    ok.write_text("print('fine')\n")
    log = tmp_path / "log.txt"
    hw_session.run_step(str(log), "unflighted step", [str(ok)],
                        timeout=60, gate_s=0)
    text = log.read_text()
    assert "flight recorder unavailable" in text
    assert "=== unflighted step done: rc=0" in text
    assert hw_session._last_step_ok is True


# ----------------------------------------------------------------------
# profiled flagship rung (ISSUE 15): ordering + verdict logging +
# failure tolerance
# ----------------------------------------------------------------------

def test_priority_queue_profiled_rung_after_variant_abs(tmp_path,
                                                        monkeypatch):
    """The BENCH_PROFILE=1 rung runs directly AFTER the variant A/Bs
    (classic -> fused -> pipelined) and BEFORE the MG A/B, on the same
    warm cache dir and size, profiling the pipelined variant when the
    overlap lint passed; the overlap + trend verdicts land in the
    session log right after the step."""
    from tools import hw_session

    steps = []

    def fake_run_step(path, name, argv, env_extra=None, **kw):
        steps.append((name, dict(env_extra or {})))
        return "rc=0"

    monkeypatch.setattr(hw_session, "run_step", fake_run_step)
    hw_session.run_priority_queue(str(tmp_path / "log.txt"), quick=True)

    names = [n for n, _ in steps]
    i_p = names.index("flagship pipelined")
    i_prof = names.index("profiled flagship")
    i_mg = names.index("mg A/B anchor (jacobi)")
    assert i_p < i_prof < i_mg, names
    env = dict(steps)["profiled flagship"]
    assert env["BENCH_PROFILE"] == "1"
    assert env["BENCH_PROFILE_DIR"]
    assert env["BENCH_PCG_VARIANT"] == "pipelined"
    assert env["BENCH_CACHE_DIR"] == \
        dict(steps)["flagship classic"]["BENCH_CACHE_DIR"]
    assert env["BENCH_NX"] == dict(steps)["flagship classic"]["BENCH_NX"]
    log = (tmp_path / "log.txt").read_text()
    # no artifact exists under the fake run_step: the verdicts still
    # logged (degraded overlap parse; the trend sentinel ran for real
    # over the committed BENCH_r*.json series)
    assert "overlap verdict" in log
    assert "trend verdict" in log


def test_priority_queue_profiled_rung_classic_when_overlap_fails(
        tmp_path, monkeypatch):
    """A FAILED overlap lint demotes the profiled rung to classic (a
    disproven latency-hiding claim must not be the profiled variant)
    but the rung itself still runs — the attribution table does not
    depend on the overlap claim."""
    from tools import hw_session

    steps = []

    def fake_run_step(path, name, argv, env_extra=None, **kw):
        steps.append((name, dict(env_extra or {})))
        if name == "overlap lint (step 0.2)":
            return "rc=1"
        return "rc=0"

    monkeypatch.setattr(hw_session, "run_step", fake_run_step)
    hw_session.run_priority_queue(str(tmp_path / "log.txt"), quick=True)
    env = dict(steps)["profiled flagship"]
    assert "BENCH_PCG_VARIANT" not in env       # classic default
    assert env["BENCH_PROFILE"] == "1"


def test_log_profile_verdicts_survives_broken_parse(tmp_path,
                                                    monkeypatch):
    """A broken trace parse (or a broken trend read) must not cost the
    step: log_profile_verdicts logs a named reason and returns."""
    from pcg_mpi_solver_tpu.obs import profview, trend
    from tools import hw_session

    def boom(*a, **k):
        raise ValueError("corrupt trace")

    monkeypatch.setattr(profview, "profile_report", boom)
    monkeypatch.setattr(trend, "trend_report", boom)
    log = tmp_path / "log.txt"
    prof = tmp_path / "prof"
    prof.mkdir()
    (prof / "x.trace.json").write_text("{}")    # artifact exists, parse dies
    hw_session.log_profile_verdicts(str(log), str(prof))
    text = log.read_text()
    assert "overlap verdict unavailable (ValueError: corrupt trace)" \
        in text
    assert "trend verdict unavailable" in text
    # ...and a STALE artifact (predating the step) is refused by name:
    # bench swallows capture failures, so an earlier round's trace must
    # not be logged as this round's measured verdict
    log2 = tmp_path / "log2.txt"
    hw_session.log_profile_verdicts(
        str(log2), str(prof),
        since=os.path.getmtime(str(prof / "x.trace.json")) + 60)
    assert "predates this step" in log2.read_text()


def test_log_profile_verdicts_reports_real_artifact(tmp_path,
                                                    monkeypatch):
    """With a real (synthetic) trace artifact on disk the overlap
    verdict line carries the parsed fraction, and a seeded fresh
    regression makes the trend line say REGRESSED."""
    import gzip
    import json as _json

    from tools import hw_session

    prof = tmp_path / "prof"
    prof.mkdir()
    evs = [{"ph": "X", "name": "all-reduce.0", "ts": 0, "dur": 10,
            "pid": 1, "tid": 1, "args": {"hlo_op": "all-reduce.0"}},
           {"ph": "X", "name": "dot.1", "ts": 0, "dur": 10, "pid": 1,
            "tid": 2, "args": {"hlo_op": "dot.1"}}]
    with gzip.open(str(prof / "x.trace.json.gz"), "wb") as f:
        f.write(_json.dumps({"traceEvents": evs}).encode())
    log = tmp_path / "log.txt"
    hw_session.log_profile_verdicts(str(log), str(prof))
    text = log.read_text()
    assert "overlap verdict: 1.000" in text
    assert "trend verdict:" in text
