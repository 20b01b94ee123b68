"""Unit coverage for bench.py's resilience machinery — the code that
stands between the round's one driver-captured perf artifact and
infrastructure weather.  Pure logic tests (no solver, no accelerator)."""

import os
from unittest import mock

import pytest

from pcg_mpi_solver_tpu import bench


def _clear_bench_env(monkeypatch):
    for k in list(os.environ):
        if k.startswith("BENCH_") or k.startswith("PCG_TPU_"):
            monkeypatch.delenv(k, raising=False)


def test_ladder_cube_default(monkeypatch):
    _clear_bench_env(monkeypatch)
    assert bench._ladder("cube", False) == [
        (150, 150, 150, 0, 0), (128, 128, 128, 0, 0), (96, 96, 96, 0, 0)]


def test_ladder_explicit_pin_wins(monkeypatch):
    _clear_bench_env(monkeypatch)
    monkeypatch.setenv("BENCH_LADDER", "100,50")
    monkeypatch.setenv("BENCH_NX", "64")
    assert bench._ladder("cube", False) == [(64, 64, 64, 0, 0)]


def test_ladder_tolerates_sloppy_spec(monkeypatch):
    """A trailing comma or spaces must not crash the artifact run."""
    _clear_bench_env(monkeypatch)
    monkeypatch.setenv("BENCH_LADDER", " 100 , 50 , ")
    assert bench._ladder("cube", False) == [
        (100, 100, 100, 0, 0), (50, 50, 50, 0, 0)]
    monkeypatch.setenv("BENCH_LADDER", ",,")
    with pytest.raises(ValueError, match="no sizes"):
        bench._ladder("cube", False)


def test_ladder_octree_pin_beats_ladder(monkeypatch):
    _clear_bench_env(monkeypatch)
    monkeypatch.setenv("BENCH_OT_LADDER", "14,8")
    monkeypatch.setenv("BENCH_OT_N", "10")
    monkeypatch.setenv("BENCH_OT_LEVEL", "3")
    assert bench._ladder("octree", False) == [(0, 0, 0, 10, 3)]


def test_ladder_cpu_fallback_is_small(monkeypatch):
    """CPU fallback must ignore flagship-size envs (a 150^3 CPU solve
    would blow the driver's wall budget — the exact failure the
    fallback exists to avoid)."""
    _clear_bench_env(monkeypatch)
    monkeypatch.setenv("BENCH_NX", "150")
    monkeypatch.setenv("BENCH_NY", "150")
    monkeypatch.setenv("BENCH_NZ", "150")
    assert bench._ladder("cube", True) == [(48, 48, 48, 0, 0)]
    monkeypatch.setenv("BENCH_OT_N", "22")
    assert bench._ladder("octree", True) == [(0, 0, 0, 6, 4)]


def test_matvec_form_pinned_on_stencil_ops(monkeypatch):
    """The form attribute lives on the stencil ops (pinned at their
    construction); the general Ops never carries one — the single rule
    bench reporting and checkpoint fingerprints both read."""
    _clear_bench_env(monkeypatch)
    from pcg_mpi_solver_tpu.ops.matvec import Ops
    from pcg_mpi_solver_tpu.parallel.hybrid import HybridOps
    from pcg_mpi_solver_tpu.parallel.structured import StructuredOps

    import dataclasses

    import pytest as _pytest

    from pcg_mpi_solver_tpu.models import make_cube_model
    from pcg_mpi_solver_tpu.parallel.structured import partition_structured

    base = dict(n_loc=3, n_iface=0)
    sp = partition_structured(make_cube_model(4, 2, 2), 1)
    # env fallback: the knob is resolved at construction...
    monkeypatch.setenv("PCG_TPU_MATVEC_FORM", "corner")
    ops = StructuredOps.from_partition(sp)
    assert ops.form == "corner"
    # ...and pinned: a later env flip does not move it
    monkeypatch.setenv("PCG_TPU_MATVEC_FORM", "gse")
    assert ops.form == "corner"
    # explicit pin beats the env
    assert StructuredOps.from_partition(sp, form="gse").form == "gse"
    assert HybridOps(**base, form="gse").form == "gse"
    # the general Ops never carries a form
    assert getattr(Ops(**base), "form", "n/a") == "n/a"
    # typo'd pins are rejected, not silently run as gse
    with _pytest.raises(ValueError, match="form"):
        StructuredOps.from_partition(sp, form="Corner")
    with _pytest.raises(ValueError, match="form"):
        dataclasses.replace(ops, form="croner")


def test_probe_retry_waits_out_timeouts(monkeypatch):
    """Transient init timeouts are retried across the budget (one 180 s
    attempt would lose the round's artifact)."""
    _clear_bench_env(monkeypatch)
    monkeypatch.setenv("BENCH_PROBE_BUDGET_S", "10")
    calls = {"n": 0}

    def fake_probe(timeout_s=180.0):
        calls["n"] += 1
        if calls["n"] < 3:
            return False, "backend init did not complete within 180s"
        return True, "ok"

    with mock.patch("pcg_mpi_solver_tpu.utils.backend_probe.probe_backend",
                    fake_probe), \
            mock.patch("time.sleep", lambda s: None):
        ok, detail = bench._probe_with_retry()
    assert ok and calls["n"] == 3


def test_probe_retry_two_strikes_on_deterministic_failure(monkeypatch):
    """A missing/broken plugin must NOT burn the 45-minute budget."""
    _clear_bench_env(monkeypatch)
    monkeypatch.setenv("BENCH_PROBE_BUDGET_S", "10000")
    calls = {"n": 0}

    def fake_probe(timeout_s=180.0):
        calls["n"] += 1
        return False, ("backend init failed (rc=1):\n"
                       "ModuleNotFoundError: No module named 'libtpu'")

    with mock.patch("pcg_mpi_solver_tpu.utils.backend_probe.probe_backend",
                    fake_probe), \
            mock.patch("time.sleep", lambda s: None):
        ok, _ = bench._probe_with_retry()
    assert not ok and calls["n"] == 2


def test_result_json_marks_unconverged(monkeypatch):
    """time_to_tol_s must be null when the emitted solve has flag != 0."""
    import json
    import types

    model = types.SimpleNamespace(n_dof=1000)
    r1 = types.SimpleNamespace(flag=1, relres=1e-3, wall_s=2.0)
    line = bench._result_json(model, "cube", r1, 50, 235.0, "note", {})
    d = json.loads(line)
    assert d["detail"]["time_to_tol_s"] is None
    assert d["detail"]["solve_wall_s"] == 2.0
    r0 = types.SimpleNamespace(flag=0, relres=1e-8, wall_s=2.0)
    d0 = json.loads(bench._result_json(model, "cube", r0, 50, 235.0, "n", {}))
    assert d0["detail"]["time_to_tol_s"] == 2.0


def test_ladder_provisional_is_tiny_and_env_proof(monkeypatch):
    """The provisional rung must ignore flagship envs (its whole job is
    landing a line in minutes) and honor only BENCH_PROV_NX."""
    _clear_bench_env(monkeypatch)
    monkeypatch.setenv("BENCH_NX", "150")
    monkeypatch.setenv("BENCH_LADDER", "150,128")
    assert bench._ladder("cube", True, provisional=True) == [
        (24, 24, 24, 0, 0)]
    # even for an octree bench request the provisional stays a cube
    # ladder shape (main() forces BENCH_MODEL=cube for the subprocess)
    monkeypatch.setenv("BENCH_PROV_NX", "16")
    assert bench._ladder("cube", True, provisional=True) == [
        (16, 16, 16, 0, 0)]


def test_emitter_exactly_once(capsys):
    """Watchdog and main flow race to emit; exactly one line may win."""
    em = bench._Emitter("initial")
    em.offer("better")
    assert em.emit() is True          # prints the best offered line
    assert em.emit("late") is False   # second emit is refused
    out = capsys.readouterr().out
    assert out == "better\n"


def test_emitter_offer_after_emit_is_noop(capsys):
    em = bench._Emitter("a")
    assert em.emit("final")
    em.offer("late-offer")
    assert em.best == "a"          # a late offer must not mutate state
    assert capsys.readouterr().out == "final\n"


def test_emitter_rank_priority(capsys):
    """A provisional (rank 1) offer must never displace an accelerator
    (rank 2) line — the watchdog races the live-baseline upgrade and the
    TPU measurement has to win (r04 review finding)."""
    em = bench._Emitter("sentinel")
    em.offer("tpu-line", rank=2)
    em.offer("provisional", rank=1)   # late watchdog offer
    assert em.emit() is True
    assert capsys.readouterr().out == "tpu-line\n"
    # equal rank upgrades in place (measured-live replaces const)
    em2 = bench._Emitter("sentinel")
    em2.offer("const", rank=2)
    em2.offer("live", rank=2)
    assert em2.best == "live"


def test_error_line_is_parseable_sentinel():
    import json

    d = json.loads(bench._error_line("boom"))
    assert d["value"] == 0.0 and d["vs_baseline"] == 0.0
    assert "boom" in d["detail"]["error"]
    assert d["metric"] == "pcg_dof_iterations_per_second"


def test_sweep_stale_tmps(tmp_path):
    """Orphaned .tmp files older than an hour are removed on the read
    path; fresh ones (a concurrent writer) are left alone."""
    import os
    import time

    d = str(tmp_path)
    old = os.path.join(d, "model_dead.tmp")
    fresh = os.path.join(d, "model_live.tmp")
    for p in (old, fresh):
        with open(p, "wb") as f:
            f.write(b"x")
    os.utime(old, (time.time() - 7200,) * 2)
    bench._sweep_stale_tmps(d)
    assert sorted(os.listdir(d)) == ["model_live.tmp"]


class _FakeProv:
    def __init__(self, line='{"metric": "m", "value": 1.0}'):
        self._line = line
        self.killed = False

    def line(self, timeout_s=0.0):
        return self._line

    def kill(self):
        self.killed = True


_OFF = object()     # sentinel: no CPU-upgrade leg in this scenario


def _orchestrate(monkeypatch, capsys, probe_ok, run_result, tmp_path,
                 prov_line='{"metric": "m", "value": 1.0}', upgrade=_OFF):
    """Drive bench.main()'s orchestrator with the heavy pieces mocked.
    ``upgrade``: omitted disables the CPU-upgrade leg; otherwise the
    line (or None) the mocked upgrade subprocess yields."""
    _clear_bench_env(monkeypatch)
    monkeypatch.chdir(tmp_path)      # bench writes provisional files in cwd
    monkeypatch.setenv("BENCH_WALL_BUDGET_S", "3600")
    if upgrade is _OFF:
        monkeypatch.setenv("BENCH_CPU_UPGRADE", "0")
    prov = _FakeProv(prov_line)

    def fake_runs(env_extra=None, logname=None, provisional=True):
        return prov if provisional else _FakeProv(upgrade)

    monkeypatch.setattr(bench, "_ProvisionalRun", fake_runs)
    monkeypatch.setattr(bench, "_probe_with_retry",
                        lambda budget_s=None: (probe_ok, "mock"))
    if isinstance(run_result, Exception):
        def run(**kw):
            raise run_result
    else:
        def run(**kw):
            return run_result
    monkeypatch.setattr(
        bench, "_run_bench",
        lambda cpu_fallback, provisional=False, deadline=None, emitter=None:
        run())
    bench.main()
    return prov, capsys.readouterr().out.strip().splitlines()


def test_orchestrator_tpu_success(monkeypatch, capsys, tmp_path):
    """Probe ok + accelerator bench succeeds: ITS line is the one line on
    stdout; the provisional subprocess is reaped."""
    prov, out = _orchestrate(monkeypatch, capsys, True, '{"tpu": 1}',
                             tmp_path)
    assert out == ['{"tpu": 1}']
    assert prov.killed


def test_orchestrator_probe_dead_emits_provisional(monkeypatch, capsys,
                                                   tmp_path):
    prov, out = _orchestrate(monkeypatch, capsys, False, '{"tpu": 1}',
                             tmp_path)
    assert out == ['{"metric": "m", "value": 1.0}']


def test_orchestrator_bench_crash_emits_provisional(monkeypatch, capsys,
                                                    tmp_path):
    """Accelerator path dies AFTER a good probe (device loss mid-solve):
    the provisional line still lands, exit stays clean."""
    prov, out = _orchestrate(monkeypatch, capsys, True,
                             RuntimeError("device lost"), tmp_path)
    assert out == ['{"metric": "m", "value": 1.0}']


def test_orchestrator_everything_dead_emits_sentinel(monkeypatch, capsys,
                                                     tmp_path):
    """No provisional AND no accelerator: the labeled zero-value sentinel
    is still exactly one parseable line."""
    import json

    prov, out = _orchestrate(monkeypatch, capsys, False,
                             '{"tpu": 1}', tmp_path, prov_line=None)
    assert len(out) == 1
    d = json.loads(out[0])
    assert d["value"] == 0.0 and "error" in d["detail"]


def _tpu_line(v=20.0, value=7e8):
    import json

    return json.dumps({"metric": "pcg_dof_iterations_per_second",
                       "value": value, "unit": "dof*iter/s",
                       "vs_baseline": v,
                       "detail": {"platform": "tpu", "n_dof": 10328853}})


def test_salvage_roundtrip_and_relabeling(monkeypatch, tmp_path):
    """A live accelerator line written by one invocation is readable by a
    later one, re-labeled so it cannot pass as a live measurement; the
    best (by vs_baseline) fresh entry wins."""
    import json

    _clear_bench_env(monkeypatch)
    monkeypatch.chdir(tmp_path)
    bench._write_salvage(_tpu_line(v=5.0))
    bench._write_salvage(_tpu_line(v=21.9))
    bench._write_salvage(_tpu_line(v=12.0))
    got = json.loads(bench._read_salvage())
    assert got["vs_baseline"] == 21.9
    det = got["detail"]
    assert det["salvaged_from_earlier_session"] is True
    assert det["salvage_age_s"] >= 0 and "not measured live" \
        in det["salvage_note"]


def test_salvage_rejects_cpu_and_stale_lines(monkeypatch, tmp_path):
    """CPU fallback/provisional lines never enter the salvage file; aged
    entries and a disabled knob read as absent."""
    import json
    import time

    _clear_bench_env(monkeypatch)
    monkeypatch.chdir(tmp_path)
    cpu = json.dumps({"metric": "m", "value": 4e7, "vs_baseline": 1.18,
                      "detail": {"platform": "cpu (CPU PROVISIONAL)"}})
    bench._write_salvage(cpu)
    assert not (tmp_path / "bench_salvage.json").exists()
    bench._write_salvage(_tpu_line())
    assert bench._read_salvage() is not None
    monkeypatch.setenv("BENCH_SALVAGE", "0")     # hardware-queue posture
    assert bench._read_salvage() is None
    monkeypatch.delenv("BENCH_SALVAGE")
    # age out: rewrite the file with an old timestamp
    p = tmp_path / "bench_salvage.json"
    data = json.loads(p.read_text())
    data["lines"][0]["unix_time"] = time.time() - 100000
    p.write_text(json.dumps(data))
    assert bench._read_salvage() is None


def test_salvage_prefers_matching_config(monkeypatch, tmp_path):
    """A config-matching entry beats a higher-vs_baseline entry from a
    different benchmark config; with no match the best any-config line
    still salvages (self-describing beats CPU)."""
    import json

    _clear_bench_env(monkeypatch)
    monkeypatch.chdir(tmp_path)
    cube = json.dumps({"metric": "m", "value": 5e8, "vs_baseline": 10.0,
                       "detail": {"platform": "tpu", "model": "cube",
                                  "mode": "mixed", "dtype": "float32"}})
    octree = json.dumps({"metric": "m", "value": 7e8, "vs_baseline": 21.0,
                         "detail": {"platform": "tpu", "model": "octree",
                                    "mode": "mixed", "dtype": "float32"}})
    bench._write_salvage(cube)
    bench._write_salvage(octree)
    assert json.loads(bench._read_salvage())["vs_baseline"] == 10.0
    monkeypatch.setenv("BENCH_MODEL", "octree")
    assert json.loads(bench._read_salvage())["vs_baseline"] == 21.0
    monkeypatch.setenv("BENCH_MODEL", "sphere")    # no match at all
    assert json.loads(bench._read_salvage())["vs_baseline"] == 21.0


def test_orchestrator_probe_dead_salvage_beats_cpu(monkeypatch, capsys,
                                                   tmp_path):
    """No device + a fresh salvage line: the salvaged TPU number is the
    round artifact (clearly re-labeled), not the CPU provisional, and the
    CPU upgrade leg is skipped entirely."""
    import json

    monkeypatch.chdir(tmp_path)
    bench._write_salvage(_tpu_line(v=21.9))
    prov, out = _orchestrate(monkeypatch, capsys, False, '{"tpu": 1}',
                             tmp_path, upgrade='{"metric": "up"}')
    d = json.loads(out[0])
    assert d["vs_baseline"] == 21.9
    assert d["detail"]["salvaged_from_earlier_session"] is True


def test_orchestrator_probe_dead_upgrade_beats_provisional(
        monkeypatch, capsys, tmp_path):
    """No device, no salvage: the mid-size CPU upgrade line outranks
    the tiny provisional."""
    prov, out = _orchestrate(
        monkeypatch, capsys, False, '{"tpu": 1}', tmp_path,
        upgrade='{"metric": "upgraded", "value": 5.0}')
    assert out == ['{"metric": "upgraded", "value": 5.0}']


def test_orchestrator_probe_dead_upgrade_fails_keeps_provisional(
        monkeypatch, capsys, tmp_path):
    """Upgrade subprocess dies without a line: the provisional still
    lands (the liveness floor never regresses)."""
    prov, out = _orchestrate(monkeypatch, capsys, False, '{"tpu": 1}',
                             tmp_path, upgrade=None)
    assert out == ['{"metric": "m", "value": 1.0}']


def test_orchestrator_success_writes_salvage(monkeypatch, capsys,
                                             tmp_path):
    """A successful accelerator run records its line for later
    invocations; CPU-labeled lines are never recorded."""
    import json

    line = _tpu_line(v=20.5)
    prov, out = _orchestrate(monkeypatch, capsys, True, line, tmp_path)
    assert out == [line]
    data = json.loads((tmp_path / "bench_salvage.json").read_text())
    assert json.loads(data["lines"][0]["line"])["vs_baseline"] == 20.5


def test_model_cache_eviction(tmp_path):
    """LRU eviction keeps the cache under the cap, never deletes the
    just-written entry, and evicts oldest-mtime first."""
    import os
    import time

    from pcg_mpi_solver_tpu.bench import _evict_model_cache

    d = str(tmp_path)
    for i, sz in enumerate([100, 200, 300]):
        p = os.path.join(d, f"model_{i}.pkl")
        with open(p, "wb") as f:
            f.write(b"x" * sz)
        os.utime(p, (time.time() - 100 + i,) * 2)
    keep = os.path.join(d, "model_2.pkl")
    _evict_model_cache(d, keep=keep, cap_bytes=550)
    assert sorted(os.listdir(d)) == ["model_1.pkl", "model_2.pkl"]
    _evict_model_cache(d, keep=keep, cap_bytes=50)
    assert sorted(os.listdir(d)) == ["model_2.pkl"]


def _live_line(value=1.0):
    import json

    return json.dumps({"metric": "m", "value": value, "unit": "u",
                       "vs_baseline": 1.0,
                       "detail": {"platform": "tpu"}})


def test_offer_rank4_persists_salvage_immediately(monkeypatch, tmp_path):
    """A LIVE accelerator line must hit bench_salvage.json the moment it
    is offered: on 2026-08-01 the watchdog's os._exit(0) fired 2 s
    before the flagship step ended, emitting the TPU line to stdout but
    racing out main's end-of-run _write_salvage."""
    import json

    from pcg_mpi_solver_tpu import bench as b

    monkeypatch.chdir(tmp_path)
    em = b._Emitter("init")
    em.offer(_live_line(), rank=4)
    data = json.load(open(b._SALVAGE_PATH))
    assert len(data["lines"]) == 1


def test_emit_persists_salvage_and_dedups(monkeypatch, tmp_path):
    import json

    from pcg_mpi_solver_tpu import bench as b

    monkeypatch.chdir(tmp_path)
    em = b._Emitter("init")
    ln = _live_line(2.0)
    em.offer(ln, rank=4)        # first write
    assert em.emit(ln) is True  # emit-side write must dedup, not append
    data = json.load(open(b._SALVAGE_PATH))
    assert len(data["lines"]) == 1
    # a CPU-labeled line must never be persisted
    em2 = b._Emitter("init")
    em2.emit(json.dumps({"metric": "m", "value": 1.0, "unit": "u",
                         "vs_baseline": 0.1,
                         "detail": {"platform": "cpu (fallback)"}}))
    data = json.load(open(b._SALVAGE_PATH))
    assert len(data["lines"]) == 1


def test_warm_solve_offers_rank4_line(monkeypatch, tmp_path):
    """A converged warm solve on an accelerator must offer (and thus
    persist) a rank-4 line BEFORE the timed solve runs: on 2026-08-01
    the device died mid-timed-solve two minutes after a completed warm
    solve and the round artifact fell back to a CPU provisional."""
    import json

    from pcg_mpi_solver_tpu import bench as b

    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(b, "_accel_platform", lambda: "tpu")
    offers = []

    class Em:
        def offer(self, line, rank=1):
            offers.append((rank, line))

    b._solve_once("cube", 4, 4, 4, 0, 0, "auto", 8, 1e-7,
                  "mixed", "float32", emitter=Em())
    warm = [ln for r, ln in offers if r == 4]
    assert warm, "no rank-4 warm line offered"
    d = json.loads(warm[0])
    assert d["detail"]["flag"] == 0
    assert d["detail"]["timing"].startswith("warm")
    assert d["detail"]["platform"] == "tpu"
    # offer(rank=4) persists to salvage via the emitter-independent path
    # only when called through _Emitter; the fake Em does not — persist
    # here happens when bench's real _Emitter is used (covered by
    # test_offer_rank4_persists_salvage_immediately)


def test_failed_timed_solve_offers_salvage_line(monkeypatch, tmp_path):
    """A solver exception mid-measurement (the r05 device death) writes
    a salvage line carrying failed=true + the reason at accelerator
    rank, so the round artifact records both the warm number and WHY the
    timed leg is missing — instead of aborting with nothing."""
    import json
    import types

    from pcg_mpi_solver_tpu import bench as b

    monkeypatch.chdir(tmp_path)
    offers = []

    class Em:
        def offer(self, line, rank=1):
            offers.append((rank, line))

    model = types.SimpleNamespace(n_dof=10_328_853)
    r0 = types.SimpleNamespace(flag=0, relres=3.2e-8, wall_s=83.3,
                               iters=3334)
    extra = {"platform": "tpu", "mode": "mixed", "dtype": "float32"}
    line = b._offer_failed_salvage(Em(), model, "cube", r0, dict(extra),
                                   "timed solve died: XlaRuntimeError: "
                                   "UNAVAILABLE: socket closed")
    assert offers and offers[0][0] == 4
    d = json.loads(line)
    assert d["detail"]["failed"] is True
    assert "UNAVAILABLE" in d["detail"]["fail_reason"]
    assert d["detail"]["timing"].startswith("warm")
    assert d["value"] > 0
    # schema stays valid with the extra failure fields
    from pcg_mpi_solver_tpu.obs.schema import validate_bench_line

    assert validate_bench_line(d) == []

    # no emitter / unconverged warm solve / CPU platform: nothing offered
    assert b._offer_failed_salvage(None, model, "cube", r0, extra, "x") \
        is None
    bad = types.SimpleNamespace(flag=1, relres=1.0, wall_s=1.0, iters=5)
    assert b._offer_failed_salvage(Em(), model, "cube", bad, extra, "x") \
        is None
    cpu = dict(extra, platform="cpu (CPU FALLBACK)")
    assert b._offer_failed_salvage(Em(), model, "cube", r0, cpu, "x") \
        is None


def test_salvage_trims_by_value_not_recency(monkeypatch, tmp_path):
    """Write pressure from warm/const/final lines across a live wave must
    never evict the highest-vs_baseline entry (the line the round-end
    driver's salvage fallback exists to re-emit)."""
    import json

    from pcg_mpi_solver_tpu import bench as b

    monkeypatch.chdir(tmp_path)

    def line(v):
        return json.dumps({"metric": "m", "value": v * 1e6, "unit": "u",
                           "vs_baseline": v,
                           "detail": {"platform": "tpu", "tag": v}})

    b._write_salvage(line(21.9))            # the flagship line
    for v in [1.1, 1.2, 1.3, 1.4, 1.5, 1.6, 1.7, 1.8, 1.9, 2.0, 2.1]:
        b._write_salvage(line(v))
    data = json.load(open(b._SALVAGE_PATH))
    vs = [json.loads(e["line"])["vs_baseline"] for e in data["lines"]]
    assert len(vs) <= 8
    assert 21.9 in vs, f"flagship line evicted: {vs}"


def test_salvage_evicts_age_expired_before_value_trim(monkeypatch,
                                                      tmp_path):
    """ADVICE r05 #2: entries older than BENCH_SALVAGE_MAX_AGE_S are
    unusable by _read_salvage, so they must be evicted FIRST — a stale
    high-vs_baseline line must never permanently occupy a slot a fresh
    (usable) lower-value line needs."""
    import json
    import time

    from pcg_mpi_solver_tpu import bench as b

    monkeypatch.chdir(tmp_path)

    def line(v, tag):
        return json.dumps({"metric": "m", "value": v * 1e6, "unit": "u",
                           "vs_baseline": v,
                           "detail": {"platform": "tpu", "tag": tag}})

    # fill every slot with stale, unbeatably-high-value entries
    for i in range(8):
        b._write_salvage(line(100.0 + i, f"stale{i}"))
    data = json.load(open(b._SALVAGE_PATH))
    now = time.time()
    for e in data["lines"]:
        e["unix_time"] = now - 2 * 43200        # 2x the default max age
    with open(b._SALVAGE_PATH, "w") as f:
        json.dump(data, f)

    # a fresh, modest line must displace them all (they can never be
    # read again), not lose the value-based trim to them
    b._write_salvage(line(1.5, "fresh"))
    data = json.load(open(b._SALVAGE_PATH))
    tags = [json.loads(e["line"])["detail"]["tag"] for e in data["lines"]]
    assert tags == ["fresh"], tags
    got = json.loads(b._read_salvage())
    assert got["detail"]["tag"] == "fresh"


def test_emitter_explicit_line_persists_even_after_watchdog_emit(
        monkeypatch, tmp_path):
    """ADVICE r05 #1: when the watchdog emitted first (done=True), main's
    fresh measured-live emit(line) must STILL persist the line to the
    salvage file — the done check only suppresses the duplicate stdout
    print, never the persist."""
    import json

    from pcg_mpi_solver_tpu import bench as b

    monkeypatch.chdir(tmp_path)
    em = b._Emitter("init")
    assert em.emit() is True                # the watchdog won the race
    fresh = _live_line(3.0)
    assert em.emit(fresh) is False          # stdout stays single-line...
    data = json.load(open(b._SALVAGE_PATH))
    assert [e["line"] for e in data["lines"]] == [fresh]  # ...persisted
