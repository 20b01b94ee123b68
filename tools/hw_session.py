"""Unattended on-hardware measurement session (RUNBOOK checklist).

Probes the accelerator until it answers (or a deadline passes), then
runs the whole RUNBOOK "on-hardware measurement checklist" as
subprocesses with per-step timeouts, appending everything to a log file
inside the repo — so a device that becomes free at any hour turns into
captured measurements without an operator in the loop.  This parent
imports no jax: each step's child can take the chip, one at a time.

Usage:
    python tools/hw_session.py [--deadline-min 360] [--log docs/HW_SESSION.log]
        [--quick]            # small sizes (smoke/CPU test of the harness)
        [--preset full|priority]

Presets:

* ``full`` (default) — the historical RUNBOOK checklist:
  1. bench_matvec         — XLA gse vs corner vs Pallas v3 at flagship scale
  2. bench_gather         — hybrid row-traffic isolation
  3. bench.py             — cube flagship (mixed)
  4. bench.py direct      — f64-direct anchor at the same scale
  5. bench.py octree      — graded-octree flagship on the blocked hybrid
  6. bench_iter_breakdown — structured per-iteration split
  7. bench_hybrid_breakdown — per-level gather/stencil/scatter split

* ``priority`` — the highest-value unanswered questions FIRST (every
  prior window died before the full queue finished; ROADMAP #3):
  1. flagship classic     — the 10.33M-dof ms/iter anchor (mixed)
  2. flagship fused       — PR-5's single-reduction loop, FIRST hardware
                            measurement (BENCH_PCG_VARIANT=fused)
  3. flagship pipelined   — ISSUE-11's stencil-overlapped psum
                            (BENCH_PCG_VARIANT=pipelined), directly
                            after the fused leg so the 3-way
                            classic/fused/pipelined ms/iter A/B reads
                            off three adjacent lines (the overlap claim
                            is lint-proven by step 0.2; this leg only
                            has to confirm the ms/iter number)
  3.5 profiled flagship   — ISSUE 15: one BENCH_PROFILE=1 rung on the
                            same warm cache/size (pipelined when the
                            overlap lint passed, else classic); the
                            captured device trace is parsed back and
                            the MEASURED overlap verdict + the
                            bench-trend verdict (obs/trend.py over the
                            committed BENCH_r*.json series) are logged
                            into this session log
  4. MG A/B               — classic+jacobi vs classic+mg at a
                            multi-level-coarsenable size (BENCH_NX=144;
                            BENCH_PRECOND=mg): iters + ms/iter +
                            detail.time_to_tol_s — the ISSUE-10
                            iteration-count lever, first hardware
                            measurement
  5. nrhs sweep 4, 16     — batched multi-RHS throughput A/B
                            (BENCH_NRHS; detail.dof_iter_rhs_per_s)
  6. Pallas v9 A/B        — first-ever hardware execution of the kernel
                            family (the hw_v9_ab.py step)
  Step 0.2 (after the fast lint, still on CPU) is the overlap lint:
  the full-tier ``psum-overlap`` rule alone (~15 s — the fast tier
  stays ~1 s and deliberately excludes it), proving the pipelined
  psum really is data-independent of the stencil before the hardware
  leg that measures the claim; a FAIL SKIPS the pipelined leg only
  (classic/fused measurements do not depend on the overlap claim).
  Step 0.5 (between the lints and the flagship) is the
  blocked-resilience smoke: a tiny solve_many with an injected
  per-column fault, proving the ISSUE-9 per-column recovery ladder +
  fault isolation live on the accelerator for seconds of window time.
  Steps 2-5 reuse step 1's warm caches (shared BENCH_CACHE_DIR), so a
  window that dies mid-queue still leaves each completed step's salvage
  line.
"""

from __future__ import annotations

import argparse
import atexit
import datetime
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Blocked-resilience smoke (priority preset step 0.5): tiny solve_many
# with a per-column NaN fault injected at the first blocked chunk
# boundary.  Asserts the poisoned column RECOVERS (per-column ladder)
# and the healthy column matches a fault-free block bit-identically —
# the ISSUE-9 fault-isolation contract, proven live on the accelerator.
_MANY_SMOKE = """
import numpy as np
from pcg_mpi_solver_tpu.config import RunConfig, SolverConfig, \
    TimeHistoryConfig
from pcg_mpi_solver_tpu.models.synthetic import make_cube_model
from pcg_mpi_solver_tpu.resilience import FaultPlan
from pcg_mpi_solver_tpu.solver.driver import Solver

m = make_cube_model(6, 5, 5, heterogeneous=True)
def mk():
    cfg = RunConfig(solver=SolverConfig(
        tol=1e-8, max_iter=2000, iters_per_dispatch=25,
        max_recoveries=2))
    cfg.time_history = TimeHistoryConfig(time_step_delta=[0.0, 1.0])
    return Solver(m, cfg, backend="general")
F = np.asarray(m.F)
fb = np.stack([F, 0.5 * F], axis=-1)
ref = mk().solve_many(fb)
s = mk()
s.fault_plan = FaultPlan("nan@col:1", recorder=s.recorder)
res = s.solve_many(fb)
assert list(res.flags) == [0, 0], (res.flags, res.quarantined)
assert res.recoveries >= 1, "column fault never engaged the ladder"
np.testing.assert_array_equal(np.asarray(res.x)[..., 0],
                              np.asarray(ref.x)[..., 0])
print("blocked-resilience smoke OK: poisoned column recovered "
      f"(recoveries={res.recoveries}), healthy column bit-identical")
"""


# Solve-service smoke (priority preset step 0.7, ISSUE 19): a tiny
# daemon over a temp spool serves 3 submitted jobs, one with an
# injected service-boundary fault (`exc@job:1`).  Asserts 2 done + 1
# failed WITH the named verdict, and that every job got a result file —
# the admission/journal/dispatch loop proven live in seconds, on CPU
# (the service layer is accelerator-agnostic; the flagship legs own the
# chip).
_SERVE_SMOKE = """
import tempfile
from pcg_mpi_solver_tpu.config import RunConfig, SolverConfig
from pcg_mpi_solver_tpu.models.synthetic import make_cube_model
from pcg_mpi_solver_tpu.resilience import FaultPlan
from pcg_mpi_solver_tpu.serve import jobs as sjobs
from pcg_mpi_solver_tpu.serve.daemon import ServeDaemon
from pcg_mpi_solver_tpu.solver.driver import Solver

m = make_cube_model(6, 5, 5, heterogeneous=True)
s = Solver(m, RunConfig(solver=SolverConfig(tol=1e-8, max_iter=2000)),
           backend="general")
spool = tempfile.mkdtemp(prefix="pcg_serve_smoke_")
ids = [sjobs.submit(spool, {"scale": sc, "deadline_s": 3600.0},
                    submit_t=float(i))
       for i, sc in enumerate([1.0, 0.5, 2.0])]
d = ServeDaemon(s, spool, queue_max=8, widths=(1, 2, 4),
                fault_plan=FaultPlan("exc@job:1", recorder=s.recorder))
reason = d.run(idle_exit_s=0.0, install_signals=False)
results = [sjobs.read_result(spool, j) for j in ids]
assert all(r is not None for r in results), results
n_ok = sum(r["ok"] for r in results)
failed = [r for r in results if not r["ok"]]
assert n_ok == 2 and len(failed) == 1, results
assert failed[0]["verdict"].startswith("injected:"), failed
print("serve smoke OK: 2 done + 1 failed with named verdict "
      f"({failed[0]['verdict']!r}), drain={reason}")
"""


def log_line(path, msg):
    stamp = datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y-%m-%d %H:%M:%SZ")
    line = f"[{stamp}] {msg}"
    print(line, flush=True)
    with open(path, "a") as f:
        f.write(line + "\n")


_last_step_ok = True

# one flight recorder per session log (lazy): the crash-durable twin of
# the log itself.  The log stream dies with a lost device or a killed
# session; the flight file's fsync-per-event begin/end brackets +
# heartbeats survive it, so "which step was in flight when the window
# died, and when did it last breathe" is a mechanical read (pcg-tpu
# summary <log>.flight.jsonl) instead of log archaeology.
_FLIGHTS = {}


@atexit.register
def _close_flights():
    # clean interpreter exit only — a SIGKILL skips this, which is the
    # point: every record is already fsync'd, close is bookkeeping
    for fl in _FLIGHTS.values():
        fl.close()


def _flight(path):
    if path not in _FLIGHTS:
        try:
            from pcg_mpi_solver_tpu.obs.flight import (
                FlightRecorder, ingest_and_rotate)
        except ImportError:
            sys.path.insert(0, REPO)
            from pcg_mpi_solver_tpu.obs.flight import (
                FlightRecorder, ingest_and_rotate)
        fpath = path + ".flight.jsonl"
        # a leftover artifact from a previous session on the same log is
        # ingested + rotated first (the shared startup discipline —
        # obs/flight.ingest_and_rotate documents why)
        fpath = ingest_and_rotate(fpath, lambda msg: log_line(path, msg))
        _FLIGHTS[path] = FlightRecorder(
            fpath, meta={"component": "hw_session"})
    return _FLIGHTS[path]


def run_step(path, name, argv, env_extra=None, timeout=3600, gate_s=900,
             force_gate=False, ok_rcs=(0,)):
    """Run one checklist step.  If the PREVIOUS step failed or timed out,
    first re-probe the accelerator (bounded by ``gate_s``): a SIGKILLed
    step's process can hold the chip until it is reaped, and the example
    scripts — unlike bench.py — have no probe/retry of their own, so
    without this gate they die instantly at the first device touch.
    ``force_gate`` probes even after an rc=0 step whose own try/except
    may have swallowed failures."""
    global _last_step_ok
    if (not _last_step_ok or force_gate) and gate_s:
        from pcg_mpi_solver_tpu.bench import _probe_with_retry

        why = "previous step failed" if not _last_step_ok else "force_gate"
        log_line(path, f"gate: {why}; re-probing before "
                       f"{name} (held-chip guard, {gate_s:.0f}s budget)")
        ok, detail = _probe_with_retry(budget_s=gate_s, probe_timeout_s=300)
        log_line(path, f"gate: {'accelerator ok' if ok else 'STILL DOWN'} "
                       f"({detail}); launching step regardless")
    env = dict(os.environ)
    env.setdefault("PCG_TPU_VERBOSE", "1")
    # persistent compile cache shared across steps/waves (see bench.py)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(REPO, ".jax_cache"))
    # examples/*.py run with sys.path[0]=examples/, and the package is
    # not pip-installed — the repo root must come from PYTHONPATH
    env["PYTHONPATH"] = REPO + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env.update(env_extra or {})
    log_line(path, f"=== {name}: {' '.join(argv)} "
                   + (f"env={env_extra} " if env_extra else ""))
    # crash-durable flight bracket around the whole step (begin fsync'd
    # BEFORE the subprocess launches; heartbeats while it runs): a
    # device loss mid-step leaves "step:<name> in flight" on disk even
    # when the log stream itself is lost.  Best-effort — recorder
    # trouble must never cost a hardware window a step.
    fl = fl_seq = None
    try:
        fl = _flight(path)
        fl_seq = fl.begin(f"step:{name}", argv=list(argv))
    except Exception as e:                              # noqa: BLE001
        log_line(path, f"flight recorder unavailable ({e}); continuing")
        fl = None
    t0 = time.monotonic()
    # The parent never touched jax, so this child may take the chip.
    # own process GROUP so a timeout kills the step's whole tree —
    # bench.py spawns its own subprocesses (reference baseline, CPU
    # fallback) which would otherwise survive as orphans competing with
    # the next step, unlogged, in an unattended session
    import signal

    # stream straight into the log (no PIPE): an external kill mid-step
    # must not lose the step's partial output — that is the exact
    # artifact-loss mode this harness exists to prevent
    with open(path, "a") as logf:
        proc = subprocess.Popen([sys.executable] + argv, cwd=REPO, env=env,
                                stdout=logf, stderr=subprocess.STDOUT,
                                text=True, start_new_session=True)
        try:
            proc.wait(timeout=timeout)
            status = f"rc={proc.returncode}"
        except subprocess.TimeoutExpired:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass    # a daemonized escapee; the group is dead, move on
            status = f"TIMEOUT after {timeout}s (process group killed)"
    wall = time.monotonic() - t0
    # ok_rcs: some steps use nonzero exits as VERDICTS, not failures
    # (cache_key_check exits 4 for a successfully-determined MISMATCH) —
    # those must not trip the next step's held-chip gate
    _last_step_ok = status in tuple(f"rc={rc}" for rc in ok_rcs)
    if fl is not None:
        try:
            fail_extra = {} if _last_step_ok else {"error": status}
            fl.end(fl_seq, f"step:{name}", ok=_last_step_ok,
                   status=status, wall_s=round(wall, 1), **fail_extra)
            if not _last_step_ok:
                # the mechanical post-mortem pointer, IN the session log:
                # where the durable artifact is and what it says
                from pcg_mpi_solver_tpu.obs.flight import (
                    flight_verdict_path)

                v = flight_verdict_path(fl.path)
                log_line(path, f"flight record: {fl.path} "
                               f"verdict={v['verdict']} "
                               f"({v['records']} record(s)"
                               + (", in flight: "
                                  + ", ".join(v["in_flight"])
                                  if v["in_flight"] else "")
                               + ")")
        except Exception as e:                          # noqa: BLE001
            log_line(path, f"flight record close failed ({e}); "
                           "continuing")
    log_line(path, f"=== {name} done: {status} ({wall:.0f}s)")
    return status


def log_profile_verdicts(path, prof_dir, since=None):
    """ISSUE 15: after the profiled flagship rung, put the two
    mechanical verdicts INTO the session log — the measured
    collective-overlap fraction parsed from the captured device trace
    (obs/profview.py) and the bench-trend verdict over the committed
    BENCH_r*.json series plus this queue's fresh line (obs/trend.py).
    ``since`` (unix seconds, the profiled step's start) guards against
    attributing a STALE artifact: bench swallows capture failures by
    design, and bench_profile/ persists across sessions — an earlier
    round's trace must not be logged as this round's measurement.
    Best-effort end to end: a broken trace parse or a missing artifact
    logs a named reason and must never cost the step (tested in
    tests/test_hw_queue.py)."""
    try:
        from pcg_mpi_solver_tpu.obs import profview

        files = profview.find_trace_files(prof_dir)
        if not files:
            raise FileNotFoundError(f"no trace artifact under "
                                    f"{prof_dir}")
        if since is not None and os.path.getmtime(files[0]) < since:
            raise FileNotFoundError(
                f"newest artifact predates this step (the capture "
                f"failed silently; stale: {files[0]})")
        rep = profview.profile_report(files[0])
        ov = rep.get("overlap_frac")
        mv = (rep.get("phases") or {}).get("matvec", {}).get(
            "ms_per_iter")
        log_line(path, "overlap verdict: "
                 + (f"{ov:.3f} of collective time hidden behind "
                    "concurrent compute" if ov is not None else
                    "n/a (no collective ops in trace)")
                 + f" (matvec {mv} ms/iter, parse verdict "
                   f"{rep.get('verdict')!r}, artifact "
                   f"{rep.get('source')})")
    except Exception as e:                              # noqa: BLE001
        log_line(path, f"overlap verdict unavailable "
                       f"({type(e).__name__}: {e}); continuing")
    # Fleet skew verdict (ISSUE 16): on a multi-controller capture the
    # p<idx>/ subdirs carry per-process traces — log the cross-process
    # transport-vs-wait attribution next to the overlap verdict.  On the
    # usual single-controller window this degrades to a named reason.
    try:
        from pcg_mpi_solver_tpu.obs import fleet

        frep = fleet.fleet_report(prof_dir)
        if frep.get("skew_frac") is not None:
            who = (f"p{frep['straggler']}" if frep.get("straggler")
                   is not None else "none (balanced)")
            log_line(path, f"fleet verdict: skew_frac="
                           f"{frep['skew_frac']:.4f} (wait "
                           f"{frep['wait_ms']:.1f} ms vs transport "
                           f"{frep['transport_ms']:.1f} ms over "
                           f"{frep['matched_collectives']} matched "
                           f"collectives), straggler {who} "
                           "(read back: pcg-tpu fleet-report)")
        else:
            log_line(path, f"fleet verdict: n/a ({frep['verdict']})")
    except Exception as e:                              # noqa: BLE001
        log_line(path, f"fleet verdict unavailable "
                       f"({type(e).__name__}: {e}); continuing")
    try:
        import glob as _glob

        from pcg_mpi_solver_tpu.obs import trend

        arts = sorted(_glob.glob(os.path.join(REPO, "BENCH_r*.json")))
        fresh = os.path.join(REPO, "bench_provisional.json")
        rep = trend.trend_report(
            arts, fresh=fresh if os.path.exists(fresh) else None)
        log_line(path, "trend verdict: " + trend.verdict_line(rep))
        for leg in rep["legs"]:
            if leg["verdict"] == "regressed":
                log_line(path, f"trend REGRESSION: {leg['leg']} "
                               f"{leg['old_value']:.3g} -> "
                               f"{leg['new_value']:.3g} "
                               f"({leg['delta_pct']:+.1f}%)")
    except Exception as e:                              # noqa: BLE001
        log_line(path, f"trend verdict unavailable "
                       f"({type(e).__name__}: {e}); continuing")


def start_queue(name, deadline_min, log):
    """Shared session-start policy for every hardware queue script: derive
    the log path, probe the accelerator with the ONE retry policy (incl.
    the deterministic-failure two-strike, pcg_mpi_solver_tpu/bench.py),
    exit(3) if the deadline passes.  Returns the log path."""
    path = os.path.join(REPO, log)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    sys.path.insert(0, REPO)
    from pcg_mpi_solver_tpu.bench import _probe_with_retry

    log_line(path, f"{name} start (deadline {deadline_min:.0f} min)")
    ok, detail = _probe_with_retry(budget_s=deadline_min * 60,
                                   probe_timeout_s=600)
    if not ok:
        log_line(path, f"deadline reached; no {name} session ({detail})")
        sys.exit(3)
    log_line(path, f"accelerator ANSWERED: {detail}")
    return path


def run_priority_queue(path, quick: bool):
    """The prioritized measurement queue (module docstring ``priority``
    preset): contract lint FIRST (step 0, on CPU — a broken structural
    claim means the measurements would benchmark a lie), then the
    3-way classic/fused/pipelined ms/iter A/B at the flagship, then the
    batched-RHS sweep, then the Pallas v9 A/B — ordered so the minutes
    a dying window DOES deliver answer the most valuable open
    questions.
    A shared warm-path cache dir makes the bench steps near-zero-setup."""
    # Step 0: `pcg-tpu lint --fast` (analysis/) — statically prove the
    # collective budgets / hot-loop purity the queue is about to measure.
    # Runs on the CPU backend (JAX_PLATFORMS=cpu: never takes the
    # chip).  A FAIL aborts BEFORE the hardware queue starts:
    # measuring a claim the lint just disproved burns the window on
    # garbage.
    status = run_step(path, "contract lint (step 0)",
                      ["-m", "pcg_mpi_solver_tpu.analysis", "--fast"],
                      env_extra={"JAX_PLATFORMS": "cpu"}, timeout=900,
                      gate_s=0)
    verdict = "PASS" if status == "rc=0" else f"FAIL ({status})"
    log_line(path, f"lint verdict: {verdict}")
    if status != "rc=0":
        log_line(path, "structural contract lint FAILED — aborting the "
                       "priority queue before any hardware step (fix the "
                       "invariant or baseline it, then relaunch)")
        return
    # Step 0.2: the psum-overlap rule ALONE, full tier, still on CPU
    # (~15 s; registered fast=False and the pipelined programs are not
    # in the --fast matrix, so step 0 deliberately never checks the
    # overlap claim — this step does, right before the hardware leg
    # that measures it).  A FAIL skips ONLY the pipelined leg: the
    # classic/fused measurements do not depend on the overlap claim,
    # so the window still answers them.
    ov_status = run_step(path, "overlap lint (step 0.2)",
                         ["-m", "pcg_mpi_solver_tpu.analysis",
                          "--rules", "psum-overlap"],
                         env_extra={"JAX_PLATFORMS": "cpu"}, timeout=900,
                         gate_s=0)
    overlap_ok = ov_status == "rc=0"
    log_line(path, "overlap lint verdict: "
                   + ("PASS" if overlap_ok else f"FAIL ({ov_status})"))
    # Step 0.5: blocked-resilience smoke (ISSUE 9) — a tiny solve_many
    # with an injected per-column fault, ON THE ACCELERATOR: proves the
    # per-column recovery ladder + fault isolation live (tier-1 only
    # ever runs it on CPU) for ~seconds of window time.  The healthy
    # column must match a fault-free run bit-identically and the
    # poisoned column must recover (flag 0 after a ladder restart).
    run_step(path, "blocked-resilience smoke", ["-c", _MANY_SMOKE],
             env_extra={"PCG_TPU_RETRY_BACKOFF_S": "0.01"}, timeout=900)
    # Step 0.6: distributed-chaos smoke (ISSUE 18) — a 2-process CPU
    # gloo group with a rank-targeted kill (`kill@rank:1:2`): the
    # survivor must raise the NAMED DeadPeerError within the collective
    # deadline (not hang in gloo), and a same-count relaunch must resume
    # from the group-committed snapshot epoch bit-identically.
    # CPU-only (jax.distributed child processes; never touches the
    # chip) and BEFORE the setup ladder, so a broken
    # fault-tolerance path fails the window in minutes, not at 3 a.m.
    run_step(path, "distributed-chaos smoke",
             ["-m", "pytest", "-x", "-q",
              "tests/test_distributed_ft.py::"
              "test_dead_peer_named_and_resume_scalar"],
             env_extra={"JAX_PLATFORMS": "cpu"}, timeout=1200, gate_s=0)
    # Step 0.7: solve-service smoke (ISSUE 19) — a tiny serve daemon
    # over a temp spool: 3 submitted jobs, one injected service-
    # boundary fault (`exc@job:1`), asserting 2 done + 1 failed with
    # the NAMED verdict and a result file for every job.  CPU-only
    # (the service layer is accelerator-agnostic; never touches the
    # chip) and before the ladder — a broken admission/journal/
    # dispatch loop fails the window in seconds.
    run_step(path, "serve smoke", ["-c", _SERVE_SMOKE],
             env_extra={"JAX_PLATFORMS": "cpu"}, timeout=900, gate_s=0)
    # BENCH_NX exported unconditionally so the flagship size is pinned
    # HERE, not silently inherited from bench.py's default
    cache = {"BENCH_CACHE_DIR": os.path.join(REPO, ".pcg_cache")}
    size = {"BENCH_NX": "24" if quick else "150"}
    # Setup ladder (ISSUE 14): the weak-scaling cold-path measurement —
    # sharded partition build vs the monolithic serial build, streamed
    # slab-ingest peak memory, shard-cache warm/cold deltas — runs on
    # CPU (jax.distributed child groups; it never touches the
    # chip) BEFORE the variant A/Bs.  It scratches inside
    # BENCH_CACHE_DIR but in an isolated per-run subdir it deletes on
    # exit (its rungs must COLD-build to measure honestly), so it
    # neither pollutes nor pre-warms the later legs' entries.
    # Artifact: SETUP_LADDER.json in the repo (BENCH-schema rungs).
    run_step(path, "setup ladder", ["bench.py"],
             env_extra=dict(cache,
                            BENCH_SETUP_LADDER="1,2" if quick else "1,2,4",
                            BENCH_SETUP_NX="12" if quick else "40",
                            BENCH_SETUP_OUT=os.path.join(
                                REPO, "SETUP_LADDER.json"),
                            JAX_PLATFORMS="cpu"),
             timeout=1800, gate_s=0)
    run_step(path, "flagship classic", ["bench.py"],
             env_extra=dict(cache, **size), timeout=3600)
    run_step(path, "flagship fused", ["bench.py"],
             env_extra=dict(cache, BENCH_PCG_VARIANT="fused", **size),
             timeout=3600)
    # Pipelined leg (ISSUE 11): same size, same warm cache dir, directly
    # after fused — the psum-overlap lint (step 0.2) already proved the
    # reduction is concurrent with the stencil in the lowered program,
    # so this step only has to confirm ms/iter; three adjacent lines =
    # the 3-way variant A/B (detail.pcg_variant labels them).
    if overlap_ok:
        run_step(path, "flagship pipelined", ["bench.py"],
                 env_extra=dict(cache, BENCH_PCG_VARIANT="pipelined",
                                **size),
                 timeout=3600)
    else:
        log_line(path, "SKIPPING the flagship pipelined leg: the "
                       "psum-overlap lint (step 0.2) FAILED — measuring "
                       "the variant would benchmark a disproven "
                       "latency-hiding claim; the rest of the queue "
                       "does not depend on it")
    # Profiled flagship rung (ISSUE 15): one BENCH_PROFILE=1 leg
    # directly after the variant A/Bs, on the SAME warm cache dir and
    # size — the bench captures a jax.profiler trace of one warm solve
    # (after its timed solve; the A/B numbers above are never
    # perturbed), parses it back (obs/profview.py), and stamps
    # detail.measured_ms_per_iter_matvec + detail.overlap_frac on its
    # line.  The profiled variant is pipelined when the overlap lint
    # passed (the hardware twin of the step-0.2 static proof — the
    # MEASURED overlap fraction), else classic.  The overlap + trend
    # verdicts land in this session log right after the step; a broken
    # trace parse logs a reason and never costs the step.
    prof_dir = os.path.join(REPO, "bench_profile")
    prof_env = dict(cache, BENCH_PROFILE="1", BENCH_PROFILE_DIR=prof_dir,
                    **size)
    if overlap_ok:
        prof_env["BENCH_PCG_VARIANT"] = "pipelined"
    t_prof0 = time.time()
    run_step(path, "profiled flagship", ["bench.py"],
             env_extra=prof_env, timeout=3600)
    log_profile_verdicts(path, prof_dir, since=t_prof0)
    # Watch smoke (ISSUE 16): one `pcg-tpu watch --once` snapshot of
    # THIS session's own flight stream (the file run_step's brackets +
    # heartbeats write), logged into the session — so a wedged hardware
    # run is diagnosed from the session log (status/last-breath/in-
    # flight names) instead of by guesswork.  Healthy session: the
    # watch step itself is in flight, so the snapshot reads RUNNING.
    run_step(path, "watch smoke (--once)",
             ["-m", "pcg_mpi_solver_tpu.cli", "watch",
              path + ".flight.jsonl", "--once"],
             env_extra={"JAX_PLATFORMS": "cpu"}, timeout=600, gate_s=0)
    # MG A/B (ISSUE 10): classic+jacobi anchor vs classic+mg at an
    # even, multi-level-coarsenable size (150 halves once to 75 and
    # stops; 144 = 16*9 gives the 72/36/18/9 coarse chain), sharing the
    # warm cache dir — read iters + tpu_ms_per_iter + time_to_tol_s off
    # the two lines (detail.precond labels them).
    mg_size = {"BENCH_NX": "24" if quick else "144"}
    run_step(path, "mg A/B anchor (jacobi)", ["bench.py"],
             env_extra=dict(cache, **mg_size), timeout=3600)
    run_step(path, "mg A/B (mg)", ["bench.py"],
             env_extra=dict(cache, BENCH_PRECOND="mg", **mg_size),
             timeout=3600)
    for nrhs in ("4", "16"):
        run_step(path, f"nrhs sweep ({nrhs})", ["bench.py"],
                 env_extra=dict(cache, BENCH_NRHS=nrhs, **size),
                 timeout=3600)
    run_step(path, "matvec A/B v9",
             ["examples/bench_matvec.py", "48" if quick else "150"],
             env_extra={"BENCH_MATVEC_VARIANTS": "v9"}, timeout=2400)
    log_line(path, "priority queue complete")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--deadline-min", type=float, default=360,
                    help="give up probing after this many minutes")
    ap.add_argument("--log", default=os.path.join("docs", "HW_SESSION.log"))
    ap.add_argument("--quick", action="store_true",
                    help="small sizes (harness smoke; also used on CPU)")
    ap.add_argument("--preset", choices=["full", "priority"],
                    default="full",
                    help="full = historical RUNBOOK checklist; priority "
                         "= the classic/fused/pipelined ms/iter A/B, "
                         "then the BENCH_NRHS sweep, then Pallas v9 "
                         "(highest-value open questions first — see "
                         "module docstring)")
    args = ap.parse_args()
    path = start_queue(f"hw_session (quick={args.quick}, "
                       f"preset={args.preset})",
                       args.deadline_min, args.log)
    if args.preset == "priority":
        run_priority_queue(path, args.quick)
        return

    nx = "48" if args.quick else "150"
    ot = ({"BENCH_OT_N": "6", "BENCH_OT_LEVEL": "2"} if args.quick else {})
    run_step(path, "matvec A/B", ["examples/bench_matvec.py", nx],
             timeout=2400)
    run_step(path, "row traffic",
             ["examples/bench_gather.py"]
             + (["0.3", "1.0"] if args.quick else []), timeout=1200)
    run_step(path, "flagship cube (mixed)", ["bench.py"],
             env_extra=dict({"BENCH_NX": nx} if args.quick else {}),
             timeout=3600)
    # direct mode needs f64 STORAGE too — f32 direct stagnates at
    # relres ~1e-5*kappa (RUNBOOK) and only ladders down
    run_step(path, "flagship cube (f64 direct)", ["bench.py"],
             env_extra=dict({"BENCH_MODE": "direct",
                             "BENCH_DTYPE": "float64"},
                            **({"BENCH_NX": nx} if args.quick else {})),
             timeout=3600)
    # hybrid auto-selection is deprecation-gated (ISSUE 14; RUNBOOK
    # "Scaling the setup path") — this step measures it DELIBERATELY
    run_step(path, "octree flagship (hybrid)", ["bench.py"],
             env_extra=dict({"BENCH_MODEL": "octree",
                             "PCG_TPU_ENABLE_HYBRID": "1"}, **ot),
             timeout=4800)
    run_step(path, "iteration breakdown",
             ["examples/bench_iter_breakdown.py", nx], timeout=1800)
    run_step(path, "hybrid per-level breakdown",
             ["examples/bench_hybrid_breakdown.py"]
             + (["6", "2", "3"] if args.quick else ["16", "4", "6"]),
             timeout=1800)
    log_line(path, "hw_session complete")


if __name__ == "__main__":
    main()
