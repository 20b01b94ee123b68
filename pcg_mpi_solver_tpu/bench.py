"""Benchmark harness: TPU SPMD solve vs the reference's per-rank hot loop.

Prints ONE JSON line on stdout: {"metric", "value", "unit", "vs_baseline"}.
A provisional copy of the line (computed with the pre-validated baseline
constant) is written to stderr and to ``bench_provisional.json`` IMMEDIATELY
after the timed solve, so the perf number survives even if the process dies
before the final emit; stdout stays single-line for the driver's parser.

Metric: sustained PCG iteration throughput (dof-iterations / second) of the
full jitted solve on the available accelerator, measured on a converged
quasi-static step with compile excluded (the solve is re-run from a zeroed
state after a warm-up solve).

Baseline: the REAL 8-rank mpi4py reference cannot run in this image —
mpi4py, OpenMPI and mgmetis are absent and installs are unavailable
(verified: ``import mpi4py`` and ``mpiexec`` both missing).  The stand-in is
measured, not guessed: ``NumpyRefSolver`` re-implements the reference's
per-rank hot loop (type-grouped gather -> Ke@(ck*u) -> bincount scatter,
pcg_solver.py:277-300) in plain numpy; its per-(dof*iteration) cost is
measured on this host (on a capped-size model when the bench model is huge;
small models have BETTER cache behavior, so scaling per-dof favors the
baseline) and divided by 8 for idealized perfect 8-rank scaling — also
favoring the baseline, since the real 8-rank demo spent 1.0 of 12.6 s in
comm-wait (BASELINE.md, notebook cell 12).

The stand-in is VALIDATED against the reference's own code: the full
reference pipeline runs under tools/mpi_shim (tools/run_reference_baseline.py).
Measured 2026-07-30 on this host at 823,875 dofs: reference 232.8
ns/dof-iter vs NumpyRefSolver 235.2 ns/dof-iter (within 1%), with EXACT
PCG iteration parity between the reference and this framework on the same
MDF model (tests/test_reference_parity.py).

Default model: 150^3 cells ~= 10.3M dofs — the BASELINE.json north-star
scale ("=>20x vs 8-rank mpi4py at 10M dofs").

Resilience posture (the round's BENCH artifact is captured by an external
driver exactly once; probe retries once consumed the driver's whole
~1800 s window and rc=124 landed with NOTHING on stdout — hence the
design rule *fallback first, upgrade second*).  This orchestrator falls
back to the CPU where a measurement should fail instead; it goes when the
benchmark replaces it (ROADMAP Queue 3 #4):

- a small, clearly-labeled CPU PROVISIONAL solve is launched in a
  subprocess IMMEDIATELY at startup (cube 24^3, validated-constant
  baseline — minutes, not tens of minutes), concurrently with the probe,
  so a printable line exists early no matter what the device does;
- a deadline WATCHDOG daemon thread guarantees stdout gets exactly one
  JSON line before BENCH_WALL_BUDGET_S (default 1680 s, under the
  observed ~1800 s driver timeout) even if the accelerator path hangs in
  an uninterruptible native call — it emits the best line available
  (TPU > CPU-provisional > explicit zero-value error line) and exits;
- the accelerator probe RETRIES with backoff for BENCH_PROBE_BUDGET_S
  (default 600 s — capped well below the driver window) instead of
  giving up after one 3-minute attempt;
- a size LADDER retries the solve at smaller models if the flagship size
  fails to build/compile/converge (cube: BENCH_LADDER nx rungs, default
  "150,128,96"; octree: BENCH_OT_LADDER n0 rungs, default "22,18,12"),
  skipping rungs the remaining wall budget cannot fit;
- the live numpy baseline runs in a crash-isolated SUBPROCESS with a
  timeout; if it fails, the pre-validated constant is used instead.

- when the accelerator is unreachable, the remaining wall budget is NOT
  wasted on the tiny provisional: a mid-size CPU measurement
  (BENCH_UPGRADE_NX^3 cells, default 48 ~= 353k dofs, f64 direct)
  upgrades the emitted line when it completes in
  budget (disable: BENCH_CPU_UPGRADE=0);
- every successful live accelerator line is recorded in
  ``bench_salvage.json``; a later invocation that finds no device
  re-emits the best fresh one (<= BENCH_SALVAGE_MAX_AGE_S, default 12 h)
  clearly re-labeled as salvaged-from-an-earlier-session — a TPU number
  measured earlier in the round (e.g. by the tools/hw_session queue)
  beats any CPU fallback as the round artifact (disable reading:
  BENCH_SALVAGE=0; the hardware queues do, so a device-less wave step
  cannot masquerade as a fresh measurement in the session log).

Env knobs: BENCH_NX/NY/NZ (cells), BENCH_TOL, BENCH_PARTS, BENCH_DTYPE,
BENCH_MODE (mixed|direct), BENCH_BACKEND (auto|structured|general),
BENCH_REF_ITERS, BENCH_REF_MAX_DOFS, BENCH_MODEL (cube|octree),
BENCH_OT_N, BENCH_OT_LEVEL, BENCH_PROBE_BUDGET_S, BENCH_LADDER,
BENCH_OT_LADDER, BENCH_CPU_FALLBACK, BENCH_REF_TIMEOUT_S,
BENCH_WALL_BUDGET_S, BENCH_PROV_NX, BENCH_PROVISIONAL (internal:
marks the fast-fallback subprocess), BENCH_CPU_UPGRADE,
BENCH_UPGRADE_NX/BENCH_UPGRADE_MODE/BENCH_UPGRADE_DTYPE, BENCH_SALVAGE,
BENCH_SALVAGE_MAX_AGE_S, BENCH_NRHS (batched multi-RHS block width: the
timed leg solves an nrhs-wide block of the reference load via
Solver.solve_many and the line carries detail.nrhs +
detail.dof_iter_rhs_per_s — the nrhs ∈ {1, 4, 16} A/B for a hardware
window), BENCH_PLATEAU (mixed-mode inner
plateau-exit window, 0=off), BENCH_PRECOND (jacobi|block3|mg — the
ISSUE-10 preconditioner A/B; detail.precond + detail.time_to_tol_s /
detail.iters make it a time-to-solution comparison),
BENCH_PCG_VARIANT (classic|fused|pipelined PCG loop
formulation — the 3-way ms/iteration A/B knob: classic's 3 serialized
reductions vs fused's single psum vs pipelined's stencil-overlapped
psum; the engaged variant is reported in detail.pcg_variant on EVERY
line, insurance/salvage included, and schema-validated against the
canonical name set — obs/schema.BENCH_PCG_VARIANT_VALUES),
BENCH_FLIGHT (crash-durable flight-recorder JSONL, default
bench_flight.jsonl, 0 = off: fsync-per-event begin/end brackets around
every rung and every solve dispatch, so a device loss mid-timed-solve
leaves a parseable artifact — a previous run's artifact is ingested
mechanically at startup, verdict logged, file rotated to .prev; every
line also carries detail.predicted_ms_per_iter / detail.model_ratio,
the obs/perf.py analytic cost model's verdict), BENCH_PROFILE=1
(ISSUE 15: one PROFILED warm rung per leg after the timed solve —
jax.profiler trace captured into BENCH_PROFILE_DIR, default
bench_profile/, parsed back by obs/profview.py; the final line gains
detail.measured_ms_per_iter_matvec + detail.overlap_frac and the
artifact stays on disk for `pcg-tpu prof-report`); plus the solver-level performance knob
PCG_TPU_HYBRID_BLOCK (docs/RUNBOOK.md knob table).
"""

import json
import os
import subprocess
import sys
import threading
import time

# obs/ is import-light by contract (no jax/numpy): safe before the
# accelerator env is configured.
from pcg_mpi_solver_tpu.obs.metrics import MetricsRecorder, StderrSink
from pcg_mpi_solver_tpu.obs.schema import BENCH_SCHEMA

# 2026-07-30: the reference's OWN hot loop measured at
# 232.8 ns/dof-iter on this host at 823,875 dofs; the NumpyRefSolver
# stand-in at 235.2 (within 1%).  Used for the provisional line and
# whenever the live baseline measurement fails.
VALIDATED_REF_NS_PER_DOF_ITER = 235.2176
_VALIDATED_NOTE = ("pre-validated constant (reference's "
                   "own hot loop 232.8 ns/dof-iter at 823,875 dofs; "
                   "stand-in within 1%)")


# The bench's metrics registry: ONE logging path for the harness and the
# Solver it drives (the Solver is constructed with recorder=_REC).  The
# historical "# ..." note bodies are kept; the stderr sink adds the
# [pcg-tpu HH:MM:SS] timestamp prefix every line — the _vlog contract that
# localizes a hung remote dispatch from the driver's captured stderr.
# Phase timings accumulate as spans (emitted as bench_phase events) and
# land in the final line's detail.phases.
_REC = MetricsRecorder(sinks=[StderrSink()])


def _log(msg):
    _REC.note(msg)


def _cpu_only_env():
    """Env for CPU-only subprocesses that must NEVER touch the chip: the
    parent may hold it, and a child that needs it fails or hangs."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    pp = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in pp:
        pp.insert(0, repo)
    env["PYTHONPATH"] = os.pathsep.join(pp)
    return env


def _probe_with_retry(budget_s=None, probe_timeout_s=180.0):
    """Retry the backend probe with backoff across a wall budget.

    One 180 s probe attempt that died on a transiently unreachable device
    once lost the whole round's perf artifact.  The driver
    gives the bench far more wall than 3 minutes — spend it.  Also the
    ONE probe-retry policy for tools/hw_session.py (pass budget_s /
    probe_timeout_s explicitly there)."""
    from pcg_mpi_solver_tpu.utils.backend_probe import probe_backend

    # default 10 min: far past the fatal one-shot 180 s of r02, but capped
    # WELL below the observed ~1800 s driver window — r03's 30-min default
    # let the probe eat the entire window and the round artifact died
    # rc=124 with nothing emitted (the provisional-first orchestrator in
    # main() is the other half of that fix)
    budget = (float(os.environ.get("BENCH_PROBE_BUDGET_S", 600))
              if budget_s is None else float(budget_s))
    t0 = time.monotonic()
    attempt = 0
    hard_fails = 0
    while True:
        attempt += 1
        ok, detail = probe_backend(timeout_s=probe_timeout_s)
        if ok:
            if attempt > 1:
                _log(f"# backend probe ok on attempt {attempt} "
                     f"({time.monotonic() - t0:.0f}s in)")
            return True, detail
        elapsed = time.monotonic() - t0
        _log(f"# backend probe attempt {attempt} failed "
             f"({elapsed:.0f}/{budget:.0f}s): {detail}")
        # a timeout or connection error is transient device trouble worth
        # waiting out; a missing/broken plugin is deterministic — two
        # strikes and move on to the fallback instead of burning the
        # whole budget on it
        deterministic = any(sig in detail for sig in (
            "ModuleNotFoundError", "ImportError",
            "not in the list of known backends"))
        if deterministic:
            hard_fails += 1
            if hard_fails >= 2:
                return False, detail
        if elapsed >= budget:
            return False, detail
        # short sleeps early (transient trouble recovers fast),
        # longer later (a killed holder of the chip takes time to reap)
        time.sleep(min(30.0 + 15.0 * attempt, 120.0))


def _model_cache_key(kind, gen_kwargs):
    """Cache key = the caller's FULL generator kwargs + a hash of the
    model-source files — so neither a generator code change, an edited
    call-site kwarg, nor a changed GENERATOR DEFAULT (callers may pass
    partial kwarg sets) can serve a stale model."""
    import hashlib

    import pcg_mpi_solver_tpu.models as m

    h = hashlib.sha256()
    pkg = os.path.dirname(m.__file__)
    for fn in sorted(os.listdir(pkg)):
        if fn.endswith(".py"):
            with open(os.path.join(pkg, fn), "rb") as f:
                h.update(f.read())
    h.update(repr((kind, sorted(gen_kwargs.items()))).encode())
    return h.hexdigest()[:16]


def cached_model(kind, **gen_kwargs):
    """Build (or load from the on-disk cache) a model.  Octree generation
    costs minutes at flagship scale on the 1-core bench host; caching cuts
    per-hardware-step latency and step-timeout pressure for the bench AND
    the examples/bench_*.py microbenchmarks (same cache, keyed on the full
    kwargs + a models-source hash).  Disable with BENCH_MODEL_CACHE=0."""
    import pickle

    cache_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             os.pardir, ".bench_cache")
    use_cache = os.environ.get("BENCH_MODEL_CACHE", "1") == "1"
    path = os.path.join(
        cache_dir, f"model_{_model_cache_key(kind, gen_kwargs)}.pkl")
    if use_cache:
        # sweep SIGKILL-orphaned .tmp files on the read path too: if cache
        # WRITES keep failing (e.g. disk full — exactly when
        # multi-hundred-MB orphans matter) the write-side sweep never runs
        _sweep_stale_tmps(cache_dir)
    if use_cache and os.path.exists(path):
        try:
            with open(path, "rb") as f:
                model = pickle.load(f)
        except Exception as e:                          # noqa: BLE001
            _log(f"# model cache read failed ({type(e).__name__}); rebuilding")
        else:
            try:
                os.utime(path)                          # LRU touch
            except OSError:
                pass            # best-effort metadata; the load succeeded
            return model

    if kind == "octree":
        from pcg_mpi_solver_tpu.models.octree import make_octree_model

        model = make_octree_model(**gen_kwargs)
    else:
        from pcg_mpi_solver_tpu.models import make_cube_model

        model = make_cube_model(**gen_kwargs)
    if use_cache:
        try:
            from pcg_mpi_solver_tpu.utils.io import write_atomic

            os.makedirs(cache_dir, exist_ok=True)
            # streamed: the flagship pickle is multi-hundred-MB and must
            # not be materialized on top of the live model
            write_atomic(path, lambda f: pickle.dump(
                model, f, protocol=pickle.HIGHEST_PROTOCOL))
            _evict_model_cache(cache_dir, keep=path)
        except Exception as e:                          # noqa: BLE001
            _log(f"# model cache write failed ({type(e).__name__}); continuing")
    return model


def _build_model(kind, nx, ny, nz, ot_n, ot_level):
    if kind == "octree":
        return cached_model(kind, nx0=ot_n, ny0=ot_n, nz0=ot_n,
                            max_level=ot_level, n_incl=6, seed=2,
                            E=30e9, nu=0.2, load="traction",
                            load_value=1e6)
    return cached_model(kind, nx=nx, ny=ny, nz=nz, E=30e9, nu=0.2,
                        load="traction", load_value=1e6,
                        heterogeneous=True)


def _sweep_stale_tmps(cache_dir):
    """Remove SIGKILL-orphaned model_*.tmp files older than an hour (a
    killed writer — run_step timeout — leaves a multi-hundred-MB orphan
    the size cap would never see).  Called from both the cache-read and
    eviction paths; best-effort."""
    try:
        for fn in os.listdir(cache_dir):
            if fn.startswith("model_") and fn.endswith(".tmp"):
                p = os.path.join(cache_dir, fn)
                if time.time() - os.stat(p).st_mtime > 3600:
                    os.remove(p)
    except OSError:
        pass


def _evict_model_cache(cache_dir, keep, cap_bytes=None):
    """LRU-evict model_*.pkl until the cache fits the size cap
    (BENCH_MODEL_CACHE_GB, default 8).  Source-file edits re-key every
    entry, permanently orphaning the old generation — without eviction
    the multi-hundred-MB flagship pickles accumulate unboundedly.
    One eviction protocol repo-wide: cache/partition_cache.evict_lru
    (jax-free, safe to import before the accelerator env is set)."""
    from pcg_mpi_solver_tpu.cache.partition_cache import evict_lru

    if cap_bytes is None:
        cap_bytes = float(os.environ.get("BENCH_MODEL_CACHE_GB", 8)) * 2**30
    _sweep_stale_tmps(cache_dir)
    evict_lru(cache_dir, keep=keep, cap_bytes=cap_bytes,
              suffix=".pkl", prefix="model_")


def measure_ref_ns(kind, n_dof, ref_max_dofs, n_ref_iters,
                   nx, ny, nz, ot_n, ot_level):
    """Measure the numpy reference hot-loop cost; prints ONE line
    ``REF_NS <ns> <note>`` on stdout.  Runs in a subprocess so an OOM or
    hang here cannot take down the bench after its timed solve."""
    from pcg_mpi_solver_tpu.solver.numpy_ref import NumpyRefSolver

    if n_dof <= ref_max_dofs:
        ref_model = _build_model(kind, nx, ny, nz, ot_n, ot_level)
        note = "same model"
    elif kind == "octree":
        ref_model = _build_model(kind, 0, 0, 0, 8, 3)
        note = f"scaled per-dof from a {ref_model.n_dof}-dof octree"
    else:
        rn = max(8, int(round((ref_max_dofs / 3.1) ** (1 / 3))) - 1)
        ref_model = _build_model("cube", rn, rn, rn, 0, 0)
        note = f"scaled per-dof from {ref_model.n_dof} dofs"
    ref_per_iter = NumpyRefSolver(ref_model).time_per_iter(n_iters=n_ref_iters)
    print(f"REF_NS {ref_per_iter / ref_model.n_dof * 1e9:.4f} {note}",
          flush=True)


def _live_baseline(kind, n_dof, nx, ny, nz, ot_n, ot_level, deadline=None):
    """Subprocess-isolated live baseline; (ref_ns, note) or None."""
    ref_max_dofs = int(os.environ.get("BENCH_REF_MAX_DOFS", 800_000))
    n_ref_iters = int(os.environ.get("BENCH_REF_ITERS", 10))
    # the timeout covers model REGENERATION in the subprocess too (crash
    # isolation means the in-memory model cannot be reused), hence roomy —
    # but never past the orchestrator's wall budget
    timeout_s = float(os.environ.get("BENCH_REF_TIMEOUT_S", 900))
    if deadline is not None:
        timeout_s = min(timeout_s, max(30.0, deadline - time.monotonic()
                                       - 60.0))
    code = (
        "from pcg_mpi_solver_tpu.bench import measure_ref_ns\n"
        f"measure_ref_ns({kind!r}, {n_dof}, {ref_max_dofs}, {n_ref_iters}, "
        f"{nx}, {ny}, {nz}, {ot_n}, {ot_level})\n")
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              env=_cpu_only_env(),
                              capture_output=True, text=True,
                              timeout=timeout_s)
    except subprocess.TimeoutExpired:
        _log(f"# live baseline timed out after {timeout_s:.0f}s")
        return None
    for line in (proc.stdout or "").splitlines():
        if line.startswith("REF_NS "):
            _, ns, note = line.split(" ", 2)
            return float(ns), note
    tail = (proc.stderr or "").strip().splitlines()[-4:]
    _log(f"# live baseline failed (rc={proc.returncode}): "
         + " | ".join(tail))
    return None


def _accel_platform():
    """Platform label of device 0 (separate function so tests can fake a
    non-CPU platform without touching the real jax device list)."""
    import jax

    return jax.devices()[0].platform


def _run_config_extra(solver, dtype, mode, n_parts, t_part, platform,
                      setup=None):
    """The run-configuration detail keys shared by the warm-insurance
    line and the final emitted line (one place, so the two cannot
    drift).  ``setup`` carries the warm-path attribution fields
    (setup_s / setup_cache / time_to_first_iter_s — schema-validated,
    obs/schema.py BENCH_DETAIL_NUMERIC)."""
    out = {
        "dtype": dtype,
        "mode": mode,
        "backend": solver.backend,
        # classic-vs-fused A/B field: the engaged PCG loop formulation,
        # so hardware-window lines are directly comparable across
        # BENCH_PCG_VARIANT settings
        "pcg_variant": getattr(
            getattr(getattr(solver, "config", None), "solver", None),
            "pcg_variant", "classic"),
        # jacobi-vs-mg A/B field (BENCH_PRECOND): the engaged
        # preconditioner, so time_to_tol_s / iters read as a
        # time-to-solution A/B across rounds (ROADMAP item 4)
        "precond": getattr(
            getattr(getattr(solver, "config", None), "solver", None),
            "precond", "jacobi"),
        # batched multi-RHS A/B field: the SolverConfig.nrhs block width
        # this round solved (BENCH_NRHS sets it at cfg build) —
        # schema-validated (obs/schema.BENCH_DETAIL_NUMERIC) and present
        # on the insurance/salvage lines too, so an interrupted window
        # still records which width it was measuring
        "nrhs": int(getattr(getattr(getattr(solver, "config", None),
                                    "solver", None), "nrhs", 1) or 1),
        "n_parts": n_parts,
        "partition_s": round(t_part, 2),
        "platform": platform,
    }
    out.update(setup or {})
    return out


class _FirstDispatchSink:
    """Metrics sink that records the wall-clock END of the first device
    dispatch it sees — the bench's ``time_to_first_iter_s`` anchor (the
    dispatch event is emitted when the span closes, so ``t`` is the
    moment the first jitted program — compile included — returned)."""

    def __init__(self):
        self.t_end = None

    def emit(self, ev):
        if self.t_end is None and ev.get("kind") == "dispatch":
            self.t_end = ev.get("t")

    def close(self):
        pass


def _predict_ms_per_iter(detail):
    """Roofline-predicted ms/iter (obs/perf.py) for a bench line, derived
    from the line's OWN detail fields so it works on every leg — final,
    warm insurance, failed-salvage — without a live solver in hand.
    Returns None (-> null) when the model cannot be built; an UNKNOWN
    variant/precond name still raises (the single-source-table loudness
    contract — a mislabeled line must not get a fabricated prediction)."""
    from pcg_mpi_solver_tpu.obs import perf as _perf

    try:
        shape = _perf.shape_from_detail(detail)
        if shape is None:
            return None
        cm = _perf.cost_model(
            shape,
            str(detail.get("pcg_variant", "classic")),
            str(detail.get("precond", "jacobi")),
            int(detail.get("nrhs", 1) or 1),
            _perf.resolve_profile(str(detail.get(
                "device_kind", detail.get("platform", "cpu")))))
        return cm["predicted_ms_per_iter"] or None
    except KeyError:
        raise
    except Exception as e:                              # noqa: BLE001
        _log(f"# cost model unavailable for this line "
             f"({type(e).__name__}: {e}); predicted_ms_per_iter=null")
        return None


def _result_json(model, kind, r1, iters, ref_ns, ref_note, extra):
    dof_iters_per_sec = model.n_dof * iters / r1.wall_s
    # idealized 8-rank reference: perfect 8x scaling of the measured hot loop
    baseline = 8.0 / (ref_ns * 1e-9)
    detail = {
        "n_dof": model.n_dof,
        "model": kind,
        "iters": int(iters),
        "flag": int(r1.flag),
        "relres": float(r1.relres),
        "solve_wall_s": round(r1.wall_s, 4),
        # wall to CONVERGED-at-tol; null when the solve did not converge
        "time_to_tol_s": round(r1.wall_s, 4) if r1.flag == 0 else None,
        "tpu_ms_per_iter": round(r1.wall_s / iters * 1e3, 4),
        "numpy_ref_ns_per_dof_iter": round(ref_ns, 4),
        "baseline_model": (
            "measured numpy re-impl of the reference per-rank hot loop "
            "/ 8 (ideal scaling; real mpi4py+OpenMPI not installable in "
            "this image)"),
        "ref_measured_on": ref_note,
    }
    detail.update(extra)
    # batched-throughput field: dof*iter*rhs/s — equals the primary value
    # at nrhs=1, and shows the batched-matvec amortization at nrhs>1 (the
    # primary metric stays the per-column rate for cross-round
    # comparability).  Emitted on EVERY line (incl. salvage/insurance,
    # which share this function) so the next hardware window can A/B
    # nrhs in one queue entry.
    nrhs = int(detail.get("nrhs", 1) or 1)
    detail["nrhs"] = nrhs
    detail["dof_iter_rhs_per_s"] = round(dof_iters_per_sec * nrhs, 1)
    # Analytic cost-model verdict (ISSUE 12, obs/perf.py): the roofline-
    # predicted ms/iter for THIS line's engaged (variant, precond, nrhs,
    # platform) and measured/predicted — stamped on EVERY leg through
    # this one shared function (final, insurance, failed-salvage), so an
    # interrupted window still records how far off the model was.  Built
    # from the line's own detail fields (a salvage line must be
    # self-describing without a live solver); null when the model cannot
    # be derived — never a fabricated number.
    predicted = _predict_ms_per_iter(detail)
    detail["predicted_ms_per_iter"] = predicted
    detail["model_ratio"] = (
        round(detail["tpu_ms_per_iter"] / predicted, 3)
        if predicted else None)
    detail["phases"] = {k: round(v["total_s"], 3)
                       for k, v in _REC.span_stats().items()}
    return json.dumps({
        "schema": BENCH_SCHEMA,
        "metric": "pcg_dof_iterations_per_second",
        "value": round(dof_iters_per_sec, 1),
        "unit": "dof*iter/s",
        "vs_baseline": round(dof_iters_per_sec / baseline, 3),
        "detail": detail,
    })


def _solve_once(kind, nx, ny, nz, ot_n, ot_level, backend, n_parts, tol,
                mode, dtype, emitter=None):
    """Build the model/solver, warm-solve (compile), timed solve.

    Returns (model, solver, r1, iters, t_part, setup_info) where
    setup_info carries the warm-path attribution fields."""
    import jax

    from pcg_mpi_solver_tpu import RunConfig, SolverConfig, TimeHistoryConfig
    from pcg_mpi_solver_tpu.parallel.mesh import make_mesh
    from pcg_mpi_solver_tpu.solver import Solver

    n_dev = len(jax.devices())
    t_gen0 = time.perf_counter()
    with _REC.span("model_gen", emit=True):
        model = _build_model(kind, nx, ny, nz, ot_n, ot_level)
    _log(f"# model: {model.n_elem} elems / {model.n_dof} dofs "
         f"(gen {time.perf_counter()-t_gen0:.1f}s); devices={n_dev} "
         f"parts={n_parts} dtype={dtype} mode={mode} backend={backend}")

    solver_kw = {}
    if "BENCH_PROGRESS" in os.environ:   # override the SolverConfig default
        solver_kw["mixed_progress_window"] = int(os.environ["BENCH_PROGRESS"])
    cfg = RunConfig(
        solver=SolverConfig(tol=tol, max_iter=20000, dtype=dtype,
                            dot_dtype="float64", precision_mode=mode,
                            # classic|fused|pipelined A/B knob for the
                            # hardware windows (fused = one collective/
                            # iteration; pipelined = that collective
                            # overlapped with the stencil).  An unknown
                            # value fails HERE, loudly, at config build
                            # (SolverConfig validates against
                            # config.PCG_VARIANTS) — never as a silent
                            # classic fallback mislabeling a round.
                            pcg_variant=os.environ.get(
                                "BENCH_PCG_VARIANT", "classic"),
                            # batched multi-RHS block width: the timed
                            # leg solves this many load cases at once
                            # (Solver.solve_many)
                            nrhs=int(os.environ.get("BENCH_NRHS", "1")
                                     or 1),
                            # jacobi|block3|mg preconditioner A/B knob
                            # (mg = the ISSUE-10 geometric V-cycle:
                            # time_to_tol_s is the number to read)
                            precond=(os.environ.get("BENCH_PRECOND",
                                                    "jacobi")
                                     or "jacobi"),
                            mixed_plateau_window=int(
                                os.environ.get("BENCH_PLATEAU", 0)),
                            **solver_kw),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 1.0]),
    )
    # Warm-path cache (cache/): BENCH_CACHE_DIR routes partitions through
    # the content-addressed on-disk cache and AOT-exports the step — the
    # re-run after a lost device then pays
    # near-zero setup.  Off by default: the flagship cold number must
    # stay an honest cold number unless the driver asks for warm.
    cfg.cache_dir = os.environ.get("BENCH_CACHE_DIR", "")
    # Preflight gate (validate/): a generator bug (NaN loads, degenerate
    # octree cell) must fail HERE, in milliseconds, not after the
    # minutes-scale partition+compile of a flagship round.  Run it once
    # explicitly so the round log carries the verdict, then mark the
    # config validated so Solver.__init__ does not re-scan the model.
    from pcg_mpi_solver_tpu.validate import run_preflight

    with _REC.span("preflight", emit=True):
        checks = run_preflight(model, cfg, recorder=_REC,
                               context={"kind": "quasi_static"})
    if checks:
        warned = sum(1 for c in checks if c.status == "warn")
        _log(f"# preflight: {len(checks)} checks ok"
             + (f" ({warned} warning(s))" if warned else ""))
        cfg.preflight = "off"       # already validated this model/config
    t_part0 = time.perf_counter()
    # time_to_first_iter_s anchor: solver-construction start -> end of
    # the FIRST device dispatch (compile included), via a one-shot
    # dispatch-event sink.  This is the bench-schema field that makes
    # cold vs warm setup visible end to end, not just per phase.
    fd_sink = _FirstDispatchSink()
    t_fd0 = time.time()                 # dispatch events carry time.time()
    _REC.add_sink(fd_sink)
    try:
        with _REC.span("partition_upload", emit=True):
            s = Solver(model, cfg, mesh=make_mesh(), n_parts=n_parts,
                       backend=backend, recorder=_REC)
        t_part = time.perf_counter() - t_part0
        _log(f"# partition+upload: {t_part:.2f}s (backend={s.backend}, "
             f"dispatch_cap={s._dispatch_cap})")

        # Warm-up: compile + first solve.
        with _REC.span("warm_solve", emit=True):
            r0 = s.step(1.0)
    finally:
        # first dispatch seen (or never will be): detach the one-shot
        # sink on EVERY exit path — a leaked sink would latch a LATER
        # ladder rung's first dispatch
        _REC.remove_sink(fd_sink)
    _log(f"# warm solve: flag={r0.flag} iters={r0.iters} "
         f"relres={r0.relres:.3e} wall={r0.wall_s:.2f}s (incl. compile)")
    # Warm-path attribution for the bench line.
    setup_info = {
        "setup_s": round(s.setup_s, 3),
        "setup_cache": s.setup_cache,
        "time_to_first_iter_s": (round(fd_sink.t_end - t_fd0, 3)
                                 if fd_sink.t_end is not None else None),
    }
    _log(f"# setup: {setup_info['setup_s']}s "
         f"({setup_info['setup_cache']} partition), first iter at "
         f"{setup_info['time_to_first_iter_s']}s")
    plat = _accel_platform() if emitter is not None else "cpu"
    # ONE run-config detail dict for the insurance line, the
    # failed-timed-solve salvage line, and (via the caller) the final
    # line — three consumers that must not drift in attribution.
    run_extra = _run_config_extra(s, dtype, mode, n_parts, t_part, plat,
                                  setup=setup_info)
    if emitter is not None and r0.flag == 0 and plat != "cpu":
        # Insurance against a device death DURING the timed solve: on
        # 2026-08-01 the device lost mid-timed-dispatch 29 SECONDS after
        # a COMPLETED warm solve (flag=0, 3334 iters, 83.3 s at 10.33M
        # dofs) and the round artifact fell back to a CPU provisional.
        # A converged warm solve is a real accelerator measurement —
        # conservative (wall includes compile + start overhead) and
        # labeled as such; the timed line displaces it at equal rank.
        warm_extra = dict(
            run_extra,
            # the warm solve is the SCALAR step: its line must report
            # the measured width (1), never fabricate nrhs-x batched
            # throughput that was never run; the configured sweep width
            # stays visible as nrhs_planned
            nrhs=1,
            nrhs_planned=run_extra.get("nrhs", 1),
            timing="warm (first solve; wall incl. compile/start "
                   "overhead — conservative)",
            baseline_source="validated-constant",
        )
        wline = _result_json(model, kind, r0, max(r0.iters, 1),
                             VALIDATED_REF_NS_PER_DOF_ITER,
                             _VALIDATED_NOTE, warm_extra)
        _log("# warm-solve accelerator line (insurance): " + wline)
        emitter.offer(wline, rank=4)

    # Measured solve from scratch state (compile cached).  A solver
    # exception HERE (the r05 failure mode: the device died mid-timed-
    # dispatch, 29 s after a completed warm solve) must not abort the
    # round silently: the warm solve is a real accelerator measurement,
    # so a salvage line carrying failed=true + the reason is offered at
    # accelerator rank before the exception continues up to the ladder /
    # fallback chain — the round artifact then records both the number
    # and WHY the timed leg is missing.
    s.reset_state()
    nrhs = int(getattr(cfg.solver, "nrhs", 1) or 1)
    try:
        if nrhs > 1:
            # Batched multi-RHS leg (BENCH_NRHS -> SolverConfig.nrhs):
            # solve an nrhs-wide
            # block of the reference load against the SAME warm
            # operator (Solver.solve_many — one lockstep Krylov loop,
            # collective count independent of nrhs).  A warm blocked
            # solve first so the timed one pays no blocked-program
            # compile, mirroring the scalar warm/timed split.
            from pcg_mpi_solver_tpu.solver.driver import StepResult

            fblk = np.repeat(np.asarray(model.F)[:, None], nrhs, axis=1)
            with _REC.span("warm_solve_many", emit=True):
                s.solve_many(fblk)
            with _REC.span("timed_solve", emit=True):
                mres = s.solve_many(fblk)
            # solve_wall_s excludes the per-call host rhs staging
            # (validate + global->local map + upload): the scalar
            # baseline's step() derives fext in-graph from device data,
            # so the blocked A/B number must not absorb PCIe/host cost
            # the classic leg never pays
            r1 = StepResult(flag=int(mres.flags.max(initial=0)),
                            relres=float(mres.relres.max(initial=0.0)),
                            iters=int(mres.iters.max(initial=0)),
                            wall_s=mres.solve_wall_s)
            # per-column resilience attribution (ISSUE 9): a blocked
            # throughput number that silently absorbed recovery
            # restarts or reported a quarantined column as healthy
            # would benchmark a lie — stamp the counts on the line
            run_extra["nrhs_quarantined"] = len(mres.quarantined)
            run_extra["nrhs_recoveries"] = int(mres.recoveries)
            _log(f"# timed blocked solve: nrhs={nrhs} "
                 f"flags={mres.flags.tolist()} "
                 f"iters={mres.iters.tolist()} wall={r1.wall_s:.3f}s "
                 f"(+{mres.wall_s - mres.solve_wall_s:.3f}s rhs staging, "
                 "excluded; quarantined="
                 f"{list(mres.quarantined)} recoveries={mres.recoveries})")
        else:
            with _REC.span("timed_solve", emit=True):
                r1 = s.step(1.0)
    except Exception as e:                              # noqa: BLE001
        _offer_failed_salvage(
            emitter, model, kind, r0, run_extra,
            f"timed solve died: {type(e).__name__}: {e}")
        raise
    iters = max(r1.iters, 1)
    _log(f"# timed solve: flag={r1.flag} iters={iters} "
         f"relres={r1.relres:.3e} wall={r1.wall_s:.3f}s "
         f"-> {r1.wall_s/iters*1e3:.3f} ms/iter")
    # BENCH_PROFILE=1: one profiled warm rung AFTER the timed solve
    # (the timed number is never perturbed); the measured fields ride
    # setup_info into the final line's detail (the earlier insurance/
    # salvage offers predate the capture and stay unstamped — absent,
    # not null, per obs/schema.py)
    setup_info.update(_capture_bench_profile(s, nrhs))
    return model, s, r1, iters, t_part, setup_info


def _capture_bench_profile(solver, nrhs):
    """BENCH_PROFILE=1 (ISSUE 15): capture + parse ONE profiled warm
    solve on the already-warm solver (obs/profview.py), AFTER the timed
    solve so the timed number is never perturbed.  Returns the
    schema-typed detail fields for the final line —
    ``measured_ms_per_iter_matvec`` / ``overlap_frac`` — when the
    capture actually measured them (absent otherwise: a line must never
    carry a measurement that was not taken).  Best-effort end to end: a
    failed capture/parse logs and returns {} — profiling trouble must
    never cost the round its perf number."""
    if os.environ.get("BENCH_PROFILE") != "1":
        return {}
    from pcg_mpi_solver_tpu.obs import profview

    out = {}
    pdir = os.environ.get("BENCH_PROFILE_DIR", "bench_profile")
    try:
        with _REC.span("profile_capture", emit=True):
            cap = profview.capture_solve_profile(
                solver, pdir, nrhs=max(1, int(nrhs or 1)), recorder=_REC)
        rep = profview.profile_report(cap["artifact"])
        profview.emit_prof_report(_REC, rep)
        mv = (rep["phases"].get("matvec") or {}).get("ms_per_iter")
        if mv is not None:
            out["measured_ms_per_iter_matvec"] = mv
        if rep.get("overlap_frac") is not None:
            out["overlap_frac"] = round(rep["overlap_frac"], 6)
        _log(f"# profiled warm rung: artifact={cap['artifact']} "
             f"verdict={rep['verdict']} matvec_ms_per_iter={mv} "
             f"overlap_frac={rep.get('overlap_frac')} "
             "(read back: pcg-tpu prof-report)")
        # Multi-controller capture (p<idx>/ subdirs): fold the fleet
        # skew verdict into the line — skew_frac / straggler_rank are
        # stamped ONLY when the report measured cross-process skew
        # (bench_detail_fields returns {} otherwise, same
        # never-fabricate contract as the fields above)
        import jax

        from pcg_mpi_solver_tpu.obs import fleet

        frep = fleet.fleet_report(pdir)
        fdet = fleet.bench_detail_fields(frep, jax.process_index())
        if fdet:
            fleet.emit_fleet_report(_REC, frep)
            out.update(fdet)
            _log(f"# fleet skew: skew_frac={fdet['skew_frac']} "
                 f"straggler_rank={fdet['straggler_rank']} "
                 f"straggler=p{frep['straggler']} "
                 "(read back: pcg-tpu fleet-report)")
    except Exception as e:                              # noqa: BLE001
        _log(f"# profile capture failed ({type(e).__name__}: {e}); "
             "continuing unprofiled")
    return out


def _offer_failed_salvage(emitter, model, kind, r0, extra, reason):
    """Salvage line for a solver exception mid-measurement: the WARM
    solve's numbers (a completed accelerator measurement) stamped with
    ``failed``/``fail_reason`` so the round continues with an honest
    artifact instead of aborting (round-5 post-mortem: the device death
    mid-timed-solve aborted the timed line entirely).  No-op when there
    is no emitter or no converged warm solve to salvage."""
    if emitter is None or r0 is None or r0.flag != 0:
        return None
    if str(extra.get("platform", "cpu")).startswith("cpu"):
        return None     # only accelerator measurements rank/salvage at 4
    line = _result_json(
        model, kind, r0, max(r0.iters, 1), VALIDATED_REF_NS_PER_DOF_ITER,
        _VALIDATED_NOTE,
        dict(extra, failed=True, fail_reason=reason,
             # the salvaged numbers come from the SCALAR warm solve —
             # report the measured width (1), keep the planned sweep
             # width visible instead of fabricating batched throughput
             nrhs=1, nrhs_planned=extra.get("nrhs", 1),
             timing="warm (timed solve failed; wall incl. compile/start "
                    "overhead — conservative)",
             baseline_source="validated-constant"))
    _log("# timed solve failed; salvage line (failed=true): " + line)
    emitter.offer(line, rank=4)
    return line


def _ladder(kind, cpu_fallback, provisional=False):
    """Rungs of (nx, ny, nz, ot_n, ot_level), flagship first."""
    def ints(s):
        vals = [int(t) for t in (x.strip() for x in s.split(",")) if t]
        if not vals:
            raise ValueError(f"no sizes in ladder spec {s!r}")
        return vals

    if provisional:
        # the fast-fallback line: must land in MINUTES on the 1-core CPU
        # host (48^3 CPU takes ~tens of minutes — too slow for this job)
        n = int(os.environ.get("BENCH_PROV_NX", 24))
        return [(n, n, n, 0, 0)]
    ot_level = int(os.environ.get("BENCH_OT_LEVEL", 4))
    if kind == "octree":
        if cpu_fallback:
            rungs = os.environ.get("BENCH_CPU_OT_N", "6")
        elif "BENCH_OT_N" in os.environ:     # explicit pin wins, like BENCH_NX
            rungs = os.environ["BENCH_OT_N"]
        else:
            # flagship 22^3 base at level 4 ~= 6M dofs (>= a 5M-dof
            # octree scale target; n=20 measured 4.66M)
            rungs = os.environ.get("BENCH_OT_LADDER", "22,18,12")
        return [(0, 0, 0, n, ot_level) for n in ints(rungs)]
    if cpu_fallback:
        n = int(os.environ.get("BENCH_CPU_NX", 48))
        return [(n, n, n, 0, 0)]
    if any(k in os.environ for k in ("BENCH_NX", "BENCH_NY", "BENCH_NZ")):
        n = int(os.environ.get("BENCH_NX", 150))
        return [(n, int(os.environ.get("BENCH_NY", n)),
                 int(os.environ.get("BENCH_NZ", n)), 0, 0)]
    return [(n, n, n, 0, 0)
            for n in ints(os.environ.get("BENCH_LADDER", "150,128,96"))]


class _Emitter:
    """Exactly-once stdout emitter shared by the main flow and the
    deadline watchdog.  ``best`` always holds the most valuable line
    computed so far, so a watchdog firing mid-upgrade still lands a
    real number (r03 lesson: rc=124 with an empty stdout is the one
    unacceptable outcome).  Offers carry a rank (0 = error sentinel,
    1 = tiny CPU provisional, 2 = mid-size CPU fallback upgrade,
    3 = salvaged earlier-session accelerator line, 4 = live accelerator
    measurement) so a late low-value line can never displace a better
    one."""

    def __init__(self, initial_line):
        self._lock = threading.Lock()
        self.done = False
        self.best = initial_line
        self._rank = 0

    @property
    def rank(self):
        with self._lock:
            return self._rank

    def offer(self, line, rank=1):
        """Record a better line for the watchdog to fall back on; kept
        only if at least as valuable as the current best."""
        with self._lock:
            if not self.done and rank >= self._rank:
                self.best = line
                self._rank = rank
        if rank >= 4:
            # persist a LIVE accelerator line the moment it exists: the
            # watchdog's os._exit(0) raced out main's end-of-run
            # _write_salvage on 2026-08-01 (flagship TPU line emitted to
            # stdout, salvage file never written — deadline-45s fired
            # 2 s before the step ended)
            _write_salvage(line)

    def emit(self, line=None):
        """Print line (or the best recorded one) once; False if already
        emitted.  Salvage-worthy lines are persisted as part of the
        emit so NO exit path can print a live accelerator number
        without recording it for later invocations (dedup in
        _write_salvage makes the double write from main's explicit
        call harmless)."""
        # An EXPLICIT line is the main flow's fresh measured-live result:
        # persist it BEFORE the done check, so a watchdog that emitted
        # first (its os._exit raced out main's end-of-run write on
        # 2026-08-01) cannot drop it from the salvage file — the in-file
        # dedup keeps the double write harmless when we also emit below.
        if line is not None:
            _write_salvage(line)
        with self._lock:
            if self.done:
                return False
            self.done = True
            out = line if line is not None else self.best
            rank = self._rank
            print(out, flush=True)
        # a best-recorded line is only persisted at rank 4 (a rank-3
        # re-labeled salvage must not be re-written — see
        # _salvage_worthy, which also rejects it by content)
        if line is None and rank >= 4:
            _write_salvage(out)
        return True


_SALVAGE_PATH = "bench_salvage.json"
_SALVAGE_LOCK = threading.Lock()


def _git_head():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        out = subprocess.run(["git", "rev-parse", "HEAD"], cwd=repo,
                             capture_output=True, text=True, timeout=10)
        return out.stdout.strip()[:12] or "unknown"
    except Exception:                                   # noqa: BLE001
        return "unknown"


def _salvage_worthy(line):
    """Only real accelerator measurements are worth keeping: a positive
    value whose platform label is not a CPU fallback/provisional, and
    that is not itself a RE-LABELED salvage from an earlier run (else a
    device-less round would refresh the entry's timestamp every run and
    the max-age guard could never expire it)."""
    try:
        d = json.loads(line)
        det = d.get("detail", {})
        plat = str(det.get("platform", ""))
        return float(d.get("value", 0)) > 0 and bool(plat) \
            and not plat.startswith("cpu") \
            and not det.get("salvaged_from_earlier_session")
    except Exception:                                   # noqa: BLE001
        return False


def _write_salvage(line):
    """Record a live accelerator line for LATER invocations (cwd file):
    if the round-end driver run finds no device, a TPU number captured
    earlier in the round (e.g. by a tools/hw_session queue step running
    this same bench) is a far better artifact than any CPU fallback.
    Re-labeled unmistakably on the read side."""
    if not _salvage_worthy(line):
        return
    # offer() (any thread), emit() (watchdog thread) and the main flow
    # may all try to record the same run's line — serialize the whole
    # read-modify-replace and dedup BEFORE the expensive entry build
    # (git rev-parse subprocess)
    with _SALVAGE_LOCK:
        data = {}
        try:
            with open(_SALVAGE_PATH) as f:
                data = json.load(f)
        except (OSError, ValueError):
            pass
        lines = [e for e in data.get("lines", []) if isinstance(e, dict)]
        if any(e.get("line") == line for e in lines):
            return                          # already recorded this run
        entry = {"line": line, "unix_time": time.time(),
                 "measured_at_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                                  time.gmtime()),
                 "git_head": _git_head()}

        # evict AGE-EXPIRED entries first: _read_salvage can never use a
        # line older than BENCH_SALVAGE_MAX_AGE_S, so a stale
        # high-vs_baseline line must not permanently occupy a slot that a
        # fresher (usable) line needs
        max_age = float(os.environ.get("BENCH_SALVAGE_MAX_AGE_S", 43200))
        now = time.time()

        def _fresh(e):
            try:
                return now - float(e["unix_time"]) <= max_age
            except (KeyError, TypeError, ValueError):
                return False        # unreadable timestamp = unusable entry

        lines = [e for e in lines if _fresh(e)]

        # then trim by VALUE, not recency: a fully live wave writes ~3
        # entries per bench step (warm insurance, const-baseline, final
        # line), and dropping the oldest would evict the flagship line
        # the round-end driver exists to re-emit
        def _vsb(e):
            try:
                return float(json.loads(e["line"]).get("vs_baseline", 0.0))
            except Exception:               # noqa: BLE001
                return -1.0

        while len(lines) > 7:
            lines.remove(min(lines, key=_vsb))
        lines.append(entry)
        try:
            from pcg_mpi_solver_tpu.utils.io import write_atomic

            # per-process+thread tmp (write_atomic): the watchdog thread
            # and main — or two bench processes in one cwd — may salvage
            # concurrently
            write_atomic(_SALVAGE_PATH,
                         json.dumps({"lines": lines}, indent=1).encode())
            _log(f"# accelerator line recorded in {_SALVAGE_PATH} "
                 "for salvage by later invocations")
        except OSError as e:
            _log(f"# salvage write failed ({e}); continuing")


def _read_salvage():
    """Best fresh accelerator line from a previous invocation, re-labeled
    so it cannot be mistaken for a live measurement; None if absent,
    stale, or disabled (BENCH_SALVAGE=0 — the hardware queues disable it
    so a device-less wave step cannot look like a fresh success)."""
    if os.environ.get("BENCH_SALVAGE", "1") != "1":
        return None
    max_age = float(os.environ.get("BENCH_SALVAGE_MAX_AGE_S", 43200))
    try:
        with open(_SALVAGE_PATH) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    # prefer entries matching THIS invocation's configuration (a
    # BENCH_MODE=direct run must not re-emit a mixed-mode line as its
    # anchor); fall back to the best any-config accelerator line — still
    # better round evidence than any CPU fallback, and self-describing
    want = (os.environ.get("BENCH_MODEL", "cube"),
            os.environ.get("BENCH_MODE", "mixed"),
            os.environ.get("BENCH_DTYPE", "float32"))
    best = None
    best_key = (-1, -1.0)       # (config_match, vs_baseline)
    now = time.time()
    for e in data.get("lines", []):
        try:
            age = now - float(e["unix_time"])
            if age > max_age or not _salvage_worthy(e["line"]):
                continue
            d = json.loads(e["line"])
            det = d.get("detail", {})
            match = int((det.get("model"), det.get("mode"),
                         det.get("dtype")) == want)
            key = (match, float(d.get("vs_baseline", 0)))
            if key > best_key:
                best_key = key
                best = (d, e, age)
        except (KeyError, TypeError, ValueError):
            continue
    if best is None:
        return None
    d, e, age = best
    det = d.setdefault("detail", {})
    det["salvaged_from_earlier_session"] = True
    det["salvage_measured_at_utc"] = e.get("measured_at_utc")
    det["salvage_age_s"] = round(age)
    det["salvage_git_head"] = e.get("git_head")
    det["salvage_note"] = (
        "accelerator measurement captured earlier this round by an "
        "invocation of this same bench; the "
        "device was unreachable when THIS invocation ran — not measured "
        "live by this process")
    return json.dumps(d)


def _attach_flight():
    """Crash-durable flight recorder around the bench run (obs/flight.py,
    ISSUE 12).  Every Solver dispatch is bracketed by fsync'd begin/end
    records (the Solver shares ``_REC``) and each ladder rung gets its
    own bracket, so a device loss / SIGKILL mid-timed-dispatch leaves a
    parseable artifact naming the in-flight program.

    A LEFTOVER artifact from a previous invocation is ingested
    MECHANICALLY first: its verdict (clean / failed / died + what was in
    flight) is logged, then the file rotates to ``.prev`` so this run's
    verdict cannot inherit the dead run's unclosed brackets.  Disable
    with BENCH_FLIGHT=0 (the provisional/upgrade subprocesses do — they
    share the parent's cwd and must not interleave with its stream)."""
    path = os.environ.get("BENCH_FLIGHT", "bench_flight.jsonl")
    if not path or path == "0":
        return None
    from pcg_mpi_solver_tpu.obs.flight import (
        FlightRecorder, ingest_and_rotate)

    path = ingest_and_rotate(path, _log,
                             label="# previous bench flight record")
    try:
        _REC.flight = FlightRecorder(path, meta={
            "component": "bench",
            "model": os.environ.get("BENCH_MODEL", "cube"),
            "pcg_variant": os.environ.get("BENCH_PCG_VARIANT", "classic"),
            "precond": os.environ.get("BENCH_PRECOND", "jacobi"),
            "nrhs": os.environ.get("BENCH_NRHS", "1")})
    except (OSError, ValueError) as e:
        _log(f"# flight recorder unavailable ({e}); continuing without")
        _REC.flight = None
    return _REC.flight


def _error_line(why):
    """Last-ditch zero-value line: clearly labeled, parseable, and
    impossible to mistake for a measurement."""
    return json.dumps({
        "schema": BENCH_SCHEMA,
        "metric": "pcg_dof_iterations_per_second",
        "value": 0.0,
        "unit": "dof*iter/s",
        "vs_baseline": 0.0,
        "detail": {"error": why,
                   "note": "no solve completed inside the wall budget; "
                           "this is a sentinel, not a measurement"},
    })


class _ProvisionalRun:
    """A CPU fallback solve in a subprocess.  Default configuration is the
    t=0 fast provisional (small cube even for BENCH_MODEL=octree: the
    hybrid octree program's multi-minute CPU compile would defeat the
    purpose); the probe-failure path reuses it with ``provisional=False``
    + env overrides for the mid-size budget-filling upgrade run."""

    def __init__(self, env_extra=None, logname="bench_fallback.log",
                 provisional=True):
        env = _cpu_only_env()
        env["BENCH_FORCE_CPU"] = "1"
        env["BENCH_MODEL"] = "cube"
        # the fallback subprocess shares the parent's cwd: its flight
        # records must not interleave with (or rotate) the parent's —
        # neither through the bench recorder nor through a Solver
        # picking up the operator's PCG_TPU_FLIGHT default
        env["BENCH_FLIGHT"] = "0"
        env["PCG_TPU_FLIGHT"] = ""
        if provisional:
            env["BENCH_PROVISIONAL"] = "1"
        else:
            env.pop("BENCH_PROVISIONAL", None)
        env.update(env_extra or {})
        self._line = None
        self._got = threading.Event()
        try:
            logf = open(logname, "w")
        except OSError:
            logf = subprocess.DEVNULL
        try:
            self._proc = subprocess.Popen(
                [sys.executable, "-m", "pcg_mpi_solver_tpu.bench"],
                env=env, stdout=subprocess.PIPE, stderr=logf, text=True)
        except OSError as e:
            _log(f"# provisional launch failed ({e}); no fast fallback")
            self._proc = None
            self._got.set()
            return
        finally:
            if logf is not subprocess.DEVNULL:
                logf.close()    # the child holds its own descriptor
        threading.Thread(target=self._reader, daemon=True).start()

    def _reader(self):
        out, _ = self._proc.communicate()
        for ln in (out or "").splitlines():
            ln = ln.strip()
            if ln.startswith("{"):
                self._line = ln
        if self._line is None:
            _log(f"# provisional subprocess produced no line "
                 f"(rc={self._proc.returncode}; see bench_fallback.log)")
        self._got.set()

    def line(self, timeout_s=0.0):
        self._got.wait(timeout=timeout_s)
        return self._line

    def kill(self):
        if self._proc is not None and self._proc.poll() is None:
            self._proc.kill()


def _fallback_chain(emitter, prov, deadline, why):
    """Accelerator-less endgame (probe exhausted or bench crashed): offer
    every fallback in value order, then emit the best available line.

    1. the t=0 tiny CPU provisional (rank 1 — liveness floor);
    2. a mid-size CPU measurement filling the remaining wall budget
       (rank 2 — the 46,875-dof provisional left
       ~1,000 s of budget unspent; a >=350k-dof f64 line is evidence,
       not just liveness);
    3. a salvaged accelerator line from an earlier invocation this round
       (rank 3 — outranks any CPU number and skips the upgrade burn).

    Rank 4 (live accelerator) may already sit in the emitter if the crash
    happened after a timed solve; nothing here can displace it."""
    ln = prov.line(timeout_s=max(5.0, min(
        300.0, deadline - time.monotonic() - 60.0)))
    if ln is not None:
        emitter.offer(ln, rank=1)
    salv = _read_salvage()
    if salv is not None:
        _log("# salvaging the accelerator line measured earlier this "
             "round (re-labeled in detail.salvage_note)")
        emitter.offer(salv, rank=3)
    elif (os.environ.get("BENCH_CPU_UPGRADE", "1") == "1"
          and emitter.rank < 2):
        left = deadline - time.monotonic() - 120.0
        if left >= 240.0:
            _log(f"# upgrading the CPU fallback with the remaining wall "
                 f"budget ({left:.0f}s, "
                 f"{os.environ.get('BENCH_UPGRADE_NX', '48')}^3 "
                 f"{os.environ.get('BENCH_UPGRADE_DTYPE', 'float64')} "
                 f"{os.environ.get('BENCH_UPGRADE_MODE', 'direct')})")
            up = _ProvisionalRun(
                env_extra={
                    "BENCH_MODE": os.environ.get("BENCH_UPGRADE_MODE",
                                                 "direct"),
                    "BENCH_DTYPE": os.environ.get("BENCH_UPGRADE_DTYPE",
                                                  "float64"),
                    "BENCH_CPU_NX": os.environ.get("BENCH_UPGRADE_NX",
                                                   "48"),
                },
                logname="bench_upgrade.log", provisional=False)
            ln2 = up.line(timeout_s=left)
            up.kill()
            if ln2 is not None:
                emitter.offer(ln2, rank=2)
            else:
                _log("# CPU upgrade produced no line in budget "
                     "(see bench_upgrade.log); keeping the provisional")
    if emitter.rank == 0:
        emitter.emit(_error_line(why))
    else:
        emitter.emit()


def main():
    t0 = time.monotonic()
    if os.environ.get("BENCH_SETUP_LADDER"):
        # ISSUE 14: the weak-scaling SETUP ladder leg — CPU-only by
        # design (it measures partition build / ingest / warm-cache
        # walls across jax.distributed process counts, never the
        # accelerator), so it runs before any probe/orchestration
        from pcg_mpi_solver_tpu.setup_ladder import main as ladder_main

        sys.exit(ladder_main())
    if os.environ.get("BENCH_SERVE"):
        # ISSUE 19: sustained solve-service throughput — saturated
        # queue with nrhs packing vs one-at-a-time dispatch, in one
        # process (no orchestration; the leg times dispatch, not the
        # probe ladder)
        from pcg_mpi_solver_tpu.serve.bench import main as serve_main

        sys.exit(serve_main())
    # a stale provisional file from a previous crashed run must not be
    # salvageable as THIS run's number
    try:
        os.remove("bench_provisional.json")
    except OSError:
        pass
    provisional = os.environ.get("BENCH_PROVISIONAL") == "1"
    if os.environ.get("BENCH_FORCE_CPU") == "1":
        # subprocess / debug mode: run the bench body directly on CPU and
        # print its one line (no orchestration — the parent handles that)
        os.environ["JAX_PLATFORMS"] = "cpu"   # must hold before import jax
        print(_run_bench(cpu_fallback=True, provisional=provisional),
              flush=True)
        return

    # --- top-level orchestrator: fallback first, upgrade second ---
    wall = float(os.environ.get("BENCH_WALL_BUDGET_S", 1680))
    deadline = t0 + wall
    emitter = _Emitter(_error_line("bench still starting up"))
    _attach_flight()
    prov = _ProvisionalRun()

    def watchdog():
        # fire with enough margin to flush stdout before the driver kills
        while not emitter.done:
            left = deadline - 45.0 - time.monotonic()
            if left <= 0:
                break
            time.sleep(min(left, 5.0))
        if emitter.done:
            return
        ln = prov.line(timeout_s=0.0)
        if ln is not None:
            emitter.offer(ln, rank=1)   # never displaces a TPU line (rank 4)
        try:
            # a hung accelerator path (e.g. a cold remote compile
            # overrunning the budget) must not downgrade the artifact to
            # the provisional while a salvaged TPU line sits on disk
            salv = _read_salvage()
            if salv is not None:
                emitter.offer(salv, rank=3)
        except Exception:                               # noqa: BLE001
            pass                # the watchdog must never die pre-emit
        _log("# WALL BUDGET EXHAUSTED — watchdog emitting best available "
             "line and exiting")
        emitter.emit()
        sys.stdout.flush()
        prov.kill()
        os._exit(0)

    threading.Thread(target=watchdog, daemon=True).start()

    # every exit path below must reap the provisional child: an orphaned
    # 24^3 solve would keep burning the 1-core host's only CPU under
    # whatever the external driver runs next
    try:
        probe_budget = min(
            float(os.environ.get("BENCH_PROBE_BUDGET_S", 600)),
            max(0.0, deadline - time.monotonic() - 360.0))
        ok, detail = _probe_with_retry(budget_s=probe_budget)
        if not ok:
            if os.environ.get("BENCH_CPU_FALLBACK", "1") != "1":
                _log(f"# FATAL: {detail}\n# No perf number can be produced "
                     "from this host.")
                sys.exit(3)
            _log(f"# accelerator unreachable after probe budget: {detail}\n"
                 "# falling back (salvage / CPU upgrade / provisional — "
                 "clearly labeled; NOT the TPU north-star number)")
            _fallback_chain(emitter, prov, deadline,
                            f"accelerator unreachable ({detail}) "
                            "and every CPU fallback failed")
            return

        try:
            line = _run_bench(cpu_fallback=False, deadline=deadline,
                              emitter=emitter)
        except SystemExit:
            raise
        except Exception as e:                          # noqa: BLE001
            _log(f"# accelerator bench failed ({type(e).__name__}: {e}); "
                 "falling back (salvage / CPU upgrade / provisional)")
            _fallback_chain(emitter, prov, deadline,
                            f"accelerator bench failed "
                            f"({type(e).__name__}: {e}) and every CPU "
                            "fallback failed")
            return
        emitter.emit(line)
    finally:
        prov.kill()
        fl = getattr(_REC, "flight", None)
        if fl is not None:
            fl.close()


def _run_bench(cpu_fallback, provisional=False, deadline=None, emitter=None):
    """The bench body: probe already done (or CPU pinned).  Returns the
    final JSON line; registers intermediate lines on ``emitter`` so the
    watchdog always has the best available number."""
    import jax

    # jax binds JAX_PLATFORMS at import: a CPU request made after that
    # (the BENCH_FORCE_CPU child sets it first) must still hold
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        jax.config.update("jax_platforms", "cpu")

    # Dispatch breadcrumbs on by default: a hung compile/execute must be
    # localizable from the driver's captured stderr.
    os.environ.setdefault("PCG_TPU_VERBOSE", "1")
    from pcg_mpi_solver_tpu.cache.aot import use_compilation_cache

    use_compilation_cache()
    kind = os.environ.get("BENCH_MODEL", "cube")   # cube | octree
    tol = float(os.environ.get("BENCH_TOL", 1e-7))
    mode = os.environ.get("BENCH_MODE", "mixed")   # mixed | direct
    backend = os.environ.get("BENCH_BACKEND", "auto")
    dtype = os.environ.get("BENCH_DTYPE", "float32")
    n_parts = int(os.environ.get("BENCH_PARTS", len(jax.devices())))

    ladder = _ladder(kind, cpu_fallback, provisional)
    # loop invariant: reaching the emit below implies the LAST iteration
    # assigned all of these (every failure path raises)
    for rung_i, (nx, ny, nz, ot_n, ot_level) in enumerate(ladder):
        last = rung_i == len(ladder) - 1
        rung = ladder[rung_i]
        failed = None
        # flight bracket per rung (on top of the Solver's per-dispatch
        # brackets): a killed run's artifact names which ladder size was
        # in flight, not just which program
        fl = getattr(_REC, "flight", None)
        fl_seq = (fl.begin(f"rung:{rung_i}", nx=nx, ot_n=ot_n)
                  if fl is not None else None)
        try:
            model, solver, r1, iters, t_part, setup_info = \
                _solve_once(
                    kind, nx, ny, nz, ot_n, ot_level, backend, n_parts,
                    tol, mode, dtype, emitter=emitter)
            if fl is not None:
                fl.end(fl_seq, f"rung:{rung_i}", ok=True)
        except Exception as e:                      # noqa: BLE001
            if fl is not None:
                # descending to a smaller rung is the ladder working BY
                # DESIGN — only the last rung's failure fails the run,
                # so only that one may make the artifact read "failed"
                fl.end(fl_seq, f"rung:{rung_i}", ok=False,
                       error=f"{type(e).__name__}: {e}",
                       expected=not last)
            if last:
                raise
            failed = f"{type(e).__name__}: {e}"
            model = solver = r1 = None
        # a non-converged timed solve is also a failed rung (a smaller
        # model that converges beats a flagship number at flag!=0)
        if failed is None and r1.flag != 0 and not last:
            failed = f"flag={r1.flag} after {iters} iters"
            model = solver = r1 = None
        if failed is None:
            break
        _log(f"# ladder rung {rung_i} failed ({failed}); stepping down")
        if deadline is not None and time.monotonic() > deadline - 240.0:
            raise RuntimeError(
                f"ladder rung {rung_i} failed ({failed}) and the remaining "
                "wall budget cannot fit another rung")
        import gc

        gc.collect()                                # free device buffers

    extra = _run_config_extra(
        solver, dtype, mode, n_parts, t_part, _accel_platform() + (
            " (CPU PROVISIONAL — fast fallback so the round artifact "
            "cannot be empty; not the TPU north-star number)"
            if provisional else
            " (CPU FALLBACK — accelerator unreachable; not the TPU "
            "north-star number)" if cpu_fallback else ""),
        setup=setup_info)
    if provisional:
        extra["provisional"] = True

    # Validated-constant record FIRST (stderr + file, NOT stdout — the
    # driver parses stdout and must see exactly one JSON line): the perf
    # number must survive anything that follows.
    const_line = _result_json(
        model, kind, r1, iters, VALIDATED_REF_NS_PER_DOF_ITER,
        _VALIDATED_NOTE, dict(extra, baseline_source="validated-constant"))
    _log("# provisional (validated-constant baseline): " + const_line)
    if emitter is not None:
        emitter.offer(const_line, rank=4)   # the watchdog's fallback is
        #                                     now a REAL accelerator line
    if not provisional:
        # the fast-fallback SUBPROCESS must not write the crash-insurance
        # file: it shares the parent's cwd, and its tiny CPU line landing
        # late would overwrite the parent's accelerator line (stdout is
        # the subprocess's only channel)
        try:
            with open("bench_provisional.json", "w") as f:
                f.write(const_line + "\n")
        except OSError:
            pass
        # cross-run salvage happens at offer(rank=4) above (self-gated
        # on the platform label, so CPU fallback/upgrade lines never
        # land there)

    if provisional:
        # the fast-fallback subprocess: the validated constant IS the
        # baseline (a live numpy measurement would double its runtime)
        return const_line

    # Live baseline in a crash-isolated subprocess (numpy-only, CPU),
    # bounded by the remaining wall budget.
    if deadline is not None and time.monotonic() > deadline - 90.0:
        _log("# skipping live baseline (wall budget); "
             "returning validated-constant line")
        return const_line
    with _REC.span("live_baseline", emit=True):
        live = _live_baseline(kind, model.n_dof, rung[0], rung[1], rung[2],
                              rung[3], rung[4], deadline=deadline)
    if live is not None:
        ref_ns, ref_note = live
        _log(f"# numpy ref ({ref_note}): {ref_ns:.3f} ns/dof-iter")
        return _result_json(model, kind, r1, iters, ref_ns, ref_note,
                            dict(extra, baseline_source="measured-live"))
    _log("# live baseline unavailable; returning validated-constant line")
    return const_line


if __name__ == "__main__":
    main()
