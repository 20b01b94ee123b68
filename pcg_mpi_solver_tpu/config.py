"""Typed configuration for the framework.

Replaces the reference's three config mechanisms — positional argv, zlib-pickled
``GlobSettings.zpkl``/``ModelDataPaths.zpkl`` dicts, and hardcoded constants
(reference: src/solver/pcg_solver.py:113-139, examples/run_basic_script.bash:30-49)
— with one set of dataclasses.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

# ---------------------------------------------------------------------------
# Canonical PCG loop-formulation name set (SolverConfig.pcg_variant).
# THE single source every variant-name surface derives from, so an
# unknown variant fails loudly everywhere instead of silently falling
# through one layer's default:
#   * SolverConfig.__post_init__ (below) — config construction,
#   * solver/pcg.py VALID_PCG_VARIANTS — the loop builders,
#   * ops/matvec.py PCG_SCALAR_PSUMS — the collective contract table
#     (an import-time assert pins its keys to this tuple),
#   * cache/keys.py step_cache_key — AOT cache keying,
#   * cli.py --pcg-variant choices and bench.py BENCH_PCG_VARIANT.
# Lives here (not ops/) because this module is jax-free by contract and
# every one of those consumers may import it before the accelerator
# environment is configured.
PCG_VARIANTS = ("classic", "fused", "pipelined")

# Canonical preconditioner name set (SolverConfig.precond) — the same
# single-source discipline as PCG_VARIANTS above.  Derived consumers:
#   * ops/precond.py VALID_PRECONDS — the prec builders (an import-time
#     guard pins its value to this tuple),
#   * obs/perf.py — the analytic per-iteration cost model enumerates
#     PCG_VARIANTS x PRECONDS; an unknown name is a loud KeyError,
#   * analysis/ cost-model-completeness rule — proves that enumeration
#     is total,
#   * cli.py --precond choices.
# Lives here because this module is jax-free by contract and obs/ and
# cli.py may consume it before the accelerator environment is
# configured.
PRECONDS = ("jacobi", "block3", "mg")


@dataclasses.dataclass
class SolverConfig:
    """PCG solver parameters (reference SolverParam, pcg_solver.py:131-132)."""

    tol: float = 1e-7
    max_iter: int = 10000
    # Numerical precision of the solve.  The reference is float64 throughout.
    # precision_mode:
    #   "direct" — one PCG in `dtype` (use float64 for reference parity);
    #   "mixed"  — f32 Krylov iterations + f64 iterative-refinement restarts:
    #              reaches f64-grade residuals at f32/MXU speed (the TPU
    #              performance path).
    precision_mode: str = "direct"
    dtype: str = "float64"        # storage dtype: "float32" | "float64"
    dot_dtype: str = "float64"    # accumulation dtype for reductions
    inner_tol: float = 1e-5       # per-refinement-cycle residual reduction (mixed)
    # Mixed mode only, EXPERIMENTAL (default off): exit an f32 inner cycle
    # after this many consecutive iterations without a meaningful (0.1%)
    # new minimal residual, handing back to the f64 refinement early.
    # Off by default because CG's residual is non-monotone pre-
    # asymptotically — a short window false-triggers during healthy
    # convergence (measured: window 5 exits at iteration 1 on a 690-dof
    # cube) and each premature restart discards the Krylov space.  Enable
    # only with an on-hardware A/B at the target scale.
    mixed_plateau_window: int = 0
    # Mixed mode only, default OFF (0): progress-RATE exit for f32 inner
    # cycles.  Every `mixed_progress_window` iterations the MONOTONE
    # minimal residual is compared to a window ago; if the window
    # contracted it by less than 1/mixed_progress_ratio AND the cycle has
    # already contracted the (normalized) rhs by mixed_progress_min_gain,
    # the cycle exits to the f64 refinement restart.  The design target
    # was the f32-floor grind at 10.33M dofs, but the
    # first A/B at a scale where the exit actually FIRES measured it
    # NEGATIVE: 96^3 / 2.74M dofs mixed, window 150: 2486 total
    # iterations vs 2009 with the exit off (+24% — premature restarts
    # discard more Krylov progress than the grind they cut), identical
    # convergence otherwise; at 64^3 / 824k dofs the exit never fires
    # (bit-identical).  2026-08-01, examples/bench_progress_ab.py.
    # Kept as an opt-in knob for an on-hardware A/B at the true flagship
    # scale (BENCH_PROGRESS=150), where the floor-grind geometry differs.
    mixed_progress_window: int = 0
    mixed_progress_ratio: float = 0.7
    mixed_progress_min_gain: float = 30.0
    # MATLAB-pcg compatibility knobs (pcg_solver.py:399-404)
    max_stag_steps: int = 3
    # PCG loop formulation (solver/pcg.py):
    #   "classic" — the MATLAB-pcg-compatible loop: three serialized
    #               scalar/fused psums per iteration (rho+inf-prec, p.q,
    #               fused 3-norm).  Bit-exact reference parity; default.
    #   "fused"   — Chronopoulos–Gear single-reduction recurrence: rho,
    #               the p.q denominator, the residual norm, the
    #               stagnation norms and the inf-preconditioner flag all
    #               come from ONE fused psum per iteration, and A.p
    #               advances by recurrence (q = A.z + beta*q) so the
    #               stencil still runs once per iteration.  Cuts the
    #               per-iteration latency spent between the matvecs at
    #               scale.  Convergence
    #               checks lag the iterate by one iteration (the
    #               pipelined-CG tradeoff), so iteration counts differ
    #               from classic by O(1) and results are NOT bit-exact
    #               with the reference — see docs/RUNBOOK.md "Choosing
    #               pcg_variant".
    #   "pipelined" — Ghysels–Vanroose depth-1 pipelined CG
    #               (arXiv:2105.06176 §3): still ONE fused psum per
    #               iteration, but its operands are all PREVIOUS-
    #               iteration recurrence state, so the psum carries no
    #               data dependence on (and none from) the iteration's
    #               stencil matvec — XLA is free to run the reduction
    #               CONCURRENTLY with the matvec, hiding the last
    #               collective's latency entirely (statically proven by
    #               the analysis/ psum-overlap rule).  The price: four
    #               extra recurrence vectors in the carry (u/w/s/z) and
    #               faster residual-recurrence drift than fused
    #               (arXiv:2501.03743 §4) — guarded by a LOWER drift
    #               limit (solver/pcg.PIPELINED_DRIFT_LIMIT) feeding the
    #               same recoverable flag 6.  Iteration counts differ
    #               from classic by O(1); NOT bit-exact with the
    #               reference.  CLI: --pcg-variant; bench:
    #               BENCH_PCG_VARIANT.
    pcg_variant: str = "classic"
    # Default RHS-block width for batched multi-RHS solves
    # (Solver.solve_many / `pcg-tpu solve-many` / bench BENCH_NRHS): the
    # number of load cases solved together against ONE shared partitioned
    # operator, with a per-RHS convergence mask in the while-loop
    # predicate (solver/pcg.pcg_many).  The per-type element matmul
    # batches to (d x d) @ (d x N_elem x nrhs) — higher MXU utilization
    # at near-constant memory traffic — and the per-iteration collective
    # COUNT is independent of nrhs (payloads widen instead; statically
    # proven by tools/check_collectives.py).  Memory cost: the Krylov
    # carry holds ~5 blocked vectors, so HBM grows ~linearly in nrhs.
    # 1 = the scalar paths are untouched.  Consumers: bench.py's timed
    # leg (BENCH_NRHS sets it) solves an nrhs-wide block, and `pcg-tpu
    # solve-many` stamps the request width here so AOT cache keys /
    # snapshot fingerprints / telemetry record it; the block actually
    # passed to Solver.solve_many always defines the executed width.
    nrhs: int = 1
    # Preconditioner: "jacobi" (scalar diag(K)^-1 — the reference's only
    # choice, pcg_solver.py:346-352), "block3" (assembled 3x3 node-block
    # Jacobi, inverted per node — stronger on vector-valued elasticity;
    # beyond-reference, BASELINE.json config 4 "block-Jacobi"), or "mg"
    # (matrix-free geometric multigrid V-cycle on the octree/structured
    # level lattice, ops/mg.py — a FIXED symmetric PSD operator, so
    # plain CG stays valid: fixed-degree Chebyshev–Jacobi smoothing with
    # setup-time eigenvalue bounds, replicated collective-free coarse
    # levels, one restriction psum per cycle.  Cuts iteration counts
    # >=5x on the lattice models at the cost of 2*mg_smooth_degree
    # assembled matvecs per iteration; needs lattice metadata
    # (ModelData.grid or .octree with 2:1-coarsenable even dims —
    # preflight-checked) and the general or structured backend.  The
    # recovery ladder demotes a broken mg hierarchy to scalar Jacobi
    # instead of failing (docs/RUNBOOK.md "Choosing a preconditioner").
    # CLI: --precond; bench: BENCH_PRECOND.)
    precond: str = "jacobi"
    # MG V-cycle shape knobs (precond="mg" only; both are STRUCTURAL —
    # they reshape the traced cycle, so they key the AOT step cache and
    # the snapshot fingerprint via the mg_shape component):
    #   mg_levels        — coarse levels below the fine lattice; 0 =
    #                      auto (halve while every dim stays even, down
    #                      to a few cells per dim).
    #   mg_smooth_degree — Chebyshev smoothing degree per level; the
    #                      fine level pays 2*degree assembled matvecs
    #                      per V-cycle (ops/matvec.precond_cycle_cost).
    mg_levels: int = 0
    mg_smooth_degree: int = 2
    # MG replication scale audit (ISSUE 14): cap on the CUMULATIVE
    # replicated coarse-level dof count.  PR 9 replicates every coarse
    # level on every device (that is what makes the coarse cycle
    # collective-free), but at 1B fine dofs the first coarse level alone
    # is ~125M dofs PER DEVICE — replication becomes the memory ceiling
    # long before the fine level does.  The builder truncates auto-depth
    # hierarchies at the cutoff and REJECTS (named reason,
    # ops/mg.apply_replication_cutoff) configs whose first coarse level
    # cannot fit, or whose explicit mg_levels request would have to be
    # silently truncated; validate/ preflights the same arithmetic.
    # Default 32M dofs ~= 256 MB/level-vector f64 — comfortably inside
    # one device at today's scales, loud long before 1B.  0 = no cutoff.
    # Structural when it bites (it reshapes the hierarchy): rides the
    # solver dict into step_cache_key and the mg_shape fingerprint.
    mg_max_replicated_dofs: int = 32_000_000
    # Split the solve into several device dispatches of at most this many
    # Krylov iterations each (-1 = auto: engage on large problems, sized so
    # one dispatch stays well under a minute; 0 = single dispatch).  Long
    # single dispatches can trip execution watchdogs, and a lost device
    # then costs one chunk; state stays on device between dispatches.
    iters_per_dispatch: int = -1
    # In-graph convergence tracing (obs/trace.py): ring-buffer length for
    # the per-iteration (normr, rho, stag, flag) trace threaded through
    # the PCG carry on device.  0 = off (the compiled program is then
    # bit-identical to no-telemetry).  When on, the ring holds the LAST
    # `trace_resid` iterations (clamped to max_iter) and crosses to the
    # host ONCE per solve.  CLI: --trace-resid.
    trace_resid: int = 0
    # Donated-carry dispatch: donate the resumable Krylov carry (and the
    # previous solution vector of the one-shot step) to XLA across
    # chunked dispatches and mixed-refinement cycles, so the multi-vector
    # carry is updated in place instead of copied every dispatch.
    # Numerically a no-op (bit-identical on/off — asserted in
    # tests/test_cache.py); off is a debugging escape hatch for
    # inspecting carries between dispatches.
    donate_carry: bool = True
    # Resilience (resilience/ subsystem): bounded recovery-ladder
    # attempts for flag-2/4 breakdowns, NaN/Inf carries, and device-loss
    # dispatch failures on the chunked dispatch paths (quasi-static
    # solver/driver.py AND the Newmark stepper) — min-residual restart
    # -> scalar-Jacobi fallback preconditioner -> f64 escalation (mixed
    # mode), each attempt an obs/metrics `recovery` event.  The same
    # budget bounds the time-history drivers' NaN/Inf
    # rollback-to-last-snapshot (solver/dynamics.py, solver/newmark.py).
    # 0 disables recovery (the historical report-and-stop behavior).
    # Healthy solves never enter it, so the default is on.
    # CLI: --max-recoveries.
    max_recoveries: int = 2
    # Device-loss dispatch retries per solve step (resilience dispatch
    # guard): a failed chunked dispatch is retried with backoff from the
    # last mid-Krylov snapshot (PCG_TPU_RETRY_BACKOFF_S tunes the base
    # backoff).  Needs RunConfig.snapshot_every > 0 to have a snapshot
    # to re-dispatch from; without one the failure escalates to the
    # recovery ladder's step restart.
    dispatch_retries: int = 2
    # Kept only because the benchmark configurations pass it: "auto" and
    # "off" both run the XLA stencil, the one matvec each backend has.
    # There is no kernel, so "on" and "interpret" are refused.
    pallas: str = "auto"

    def __post_init__(self):
        # fail at CONSTRUCTION, with the same named set every other
        # surface derives from (PCG_VARIANTS above) — a typo'd variant
        # must never survive to a driver/cache/analysis layer that
        # would each have its own idea of the valid names
        if self.pcg_variant not in PCG_VARIANTS:
            raise ValueError(
                f"SolverConfig.pcg_variant must be one of "
                f"{PCG_VARIANTS}, got {self.pcg_variant!r}")
        if self.pallas not in ("auto", "off"):
            raise ValueError(
                f"SolverConfig.pallas={self.pallas!r}: there is no Pallas "
                f"matvec kernel; 'auto' and 'off' both run the XLA stencil")


@dataclasses.dataclass
class TimeHistoryConfig:
    """Quasi-static time stepping + export settings.

    Mirrors the reference TimeHistoryParam (run_basic_script.bash:34-39).
    ``time_step_delta[t]`` scales both the prescribed displacement ``Ud`` and
    the reference load ``F`` at step t (Dirichlet lifting, pcg_solver.py:226-238).
    """

    time_step_delta: Sequence[float] = (0.0, 1.0)
    export_flag: bool = True
    export_frame_rate: int = 1
    export_frames: Sequence[int] = ()
    plot_flag: bool = False
    export_vars: str = "U"   # subset of "U D ES PS PE PS1..PS3 PE1..PE3"
    dt: float = 1.0
    # Probe dofs sampled every step into PlotData (reference RefPlotDofVec,
    # partition_mesh.py:142 + pcg_solver.py:817-838)
    probe_dofs: Sequence[int] = ()


@dataclasses.dataclass
class RunConfig:
    """Top-level run description (paths + partitioning + solver)."""

    scratch_path: str = "./scratch"
    model_name: str = "model"
    run_id: str = "1"
    n_parts: int = 1
    # Element->part assignment: "rcb" (coordinate bisection), "graph"
    # (native multilevel dual-graph partitioner — the METIS-equivalent
    # path, reference run_metis.py:84-88), or "auto".
    partition_method: str = "rcb"
    speed_test: bool = False
    # In-solve checkpointing: write solver state every N completed time
    # steps (0 = off).  The reference is resumable only at pipeline-stage
    # granularity (SURVEY.md §5); this adds step granularity.
    checkpoint_every: int = 0
    # Resumable snapshots (resilience/), one knob with path-appropriate
    # granularity (CLI: --snapshot-every):
    # * quasi-static chunked dispatch path: persist the resumable
    #   Krylov carry every N CHUNK boundaries (snap_*.npz) — a killed
    #   process or lost device loses at most N chunks, and
    #   `solve(resume=True)` continues MID-SOLVE with bit-identical
    #   history; also the restore point the dispatch guard re-dispatches
    #   from after a device-loss exception.
    # * dynamics/Newmark time histories: persist the full kinematic
    #   state (u, v[, w], histories, probe series, frames) every N
    #   completed TIMESTEPS (step_*.npz, retention-bounded by
    #   PCG_TPU_SNAP_KEEP) — `run(..., resume=True)` continues
    #   MID-TIME-HISTORY, and NaN/Inf rollback restores the last one.
    # 0 = off.
    snapshot_every: int = 0
    # Sharded setup path (ISSUE 14): under multi-process jax.distributed
    # the general/structured partition builders construct ONLY this
    # process's parts (the global layout merges via host allreduce) and
    # the warm cache reads only this process's per-part entries.
    #   "auto" — engage when multi-process with an eligible mesh/backend;
    #   "on"   — like auto, but raise when the mesh layout prevents it;
    #   "off"  — every process builds/loads the full partition (the
    #            historical behavior).
    # Trace-neutral: the engaged sharded build produces bit-identical
    # partition content for this process's rows, so the compiled program
    # and all cache keys are unchanged.
    setup_shard: str = "auto"
    # Preflight gate (validate/ subsystem): sanity-check the ModelData
    # and config cross-constraints BEFORE any partition build or XLA
    # compile.  "" = environment default (PCG_TPU_PREFLIGHT, ultimately
    # "fail"); explicit "fail" | "warn" | "off" overrides.  CLI:
    # --preflight and the `validate` subcommand.
    preflight: str = ""
    # Warm-path cache directory (cache/): when set, partitions are served
    # from a content-addressed on-disk cache, the jitted PCG step is
    # AOT-exported/deserialized (skipping re-tracing), and jax's
    # persistent XLA compilation cache is pointed at <cache_dir>/xla —
    # the second solve of the same model/n_parts/backend performs zero
    # partitioning work and zero step tracing.  CLI: --cache-dir and the
    # `warmup` subcommand (docs/RUNBOOK.md "Warm path").
    cache_dir: str = ""
    # Telemetry (obs/): when set, every structured event (steps, dispatch
    # timings, residual traces, run summary) is appended to this JSONL
    # file, one schema-versioned object per line.  CLI: --telemetry-out.
    # Under multi-process jax.distributed each process writes its OWN
    # shard (path.p<process_index>.jsonl); `pcg-tpu telemetry-merge`
    # aggregates the shards into one time-ordered stream.
    telemetry_path: str = ""
    # Flight recorder (obs/flight.py): when set, every solve dispatch is
    # bracketed by fsync-per-event begin/end records (plus periodic
    # monotonic+wall heartbeats) appended to this JSONL file — a device
    # loss or SIGKILL mid-solve leaves a parseable artifact naming the
    # in-flight dispatch and its last heartbeat, instead of a log to
    # hand-reconstruct.  Sharded per process like telemetry_path.
    # "" = environment default (PCG_TPU_FLIGHT), ultimately off.
    # CLI: --flight-out.
    flight_path: str = ""
    # When set, the solve loop runs under a jax.profiler trace written here
    # (open with TensorBoard/XProf).  This is the TPU-native replacement for
    # the reference's hand-rolled calc vs comm-wait bracketing
    # (pcg_solver.py:631-641): collective time shows up as its own ops in
    # the trace instead of host-side timer brackets.
    profile_dir: str = ""
    # Calc vs comm-wait attribution (the reference's primary scaling
    # diagnostic, pcg_solver.py:631-641): after a solve with exports, run
    # this many probe iterations of the PCG body with and without
    # collectives; the measured difference fills TimeData's
    # Mean_CommWaitTime.  0 disables the probe (Mean_CommWaitTime = 0).
    comm_probe_iters: int = 30
    solver: SolverConfig = dataclasses.field(default_factory=SolverConfig)
    time_history: TimeHistoryConfig = dataclasses.field(default_factory=TimeHistoryConfig)

    @property
    def result_path(self) -> str:
        suffix = "_SpeedTest" if self.speed_test else ""
        return f"{self.scratch_path}/Results_Run{self.run_id}{suffix}"

    @property
    def res_vec_path(self) -> str:
        return f"{self.result_path}/ResVecData"

    @property
    def plot_path(self) -> str:
        return f"{self.result_path}/PlotData"

    @property
    def checkpoint_path(self) -> str:
        return f"{self.result_path}/Checkpoints"
