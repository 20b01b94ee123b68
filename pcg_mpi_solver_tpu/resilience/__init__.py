"""Resilience subsystem: preemption-safe solves on scarce hardware.

The paper's billion-DOF regime runs on preemptible TPUs where a failed
dispatch throws away minutes of compile and thousands of Krylov
iterations.  This package makes the solver SURVIVE those failures rather
than report them:

* mid-Krylov snapshots (``utils/checkpoint.SnapshotStore`` + the
  per-step :class:`~pcg_mpi_solver_tpu.resilience.recovery.ResilienceContext`)
  — a killed process or lost device loses at most one snapshot interval,
  and ``--resume`` continues MID-SOLVE with bit-identical history;
* a bounded recovery ladder for flag-2/4 breakdowns and NaN/Inf carries
  (:class:`~pcg_mpi_solver_tpu.resilience.recovery.RecoveryLadder`);
* a retry-with-backoff dispatch guard for XLA/device-loss exceptions
  (:class:`~pcg_mpi_solver_tpu.resilience.recovery.DispatchGuard`);
* the shared recovery orchestration + timestep-granular time-history
  harness (:mod:`pcg_mpi_solver_tpu.resilience.engine`:
  :func:`~pcg_mpi_solver_tpu.resilience.engine.run_with_recovery`,
  :class:`~pcg_mpi_solver_tpu.resilience.engine.TimeHistoryGuard`) —
  one copy of the machinery, consumed by the quasi-static driver, the
  implicit Newmark stepper and the explicit dynamics driver;
* deterministic fault injection so every path above is exercised in
  tier-1 on CPU (:mod:`pcg_mpi_solver_tpu.resilience.faultinject`),
  including the step domain (``kill@s:N``) for time histories and the
  rank domain (``kill@rank:R:N``) for multi-process chaos runs;
* multi-process fault tolerance (ISSUE 18,
  :mod:`pcg_mpi_solver_tpu.resilience.distributed`): deadline-guarded
  host collectives that turn a dead peer into a named
  :class:`~pcg_mpi_solver_tpu.resilience.distributed.DeadPeerError`
  in bounded time, group-consistent two-phase snapshot epochs, and
  elastic resume of an N-process run onto M processes
  (``Solver.resume_elastic``).

Import contract: jax-free at module load (the fault poisoners and the
state put/fetch closures import jax lazily), matching ``cache/`` and
``obs/``.
"""

from pcg_mpi_solver_tpu.resilience.distributed import (
    DeadPeerError, GroupSnapshotStore, GuardedComm,
    collective_deadline_s, suspect_dead_rank)
from pcg_mpi_solver_tpu.resilience.engine import (
    ManyRecoveryHooks, RecoveryHooks, TimeHistoryGuard,
    kinematic_state_io, run_many_with_recovery, run_with_recovery)
from pcg_mpi_solver_tpu.resilience.faultinject import (
    FaultPlan, InjectedDispatchError, SimulatedKill)
from pcg_mpi_solver_tpu.resilience.recovery import (
    DispatchGuard, RecoveryLadder, ResilienceContext, breakdown_trigger,
    column_trigger, is_device_loss, retry_deadline_s)

__all__ = [
    "FaultPlan",
    "InjectedDispatchError",
    "SimulatedKill",
    "DeadPeerError",
    "DispatchGuard",
    "GroupSnapshotStore",
    "GuardedComm",
    "collective_deadline_s",
    "suspect_dead_rank",
    "ManyRecoveryHooks",
    "RecoveryHooks",
    "RecoveryLadder",
    "ResilienceContext",
    "TimeHistoryGuard",
    "breakdown_trigger",
    "column_trigger",
    "is_device_loss",
    "kinematic_state_io",
    "retry_deadline_s",
    "run_many_with_recovery",
    "run_with_recovery",
]
