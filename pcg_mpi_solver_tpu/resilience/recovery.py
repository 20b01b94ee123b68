"""Recovery policy: dispatch guard, breakdown ladder, and the per-step
resilience context threaded through the chunked budget loop.

Division of labor (one failure taxonomy, three handlers):

* **Device loss** (XLA runtime errors, injected
  ``exc`` faults): the :class:`DispatchGuard` retries the dispatch with
  backoff, re-dispatching from the last mid-Krylov snapshot — losing at
  most one snapshot interval of iterations instead of the whole step.
  With no snapshot in memory (or the retry budget spent) the exception
  propagates to the driver, whose ladder restarts the step from its
  start state (the ``device_loss`` trigger).
* **In-graph breakdown** (flag 2 Inf-preconditioner, flag 4 rho/pq —
  ``solver/pcg.py`` BREAKDOWN_FLAGS) and **NaN/Inf carry** (silent
  corruption no MATLAB flag catches): the driver-level
  :class:`RecoveryLadder` restarts from the tracked min-residual
  iterate through a bounded escalation — plain restart -> scalar-Jacobi
  fallback preconditioner -> f64 escalation (mixed mode) — each attempt
  an ``obs/metrics`` ``recovery`` event.
* **Process death** (SIGKILL, preemption, injected ``kill`` faults):
  nothing in-process — the next run's ``--resume`` restores the last
  mid-Krylov snapshot (``utils/checkpoint.SnapshotStore``) and
  continues bit-identically.
"""

from __future__ import annotations

import math
import os
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from pcg_mpi_solver_tpu.resilience.faultinject import (
    FaultPlan, InjectedDispatchError)

# Exception type names that indicate the DEVICE (not the math) failed —
# matched by name so no jaxlib import is needed at module load, and the
# set survives jax moving its error types between releases.
_DEVICE_ERROR_NAMES = frozenset({
    "XlaRuntimeError", "JaxRuntimeError", "InternalError",
    "UnavailableError", "FailedPreconditionError", "AbortedError",
})
_DEVICE_ERROR_MARKERS = ("UNAVAILABLE", "DEADLINE_EXCEEDED", "INTERNAL:",
                         "ABORTED", "device loss", "Device loss")


def is_device_loss(exc: BaseException) -> bool:
    """Does this exception mean the device/dispatch died (retryable),
    rather than the computation being wrong (not retryable)?"""
    if isinstance(exc, InjectedDispatchError):
        return True
    if type(exc).__name__ in _DEVICE_ERROR_NAMES:
        return True
    msg = str(exc)
    return any(m in msg for m in _DEVICE_ERROR_MARKERS)


def breakdown_trigger(flag: int, relres: float) -> Optional[str]:
    """Classify a terminal chunked-solve outcome into a ladder trigger
    (None = no recovery warranted: converged, budget, or stagnation)."""
    from pcg_mpi_solver_tpu.solver.pcg import BREAKDOWN_FLAGS

    if not math.isfinite(relres):
        return "nan_carry"
    if flag in BREAKDOWN_FLAGS:
        return f"flag{flag}"
    return None


def column_trigger(flag: int, normr: float) -> Optional[str]:
    """Per-column ladder trigger of a blocked multi-RHS carry
    (:func:`resilience.engine.run_many_with_recovery`): the blocked
    twin of :func:`breakdown_trigger`, reading the column's carry flag
    and carry residual norm.  A flag-1 (still running) column with a
    non-finite norm is the NaN-carry case — no MATLAB flag ever trips
    on NaN, so the host must intervene before the column burns the
    whole lockstep budget on poison."""
    from pcg_mpi_solver_tpu.solver.pcg import BREAKDOWN_FLAGS

    if flag in BREAKDOWN_FLAGS:
        return f"flag{flag}"
    if flag == 1 and not math.isfinite(normr):
        return "nan_carry"
    return None


def retry_deadline_s() -> Optional[float]:
    """Optional wall clamp on retry storms (``PCG_TPU_RETRY_DEADLINE_S``
    seconds, env-only): a scarce hardware window must not be eaten by
    backoff loops.  A malformed value must not kill the solve the knob
    protects — it disables the deadline with a warning instead."""
    raw = os.environ.get("PCG_TPU_RETRY_DEADLINE_S", "")
    if not raw:
        return None
    try:
        return float(raw)
    except ValueError:
        import warnings

        warnings.warn(f"PCG_TPU_RETRY_DEADLINE_S={raw!r} is not a "
                      "number; retry deadline disabled")
        return None


class DispatchGuard:
    """Retry-with-backoff + deadline budget for device dispatches.

    One instance per solve step: the retry budget is a per-step total
    (a flapping device must not retry forever), the deadline an absolute
    wall clamp.  Backoff is exponential from
    ``PCG_TPU_RETRY_BACKOFF_S`` (default 0.5 s; tests set it near 0).
    """

    def __init__(self, retries: int = 2, deadline_s: Optional[float] = None,
                 recorder=None):
        self.retries = int(retries)
        self.failures = 0
        self.recorder = recorder
        self._deadline = (time.monotonic() + deadline_s
                          if deadline_s else None)
        self._backoff0 = float(os.environ.get("PCG_TPU_RETRY_BACKOFF_S",
                                              "0.5"))

    def should_retry(self, exc: BaseException) -> bool:
        """Account one dispatch failure; True when a retry is allowed
        (device-loss shaped, budget left, deadline not passed)."""
        if not is_device_loss(exc):
            return False
        self.failures += 1
        if self.failures > self.retries:
            return False
        if self._deadline is not None and time.monotonic() > self._deadline:
            return False
        return True

    def backoff(self) -> None:
        time.sleep(min(self._backoff0 * (2 ** (self.failures - 1)), 30.0))


class RecoveryLadder:
    """Bounded escalation ladder for breakdown/NaN/device-loss triggers.

    Rung order (ISSUE 3 / arXiv:2501.03743's recoverable-breakdown
    posture): restart from the min-residual iterate -> same restart with
    the scalar-Jacobi fallback preconditioner (when the configured one
    is stronger, ``ops/precond.fallback_kind``) -> f64 escalation (mixed
    mode: finish the solve with direct f64 Krylov cycles).  Attempts
    past the last applicable rung repeat it; ``max_recoveries`` bounds
    the total.
    """

    def __init__(self, *, precond: str, mixed: bool, max_recoveries: int,
                 recorder=None, extra: Optional[Dict[str, Any]] = None):
        from pcg_mpi_solver_tpu.ops.precond import fallback_kind

        self.max_recoveries = int(max_recoveries)
        self.attempt = 0
        self.recorder = recorder
        # extra fields stamped on every `recovery` event this ladder
        # emits (the per-column ladders of a blocked solve tag theirs
        # with the column index: extra={"rhs": k})
        self.extra = dict(extra or {})
        self.actions_taken: List[str] = []
        rungs = ["restart_minres"]
        if fallback_kind(precond) is not None:
            rungs.append("fallback_prec")
        if mixed:
            rungs.append("escalate_f64")
        self._rungs = rungs

    @property
    def exhausted(self) -> bool:
        return self.attempt >= self.max_recoveries

    def next_action(self, trigger: str) -> Optional[str]:
        """Consume one attempt; returns the rung action (None when the
        budget is spent) and records the ``recovery`` telemetry event
        that makes every attempt visible in the JSONL stream."""
        if self.exhausted:
            return None
        self.attempt += 1
        action = self._rungs[min(self.attempt - 1, len(self._rungs) - 1)]
        self.actions_taken.append(action)
        if self.recorder is not None:
            self.recorder.event("recovery", action=action,
                                attempt=self.attempt, trigger=trigger,
                                **self.extra)
            self.recorder.inc(f"resilience.recovery.{action}")
        return action


class ResilienceContext:
    """Everything the chunked budget loop needs per solve step: the
    mid-Krylov snapshot cadence (disk via ``SnapshotStore`` + the
    in-memory restore point the dispatch guard re-dispatches from), the
    guard itself, and the optional fault plan.

    ``fetch_state`` / ``put_state`` are driver-supplied closures mapping
    a device pytree to host numpy and back (sharding-aware) — the
    context itself stays jax-free.
    """

    def __init__(self, *, store=None, step: int = 0, snapshot_every: int = 0,
                 fetch_state: Callable[[Any], Any] = None,
                 put_state: Callable[[Any], Any] = None,
                 guard: Optional[DispatchGuard] = None,
                 faults: Optional[FaultPlan] = None,
                 recorder=None, resume: bool = False,
                 ladder_armed: bool = False, comm=None):
        self.store = store
        self.step = int(step)
        self.snapshot_every = int(snapshot_every)
        self.fetch_state = fetch_state
        self.put_state = put_state
        self.guard = guard
        self.faults = faults
        self.recorder = recorder
        # host-collective group of a multi-process run
        # (resilience.distributed.GuardedComm; watchdog armed only when
        # PCG_TPU_COLLECTIVE_DEADLINE_S is set), or None single-process:
        # drives the chunk-boundary liveness sync and the consensus
        # agreements of the recovery engine
        self.comm = comm
        # whether the driver will actually consume engine.restart_x — the
        # engine skips the per-cycle restart-iterate copy otherwise
        self.ladder_armed = bool(ladder_armed)
        self._allow_resume = bool(resume)
        self._mem: Optional[Dict[str, Any]] = None   # last good host state
        self._since_snapshot = 0

    # -- group liveness -------------------------------------------------
    def sync_boundary(self) -> None:
        """Chunk-boundary liveness probe of a multi-process run: one
        tiny deadline-guarded collective at the TOP of each chunk
        iteration, OUTSIDE the dispatch try/except — a dead peer
        surfaces as a named DeadPeerError in bounded time (never an
        infinite psum hang, never a dispatch-guard retry), before any
        device work of the next chunk is enqueued.  No-op without a
        multi-process comm."""
        comm = self.comm
        if comm is None or getattr(comm, "n_procs", 1) <= 1:
            return
        if hasattr(comm, "barrier"):
            comm.barrier("chunk_boundary")
        else:
            comm.allreduce(np.ones(1, dtype=np.int64), "min")

    # -- snapshots ------------------------------------------------------
    def load_resume_state(self) -> Optional[Dict[str, Any]]:
        """The persisted mid-step state to resume from, or None.  Only
        honored when the caller asked for --resume (a FRESH solve must
        never silently continue a stale snapshot from a previous
        generation of the same run directory)."""
        if not (self._allow_resume and self.store is not None):
            return None
        self._allow_resume = False
        state = self.store.load(self.step)
        if state is None:
            return None
        self._mem = state           # also the guard's restore point
        if self.recorder is not None:
            self.recorder.event("snapshot", op="restore", step=self.step,
                                chunk=int(state.get("chunk", -1)))
        return state

    def after_chunk(self, state_fn: Callable[[], Dict[str, Any]]) -> None:
        """Chunk-boundary hook: every ``snapshot_every`` completed chunks,
        fetch the resumable state to host (``state_fn`` builds the device
        pytree lazily — with snapshots off this costs nothing), keep it
        as the guard's restore point, and persist it atomically."""
        if self.snapshot_every <= 0:
            return
        self._since_snapshot += 1
        if self._since_snapshot < self.snapshot_every:
            return
        self._since_snapshot = 0
        state = state_fn()
        state = self.fetch_state(state) if self.fetch_state else state
        self._mem = state
        if self.store is not None:
            self.store.save(self.step, state)
            if self.recorder is not None:
                self.recorder.event("snapshot", op="save", step=self.step,
                                    chunk=int(state.get("chunk", -1)))

    def discard(self) -> None:
        """Drop the step's snapshot (the step completed — the record
        must not outlive the state it describes)."""
        self._mem = None
        if self.store is not None:
            self.store.discard(self.step)

    # -- dispatch guard -------------------------------------------------
    def handle_dispatch_failure(self, exc: BaseException,
                                kind: Optional[str] = None) \
            -> Optional[Dict[str, Any]]:
        """Guard decision for a failed dispatch: the host state to
        re-dispatch from (after backoff), or None to propagate.  Needs
        BOTH a retry budget and an in-memory restore point — without a
        snapshot there is nothing safe to re-dispatch (the donated carry
        may be gone), so the driver-level ladder handles it instead.
        ``kind`` (``"direct"``/``"mixed"``) rejects a restore point of
        the wrong schema (e.g. one predating an escalation switch)
        WITHOUT consuming a retry."""
        if self.guard is None or self._mem is None:
            return None
        if kind is not None and str(
                np.asarray(self._mem.get("kind", ""))) != kind:
            return None
        if not self.guard.should_retry(exc):
            return None
        if self.recorder is not None:
            self.recorder.event(
                "recovery", action="redispatch",
                attempt=self.guard.failures, trigger="device_loss",
                error=f"{type(exc).__name__}: {exc}")
            self.recorder.inc("resilience.recovery.redispatch")
        self.guard.backoff()
        return self._mem

    def restore_device(self, state: Dict[str, Any]) -> Dict[str, Any]:
        """Host snapshot state -> device pytree (sharding-faithful)."""
        return self.put_state(state) if self.put_state else state
