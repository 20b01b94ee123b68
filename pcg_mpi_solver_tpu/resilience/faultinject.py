"""Deterministic fault injection for the resilience subsystem.

Every recovery path in the framework (breakdown ladder, dispatch guard,
mid-Krylov snapshot/resume) must be exercisable in tier-1 on CPU, without
hardware and without flaky timing: faults fire at exact, configured
positions in the chunked dispatch sequence, so a test (or a chaos run on
real hardware) is bit-reproducible.

A :class:`FaultPlan` is parsed from a spec string (env ``PCG_TPU_FAULTS``
or passed programmatically, e.g. ``Solver.fault_plan = FaultPlan(...)``):

    spec     := term ("," term)*
    term     := mode "@" ["s:" | "col:" | "job:" | "rank:" rank ":"]
                index ["*" count]
    mode     := "kill" | "exc" | "nan" | "inf" | "rho0" | "sleep"
    index    := 0-based position in the mode's counter (see below);
                with the "s:" prefix, the ABSOLUTE timestep number of a
                time-history run; with the "col:" prefix, the COLUMN
                index of a blocked multi-RHS solve; with the "job:"
                prefix, the ABSOLUTE admission ordinal of a solve-
                service job (serve/); with the "rank:" prefix, the
                dispatch/boundary counter index on process ``rank``
                only (omitted index = 0: ``kill@rank:1`` ==
                ``kill@rank:1:0``)
    count    := consecutive firings (default 1; "exc@3*2" also fails the
                first retry of dispatch 3)

Six counter domains.  The first two are monotone over the life of the
plan (they keep running across recovery restarts, so a second fault can
be aimed at a later ladder rung):

* the DISPATCH counter advances once per successfully completed Krylov
  dispatch ("exc" fires *before* the dispatch with that index runs);
* the BOUNDARY counter advances once per chunk boundary — after a direct
  chunk / mixed refinement cycle completes and any due snapshot is taken
  ("kill" / "nan" / "inf" / "rho0" / "sleep" fire *at* that boundary);
* the STEP domain ("s:" prefix — ``kill@s:3``, ``nan@s:5``) is indexed
  by the absolute completed-timestep number of a dynamics/Newmark time
  history (:meth:`FaultPlan.at_step`, driven by
  ``resilience/engine.TimeHistoryGuard``): the fault fires at EXACTLY
  timestep N, after any due step snapshot — so a rollback/resume that
  replays past N does not re-fire a consumed fault, while ``*count``
  deliberately re-fires it to exercise budget exhaustion.  Step-domain
  modes are ``kill``/``nan``/``inf`` (poison lands on the kinematic
  state leaf ``u``);
* the COLUMN domain ("col:" prefix — ``nan@col:2``, ``rho0@col:0``) is
  indexed by the RHS-block COLUMN of a blocked multi-RHS solve
  (``Solver.solve_many``): the fault fires at the next blocked chunk
  boundary (after any due snapshot, like the boundary domain) and
  poisons ONLY that column of the carry — ``nan``/``inf`` land on the
  column's residual, ``rho0`` zeroes the column's rho — so the
  per-column recovery ladder and quarantine paths run deterministically
  in tier-1 while every other column stays bit-identical (the poison is
  a ``jnp.where`` column select, never a whole-block rescale).
  ``*count`` re-fires it at that many consecutive boundaries to defeat
  a bounded per-column recovery budget;
* the JOB domain ("job:" prefix — ``exc@job:1``, ``nan@job:0``,
  ``sleep@job:2``) is indexed by the ABSOLUTE admission ordinal of a
  solve-service job (``serve/``, :meth:`FaultPlan.at_job`): the fault
  fires at the SERVICE BOUNDARY, when the daemon is about to dispatch
  the block containing the k-th admitted job — ``exc`` raises
  :class:`InjectedDispatchError` (the job fails with a named verdict,
  its co-batched tenants dispatch unharmed), ``nan`` asks the daemon
  to poison THAT job's RHS column (the service-boundary quarantine
  drill), ``sleep`` delays the whole block on the host (the
  deterministic window the SIGKILL chaos test fires inside).  Ordinals
  never reset: a restarted daemon continues the journal's admission
  numbering, and replay pre-consumes the ordinals the journal proves
  already passed the boundary (:meth:`FaultPlan.replay_consume_job`) —
  same never-re-fire contract as the step domain's absolute indexing;
* the RANK domain ("rank:" prefix — ``kill@rank:1``, ``exc@rank:0``,
  ``sleep@rank:1:3``) gates a dispatch/boundary-counter fault on ONE
  process of a multi-controller run, so distributed chaos drills are
  deterministic: every process parses the same spec
  (``PCG_TPU_FAULTS`` is shared), but the fault fires only where
  ``jax.process_index()`` matches.  ``exc`` rides the dispatch
  counter, the other modes the boundary counter, exactly like their
  unprefixed twins.  A rank at/past ``jax.process_count()`` follows
  the cannot-land contract (neither consumed nor recorded), same as a
  column fault aimed past the block width.

Modes and the recovery path each one exercises:

``exc``   raise :class:`InjectedDispatchError` (walks/talks like an XLA
          device-loss error) before the dispatch -> dispatch guard
          (snapshot re-dispatch) or, with the guard exhausted, the
          driver ladder's ``device_loss`` restart.
``kill``  raise :class:`SimulatedKill` at the chunk boundary, after the
          snapshot -> kill-and-resume (``BaseException`` on purpose:
          like a real SIGKILL it must not be swallowable by any
          ``except Exception`` on the way out).
``inf``   overwrite the nonzero entries of the carry residual with Inf
          -> the next preconditioner apply goes Inf -> flag 2.
``rho0``  zero the carry ``rho`` -> the resumed beta recurrence divides
          by zero -> flag 4 (rho/pq breakdown).
``nan``   multiply the carry residual by NaN — the silent-corruption
          case: NO MATLAB flag trips on NaN (every breakdown predicate
          compares false), so this exercises the host-side NaN-carry
          detection, not the in-graph flags.
``sleep`` ``time.sleep`` on the HOST at the chunk boundary (duration
          ``FaultPlan.sleep_s``, env ``PCG_TPU_FAULT_SLEEP_S``, default
          0.25 s) — the straggler simulator: not a failure at all, so
          no recovery path fires, but on a multi-controller run every
          OTHER process blocks at the next collective until this one
          arrives.  The deterministic delayed-rank injection the
          obs/fleet.py skew-attribution tests are built on.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional

MODES = ("kill", "exc", "nan", "inf", "rho0", "sleep")
_DISPATCH_MODES = ("exc",)
_BOUNDARY_MODES = ("kill", "nan", "inf", "rho0", "sleep")
_STEP_MODES = ("kill", "nan", "inf")
_COL_MODES = ("nan", "inf", "rho0")
_JOB_MODES = ("exc", "nan", "sleep")


class SimulatedKill(BaseException):
    """Simulated process death at a chunk boundary.

    Derives from ``BaseException`` (like ``KeyboardInterrupt``) so no
    recovery handler can catch it: a killed process does not get to run
    its ladder — only a NEW process's ``--resume`` does.
    """


class InjectedDispatchError(RuntimeError):
    """Synthetic device-loss exception (stands in for XlaRuntimeError/
    UNAVAILABLE from a lost or preempted device)."""


def _parse(spec: str):
    """spec string -> ({mode: {index: count}}, {mode: {step: count}},
    {mode: {col: count}}, {mode: {job: count}},
    {mode: {(rank, index): count}}) for the dispatch/boundary domains,
    the step domain, the per-column domain of blocked multi-RHS solves,
    the per-job domain of the solve service, and the per-process rank
    domain."""
    out: Dict[str, Dict[int, int]] = {}
    steps: Dict[str, Dict[int, int]] = {}
    cols: Dict[str, Dict[int, int]] = {}
    jobs: Dict[str, Dict[int, int]] = {}
    ranks: Dict[str, Dict[tuple, int]] = {}
    for term in (t.strip() for t in spec.split(",")):
        if not term:
            continue
        try:
            mode, rest = term.split("@", 1)
            count = 1
            if "*" in rest:
                rest, c = rest.split("*", 1)
                count = int(c)
            rest = rest.strip()
            step_domain = rest.startswith("s:")
            col_domain = rest.startswith("col:")
            job_domain = rest.startswith("job:")
            rank_domain = rest.startswith("rank:")
            rank = None
            if rank_domain:
                bits = rest[len("rank:"):].split(":")
                if len(bits) > 2:
                    raise ValueError(rest)
                rank = int(bits[0])
                idx = int(bits[1]) if len(bits) > 1 else 0
            else:
                idx = int(rest[4:] if col_domain or job_domain
                          else rest[2:] if step_domain else rest)
        except ValueError:
            raise ValueError(
                f"bad fault term {term!r} "
                "(want mode@[s:|col:|job:|rank:R:]index[*count])")
        mode = mode.strip()
        if mode not in MODES:
            raise ValueError(f"unknown fault mode {mode!r} "
                             f"(valid: {', '.join(MODES)})")
        if idx < 0 or count < 1 or (rank is not None and rank < 0):
            raise ValueError(f"bad fault term {term!r}: rank >= 0, "
                             f"index >= 0, count >= 1")
        if rank_domain:
            ranks.setdefault(mode, {})[(rank, idx)] = count
        elif step_domain:
            if mode not in _STEP_MODES:
                raise ValueError(
                    f"fault mode {mode!r} has no step-domain trigger "
                    f"(valid at s: indices: {', '.join(_STEP_MODES)})")
            steps.setdefault(mode, {})[idx] = count
        elif col_domain:
            if mode not in _COL_MODES:
                raise ValueError(
                    f"fault mode {mode!r} has no column-domain trigger "
                    f"(valid at col: indices: {', '.join(_COL_MODES)})")
            cols.setdefault(mode, {})[idx] = count
        elif job_domain:
            if mode not in _JOB_MODES:
                raise ValueError(
                    f"fault mode {mode!r} has no job-domain trigger "
                    f"(valid at job: indices: {', '.join(_JOB_MODES)})")
            jobs.setdefault(mode, {})[idx] = count
        else:
            out.setdefault(mode, {})[idx] = count
    return out, steps, cols, jobs, ranks


class FaultPlan:
    """One deterministic injection schedule (see module docstring).

    Stateful and single-use by design: counters and remaining fire-counts
    advance as the solve runs, so a plan instance describes one process
    lifetime, exactly like the failures it simulates.
    """

    def __init__(self, spec: str, recorder=None):
        (self._faults, self._step_faults, self._col_faults,
         self._job_faults, self._rank_faults) = _parse(spec)
        self.recorder = recorder
        self.dispatches = 0         # completed Krylov dispatches
        self.boundaries = 0         # completed chunk boundaries
        self.fired: List[dict] = []  # (mode, point, index) audit trail
        try:
            self.sleep_s = float(
                os.environ.get("PCG_TPU_FAULT_SLEEP_S", 0.25))
        except ValueError:
            self.sleep_s = 0.25     # straggler-delay duration ("sleep")

    @classmethod
    def from_env(cls, recorder=None) -> Optional["FaultPlan"]:
        """Plan from ``PCG_TPU_FAULTS``; None when unset/empty."""
        spec = os.environ.get("PCG_TPU_FAULTS", "").strip()
        return cls(spec, recorder=recorder) if spec else None

    @property
    def armed(self) -> bool:
        return (any(self._faults.values()) or self.step_armed
                or self.col_armed or self.job_armed
                or any(self._rank_faults.values()))

    @property
    def step_armed(self) -> bool:
        """Any step-domain fault still pending."""
        return any(self._step_faults.values())

    @property
    def col_armed(self) -> bool:
        """Any column-domain fault still pending."""
        return any(self._col_faults.values())

    @property
    def job_armed(self) -> bool:
        """Any job-domain (service-boundary) fault still pending."""
        return any(self._job_faults.values())

    def next_step_fault(self, after: int) -> Optional[int]:
        """Smallest pending step-domain index > ``after``, or None — the
        time loop splits its device chunks there so the fault's timestep
        is an actual host boundary."""
        pending = [i for m in self._step_faults.values() for i in m
                   if i > after]
        return min(pending) if pending else None

    def _take(self, mode: str, idx: int) -> bool:
        pending = self._faults.get(mode, {})
        if pending.get(idx, 0) <= 0:
            return False
        pending[idx] -= 1
        if pending[idx] <= 0:
            del pending[idx]
        return True

    @staticmethod
    def _process_slot():
        """``(process_index, process_count)`` of an ALREADY-IMPORTED
        jax (never importing it here — faultinject stays import-light),
        defaulting to the single-process identity."""
        import sys

        jax = sys.modules.get("jax")
        if jax is None:
            return 0, 1
        try:
            return int(jax.process_index()), int(jax.process_count())
        except Exception:                               # noqa: BLE001
            return 0, 1     # backend not initialized: single-process

    def _take_rank(self, mode: str, idx: int) -> bool:
        """Consume a pending rank-domain fault of ``mode`` at counter
        position ``idx`` aimed at THIS process; True when it fires
        here.  A rank at/past the process count cannot land — neither
        consumed nor recorded (cannot-land contract); a fault aimed at
        a DIFFERENT live rank stays pending on this process (its plan
        never fires it, but `armed` must keep every process's
        resilience context engaged for the collective snapshot/resume
        protocol)."""
        pending = self._rank_faults.get(mode, {})
        here, n_procs = self._process_slot()
        for rank, at in sorted(pending):
            if at != idx or pending[(rank, at)] <= 0:
                continue
            if rank >= n_procs or rank != here:
                continue
            pending[(rank, at)] -= 1
            if pending[(rank, at)] <= 0:
                del pending[(rank, at)]
            return True
        return False

    def _fire(self, mode: str, point: str, idx: int) -> None:
        self.fired.append({"mode": mode, "point": point, "at": idx})
        if self.recorder is not None:
            self.recorder.event("fault", mode=mode, point=point, at=idx)

    # -- engine hooks ---------------------------------------------------
    def on_dispatch(self) -> None:
        """Called immediately before a Krylov dispatch.  May raise
        :class:`InjectedDispatchError` (the count is consumed, so a
        guarded retry of the same dispatch succeeds unless the spec asked
        for consecutive failures with ``*count``)."""
        idx = self.dispatches
        if self._take("exc", idx):
            self._fire("exc", "dispatch", idx)
            raise InjectedDispatchError(
                f"injected device loss before dispatch {idx} "
                "(PCG_TPU_FAULTS)")
        if self._take_rank("exc", idx):
            self._fire("exc", "rank-dispatch", idx)
            raise InjectedDispatchError(
                f"injected device loss before dispatch {idx} on this "
                "process (PCG_TPU_FAULTS rank domain)")

    def on_dispatch_done(self) -> None:
        """Called after a dispatch completes successfully."""
        self.dispatches += 1

    def at_boundary(self, carry: dict, blocked: bool = False) -> dict:
        """Called at a chunk boundary AFTER any snapshot was taken (the
        snapshot must hold the clean state — corruption happens to the
        live carry, as it would on real hardware).  Returns the
        (possibly poisoned) carry; may raise :class:`SimulatedKill`.

        ``blocked`` marks a blocked multi-RHS boundary: pending
        column-domain faults (``mode@col:k``) then fire too, poisoning
        ONLY column ``k`` of the blocked carry (nan/inf on the column's
        residual, rho0 on the column's rho) — every other column's
        leaves stay bitwise untouched.

        A poison mode whose target leaf is absent from this path's carry
        (``rho0`` needs ``rho`` — the mixed outer state has none) is NOT
        consumed and NOT recorded as fired: a chaos drill must never
        read "recovery path exercised" off an injection that could not
        land."""
        idx = self.boundaries
        self.boundaries += 1
        if self._take("sleep", idx):
            # host-side straggler delay: fires BEFORE any poison/kill at
            # this boundary — a delayed process still runs its chunk, it
            # just arrives late at the next collective
            self._fire("sleep", "boundary", idx)
            time.sleep(self.sleep_s)
        if self._take_rank("sleep", idx):
            self._fire("sleep", "rank-boundary", idx)
            time.sleep(self.sleep_s)
        for mode, leaf in (("nan", "r"), ("inf", "r"), ("rho0", "rho")):
            if leaf in carry and self._take(mode, idx):
                self._fire(mode, "boundary", idx)
                carry = _poison(carry, mode)
            if leaf in carry and self._take_rank(mode, idx):
                self._fire(mode, "rank-boundary", idx)
                carry = _poison(carry, mode)
        if blocked:
            # block width from the carry itself: a column fault aimed
            # past the actual width cannot land — like the absent-leaf
            # case above it must be neither consumed nor recorded
            r, rho = carry.get("r"), carry.get("rho")
            width = (r.shape[-1] if getattr(r, "ndim", 0) == 3
                     else rho.shape[0]
                     if getattr(rho, "ndim", 0) == 1 else 0)
            for mode, leaf in (("nan", "r"), ("inf", "r"),
                               ("rho0", "rho")):
                pend = self._col_faults.get(mode, {})
                for col in sorted(pend):
                    if col < width and leaf in carry \
                            and self._take_col(mode, col):
                        self._fire(mode, "col", col)
                        carry = _poison_col(carry, mode, col, leaf)
        if self._take("kill", idx):
            self._fire("kill", "boundary", idx)
            raise SimulatedKill(
                f"injected kill at chunk boundary {idx} (PCG_TPU_FAULTS)")
        if self._take_rank("kill", idx):
            self._fire("kill", "rank-boundary", idx)
            raise SimulatedKill(
                f"injected kill at chunk boundary {idx} on this process "
                "(PCG_TPU_FAULTS rank domain)")
        return carry

    def _take_col(self, mode: str, col: int) -> bool:
        pending = self._col_faults.get(mode, {})
        if pending.get(col, 0) <= 0:
            return False
        pending[col] -= 1
        if pending[col] <= 0:
            del pending[col]
        return True

    def _take_job(self, mode: str, job: int) -> bool:
        pending = self._job_faults.get(mode, {})
        if pending.get(job, 0) <= 0:
            return False
        pending[job] -= 1
        if pending[job] <= 0:
            del pending[job]
        return True

    def at_job(self, ordinal: int) -> Optional[str]:
        """Called by the solve service at the SERVICE BOUNDARY — the
        daemon is about to dispatch the block containing the job with
        ABSOLUTE admission ordinal ``ordinal`` (serve/daemon.py).
        Fires in straggler-first order, like :meth:`at_boundary`:
        ``sleep`` delays the host (the whole block arrives late — the
        deterministic window the SIGKILL chaos test fires inside), then
        ``nan`` returns ``"nan"`` asking the caller to poison THAT
        job's RHS column, then ``exc`` raises
        :class:`InjectedDispatchError` (the job fails with a named
        verdict while its co-batched tenants dispatch unharmed).  A job
        ordinal never admitted simply never reaches this hook —
        the cannot-land contract needs no width check here."""
        poison = None
        if self._take_job("sleep", ordinal):
            self._fire("sleep", "job", ordinal)
            time.sleep(self.sleep_s)
        if self._take_job("nan", ordinal):
            self._fire("nan", "job", ordinal)
            poison = "nan"
        if self._take_job("exc", ordinal):
            self._fire("exc", "job", ordinal)
            raise InjectedDispatchError(
                f"injected service-boundary failure for job ordinal "
                f"{ordinal} (PCG_TPU_FAULTS job domain)")
        return poison

    def replay_consume_job(self, ordinal: int) -> None:
        """Journal-replay pre-consumption: drop every pending job-domain
        fault aimed at ``ordinal`` WITHOUT firing or recording it.  A
        restarted daemon re-parses ``PCG_TPU_FAULTS`` into a fresh plan,
        but the journal proves ordinal ``ordinal`` already passed the
        service boundary (a ``dispatched`` or terminal record) — its
        fault was consumed by the dead process, and the absolute-
        indexing contract (step-domain precedent) says replay must
        never re-fire it."""
        for pending in self._job_faults.values():
            pending.pop(ordinal, None)

    def _take_step(self, mode: str, t: int) -> bool:
        pending = self._step_faults.get(mode, {})
        if pending.get(t, 0) <= 0:
            return False
        pending[t] -= 1
        if pending[t] <= 0:
            del pending[t]
        return True

    def at_step(self, t: int, state: dict) -> dict:
        """Called after completed timestep ``t`` of a time history,
        AFTER any due step snapshot (the snapshot must hold the clean
        state — corruption happens to the live run, as it would on real
        hardware).  Poison lands on the kinematic leaf ``u``; ``kill``
        raises :class:`SimulatedKill` last, so a poison+kill at the same
        step persists the poison-free snapshot first.  Indexed by the
        ABSOLUTE timestep number: a rollback or resume that replays past
        ``t`` does not re-fire a consumed fault."""
        for mode in ("nan", "inf"):
            if "u" in state and self._take_step(mode, t):
                self._fire(mode, "step", t)
                state = _poison(state, mode, leaf="u")
        if self._take_step("kill", t):
            self._fire("kill", "step", t)
            raise SimulatedKill(
                f"injected kill at timestep {t} (PCG_TPU_FAULTS)")
        return state


def _poison(carry: dict, mode: str, leaf: str = "r") -> dict:
    """Corrupt a device-resident carry dict (new leaves, never in-place:
    the donated-carry contract means the input dict's leaves may be the
    fresh outputs of the previous dispatch — poisoning builds replacement
    arrays and leaves the originals to the garbage collector).  ``leaf``
    is the poison target: the Krylov residual ``r`` at chunk boundaries,
    the kinematic state ``u`` at timestep boundaries."""
    import jax.numpy as jnp

    out = dict(carry)
    if mode == "rho0":
        if "rho" in out:
            out["rho"] = jnp.zeros_like(out["rho"])
        return out
    r = out.get(leaf)
    if r is None:
        return out
    if mode == "nan":
        out[leaf] = r * jnp.asarray(float("nan"), r.dtype)
    elif mode == "inf":
        # only the nonzero entries: constrained dofs stay exactly 0, so
        # the Inf lands where the preconditioner inverse is > 0 and the
        # next apply_prec trips the flag-2 Inf-preconditioner exit
        out[leaf] = jnp.where(r != 0, jnp.asarray(float("inf"), r.dtype),
                              r)
    return out


def _poison_col(carry: dict, mode: str, col: int, leaf: str) -> dict:
    """Column-domain poisoner for a blocked multi-RHS carry: corrupt
    ONLY column ``col`` (trailing RHS axis of the (P, n_loc, R) vectors,
    index ``col`` of the (R,) scalars).  Built from ``jnp.where`` column
    selects so every other column's values stay bitwise identical — the
    fault-isolation tests compare them bit for bit.  Same new-leaves
    discipline as :func:`_poison` (donated-carry contract)."""
    import jax.numpy as jnp

    out = dict(carry)
    if mode == "rho0":
        rho = out.get("rho")
        if rho is not None and getattr(rho, "ndim", 0) == 1:
            mask = jnp.arange(rho.shape[0]) == col
            out["rho"] = jnp.where(mask, jnp.zeros((), rho.dtype), rho)
        return out
    r = out.get(leaf)
    if r is None or getattr(r, "ndim", 0) != 3:
        return out
    mask = (jnp.arange(r.shape[-1]) == col)[None, None, :]
    if mode == "nan":
        out[leaf] = jnp.where(mask, r * jnp.asarray(float("nan"),
                                                    r.dtype), r)
    elif mode == "inf":
        out[leaf] = jnp.where(mask & (r != 0),
                              jnp.asarray(float("inf"), r.dtype), r)
    return out
