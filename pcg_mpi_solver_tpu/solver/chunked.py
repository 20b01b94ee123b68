"""Dispatch-chunked PCG execution, shared by solvers.

A single device dispatch that runs for minutes can trip execution
watchdogs, and a lost device loses all of it; above ~4M dofs the
solvers split a solve into host-driven dispatches of at most ``cap``
Krylov iterations, with all state resident on device between calls.  The
Krylov recurrence is resumable (solver/pcg.py ``carry_in``), so N capped
dispatches are iteration-for-iteration identical to one long solve in
direct mode, and chunk boundaries align with refinement cycles in mixed
mode.

This module owns everything AFTER the per-solver start step (which
differs: Dirichlet lifting for the quasi-static driver, the Newmark
history term for the implicit dynamics solver): the jitted cycle/refine/
finalize programs and the host-side budget loop.  Used by
``solver/driver.py`` and ``solver/newmark.py``.
"""

from __future__ import annotations

import contextlib
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from pcg_mpi_solver_tpu.obs.trace import trace_host_init, trace_specs
from pcg_mpi_solver_tpu.solver.pcg import (
    LAGGED_VARIANTS, carry_part_specs, cold_carry, pcg, refine_tol,
    scoped, select_best)


def _state_kind(state) -> str:
    """The ``kind`` tag of a (possibly npz-round-tripped) snapshot
    state: plain str programmatically, 0-d unicode array from disk."""
    return str(np.asarray(state.get("kind", "")))


class ChunkedEngine:
    """Capped-dispatch budget loop over a resumable PCG.

    ``ops``/``ops32`` follow the Ops protocol (the Newmark solver passes
    mass-shifted wrappers).  In mixed mode ``data`` is the
    ``{"f64": ..., "f32": ...}`` pytree and the preconditioner inverse is
    f32; in direct mode ``data`` is the flat pytree and the inverse
    matches the solve dtype.  The preconditioner is built once per step
    by the caller and passed into :meth:`run`.
    """

    def __init__(self, *, mesh, data_specs, part_spec, rep_spec, ops,
                 scfg, glob_n_dof_eff: int, cap: int, mixed: bool,
                 ops32=None, amul_fn=None, trace_len: int = 0,
                 recorder=None, donate: bool = False, prec_spec=None):
        """``amul_fn``, when given, is a host-level callable
        ``(data, v) -> eff * K.v`` backed by ONE separately-jitted
        program the caller shares across all its out-of-loop f64 matvec
        uses (Dirichlet lifting, r0, refine) — at octree-flagship scale
        every stencil INSTANTIATION costs minutes of compile, so the
        refine step is then
        composed from two tiny elementwise programs around it instead of
        instantiating the stencil a second time in its own program.

        ``trace_len`` > 0 threads the in-graph convergence ring
        (obs/trace.py) through the dispatch carries: in direct mode the
        ring rides the caller-built cold carry (``cold_carry(...,
        trace=...)``), in mixed mode the engine owns it and hands it from
        cycle to cycle.  Either way the ring stays device-resident across
        all dispatches of a solve and is surfaced once, as
        ``self.last_trace``, after :meth:`run` terminates.  ``recorder``
        (obs/metrics.py MetricsRecorder) gets a ``dispatch`` span around
        every jitted call; None disables that instrumentation.

        ``donate`` enables donated-carry dispatch: each capped dispatch
        DONATES its input Krylov carry (and the refine step its previous
        f64 iterate) to XLA, so the multi-vector resumable state is
        aliased in place instead of copied per dispatch.  Numerically a
        no-op (bit-identical on/off, tests/test_cache.py); the budget
        loop in :meth:`run` honors the contract by never touching a
        carry object after passing it to a donating program — every read
        (``final``/``final32``, the trace hand-off) is from the LATEST
        dispatch's freshly-allocated outputs."""
        self.mixed = mixed
        self.scfg = scfg
        self._amul_fn = amul_fn
        self.trace_len = int(trace_len)
        self._rec = recorder
        self.last_trace = None
        self.donate = bool(donate)
        # Loop formulation (SolverConfig.pcg_variant): threads through
        # every resumable pcg() call below and sizes the carry schema —
        # the recurrence variants (fused, pipelined) ride their
        # q/alpha/fresh (+ GV u/w/s/z/init) recurrence state alongside
        # the classic Krylov carry, so capped dispatches stay
        # bit-identical to one long solve of the same variant.
        variant = self.variant = getattr(scfg, "pcg_variant", "classic")
        lagged_v = variant in LAGGED_VARIANTS
        cap = int(cap)
        P, R = part_spec, rep_spec
        # preconditioner-operand spec: the plain part spec for the array
        # inverses (jacobi/block3), or the caller-supplied PYTREE of
        # specs for structured prec operands (the mg dict —
        # driver/newmark pass {"mg_diag": P, "fb": R})
        prec_spec = P if prec_spec is None else prec_spec
        carry_specs = carry_part_specs(P, R, trace=self.trace_len > 0,
                                       variant=variant)

        def smap(f, in_specs, out_specs, donate_argnums=()):
            return jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False),
                donate_argnums=donate_argnums if self.donate else ())

        if mixed:
            # Three jitted pieces so the f32 Krylov state survives dispatch
            # boundaries WITHIN a refinement cycle (restarting CG at every
            # dispatch loses superlinear convergence):
            #   inner_start: normalize the f64 residual, cold f32 carry +
            #                adaptive cycle tolerance;
            #   inner_cycle: resumable capped f32 PCG dispatch;
            #   refine:      f64 solution update + true-residual recompute.
            dd32 = jnp.float32

            traced = self.trace_len > 0

            @scoped("solve/start")
            def _inner_start(data, r, normr, n2b, trace=None):
                tol_cycle = refine_tol(scfg.tol * n2b, normr, scfg.inner_tol)
                rhat32 = (r / normr).astype(dd32)
                # ||rhat||_w = ||r||_w / normr = 1 exactly; no matvec needed.
                one = jnp.asarray(1.0, ops32.dot_dtype)
                carry0 = cold_carry(jnp.zeros_like(rhat32), rhat32, one,
                                    ops32.dot_dtype, trace=trace,
                                    variant=variant)
                return rhat32, tol_cycle, carry0

            in_start = (data_specs, P, R, R) + (
                (trace_specs(R),) if traced else ())
            self._inner_start_fn = smap(
                _inner_start, in_start, (P, R, carry_specs))

            def _inner_cycle(data, rhat32, prec32, tol_cycle, carry32,
                             budget, scale=None):
                if recorder is not None:       # runs at trace time only
                    recorder.inc("trace.inner_cycle")
                res, carry2 = pcg(
                    ops32, data["f32"], rhat32, carry32["x"], prec32,
                    tol=tol_cycle,
                    max_iter=jnp.minimum(cap, budget),
                    glob_n_dof_eff=glob_n_dof_eff,
                    max_stag_steps=scfg.max_stag_steps,
                    max_iter_nominal=scfg.max_iter,
                    carry_in=carry32, return_carry=True,
                    plateau_window=scfg.mixed_plateau_window,
                    progress_window=scfg.mixed_progress_window,
                    progress_ratio=scfg.mixed_progress_ratio,
                    progress_min_gain=scfg.mixed_progress_min_gain,
                    # inner iterations run on r/normr: the ring records
                    # absolute residuals via the cycle's refresh norm
                    trace_scale=scale,
                    variant=variant)
                return res.x, carry2, res.flag

            in_cycle = (data_specs, P, prec_spec, R, carry_specs, R) + (
                (R,) if traced else ())
            # donated f32 carry: each resumable dispatch updates the
            # Krylov state in place instead of copying it
            self._inner_cycle_fn = smap(
                _inner_cycle, in_cycle, (P, carry_specs, R),
                donate_argnums=(4,))

            if amul_fn is None:
                def _refine(data, fext, x, xinc32, scale):
                    data64 = data["f64"]
                    eff = data64["eff"]
                    w = data64["weight"] * eff
                    with jax.named_scope("solve/start"):
                        x2 = x + xinc32.astype(x.dtype) * scale
                    with jax.named_scope("solve/residual64"):
                        r2 = fext - eff * ops.matvec(data64, x2)
                        normr2 = jnp.sqrt(ops.wdot(w, r2, r2))
                    return x2, r2, normr2

                # donated previous iterate: x2 replaces x 1:1
                self._refine_fn = smap(
                    _refine, (data_specs, P, P, P, R), (P, P, R),
                    donate_argnums=(2,))
            else:
                @scoped("solve/start")
                def _refine_pre(x, xinc32, scale):
                    return x + xinc32.astype(x.dtype) * scale

                # donated previous iterate: x2 replaces x 1:1
                self._refine_pre_fn = smap(_refine_pre, (P, P, R), P,
                                           donate_argnums=(0,))

                @scoped("solve/residual64")
                def _refine_post(data, fext, kx2):
                    data64 = data["f64"]
                    w = data64["weight"] * data64["eff"]
                    r2 = fext - kx2          # kx2 = eff * K.x2 (amul_fn)
                    normr2 = jnp.sqrt(ops.wdot(w, r2, r2))
                    return r2, normr2

                self._refine_post_fn = smap(
                    _refine_post, (data_specs, P, P), (P, R))

            def _final32(data, rhat32, carry32):
                """f32 min-residual selection when an inner solve fails
                (matches the one-shot pcg_mixed's finalize_bad;
                recurrence-variant carries never evaluated their last
                iterate, so they take the min unconditionally)."""
                x, _ = select_best(ops32, data["f32"], rhat32, carry32,
                                   always_min=lagged_v)
                return x

            self._final32_fn = smap(
                _final32, (data_specs, P, carry_specs), P)
        else:
            def _cycle(data, fext, inv_diag, carry, budget):
                # Resumable call: the Krylov recurrence continues across
                # dispatch boundaries, so N capped dispatches are iteration-
                # for-iteration identical to one long solve.
                if recorder is not None:       # runs at trace time only
                    recorder.inc("trace.cycle")
                res, carry2 = pcg(
                    ops, data, fext, carry["x"], inv_diag,
                    tol=scfg.tol,
                    max_iter=jnp.minimum(cap, budget),
                    glob_n_dof_eff=glob_n_dof_eff,
                    max_stag_steps=scfg.max_stag_steps,
                    max_iter_nominal=scfg.max_iter,
                    carry_in=carry, return_carry=True,
                    variant=variant)
                return res.x, carry2, res.flag, res.relres

            # donated carry: the resumable Krylov state is aliased across
            # dispatch boundaries instead of copied
            self._cycle_fn = smap(
                _cycle, (data_specs, P, prec_spec, carry_specs, R),
                (P, carry_specs, R, R), donate_argnums=(3,))

            def _final(data, fext, carry):
                """Min-residual selection at terminal failure (once/step);
                recurrence-variant carries never evaluated their last
                iterate, so they take the min unconditionally."""
                return select_best(ops, data, fext, carry,
                                   always_min=lagged_v)

            self._final_fn = smap(
                _final, (data_specs, P, carry_specs), (P, R))

    def _disp(self, name: str):
        """Dispatch span (compile/execute attribution + optional profiler
        annotation) when a recorder is attached; free otherwise.

        jax dispatch is asynchronous: a span only measures device
        execution when the blocking scalar fetch sits INSIDE it, so every
        call site keeps its ``int(...)``/``float(...)`` coercions in the
        block.  Spans with no scalar of their own (inner_start, final32)
        time the enqueue; their execution is absorbed by the next fetching
        span (the device serializes programs), so per-CYCLE attribution
        stays correct."""
        if self._rec is None:
            return contextlib.nullcontext()
        return self._rec.dispatch(name)

    def _span(self, name: str):
        """Host span (wall time + the ``pcg-tpu/<name>`` trace
        annotation) around several dispatches; free without a
        recorder."""
        if self._rec is None:
            return contextlib.nullcontext()
        return self._rec.span(name)

    def warmup(self, data, fext, carry, normr0, n2b, prec):
        """Compile every budget-loop program by running each ONCE with a
        1-iteration budget: a single Krylov iteration of execution per
        program, negligible next to the minutes-scale XLA compiles this
        front-loads into the persistent compilation cache
        (Solver.warmup / `pcg-tpu warmup`).  CONSUMES ``carry`` when
        donation is on — callers pass a throwaway start state; every
        output is discarded."""
        one = jnp.asarray(1, jnp.int32)
        # Same dispatch names/spans as run(): the warmup call IS the
        # call that pays compile, and booking it cold here keeps the
        # real solve's first dispatch truthfully warm in
        # dispatch_stats() / the run_summary attribution.
        if self.mixed:
            trace = (trace_host_init(self.trace_len)
                     if self.trace_len > 0 else None)
            start_args = (data, carry["r"], normr0, n2b) + (
                (trace,) if trace is not None else ())
            with self._disp("inner_start"):
                rhat32, tol_cycle, c32 = self._inner_start_fn(*start_args)
            cyc_args = (data, rhat32, prec, tol_cycle, c32, one) + (
                (normr0,) if trace is not None else ())
            with self._disp("inner_cycle"):
                _xin, c32, _flag = self._inner_cycle_fn(*cyc_args)
                jax.block_until_ready(c32["exec"])
            with self._disp("final32"):
                xin = self._final32_fn(data, rhat32, c32)
            with self._disp("refine"):
                if self._amul_fn is None:
                    out = self._refine_fn(data, fext, carry["x"], xin,
                                          normr0)
                else:
                    x2 = self._refine_pre_fn(carry["x"], xin, normr0)
                    out = self._refine_post_fn(data, fext,
                                               self._amul_fn(data, x2))
                jax.block_until_ready(out)
        else:
            with self._disp("cycle"):
                _x, c2, _flag, _rel = self._cycle_fn(data, fext, prec,
                                                     carry, one)
                jax.block_until_ready(c2["exec"])
            with self._disp("final"):
                out = self._final_fn(data, fext, c2)
                jax.block_until_ready(out)

    def run(self, data, fext, carry, normr0, n2b, prec,
            vlog: Optional[Callable[[str], None]] = None,
            resilience=None, total0: int = 0):
        """Budget loop from a prepared start state to termination.

        ``carry``: cold carry at the start iterate (``cold_carry``);
        ``prec``: preconditioner inverse (f32 in mixed mode, solve dtype in
        direct mode).  Returns ``(x_fin, flag, relres, total_iters)``.
        The caller handles the ``n2b == 0`` and already-converged early
        exits (they need no dispatches).

        With ``trace_len`` > 0 the convergence ring of the finished solve
        is left (device-resident) on ``self.last_trace`` — unpack it with
        ``obs.trace.unpack_trace`` (that is the single host transfer).

        ``resilience`` (resilience/recovery.ResilienceContext, optional)
        threads the preemption-safety hooks through the loop — all
        no-ops when None.  Healthy-path cost with a context attached:
        the snapshot state thunks are only evaluated at cadence; the
        only unconditional extras are two already-adjacent scalar reads
        per inner dispatch (mixed corruption detection) and, with the
        ladder armed, one device-side copy of the iterate per mixed
        refinement cycle (the restart iterate must survive the refine
        step's buffer donation).  The hooks:

        * chunk boundaries snapshot the resumable state (direct: the
          Krylov carry; mixed: the outer refinement state — chunk
          boundaries align with refinement cycles on this path) and are
          where deterministic faults fire;
        * a device-loss exception from a dispatch re-dispatches from the
          last snapshot via the retry/backoff guard, composing with
          donated-carry dispatch (the snapshot is a HOST copy, so a
          consumed-then-crashed donation cannot orphan the solve);
        * a persisted mid-step snapshot (``--resume`` after a kill)
          replaces the cold start state;
        * a NaN/Inf residual — which trips NO in-graph flag (pcg.py
          BREAKDOWN_FLAGS) — breaks the loop within one chunk so the
          driver's recovery ladder can restart from the min-residual
          iterate instead of burning the whole budget on poison.

        ``total0`` continues the iteration budget across ladder restarts
        and mid-step resumes.  After the loop, ``self.restart_x`` holds
        the iterate a recovery restart should start from (direct: the
        tracked min-residual iterate ``xmin``; mixed: the last iterate
        whose f64 refresh was finite).
        """
        scfg = self.scfg
        vlog = vlog or (lambda s: None)
        self.last_trace = None
        self.restart_x = None
        n2b_f = float(n2b)
        tolb = scfg.tol * n2b_f
        total, flag = int(total0), 1
        cur = float(normr0)
        relres = cur / n2b_f
        x_fin = carry["x"]
        faults = resilience.faults if resilience is not None else None
        resume = (resilience.load_resume_state()
                  if resilience is not None else None)
        if cur <= tolb and resume is None:
            # already converged at entry (a cold start below tol, or a
            # ladder-restart iterate whose true residual already meets
            # it): report the CUMULATIVE iteration count and surface the
            # carry's ring (empty-but-valid) rather than dropping both
            self.last_trace = carry.get("trace")
            self.restart_x = carry.get("xmin")
            return x_fin, 0, relres, total
        if self.mixed:
            x, r, normr = carry["x"], carry["r"], normr0
            stall = 0
            chunk_i = 0
            trace = (trace_host_init(self.trace_len)
                     if self.trace_len > 0 else None)
            def _restore_mixed(st):
                """Snapshot state -> (x, r, normr, stall, total, trace):
                the ONE mixed-state restore, shared by mid-step resume
                and the guard's re-dispatch so the two cannot drift."""
                dev = resilience.restore_device(
                    {k: st[k] for k in ("x", "r")})
                tr = (resilience.restore_device(
                    {"trace": st["trace"]})["trace"]
                    if "trace" in st else None)
                return (dev["x"], dev["r"], np.asarray(st["normr"]),
                        int(np.asarray(st["stall"])),
                        int(np.asarray(st["total"])), tr)

            if resume is not None and _state_kind(resume) == "mixed":
                x, r, normr, stall, total, tr = _restore_mixed(resume)
                if trace is not None and tr is not None:
                    trace = tr
                cur = float(normr)
                relres = cur / n2b_f
            # the restart iterate must survive the refine step's donation
            # of the previous x (a kept alias would die with the buffer);
            # copied only when the driver ladder will actually consume it
            keep_restart = (resilience is not None
                            and resilience.ladder_armed)
            good_x = jnp.copy(x) if keep_restart else None
            while flag == 1 and total < scfg.max_iter:
                # group liveness first, OUTSIDE the dispatch guard: a
                # dead peer surfaces as a named DeadPeerError within the
                # deadline instead of an XLA collective hanging inside
                # the refinement dispatch
                if resilience is not None:
                    resilience.sync_boundary()
                prev = cur
                try:
                    with self._span("refine_cycle"):
                        # One refinement cycle: run the f32 inner solve to
                        # ITS convergence via resumable capped dispatches,
                        # refine.
                        vlog(f"inner_start dispatch "
                             f"(normr={float(normr):.3e})")
                        start_args = (data, r, normr, n2b) + (
                            (trace,) if trace is not None else ())
                        with self._disp("inner_start"):
                            rhat32, tol_cycle, c32 = self._inner_start_fn(
                                *start_args)
                        inner_flag, xin = 1, None
                        first_dispatch, poisoned = True, False
                        while inner_flag == 1 and total < scfg.max_iter:
                            budget = jnp.asarray(scfg.max_iter - total,
                                                 jnp.int32)
                            vlog(f"inner_cycle dispatch (total={total})")
                            if faults is not None:
                                faults.on_dispatch()
                            cyc_args = (data, rhat32, prec, tol_cycle, c32,
                                        budget) + (
                                (normr,) if trace is not None else ())
                            with self._disp("inner_cycle"):
                                xin, c32, iflag = self._inner_cycle_fn(
                                    *cyc_args)
                                # scalar fetches INSIDE the span: jax
                                # dispatch is async, so the span only
                                # measures execution if it contains the
                                # blocking host transfer
                                exec_n = int(c32["exec"])
                                total += exec_n
                                inner_flag = int(iflag)
                            if faults is not None:
                                faults.on_dispatch_done()
                            vlog(f"inner_cycle done: +{exec_n} iters "
                                 f"flag={inner_flag}")
                            if resilience is not None:
                                # Corruption detection off ALREADY-fetched
                                # scalars (no extra host sync on the healthy
                                # path): (a) flag 0 with 0 iterations on the
                                # cycle's FIRST dispatch is impossible for the
                                # normalized inner rhs (||rhat|| = 1 > any
                                # tol_cycle <= 0.25) unless an Inf rhs faked
                                # tolb = tol * ||Inf|| = Inf; (b) a NaN carry
                                # norm trips no MATLAB flag at all.  Either
                                # way, hand the step to the recovery ladder.
                                if (first_dispatch and inner_flag == 0
                                        and exec_n == 0) or not math.isfinite(
                                            float(c32["normr_act"])):
                                    vlog("inner state non-finite/corrupt; "
                                         "handing the step to the recovery "
                                         "ladder")
                                    poisoned = True
                                    break
                            first_dispatch = False
                        if poisoned:
                            if trace is not None:
                                trace = c32["trace"]
                            cur = float("nan")
                            break
                        if trace is not None:
                            # ring hand-off to the next cycle
                            # (device-to-device)
                            trace = c32["trace"]
                        if inner_flag != 0:
                            # Failed/exhausted inner solve: min-residual
                            # selection (the resumable path defers it;
                            # matches one-shot pcg_mixed's inner
                            # finalize_bad).
                            with self._disp("final32"):
                                xin = self._final32_fn(data, rhat32, c32)
                        vlog("refine dispatch (f64 true-residual matvec)")
                        with self._disp("refine"):
                            if self._amul_fn is None:
                                x, r, normr = self._refine_fn(
                                    data, fext, x, xin, normr)
                            else:
                                x = self._refine_pre_fn(x, xin, normr)
                                r, normr = self._refine_post_fn(
                                    data, fext, self._amul_fn(data, x))
                            # blocking fetch inside the span (async
                            # dispatch) — this also absorbs any
                            # still-running earlier program
                            # (inner_start/final32 have no fetch)
                            cur = float(normr)
                except Exception as e:                  # noqa: BLE001
                    st = (resilience.handle_dispatch_failure(e, "mixed")
                          if resilience is not None else None)
                    if st is None:
                        # no retry budget, or no snapshot of THIS mode's
                        # state (e.g. one predating an escalation
                        # switch): escalate to the driver ladder
                        raise
                    # re-dispatch from the snapshot: lose at most one
                    # snapshot interval, not the step
                    x, r, normr, stall, total, tr = _restore_mixed(st)
                    if trace is not None and tr is not None:
                        trace = tr
                    cur = float(normr)
                    if keep_restart:
                        good_x = jnp.copy(x)
                    continue
                vlog(f"refine done: relres={cur / n2b_f:.3e} total={total}")
                if not math.isfinite(cur):
                    # poisoned carry: break BEFORE the snapshot/stall
                    # bookkeeping (never persist non-finite state); the
                    # driver ladder restarts from self.restart_x
                    break
                if keep_restart:
                    good_x = jnp.copy(x)
                chunk_i += 1
                if cur <= tolb:
                    flag = 0
                elif inner_flag == 2:
                    flag = 2
                elif cur > 0.9 * prev:
                    # no meaningful contraction over a refinement cycle
                    stall += 1
                    if stall >= 2:
                        flag = 3
                else:
                    stall = 0
                if resilience is not None and flag == 1:
                    resilience.after_chunk(lambda: dict(
                        kind="mixed", chunk=chunk_i, total=total,
                        stall=stall, normr=normr, x=x, r=r,
                        **({"trace": trace} if trace is not None else {})))
                    if faults is not None:
                        st = faults.at_boundary({"r": r})
                        r = st["r"]
            x_fin, relres = x, cur / n2b_f
            self.last_trace = trace
            self.restart_x = good_x if good_x is not None else x
        else:
            chunk_i = 0

            def _restore_direct(st):
                """Snapshot state -> (carry, total, relres): the ONE
                direct-state restore, shared by mid-step resume and the
                guard's re-dispatch so the two cannot drift.  Fused
                snapshots written before the drift-guard leaf existed
                resume with its cold value (the legacy-shim precedent of
                CheckpointManager.restore)."""
                sc = dict(st["carry"])
                if self.variant == "fused":
                    sc.setdefault("drift", np.zeros((), np.int32))
                c = resilience.restore_device({"carry": sc})["carry"]
                return (c, int(np.asarray(st["total"])),
                        float(np.asarray(sc["normr_act"])) / n2b_f)

            if resume is not None and _state_kind(resume) == "direct":
                carry, total, relres = _restore_direct(resume)
                x_fin = carry["x"]
            while flag == 1 and total < scfg.max_iter:
                # group liveness first, OUTSIDE the dispatch guard: a
                # dead peer surfaces as a named DeadPeerError within the
                # deadline instead of an XLA collective hanging inside
                # the cycle dispatch being misread as device loss
                if resilience is not None:
                    resilience.sync_boundary()
                budget = jnp.asarray(scfg.max_iter - total, jnp.int32)
                try:
                    if faults is not None:
                        faults.on_dispatch()
                    with self._disp("cycle"):
                        x_fin, carry, cflag, crelres = self._cycle_fn(
                            data, fext, prec, carry, budget)
                        # scalar fetches INSIDE the span (async dispatch):
                        # the span must contain the blocking transfer to
                        # time execution, not enqueue
                        total += int(carry["exec"])
                        flag = int(cflag)
                        relres = float(crelres)
                except Exception as e:                  # noqa: BLE001
                    st = (resilience.handle_dispatch_failure(e, "direct")
                          if resilience is not None else None)
                    if st is None:
                        # no retry budget, or no snapshot of THIS mode's
                        # state (e.g. one predating an escalation
                        # switch): escalate to the driver ladder
                        raise
                    # re-dispatch from the snapshot (the donated carry may
                    # have been consumed by the failed dispatch — the host
                    # snapshot is the one copy that cannot have been)
                    carry, total, relres = _restore_direct(st)
                    flag = 1
                    continue
                if faults is not None:
                    faults.on_dispatch_done()
                chunk_i += 1
                if flag != 1 or not math.isfinite(relres):
                    # terminal, or NaN carry (no in-graph flag trips on
                    # NaN): never snapshot past this point — a persisted
                    # poisoned carry would poison the resume too
                    break
                if resilience is not None:
                    resilience.after_chunk(lambda: dict(
                        kind="direct", chunk=chunk_i, total=total,
                        carry=carry))
                    if faults is not None:
                        carry = faults.at_boundary(carry)
            if flag != 0:
                # Terminal failure: the resumable path defers MATLAB pcg's
                # min-residual fallback to here (once per step).
                with self._disp("final"):
                    x_fin, relres_dev = self._final_fn(data, fext, carry)
                    best = float(relres_dev)
                # a NaN-poisoned carry must stay visible to the ladder's
                # nan_carry trigger: classic's select_best propagates the
                # non-finite normr_act through its NaN-false compare, but
                # the fused always-min selection reports the (finite)
                # recomputed min residual — keep the poison marker either
                # way and let the ladder restart from restart_x
                if math.isfinite(relres):
                    relres = best
            self.last_trace = carry.get("trace")
            # min-residual restart iterate for the recovery ladder (only
            # ever updated by committed finite iterations, so it stays
            # finite through NaN poisoning and flag-2/4 breakdowns)
            self.restart_x = carry["xmin"]
            if self.variant in LAGGED_VARIANTS and self._rec is not None \
                    and "drift" in carry:
                # recurrence-variant residual-drift telemetry
                # (obs/schema `resid_drift`): how many deferred
                # true-residual checks disagreed with the recurrence
                # norm this solve (flag 6 routes sustained drift into
                # the ladder; the count is the observability twin) —
                # one scalar fetch, at termination only
                d = int(carry["drift"])
                if d > 0:
                    self._rec.event("resid_drift", drift=d)
                    self._rec.gauge("resid.drift", d)
        return x_fin, flag, relres, total


def auto_dispatch_cap(scfg, glob_n_dof: int, n_loc_dev: int,
                      force_engage: bool = False) -> int:
    """Resolve SolverConfig.iters_per_dispatch (-1 = auto: engage on large
    problems, sized so one dispatch stays well under a minute).

    ``force_engage`` makes auto engage at ANY size — the hybrid backend
    always prefers the chunked path, whose programs instantiate its
    minutes-to-compile stencil strictly fewer times than the one-shot
    step program (1 shared f64 + 1 f32 loop body vs 2 f64 + 1 f32 in one
    program); chunked dispatches are iteration-identical to one-shot."""
    cap = scfg.iters_per_dispatch
    if cap < 0:
        if glob_n_dof < 4_000_000 and not force_engage:
            cap = 0
        else:
            cap = max(200, int(45.0 / (4e-9 * max(n_loc_dev, 1))))
    return int(cap)
