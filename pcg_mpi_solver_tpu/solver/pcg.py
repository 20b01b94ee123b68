"""MATLAB-``pcg``-compatible preconditioned conjugate gradients, fully
in-graph.

Re-implements the reference's PCG (pcg_solver.py:356-598) — itself a
line-for-line port of MATLAB ``pcg`` semantics — as a single
``lax.while_loop``: iterations never leave the device, and every decision the
reference takes on the host (breakdown flags, stagnation, the extra
true-residual matvec on candidate convergence, minimal-residual fallback) is
traced control flow.

Flags (reference pcg_solver.py:399,449,467-469,492-498,560-562):
  0 converged; 1 max-iterations; 2 inf preconditioner; 3 stagnation /
  tolerance too small; 4 rho/pq breakdown.

Per iteration (``variant="classic"``): 3 scalar/fused psums + 1
interface-assembly psum inside the matvec — the same communication count
as the reference's 3 allreduces + 1 halo exchange (SURVEY.md §3.1).

Every loop body (all three variants, scalar and blocked) traces its
phases under ``jax.named_scope`` labels — ``pcg/matvec``,
``pcg/precond``, ``pcg/reduce``, ``pcg/axpy`` — so profiler-trace
events bucket deterministically into the obs/perf.py attribution
phases (obs/profview.py parses them back; the analysis/ fast-tier
``scope-labels`` rule proves the labels exist in every traced variant).
The work outside those four phases is labelled too: ``solve/track`` is
the loop's own bookkeeping (convergence and stagnation tests,
min-residual tracking, the trace ring), ``solve/start`` the work around
the loop (initial residual, norms, terminal selection) and
``solve/residual64`` the mixed path's f64 true-residual refresh.  The
labels are metadata only: the compiled program is the same without them.

``variant="fused"`` restructures the loop body around the
Chronopoulos–Gear recurrence (the single-reduction CG of arXiv:2105.06176
§2): the matvec runs on the preconditioned residual (w = A.z), the search
direction and its A-image advance by recurrence (p = z + beta*p,
q = w + beta*q), and rho = <r,z>, the p.Ap denominator
(mu - beta*rho/alpha_prev), the residual norm, the stagnation norms and
the inf-preconditioner predicate are all read from ONE fused psum — so a
fused iteration is 1 scalar psum + the interface psum, vs classic's 3+1,
and no axpy is serialized between reductions.  The price: convergence/
stagnation tests see the residual of the iterate committed one trip
earlier (the pipelined lag), so iteration counts differ from classic by
O(1) and the variant is NOT bit-exact with the MATLAB reference (classic
stays the parity default).  The deferred true-residual check (mode 1)
and the flag taxonomy are shared between variants.

``variant="pipelined"`` is Ghysels–Vanroose depth-1 pipelined CG
(arXiv:2105.06176 §3, safeguarded per the communication-reduced survey
arXiv:2501.03743): the fused variant's single psum still READS this
iteration's matvec output (mu = <z, A.z>), so the reduction serializes
after the stencil; pipelining removes that last dependency by keeping
the preconditioned residual u = M^-1.r and its A-image w = A.u in the
carry and advancing BOTH by recurrence.  Per trip the single fused psum
(gamma = <r,u>, delta = <w,u>, the residual/stagnation norms, the
inf-preconditioner flag) consumes ONLY previous-iteration carry leaves,
and the trip's preconditioner apply m = M^-1.w plus stencil matvec
n = A.m consume only carry leaves too — the psum and the matvec are
data-independent in BOTH directions, so the scheduler may run the
reduction concurrently with the stencil and the collective's latency
disappears behind compute (the analysis/ ``psum-overlap`` rule proves
that independence on the traced body jaxpr; classic and fused are its
serialized negative controls).  The price: four more recurrence vectors
(s = A.p, q = M^-1.s, z = A.q ride the carry next to u/w), one priming
trip per cold start (u0/w0 through the body's own precond/matvec — no
extra stencil instantiation), and a residual recurrence that drifts
from truth FASTER than fused's (2501.03743 §4) — the same deferred
true-residual drift guard applies with the LOWER
``PIPELINED_DRIFT_LIMIT``.  Iteration counts differ from classic by
O(1); NOT bit-exact with the reference.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from pcg_mpi_solver_tpu.config import PCG_VARIANTS
from pcg_mpi_solver_tpu.obs.trace import trace_record, trace_specs
from pcg_mpi_solver_tpu.ops.matvec import Ops

# Flag taxonomy for recovery policy (resilience/): flags 2 (Inf
# preconditioner), 4 (rho/pq breakdown) and 6 (sustained fused
# residual drift, below) are RECOVERABLE-by-restart — they mean the
# Krylov recurrence collapsed, not that the system is unsolvable, so
# restarting CG from the tracked min-residual iterate (a fresh
# direction set, possibly with a weaker-but-safer preconditioner)
# routinely completes the solve.  Flags 1 (budget) and 3 (stagnation /
# tolerance floor) are NOT in this set: restarts cannot conjure more
# iterations or a finer floor.  NaN carries trip NO flag at all (every
# breakdown predicate compares false on NaN) — detecting them is the
# host-side budget loop's job (solver/chunked.py).
BREAKDOWN_FLAGS = (2, 4, 6)

# Terminal flag of a QUARANTINED column of a blocked multi-RHS solve
# (resilience/engine.run_many_with_recovery, and pcg_many's one-shot
# finalize for a NaN-poisoned column): the column's recovery budget is
# spent (or there is none) and its reported solution is the tracked
# min-residual iterate with its recomputed true residual — the block
# completes instead of failing on one pathological tenant.  Documented
# in docs/RUNBOOK.md "Blocked solve failure modes & quarantine".
QUARANTINE_FLAG = 5

# Fused-variant residual-drift guard (satellite of ISSUE 9, per the
# communication-reduced CG survey arXiv:2501.03743 §4: recurrence-based
# variants accumulate true-vs-recurrence residual drift).  The deferred
# true-residual check (mode 1) already owns an honest recomputed norm;
# when it exceeds FUSED_DRIFT_FACTOR x the recurrence norm that
# prompted the candidacy (and the check did not converge), the
# iteration is counted as DRIFTED in the carry's ``drift`` leaf.  At
# FUSED_DRIFT_LIMIT drifted checks the loop exits with flag 6
# (DRIFT_FLAG) — a recoverable breakdown: the ladder restarts from the
# min-residual iterate with a fresh recurrence instead of letting the
# solve grind on a residual recurrence that no longer tracks truth.
# Constants, not SolverConfig knobs: they gate a failure diagnostic,
# not a numerics choice, so they must not fork cache keys/fingerprints.
DRIFT_FLAG = 6
FUSED_DRIFT_FACTOR = 2.0
FUSED_DRIFT_LIMIT = 3

# The pipelined recurrence keeps FOUR derived vectors (u/w/s/z on top of
# q) current by axpy instead of recomputation, so its residual
# recurrence drifts from the true residual faster than fused's
# (arXiv:2501.03743 §4 measures roughly one extra digit lost per depth)
# — the same flag-6 guard applies with a LOWER limit: two drifted
# deferred checks, not three, hand the solve to the ladder's
# fresh-recurrence restart.
PIPELINED_DRIFT_LIMIT = 2

# Periodic true-residual replacement cadence (the second 2501.03743
# safeguard, and the one that sets the variant's ATTAINABLE accuracy):
# after this many committed iterations without a deferred check, a
# check trip is FORCED — the true residual replaces the recurrence one
# and the priming bit re-arms, re-synchronizing the u/w chain.  Without
# it the f32 recurrence floors near 5e-3 and breaks down (flag 4: the
# delta - beta*gamma/alpha denominator goes non-positive at ~35-80
# iterations on the golden cube); at 25 the f32 inner solve reaches
# tol 1e-5 in ~105 iterations vs classic's 101 (measured), while 50 is
# already too coarse (breakdown before the first replacement).  Cost:
# ~3 extra matvec-bearing trips per cadence — the pending trip whose
# precond/matvec products are abandoned when forced candidacy fires,
# the check (A.x), and the re-prime (M^-1.r + A.u) — ~12% at 25;
# iteration COUNTS are unaffected (forced checks do not advance i,
# count MoreSteps, touch stagnation, or tick the plateau/progress
# windows).  A constant, not a knob: it
# gates a numerical-safety mechanism, so it must not fork cache
# keys/fingerprints.
PIPELINED_REPLACE_EVERY = 25

# Loop formulations (SolverConfig.pcg_variant): "classic" is the
# MATLAB-compatible 3-reduction body, "fused" the Chronopoulos–Gear
# single-reduction recurrence, "pipelined" the Ghysels–Vanroose depth-1
# overlap form (see module docstring).  Derived from the canonical
# config.PCG_VARIANTS name table — the single source the CLI, config
# validation, cache keys and the ops collective tables share.
VALID_PCG_VARIANTS = PCG_VARIANTS

# Variants whose convergence bookkeeping lags the committed iterate by
# one trip (the recurrence forms): their carry ``x`` is an iterate whose
# residual was never evaluated, so terminal selection must take the
# tracked min-residual iterate unconditionally (``select_best
# always_min``), and a warm resume must never flag-0 off the
# predecessor's stale norm.
LAGGED_VARIANTS = ("fused", "pipelined")


def scoped(label: str):
    """Decorator: trace ``f`` under the ``jax.named_scope`` ``label``."""
    def deco(f):
        @functools.wraps(f)
        def g(*args, **kwargs):
            with jax.named_scope(label):
                return f(*args, **kwargs)
        return g
    return deco


def drift_limit_for(variant: str) -> int:
    """Flag-6 drift budget of a recurrence variant's deferred checks."""
    return (PIPELINED_DRIFT_LIMIT if variant == "pipelined"
            else FUSED_DRIFT_LIMIT)


class PCGResult(NamedTuple):
    x: jnp.ndarray        # (P, n_loc) solution on effective dofs (0 elsewhere)
    flag: jnp.ndarray     # () int32
    relres: jnp.ndarray   # () float
    iters: jnp.ndarray    # () int32  (1-based, MATLAB-compatible)


def cold_carry(x0, r0, normr0, dot_dtype, trace=None,
               variant: str = "classic") -> dict:
    """Cold-start Krylov carry for resumable ``pcg`` calls: with p=0, rho=1
    the resumed beta/p recurrence reduces to the standard first iteration
    p = z.  The single schema shared by every chunked-dispatch call site.
    ``trace`` (obs/trace.py ring dict) rides the carry when convergence
    tracing is on — it resumes across dispatch boundaries like the rest of
    the Krylov state.

    Donation contract (solver/chunked.py donated-carry dispatch): a carry
    dict is a linear resource — once passed to a dispatch compiled with
    ``donate_argnums`` on the carry argument, the caller must never touch
    that dict (or any alias of its leaves) again; the next dispatch's
    carry is the previous dispatch's freshly-returned one.  ``pcg``'s
    ``return_carry`` output satisfies the producer side: every returned
    leaf is an output of the traced computation (never a passed-through
    host reference), so donating the INPUT carry can at most alias
    input->output buffers, exactly as intended."""
    dd = dot_dtype
    zero_i = jnp.asarray(0, jnp.int32)
    out = dict(
        x=x0, r=r0, p=jnp.zeros_like(x0),
        rho=jnp.asarray(1.0, dd),
        stag=zero_i, moresteps=zero_i,
        normrmin=jnp.asarray(normr0, dd), xmin=x0, imin=zero_i,
        since_best=zero_i, best_at_reset=jnp.asarray(normr0, dd),
        win_start=jnp.asarray(normr0, dd), win_count=zero_i,
        normr_act=jnp.asarray(normr0, dd), exec=zero_i)
    if variant in LAGGED_VARIANTS:
        # Chronopoulos–Gear recurrence state (``variant="fused"``, and
        # the base of the pipelined carry): ``q`` tracks an A-chain
        # vector alongside p and ``alpha`` is the previous step size.
        # The cold values make the first recurrence trip reduce to the
        # classic first iteration: with p = q = 0 the direction
        # recurrence collapses to p = z, q = w, and alpha = +inf zeroes
        # the denominator correction exactly (beta*rho/inf == 0 in
        # IEEE), leaving alpha = rho/mu — the textbook first step.
        # ``fresh`` gates candidate true-residual checks on a committed
        # update since the last check (see the fused body in ``pcg``).
        out["q"] = jnp.zeros_like(x0)
        out["alpha"] = jnp.asarray(np.inf, dd)
        out["fresh"] = jnp.asarray(1, jnp.int32)
        # drifted-true-residual-check count (drift_limit_for guard);
        # rides the resumable carry so capped dispatches accumulate it
        out["drift"] = zero_i
    if variant == "pipelined":
        # Ghysels–Vanroose recurrence vectors: u = M^-1.r, w = A.u,
        # s = A.p, z = A.q (q doubles as M^-1.s in GV notation).  All
        # cold-zero; ``init`` = 1 arms the PRIMING trip — the first body
        # trip computes u0 = M^-1.r0, w0 = A.u0 through the body's own
        # preconditioner apply and stencil matvec (no pre-loop stencil
        # instantiation, no budget consumed) and clears the bit.
        out["u"] = jnp.zeros_like(x0)
        out["w"] = jnp.zeros_like(x0)
        out["s"] = jnp.zeros_like(x0)
        out["z"] = jnp.zeros_like(x0)
        out["init"] = jnp.asarray(1, jnp.int32)
        # committed iterations since the last deferred check — the
        # PIPELINED_REPLACE_EVERY forced-replacement cadence counter
        out["sc"] = zero_i
    if trace is not None:
        out["trace"] = trace
    return out


def carry_part_specs(part_spec, rep_spec, trace: bool = False,
                     variant: str = "classic", many: bool = False) -> dict:
    """shard_map PartitionSpecs for the carry dict (vectors on the parts
    axis, bookkeeping scalars replicated; the optional trace ring is
    replicated scalar streams; the recurrence variants add their extra
    leaves — fused the A.p vector and replicated scalars, pipelined the
    four GV recurrence vectors plus the priming bit).  ``many`` is
    the RHS-blocked carry (:func:`pcg_many`): same keys with (R,)
    bookkeeping vectors (still replicated) plus the per-RHS ``flag``
    and ``prec_sel`` leaves — a blocked resume must keep
    already-terminated columns frozen and per-column recovery state
    (which preconditioner each column runs, resilience/) intact across
    dispatch boundaries, which the scalar carry never needed."""
    P, R = part_spec, rep_spec
    out = dict(x=P, r=P, p=P, rho=R, stag=R, moresteps=R,
               normrmin=R, xmin=P, imin=R, since_best=R, best_at_reset=R,
               win_start=R, win_count=R,
               normr_act=R, exec=R)
    if variant in LAGGED_VARIANTS:
        out.update(q=P, alpha=R, fresh=R, drift=R)
    if variant == "pipelined":
        out.update(u=P, w=P, s=P, z=P, init=R, sc=R)
    if many:
        out["flag"] = R
        out["prec_sel"] = R
    if trace:
        out["trace"] = trace_specs(R)
    return out


def refine_tol(tolb, normr, inner_tol):
    """Adaptive inner tolerance for one mixed-precision refinement cycle:
    the final cycle only needs to contract the residual by tolb/normr — a
    fixed inner_tol would overshoot the outer tolerance by orders of
    magnitude (wasted iterations)."""
    return jnp.clip(0.5 * tolb / jnp.maximum(normr, tolb * 1e-30),
                    inner_tol, 0.25).astype(jnp.float32)


@scoped("solve/start")
def select_best(ops: Ops, data: dict, fext: jnp.ndarray, carry: dict,
                always_min: bool = False):
    """Min-residual fallback for a terminally-failed resumable solve.

    The ``return_carry`` path of ``pcg`` skips MATLAB pcg's min-residual
    finalize (it would cost one matvec + psum per dispatch whose result the
    resuming caller discards); the driver applies this once, at actual
    termination.  Returns (x, relres) matching finalize_bad's semantics.

    ``always_min`` (the fused variant): the carry ``x`` is the
    pipelined-lag fresh iterate whose residual was never evaluated and
    ``normr_act`` belongs to its predecessor, so the MATLAB
    last-vs-min comparison has no honest operand pair — return the
    min-residual iterate with its recomputed true residual
    unconditionally (an internally consistent (x, relres) pair)."""
    eff = data["eff"]
    w = data["weight"] * eff
    n2b = jnp.sqrt(ops.wdot(w, fext, fext))
    r_min = fext - eff * ops.matvec(data, carry["xmin"])
    normr_min = jnp.sqrt(ops.wdot(w, r_min, r_min))
    den = jnp.maximum(n2b, jnp.asarray(np.finfo(np.float32).tiny, n2b.dtype))
    if always_min:
        return carry["xmin"], normr_min / den
    use_min = normr_min < carry["normr_act"]
    x = jnp.where(use_min, carry["xmin"], carry["x"])
    relres = jnp.where(use_min, normr_min, carry["normr_act"]) / den
    return x, relres


@scoped("solve/start")
def pcg(
    ops: Ops,
    data: dict,
    fext: jnp.ndarray,        # (P, n_loc) rhs, already restricted to eff dofs
    x0: jnp.ndarray,          # (P, n_loc) initial guess (eff-restricted)
    inv_diag,                 # M^-1 on eff dofs (0 elsewhere): (P, n_loc)
                              # scalar Jacobi, (P, n_node_loc, 3, 3)
                              # block-Jacobi, or the mg V-cycle prec
                              # dict (all applied via ops.apply_prec)
    tol,
    max_iter,                 # static int, or traced scalar (then pass
                              # max_iter_nominal for the MoreSteps budget)
    glob_n_dof_eff: int,
    max_stag_steps: int = 3,
    max_iter_nominal: Optional[int] = None,
    carry_in: Optional[dict] = None,
    return_carry: bool = False,
    plateau_window: int = 0,
    x0_zero: bool = False,
    progress_window: int = 0,
    progress_ratio: float = 0.7,
    progress_min_gain: float = 30.0,
    trace_in: Optional[dict] = None,
    trace_scale=None,
    variant: str = "classic",
):
    """Returns PCGResult, or (PCGResult, carry) with ``return_carry``, or
    (PCGResult, trace) when tracing is on without ``return_carry``.

    ``variant`` selects the loop formulation (``VALID_PCG_VARIANTS``):
    "classic" is the MATLAB-compatible 3-reduction body below; "fused"
    the Chronopoulos–Gear single-reduction recurrence; "pipelined" the
    Ghysels–Vanroose depth-1 overlap form (module docstring).  All
    share the carry schema (``cold_carry`` / ``carry_part_specs`` with
    the matching ``variant``), the flag taxonomy, the deferred
    true-residual check, the trace ring and the resumable-dispatch
    contract — a sequence of capped recurrence-variant calls is
    bit-identical to one long solve of that variant, exactly like
    classic.

    ``trace_in`` (an ``obs/trace.py`` ring dict) enables in-graph
    convergence tracing: each committed iteration appends
    (normr, rho, stag, flag) to the device-resident ring inside the
    while_loop — four dynamic-index scalar stores, no extra collectives,
    no host transfers.  With ``return_carry`` the (updated) ring rides the
    returned carry under ``"trace"`` and a subsequent call resumes it via
    ``carry_in`` (so a chunked solve still surfaces ONE ring at the end);
    otherwise the updated ring is returned as a second output.
    ``trace_scale`` rescales recorded residual norms (mixed-precision
    inner cycles iterate on r/||r||; passing ||r|| restores absolute
    residuals in the trace).

    ``progress_window`` > 0 adds a progress-RATE exit for mixed-mode inner
    cycles (flag 3, min-residual iterate — the refinement driver restarts
    in f64): every ``progress_window`` iterations the MONOTONE minimal
    residual ``normrmin`` is compared against its value a window ago; if
    the window contracted it by less than 1/``progress_ratio`` AND the
    cycle has already contracted the rhs norm by ``progress_min_gain``
    (i.e. the cheap early phase is long over and the iterate is plausibly
    near its f32 floor), the remaining grind is worth less than one f64
    restart.  The min-gain gate is what the plateau knob lacked: CG's
    residual is non-monotone and plateaus pre-asymptotically, so a bare
    no-improvement window false-triggers at small scale (window 30
    DIVERGED at iter 31/255);
    requiring 30x achieved contraction first makes early plateaus
    unreachable.  Keep OFF (0) for direct/f64 solves — the reference's
    iteration-parity contract has no such exit.

    ``x0_zero`` declares (statically) that ``x0`` is all zeros, eliding the
    initial-residual matvec: r0 = fext - A.0 = fext exactly, and
    ||r0|| = ||fext|| = n2b (the same reduction).  One fewer stencil
    instantiation in the compiled program — the hybrid octree stencil
    costs minutes of compile time PER INSTANTIATION — and one fewer
    matvec execution at runtime.

    ``plateau_window`` > 0 adds a plateau exit beyond MATLAB pcg's
    stagnation test: if no meaningfully (0.1%) better minimal residual
    appears for that many consecutive iterations, exit with flag 3 and
    the min-residual iterate.  Off (0) by default and EXPERIMENTAL:
    CG's residual is non-monotone pre-asymptotically, so short windows
    false-trigger during healthy convergence (see SolverConfig.
    mixed_plateau_window).  The counter rides the carry, so chunked
    dispatch resumes it exactly.

    ``carry_in`` resumes the Krylov iteration from a previous call's carry
    (search direction, rho, stagnation/min-residual bookkeeping), making a
    sequence of capped-budget calls mathematically identical to one long
    solve — the dispatch-chunked driver path relies on this.  When given,
    it overrides ``x0`` and the initial-residual matvec.
    """
    if variant not in VALID_PCG_VARIANTS:
        raise ValueError(f"pcg variant must be one of "
                         f"{VALID_PCG_VARIANTS}, got {variant!r}")
    fused = variant == "fused"
    pipelined = variant == "pipelined"
    lagged = variant in LAGGED_VARIANTS
    # flag-6 drift budget of this variant's deferred checks (the ONE
    # variant-to-limit dispatch point; trace-time constant)
    drift_limit = drift_limit_for(variant)
    warm = carry_in is not None
    if warm and "trace" in carry_in:
        # resumable dispatch: the ring continues from the previous call
        trace0 = carry_in["trace"]
    else:
        trace0 = trace_in
    traced = trace0 is not None
    eff = data["eff"]
    w = data["weight"] * eff
    dt = fext.dtype
    eps = jnp.asarray(np.finfo(np.dtype(dt)).eps, ops.dot_dtype)

    # MATLAB: maxmsteps = min([floor(n/50), 5, n-maxit])
    nominal = max_iter_nominal if max_iter_nominal is not None else max_iter
    maxmsteps = min(glob_n_dof_eff // 50, 5, glob_n_dof_eff - nominal)

    n2b = jnp.sqrt(ops.wdot(w, fext, fext))
    tolb = tol * n2b

    def amul(v):
        """Assembled K.v restricted to effective dofs (reference computes the
        full product then slices to LocDofEff, pcg_solver.py:482-484).
        Traced under the ``pcg/matvec`` named scope so profiler-trace
        events bucket deterministically (obs/profview.py; the handful of
        out-of-loop applications — r0, finalize, deferred checks — are
        O(1) per solve and absorbed by the per-iteration division).  The
        restriction is the last step of ``mv/scatter``: XLA fuses it with
        the scatter's final adds."""
        with jax.named_scope("pcg/matvec"):
            y = ops.matvec(data, v)
            with jax.named_scope("mv/scatter"):
                return eff * y

    if warm:
        x0 = carry_in["x"]
        r0 = carry_in["r"]
        normr0 = carry_in["normr_act"].astype(ops.dot_dtype)
    elif x0_zero:
        r0 = fext
        normr0 = n2b
    else:
        r0 = fext - amul(x0)
        normr0 = jnp.sqrt(ops.wdot(w, r0, r0))

    zero_rhs = n2b == 0
    if lagged and warm:
        # the warm recurrence-variant normr0 is the PREDECESSOR
        # iterate's norm (the pipelined lag): never flag-0 the
        # unevaluated resumed iterate off it — the first trip reduces
        # the fresh norm and the deferred check gates flag 0 on a true
        # residual as usual
        initial_ok = jnp.asarray(False)
    else:
        initial_ok = normr0 <= tolb

    carry0 = dict(
        x=x0,
        r=r0,
        p=carry_in["p"] if warm else jnp.zeros_like(x0),
        rho=carry_in["rho"] if warm else jnp.asarray(1.0, ops.dot_dtype),
        i=jnp.asarray(0, jnp.int32),
        # zero rhs => skip the loop entirely (reference early-returns,
        # pcg_solver.py:387-395); the outputs are forced to zero below.
        flag=jnp.where(zero_rhs | initial_ok, 0, 1).astype(jnp.int32),
        stag=carry_in["stag"] if warm else jnp.asarray(0, jnp.int32),
        moresteps=carry_in["moresteps"] if warm else jnp.asarray(0, jnp.int32),
        iter_out=jnp.asarray(0, jnp.int32),
        normr_act=normr0.astype(ops.dot_dtype),
        normrmin=carry_in["normrmin"] if warm else normr0.astype(ops.dot_dtype),
        xmin=carry_in["xmin"] if warm else x0,
        imin=carry_in["imin"] if warm else jnp.asarray(0, jnp.int32),
        since_best=(carry_in["since_best"] if warm
                    else jnp.asarray(0, jnp.int32)),
        best_at_reset=(carry_in["best_at_reset"] if warm
                       else normr0.astype(ops.dot_dtype)),
        win_start=(carry_in["win_start"] if warm
                   else normr0.astype(ops.dot_dtype)),
        win_count=(carry_in["win_count"] if warm
                   else jnp.asarray(0, jnp.int32)),
        # mode 1 = the NEXT trip performs the deferred true-residual check
        # of the iteration committed this trip (see body); always 0 at loop
        # exit, so it never rides the exported resume carry
        mode=jnp.asarray(0, jnp.int32),
    )
    if lagged:
        # Chronopoulos–Gear / GV recurrence state (see cold_carry): cold
        # values make the first trip the textbook first CG step; warm
        # values continue the recurrence exactly across dispatch
        # boundaries.
        carry0["q"] = carry_in["q"] if warm else jnp.zeros_like(x0)
        carry0["alpha"] = (carry_in["alpha"] if warm
                           else jnp.asarray(np.inf, ops.dot_dtype))
        carry0["fresh"] = (carry_in["fresh"] if warm
                           else jnp.asarray(1, jnp.int32))
        # residual-drift guard state: cumulative drifted-check count
        # (exported, resumes across dispatches) and the recurrence norm
        # of the pending candidate (internal — mode is always 0 at loop
        # exit, so it never needs to ride the exported carry)
        carry0["drift"] = (carry_in["drift"] if warm
                           else jnp.asarray(0, jnp.int32))
        carry0["chk_normr"] = jnp.asarray(0.0, ops.dot_dtype)
    if pipelined:
        # GV recurrence vectors + the priming bit (see cold_carry): a
        # warm resume continues all five recurrences exactly; a cold
        # start (or a ladder restart that re-armed ``init``) primes
        # u0/w0 on the first trip through the body's own precond/matvec.
        for k in ("u", "w", "s", "z"):
            carry0[k] = carry_in[k] if warm else jnp.zeros_like(x0)
        carry0["init"] = (carry_in["init"] if warm
                          else jnp.asarray(1, jnp.int32))
        carry0["sc"] = (carry_in["sc"] if warm
                        else jnp.asarray(0, jnp.int32))
        # internal: whether the pending mode-1 check was FORCED by the
        # replacement cadence alone (then it must not count MoreSteps /
        # candidacy bookkeeping); mode is always 0 at loop exit, so it
        # never rides the exported carry
        carry0["chk_forced"] = jnp.asarray(0, jnp.int32)
    if traced:
        carry0["trace"] = trace0

    def cond(c):
        return (c["flag"] == 1) & (c["i"] < max_iter)

    def _resolve(c, x, r, p, rho, stag, normr_act, candidate, i,
                 extra=None, record=None, count_windows=None):
        """Shared iteration epilogue (reference pcg_solver.py:536-562):
        stag reset / MoreSteps / min-residual / plateau bookkeeping and
        the flag decision, with ``candidate`` marking a true-residual
        check (then ``normr_act`` is the recomputed actual residual
        norm, else the recurrence norm).  ``extra`` overrides/extends
        the output carry entries AFTER the bookkeeping — the fused body
        uses it to track the min residual against the lagged iterate
        ``x`` while committing the freshly-updated vectors (and its
        q/alpha/fresh recurrence state) to the carry.  ``record`` (a
        traced bool, default always-on) gates the trace-ring append:
        the fused trip after a FAILED true-residual check resolves the
        same iterate a second time and must not write a duplicate
        slot.  ``count_windows`` (a traced bool, default always-on)
        gates the plateau/progress-window counters AND their flag-3
        verdicts: a pipelined CADENCE-forced check resolves no new
        committed iteration, so it must not advance the windows' clocks
        (they would tick ~26x per 25 committed iterations — a silent
        variant-dependent early flag-3 drift)."""
        converged = candidate & (normr_act <= tolb)
        # not converged on candidate: stag reset + MoreSteps bookkeeping
        # (reference pcg_solver.py:544-552)
        stag = jnp.where(candidate & ~converged
                         & (stag >= max_stag_steps) & (c["moresteps"] == 0),
                         0, stag).astype(jnp.int32)
        moresteps = jnp.where(candidate & ~converged,
                              c["moresteps"] + 1,
                              c["moresteps"]).astype(jnp.int32)
        toosmall = candidate & ~converged & (moresteps >= maxmsteps)

        # minimal-residual iterate bookkeeping (pcg_solver.py:554-558)
        better = normr_act < c["normrmin"]
        normrmin = jnp.where(better, normr_act, c["normrmin"])
        xmin = jnp.where(better, x, c["xmin"])
        imin = jnp.where(better, i, c["imin"])
        # the plateau counter demands a MEANINGFUL (0.1%) improvement
        # since the LAST RESET (a snapshot, not the ratcheting
        # normrmin: steady sub-0.1%-per-iteration convergence must
        # accumulate against the snapshot and keep resetting, while
        # hair-thin dips at the f32 floor must not)
        improved = normr_act < c["best_at_reset"] * (1 - 1e-3)
        since_best = jnp.where(improved, 0,
                               c["since_best"] + 1).astype(jnp.int32)
        best_at_reset = jnp.where(improved, normr_act,
                                  c["best_at_reset"])

        stagnated = (stag >= max_stag_steps) & ~converged & ~toosmall
        plateaued = ((since_best > plateau_window) & ~converged
                     & ~toosmall if plateau_window else jnp.asarray(False))

        if progress_window:
            # progress-rate exit (see docstring): evaluated on the
            # MONOTONE normrmin each time a full window elapses
            win_count = c["win_count"] + 1
            at_window = win_count >= progress_window
            weak_window = normrmin > jnp.asarray(
                progress_ratio, normrmin.dtype) * c["win_start"]
            deep_enough = normrmin * jnp.asarray(
                progress_min_gain, normrmin.dtype) < n2b
            no_progress = (at_window & weak_window & deep_enough
                           & ~converged & ~toosmall)
            # window rolls over when it elapses without tripping
            win_start = jnp.where(at_window, normrmin, c["win_start"])
            win_count = jnp.where(at_window, 0, win_count).astype(jnp.int32)
        else:
            no_progress = jnp.asarray(False)
            win_start, win_count = c["win_start"], c["win_count"]

        if count_windows is not None:
            # frozen window clocks (forced checks): keep the carry
            # values and suppress the verdicts those extra ticks alone
            # could have fired — the next committed trip re-derives them
            tick = count_windows
            since_best = jnp.where(tick, since_best,
                                   c["since_best"]).astype(jnp.int32)
            best_at_reset = jnp.where(tick, best_at_reset,
                                      c["best_at_reset"])
            win_start = jnp.where(tick, win_start, c["win_start"])
            win_count = jnp.where(tick, win_count,
                                  c["win_count"]).astype(jnp.int32)
            plateaued = plateaued & tick
            no_progress = no_progress & tick

        flag = jnp.where(converged, 0,
                jnp.where(toosmall | stagnated | plateaued | no_progress, 3,
                          1)).astype(jnp.int32)
        stop = flag != 1
        out = dict(
            x=x, r=r, p=p, rho=rho,
            i=jnp.where(stop, i, i + 1).astype(jnp.int32),
            flag=flag, stag=stag, moresteps=moresteps,
            iter_out=i,
            normr_act=normr_act, normrmin=normrmin, xmin=xmin, imin=imin,
            since_best=since_best, best_at_reset=best_at_reset,
            win_start=win_start, win_count=win_count,
            mode=jnp.asarray(0, jnp.int32),
        )
        if extra:
            out.update(extra)
        if traced:
            # each committed iteration reaches _resolve exactly once
            # (immediately, or via the deferred mode-1 check with the TRUE
            # residual norm) — one ring slot per iteration; the fused
            # body's re-resolve after a failed check sets record=False
            rec_tr = trace_record(
                c["trace"], normr=normr_act, rho=rho, stag=stag, flag=flag,
                scale=trace_scale)
            if record is None:
                out["trace"] = rec_tr
            else:
                out["trace"] = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(record, a, b),
                    rec_tr, c["trace"])
        # carry leaves the epilogue does not own (the fused drift guard
        # state) pass through unchanged unless ``extra`` overrode them —
        # the while carry must stay type-stable across every branch
        for k in c:
            out.setdefault(k, c[k])
        return out

    def body(c):
        """One trip = one CG iteration (mode 0), or the deferred
        true-residual check of the just-committed iteration (mode 1,
        reference pcg_solver.py:527-533; ``i`` does not advance on the
        committing trip, so iteration counts match the reference
        exactly).  The matvec operand is selected BEFORE the single
        ``amul`` below — the stencil is instantiated ONCE in the whole
        loop body, which at octree-flagship scale is minutes of compile
        time per instantiation."""
        i = c["i"]
        is_check = c["mode"] == 1

        def pre_iterate(c):
            # scalar Jacobi inverse (P, n_loc), block-Jacobi inverse
            # (P, n_node_loc, 3, 3), or the mg V-cycle dict —
            # ops.apply_prec dispatches on type/rank (data carries the
            # mg hierarchy; unused by the array preconditioners)
            with jax.named_scope("pcg/precond"):
                z = ops.apply_prec(inv_diag, c["r"], data=data)
            # The inf-preconditioner predicate must agree across shards or
            # the while_loop exits divergently and collective counts
            # desync; fuse its global reduction into the rho psum (still
            # one collective).
            inf_loc = jnp.any(jnp.isinf(z)).astype(ops.dot_dtype)
            with jax.named_scope("pcg/reduce"):
                red = ops.wdots(w, [(z, c["r"])], extra=[inf_loc])
            rho, flag2 = red[0], red[1] > 0
            bad_rho = (rho == 0) | jnp.isinf(rho)
            beta = (rho / c["rho"]).astype(dt)
            with jax.named_scope("pcg/axpy"):
                if warm:
                    # Resumed iteration: the beta/p recurrence continues
                    # from the previous call's direction on the very
                    # first pass.
                    bad_beta = (beta == 0) | jnp.isinf(beta)
                    p = z + beta * c["p"]
                else:
                    bad_beta = (i > 0) & ((beta == 0) | jnp.isinf(beta))
                    p = jnp.where(i == 0, z, z + beta * c["p"])
            return p, dict(rho=rho, flag2=flag2, bad_pre=bad_rho | bad_beta)

        def pre_check(c):
            false = jnp.asarray(False)
            return c["x"], dict(rho=c["rho"], flag2=false, bad_pre=false)

        operand, aux = jax.lax.cond(is_check, pre_check, pre_iterate, c)
        q = amul(operand)     # the ONE stencil instantiation in the body

        def post_iterate(args):
            c, p, q, aux = args
            rho = aux["rho"]
            with jax.named_scope("pcg/reduce"):
                pq = ops.wdot(w, p, q)
            bad_pq = (pq <= 0) | jnp.isinf(pq)
            alpha = (rho / pq).astype(dt)
            bad_alpha = jnp.isinf(alpha)

            breakdown = aux["bad_pre"] | bad_pq | bad_alpha
            new_flag = jnp.where(aux["flag2"], 2,
                                 jnp.where(breakdown, 4, 1)).astype(jnp.int32)

            def on_break(c):
                out = dict(c)
                out["flag"] = new_flag
                out["iter_out"] = i
                out["rho"] = rho
                if traced:
                    # breakdown exits skip the epilogue; record the flag-2/4
                    # slot here so the trace shows WHY the solve died
                    out["trace"] = trace_record(
                        c["trace"], normr=c["normr_act"], rho=rho,
                        stag=c["stag"], flag=new_flag, scale=trace_scale)
                return out

            def on_continue(c):
                with jax.named_scope("pcg/axpy"):
                    r = c["r"] - alpha * q
                # Fused 3-norm reduction: ||p||, ||x_old||, ||r|| in ONE
                # psum (reference pcg_solver.py:504-507).
                with jax.named_scope("pcg/reduce"):
                    sq = ops.wdots(w, [(p, p), (c["x"], c["x"]), (r, r)])
                normp, normx, normr = (jnp.sqrt(sq[0]), jnp.sqrt(sq[1]),
                                       jnp.sqrt(sq[2]))
                stag = jnp.where(
                    normp * jnp.abs(alpha).astype(ops.dot_dtype)
                    < eps * normx,
                    c["stag"] + 1, 0).astype(jnp.int32)
                with jax.named_scope("pcg/axpy"):
                    x = c["x"] + alpha * p

                candidate = ((normr <= tolb) | (stag >= max_stag_steps)
                             | (c["moresteps"] > 0))

                # Non-candidate epilogue (normr_act := recurrence norm).
                resolved = _resolve(c, x=x, r=r, p=p, rho=rho, stag=stag,
                                    normr_act=normr.astype(ops.dot_dtype),
                                    candidate=jnp.asarray(False), i=i)
                # Candidate: COMMIT the iterate but DEFER the epilogue to
                # the next trip's true-residual check (mode 1); i, flag and
                # all bookkeeping are untouched until then.
                pending = dict(c, x=x, r=r, p=p, rho=rho, stag=stag,
                               iter_out=i, mode=jnp.asarray(1, jnp.int32))
                return jax.tree_util.tree_map(
                    lambda a, b: jnp.where(candidate, a, b),
                    pending, resolved)

            return jax.lax.cond(aux["flag2"] | breakdown, on_break,
                                on_continue, c)

        def post_check(args):
            c, _x, q, _aux = args
            # q = amul(x): recompute the ACTUAL residual before declaring
            # convergence (reference pcg_solver.py:527-533).
            r_true = fext - q
            with jax.named_scope("pcg/reduce"):
                normr_act = jnp.sqrt(ops.wdot(w, r_true, r_true))
            return _resolve(c, x=c["x"], r=r_true, p=c["p"], rho=c["rho"],
                            stag=c["stag"], normr_act=normr_act,
                            candidate=jnp.asarray(True), i=i)

        return jax.lax.cond(is_check, post_check, post_iterate,
                            (c, operand, q, aux))

    def body_fused(c):
        """One trip of the fused-collective (Chronopoulos–Gear) variant:
        z = M^-1.r, w = A.z (still the ONE stencil instantiation per
        body), then EVERY per-iteration reduction of the classic loop —
        rho = <r,z>, the p.Ap denominator via mu = <z,w>, the residual
        norm, the stagnation norms and the inf-preconditioner predicate
        — in a SINGLE fused psum.  The search direction and its A-image
        advance by recurrence (p = z + beta*p, q = w + beta*q;
        <p,Ap> = mu - beta*rho/alpha_prev in exact arithmetic), so no
        reduction serializes against an axpy.

        Pipelined lag: the reduction reads the residual of the iterate
        committed LAST trip, so the epilogue (stag / min-residual /
        candidate detection) resolves that iterate while this trip's
        update is computed — iteration counts differ from classic by
        O(1).  Mode 1 is the same deferred true-residual check as
        classic, but gated by the ``fresh`` carry bit so a failed check
        always commits an update before re-checking (MATLAB's MoreSteps
        alternation; without the gate the moresteps>0 clause would
        re-check the same iterate forever)."""
        i = c["i"]
        is_check = c["mode"] == 1

        def pre_iterate(c):
            # scalar/block-Jacobi inverse or mg V-cycle (classic
            # pre_iterate's z)
            with jax.named_scope("pcg/precond"):
                return ops.apply_prec(inv_diag, c["r"], data=data)

        def pre_check(c):
            return c["x"]

        operand = jax.lax.cond(is_check, pre_check, pre_iterate, c)
        kop = amul(operand)   # the ONE stencil instantiation in the body

        def post_iterate(args):
            c, z, wz = args
            # the inf-preconditioner predicate rides the same collective
            # (classic fuses it into the rho psum the same way)
            inf_loc = jnp.any(jnp.isinf(z)).astype(ops.dot_dtype)
            with jax.named_scope("pcg/reduce"):
                red = ops.wdots(w, [(c["r"], z), (z, wz),
                                    (c["r"], c["r"]), (c["p"], c["p"]),
                                    (c["x"], c["x"])], extra=[inf_loc])
            rho, mu = red[0], red[1]
            normr = jnp.sqrt(red[2])
            normp, normx = jnp.sqrt(red[3]), jnp.sqrt(red[4])
            flag2 = red[5] > 0

            # lagged stagnation bookkeeping: the update committed LAST
            # trip moved x by alpha_prev * p (both ride the carry).  On
            # a cold start p = 0 and alpha_prev = inf make the product
            # NaN, which compares False — no increment, as there is no
            # update to check yet.  MATLAB compares against ||x_old||;
            # the fused form uses the post-update ||x|| already in the
            # reduction (an eps-scale test — the variant is documented
            # non-bit-exact).
            # fresh == 0 means the CURRENT iterate's epilogue was already
            # resolved by the preceding (failed) true-residual check —
            # the same update must not be stag-checked twice, and the
            # ring must not get a duplicate slot (record below)
            already = c["fresh"] == 0
            small = normp * jnp.abs(c["alpha"]) < eps * normx
            stag = jnp.where(already, c["stag"],
                             jnp.where(small, c["stag"] + 1,
                                       0)).astype(jnp.int32)
            candidate = (((normr <= tolb) | (stag >= max_stag_steps)
                          | (c["moresteps"] > 0)) & ~already)

            # Chronopoulos–Gear scalars; same breakdown taxonomy as
            # classic (bad denominator <=0/Inf <=> classic's bad pq —
            # SPD demands <p,Ap> > 0).  A candidate trip skips them: rho
            # legitimately collapses as r -> 0, and the true-residual
            # check decides before a spurious flag 4 can.
            bad_rho = (rho == 0) | jnp.isinf(rho)
            beta = rho / c["rho"]
            bad_beta = (beta == 0) | jnp.isinf(beta)
            pq = mu - beta * rho / c["alpha"]
            bad_pq = (pq <= 0) | jnp.isinf(pq)
            alpha = rho / pq
            bad_alpha = jnp.isinf(alpha)
            breakdown = bad_rho | bad_beta | bad_pq | bad_alpha
            new_flag = jnp.where(flag2, 2,
                                 jnp.where(breakdown, 4, 1)).astype(jnp.int32)

            def on_break(c):
                out = dict(c)
                out["flag"] = new_flag
                out["iter_out"] = i
                out["rho"] = rho
                if traced:
                    out["trace"] = trace_record(
                        c["trace"], normr=normr, rho=rho,
                        stag=stag, flag=new_flag, scale=trace_scale)
                return out

            def on_continue(c):
                beta_dt = beta.astype(dt)
                alpha_dt = alpha.astype(dt)
                with jax.named_scope("pcg/axpy"):
                    p2 = z + beta_dt * c["p"]    # p = 0 cold => p2 = z
                    q2 = wz + beta_dt * c["q"]   # A.p by recurrence
                    x2 = c["x"] + alpha_dt * p2
                    r2 = c["r"] - alpha_dt * q2
                # Epilogue of the LAGGED iterate (min residual tracked
                # against c["x"], whose norm this trip's reduction
                # computed), while the carry commits the fresh update.
                resolved = _resolve(
                    c, x=c["x"], r=c["r"], p=c["p"], rho=rho, stag=stag,
                    normr_act=normr.astype(ops.dot_dtype),
                    candidate=jnp.asarray(False), i=i,
                    extra=dict(x=x2, r=r2, p=p2, q=q2,
                               alpha=alpha.astype(ops.dot_dtype),
                               fresh=jnp.asarray(1, jnp.int32)),
                    record=~already)
                # Candidate: defer to the next trip's true-residual
                # check of the CURRENT iterate; nothing is committed
                # (``chk_normr`` records the recurrence norm the check
                # will be compared against — the drift guard).
                pending = dict(c, stag=stag, iter_out=i,
                               mode=jnp.asarray(1, jnp.int32),
                               chk_normr=normr)
                return jax.tree_util.tree_map(
                    lambda a, b: jnp.where(candidate, a, b),
                    pending, resolved)

            return jax.lax.cond((flag2 | breakdown) & ~candidate,
                                on_break, on_continue, c)

        def post_check(args):
            c, _x, kx = args
            # kx = amul(x): recompute the ACTUAL residual before
            # declaring convergence (same contract as classic).  ``i``
            # must not advance (no update was committed on the candidate
            # trip), and ``fresh`` drops so a failed check cannot
            # re-fire without an intervening committed update.
            r_true = fext - kx
            with jax.named_scope("pcg/reduce"):
                normr_act = jnp.sqrt(ops.wdot(w, r_true, r_true))
            # residual-drift guard (arXiv:2501.03743): a non-converged
            # check whose TRUE residual exceeds FUSED_DRIFT_FACTOR x the
            # recurrence norm that prompted the candidacy means the
            # recurrence residual no longer tracks truth
            disagree = ((normr_act > tolb)
                        & (normr_act > jnp.asarray(
                            FUSED_DRIFT_FACTOR, normr_act.dtype)
                           * c["chk_normr"]))
            drift = (c["drift"] + disagree).astype(jnp.int32)
            out = _resolve(c, x=c["x"], r=r_true, p=c["p"], rho=c["rho"],
                           stag=c["stag"], normr_act=normr_act,
                           candidate=jnp.asarray(True), i=i,
                           extra=dict(q=c["q"], alpha=c["alpha"],
                                      fresh=jnp.asarray(0, jnp.int32),
                                      i=i, drift=drift))
            # sustained drift: exit recoverably (flag 6) instead of
            # grinding on a stale recurrence — the ladder restarts from
            # the min-residual iterate with a fresh recurrence
            drift_exit = (out["flag"] == 1) & (drift >= drift_limit)
            out["flag"] = jnp.where(drift_exit, DRIFT_FLAG,
                                    out["flag"]).astype(jnp.int32)
            return out

        return jax.lax.cond(is_check, post_check, post_iterate,
                            (c, operand, kop))

    def body_pipelined(c):
        """One trip of the Ghysels–Vanroose depth-1 pipelined variant.

        The single fused psum is issued FIRST, on previous-iteration
        carry state only — gamma = <r,u>, delta = <w,u> (u = M^-1.r and
        w = A.u ride the carry by recurrence), the residual/stagnation
        norms and the inf-preconditioner flag (read off the carry ``u``,
        where an Inf inverse lands at priming) — and the trip's
        preconditioner apply m = M^-1.w plus stencil matvec n = A.m
        consume only carry state too: neither the psum nor the matvec
        transitively reads the other's output, so the lowered program
        is free to overlap the collective with the stencil (the
        analysis/ psum-overlap rule proves the independence; the psum
        is NOT placed inside the mode conditional precisely so the
        dependence structure stays first-order visible).

        Trip kinds: mode 1 is the shared deferred true-residual check;
        an armed ``init`` bit makes the trip a PRIMING trip (cold start
        or ladder restart) that computes u0 = M^-1.r0, w0 = A.u0
        through the same precond/matvec slots and commits nothing else;
        otherwise the trip advances the x/r/u/w and p/s/q/z recurrences
        (GV: p = u + beta*p, s = w + beta*s, q = m + beta*q,
        z = n + beta*z, then x += alpha*p, r -= alpha*s, u -= alpha*q,
        w -= alpha*z).  Epilogue semantics (pipelined lag, ``fresh``
        gate, drift guard) mirror the fused body, with
        PIPELINED_DRIFT_LIMIT as the flag-6 budget."""
        i = c["i"]
        is_check = c["mode"] == 1

        # ---- the ONE fused psum: carry-state operands only ------------
        inf_loc = jnp.any(jnp.isinf(c["u"])).astype(ops.dot_dtype)
        with jax.named_scope("pcg/reduce"):
            red = ops.wdots(w, [(c["r"], c["u"]), (c["w"], c["u"]),
                                (c["r"], c["r"]), (c["p"], c["p"]),
                                (c["x"], c["x"])], extra=[inf_loc])
        gamma, delta = red[0], red[1]
        normr = jnp.sqrt(red[2])
        normp, normx = jnp.sqrt(red[3]), jnp.sqrt(red[4])
        flag2 = red[5] > 0

        def pre_check(c):
            return c["x"]

        def pre_work(c):
            # priming trips precondition the residual (u0 = M^-1.r0);
            # iterate trips precondition w (m = M^-1.w — the GV overlap
            # operand).  Both sources are carry leaves: the apply never
            # waits on the psum above.
            src = jnp.where(c["init"] > 0, c["r"], c["w"])
            with jax.named_scope("pcg/precond"):
                return ops.apply_prec(inv_diag, src, data=data)

        m = jax.lax.cond(is_check, pre_check, pre_work, c)
        km = amul(m)          # the ONE stencil instantiation in the body

        def post_prime(args):
            c, m, km = args
            # commit u0 = M^-1.r0 and w0 = A.u0; no iteration advances,
            # no budget is consumed — the next trip is the textbook
            # first step (p = s = q = z = 0, alpha_prev = inf)
            return dict(c, u=m, w=km, init=jnp.asarray(0, jnp.int32))

        def post_iterate(args):
            c, m, km = args
            # lagged stagnation bookkeeping: identical contract to the
            # fused body (the update committed LAST trip moved x by
            # alpha_prev * p; cold p = 0 / alpha_prev = inf compare
            # False — nothing to check yet)
            already = c["fresh"] == 0
            small = normp * jnp.abs(c["alpha"]) < eps * normx
            stag = jnp.where(already, c["stag"],
                             jnp.where(small, c["stag"] + 1,
                                       0)).astype(jnp.int32)
            natural = ((normr <= tolb) | (stag >= max_stag_steps)
                       | (c["moresteps"] > 0))
            # forced replacement cadence (PIPELINED_REPLACE_EVERY):
            # a check trip fires even without natural candidacy, purely
            # to re-synchronize the residual chain
            forced = c["sc"] >= PIPELINED_REPLACE_EVERY
            candidate = (natural | forced) & ~already

            # GV scalars; breakdown taxonomy shared with fused (the
            # denominator delta - beta*gamma/alpha_prev is <p,Ap> in
            # exact arithmetic — SPD demands > 0)
            bad_rho = (gamma == 0) | jnp.isinf(gamma)
            beta = gamma / c["rho"]
            bad_beta = (beta == 0) | jnp.isinf(beta)
            pq = delta - beta * gamma / c["alpha"]
            bad_pq = (pq <= 0) | jnp.isinf(pq)
            alpha = gamma / pq
            bad_alpha = jnp.isinf(alpha)
            breakdown = bad_rho | bad_beta | bad_pq | bad_alpha
            new_flag = jnp.where(flag2, 2,
                                 jnp.where(breakdown, 4, 1)).astype(jnp.int32)

            def on_break(c):
                out = dict(c)
                out["flag"] = new_flag
                out["iter_out"] = i
                out["rho"] = gamma
                if traced:
                    out["trace"] = trace_record(
                        c["trace"], normr=normr, rho=gamma,
                        stag=stag, flag=new_flag, scale=trace_scale)
                return out

            def on_continue(c):
                beta_dt = beta.astype(dt)
                alpha_dt = alpha.astype(dt)
                with jax.named_scope("pcg/axpy"):
                    p2 = c["u"] + beta_dt * c["p"]  # p = 0 cold => p2 = u
                    s2 = c["w"] + beta_dt * c["s"]  # A.p by recurrence
                    q2 = m + beta_dt * c["q"]       # M^-1.s by recurrence
                    z2 = km + beta_dt * c["z"]      # A.q by recurrence
                    x2 = c["x"] + alpha_dt * p2
                    r2 = c["r"] - alpha_dt * s2
                    u2 = c["u"] - alpha_dt * q2     # M^-1.r by recurrence
                    w2 = c["w"] - alpha_dt * z2     # A.u by recurrence
                resolved = _resolve(
                    c, x=c["x"], r=c["r"], p=c["p"], rho=gamma, stag=stag,
                    normr_act=normr.astype(ops.dot_dtype),
                    candidate=jnp.asarray(False), i=i,
                    extra=dict(x=x2, r=r2, p=p2, u=u2, w=w2, s=s2, q=q2,
                               z=z2, alpha=alpha.astype(ops.dot_dtype),
                               fresh=jnp.asarray(1, jnp.int32),
                               sc=(c["sc"] + 1).astype(jnp.int32)),
                    record=~already)
                pending = dict(c, stag=stag, iter_out=i,
                               mode=jnp.asarray(1, jnp.int32),
                               chk_normr=normr,
                               chk_forced=(forced & ~natural
                                           ).astype(jnp.int32))
                return jax.tree_util.tree_map(
                    lambda a, b: jnp.where(candidate, a, b),
                    pending, resolved)

            return jax.lax.cond((flag2 | breakdown) & ~candidate,
                                on_break, on_continue, c)

        def post_work(args):
            c = args[0]
            return jax.lax.cond(c["init"] > 0, post_prime, post_iterate,
                                args)

        def post_check(args):
            c, _x, kx = args
            # kx = amul(x): the shared deferred true-residual check,
            # plus the 2501.03743 TRUE-RESIDUAL REPLACEMENT the
            # pipelined recurrence needs: the carry residual is replaced
            # with the recomputed one (like classic/fused), and because
            # u = M^-1.r / w = A.u advance by recurrence against the OLD
            # r, the priming bit is RE-ARMED — the next trip rebuilds
            # u/w from the honest residual through the body's own
            # precond/matvec (one extra trip per check, no budget
            # consumed), re-synchronizing the residual chain instead of
            # letting f32 recurrence drift degrade the search.  The
            # p/s and q/z direction chains are exact recurrence PAIRS
            # (s mirrors p under A, z mirrors q), so they stay.
            # Sustained disagreement still exits via flag 6 at the
            # TIGHTER pipelined budget — replacement bounds drift per
            # check; the counter catches a recurrence that keeps lying.
            r_true = fext - kx
            with jax.named_scope("pcg/reduce"):
                normr_act = jnp.sqrt(ops.wdot(w, r_true, r_true))
            disagree = ((normr_act > tolb)
                        & (normr_act > jnp.asarray(
                            FUSED_DRIFT_FACTOR, normr_act.dtype)
                           * c["chk_normr"]))
            drift = (c["drift"] + disagree).astype(jnp.int32)
            # a CADENCE-forced check must not act as convergence
            # candidacy (no MoreSteps/stagnation bookkeeping) nor tick
            # the plateau/progress-window clocks (count_windows) — it
            # only replaces the residual and re-primes; a natural check
            # runs the full shared candidate epilogue
            natural = c["chk_forced"] == 0
            out = _resolve(c, x=c["x"], r=r_true, p=c["p"], rho=c["rho"],
                           stag=c["stag"], normr_act=normr_act,
                           candidate=natural, i=i,
                           extra=dict(fresh=jnp.asarray(0, jnp.int32),
                                      i=i, drift=drift,
                                      init=jnp.asarray(1, jnp.int32),
                                      sc=jnp.asarray(0, jnp.int32),
                                      chk_forced=jnp.asarray(
                                          0, jnp.int32)),
                           count_windows=natural)
            drift_exit = ((out["flag"] == 1)
                          & (drift >= drift_limit))
            out["flag"] = jnp.where(drift_exit, DRIFT_FLAG,
                                    out["flag"]).astype(jnp.int32)
            return out

        return jax.lax.cond(is_check, post_check, post_work, (c, m, km))

    loop_body = (body_pipelined if pipelined
                 else body_fused if fused else body)
    # the loop itself is ``solve/track``: its cond, its body and the
    # carry copies XLA adds around the loop (which carry the while op's
    # label) all count as the loop's own work
    with jax.named_scope("solve/track"):
        c = jax.lax.while_loop(cond, loop_body, carry0)

    # ---- finalize (reference pcg_solver.py:566-584): on any non-converged
    # exit return the minimal-residual iterate (MATLAB pcg semantics).
    def finalize_ok(c):
        relres = c["normr_act"] / n2b
        return c["x"], relres, c["iter_out"]

    def finalize_bad(c):
        # MATLAB pcg: on failure return whichever of (last iterate, minimal-
        # residual iterate) actually has the smaller true residual, with
        # matching relres/iters.  (The reference accidentally always returns
        # XMin while reporting the better residual, pcg_solver.py:566-582 —
        # we keep x consistent with the reported numbers instead.)
        r_min = fext - amul(c["xmin"])
        normr_min = jnp.sqrt(ops.wdot(w, r_min, r_min))
        if lagged:
            # pipelined lag: the carry x is the fresh iterate whose
            # residual was never evaluated, and normr_act belongs to its
            # predecessor — the min-residual iterate is the only
            # candidate with an honest (recomputed) residual, so return
            # it unconditionally (x/relres/iters stay consistent)
            return c["xmin"], normr_min / n2b, c["imin"]
        use_min = normr_min < c["normr_act"]
        relres = jnp.where(use_min, normr_min, c["normr_act"]) / n2b
        iters = jnp.where(use_min, c["imin"], c["iter_out"])
        x = jnp.where(use_min, c["xmin"], c["x"])
        return x, relres, iters

    if return_carry:
        # Resumable call: skip the min-residual finalize (one matvec + psum
        # per dispatch the resuming caller would discard) — the caller runs
        # select_best() once at actual termination.
        x, relres, iters = finalize_ok(c)
    else:
        x, relres, iters = jax.lax.cond(
            c["flag"] == 0, finalize_ok, finalize_bad, c)

    # all-zero rhs => all-zero solution (reference pcg_solver.py:387-395)
    x = jnp.where(zero_rhs, jnp.zeros_like(x), x)
    relres = jnp.where(zero_rhs, 0.0, relres)
    # +1 makes the count 1-based (MATLAB-compatible, pcg_solver.py:584);
    # the two pre-loop early exits report 0 (pcg_solver.py:392,424).
    iters = jnp.where(zero_rhs | initial_ok, 0, iters + 1)
    flag = jnp.where(zero_rhs, 0, c["flag"]).astype(jnp.int32)

    result = PCGResult(x=x, flag=flag, relres=relres.astype(jnp.float32), iters=iters)
    if return_carry:
        # Raw (non-finalized) continuation state: x is the LAST iterate, not
        # the min-residual fallback — resuming must continue the recurrence.
        # Every entry comes out of the while_loop carry (fresh outputs of
        # the traced program), which is what makes the chunked engine's
        # donated-carry dispatch safe (see cold_carry's donation contract).
        keys = ["x", "r", "p", "rho", "stag", "moresteps",
                "normrmin", "xmin", "imin", "since_best",
                "best_at_reset", "win_start", "win_count", "normr_act"]
        if lagged:
            # the recurrence state resumes like the rest of the Krylov
            # carry (the A-chain vector q, the previous alpha, the
            # update-since-check gate, and the drift-guard count)
            keys += ["q", "alpha", "fresh", "drift"]
        if pipelined:
            # the GV vectors, the priming bit (a dispatch that exits
            # before its priming trip ran must hand the armed bit to
            # its successor) and the replacement-cadence counter
            keys += ["u", "w", "s", "z", "init", "sc"]
        carry = {k: c[k] for k in keys}
        # Executed body-iteration count for host-side budget accounting
        # (result.iters reports the min-residual index on failure, which
        # would undercount).
        carry["exec"] = jnp.where(zero_rhs | initial_ok, 0,
                                  c["iter_out"] + 1).astype(jnp.int32)
        if traced:
            carry["trace"] = c["trace"]
        return result, carry
    if traced:
        return result, c["trace"]
    return result


@scoped("solve/start")
def pcg_mixed(
    ops32: Ops,
    data32: dict,
    ops64: Ops,
    data64: dict,
    fext: jnp.ndarray,        # (P, n_loc) f64 rhs on eff dofs
    x0: jnp.ndarray,          # (P, n_loc) f64 initial guess
    inv_diag32: jnp.ndarray,  # f32 preconditioner inverse (scalar Jacobi
                              # (P, n_loc) or block-Jacobi (P, n, 3, 3))
    tol: float,
    max_iter: int,
    glob_n_dof_eff: int,
    max_stag_steps: int = 3,
    inner_tol: float = 1e-5,
    max_outer: int = 12,
    plateau_window: int = 0,
    progress_window: int = 0,
    progress_ratio: float = 0.7,
    progress_min_gain: float = 30.0,
    trace_in: Optional[dict] = None,
    variant: str = "classic",
) -> PCGResult:
    """Mixed-precision PCG by iterative refinement (TPU performance path).

    ``variant`` selects the inner f32 Krylov loop's formulation
    (``pcg``'s classic 3-reduction body or the fused Chronopoulos–Gear
    single-reduction recurrence); the f64 refinement shell is identical
    either way.

    ``trace_in`` (f32 ring dict, obs/trace.py) threads in-graph convergence
    tracing through the f32 inner cycles: recorded norms are rescaled by
    the cycle's f64 refresh norm, so the trace reads as ABSOLUTE residuals
    across the whole refinement sequence.  Returns (PCGResult, trace) when
    given.

    Finite-precision CG can only reach a relative residual of roughly
    eps*kappa; in f32 that is far above the reference's tol=1e-7 (SURVEY.md §7
    "hard parts (a)").  Classic fix: run the Krylov iterations in fast f32 on
    a NORMALIZED residual (so f32's dynamic range is centered), and
    periodically recompute the true residual and accumulate the solution in
    f64.  Each outer cycle costs one f64 matvec (emulated on TPU but rare);
    all hot iterations run at f32/MXU speed.  Total inner-iteration count is
    comparable to a pure-f64 solve.
    """
    eff64 = data64["eff"]
    w64 = data64["weight"] * eff64

    def amul64(v):
        return eff64 * ops64.matvec(data64, v)

    n2b = jnp.sqrt(ops64.wdot(w64, fext, fext))
    tolb = tol * n2b

    # The f64 residual is refreshed at the TOP of the loop body (for the
    # CURRENT x) instead of pre-loop + bottom: the numerical sequence
    # r0, inner, r1, inner, ..., rN is identical, but the f64 stencil is
    # instantiated ONCE in the whole program instead of twice — at octree
    # flagship scale each instantiation is minutes of compile time.
    # Internal flag -1 = still running
    # (the final residual evaluation happens in-body, so the loop cond
    # only tests the flag).
    carry0 = dict(
        x=x0,
        normr=jnp.asarray(np.inf, ops64.dot_dtype),   # last refreshed norm
        outer=jnp.asarray(0, jnp.int32),
        total=jnp.asarray(0, jnp.int32),
        flag=jnp.where(n2b == 0, 0, -1).astype(jnp.int32),
        # inner inf-preconditioner exit last cycle: terminal flag 2, but
        # only AFTER this trip's refresh so the reported residual is the
        # post-cycle one (matches the refresh-at-bottom formulation)
        fatal2=jnp.asarray(False),
    )
    traced = trace_in is not None
    if traced:
        carry0["trace"] = trace_in

    def cond(c):
        return c["flag"] == -1

    def body(c):
        with jax.named_scope("solve/residual64"):
            r = fext - amul64(c["x"])
            normr = jnp.sqrt(ops64.wdot(w64, r, r))
        converged = normr <= tolb
        # no-progress guard: refinement must contract the residual
        # (first trip: normr_prev = inf, never trips)
        stalled = normr > 0.5 * c["normr"]
        exhausted = (c["outer"] >= max_outer) | (c["total"] >= max_iter)
        run_inner = ~(converged | stalled | c["fatal2"] | exhausted)

        def do_inner(args):
            r, normr = args
            rhat32 = (r / normr).astype(jnp.float32)
            remaining = jnp.maximum(max_iter - c["total"], 1)
            tol_cycle = refine_tol(tolb, normr, inner_tol)
            # return_carry gives the EXECUTED body-iteration count: on
            # flag-3 exits inner.iters is the min-residual index, which
            # would both undercount the reported work and let the budget
            # run past max_iter.
            inner, icarry = pcg(
                ops32, data32,
                fext=rhat32,
                x0=jnp.zeros_like(rhat32),
                inv_diag=inv_diag32,
                tol=tol_cycle,
                max_iter=remaining,
                glob_n_dof_eff=glob_n_dof_eff,
                max_stag_steps=max_stag_steps,
                max_iter_nominal=max_iter,
                plateau_window=plateau_window,
                return_carry=True,
                x0_zero=True,
                progress_window=progress_window,
                progress_ratio=progress_ratio,
                progress_min_gain=progress_min_gain,
                trace_in=c["trace"] if traced else None,
                # inner iterations run on r/normr: rescale recorded norms
                # to absolute residuals
                trace_scale=normr if traced else None,
                variant=variant,
            )
            # return_carry skips the min-residual finalize, so inner.x is
            # the LAST iterate.  CG's residual is non-monotone: on a
            # non-converged exit (flag 3 from the progress/plateau exits,
            # or budget flag 1) a spiked last iterate hands the f64
            # refresh a worse restart and can spuriously trip the 0.5x
            # stalled guard.  Select the tracked min-residual iterate
            # in-graph — normrmin/xmin ride the carry.  Unlike
            # select_best (the chunked path's finalize, which recomputes
            # xmin's TRUE residual), this trusts the recurrence-tracked
            # norms: one more stencil instantiation here would cost
            # minutes of compile at octree scale for a tie-break that
            # the outer loop immediately re-evaluates anyway — the next
            # trip's f64 refresh computes the true residual of whichever
            # iterate wins, and the 0.5x stalled guard bounds the damage
            # of a drift-optimistic pick.
            use_min = (inner.flag != 0) & (
                icarry["normrmin"] < icarry["normr_act"])
            xbest = jnp.where(use_min, icarry["xmin"], inner.x)
            out = (xbest.astype(fext.dtype) * normr,
                   jnp.maximum(icarry["exec"], 1), inner.flag)
            return out + ((icarry["trace"],) if traced else ())

        def skip_inner(args):
            r, _ = args
            out = (jnp.zeros_like(fext), jnp.asarray(0, jnp.int32),
                   jnp.asarray(1, jnp.int32))
            return out + ((c["trace"],) if traced else ())

        inner_out = jax.lax.cond(
            run_inner, do_inner, skip_inner, (r, normr))
        xinc, exec_n, inner_flag = inner_out[:3]

        flag = jnp.where(
            converged, 0,
            jnp.where(stalled, 3,
             jnp.where(c["fatal2"], 2,
              jnp.where(exhausted, 1, -1)))).astype(jnp.int32)
        out = dict(x=c["x"] + xinc, normr=normr,
                   outer=c["outer"] + run_inner.astype(jnp.int32),
                   total=c["total"] + exec_n, flag=flag,
                   fatal2=inner_flag == 2)
        if traced:
            out["trace"] = inner_out[3]
        return out

    c = jax.lax.while_loop(cond, body, carry0)
    zero_rhs = n2b == 0
    relres = jnp.where(zero_rhs, 0.0, c["normr"] / n2b)
    x = jnp.where(zero_rhs, jnp.zeros_like(c["x"]), c["x"])
    # flag 1 if budget exhausted without convergence
    result = PCGResult(x=x, flag=c["flag"], relres=relres.astype(jnp.float32),
                       iters=c["total"])
    if traced:
        return result, c["trace"]
    return result


# ---------------------------------------------------------------------------
# Batched multi-RHS PCG (ISSUE 6): one Krylov loop over an RHS block.
#
# The block rides a TRAILING axis through every vector (x/r/p/q are
# (P, n_loc, nrhs)) and every bookkeeping scalar becomes an (nrhs,)
# vector.  The loop is LOCKSTEP: one blocked matvec per trip (the
# per-type element matmul batches to (d x d) @ (d x N x nrhs) — the MXU
# amortization the ISSUE targets), and every per-RHS scalar reduction of
# a trip folds into the same psums the single-RHS body runs — the psum
# COUNT is independent of nrhs (classic 5 / fused 3 body psums, proven
# statically by tools/check_collectives.py); only payloads widen.
#
# Per-column semantics mirror solver/pcg.pcg exactly: each column runs
# its own mode-0/mode-1 (deferred true-residual check) sequence, its own
# stagnation/MoreSteps/min-residual bookkeeping and flag taxonomy, and a
# CONVERGED (or broken-down) column FREEZES — every state update is
# gated by a per-column mask, so the remaining columns iterate while
# finished ones hold their accepted iterate.  On CPU a blocked classic
# solve reproduces each column of the equivalent single-RHS solves
# bit-identically (tests/test_pcg_many.py): the blocked gathers/matmuls/
# reductions keep per-column operation order (verified for the general
# element path), and the lockstep merge only reorders WHICH trip a
# column's arithmetic runs on, never the arithmetic itself.
#
# Not supported on the blocked path (by design, documented in
# docs/RUNBOOK.md "Many right-hand sides"): the in-graph trace ring
# (per-solve, not per-column) — telemetry instead carries per-RHS
# `rhs_solve` events from the driver.
# ---------------------------------------------------------------------------


def _colsel(mask, a, b):
    """Per-column select: ``mask`` (R,) over blocked vectors (P, n, R)."""
    return jnp.where(mask[None, None, :], a, b)


def cold_carry_many(x0, r0, normr0, dot_dtype,
                    variant: str = "classic") -> dict:
    """Blocked twin of :func:`cold_carry`: x0/r0 are (P, n_loc, R), the
    bookkeeping rides as (R,) vectors, and the per-RHS ``flag`` and
    ``prec_sel`` leaves (all-1 = running, all-0 = primary
    preconditioner) join the carry so a resumed dispatch keeps
    already-terminated columns frozen and per-column recovery state
    intact.  Same donation contract."""
    dd = dot_dtype
    R = x0.shape[-1]
    zi = jnp.zeros((R,), jnp.int32)
    n0 = jnp.asarray(normr0, dd)
    out = dict(
        x=x0, r=r0, p=jnp.zeros_like(x0),
        rho=jnp.ones((R,), dd),
        stag=zi, moresteps=zi,
        normrmin=n0, xmin=x0, imin=zi,
        since_best=zi, best_at_reset=n0,
        win_start=n0, win_count=zi,
        normr_act=n0, exec=zi,
        flag=jnp.ones((R,), jnp.int32),
        prec_sel=zi)
    if variant in LAGGED_VARIANTS:
        out["q"] = jnp.zeros_like(x0)
        out["alpha"] = jnp.full((R,), np.inf, dd)
        out["fresh"] = jnp.ones((R,), jnp.int32)
        out["drift"] = zi
    if variant == "pipelined":
        # GV recurrence vectors + per-COLUMN priming bits (a ladder-
        # restarted column re-primes independently of its neighbors)
        out["u"] = jnp.zeros_like(x0)
        out["w"] = jnp.zeros_like(x0)
        out["s"] = jnp.zeros_like(x0)
        out["z"] = jnp.zeros_like(x0)
        out["init"] = jnp.ones((R,), jnp.int32)
        out["sc"] = zi
    return out


@scoped("solve/start")
def select_best_many(ops: Ops, data: dict, fext: jnp.ndarray, carry: dict,
                     always_min: bool = False,
                     respect_flags: bool = False):
    """Per-column min-residual fallback for a terminally-failed blocked
    resumable solve (the blocked twin of :func:`select_best`): one
    blocked matvec and two R-wide dot psums for the WHOLE block — once
    per solve, never per iteration.

    ``respect_flags`` makes this the ONE terminal per-column selection
    (the chunked driver's finalize): converged columns (carry flag 0)
    keep their accepted iterate and true residual, zero-rhs columns
    return exact zeros, and only failed columns take the min-residual
    fallback — MATLAB pcg's taxonomy, in one place."""
    eff = data["eff"]
    w = data["weight"] * eff
    n2b = jnp.sqrt(ops.wdot_many(w, fext, fext))
    r_min = fext - eff[..., None] * ops.matvec(data, carry["xmin"])
    normr_min = jnp.sqrt(ops.wdot_many(w, r_min, r_min))
    den = jnp.maximum(n2b, jnp.asarray(np.finfo(np.float32).tiny, n2b.dtype))
    if always_min:
        x, relres = carry["xmin"], normr_min / den
    else:
        # a NaN/Inf-poisoned column compares False everywhere: force the
        # min-residual fallback so a quarantined column still returns an
        # internally-consistent finite (x, relres) pair (xmin is only
        # ever updated by committed finite iterations)
        use_min = ((normr_min < carry["normr_act"])
                   | ~jnp.isfinite(carry["normr_act"]))
        x = _colsel(use_min, carry["xmin"], carry["x"])
        relres = jnp.where(use_min, normr_min, carry["normr_act"]) / den
    if respect_flags:
        ok = carry["flag"] == 0
        x = _colsel(ok, carry["x"], x)
        relres = jnp.where(ok, carry["normr_act"] / den, relres)
        zero = n2b == 0
        x = jnp.where(zero[None, None, :], jnp.zeros_like(x), x)
        relres = jnp.where(zero, 0.0, relres)
    return x, relres


@scoped("solve/start")
def restart_carry_many(ops: Ops, data: dict, fext: jnp.ndarray,
                       carry: dict, restart_mask, fallback_mask,
                       quarantine_mask, variant: str = "classic") -> dict:
    """Per-column recovery surgery on a blocked resumable carry (the
    masked twin of the scalar ladder's min-residual restart,
    resilience/engine.run_many_with_recovery):

    * ``restart_mask`` columns get a COLD Krylov carry at their tracked
      min-residual iterate ``xmin`` — residual recomputed by ONE blocked
      matvec for the whole block, flag back to 1 (running), recurrence/
      bookkeeping/drift state reset;
    * ``fallback_mask`` (a subset of restart) columns additionally flip
      their ``prec_sel`` to the scalar-Jacobi fallback preconditioner
      (the per-column rung-2 escalation);
    * ``quarantine_mask`` columns get the terminal ``QUARANTINE_FLAG``
      and are otherwise frozen (their min-residual fallback happens once,
      in :func:`select_best_many`).

    Every UNMASKED column's leaves pass through bit-identically
    (``jnp.where`` selects, never rescales), which is what keeps healthy
    columns' solutions bit-identical to a fault-free block run — the
    fault-isolation contract of tests/test_pcg_many.py."""
    eff = data["eff"]
    w = data["weight"] * eff
    dd = carry["rho"].dtype
    R = fext.shape[-1]
    m = restart_mask
    xmin = carry["xmin"]
    r_new = fext - eff[..., None] * ops.matvec(data, xmin)
    normr_new = jnp.sqrt(ops.wdot_many(w, r_new, r_new))
    zi = jnp.zeros((R,), jnp.int32)
    out = dict(carry)
    out["x"] = _colsel(m, xmin, carry["x"])
    out["r"] = _colsel(m, r_new, carry["r"])
    out["p"] = _colsel(m, jnp.zeros_like(xmin), carry["p"])
    out["rho"] = jnp.where(m, jnp.ones((R,), dd), carry["rho"])
    for k in ("stag", "moresteps", "imin", "since_best", "win_count",
              "exec"):
        out[k] = jnp.where(m, zi, carry[k]).astype(jnp.int32)
    for k in ("normrmin", "best_at_reset", "win_start", "normr_act"):
        out[k] = jnp.where(m, normr_new, carry[k])
    out["prec_sel"] = jnp.where(fallback_mask, 1,
                                carry["prec_sel"]).astype(jnp.int32)
    out["flag"] = jnp.where(
        quarantine_mask, QUARANTINE_FLAG,
        jnp.where(m, 1, carry["flag"])).astype(jnp.int32)
    if variant in LAGGED_VARIANTS:
        out["q"] = _colsel(m, jnp.zeros_like(xmin), carry["q"])
        out["alpha"] = jnp.where(m, jnp.full((R,), np.inf, dd),
                                 carry["alpha"])
        out["fresh"] = jnp.where(m, 1, carry["fresh"]).astype(jnp.int32)
        out["drift"] = jnp.where(m, zi, carry["drift"]).astype(jnp.int32)
    if variant == "pipelined":
        # restarted columns drop their whole GV recurrence and re-ARM
        # the priming bit: the column's next trip recomputes u/w from
        # the restarted residual through the body's own precond/matvec
        # (unmasked columns' chains pass through bitwise, as ever)
        for k in ("u", "w", "s", "z"):
            out[k] = _colsel(m, jnp.zeros_like(xmin), carry[k])
        out["init"] = jnp.where(m, 1, carry["init"]).astype(jnp.int32)
        out["sc"] = jnp.where(m, zi, carry["sc"]).astype(jnp.int32)
    return out


@scoped("solve/start")
def pcg_many(
    ops: Ops,
    data: dict,
    fext: jnp.ndarray,        # (P, n_loc, R) rhs block on eff dofs
    x0: jnp.ndarray,          # (P, n_loc, R) initial guess block
    inv_diag: jnp.ndarray,    # preconditioner inverse (shared by columns)
    tol,                      # scalar or (R,) per-column tolerance
    max_iter,                 # static int or traced scalar budget
    glob_n_dof_eff: int,
    max_stag_steps: int = 3,
    max_iter_nominal: Optional[int] = None,
    carry_in: Optional[dict] = None,
    return_carry: bool = False,
    plateau_window: int = 0,
    x0_zero: bool = False,
    progress_window: int = 0,
    progress_ratio: float = 0.7,
    progress_min_gain: float = 30.0,
    variant: str = "classic",
    inv_diag_fb: Optional[jnp.ndarray] = None,
):
    """Blocked multi-RHS ``pcg``: solves K.x_j = fext_j for every column
    j of the RHS block in ONE lockstep while-loop with a per-RHS
    convergence mask in the predicate.  Returns a :class:`PCGResult`
    whose ``x`` is (P, n_loc, R) and whose flag/relres/iters are (R,)
    per-column vectors, or ``(result, carry)`` with ``return_carry``
    (the resumable-dispatch contract of :func:`pcg`, per column).

    ``inv_diag_fb`` (optional) is the scalar-Jacobi FALLBACK
    preconditioner inverse for per-column recovery: the carry's
    ``prec_sel`` leaf selects, per column, whether the primary or the
    fallback inverse preconditions that column's residual — the
    recovery ladder flips one broken column to the safe inverse while
    every other column's arithmetic stays bit-identical (both applies
    are collective-free elementwise/small-matmul work, so the body psum
    count is untouched).  Without it the selection is compiled out.

    See the module-level "Batched multi-RHS PCG" note for the exact
    per-column semantics and the collective-count contract."""
    if variant not in VALID_PCG_VARIANTS:
        raise ValueError(f"pcg variant must be one of "
                         f"{VALID_PCG_VARIANTS}, got {variant!r}")
    fused = variant == "fused"
    pipelined = variant == "pipelined"
    lagged = variant in LAGGED_VARIANTS
    drift_limit = drift_limit_for(variant)
    warm = carry_in is not None
    eff = data["eff"]
    w = data["weight"] * eff
    dt = fext.dtype
    dd = ops.dot_dtype
    R = fext.shape[-1]
    eps = jnp.asarray(np.finfo(np.dtype(dt)).eps, dd)

    nominal = max_iter_nominal if max_iter_nominal is not None else max_iter
    maxmsteps = min(glob_n_dof_eff // 50, 5, glob_n_dof_eff - nominal)

    n2b = jnp.sqrt(ops.wdot_many(w, fext, fext))       # (R,)
    tolb = jnp.asarray(tol, dd) * n2b                  # (R,)

    def amul(v):
        # named pcg/matvec: blocked trace events bucket like the scalar
        # loop's (obs/profview.py)
        with jax.named_scope("pcg/matvec"):
            y = ops.matvec(data, v)
            with jax.named_scope("mv/scatter"):
                return eff[..., None] * y

    if warm:
        x0 = carry_in["x"]
        r0 = carry_in["r"]
        normr0 = carry_in["normr_act"].astype(dd)
        frozen0 = carry_in["flag"] != 1
    else:
        frozen0 = jnp.zeros((R,), bool)
        if x0_zero:
            r0 = fext
            normr0 = n2b
        else:
            r0 = fext - amul(x0)
            normr0 = jnp.sqrt(ops.wdot_many(w, r0, r0))

    zero_rhs = n2b == 0
    if lagged and warm:
        # warm recurrence-variant normr0 is the predecessor iterate's
        # norm (pipelined lag) — never flag-0 the unevaluated resumed
        # column off it
        initial_ok = jnp.zeros((R,), bool)
    else:
        initial_ok = normr0 <= tolb

    zi = jnp.zeros((R,), jnp.int32)
    flag0 = carry_in["flag"] if warm else jnp.ones((R,), jnp.int32)
    carry0 = dict(
        x=x0,
        r=r0,
        p=carry_in["p"] if warm else jnp.zeros_like(x0),
        rho=carry_in["rho"] if warm else jnp.ones((R,), dd),
        i=zi,
        flag=jnp.where(zero_rhs | initial_ok,
                       0, flag0).astype(jnp.int32),
        stag=carry_in["stag"] if warm else zi,
        moresteps=carry_in["moresteps"] if warm else zi,
        iter_out=zi,
        normr_act=normr0.astype(dd),
        normrmin=carry_in["normrmin"] if warm else normr0.astype(dd),
        xmin=carry_in["xmin"] if warm else x0,
        imin=carry_in["imin"] if warm else zi,
        since_best=carry_in["since_best"] if warm else zi,
        best_at_reset=(carry_in["best_at_reset"] if warm
                       else normr0.astype(dd)),
        win_start=(carry_in["win_start"] if warm
                   else normr0.astype(dd)),
        win_count=carry_in["win_count"] if warm else zi,
        mode=zi,
        # per-column preconditioner selector (0 = primary, 1 = fallback):
        # recovery state that must resume with the rest of the carry
        prec_sel=(carry_in["prec_sel"] if warm else zi),
    )
    if lagged:
        carry0["q"] = carry_in["q"] if warm else jnp.zeros_like(x0)
        carry0["alpha"] = (carry_in["alpha"] if warm
                           else jnp.full((R,), np.inf, dd))
        carry0["fresh"] = (carry_in["fresh"] if warm
                           else jnp.ones((R,), jnp.int32))
        carry0["drift"] = carry_in["drift"] if warm else zi
        carry0["chk_normr"] = jnp.zeros((R,), dd)
    if pipelined:
        for k in ("u", "w", "s", "z"):
            carry0[k] = carry_in[k] if warm else jnp.zeros_like(x0)
        carry0["init"] = (carry_in["init"] if warm
                          else jnp.ones((R,), jnp.int32))
        carry0["sc"] = carry_in["sc"] if warm else zi
        # internal forced-check marker (see the scalar carry0)
        carry0["chk_forced"] = zi

    def _prec_apply(c, src=None):
        """Per-column preconditioner apply: the primary inverse, with
        ``prec_sel`` columns flipped to the fallback inverse when one is
        wired (collective-free — the psum budget is untouched).
        ``src`` overrides the preconditioned vector (default the carry
        residual; the pipelined body passes its per-column r/w
        select)."""
        src = c["r"] if src is None else src
        with jax.named_scope("pcg/precond"):
            z = ops.apply_prec(inv_diag, src, data=data)
            if inv_diag_fb is not None:
                z = _colsel(c["prec_sel"] > 0,
                            ops.apply_prec(inv_diag_fb, src), z)
        return z

    def cond(c):
        return jnp.any((c["flag"] == 1) & (c["i"] < max_iter))

    def _resolve_many(c, x, r, p, rho, stag, normr_act, candidate, i,
                      extra=None, count_windows=None):
        """Elementwise (per-column) twin of ``pcg``'s ``_resolve``: the
        shared iteration epilogue, with every scalar decision an (R,)
        vector.  ``extra`` overrides output entries AFTER the
        bookkeeping (the fused body commits fresh vectors while the
        epilogue resolves the lagged iterate).  ``count_windows`` (an
        (R,) bool, default always-on) gates the per-column plateau/
        progress-window clocks exactly like the scalar ``_resolve``'s:
        a cadence-forced pipelined column check must not tick them."""
        converged = candidate & (normr_act <= tolb)
        stag = jnp.where(candidate & ~converged
                         & (stag >= max_stag_steps) & (c["moresteps"] == 0),
                         0, stag).astype(jnp.int32)
        moresteps = jnp.where(candidate & ~converged,
                              c["moresteps"] + 1,
                              c["moresteps"]).astype(jnp.int32)
        toosmall = candidate & ~converged & (moresteps >= maxmsteps)

        better = normr_act < c["normrmin"]
        normrmin = jnp.where(better, normr_act, c["normrmin"])
        xmin = _colsel(better, x, c["xmin"])
        imin = jnp.where(better, i, c["imin"])
        improved = normr_act < c["best_at_reset"] * (1 - 1e-3)
        since_best = jnp.where(improved, 0,
                               c["since_best"] + 1).astype(jnp.int32)
        best_at_reset = jnp.where(improved, normr_act, c["best_at_reset"])

        stagnated = (stag >= max_stag_steps) & ~converged & ~toosmall
        plateaued = ((since_best > plateau_window) & ~converged
                     & ~toosmall if plateau_window
                     else jnp.zeros((R,), bool))

        if progress_window:
            win_count = c["win_count"] + 1
            at_window = win_count >= progress_window
            weak_window = normrmin > jnp.asarray(
                progress_ratio, normrmin.dtype) * c["win_start"]
            deep_enough = normrmin * jnp.asarray(
                progress_min_gain, normrmin.dtype) < n2b
            no_progress = (at_window & weak_window & deep_enough
                           & ~converged & ~toosmall)
            win_start = jnp.where(at_window, normrmin, c["win_start"])
            win_count = jnp.where(at_window, 0, win_count).astype(jnp.int32)
        else:
            no_progress = jnp.zeros((R,), bool)
            win_start, win_count = c["win_start"], c["win_count"]

        if count_windows is not None:
            # frozen per-column window clocks (forced checks) — see the
            # scalar _resolve
            tick = count_windows
            since_best = jnp.where(tick, since_best,
                                   c["since_best"]).astype(jnp.int32)
            best_at_reset = jnp.where(tick, best_at_reset,
                                      c["best_at_reset"])
            win_start = jnp.where(tick, win_start, c["win_start"])
            win_count = jnp.where(tick, win_count,
                                  c["win_count"]).astype(jnp.int32)
            plateaued = plateaued & tick
            no_progress = no_progress & tick

        flag = jnp.where(converged, 0,
                jnp.where(toosmall | stagnated | plateaued | no_progress, 3,
                          1)).astype(jnp.int32)
        stop = flag != 1
        out = dict(
            x=x, r=r, p=p, rho=rho,
            i=jnp.where(stop, i, i + 1).astype(jnp.int32),
            flag=flag, stag=stag, moresteps=moresteps,
            iter_out=i,
            normr_act=normr_act, normrmin=normrmin, xmin=xmin, imin=imin,
            since_best=since_best, best_at_reset=best_at_reset,
            win_start=win_start, win_count=win_count,
            mode=jnp.zeros_like(i),
        )
        if extra:
            out.update(extra)
        # recovery/drift leaves the epilogue does not own pass through
        # unchanged (prec_sel, and the fused drift-guard state) — the
        # while carry must stay type-stable across every branch
        for k in c:
            out.setdefault(k, c[k])
        return out

    def _merge_cases(c, cases):
        """Per-column merge of branch outcomes: ``cases`` is a list of
        (mask (R,), state dict) with DISJOINT masks; columns matching no
        mask keep their old state ``c`` (frozen/inactive columns)."""
        out = {}
        for k in c:
            v = c[k]
            for m, d in cases:
                nv = d[k]
                mv = m[None, None, :] if nv.ndim == 3 else m
                v = jnp.where(mv, nv, v)
            out[k] = v
        return out

    def body(c):
        """Classic blocked body: the per-column merge of ``pcg``'s
        mode-0 iterate / mode-1 deferred-check / breakdown branches.
        Psums: rho+inf (1) + interface assembly inside the one blocked
        matvec (1) + p.q (1) + fused 3-norm (1) + the check's
        true-residual norm (1) = 5, independent of nrhs."""
        i = c["i"]
        active = (c["flag"] == 1) & (i < max_iter)
        is_check = (c["mode"] == 1) & active
        it_m = active & ~is_check

        # -- pre (mode 0): z, rho, beta, direction recurrence ----------
        z = _prec_apply(c)
        inf_col = jnp.isinf(z).any(axis=(0, 1)).astype(dd)
        with jax.named_scope("pcg/reduce"):
            red = ops.wdots_many(w, [(z, c["r"])], extra=[inf_col])
        rho_new, flag2 = red[0], red[1] > 0
        bad_rho = (rho_new == 0) | jnp.isinf(rho_new)
        beta = (rho_new / c["rho"]).astype(dt)
        with jax.named_scope("pcg/axpy"):
            if warm:
                bad_beta = (beta == 0) | jnp.isinf(beta)
                p_new = z + beta[None, None, :] * c["p"]
            else:
                bad_beta = (i > 0) & ((beta == 0) | jnp.isinf(beta))
                p_new = jnp.where((i == 0)[None, None, :], z,
                                  z + beta[None, None, :] * c["p"])

        # the ONE blocked stencil application: check columns ride their
        # committed iterate through the same matvec (q_j = A.x_j there)
        operand = _colsel(is_check, c["x"], p_new)
        q = amul(operand)

        # -- iterate path ----------------------------------------------
        with jax.named_scope("pcg/reduce"):
            pq = ops.wdot_many(w, p_new, q)
        bad_pq = (pq <= 0) | jnp.isinf(pq)
        alpha = (rho_new / pq).astype(dt)
        bad_alpha = jnp.isinf(alpha)
        breakdown = bad_rho | bad_beta | bad_pq | bad_alpha
        new_flag = jnp.where(flag2, 2,
                             jnp.where(breakdown, 4, 1)).astype(jnp.int32)

        with jax.named_scope("pcg/axpy"):
            r_upd = c["r"] - alpha[None, None, :] * q
        with jax.named_scope("pcg/reduce"):
            sq = ops.wdots_many(w, [(p_new, p_new), (c["x"], c["x"]),
                                    (r_upd, r_upd)])
        normp, normx = jnp.sqrt(sq[0]), jnp.sqrt(sq[1])
        normr = jnp.sqrt(sq[2])
        stag_upd = jnp.where(
            normp * jnp.abs(alpha).astype(dd) < eps * normx,
            c["stag"] + 1, 0).astype(jnp.int32)
        with jax.named_scope("pcg/axpy"):
            x_upd = c["x"] + alpha[None, None, :] * p_new
        cand_new = ((normr <= tolb) | (stag_upd >= max_stag_steps)
                    | (c["moresteps"] > 0))

        # -- check path: true residual of the committed iterate --------
        r_true = fext - q
        with jax.named_scope("pcg/reduce"):
            normr_chk = jnp.sqrt(ops.wdot_many(w, r_true, r_true))

        chk = _resolve_many(c, x=c["x"], r=r_true, p=c["p"], rho=c["rho"],
                            stag=c["stag"], normr_act=normr_chk,
                            candidate=jnp.ones((R,), bool), i=i)
        brk = dict(c, flag=new_flag, iter_out=i, rho=rho_new)
        pend = dict(c, x=x_upd, r=r_upd, p=p_new, rho=rho_new,
                    stag=stag_upd, iter_out=i,
                    mode=jnp.ones((R,), jnp.int32))
        res = _resolve_many(c, x=x_upd, r=r_upd, p=p_new, rho=rho_new,
                            stag=stag_upd,
                            normr_act=normr.astype(dd),
                            candidate=jnp.zeros((R,), bool), i=i)

        m_brk = it_m & (flag2 | breakdown)
        m_pend = it_m & ~(flag2 | breakdown) & cand_new
        m_res = it_m & ~(flag2 | breakdown) & ~cand_new
        return _merge_cases(c, [(is_check, chk), (m_brk, brk),
                                (m_pend, pend), (m_res, res)])

    def body_fused(c):
        """Fused (Chronopoulos–Gear) blocked body: ONE fused psum
        carries every per-RHS reduction (rho, mu, ||r||, ||p||, ||x||,
        inf flag — a (6, R) payload) + the interface psum + the check's
        true-residual norm = 3 body psums, independent of nrhs.  Same
        pipelined-lag semantics per column as ``pcg``'s fused body."""
        i = c["i"]
        active = (c["flag"] == 1) & (i < max_iter)
        is_check = (c["mode"] == 1) & active
        it_m = active & ~is_check

        z = _prec_apply(c)
        operand = _colsel(is_check, c["x"], z)
        kop = amul(operand)          # A.z (iterate cols) / A.x (check cols)

        inf_col = jnp.isinf(z).any(axis=(0, 1)).astype(dd)
        with jax.named_scope("pcg/reduce"):
            red = ops.wdots_many(w, [(c["r"], z), (z, kop),
                                     (c["r"], c["r"]), (c["p"], c["p"]),
                                     (c["x"], c["x"])], extra=[inf_col])
        rho, mu = red[0], red[1]
        normr = jnp.sqrt(red[2])
        normp, normx = jnp.sqrt(red[3]), jnp.sqrt(red[4])
        flag2 = red[5] > 0

        already = c["fresh"] == 0
        small = normp * jnp.abs(c["alpha"]) < eps * normx
        stag = jnp.where(already, c["stag"],
                         jnp.where(small, c["stag"] + 1,
                                   0)).astype(jnp.int32)
        candidate = (((normr <= tolb) | (stag >= max_stag_steps)
                      | (c["moresteps"] > 0)) & ~already)

        bad_rho = (rho == 0) | jnp.isinf(rho)
        beta = rho / c["rho"]
        bad_beta = (beta == 0) | jnp.isinf(beta)
        pq = mu - beta * rho / c["alpha"]
        bad_pq = (pq <= 0) | jnp.isinf(pq)
        alpha = rho / pq
        bad_alpha = jnp.isinf(alpha)
        breakdown = bad_rho | bad_beta | bad_pq | bad_alpha
        new_flag = jnp.where(flag2, 2,
                             jnp.where(breakdown, 4, 1)).astype(jnp.int32)

        beta_dt = beta.astype(dt)[None, None, :]
        alpha_dt = alpha.astype(dt)[None, None, :]
        with jax.named_scope("pcg/axpy"):
            p2 = z + beta_dt * c["p"]
            q2 = kop + beta_dt * c["q"]
            x2 = c["x"] + alpha_dt * p2
            r2 = c["r"] - alpha_dt * q2

        res = _resolve_many(
            c, x=c["x"], r=c["r"], p=c["p"], rho=rho, stag=stag,
            normr_act=normr.astype(dd),
            candidate=jnp.zeros((R,), bool), i=i,
            extra=dict(x=x2, r=r2, p=p2, q=q2,
                       alpha=alpha.astype(dd),
                       fresh=jnp.ones((R,), jnp.int32)))
        pend = dict(c, stag=stag, iter_out=i,
                    mode=jnp.ones((R,), jnp.int32),
                    chk_normr=jnp.where(candidate, normr.astype(dd),
                                        c["chk_normr"]))
        brk = dict(c, flag=new_flag, iter_out=i, rho=rho)

        r_true = fext - kop
        with jax.named_scope("pcg/reduce"):
            normr_chk = jnp.sqrt(ops.wdot_many(w, r_true, r_true))
        # per-column residual-drift guard (same contract as the scalar
        # fused post_check): a non-converged check whose true residual
        # exceeds FUSED_DRIFT_FACTOR x the recurrence norm counts as
        # drifted; at FUSED_DRIFT_LIMIT the column exits with flag 6
        disagree = ((normr_chk > tolb)
                    & (normr_chk > jnp.asarray(FUSED_DRIFT_FACTOR, dd)
                       * c["chk_normr"]))
        drift = (c["drift"] + disagree).astype(jnp.int32)
        chk = _resolve_many(c, x=c["x"], r=r_true, p=c["p"], rho=c["rho"],
                            stag=c["stag"], normr_act=normr_chk,
                            candidate=jnp.ones((R,), bool), i=i,
                            extra=dict(q=c["q"], alpha=c["alpha"],
                                       fresh=jnp.zeros((R,), jnp.int32),
                                       i=i, drift=drift))
        drift_exit = (chk["flag"] == 1) & (drift >= drift_limit)
        chk["flag"] = jnp.where(drift_exit, DRIFT_FLAG,
                                chk["flag"]).astype(jnp.int32)

        m_brk = it_m & (flag2 | breakdown) & ~candidate
        m_pend = it_m & candidate
        m_res = it_m & ~candidate & ~(flag2 | breakdown)
        return _merge_cases(c, [(is_check, chk), (m_brk, brk),
                                (m_pend, pend), (m_res, res)])

    def body_pipelined(c):
        """Ghysels–Vanroose depth-1 pipelined blocked body: the ONE
        fused psum (a (6, R) payload) consumes ONLY previous-iteration
        carry leaves — gamma = <r,u>, delta = <w,u>, the residual/
        stagnation norms, the inf-prec flag off ``u`` — and the blocked
        precond apply + stencil matvec consume only carry leaves too,
        so the psum and the matvec are data-independent both ways
        (the analysis/ psum-overlap rule's contract; see the scalar
        ``body_pipelined``).  3 body psums (fused + iface + deferred
        check), independent of nrhs.  Per-column trip kinds: mode-1
        deferred check, per-column PRIMING (armed ``init`` bits:
        u0 = M^-1.r0, w0 = A.u0 — a ladder-restarted column re-primes
        alone), and the GV recurrence advance."""
        i = c["i"]
        active = (c["flag"] == 1) & (i < max_iter)
        is_check = (c["mode"] == 1) & active
        is_prime = (c["init"] > 0) & active & ~is_check
        it_m = active & ~is_check & ~is_prime

        # ---- the ONE fused psum: carry-state operands only ------------
        inf_col = jnp.isinf(c["u"]).any(axis=(0, 1)).astype(dd)
        with jax.named_scope("pcg/reduce"):
            red = ops.wdots_many(w, [(c["r"], c["u"]), (c["w"], c["u"]),
                                     (c["r"], c["r"]), (c["p"], c["p"]),
                                     (c["x"], c["x"])], extra=[inf_col])
        gamma, delta = red[0], red[1]
        normr = jnp.sqrt(red[2])
        normp, normx = jnp.sqrt(red[3]), jnp.sqrt(red[4])
        flag2 = red[5] > 0

        # per-column precond source: priming columns precondition their
        # residual, iterating columns their w; check columns' apply is
        # discarded by the operand select below.  All carry leaves — the
        # apply/matvec chain never waits on the psum above.
        m = _prec_apply(c, src=_colsel(c["init"] > 0, c["r"], c["w"]))
        operand = _colsel(is_check, c["x"], m)
        kop = amul(operand)

        already = c["fresh"] == 0
        small = normp * jnp.abs(c["alpha"]) < eps * normx
        stag = jnp.where(already, c["stag"],
                         jnp.where(small, c["stag"] + 1,
                                   0)).astype(jnp.int32)
        natural = ((normr <= tolb) | (stag >= max_stag_steps)
                   | (c["moresteps"] > 0))
        # per-column forced replacement cadence (see the scalar body)
        forced = c["sc"] >= PIPELINED_REPLACE_EVERY
        candidate = (natural | forced) & ~already

        bad_rho = (gamma == 0) | jnp.isinf(gamma)
        beta = gamma / c["rho"]
        bad_beta = (beta == 0) | jnp.isinf(beta)
        pq = delta - beta * gamma / c["alpha"]
        bad_pq = (pq <= 0) | jnp.isinf(pq)
        alpha = gamma / pq
        bad_alpha = jnp.isinf(alpha)
        breakdown = bad_rho | bad_beta | bad_pq | bad_alpha
        new_flag = jnp.where(flag2, 2,
                             jnp.where(breakdown, 4, 1)).astype(jnp.int32)

        beta_dt = beta.astype(dt)[None, None, :]
        alpha_dt = alpha.astype(dt)[None, None, :]
        with jax.named_scope("pcg/axpy"):
            p2 = c["u"] + beta_dt * c["p"]   # p = 0 cold => p2 = u
            s2 = c["w"] + beta_dt * c["s"]   # A.p by recurrence
            q2 = m + beta_dt * c["q"]        # M^-1.s by recurrence
            z2 = kop + beta_dt * c["z"]      # A.q by recurrence
            x2 = c["x"] + alpha_dt * p2
            r2 = c["r"] - alpha_dt * s2
            u2 = c["u"] - alpha_dt * q2      # M^-1.r by recurrence
            w2 = c["w"] - alpha_dt * z2      # A.u by recurrence

        res = _resolve_many(
            c, x=c["x"], r=c["r"], p=c["p"], rho=gamma, stag=stag,
            normr_act=normr.astype(dd),
            candidate=jnp.zeros((R,), bool), i=i,
            extra=dict(x=x2, r=r2, p=p2, u=u2, w=w2, s=s2, q=q2, z=z2,
                       alpha=alpha.astype(dd),
                       fresh=jnp.ones((R,), jnp.int32),
                       sc=(c["sc"] + 1).astype(jnp.int32)))
        pend = dict(c, stag=stag, iter_out=i,
                    mode=jnp.ones((R,), jnp.int32),
                    chk_normr=jnp.where(candidate, normr.astype(dd),
                                        c["chk_normr"]),
                    chk_forced=(forced & ~natural).astype(jnp.int32))
        brk = dict(c, flag=new_flag, iter_out=i, rho=gamma)
        # priming commit: u0/w0 land, the bit clears, nothing advances
        prime = dict(c, u=m, w=kop,
                     init=jnp.zeros((R,), jnp.int32))

        # deferred check (kop = A.x for check columns) with per-column
        # TRUE-RESIDUAL REPLACEMENT (see the scalar post_check): the
        # column's residual is replaced with the honest one and its
        # priming bit re-armed so u/w re-sync next trip; the TIGHTER
        # pipelined drift budget still gates flag 6
        r_true = fext - kop
        with jax.named_scope("pcg/reduce"):
            normr_chk = jnp.sqrt(ops.wdot_many(w, r_true, r_true))
        disagree = ((normr_chk > tolb)
                    & (normr_chk > jnp.asarray(FUSED_DRIFT_FACTOR, dd)
                       * c["chk_normr"]))
        drift = (c["drift"] + disagree).astype(jnp.int32)
        # a cadence-forced column check replaces/re-primes only — no
        # MoreSteps/candidacy bookkeeping, no plateau/progress-window
        # ticks (count_windows; see the scalar post_check)
        chk_nat = c["chk_forced"] == 0
        chk = _resolve_many(c, x=c["x"], r=r_true, p=c["p"], rho=c["rho"],
                            stag=c["stag"], normr_act=normr_chk,
                            candidate=chk_nat, i=i,
                            extra=dict(fresh=jnp.zeros((R,), jnp.int32),
                                       i=i, drift=drift,
                                       init=jnp.ones((R,), jnp.int32),
                                       sc=jnp.zeros((R,), jnp.int32),
                                       chk_forced=jnp.zeros(
                                           (R,), jnp.int32)),
                            count_windows=chk_nat)
        drift_exit = (chk["flag"] == 1) & (drift >= drift_limit)
        chk["flag"] = jnp.where(drift_exit, DRIFT_FLAG,
                                chk["flag"]).astype(jnp.int32)

        m_brk = it_m & (flag2 | breakdown) & ~candidate
        m_pend = it_m & candidate
        m_res = it_m & ~candidate & ~(flag2 | breakdown)
        return _merge_cases(c, [(is_check, chk), (is_prime, prime),
                                (m_brk, brk), (m_pend, pend),
                                (m_res, res)])

    loop_body = (body_pipelined if pipelined
                 else body_fused if fused else body)
    # the loop itself is ``solve/track``: its cond, its body and the
    # carry copies XLA adds around the loop (which carry the while op's
    # label) all count as the loop's own work
    with jax.named_scope("solve/track"):
        c = jax.lax.while_loop(cond, loop_body, carry0)

    skip_mask = zero_rhs | initial_ok | frozen0

    def finalize():
        ok = c["flag"] == 0
        relres_ok = c["normr_act"] / n2b
        # per-column min-residual fallback (MATLAB pcg semantics); ONE
        # blocked matvec for the whole block
        r_min = fext - amul(c["xmin"])
        normr_min = jnp.sqrt(ops.wdot_many(w, r_min, r_min))
        if lagged:
            x_bad, relres_bad = c["xmin"], normr_min / n2b
            iters_bad = c["imin"]
        else:
            # NaN-poisoned columns compare False: force the min-residual
            # fallback so a poisoned column still reports a finite,
            # internally-consistent (x, relres) pair (quarantine
            # semantics — the host has no ladder on the one-shot path)
            use_min = ((normr_min < c["normr_act"])
                       | ~jnp.isfinite(c["normr_act"]))
            x_bad = _colsel(use_min, c["xmin"], c["x"])
            relres_bad = jnp.where(use_min, normr_min,
                                   c["normr_act"]) / n2b
            iters_bad = jnp.where(use_min, c["imin"], c["iter_out"])
        x = _colsel(ok, c["x"], x_bad)
        relres = jnp.where(ok, relres_ok, relres_bad)
        iters = jnp.where(ok, c["iter_out"], iters_bad)
        return x, relres, iters

    if return_carry:
        x, relres, iters = c["x"], c["normr_act"] / n2b, c["iter_out"]
    else:
        x, relres, iters = finalize()

    x = jnp.where(zero_rhs[None, None, :], jnp.zeros_like(x), x)
    relres = jnp.where(zero_rhs, 0.0, relres)
    iters = jnp.where(skip_mask, 0, iters + 1)
    flag = jnp.where(zero_rhs, 0, c["flag"]).astype(jnp.int32)
    if not return_carry:
        # One-shot terminal reporting: a NaN/Inf-poisoned column trips
        # NO MATLAB flag, but finalize() already handed it the finite
        # min-residual fallback — surface the poisoning as the terminal
        # QUARANTINE_FLAG instead of a flag that reads like an honest
        # budget/stagnation exit.  The resumable path must NOT do this:
        # the host-side per-column ladder reads flag 1 + a non-finite
        # carry norm as its nan_carry trigger.
        poisoned = ~jnp.isfinite(c["normr_act"]) & (flag != 0) & ~zero_rhs
        flag = jnp.where(poisoned, QUARANTINE_FLAG, flag).astype(jnp.int32)

    result = PCGResult(x=x, flag=flag, relres=relres.astype(jnp.float32),
                       iters=iters)
    if return_carry:
        keys = ["x", "r", "p", "rho", "stag", "moresteps",
                "normrmin", "xmin", "imin", "since_best",
                "best_at_reset", "win_start", "win_count", "normr_act",
                "prec_sel"]
        if lagged:
            keys += ["q", "alpha", "fresh", "drift"]
        if pipelined:
            keys += ["u", "w", "s", "z", "init", "sc"]
        carry = {k: c[k] for k in keys}
        carry["flag"] = flag
        # executed body-iteration count per column; columns that never
        # ran this dispatch (frozen at entry / converged at entry /
        # zero rhs) report 0
        carry["exec"] = jnp.where(skip_mask, 0,
                                  c["iter_out"] + 1).astype(jnp.int32)
        return result, carry
    return result


def _pow2_scale(v):
    """A power of two within a factor of two of each positive ``v``
    (R,): ``v``'s float32 exponent with its mantissa cleared, by bit
    operations on float32 only (the TPU has no 64-bit bitcast).  Where
    ``v`` is not a normal float32, ``v`` itself."""
    v32 = v.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(v32, jnp.uint32) & jnp.uint32(
        0x7F800000)
    p = jax.lax.bitcast_convert_type(bits, jnp.float32).astype(v.dtype)
    normal = (v32 >= np.finfo(np.float32).tiny) & jnp.isfinite(v32)
    return jnp.where(normal, p, v)


@scoped("solve/start")
def pcg_mixed_many(
    ops32: Ops,
    data32: dict,
    ops64: Ops,
    data64: dict,
    fext: jnp.ndarray,        # (P, n_loc, R) f64 rhs block on eff dofs
    x0: jnp.ndarray,          # (P, n_loc, R) f64 initial guess block
    inv_diag32: jnp.ndarray,  # f32 preconditioner inverse (shared)
    tol: float,
    max_iter: int,
    glob_n_dof_eff: int,
    max_stag_steps: int = 3,
    inner_tol: float = 1e-5,
    max_outer: int = 12,
    plateau_window: int = 0,
    progress_window: int = 0,
    progress_ratio: float = 0.7,
    progress_min_gain: float = 30.0,
    variant: str = "classic",
):
    """Blocked mixed-precision PCG by iterative refinement: the blocked
    twin of :func:`pcg_mixed`.  The f32 inner Krylov cycles run
    :func:`pcg_many` on the per-column normalized residuals (a finished
    column's inner rhs is zeroed, so its inner solve early-exits and
    costs nothing but a masked lane), and the f64 refresh is one blocked
    matvec per cycle.  Per-column flags follow pcg_mixed's taxonomy.

    Returns ``(result, lockstep)``: the per-column :class:`PCGResult`
    and the () int32 count of inner iterations the block ran in
    lockstep — per refinement cycle the most any column ran, summed over
    the cycles.  ``sum(result.iters) / (R * lockstep)`` is the share of
    the block's lanes that did live work."""
    eff64 = data64["eff"]
    w64 = data64["weight"] * eff64
    R = fext.shape[-1]
    dd = ops64.dot_dtype

    def amul64(v):
        return eff64[..., None] * ops64.matvec(data64, v)

    n2b = jnp.sqrt(ops64.wdot_many(w64, fext, fext))   # (R,)
    tolb = tol * n2b

    carry0 = dict(
        x=x0,
        normr=jnp.full((R,), np.inf, dd),
        outer=jnp.zeros((R,), jnp.int32),
        total=jnp.zeros((R,), jnp.int32),
        flag=jnp.where(n2b == 0, 0, -1).astype(jnp.int32),
        fatal2=jnp.zeros((R,), bool),
        lockstep=jnp.zeros((), jnp.int32),
    )

    def cond(c):
        return jnp.any(c["flag"] == -1)

    def body(c):
        with jax.named_scope("solve/residual64"):
            r = fext - amul64(c["x"])
            normr = jnp.sqrt(ops64.wdot_many(w64, r, r))
        live = c["flag"] == -1
        converged = normr <= tolb
        stalled = normr > 0.5 * c["normr"]
        exhausted = (c["outer"] >= max_outer) | (c["total"] >= max_iter)
        run_inner = live & ~(converged | stalled | c["fatal2"] | exhausted)

        # normalized inner rhs per column; columns NOT running this
        # cycle get a zero rhs, which pcg_many's per-column zero-rhs
        # early exit freezes at flag 0 / 0 iterations immediately.  The
        # scale is a power of two near the norm: dividing by it and
        # multiplying the inner solution back are exact, so the update
        # x + xbest * scale rounds once whether or not the backend fuses
        # it into one FMA, which it may do at one block width and not at
        # another — a column rounds the same at every width
        denom = jnp.where(normr > 0, _pow2_scale(normr),
                          jnp.ones_like(normr))
        rhat32 = jnp.where(run_inner[None, None, :],
                           r / denom[None, None, :], 0.0
                           ).astype(jnp.float32)
        # PER-COLUMN inner budget, exactly the scalar path's
        # max_iter - total per solve: a lightly-spent column must not be
        # clamped by the most-spent column's remaining budget (pcg_many
        # takes an (R,) max_iter — its budget test is elementwise)
        remaining = jnp.maximum(max_iter - c["total"], 1)
        tol_cycle = refine_tol(tolb, normr, inner_tol)
        inner, icarry = pcg_many(
            ops32, data32,
            fext=rhat32,
            x0=jnp.zeros_like(rhat32),
            inv_diag=inv_diag32,
            tol=tol_cycle,
            max_iter=remaining,
            glob_n_dof_eff=glob_n_dof_eff,
            max_stag_steps=max_stag_steps,
            max_iter_nominal=max_iter,
            plateau_window=plateau_window,
            return_carry=True,
            x0_zero=True,
            progress_window=progress_window,
            progress_ratio=progress_ratio,
            progress_min_gain=progress_min_gain,
            variant=variant,
        )
        use_min = (inner.flag != 0) & (icarry["normrmin"]
                                       < icarry["normr_act"])
        xbest = _colsel(use_min, icarry["xmin"], inner.x)
        xinc = xbest.astype(fext.dtype) * denom[None, None, :]
        xinc = jnp.where(run_inner[None, None, :], xinc,
                         jnp.zeros_like(xinc))
        exec_n = jnp.where(run_inner, jnp.maximum(icarry["exec"], 1), 0)
        inner_flag = jnp.where(run_inner, inner.flag, 1)

        flag = jnp.where(
            ~live, c["flag"],
            jnp.where(converged, 0,
             jnp.where(stalled, 3,
              jnp.where(c["fatal2"], 2,
               jnp.where(exhausted, 1, -1))))).astype(jnp.int32)
        return dict(x=c["x"] + xinc,
                    normr=jnp.where(live, normr, c["normr"]),
                    outer=c["outer"] + run_inner.astype(jnp.int32),
                    total=c["total"] + exec_n,
                    flag=flag,
                    fatal2=inner_flag == 2,
                    lockstep=c["lockstep"] + jnp.max(exec_n))

    c = jax.lax.while_loop(cond, body, carry0)
    zero_rhs = n2b == 0
    relres = jnp.where(zero_rhs, 0.0, c["normr"] / n2b)
    x = jnp.where(zero_rhs[None, None, :], jnp.zeros_like(c["x"]), c["x"])
    return (PCGResult(x=x, flag=c["flag"],
                      relres=relres.astype(jnp.float32), iters=c["total"]),
            c["lockstep"])
