"""Phase-attribution probes: MEASURE where each ms/iter goes.

The analytic cost model (obs/perf.py) predicts per-phase ms/iter from
the ops tables; this module measures the same four phases — ``matvec``
/ ``precond`` / ``reduction`` / ``axpy`` — by compiling each
sub-program ONCE from the live solver's own ops/data (identical gather/
einsum/scatter code paths, identical sharding) and timing it with
``block_until_ready`` around an inner ``fori_loop`` that amortizes
dispatch overhead.  The whole-iteration anchor comes from the REAL
solve program: a warm capped-iteration solve divided by its committed
iteration count.  measured-vs-model is then the attribution table that
explains gaps like round 5's 24.994 ms/iter vs 13.741 ms/matvec — and
it runs chiplessly on CPU (the probes are ordinary jitted programs), so
``pcg-tpu perf-report`` can sanity the attribution before a hardware
window ever opens.

Probe fidelity notes:

* every probe normalizes its carry by a LOCAL (collective-free) max so
  repeated applications of K (growth ~||K||) or M^-1 (shrink ~1/||K||)
  cannot overflow/underflow across the inner reps — a light extra pass
  whose cost is part of the quoted number;
* the reduction/axpy probes execute the VARIANT's declared per-iteration
  counts (``PCG_SCALAR_PSUMS`` worth of psums carrying the 6 reduced
  scalars, ``PCG_VECTOR_AXPYS`` vector updates), so the per-phase
  numbers line up 1:1 with the cost model's rows;
* timings take the best of ``reps`` outer rounds (min, not mean: host
  jitter only ever adds), and each round times every phase AND the
  whole-iteration anchor back to back — both sides of the attribution
  ratio see the same machine weather.

jax is imported lazily: the module is import-light until a probe is
actually built.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

from pcg_mpi_solver_tpu.obs.perf import PHASES

#: inner fori_loop applications per timed dispatch (amortizes the
#: per-dispatch host overhead the real while-loop also amortizes).
DEFAULT_INNER = 16


class PhaseProbe:
    """Compiled-once phase timing programs for a live (direct-mode)
    Solver.  Construction is cheap; programs compile on first
    :meth:`measure`."""

    def __init__(self, solver, nrhs: int = 1, inner: int = DEFAULT_INNER):
        if getattr(solver, "mixed", False):
            raise ValueError(
                "phase probes need a direct-mode solver (one dtype, one "
                "loop); precision_mode='mixed' interleaves f32 cycles "
                "with f64 refreshes and has no single per-iteration "
                "phase split")
        self.solver = solver
        self.nrhs = max(1, int(nrhs))
        self.inner = max(1, int(inner))
        self._progs: Optional[Dict[str, Any]] = None
        self._prec = None

    # -- program construction ------------------------------------------
    def _build(self):
        import jax
        import jax.numpy as jnp

        from pcg_mpi_solver_tpu.ops.precond import make_prec
        from pcg_mpi_solver_tpu.ops.matvec import (
            PCG_SCALAR_PSUMS, PCG_VECTOR_AXPYS)
        s = self.solver
        ops = s.ops
        mesh = s.mesh
        specs = s._specs
        P = s._part_spec
        prec_spec = s._prec_operand_spec()
        variant = s.config.solver.pcg_variant
        precond = s.config.solver.precond
        M = self.inner
        R = self.nrhs
        n_psums = PCG_SCALAR_PSUMS[variant]     # KeyError = the contract
        n_axpys = PCG_VECTOR_AXPYS[variant]

        def _seed(data):
            """A bounded, fully-populated start vector from the device
            data (no host staging): |F| + eff, locally normalized."""
            x = jnp.abs(data["F"]) + data["eff"] + 1e-3
            x = x / jnp.max(x)
            if R > 1:
                x = jnp.repeat(x[..., None], R, axis=-1)
            return x

        def _norm(v):
            # LOCAL max normalization (no collective): keeps repeated
            # operator applications bounded without touching the
            # phase's collective count
            m = jnp.max(jnp.abs(v))
            return v / jnp.where(m > 0, m, 1.0)

        def _out(v):
            # per-part scalar: a tiny fetch that still forces the loop
            return jnp.sum(jnp.abs(v),
                           axis=tuple(range(1, v.ndim)))

        def matvec_prog(data):
            x = _seed(data)

            def body(_, v):
                return _norm(ops.matvec(data, v))

            return _out(jax.lax.fori_loop(0, M, body, x))

        def precond_prog(data, prec):
            x = _seed(data)

            def body(_, v):
                return _norm(ops.apply_prec(prec, v, data=data))

            return _out(jax.lax.fori_loop(0, M, body, x))

        def reduction_prog(data):
            x = _seed(data)
            w = data["weight"] * data["eff"]
            r, z, p, q = x, x * 0.5, x * 2.0, x * 0.25
            if R > 1:
                one_dot, many_dots = ops.wdot_many, ops.wdots_many
            else:
                one_dot, many_dots = ops.wdot, ops.wdots

            def body(_, v):
                if n_psums >= 3:    # classic: three serialized psums
                    s1 = one_dot(w, v, z)
                    s2 = one_dot(w, p, q)
                    s3 = many_dots(w, [(p, p), (v, v), (z, z)],
                                   extra=(jnp.zeros(
                                       (R,) if R > 1 else (),
                                       ops.dot_dtype),))
                    tot = jnp.sum(s1) + jnp.sum(s2) + jnp.sum(s3)
                else:               # fused/pipelined: ONE fused psum
                    red = many_dots(
                        w, [(v, z), (z, q), (v, v), (p, p), (q, q)],
                        extra=(jnp.zeros((R,) if R > 1 else (),
                                         ops.dot_dtype),))
                    tot = jnp.sum(red)
                # fold the reduced scalar back so the loop is sequential
                # without perturbing the operand magnitudes (cast keeps
                # the carry dtype stable — tot is dot_dtype, v may not be)
                return v + (tot * 1e-300).astype(v.dtype)

            return _out(jax.lax.fori_loop(0, M, body, r))

        def axpy_prog(data):
            x = _seed(data)
            a, b, c = x, x * 0.5, x * 0.25

            def body(_, carry):
                va, vb, vc = carry
                bufs = [va, vb, vc]
                for k in range(n_axpys):
                    dst, src = k % 3, (k + 1) % 3
                    bufs[dst] = bufs[src] + 0.5 * bufs[dst]
                va, vb, vc = bufs
                return _norm(va), _norm(vb), _norm(vc)

            out = jax.lax.fori_loop(0, M, body, (a, b, c))
            return _out(out[0])

        sm = jax.shard_map
        self._prec_builder = jax.jit(sm(
            lambda data: make_prec(ops, data, precond),
            mesh=mesh, in_specs=(specs,), out_specs=prec_spec,
            check_vma=False))
        self._progs = {
            "matvec": jax.jit(sm(matvec_prog, mesh=mesh, in_specs=(specs,),
                                 out_specs=P, check_vma=False)),
            "precond": jax.jit(sm(precond_prog, mesh=mesh,
                                  in_specs=(specs, prec_spec),
                                  out_specs=P, check_vma=False)),
            "reduction": jax.jit(sm(reduction_prog, mesh=mesh,
                                    in_specs=(specs,), out_specs=P,
                                    check_vma=False)),
            "axpy": jax.jit(sm(axpy_prog, mesh=mesh, in_specs=(specs,),
                               out_specs=P, check_vma=False)),
        }

    # -- timing --------------------------------------------------------
    #
    # Noise discipline: the phases and the whole-iteration anchor are
    # timed INTERLEAVED — each round measures every phase once and runs
    # one anchor solve, and the final numbers are per-quantity minima
    # across rounds.  Timing them in separate blocks (all phase reps,
    # then all anchor reps) lets a background-load swing land entirely
    # on one side and move the attribution ratio by tens of percent;
    # interleaved rounds put both sides of the ratio inside the same
    # ~second of machine weather, and min-of-rounds picks the quietest.

    def _time_once(self, fn, args) -> float:
        import jax

        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        return (time.perf_counter() - t0) / self.inner

    def _phase_args(self, ph):
        data = self.solver.data
        return (data, self._prec) if ph == "precond" else (data,)

    def warm(self) -> None:
        """Compile + warm every probe program (and build the prec
        operand) so the timed rounds never pay a trace."""
        if self._progs is None:
            self._build()
        import jax

        self._prec = self._prec_builder(self.solver.data)
        jax.block_until_ready(self._prec)
        for ph in PHASES:
            jax.block_until_ready(
                self._progs[ph](*self._phase_args(ph)))

    def measure_phases_once(self) -> Dict[str, float]:
        """One timed application of every phase program: per-phase
        seconds per ITERATION-EQUIVALENT (one matvec, one precond apply,
        the variant's reduction set, the variant's axpy set), measured
        on the live device data.  Call :meth:`warm` first."""
        return {ph: self._time_once(self._progs[ph], self._phase_args(ph))
                for ph in PHASES}

    def measure_whole_once(self) -> Dict[str, float]:
        """One whole-iteration anchor from the REAL solve program: a
        warm capped solve (the solver's configured max_iter bounds it),
        wall divided by committed iterations.  The solver's state is
        reset around the measurement."""
        s = self.solver
        if self.nrhs > 1:
            import numpy as np

            F = np.repeat(
                np.asarray(s._model.F)[:, None], self.nrhs, axis=1)
            res = s.solve_many(F)
            iters = int(max(1, int(res.iters.max(initial=1))))
            wall = float(res.solve_wall_s)
        else:
            r = s.step(1.0)
            s.reset_state()
            iters = max(1, int(r.iters))
            wall = float(r.wall_s)
        return {"wall_s": wall, "iters": iters,
                "s_per_iter": wall / iters}

    def measure(self, reps: int = 3,
                whole: bool = False) -> Dict[str, Any]:
        """``reps`` interleaved rounds; returns the per-phase minima,
        with ``whole=True`` the best anchor under ``"whole"`` plus the
        MEDIAN of the per-round sum/whole ratios under
        ``"attribution"``.  The ratio is quoted round-wise because both
        of its sides then sat in the same second of machine weather — a
        load swing inflates them together and cancels, where a ratio of
        independently-taken minima needs BOTH sides to have caught a
        quiet window."""
        self.warm()
        if whole:
            self.measure_whole_once()           # warm the solve program
        best: Dict[str, float] = {}
        best_whole = None
        ratios = []
        for _ in range(max(1, reps)):
            round_a = self.measure_phases_once()
            for ph, v in round_a.items():
                best[ph] = min(best.get(ph, float("inf")), v)
            if whole:
                w = self.measure_whole_once()
                if best_whole is None or \
                        w["s_per_iter"] < best_whole["s_per_iter"]:
                    best_whole = w
                # bracket the anchor: a second phase pass AFTER it, the
                # round ratio from the mean of the two — a load ramp
                # across the round inflates the anchor like the average
                # of its brackets and cancels to first order
                round_b = self.measure_phases_once()
                for ph, v in round_b.items():
                    best[ph] = min(best[ph], v)
                if w["s_per_iter"] > 0:
                    ratios.append(
                        0.5 * (sum(round_a.values())
                               + sum(round_b.values()))
                        / w["s_per_iter"])
        out: Dict[str, Any] = dict(best)
        if whole:
            out["whole"] = best_whole
            ratios.sort()
            out["attribution"] = (
                ratios[len(ratios) // 2] if len(ratios) % 2 else
                0.5 * (ratios[len(ratios) // 2 - 1]
                       + ratios[len(ratios) // 2])) if ratios else None
        return out


def run_phase_probe(solver, recorder=None, reps: int = 3,
                    nrhs: int = 1, inner: int = DEFAULT_INNER,
                    whole: bool = True) -> Dict[str, Any]:
    """Measure the phases (and optionally the whole-iteration anchor) on
    a live solver, emit the ``phase_probe`` telemetry event, and return
    the payload: per-phase ms, their sum, the whole-iteration ms and the
    sum/whole attribution ratio."""
    probe = PhaseProbe(solver, nrhs=nrhs, inner=inner)
    measured = probe.measure(reps=reps, whole=whole)
    w = measured.pop("whole", None)
    attribution = measured.pop("attribution", None)
    phases_ms = {ph: round(v * 1e3, 6) for ph, v in measured.items()}
    total_ms = round(sum(phases_ms.values()), 6)
    payload: Dict[str, Any] = {
        "pcg_variant": solver.config.solver.pcg_variant,
        "precond": solver.config.solver.precond,
        "nrhs": int(nrhs),
        "backend": solver.backend,
        "inner": int(inner),
        "phases": phases_ms,
        "sum_ms_per_iter": total_ms,
        "whole_ms_per_iter": None,
        "attribution": None,
    }
    if w is not None:
        payload["whole_ms_per_iter"] = round(w["s_per_iter"] * 1e3, 6)
        payload["whole_iters"] = w["iters"]
        # round-wise median, NOT min-sum/min-whole: each round's ratio
        # compares numbers taken in the same second of machine weather
        if attribution is not None:
            payload["attribution"] = round(attribution, 4)
        elif payload["whole_ms_per_iter"]:
            payload["attribution"] = round(
                total_ms / payload["whole_ms_per_iter"], 4)
    rec = recorder if recorder is not None else getattr(
        solver, "recorder", None)
    if rec is not None:
        rec.event("phase_probe", **payload)
        for ph, v in phases_ms.items():
            rec.gauge(f"perf.measured.{ph}_ms", v)
        if payload["whole_ms_per_iter"] is not None:
            rec.gauge("perf.measured.whole_ms", payload["whole_ms_per_iter"])
    return payload
