"""Implicit elasto-dynamics: Newmark-beta time integration with a PCG
solve per step.

The reference's dynamics era was explicit-only (vestigial ``DiagM``/``Vd``/
``Cm`` arrays, partition_mesh.py:324-330; no implicit integrator exists
anywhere in it).  This module adds the implicit path (BASELINE.json
config 5: "elasto-dynamic (implicit Newmark), repeated PCG solves per
timestep"), TPU-first: each step is ONE jitted shard_map program — the
effective-force build, the full PCG ``lax.while_loop`` on the shifted
operator, and the kinematic updates never leave the device.

Discretization (a-form, lumped mass M, mass-proportional damping C=c_m M):

    A u_{n+1} = F(t_{n+1}) + M (a0 u_n + a2 v_n + a3 w_n)
                           + C (a1 u_n + a4 v_n + a5 w_n)
    w_{n+1}   = a0 (u_{n+1} - u_n) - a2 v_n - a3 w_n
    v_{n+1}   = v_n + dt ((1-gamma) w_n + gamma w_{n+1})

with A = K + a0 M + a1 C,  a0 = 1/(beta dt^2),  a1 = gamma/(beta dt),
a2 = 1/(beta dt), a3 = 1/(2 beta) - 1, a4 = gamma/beta - 1,
a5 = dt (gamma/(2 beta) - 1); (w = acceleration).  Default
beta=1/4, gamma=1/2 (average acceleration: unconditionally stable, no
algorithmic damping) — dt is a resolution choice, not a CFL bound, unlike
the explicit solver (solver/dynamics.py).

Because M is lumped (diagonal) and assembled, the shifted operator is the
stock matrix-free K matvec plus an elementwise axpy; its Jacobi diagonal
and 3x3 node blocks shift the same way, so both preconditioners and the
mixed-precision refinement path work unchanged.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from pcg_mpi_solver_tpu.config import RunConfig
from pcg_mpi_solver_tpu.models.model_data import ModelData
from pcg_mpi_solver_tpu.obs.metrics import MetricsRecorder
from pcg_mpi_solver_tpu.obs.trace import (
    ConvergenceTrace, clamp_trace_len, empty_trace, trace_init,
    unpack_trace)
from pcg_mpi_solver_tpu.ops.matvec import Ops, with_f32_shadow
from pcg_mpi_solver_tpu.parallel.mesh import PARTS_AXIS, make_mesh
from pcg_mpi_solver_tpu.resilience.faultinject import FaultPlan
from pcg_mpi_solver_tpu.solver.driver import StepResult, _data_specs
from pcg_mpi_solver_tpu.solver.pcg import pcg, pcg_mixed


@dataclasses.dataclass(frozen=True)
class MassShiftedOps:
    """A + c*M wrapper over any backend's Ops: matvec/diag/node blocks gain
    the (assembled, diagonal) mass term; everything else delegates."""

    base: Ops
    c: float

    def matvec(self, data, x):
        return self.base.matvec(data, x) + self.c * data["diag_M"] * x

    def matvec_local(self, data, x):
        # diag_M holds ASSEMBLED values on every copy of a shared dof, so
        # the shift must ride on the assembled product only (matvec above);
        # a local partial sum plus the full mass term would double-count
        # after assembly.
        raise NotImplementedError("MassShiftedOps only exposes the "
                                  "assembled matvec")

    def diag_local(self, data):
        # same double-count trap as matvec_local: partial K sums must not
        # carry the assembled mass term
        raise NotImplementedError("MassShiftedOps only exposes the "
                                  "assembled diag")

    def _node_block_local(self, data):
        raise NotImplementedError("MassShiftedOps only exposes the "
                                  "assembled node_block_diag")

    def diag(self, data):
        return self.base.diag(data) + self.c * data["diag_M"]

    def node_block_diag(self, data):
        B = self.base.node_block_diag(data)
        m3 = self.base._as_node3(self.c * data["diag_M"])
        return B + m3[..., :, None] * jnp.eye(3, dtype=B.dtype)

    def block_precond(self, data):
        from pcg_mpi_solver_tpu.ops.precond import invert_node_blocks

        return invert_node_blocks(self.node_block_diag(data),
                                  self.base._as_node3(data["eff"]))

    def apply_prec(self, m, r, data=None):
        # the mg V-cycle must run on THIS (shifted) operator — the
        # __getattr__ delegation below would bind mg_apply's ops to the
        # unshifted base, whose defect matvecs would precondition K
        # instead of A = K + c*M
        if isinstance(m, dict):
            from pcg_mpi_solver_tpu.ops.mg import mg_apply

            return mg_apply(self, data, m, r)
        return self.base.apply_prec(m, r)

    def __getattr__(self, name):
        if name in ("base", "c") or name.startswith("__"):
            raise AttributeError(name)
        return getattr(self.base, name)


class NewmarkSolver:
    """Implicit Newmark-beta on the SPMD-partitioned model.

    Shares the quasi-static driver's backend selection (general node-ELL or
    hybrid level-grid; the structured slab path has no mass data), its
    precision/preconditioner config (``config.solver.precision_mode``,
    ``config.solver.precond``), and its dispatch-chunked solve machinery
    (``config.solver.iters_per_dispatch``, auto-engaged above ~4M dofs —
    solver/chunked.py)."""

    def __init__(
        self,
        model: ModelData,
        config: Optional[RunConfig] = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        n_parts: Optional[int] = None,
        dt: float = 1.0,
        beta: float = 0.25,
        gamma: float = 0.5,
        damping: float = 0.0,          # c_m: C = c_m * M
        backend: str = "auto",         # "auto" | "hybrid" | "general"
        recorder: Optional[MetricsRecorder] = None,
    ):
        self.config = config or RunConfig()
        scfg = self.config.solver
        # Telemetry: same default wiring as the quasi-static driver
        # (stderr sink iff PCG_TPU_VERBOSE=1, JSONL sink iff
        # config.telemetry_path is set).
        self.recorder = recorder if recorder is not None else (
            MetricsRecorder.default(
                jsonl_path=self.config.telemetry_path or None))
        self._rec = self.recorder
        # Flight recorder: same crash-durable dispatch brackets as the
        # quasi-static and explicit-dynamics drivers (obs/flight.py).
        from pcg_mpi_solver_tpu.obs.flight import attach_flight

        attach_flight(self._rec, self.config.flight_path, "newmark",
                      pcg_variant=scfg.pcg_variant, precond=scfg.precond)
        from pcg_mpi_solver_tpu.ops.precond import VALID_PRECONDS
        from pcg_mpi_solver_tpu.solver.pcg import VALID_PCG_VARIANTS

        if scfg.precond not in VALID_PRECONDS:
            raise ValueError(f"SolverConfig.precond must be one of "
                             f"{VALID_PRECONDS}, got {scfg.precond!r}")
        if scfg.pcg_variant not in VALID_PCG_VARIANTS:
            raise ValueError(
                f"SolverConfig.pcg_variant must be one of "
                f"{VALID_PCG_VARIANTS}, got {scfg.pcg_variant!r}")
        self._rec.gauge("pcg_variant", scfg.pcg_variant)
        self._rec.gauge("precond", scfg.precond)
        # Preflight gate (validate/): reject a pathological model/config
        # before the partition build below is paid.
        from pcg_mpi_solver_tpu.validate import run_preflight

        run_preflight(model, self.config, recorder=self._rec,
                      context={"kind": "newmark"})
        self.mesh = mesh if mesh is not None else make_mesh()
        n_dev = self.mesh.devices.size
        n_parts = n_parts or max(self.config.n_parts, n_dev)
        if n_parts % n_dev != 0:
            raise ValueError(f"n_parts={n_parts} must be a multiple of "
                             f"device count {n_dev}")
        if beta <= 0:
            raise ValueError("NewmarkSolver requires beta > 0 (beta == 0 is "
                             "the explicit path: solver/dynamics.py)")
        if dt <= 0:
            raise ValueError(f"NewmarkSolver requires dt > 0, got {dt}")
        if gamma <= 0:
            raise ValueError(f"NewmarkSolver requires gamma > 0, got {gamma}")
        if gamma < 0.5:
            import warnings

            # gamma < 1/2 gives NEGATIVE algorithmic damping: each step
            # returns flag=0 while the integration grows without bound
            warnings.warn(
                f"Newmark gamma={gamma} < 0.5 is numerically unstable "
                "(negative algorithmic damping); unconditional stability "
                "requires gamma >= 1/2 with beta >= gamma/2", stacklevel=2)
        elif 2.0 * beta < gamma:
            import warnings

            warnings.warn(
                f"Newmark beta={beta} < gamma/2={gamma/2}: only "
                "conditionally stable — the integration diverges for dt "
                "above the stability bound while each step reports flag=0",
                stacklevel=2)
        self.dt, self.beta, self.gamma = float(dt), float(beta), float(gamma)
        self.damping = float(damping)

        self.mixed = scfg.precision_mode == "mixed"
        dtype = jnp.dtype(jnp.float64) if self.mixed else jnp.dtype(scfg.dtype)
        dot_dtype = jnp.dtype(scfg.dot_dtype)
        if self.mixed or jnp.float64 in (dtype, dot_dtype):
            if not jax.config.jax_enable_x64:
                # honor requested f64 math (same rule as the quasi-static
                # driver) — f32 storage still gets f64-accumulated dots
                jax.config.update("jax_enable_x64", True)
        self.dtype = dtype

        from pcg_mpi_solver_tpu.solver.backends import select_time_backend

        self.backend, self.pm, mk_ops, mk_data = select_time_backend(
            model, n_parts,
            partition_method=self.config.partition_method,
            backend=backend)
        self._mg_meta = None
        self._mg_setup = None
        if scfg.precond == "mg":
            if self.backend != "general":
                raise ValueError(
                    "precond='mg' on the Newmark path is supported on "
                    "the general backend only (the hybrid level-grid "
                    "stencil costs minutes of compile per "
                    "instantiation); use backend='general' or "
                    "precond='jacobi'|'block3'")
            # MG hierarchy (ops/mg.py): the level lattice preconditions
            # the K part; the mass shift rides the fine level through
            # this solver's shifted matvec/diag (MassShiftedOps.
            # apply_prec) — coarse levels on K alone keep M^-1 SPD
            from pcg_mpi_solver_tpu.ops import mg as mgmod

            t_mg0 = time.perf_counter()
            with self._rec.span("mg_setup"):
                mg_setup = mgmod.build_mg_host(
                    model, self.pm, n_levels=int(scfg.mg_levels),
                    degree=int(scfg.mg_smooth_degree),
                    max_replicated_dofs=int(
                        scfg.mg_max_replicated_dofs))
            self._mg_meta = mg_setup.meta
            self._mg_setup = (mg_setup, time.perf_counter() - t_mg0)
        data = mk_data(dtype)

        # Newmark coefficients (a-form)
        dt_, b, g = self.dt, self.beta, self.gamma
        self.a0 = 1.0 / (b * dt_ * dt_)
        self.a1 = g / (b * dt_)
        self.a2 = 1.0 / (b * dt_)
        self.a3 = 1.0 / (2.0 * b) - 1.0
        self.a4 = g / b - 1.0
        self.a5 = dt_ * (g / (2.0 * b) - 1.0)
        cshift = self.a0 + self.a1 * self.damping

        base_ops = mk_ops(dot_dtype)
        if scfg.precond == "mg":
            from pcg_mpi_solver_tpu.ops import mg as mgmod

            base_ops = dataclasses.replace(
                base_ops, mg_degree=int(scfg.mg_smooth_degree),
                mg_coarse_dofs=mgmod.coarse_dofs(self._mg_meta))
        self.ops = MassShiftedOps(base_ops, cshift)

        # Assembled lumped-mass diagonal, per-part (reference DiagM,
        # partition_mesh.py:324-330), gathered exactly — bitwise equal to
        # the model's M (zero-mass dofs stay 0: A = K there, still SPD).
        gid = self.pm.dof_gid
        data["diag_M"] = np.asarray(
            np.where(gid >= 0, model.diag_M[np.maximum(gid, 0)], 0.0), dtype)
        data["Vd"] = np.asarray(
            np.where(gid >= 0, model.Vd[np.maximum(gid, 0)], 0.0), dtype)

        if scfg.precond == "mg":
            from pcg_mpi_solver_tpu.ops import mg as mgmod

            # float leaves at the storage dtype (same rule as driver.py)
            data["mg"] = mgmod.cast_tree(self._mg_setup[0].tree, dtype)

        if self.mixed:
            data = with_f32_shadow(data)
            ops32_base = mk_ops(jnp.float32)
            if scfg.precond == "mg":
                from pcg_mpi_solver_tpu.ops import mg as mgmod

                ops32_base = dataclasses.replace(
                    ops32_base, mg_degree=int(scfg.mg_smooth_degree),
                    mg_coarse_dofs=mgmod.coarse_dofs(self._mg_meta))
            self.ops32 = MassShiftedOps(ops32_base, cshift)
        self._specs = _data_specs(data)

        from pcg_mpi_solver_tpu.parallel.distributed import put_sharded, put_tree

        self.data = put_tree(data, self.mesh, self._specs)
        self._part_spec = jax.sharding.PartitionSpec(PARTS_AXIS)
        self._rep_spec = jax.sharding.PartitionSpec()
        if scfg.precond == "mg":
            self._finish_mg_setup()
        P, n_loc = self.pm.n_parts, self.pm.n_loc
        zeros = lambda: put_sharded(np.zeros((P, n_loc), dtype),
                                    self.mesh, self._part_spec)
        self.u, self.v, self.w = zeros(), zeros(), zeros()

        glob_n_eff = self.pm.glob_n_dof_eff
        a0, a2_, a3_ = self.a0, self.a2, self.a3
        a1_, a4_, a5_ = self.a1, self.a4, self.a5
        cm = self.damping

        def _effective_force(data64, u, v, w, delta_next):
            """History term + Dirichlet lifting at t_{n+1} (the quasi-static
            driver's updateBC shape, pcg_solver.py:226-238, with A in place
            of K) — the ONE copy of the Newmark rhs physics, shared by the
            one-shot and chunked paths."""
            eff = data64["eff"]
            fix = 1.0 - eff
            M = data64["diag_M"]
            hist = M * ((a0 * u + a2_ * v + a3_ * w)
                        + cm * (a1_ * u + a4_ * v + a5_ * w))
            rhs = data64["F"] * delta_next + hist
            udi = fix * data64["Ud"] * delta_next
            fext = eff * (rhs - self.ops.matvec(data64, udi))
            return udi, fext

        def _kinematics(data64, x, udi, u, v, w, delta_next):
            """u/v/w updates from the solved increment; on fixed dofs u2
            carries the prescribed motion, so w2 is its finite-difference-
            consistent acceleration.  Shared by both paths."""
            eff = data64["eff"]
            fix = 1.0 - eff
            u2 = x + udi
            w2 = a0 * (u2 - u) - a2_ * v - a3_ * w
            v2 = v + dt_ * ((1.0 - g) * w + g * w2)
            v2 = eff * v2 + fix * data64["Vd"] * delta_next
            return u2, v2, w2

        def _step(data, prec, u, v, w, delta_next):
            data64 = data["f64"] if self.mixed else data
            eff = data64["eff"]
            udi, fext = _effective_force(data64, u, v, w, delta_next)
            x0 = eff * u
            if self.mixed:
                res = pcg_mixed(
                    self.ops32, data["f32"], self.ops, data64, fext, x0,
                    prec,
                    tol=scfg.tol, max_iter=scfg.max_iter,
                    glob_n_dof_eff=glob_n_eff,
                    max_stag_steps=scfg.max_stag_steps,
                    inner_tol=scfg.inner_tol,
                    variant=scfg.pcg_variant)
            else:
                res = pcg(
                    self.ops, data64, fext, x0, prec,
                    tol=scfg.tol, max_iter=scfg.max_iter,
                    glob_n_dof_eff=glob_n_eff,
                    max_stag_steps=scfg.max_stag_steps,
                    variant=scfg.pcg_variant)
            u2, v2, w2 = _kinematics(data64, res.x, udi, u, v, w, delta_next)
            return u2, v2, w2, res.flag, res.relres, res.iters

        P_, R_ = self._part_spec, self._rep_spec
        self._step_fn = jax.jit(jax.shard_map(
            _step, mesh=self.mesh,
            in_specs=(self._specs, self._prec_operand_spec(),
                      P_, P_, P_, R_),
            out_specs=(P_, P_, P_, R_, R_, R_), check_vma=False))

        # In-graph convergence trace (obs/trace.py), chunked path only:
        # the one-shot step program keeps its pre-telemetry shape.
        self.trace_len = (clamp_trace_len(scfg.trace_resid, scfg.max_iter)
                          if scfg.trace_resid > 0 else 0)
        self._trace_dtype = (jnp.float32 if self.mixed
                             else jnp.dtype(scfg.dot_dtype))
        self.last_trace: Optional[ConvergenceTrace] = None

        # ---- dispatch-chunked step path (large problems) ------------------
        # Same machinery as the quasi-static driver (solver/chunked.py):
        # the Newmark start step swaps Dirichlet lifting for the history
        # term; the engine's cycles are untouched.
        from pcg_mpi_solver_tpu.solver.chunked import (
            ChunkedEngine, auto_dispatch_cap)

        self._dispatch_cap = auto_dispatch_cap(
            scfg, self.pm.glob_n_dof,
            self.pm.n_loc * (self.pm.n_parts // n_dev))
        # donation-safe here too: the carry is built fresh by
        # _start_ch_fn each step and never read after run()
        self._donate = bool(getattr(scfg, "donate_carry", False))
        if self._dispatch_cap > 0:
            from pcg_mpi_solver_tpu.solver.pcg import (
                carry_part_specs, cold_carry)

            variant = scfg.pcg_variant
            trace_direct = self.trace_len > 0 and not self.mixed
            carry_specs = carry_part_specs(P_, R_, trace=trace_direct,
                                           variant=variant)
            trace_len, trace_dtype = self.trace_len, self._trace_dtype

            def _start_ch(data, u, v, w, delta_next):
                data64 = data["f64"] if self.mixed else data
                eff = data64["eff"]
                wts = data64["weight"] * eff
                udi, fext = _effective_force(data64, u, v, w, delta_next)
                x0 = eff * u
                r0 = fext - eff * self.ops.matvec(data64, x0)
                n2b = jnp.sqrt(self.ops.wdot(wts, fext, fext))
                normr0 = jnp.sqrt(self.ops.wdot(wts, r0, r0))
                carry0 = cold_carry(
                    x0, r0, normr0, self.ops.dot_dtype,
                    trace=(trace_init(trace_len, trace_dtype)
                           if trace_direct else None),
                    variant=variant)
                return udi, fext, carry0, normr0, n2b

            self._start_ch_fn = jax.jit(jax.shard_map(
                _start_ch, mesh=self.mesh,
                in_specs=(self._specs, P_, P_, P_, R_),
                out_specs=(P_, P_, carry_specs, R_, R_), check_vma=False))

            def _finish_ch(data, x, udi, u, v, w, delta_next):
                data64 = data["f64"] if self.mixed else data
                return _kinematics(data64, x, udi, u, v, w, delta_next)

            self._finish_ch_fn = jax.jit(jax.shard_map(
                _finish_ch, mesh=self.mesh,
                in_specs=(self._specs, P_, P_, P_, P_, P_, R_),
                out_specs=(P_, P_, P_), check_vma=False))

            self._engine = ChunkedEngine(
                mesh=self.mesh, data_specs=self._specs, part_spec=P_,
                rep_spec=R_, ops=self.ops, scfg=scfg,
                glob_n_dof_eff=glob_n_eff, cap=self._dispatch_cap,
                mixed=self.mixed,
                ops32=self.ops32 if self.mixed else None,
                trace_len=self.trace_len, recorder=self._rec,
                donate=self._donate,
                prec_spec=self._prec_operand_spec())

        # A = K + c*M is CONSTANT over the run (unlike the quasi-static
        # driver, whose per-step Jacobi rebuild is reference parity):
        # build + invert the preconditioner ONCE, device-resident.
        from pcg_mpi_solver_tpu.ops.precond import make_prec

        def _prec(data):
            if self.mixed:
                return make_prec(self.ops32, data["f32"], scfg.precond)
            return make_prec(self.ops, data, scfg.precond)

        self._prec = jax.jit(jax.shard_map(
            _prec, mesh=self.mesh,
            in_specs=(self._specs,), out_specs=self._prec_operand_spec(),
            check_vma=False))(self.data)

        def _init_accel(data, u, v, delta0):
            """w = M^-1 (F(t)*delta0 - K u - C v) at the CURRENT state:
            lumped M makes the solve elementwise (one K matvec)."""
            data64 = data["f64"] if self.mixed else data
            M = data64["diag_M"]
            inv_m = jnp.where(M > 0, 1.0 / jnp.where(M > 0, M, 1.0), 0.0)
            fint = base_ops.matvec(data64, u)      # K u (unshifted)
            return data64["eff"] * (
                inv_m * (data64["F"] * delta0 - fint) - cm * v)

        self._init_fn = jax.jit(jax.shard_map(
            _init_accel, mesh=self.mesh,
            in_specs=(self._specs, P_, P_, R_), out_specs=P_,
            check_vma=False))

        # ---- resilience (resilience/): per-step recovery ladder on the
        # chunked path + timestep-granular snapshots/rollback in run().
        # `fault_plan` is settable (tests inject programmatically;
        # PCG_TPU_FAULTS drives chaos runs — incl. the step domain
        # `kill@s:N`).
        self.fault_plan = FaultPlan.from_env(recorder=self._rec)
        self._amulA_fn = None           # lazy: shifted-operator amul
        self._restart_post_fn = None    # lazy: ladder restart program
        self._fallback_prec_fn = None   # lazy: scalar-Jacobi fallback
        self._esc_engine = None         # lazy: f64 escalation engine
        self._esc_prec_fn = None
        self._finite_fn = jax.jit(lambda a: jnp.isfinite(a).all())
        self._model = model             # checkpoint fingerprint content

        self.flags: List[int] = []
        self.relres: List[float] = []
        self.iters: List[int] = []

    # ------------------------------------------------------------------
    def _prec_operand_spec(self):
        """shard_map spec (pytree) of the preconditioner operand: the
        part spec for array inverses, the mg dict spec for precond='mg'
        (mirrors driver.Solver._prec_operand_spec)."""
        if self.config.solver.precond == "mg":
            return {"mg_diag": self._part_spec, "fb": self._rep_spec}
        return self._part_spec

    def _finish_mg_setup(self):
        """Post-upload MG setup (the Newmark twin of
        driver.Solver._finish_mg_setup, without the partition-cache
        shortcut — Newmark has no cache_dir wiring): estimate the fine
        Chebyshev bound ON THE SHIFTED OPERATOR, then install the
        per-level lambda vector + telemetry/warning through the shared
        ``mg.install_lam_and_report``."""
        from pcg_mpi_solver_tpu.ops import mg as mgmod

        setup, t_build = self._mg_setup
        data64 = self.data["f64"] if self.mixed else self.data
        specs64 = self._specs["f64"] if self.mixed else self._specs
        t0 = time.perf_counter()
        with self._rec.span("mg_lam"):
            lam_fine = mgmod.estimate_fine_lam(
                self.ops, data64, self.mesh, specs64, self._part_spec)
        trees = ([self.data["f64"], self.data["f32"]] if self.mixed
                 else [self.data])
        mgmod.install_lam_and_report(
            setup, lam_fine, trees=trees, mesh=self.mesh,
            rep_spec=self._rep_spec, recorder=self._rec,
            wall_s=t_build + time.perf_counter() - t0, cached=False)

    # ------------------------------------------------------------------
    # Resilience (resilience/): recovery programs + step harness
    # ------------------------------------------------------------------
    def _build_restart(self):
        """Lazily-built ladder restart programs on the SHIFTED operator:
        one amul program ``(data, v) -> eff * A.v`` shared by every
        restart, plus ``(data, fext, x, kx) -> (cold carry at x, ||r||)``
        — compiled only if a recovery ever fires (mirrors
        driver._restart_post)."""
        if self._restart_post_fn is not None:
            return
        from pcg_mpi_solver_tpu.solver.pcg import carry_part_specs, cold_carry

        mixed = self.mixed
        variant = self.config.solver.pcg_variant
        trace_direct = self.trace_len > 0 and not mixed
        P, R = self._part_spec, self._rep_spec
        carry_specs = carry_part_specs(P, R, trace=trace_direct,
                                       variant=variant)
        trace_len, trace_dtype = self.trace_len, self._trace_dtype

        def _amulA(data, v):
            d = data["f64"] if mixed else data
            return d["eff"] * self.ops.matvec(d, v)

        self._amulA_fn = jax.jit(jax.shard_map(
            _amulA, mesh=self.mesh, in_specs=(self._specs, P),
            out_specs=P, check_vma=False))

        def _restart(data, fext, x, kx):
            d = data["f64"] if mixed else data
            w = d["weight"] * d["eff"]
            r = fext - kx
            normr = jnp.sqrt(self.ops.wdot(w, r, r))
            tr = (trace_init(trace_len, trace_dtype)
                  if trace_direct else None)
            return cold_carry(x, r, normr, self.ops.dot_dtype,
                              trace=tr, variant=variant), normr

        self._restart_post_fn = jax.jit(jax.shard_map(
            _restart, mesh=self.mesh, in_specs=(self._specs, P, P, P),
            out_specs=(carry_specs, R), check_vma=False))

    def _fallback_prec(self):
        """Scalar-Jacobi fallback inverse on the shifted operator
        (ladder rung 2; the mass shift rides ops.diag, so the fallback
        is still a preconditioner of A, not of K)."""
        from pcg_mpi_solver_tpu.ops.precond import make_prec

        if self._fallback_prec_fn is None:
            mixed = self.mixed
            mg = self.config.solver.precond == "mg"

            def _fb(data):
                if mixed:
                    inv = make_prec(self.ops32, data["f32"], "jacobi")
                else:
                    inv = make_prec(self.ops, data, "jacobi")
                if mg:
                    # mg demotion: keep the compiled prec-operand shape,
                    # flip the apply to the plain scalar branch
                    from pcg_mpi_solver_tpu.ops.mg import fallback_operand

                    return fallback_operand(inv)
                return inv

            self._fallback_prec_fn = jax.jit(jax.shard_map(
                _fb, mesh=self.mesh, in_specs=(self._specs,),
                out_specs=self._prec_operand_spec(), check_vma=False))
        with self._rec.dispatch("fallback_prec"):
            prec = self._fallback_prec_fn(self.data)
            jax.block_until_ready(prec)
        return prec

    def _escalation(self):
        """f64 escalation (ladder rung 3, mixed mode): finish the step
        with direct f64 Krylov cycles on the shifted f64 ops/data — a
        second ChunkedEngine built lazily, exactly like the quasi-static
        driver's."""
        from pcg_mpi_solver_tpu.ops.precond import make_prec
        from pcg_mpi_solver_tpu.solver.chunked import ChunkedEngine

        if self._esc_engine is None:
            specs64 = self._specs["f64"]
            self._esc_engine = ChunkedEngine(
                mesh=self.mesh, data_specs=specs64,
                part_spec=self._part_spec, rep_spec=self._rep_spec,
                ops=self.ops, scfg=self.config.solver,
                glob_n_dof_eff=self.pm.glob_n_dof_eff,
                cap=self._dispatch_cap, mixed=False, trace_len=0,
                recorder=self._rec, donate=self._donate)

            def _p64(data):
                return make_prec(self.ops, data, "jacobi")

            self._esc_prec_fn = jax.jit(jax.shard_map(
                _p64, mesh=self.mesh, in_specs=(specs64,),
                out_specs=self._part_spec, check_vma=False))
        with self._rec.dispatch("esc_prec"):
            prec = self._esc_prec_fn(self.data["f64"])
            jax.block_until_ready(prec)
        return self._esc_engine, self.data["f64"], prec

    def _make_resilience(self):
        """Chunk-level resilience context for one step's budget loop
        (fault hooks + dispatch guard), or None when idle.  Timestep-
        granular snapshots live one level up (the TimeHistoryGuard in
        :meth:`run`); mid-Krylov snapshot cadence stays a quasi-static-
        path feature."""
        scfg = self.config.solver
        plan = self.fault_plan
        if scfg.max_recoveries <= 0 and plan is None:
            return None
        from pcg_mpi_solver_tpu.resilience.recovery import (
            DispatchGuard, ResilienceContext)

        return ResilienceContext(
            step=len(self.flags) + 1,
            guard=DispatchGuard(retries=scfg.dispatch_retries,
                                recorder=self._rec),
            faults=plan, recorder=self._rec,
            ladder_armed=scfg.max_recoveries > 0)

    def _make_guard(self, resume: bool):
        """Timestep-granular resilience harness for :meth:`run`
        (resilience/engine.TimeHistoryGuard): step snapshots at
        ``config.snapshot_every`` completed steps, step-domain fault
        triggers, NaN/Inf rollback bounded by ``max_recoveries``."""
        every = int(getattr(self.config, "snapshot_every", 0))
        plan = self.fault_plan
        if every <= 0 and plan is None and not resume:
            return None
        from pcg_mpi_solver_tpu.resilience.engine import (
            TimeHistoryGuard, kinematic_state_io)

        store = None
        if every > 0 or resume:
            from pcg_mpi_solver_tpu.utils.checkpoint import SnapshotStore

            store = SnapshotStore.for_time_solver(self)
        fetch, put = kinematic_state_io(self.mesh, self._part_spec,
                                        self.dtype, ("u", "v", "w"))
        return TimeHistoryGuard(
            store=store, snapshot_every=every, fetch_state=fetch,
            put_state=put, recorder=self._rec, faults=plan,
            max_recoveries=int(self.config.solver.max_recoveries))

    def _history_state(self, t: int, deltas) -> dict:
        """Full resumable state after completed step ``t``: kinematic
        vectors (device) + solve histories + the schedule prefix guard."""
        return {"u": self.u, "v": self.v, "w": self.w,
                "t": np.int64(t),
                "flags": np.asarray(self.flags, np.int64),
                "relres": np.asarray(self.relres, np.float64),
                "iters": np.asarray(self.iters, np.int64),
                "deltas": np.asarray(deltas, np.float64)}

    def _step_chunked(self, delta_next):
        """Chunked step through the shared recovery harness
        (resilience/engine.run_with_recovery): flag-2/4 breakdowns,
        NaN/Inf carries and device-loss dispatch failures restart from
        the min-residual iterate through the bounded ladder — restart ->
        scalar-Jacobi fallback prec -> f64 escalation (mixed)."""
        from pcg_mpi_solver_tpu.resilience.engine import (
            RecoveryHooks, run_with_recovery)

        rec = self._rec
        d = jnp.asarray(delta_next, self.dtype)
        with rec.dispatch("start"):
            udi, fext, carry, normr0, n2b = self._start_ch_fn(
                self.data, self.u, self.v, self.w, d)
            n2b_f = float(n2b)
        if n2b_f == 0.0:
            x_fin, flag, relres, total = (jnp.zeros_like(carry["x"]),
                                          0, 0.0, 0)
            if self.trace_len:
                self.last_trace = empty_trace()
        else:
            def _restart(x):
                self._build_restart()
                with rec.dispatch("restart"):
                    kx = self._amulA_fn(self.data, x)
                    c, nr = self._restart_post_fn(self.data, fext, x, kx)
                    jax.block_until_ready(nr)
                return c, nr

            def _cold_restart():
                # device loss: rebuild the step's cold start state (the
                # kinematic vectors are intact — the start program never
                # donates them); the constant prec is always live
                with rec.dispatch("start"):
                    _u2, _f2, c, nr, _n = self._start_ch_fn(
                        self.data, self.u, self.v, self.w, d)
                    jax.block_until_ready(nr)
                return c, nr, self._prec

            engine, x_fin, flag, relres, total = run_with_recovery(
                self._engine, self.data, fext, carry, normr0, n2b,
                self._prec,
                scfg=self.config.solver, mixed=self.mixed, recorder=rec,
                hooks=RecoveryHooks(restart=_restart,
                                    cold_restart=_cold_restart,
                                    fallback_prec=self._fallback_prec,
                                    escalation=self._escalation),
                resilience=self._make_resilience())
            if self.trace_len:
                tr = engine.last_trace
                self.last_trace = (unpack_trace(tr) if tr is not None
                                   else empty_trace())
        self.u, self.v, self.w = self._finish_ch_fn(
            self.data, x_fin, udi, self.u, self.v, self.w, d)
        return flag, relres, total

    def step(self, delta_next: float) -> StepResult:
        # recovery-exempt: the one-shot Newmark step is a single
        # stateless dispatch with no resumable carry to restart from —
        # resilience is the chunked path's job (_step_chunked ->
        # run_with_recovery), and the time-history level already has the
        # TimeHistoryGuard rollback/resume harness around run().
        t0 = time.perf_counter()
        if self._dispatch_cap > 0:
            flag, relres, iters = self._step_chunked(delta_next)
        else:
            u, v, w, flag, relres, iters = self._step_fn(
                self.data, self._prec, self.u, self.v, self.w,
                jnp.asarray(delta_next, self.dtype))
            self.u, self.v, self.w = u, v, w
        wall = time.perf_counter() - t0
        res = StepResult(int(flag), float(relres), int(iters), wall)
        self.flags.append(res.flag)
        self.relres.append(res.relres)
        self.iters.append(res.iters)
        step_i = len(self.flags)
        self._rec.event("step", step=step_i, flag=res.flag,
                        relres=res.relres, iters=res.iters,
                        wall_s=round(wall, 6))
        if self.trace_len and self.last_trace is not None:
            self._rec.event("resid_trace",
                            **self.last_trace.to_event_fields(step_i))
        return res

    def run(self, load_factor: Sequence[float],
            init_accel_delta: Optional[float] = None,
            resume: bool = False) -> List[StepResult]:
        """Integrate one step per load factor (load_factor[t] scales F, Ud
        and Vd at t_{t+1}, like the quasi-static schedule).  With
        ``init_accel_delta`` set, w is (re)initialized consistently from
        the CURRENT state, w = M^-1 (F*delta - K u - C v) — standard when
        F(t_0) != 0, and also correct for continuing a run.

        Resilience (resilience/engine.TimeHistoryGuard): with
        ``config.snapshot_every > 0`` the full kinematic state
        ``(u, v, w, histories)`` is checkpointed every N completed steps
        (``step_*.npz``, retention-bounded by ``PCG_TPU_SNAP_KEEP``);
        ``resume=True`` restores the newest one and continues
        MID-TIME-HISTORY with bit-identical histories.  A non-finite
        state after a step rolls back to the last snapshot (bounded by
        ``config.solver.max_recoveries``) instead of integrating
        garbage.  Returns results for the steps run in THIS call."""
        deltas = [float(d) for d in load_factor]
        guard = self._make_guard(resume)
        t = 0
        if resume and guard is not None:
            got = guard.load_resume()
            if got is not None:
                t0, st = got
                saved = np.asarray(st["deltas"])
                if not np.array_equal(saved[:t0],
                                      np.asarray(deltas)[:t0]):
                    raise ValueError(
                        "resume schedule mismatch: the snapshot was "
                        "written under a different load_factor prefix")
                self.u, self.v, self.w = st["u"], st["v"], st["w"]
                self.flags = [int(x) for x in np.asarray(st["flags"])]
                self.relres = [float(x) for x in np.asarray(st["relres"])]
                self.iters = [int(x) for x in np.asarray(st["iters"])]
                t = int(t0)
        if init_accel_delta is not None and t == 0:
            self.w = self._init_fn(self.data, self.u, self.v,
                                   jnp.asarray(init_accel_delta, self.dtype))
        t_start = t
        results: List[StepResult] = []
        while t < len(deltas):
            res = self.step(deltas[t])
            t += 1
            results.append(res)
            finite = (math.isfinite(res.relres)
                      and bool(self._finite_fn(self.u)))
            if not finite:
                if guard is None:
                    raise FloatingPointError(
                        f"non-finite state after Newmark step {t} and no "
                        "snapshot to roll back to (set snapshot_every)")
                t0, st = guard.rollback(t)
                self.u, self.v, self.w = st["u"], st["v"], st["w"]
                self.flags = self.flags[:t0]
                self.relres = self.relres[:t0]
                self.iters = self.iters[:t0]
                del results[max(t0 - t_start, 0):]
                t = t0
                continue
            if guard is not None:
                st = guard.boundary(
                    t, lambda: self._history_state(t, deltas))
                if st is not None:
                    self.u, self.v, self.w = st["u"], st["v"], st["w"]
        # End-of-run counter/gauge snapshot, like the quasi-static
        # driver's solve() and the explicit dynamics run().
        self._rec.emit_run_summary()
        return results

    def displacement_global(self) -> np.ndarray:
        from pcg_mpi_solver_tpu.parallel.distributed import gather_owned_global

        return gather_owned_global(self.pm, self.u, self.mesh,
                                   np.dtype(self.dtype))

    def state_global(self):
        """(u, v, w) global vectors (for tests/restarts)."""
        from pcg_mpi_solver_tpu.parallel.distributed import gather_owned_global

        return tuple(gather_owned_global(self.pm, arr, self.mesh,
                                         np.dtype(self.dtype))
                     for arr in (self.u, self.v, self.w))
