"""Explicit elasto-dynamics: central-difference time integration.

The reference is quasi-static, but its data model and utilities are from an
explicit-dynamics/damage era it kept vestigially: lumped mass ``DiagM`` and
prescribed-velocity ``Vd`` arrays (partition_mesh.py:324-330), per-element
mass scale ``Cm`` (:172-175), ``Me.mat`` element mass library (:538-599),
``dt`` (run_metis.py:19-43), and offline crack-tip velocity post-processing
(file_operations.py:542-726).  This module makes that capability live,
TPU-first:

    a_n = M^-1 (Fext(t_n) - K u_n - c_m M v_n)        (lumped M, mass damping)
    v_{n+1/2} = v_{n-1/2} + dt a_n
    u_{n+1}  = u_n + dt v_{n+1/2}

with Dirichlet dofs driven as u = Ud*delta(t), v = Vd*delta(t).  The whole
step loop runs as ONE ``lax.scan`` inside a jitted shard_map program over
the device mesh — K u_n is the same node-ELL matvec + psum interface
assembly as the PCG path, probe sampling happens in-scan, and only chunk
boundaries (export frames) surface to the host.
"""

from __future__ import annotations

import dataclasses
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from pcg_mpi_solver_tpu.config import RunConfig
from pcg_mpi_solver_tpu.models.model_data import ModelData
from pcg_mpi_solver_tpu.obs.metrics import MetricsRecorder
from pcg_mpi_solver_tpu.parallel.mesh import PARTS_AXIS, make_mesh
from pcg_mpi_solver_tpu.resilience.faultinject import FaultPlan
from pcg_mpi_solver_tpu.solver.driver import _data_specs


def stable_dt(model: ModelData, safety: float = 0.9) -> float:
    """CFL estimate: h_min / c_d with c_d = sqrt(E_max/rho_min) the
    dilatational wave speed (conservative for hex elements)."""
    E = np.array([m["E"] for m in model.mat_prop], dtype=float)
    rho = np.array([m.get("Rho", 1.0) for m in model.mat_prop], dtype=float)
    c = float(np.sqrt((E / rho).max()))
    # ck = E*h, ce = 1/h  =>  h = 1/ce
    h_min = float((1.0 / model.ce).min())
    return safety * h_min / c


@dataclasses.dataclass
class DynamicsResult:
    u: np.ndarray                 # final global displacement (n_dof,)
    probe_t: np.ndarray           # (n_steps,)
    probe_u: np.ndarray           # (n_probe, n_steps)
    frames: List[np.ndarray]      # exported global displacement frames
    frame_times: List[float]


class DynamicsSolver:
    """Explicit central-difference solver on the SPMD-partitioned model."""

    def __init__(
        self,
        model: ModelData,
        config: Optional[RunConfig] = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        n_parts: Optional[int] = None,
        dt: Optional[float] = None,
        damping: float = 0.0,          # c_m: mass-proportional damping
        probe_dofs: Sequence[int] = (),
        backend: str = "auto",         # "auto" | "hybrid" | "general"
        recorder: Optional[MetricsRecorder] = None,
    ):
        self.config = config or RunConfig()
        # Telemetry registry (obs/metrics.py): same default wiring as the
        # quasi-static Solver — stderr sink iff PCG_TPU_VERBOSE=1, JSONL
        # sink iff config.telemetry_path is set.
        self.recorder = recorder if recorder is not None else (
            MetricsRecorder.default(
                jsonl_path=self.config.telemetry_path or None))
        self._rec = self.recorder
        # Flight recorder (obs/flight.py): the same crash-durable
        # dispatch brackets the quasi-static Solver gets — a long
        # explicit time history is exactly the run a device loss
        # orphans mid-chunk.
        from pcg_mpi_solver_tpu.obs.flight import attach_flight

        attach_flight(self._rec, self.config.flight_path, "dynamics")
        self.mesh = mesh if mesh is not None else make_mesh()
        n_dev = self.mesh.devices.size
        n_parts = n_parts or max(self.config.n_parts, n_dev)
        dt_source = ("arg" if dt is not None else
                     "model" if model.dt and model.dt > 0 else "cfl")
        self.dt = float(dt if dt is not None else
                        (model.dt if model.dt and model.dt > 0 else
                         stable_dt(model)))
        self.damping = float(damping)
        # Preflight gate (validate/): model sanity + the explicit-dt vs
        # stable_dt margin check, before the partition build below.  An
        # explicit caller dt above the CFL bound is rejected; a model-
        # file dt (legacy placeholder) only warns.
        from pcg_mpi_solver_tpu.validate import run_preflight

        run_preflight(model, self.config, recorder=self._rec,
                      context={"kind": "dynamics", "dt": self.dt,
                               "dt_source": dt_source})

        dtype = jnp.dtype(self.config.solver.dtype)
        if dtype == jnp.float64 and not jax.config.jax_enable_x64:
            jax.config.update("jax_enable_x64", True)
        self.dtype = dtype

        # Backend: the hybrid level-grid path serves octree models' matvec
        # (the per-step hot op) exactly as in the quasi-static driver;
        # everything else stays on the general path.  Pallas only ever
        # dispatches on f32 matvecs; dynamics has no mixed-precision f32
        # shadow, so the probe is skipped in f64 runs.
        from pcg_mpi_solver_tpu.solver.backends import select_time_backend

        self.backend, self.pm, mk_ops, mk_data = select_time_backend(
            model, n_parts,
            partition_method=self.config.partition_method,
            pallas_mode=self.config.solver.pallas, mesh=self.mesh,
            kernels_f32=dtype == jnp.float32, backend=backend)
        self.ops = mk_ops(dtype)
        data = mk_data(dtype)
        # Assembled lumped-mass diagonal: model.diag_M is already the global
        # assembled diagonal, sliced per part (partition extract_NodalVectors
        # analogue) — no cross-part assembly needed.
        inv_m = np.where(self.pm.inv_diag_M > 0, self.pm.inv_diag_M, 0.0)
        data["inv_M"] = np.asarray(inv_m, dtype)
        # Prescribed velocity (reference Vd, partition_mesh.py:324-330),
        # sliced per part like F/Ud.
        gid = self.pm.dof_gid
        data["Vd"] = np.asarray(
            np.where(gid >= 0, model.Vd[np.maximum(gid, 0)], 0.0), dtype)

        # Probe maps: local index of each probe dof per part + owner mask,
        # so in-scan sampling is a tiny gather + the mesh psum (works under
        # shard_map where each device only sees its local parts).
        self._probe = np.asarray(probe_dofs, dtype=np.int64)
        P_, n_loc_ = gid.shape
        np_ = len(self._probe)
        pidx = np.zeros((P_, max(np_, 1)), dtype=np.int32)
        pmask = np.zeros((P_, max(np_, 1)))
        for j, d in enumerate(self._probe):
            hits = np.argwhere((gid == d) & (self.pm.weight > 0))
            if len(hits) == 0:
                raise ValueError(
                    f"probe dof {int(d)} is not an owned dof of any part "
                    "(out of range or Dirichlet-constrained everywhere)")
            p, i = hits[0]
            pidx[p, j], pmask[p, j] = i, 1.0
        data["probe_idx"] = pidx
        data["probe_mask"] = np.asarray(pmask, dtype)
        self._specs = _data_specs(data)

        from pcg_mpi_solver_tpu.parallel.distributed import put_sharded, put_tree

        self.data = put_tree(data, self.mesh, self._specs)
        self._part_spec = jax.sharding.PartitionSpec(PARTS_AXIS)
        P, n_loc = self.pm.n_parts, self.pm.n_loc
        self.u = put_sharded(np.zeros((P, n_loc), dtype),
                             self.mesh, self._part_spec)
        self.v = put_sharded(np.zeros((P, n_loc), dtype),
                             self.mesh, self._part_spec)

        # ---- resilience (resilience/): timestep-granular snapshots,
        # NaN/Inf chunk-boundary detection with rollback, step-domain
        # fault injection (`kill@s:N` etc.).  `fault_plan` is settable.
        self.fault_plan = FaultPlan.from_env(recorder=self._rec)
        self._finite_fn = jax.jit(lambda a: jnp.isfinite(a).all())
        self.mixed = False           # checkpoint fingerprint contract
        self._model = model          # fingerprint content hash

        ops, dt_, cm = self.ops, self.dt, self.damping

        def _chunk(data, carry, deltas):
            """Scan over a chunk of steps; deltas: (k,) load factors."""
            eff = data["eff"]
            fix = 1.0 - eff

            def body(carry, delta):
                u, v = carry
                fint = ops.matvec(data, u)
                # mass damping: C = c_m M  =>  M^-1 C v = c_m v
                a = data["inv_M"] * (data["F"] * delta - fint) - cm * v
                v2 = v + dt_ * a
                u2 = u + dt_ * v2
                # Dirichlet driving
                u2 = eff * u2 + fix * data["Ud"] * delta
                v2 = eff * v2 + fix * data["Vd"] * delta
                # owner-masked probe sample, combined over the mesh
                vals = jnp.take_along_axis(u2, data["probe_idx"], axis=1)
                probes = ops._psum((vals * data["probe_mask"]).sum(axis=0))
                return (u2, v2), probes

            (u, v), probe = jax.lax.scan(body, carry, deltas)
            return u, v, probe

        shard_chunk = jax.shard_map(
            _chunk, mesh=self.mesh,
            in_specs=(self._specs, (self._part_spec, self._part_spec),
                      jax.sharding.PartitionSpec()),
            out_specs=(self._part_spec, self._part_spec,
                       jax.sharding.PartitionSpec()),
            check_vma=False,
        )
        self._chunk_fn = jax.jit(shard_chunk)

    def _make_guard(self, resume: bool):
        """Timestep-granular resilience harness
        (resilience/engine.TimeHistoryGuard): ``config.snapshot_every``
        checkpoints of the full state (u, v, probe series, export
        frames) into ``step_*.npz``, step-domain fault triggers, NaN/Inf
        rollback bounded by ``config.solver.max_recoveries``."""
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
                                        self.dtype, ("u", "v"))
        return TimeHistoryGuard(
            store=store, snapshot_every=every, fetch_state=fetch,
            put_state=put, recorder=self._rec, faults=plan,
            max_recoveries=int(self.config.solver.max_recoveries))

    def _next_chunk(self, done: int, n_steps: int, export_every: int,
                    guard) -> int:
        """Steps to integrate in the next device chunk: up to the
        nearest host boundary (export frame, snapshot cadence, pending
        step-domain fault, end of schedule).  Distinct chunk lengths
        compile distinct scan programs, so cadences that divide the
        export rate keep the historical two-program profile."""
        cands = [n_steps]
        if export_every > 0:
            cands.append(done + export_every - done % export_every)
        if guard is not None:
            if guard.snapshot_every > 0:
                cands.append(done + guard.snapshot_every
                             - done % guard.snapshot_every)
            if guard.faults is not None:
                nf = guard.faults.next_step_fault(done)
                if nf is not None:
                    cands.append(nf)
        return min(c for c in cands if c > done) - done

    def run(self, n_steps: int, load_factor=None,
            export_every: int = 0, resume: bool = False) -> DynamicsResult:
        """Integrate n_steps.  ``load_factor``: scalar, (n_steps,) array, or
        None (=1.0).  ``export_every``: displacement frames every k steps.

        Resilience (resilience/engine.TimeHistoryGuard): with
        ``config.snapshot_every > 0`` the full state — kinematic vectors,
        probe series, export frames — is checkpointed every N completed
        TIMESTEPS (``step_*.npz``, retention-bounded by
        ``PCG_TPU_SNAP_KEEP``); ``resume=True`` restores the newest one
        and continues mid-history, reproducing the uninterrupted run's
        probe series and frames bit-identically.  Non-finite state
        detected at a chunk boundary rolls back to the last snapshot
        (bounded by ``config.solver.max_recoveries``) instead of
        silently integrating garbage."""
        if load_factor is None:
            deltas = np.ones(n_steps)
        else:
            deltas = np.broadcast_to(np.asarray(load_factor, dtype=float),
                                     (n_steps,)).copy()
        guard = self._make_guard(resume)
        frames: List[np.ndarray] = []
        frame_steps: List[int] = []
        n_pcols = max(len(self._probe), 1)
        # probe samples accumulate as a list of per-chunk arrays and are
        # concatenated lazily (at snapshot/rollback/return) — a per-chunk
        # concat of the growing history would be O(n^2) over a long run
        probe_chunks: List[np.ndarray] = []

        def _probe_cat() -> np.ndarray:
            return (np.concatenate(probe_chunks, axis=0) if probe_chunks
                    else np.zeros((0, n_pcols)))

        done = 0
        u, v = self.u, self.v
        if resume and guard is not None:
            got = guard.load_resume()
            if got is not None:
                t0, st = got
                if not np.array_equal(np.asarray(st["deltas"])[:t0],
                                      deltas[:t0]):
                    raise ValueError(
                        "resume schedule mismatch: the snapshot was "
                        "written under a different load_factor prefix")
                u, v = st["u"], st["v"]
                done = int(t0)
                probe_chunks = [np.asarray(st["probe"])[:done]]
                frames = [f.copy() for f in np.asarray(st["frames"])]
                frame_steps = [int(s) for s in
                               np.asarray(st["frame_steps"])]
        while done < n_steps:
            k = self._next_chunk(done, n_steps, export_every, guard)
            t0c = time.perf_counter()
            with self._rec.dispatch("dynamics_chunk", emit=False):
                u2, v2, pr = self._chunk_fn(
                    self.data, (u, v),
                    jnp.asarray(deltas[done:done + k], self.dtype))
                # the probe fetch forces the transfer, so the chunk wall
                # time below covers execution, not just dispatch
                pr = np.asarray(pr)
            self._rec.event(
                "dynamics_chunk", steps=int(k),
                wall_s=round(time.perf_counter() - t0c, 6))
            # NaN/Inf detection between chunks: an explicit run has no
            # flags or residuals to report corruption on its own, so
            # poison would otherwise integrate silently to the end
            if not (np.isfinite(pr).all() and bool(self._finite_fn(u2))):
                if guard is None:
                    raise FloatingPointError(
                        f"non-finite state within dynamics steps "
                        f"{done + 1}..{done + k} (dt={self.dt:.3e}; "
                        f"check against stable_dt(); set snapshot_every "
                        "for rollback)")
                t_roll, st = guard.rollback(done + k)
                u, v = st["u"], st["v"]
                done = int(t_roll)
                probe_chunks = [_probe_cat()[:done]]
                n_keep = sum(1 for s in frame_steps if s <= done)
                frames, frame_steps = frames[:n_keep], frame_steps[:n_keep]
                continue
            u, v = u2, v2
            done += k
            if len(self._probe):
                probe_chunks.append(pr)
            if export_every > 0 and (done % export_every == 0
                                     or done == n_steps):
                frames.append(self._global_u(u))
                frame_steps.append(done)
            if guard is not None:
                st = guard.boundary(done, lambda: {
                    "u": u, "v": v, "t": np.int64(done),
                    "probe": _probe_cat(),
                    "frames": (np.stack(frames) if frames
                               else np.zeros((0, self._model.n_dof))),
                    "frame_steps": np.asarray(frame_steps, np.int64),
                    "deltas": deltas})
                if st is not None:
                    u, v = st["u"], st["v"]
        self.u, self.v = u, v
        # End-of-run snapshot, like the quasi-static driver's solve():
        # without it the gauges/dispatch attribution of a JSONL-sinking
        # run would be silently discarded.
        self._rec.emit_run_summary()
        probe_u = (_probe_cat().T[: len(self._probe)]
                   if len(self._probe) else np.zeros((0, n_steps)))
        return DynamicsResult(
            u=self._global_u(u),
            probe_t=(np.arange(n_steps) + 1) * self.dt,
            probe_u=probe_u,
            frames=frames,
            frame_times=[s * self.dt for s in frame_steps],
        )

    def _global_u(self, u) -> np.ndarray:
        from pcg_mpi_solver_tpu.parallel.distributed import gather_owned_global

        return gather_owned_global(self.pm, u, self.mesh, self.dtype)
