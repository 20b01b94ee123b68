"""Quasi-static solve driver: the reference's main program re-designed.

Reference flow (pcg_solver.py:1002-1008): for each time step —
updateBC (Dirichlet lifting) -> updatePreconditioner (Jacobi rebuild) ->
PCG -> history/exports.  Here the whole step (lifting matvec + diagonal
assembly + the full PCG while_loop) is ONE jitted shard_map'd SPMD program
over the device mesh; only the small per-step scalars (flag/relres/iters)
come back to the host.
"""

from __future__ import annotations

import dataclasses
import os
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from pcg_mpi_solver_tpu.config import RunConfig
from pcg_mpi_solver_tpu.models.model_data import ModelData
from pcg_mpi_solver_tpu.obs.metrics import MetricsRecorder
from pcg_mpi_solver_tpu.obs.trace import (
    ConvergenceTrace, clamp_trace_len, empty_trace, trace_init, trace_specs,
    unpack_trace)
from pcg_mpi_solver_tpu.ops.matvec import Ops, device_data, with_f32_shadow
from pcg_mpi_solver_tpu.parallel.mesh import PARTS_AXIS, make_mesh
from pcg_mpi_solver_tpu.parallel.partition import (
    PartitionedModel, balance_gauges, partition_model)
from pcg_mpi_solver_tpu.resilience.faultinject import FaultPlan
from pcg_mpi_solver_tpu.solver.pcg import pcg, pcg_mixed, scoped

@dataclasses.dataclass
class StepResult:
    flag: int
    relres: float
    iters: int
    wall_s: float


def normalize_rhs_block(fexts, n_dof: int, dtype=None) -> np.ndarray:
    """ONE authoritative normalization of a solve_many request to the
    (n_dof, nrhs) column contract: a single (n_dof,) vector promotes to
    one column, a stacked (nrhs, n_dof) list transposes when
    unambiguous.  Shared by Solver.solve_many and the CLI front-end so
    the shape heuristic cannot diverge between entry points.  With
    ``dtype=None`` the input dtype is kept (a shape-only pass: no
    full-block copy when the caller just needs the width)."""
    fb = np.asarray(fexts) if dtype is None \
        else np.asarray(fexts, dtype=dtype)
    if fb.ndim == 1:
        fb = fb[:, None]
    elif fb.ndim == 2 and fb.shape[0] != n_dof and fb.shape[1] == n_dof:
        fb = fb.T
    return fb


@dataclasses.dataclass
class ManySolveResult:
    """Per-RHS outcome of a batched :meth:`Solver.solve_many` block:
    flags/relres/iters are (nrhs,) per-column vectors (MATLAB pcg flag
    taxonomy per column, plus flag 5 = quarantined — see
    ``solver/pcg.QUARANTINE_FLAG`` and docs/RUNBOOK.md "Blocked solve
    failure modes & quarantine"), ``x`` the device-resident blocked
    solution (n_parts, n_loc, nrhs) on effective dofs — fetch global
    per-column vectors with :meth:`Solver.displacement_global_many`."""
    flags: np.ndarray
    relres: np.ndarray
    iters: np.ndarray
    wall_s: float
    x: object = None

    # wall of the Krylov work alone (staging — validation, the
    # global->local map, the device upload — excluded): the honest
    # per-iteration denominator for nrhs A/Bs, since the scalar step()
    # baseline derives its rhs in-graph from device-resident data
    solve_wall_s: float = 0.0

    # fault isolation between columns (resilience/): the column indices
    # that ended QUARANTINED (flag 5 — unrecoverable breakdown/poison,
    # reported as their min-residual iterate + true residual), the total
    # per-column recovery-ladder attempts consumed, and the fused
    # residual-drift check count (0 for classic)
    quarantined: tuple = ()
    recoveries: int = 0
    drift: int = 0

    # inner iterations the block ran in lockstep: per Krylov cycle the
    # most any column ran, summed over the cycles (a converged column
    # rides the rest of a cycle as a masked lane); sum(iters) /
    # (nrhs * lockstep_iters) is the share of lanes that did live work
    lockstep_iters: int = 0

    @property
    def nrhs(self) -> int:
        return int(len(self.flags))


class Solver:
    """Owns the partitioned model on the device mesh and runs time steps."""

    def __init__(
        self,
        model: ModelData,
        config: Optional[RunConfig] = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        n_parts: Optional[int] = None,
        elem_part: Optional[np.ndarray] = None,
        backend: str = "auto",   # "auto" | "structured" | "hybrid" | "general"
        recorder: Optional[MetricsRecorder] = None,
    ):
        self._t_init0 = time.perf_counter()
        self.config = config or RunConfig()
        # Telemetry: an injected recorder wins; otherwise build the default
        # (stderr sink iff PCG_TPU_VERBOSE=1 — the historical knob — plus a
        # JSONL sink iff config.telemetry_path is set).
        self.recorder = recorder if recorder is not None else (
            MetricsRecorder.default(
                jsonl_path=self.config.telemetry_path or None))
        self._rec = self.recorder
        # ---- flight recorder (obs/flight.py): crash-durable begin/end
        # brackets + heartbeats around every solve dispatch, so a device
        # loss / SIGKILL mid-solve leaves a parseable artifact instead
        # of a log to hand-reconstruct.
        from pcg_mpi_solver_tpu.obs.flight import attach_flight

        self._flight = attach_flight(
            self._rec, self.config.flight_path, "solver",
            pcg_variant=self.config.solver.pcg_variant,
            precond=self.config.solver.precond)
        # ---- preflight gate (validate/): reject a pathological model or
        # config BEFORE any partition build or XLA compile is paid (the
        # flagship pays minutes of both).  Policy: config.preflight >
        # PCG_TPU_PREFLIGHT > fail.
        from pcg_mpi_solver_tpu.validate import run_preflight

        # No n_steps in the context: on this path snapshot_every counts
        # CHUNK boundaries, not time steps, so the cadence-vs-schedule
        # cross-check does not apply (it would false-warn on every
        # protected multi-chunk solve).
        run_preflight(model, self.config, recorder=self._rec,
                      context={"kind": "quasi_static"})
        self.mesh = mesh if mesh is not None else make_mesh()
        n_dev = self.mesh.devices.size
        if n_parts is None:
            n_parts = max(self.config.n_parts, n_dev)
        if n_parts < 1:
            raise ValueError(f"n_parts must be >= 1, got {n_parts}")
        if n_parts % n_dev != 0:
            raise ValueError(f"n_parts={n_parts} must be a multiple of device count {n_dev}")

        solver_cfg = self.config.solver
        from pcg_mpi_solver_tpu.ops.precond import VALID_PRECONDS
        from pcg_mpi_solver_tpu.solver.pcg import VALID_PCG_VARIANTS

        if solver_cfg.precond not in VALID_PRECONDS:
            raise ValueError(f"SolverConfig.precond must be one of "
                             f"{VALID_PRECONDS}, got {solver_cfg.precond!r}")
        if solver_cfg.pcg_variant not in VALID_PCG_VARIANTS:
            raise ValueError(
                f"SolverConfig.pcg_variant must be one of "
                f"{VALID_PCG_VARIANTS}, got {solver_cfg.pcg_variant!r}")
        self.mixed = solver_cfg.precision_mode == "mixed"
        dtype = jnp.dtype(jnp.float64) if self.mixed else jnp.dtype(solver_cfg.dtype)
        dot_dtype = jnp.dtype(solver_cfg.dot_dtype)
        if self.mixed or jnp.float64 in (dtype, dot_dtype):
            if not jax.config.jax_enable_x64:
                # The config asked for f64 math — honor it rather than
                # silently downgrading (the reference is f64 throughout).
                jax.config.update("jax_enable_x64", True)
        self.dtype = dtype

        # ---- warm-path cache + donated-carry dispatch (cache/) -----------
        # An accelerator solver keeps jax's persistent compilation cache
        # in its one place (cache/aot.use_compilation_cache).  cache_dir
        # set => partitions come from the content-addressed on-disk
        # cache and the one-shot PCG step is AOT-exported (zero
        # re-tracing on a warm run).  The model fingerprint is the
        # content half of every cache key.
        if self.mesh.devices.flat[0].platform != "cpu":
            from pcg_mpi_solver_tpu.cache.aot import use_compilation_cache

            self._rec.gauge("cache.xla_dir", use_compilation_cache())
        self._donate = bool(solver_cfg.donate_carry)
        self._cache_dir = (self.config.cache_dir or "").strip() or None
        self._model_fp = None
        # Counter baseline for THIS construction: the recorder may be a
        # process-lifetime one (bench._REC) whose cache counters already
        # carry earlier solvers' hits/misses — setup_cache must reflect
        # only the partitions this __init__ resolved.
        self._cache_hm0 = (
            self._rec.counters.get("cache.partition.hit", 0),
            self._rec.counters.get("cache.partition.miss", 0))
        if self._cache_dir:
            from pcg_mpi_solver_tpu.cache.keys import model_fingerprint

            with self._rec.span("cache_fingerprint"):
                self._model_fp = model_fingerprint(model)
            self._rec.gauge("cache.dir", self._cache_dir)

        # ---- backend selection: structured slab fast path when possible ----
        # (TPU has no vector gather/scatter; the structured path replaces
        # them with contiguous slice shifts, parallel/structured.py.)
        can_structured = (
            model.grid is not None
            and not model.elem_sign_flat.any()
            and not model.intfc_elems
            and n_parts == n_dev
            # An explicitly requested non-default partitioner (method or an
            # elem_part array) must not be silently replaced by the
            # structured slab partition.
            and self.config.partition_method in ("rcb", "auto")
            and elem_part is None
            and model.grid[0] % n_parts == 0
        )
        if backend not in ("auto", "structured", "hybrid", "general"):
            raise ValueError(f"backend must be 'auto'|'structured'|'hybrid'|"
                             f"'general', got {backend!r}")
        setup_shard = getattr(self.config, "setup_shard", "auto")
        if setup_shard not in ("auto", "on", "off"):
            raise ValueError(f"RunConfig.setup_shard must be "
                             f"'auto'|'on'|'off', got {setup_shard!r}")
        # f64-refresh formulation (hybrid+mixed only; see the hybrid
        # branch below).  Recorded in the checkpoint fingerprint: the
        # general form's summation order differs, which can drift
        # refresh residuals in the last bits.
        self.f64_refresh = "stencil"
        self._refresh64_src = None
        if backend == "structured" and not can_structured:
            raise ValueError("structured backend requested but model/partition "
                             "layout does not allow it")
        from pcg_mpi_solver_tpu.parallel.hybrid import can_hybrid as _can_hy

        can_hybrid = _can_hy(model)
        if backend == "hybrid" and not can_hybrid:
            raise ValueError("hybrid backend requested but model has no "
                             "octree/brick metadata")
        # Hybrid demotion gate (ISSUE 14 satellite): dry-runs put the
        # hybrid partition build at 117-183 s where structured takes
        # 10.5 s at the same scale (ROADMAP item 2), and its level-grid
        # stencil compile costs minutes per instantiation — AUTO
        # selection now requires the explicit PCG_TPU_ENABLE_HYBRID=1
        # opt-in (docs/RUNBOOK.md "Scaling the setup path" carries the
        # deprecation note).  An EXPLICIT backend="hybrid" request is
        # honored unchanged — the gate only stops silent auto-routing.
        hybrid_ok = os.environ.get("PCG_TPU_ENABLE_HYBRID") == "1"
        if backend in ("auto", "structured") and can_structured:
            self.backend = "structured"
        elif backend == "hybrid" and can_hybrid:
            self.backend = "hybrid"
        elif backend == "auto" and can_hybrid and hybrid_ok:
            self.backend = "hybrid"
        else:
            if backend == "auto" and can_hybrid and not hybrid_ok:
                self._rec.note(
                    "model is hybrid-backend eligible but auto-selection "
                    "is gated (set PCG_TPU_ENABLE_HYBRID=1 or pass "
                    "backend='hybrid'); using the general backend — see "
                    "RUNBOOK 'Scaling the setup path'")
            self.backend = "general"

        # ---- sharded setup (ISSUE 14): under multi-process
        # jax.distributed, build/load only THIS process's parts of the
        # partition (the general/structured builders take part_range; the
        # global layout merges via HostComm reductions) — the cold path
        # then scales with process count instead of model size.  The
        # hybrid backend keeps the monolithic build (level grids are not
        # part-sharded).
        from pcg_mpi_solver_tpu.parallel.distributed import (
            HostComm, local_part_range)

        self._setup_range = None
        self._setup_comm = None
        self.partition_build_s = 0.0
        if (setup_shard != "off" and jax.process_count() > 1
                and self.backend in ("general", "structured")):
            rng = local_part_range(self.mesh, n_parts)
            # equal contiguous slabs only: the glue exchange allgathers
            # same-shaped blocks from every process
            ok = (rng is not None and rng != (0, n_parts)
                  and (rng[1] - rng[0]) * jax.process_count() == n_parts)
            # The engage decision GATES collective code paths (warmup,
            # the layout exchange, the glue allgathers) — it must be
            # GROUP-AGREED: an exotic device order can make one
            # process's parts non-contiguous while another's pass, and
            # a split decision deadlocks the group on its first
            # unmatched collective.  Every process reaches this reduce
            # (the inputs above are process-invariant).
            from pcg_mpi_solver_tpu.parallel.consensus import agree_flag

            comm = HostComm()
            if agree_flag(comm, ok):
                self._setup_range = rng
                self._setup_comm = comm
                from pcg_mpi_solver_tpu.parallel.partition import (
                    layout_exchange_sizes)

                with self._rec.span("setup_comm_warmup"):
                    self._setup_comm.warmup(layout_exchange_sizes(
                        model.n_dof, model.n_node,
                        len(model.elem_lib), n_parts))
            elif setup_shard == "on":
                raise ValueError(
                    "RunConfig.setup_shard='on' but some process's parts "
                    "are not one contiguous equal block of the mesh (use "
                    "make_global_mesh, or n_parts divisible by the "
                    "device count)")

        if self.backend == "structured":
            from pcg_mpi_solver_tpu.parallel.structured import (
                StructuredOps, device_data_structured, partition_structured)

            self.pm = self._partition_cached(
                "structured",
                lambda part_range=None: partition_structured(
                    model, n_parts, part_range=part_range),
                n_parts=n_parts, shard=True)
            self.ops = StructuredOps.from_partition(
                self.pm, dot_dtype=dot_dtype, axis_name=PARTS_AXIS)
            data = device_data_structured(self.pm, dtype)
            ops32_factory = lambda: StructuredOps.from_partition(
                self.pm, dot_dtype=jnp.float32, axis_name=PARTS_AXIS)
        elif self.backend == "hybrid":
            from pcg_mpi_solver_tpu.parallel.hybrid import (
                HybridOps, device_data_hybrid, partition_env_knobs,
                partition_hybrid)

            # PCG_TPU_HYBRID_F64_REFRESH: formulation of the out-of-loop f64
            # matvecs (Dirichlet lifting, r0, refinement true-residual — ~4
            # calls/solve).  Default BUCKETED: a full general element partition
            # with the 200+ per-type structures stacked into a few padded
            # batched einsums. Chipless compile at the 5.67M-dof flagship:
            # stencil 999 s / general 1343 s / bucketed (5 buckets) 425 s —
            # compile cost tracks emitted structure count, and the f64 stencil
            # amul was the flagship's single largest program.  Runtime is
            # per-cycle, so compile dominates the session economics; "stencil"
            # forces the old form (slightly less HBM, fastest execution).
            # Needs the SAME elem_part so the local dof numbering is identical
            # (partition_model's numbering is block_filter-independent).
            self.f64_refresh = "stencil"
            _knob = os.environ.get("PCG_TPU_HYBRID_F64_REFRESH",
                                   "bucketed")
            if _knob not in ("stencil", "general", "bucketed"):
                # the mode drives checkpoint fingerprints and a 2.35x
                # compile-cost spread — a typo must not silently pick one
                raise ValueError(
                    f"PCG_TPU_HYBRID_F64_REFRESH={_knob!r}: expected "
                    "'bucketed' (default), 'stencil' or 'general'")
            if self.mixed and _knob in ("general", "bucketed"):
                self.f64_refresh = _knob
            method = self.config.partition_method
            self.pm = self._partition_cached(
                "hybrid",
                lambda: partition_hybrid(model, n_parts,
                                         elem_part=elem_part,
                                         method=method),
                n_parts=n_parts, method=method, elem_part=elem_part,
                # every partition-time env knob keys the entry, resolved
                # by the module that owns the defaults (block/merge
                # reshape the level grids, kd shapes CombineMaps)
                extra=partition_env_knobs())
            if self.f64_refresh in ("general", "bucketed") \
                    and elem_part is None:
                # The general-refresh partition below must use the SAME
                # element->part map (identical local dof numbering).  A
                # cache hit skipped make_elem_part entirely, so recover
                # the map from the partition itself.
                elem_part = np.asarray(self.pm.elem_part)
            self.ops = HybridOps.from_hybrid(
                self.pm, dot_dtype=dot_dtype, axis_name=PARTS_AXIS)
            data = device_data_hybrid(self.pm, dtype)
            ops32_factory = lambda: HybridOps.from_hybrid(
                self.pm, dot_dtype=jnp.float32, axis_name=PARTS_AXIS)
            if self.f64_refresh in ("general", "bucketed"):
                pm_full = self._partition_cached(
                    "general",
                    lambda: partition_model(model, n_parts,
                                            elem_part=elem_part),
                    n_parts=n_parts, method="explicit",
                    elem_part=elem_part)
                if not (pm_full.n_loc == self.pm.n_loc
                        and np.array_equal(pm_full.node_gid,
                                           self.pm.node_gid)):
                    raise RuntimeError(
                        "general-refresh partition numbering diverged "
                        "from the hybrid partition (same elem_part must "
                        "yield identical local dof layouts)")
                if self.f64_refresh == "bucketed" and pm_full.ell is None:
                    # bucketing needs the 3-dof node layout (its gather/
                    # scatter move node rows); models that break it
                    # (e.g. node-less spring dofs, partition.py) degrade
                    # to the unbucketed general form instead of failing
                    # a construction that both older forms handled
                    import warnings

                    warnings.warn(
                        "PCG_TPU_HYBRID_F64_REFRESH=bucketed needs the "
                        "node layout; using 'general' for this model")
                    self.f64_refresh = "general"
                if self.f64_refresh == "bucketed":
                    from pcg_mpi_solver_tpu.ops.matvec import (
                        build_bucketed_blocks)

                    rdata = device_data(pm_full, jnp.float64, blocks=False)
                    rdata["buckets"] = build_bucketed_blocks(
                        pm_full, jnp.float64)
                else:
                    rdata = device_data(pm_full, jnp.float64)
                self._refresh64_src = (
                    Ops.from_model(pm_full, dot_dtype=jnp.float64,
                                   axis_name=PARTS_AXIS),
                    rdata)
        else:
            method = self.config.partition_method
            extra = {}
            if method == "slab2":
                # two-level split: the coarse slab count is structural
                # (a different count = a different partition) — one slab
                # per process so each process refines only its own slab.
                # A function of the PROCESS TOPOLOGY alone, never of
                # whether sharding engaged: toggling setup_shard (a
                # TRACE_NEUTRAL_RUNCONFIG field) must not change the
                # element partition.
                extra["slab2_slabs"] = jax.process_count()
            self.pm = self._partition_cached(
                "general",
                lambda part_range=None: partition_model(
                    model, n_parts, elem_part=elem_part, method=method,
                    part_range=part_range, comm=self._setup_comm,
                    slab2_slabs=extra.get("slab2_slabs", 1)),
                n_parts=n_parts, method=method,
                elem_part=elem_part, extra=extra, shard=True)
            self.ops = Ops.from_model(self.pm, dot_dtype=dot_dtype,
                                      axis_name=PARTS_AXIS)
            data = device_data(self.pm, dtype)
            ops32_factory = lambda: Ops.from_model(
                self.pm, dot_dtype=jnp.float32, axis_name=PARTS_AXIS)

        if self._setup_range is not None:
            # Sharded setup: re-assemble the small host-side EXPORT GLUE
            # (owner masks + global id maps — gather_owned_global,
            # solve_many staging, export maps read ALL parts' rows) from
            # every process's slab.  The heavy per-part structures stay
            # local; this is O(P * n_loc) ids, not O(model).
            self._exchange_export_glue(self.pm)

        # ---- MG hierarchy (precond="mg" — ops/mg.py): host-built level
        # lattice + transfers into the device data tree, the Chebyshev
        # degree pinned on the ops (it shapes the traced V-cycle).  The
        # hybrid backend is out of scope by design: its level-grid
        # stencil costs minutes of compile PER INSTANTIATION and the
        # cycle adds 2*degree more.
        self._mg_meta = None
        self._mg_setup = None
        if solver_cfg.precond == "mg":
            if self.backend == "hybrid":
                raise ValueError(
                    "precond='mg' is not supported on the hybrid "
                    "level-grid backend; use backend='general' or "
                    "'structured' (or precond='jacobi'|'block3')")
            from pcg_mpi_solver_tpu.ops import mg as mgmod

            t_mg0 = time.perf_counter()
            with self._rec.span("mg_setup"):
                mg_setup = self._build_mg_cached(model, solver_cfg)
            # float leaves at the STORAGE dtype (mgmod.cast_tree); the
            # mixed shadow below re-derives its f32 copy
            data["mg"] = mgmod.cast_tree(mg_setup.tree, dtype)
            self._mg_meta = mg_setup.meta
            self._mg_setup = (mg_setup, time.perf_counter() - t_mg0)
            deg = int(solver_cfg.mg_smooth_degree)
            cdofs = mgmod.coarse_dofs(mg_setup.meta)
            self.ops = dataclasses.replace(self.ops, mg_degree=deg,
                                           mg_coarse_dofs=cdofs)
            _base32_factory = ops32_factory
            ops32_factory = lambda: dataclasses.replace(
                _base32_factory(), mg_degree=deg, mg_coarse_dofs=cdofs)

        if self.mixed:
            # f32 shadow of the float leaves; index/bool arrays are shared
            # (same device buffers), so the extra memory is only the f32 floats.
            data = with_f32_shadow(data)
            self.ops32 = ops32_factory()
        self._specs = _data_specs(data)
        # The whole tree is host arrays up to here: the upload places each
        # part's rows on its own device (multi-host: each process only
        # its addressable shards; parallel/distributed.py).
        from pcg_mpi_solver_tpu.parallel.distributed import put_tree

        self._refresh64 = None
        with self._rec.span("upload"):
            self.data = put_tree(data, self.mesh, self._specs)
            if self._refresh64_src is not None:
                rops, rdata = self._refresh64_src
                rspecs = _data_specs(rdata)
                self._refresh64 = (rops, put_tree(rdata, self.mesh, rspecs),
                                   rspecs)
                self._refresh64_src = None      # free the host copies
            jax.block_until_ready(
                (self.data, self._refresh64 and self._refresh64[1]))

        self._part_spec = jax.sharding.PartitionSpec(PARTS_AXIS)
        self._rep_spec = jax.sharding.PartitionSpec()

        if solver_cfg.precond == "mg":
            # fine-level Chebyshev bound: a few power-iteration matvecs
            # on the uploaded operator (cached in the partition cache —
            # warm runs skip the device work), then the per-level lambda
            # vector joins the device tree and the setup telemetry/
            # degenerate-interval warning fire
            self._finish_mg_setup(solver_cfg)

        glob_n_eff = self.pm.glob_n_dof_eff

        # Static telemetry gauges: problem size, backend, and the per-PCG-
        # iteration collective estimate from the ops shapes (psum count /
        # payload bytes) — reported in the run_summary event.
        self._rec.gauge("backend", self.backend)
        self._rec.gauge("n_parts", int(self.pm.n_parts))
        self._rec.gauge("n_dof", int(self.pm.glob_n_dof))
        self._rec.gauge("precision_mode", solver_cfg.precision_mode)
        self._rec.gauge("pcg_variant", solver_cfg.pcg_variant)
        self._rec.gauge("precond", solver_cfg.precond)
        if self.backend == "structured":
            # which stencil layout ran: real cells over lane-padded slots
            self._rec.gauge("structured.stencil_lane_fill",
                            round(self.ops.stencil_lane_fill(), 4))
        cmaps = getattr(self.pm, "combine", None)
        if cmaps is not None:
            for k, v in cmaps.gauges().items():
                self._rec.gauge(k, v)
        if self.backend != "structured":
            for k, v in balance_gauges(self.pm).items():
                self._rec.gauge(k, v)
        # mixed mode: the Krylov iterations (vectors AND dot reductions)
        # run on the f32 ops, so that is the ops object to size from;
        # the variant sets the per-iteration collective count (fused =
        # one scalar psum, classic = three)
        est_ops = self.ops32 if self.mixed else self.ops
        iter_dtype = jnp.float32 if self.mixed else dtype
        for k, v in est_ops.comm_estimate(
                storage_dtype=iter_dtype,
                variant=solver_cfg.pcg_variant,
                precond=solver_cfg.precond).items():
            self._rec.gauge(f"comm.{k}", v)

        # Analytic per-iteration cost model (obs/perf.py, ISSUE 12):
        # roofline-predicted ms/iter per phase for the engaged
        # (variant, precond, nrhs, backend), emitted as a schema-
        # versioned `cost_model` event + perf.* gauges so every
        # telemetry stream carries the number its measured ms/iter
        # should be judged against.  An unknown variant/precond is a
        # loud KeyError (the single-source-table contract) — kept loud
        # ONLY for the cost_model() table lookup itself; any hiccup in
        # shape derivation, profile resolution or event emission on an
        # exotic model degrades to a note — the model is observability,
        # not a solve dependency.
        from pcg_mpi_solver_tpu.obs import perf as _perf

        self._perf_shape = None
        self._perf_profile = None
        self._cost_model = None
        self._cost_models_by_width: Dict[int, Any] = {}
        try:
            shape = _perf.shape_from_solver(self)
            profile = _perf.resolve_profile(
                self.mesh.devices.flat[0].device_kind)
        except Exception as e:                          # noqa: BLE001
            self._rec.note(f"cost_model unavailable: "
                           f"{type(e).__name__}: {e}")
        else:
            try:
                cm = _perf.cost_model(
                    shape, solver_cfg.pcg_variant, solver_cfg.precond,
                    max(1, int(solver_cfg.nrhs)), profile)
            except KeyError:
                raise       # unknown variant/precond stays loud
            except Exception as e:                      # noqa: BLE001
                self._rec.note(f"cost_model unavailable: "
                               f"{type(e).__name__}: {e}")
            else:
                self._perf_shape = shape
                self._perf_profile = profile
                self._cost_model = cm
                self._cost_models_by_width[
                    max(1, int(solver_cfg.nrhs))] = cm
                try:
                    _perf.emit_cost_model(self._rec, cm)
                except Exception as e:                  # noqa: BLE001
                    self._rec.note(f"cost_model emission failed: "
                                   f"{type(e).__name__}: {e}")

        # In-graph convergence trace: ring length (0 = off) and its float
        # dtype — the dot dtype of whatever runs the Krylov iterations
        # (f32 for the mixed inner cycles, whose records are rescaled to
        # absolute residuals).
        self.trace_len = (clamp_trace_len(solver_cfg.trace_resid,
                                          solver_cfg.max_iter)
                          if solver_cfg.trace_resid > 0 else 0)
        self._trace_dtype = (jnp.float32 if self.mixed
                             else jnp.dtype(solver_cfg.dot_dtype))
        self.last_trace: Optional[ConvergenceTrace] = None
        trace_len = self.trace_len

        @scoped("solve/start")
        def _step(data, un_prev, delta):
            # Host-side trace counter: runs ONLY while jax traces this
            # function.  The warm-path contract — an AOT-cache hit
            # re-runs the step with ZERO tracing — is asserted against it
            # (tests/test_cache.py).
            self._rec.inc("trace.step")
            data64 = data["f64"] if self.mixed else data
            eff = data64["eff"]
            # Dirichlet lifting: Fext = F*delta - K.(Ud*delta)
            # (reference updateBC, pcg_solver.py:226-238)
            udi = data64["Ud"] * delta
            fdi = self.ops.matvec(data64, udi)
            fext = eff * (data64["F"] * delta - fdi)
            x0 = eff * un_prev
            trace0 = (trace_init(trace_len, self._trace_dtype)
                      if trace_len else None)
            if self.mixed:
                data32 = data["f32"]
                # preconditioner rebuild in f32 (pcg_solver.py:346-352)
                inv_diag32 = self._make_prec(self.ops32, data32)
                res = pcg_mixed(
                    self.ops32, data32, self.ops, data64,
                    fext, x0, inv_diag32,
                    tol=solver_cfg.tol, max_iter=solver_cfg.max_iter,
                    glob_n_dof_eff=glob_n_eff,
                    max_stag_steps=solver_cfg.max_stag_steps,
                    inner_tol=solver_cfg.inner_tol,
                    plateau_window=solver_cfg.mixed_plateau_window,
                    progress_window=solver_cfg.mixed_progress_window,
                    progress_ratio=solver_cfg.mixed_progress_ratio,
                    progress_min_gain=solver_cfg.mixed_progress_min_gain,
                    trace_in=trace0,
                    variant=solver_cfg.pcg_variant,
                )
            else:
                # preconditioner rebuild (pcg_solver.py:346-352)
                inv_diag = self._make_prec(self.ops, data64)
                res = pcg(
                    self.ops, data64, fext, x0, inv_diag,
                    tol=solver_cfg.tol, max_iter=solver_cfg.max_iter,
                    glob_n_dof_eff=glob_n_eff,
                    max_stag_steps=solver_cfg.max_stag_steps,
                    trace_in=trace0,
                    variant=solver_cfg.pcg_variant,
                )
            if trace_len:
                res, trace = res
            un = res.x + udi
            out = (un, res.flag, res.relres, res.iters)
            return out + ((trace,) if trace_len else ())

        R = self._rep_spec
        step_out = (self._part_spec, R, R, R) + (
            (trace_specs(R),) if trace_len else ())
        shard_step = jax.shard_map(
            _step,
            mesh=self.mesh,
            in_specs=(self._specs, self._part_spec, self._rep_spec),
            out_specs=step_out,
            check_vma=False,
        )
        # Donated previous-solution vector: the step's output un replaces
        # its input un_prev 1:1 (same shape/dtype/sharding), so XLA may
        # alias the buffers instead of copying.  The attribute rebinding
        # in step() is the only live reference either way.
        donate_step = (1,) if self._donate else ()
        self._step_fn = jax.jit(shard_step, donate_argnums=donate_step)
        # kept lowerable even when the AOT warm path replaces _step_fn
        # below: obs/profview.scope_map_from_solver re-lowers THIS to
        # read the compiled op_name metadata (named-scope -> phase map)
        self._step_fn_jit = self._step_fn

        # ---- dispatch-chunked solve path (large problems) -----------------
        # (solver/chunked.py; auto-engaged above ~4M dofs)
        from pcg_mpi_solver_tpu.solver.chunked import auto_dispatch_cap

        self._dispatch_cap = auto_dispatch_cap(
            solver_cfg, self.pm.glob_n_dof,
            self.pm.n_loc * (self.pm.n_parts // n_dev),
            force_engage=self.backend == "hybrid")
        # ---- resilience subsystem (resilience/): recovery ladder, mid-
        # Krylov snapshots, dispatch guard, deterministic fault injection.
        # All chunked-path-only; the one-shot path keeps its donated-carry
        # zero-state restore (step(), below).  `fault_plan` is settable
        # (tests inject programmatically; PCG_TPU_FAULTS drives chaos runs).
        self.fault_plan = FaultPlan.from_env(recorder=self._rec)
        self._resume_pending = False     # solve(resume=True) arms mid-step
        #                                  snapshot resume for its steps
        self._snap_store = None          # lazy: fingerprints the model once
        self._group_comm = None          # lazy: guarded multi-proc HostComm
        self._elastic_dir = None         # resume_elastic() arms the named
        #                                  n_procs-mismatch resume path
        self._many_progs = {}            # nrhs -> jitted blocked programs
        self._many_snap = {}             # nrhs -> blocked snapshot store
        self._restart_post_fn = None     # lazy: ladder restart program
        self._fallback_prec_fn = None    # lazy: scalar-Jacobi fallback
        self._esc_engine = None          # lazy: f64 escalation engine
        self._esc_prec_fn = None
        if self._dispatch_cap > 0:
            self._build_chunked(solver_cfg, glob_n_eff)
        elif self._cache_dir:
            # AOT warm path for the one-shot step program (the chunked
            # programs rely on the persistent XLA cache + warmup()): a
            # cache hit deserializes the exported StableHLO — zero
            # re-tracing of _step — and its compile hits <cache_dir>/xla.
            aot_step = self._build_aot_step(shard_step, donate_step)
            if aot_step is not None:
                self._step_fn = aot_step

        # Initial state: deterministic zeros (the reference seeds Un with
        # unseeded 1e-200*rand, pcg_solver.py:996 — an intentional
        # nondeterminism we do not reproduce).
        from pcg_mpi_solver_tpu.parallel.distributed import put_sharded

        self.un = put_sharded(
            np.zeros((self.pm.n_parts, self.pm.n_loc), dtype),
            self.mesh, self._part_spec)

        self._export_fn = None
        self._nu = float(model.mat_prop[0]["Pos"]) if model.mat_prop else 0.2
        self._model = model          # kept for host-side export paths (NS)
        self._nonlocal = None        # lazily built nonlocal weight operator

        # History records (reference TimeList_*, pcg_solver.py:163-165)
        self.flags: List[int] = []
        self.relres: List[float] = []
        self.iters: List[int] = []
        self.step_times: List[float] = []
        self._probe_u: List[np.ndarray] = []
        self._export_wall: float = 0.0
        # Steps timed in THIS process (not checkpoint-restored): the compile
        # estimate must compare a first step that actually paid the compile.
        self._proc_step_times: List[float] = []

        # Setup attribution (bench setup_s / warm-path triage): wall from
        # construction start to ready-to-step, plus whether the partition
        # came cold (built) or warm (cache).
        self.setup_s = time.perf_counter() - self._t_init0
        hits = self._rec.counters.get("cache.partition.hit", 0) \
            - self._cache_hm0[0]
        miss = self._rec.counters.get("cache.partition.miss", 0) \
            - self._cache_hm0[1]
        self.setup_cache = ("off" if not self._cache_dir
                            else "warm" if hits and not miss else "cold")
        self._rec.gauge("setup_s", round(self.setup_s, 3))
        self._rec.gauge("setup.cache", self.setup_cache)
        self._rec.gauge("setup.partition_build_s",
                        round(self.partition_build_s, 3))
        if self._setup_range is not None:
            # setup-phase shard attribution (ISSUE 14): which parts this
            # process built/loaded and whether the partition came warm —
            # the flight-recorder-grade record the setup ladder and the
            # sharded-warm-start tests read
            self._rec.event(
                "setup_shard", parts=list(self._setup_range),
                n_parts=int(self.pm.n_parts),
                cold=self.setup_cache != "warm",
                partition_build_s=round(self.partition_build_s, 6),
                setup_s=round(self.setup_s, 6))

    # ------------------------------------------------------------------
    def _exchange_export_glue(self, pm):
        """Sharded setup: allgather the per-part export-glue rows (owner
        weights + global id maps) so host-side global views
        (gather_owned_global, owner_mask, solve_many staging) keep their
        all-parts contract while everything heavy stays per-process.
        Each process contributes exactly its built slab; slabs tile
        [0, n_parts) by construction (local_part_range).  Packed into
        TWO collectives (one int64 buffer for ranges+id maps, one
        float64 for the weights) — each allgather costs a dispatch and
        a per-shape compile, and this runs on EVERY sharded
        construction, warm starts included."""
        from jax.experimental import multihost_utils as mh

        lo, hi = self._setup_range
        names = ("dof_gid", "node_gid", "weight", "node_weight")
        arrs = {n: np.asarray(getattr(pm, n)) for n in names
                if getattr(pm, n, None) is not None}
        ints = np.concatenate(
            [np.asarray([lo, hi], dtype=np.int64)]
            + [arrs[n][lo:hi].ravel().astype(np.int64)
               for n in ("dof_gid", "node_gid") if n in arrs])
        flts = np.concatenate(
            [arrs[n][lo:hi].ravel().astype(np.float64)
             for n in ("weight", "node_weight") if n in arrs]
            or [np.zeros(0)])
        # consensus-exempt: unconditional layout data exchange — every
        # engaged process reaches both gathers (engage is group-agreed)
        g_int = np.asarray(mh.process_allgather(ints))
        g_flt = np.asarray(mh.process_allgather(flts))
        for proc in range(g_int.shape[0]):
            l, h = int(g_int[proc, 0]), int(g_int[proc, 1])
            pos_i, pos_f = 2, 0
            for n in names:
                if n not in arrs:
                    continue
                full = arrs[n]
                rows = (h - l,) + full.shape[1:]
                cnt = int(np.prod(rows))
                if n in ("dof_gid", "node_gid"):
                    blk = g_int[proc, pos_i:pos_i + cnt]
                    pos_i += cnt
                else:
                    blk = g_flt[proc, pos_f:pos_f + cnt]
                    pos_f += cnt
                full[l:h] = blk.reshape(rows).astype(full.dtype)
        for n, full in arrs.items():
            setattr(pm, n, full)

    def _make_prec(self, ops, d):
        """Preconditioner inverse per config.solver.precond: scalar Jacobi
        (P, n_loc), 3x3 node-block Jacobi (P, n_node_loc, 3, 3), or the
        mg V-cycle prec dict; any of them feeds ops.apply_prec inside
        the PCG body."""
        from pcg_mpi_solver_tpu.ops.precond import make_prec

        return make_prec(ops, d, self.config.solver.precond)

    def _build_mg_cached(self, model, scfg):
        """Host MG hierarchy build, served from the SHARD-ADDRESSED
        partition cache when a cache dir is set (ISSUE 14): the
        replicated coarse hierarchy + meta live in one glue entry, the
        parts-sharded fine transfer arrays in per-part entries — a warm
        start (or an N-host warm start, each host its own parts) skips
        the whole host-side rediscretization.  The structural knobs
        (levels/degree/replication cutoff) key every entry."""
        from pcg_mpi_solver_tpu.ops import mg as mgmod

        def build():
            return mgmod.build_mg_host(
                model, self.pm,
                n_levels=int(scfg.mg_levels),
                degree=int(scfg.mg_smooth_degree),
                max_replicated_dofs=int(scfg.mg_max_replicated_dofs))

        if not self._cache_dir:
            return build()
        from pcg_mpi_solver_tpu.cache import keys as ckeys
        from pcg_mpi_solver_tpu.cache.partition_cache import (
            cached_partition_shards)
        from pcg_mpi_solver_tpu.cache.shards import join_mg, split_mg

        rng = self._setup_range or (0, int(self.pm.n_parts))
        key_kw = dict(
            n_parts=int(self.pm.n_parts), backend=f"mg-{self.backend}",
            dtype=str(np.dtype(self.dtype)),
            extra={"levels": int(scfg.mg_levels),
                   "degree": int(scfg.mg_smooth_degree),
                   "max_replicated_dofs":
                       int(scfg.mg_max_replicated_dofs),
                   # the fine transfers are laid out in the PARTITION's
                   # node order — hierarchies built against different
                   # partitions of the same model must never collide
                   "partition": getattr(self, "_partition_cache_id",
                                        None)})
        part_keys = {p: ckeys.partition_shard_key(
            self._model_fp, part_idx=p, **key_kw)
            for p in range(rng[0], rng[1])}
        return cached_partition_shards(
            self._cache_dir,
            glue_key=ckeys.partition_glue_key(self._model_fp, **key_kw),
            part_keys=part_keys, builder=build,
            split=lambda s: split_mg(s, rng), join=join_mg,
            comm=self._setup_comm, recorder=self._rec, label="mg")

    def _prec_operand_spec(self):
        """shard_map PartitionSpec (pytree) of the preconditioner
        operand the chunked programs thread: the part spec for the array
        inverses, the {mg_diag: parts, fb: replicated} dict for mg."""
        if self.config.solver.precond == "mg":
            return {"mg_diag": self._part_spec, "fb": self._rep_spec}
        return self._part_spec

    def _finish_mg_setup(self, scfg):
        """Post-upload half of the MG setup: estimate the fine-level
        Chebyshev bound via a few power-iteration matvecs on the REAL
        partitioned operator (ops/mg.estimate_fine_lam; served from the
        partition cache on warm runs), then install the per-level lambda
        vector + emit the ``mg_setup`` telemetry and degenerate-interval
        warning through the shared ``mg.install_lam_and_report``."""
        from pcg_mpi_solver_tpu.ops import mg as mgmod

        setup, t_build = self._mg_setup
        data64 = self.data["f64"] if self.mixed else self.data
        specs64 = self._specs["f64"] if self.mixed else self._specs
        t0 = time.perf_counter()
        cached = False
        if self._cache_dir:
            from pcg_mpi_solver_tpu.cache import keys as ckeys
            from pcg_mpi_solver_tpu.cache.partition_cache import (
                cached_partition)

            key = ckeys.partition_cache_key(
                self._model_fp, n_parts=int(self.pm.n_parts),
                backend=f"mglam-{self.backend}",
                dtype=str(np.dtype(self.dtype)),
                extra=dict(setup.meta, iters=mgmod.MG_POWER_ITERS))
            hit0 = self._rec.counters.get("cache.partition.hit", 0)
            entry = cached_partition(
                self._cache_dir, key,
                lambda: {"lam": mgmod.estimate_fine_lam(
                    self.ops, data64, self.mesh, specs64,
                    self._part_spec)},
                recorder=self._rec, label="mg_lam")
            cached = self._rec.counters.get("cache.partition.hit",
                                            0) > hit0
            lam_fine = float(entry["lam"])
        else:
            with self._rec.span("mg_lam"):
                lam_fine = mgmod.estimate_fine_lam(
                    self.ops, data64, self.mesh, specs64,
                    self._part_spec)
        trees = ([self.data["f64"], self.data["f32"]] if self.mixed
                 else [self.data])
        mgmod.install_lam_and_report(
            setup, lam_fine, trees=trees, mesh=self.mesh,
            rep_spec=self._rep_spec, recorder=self._rec,
            wall_s=t_build + time.perf_counter() - t0, cached=cached)

    # ------------------------------------------------------------------
    # Warm-path subsystem (cache/): partition cache, AOT step, warmup
    # ------------------------------------------------------------------
    def _partition_cached(self, backend_label, builder, *, n_parts,
                          method="n/a", elem_part=None, extra=None,
                          shard=False):
        """Serve a partition from the content-addressed cache (cache/),
        falling through to ``builder`` on a miss.  The key covers the
        model content (fingerprint), n_parts, backend, dtype, the
        partition method (resolving 'auto' to whether the native graph
        partitioner is actually available), an explicit elem_part array's
        hash, and backend-specific layout knobs — plus the cache schema
        and package version (cache/keys.py), so a code bump invalidates
        rather than deserializing stale layouts.

        ``shard=True`` (the general/structured backends, ISSUE 14) routes
        through the SHARD-ADDRESSED store: per-part entries + one glue
        entry, so on a warm start each process reads only its own parts'
        entries; the monolithic key stays as the legacy-entry shim.
        ``builder`` then takes ``part_range=`` (None = full build).
        Cold builds are timed into ``self.partition_build_s`` under the
        ``partition_build`` span — the setup ladder's attribution."""
        part_range = self._setup_range if shard else None

        def timed_build(part_range=part_range):
            t0 = time.perf_counter()
            with self._rec.span("partition_build"):
                pm = builder(part_range=part_range) if shard else builder()
            self.partition_build_s += time.perf_counter() - t0
            return pm

        if not self._cache_dir:
            return timed_build()
        from pcg_mpi_solver_tpu.cache import keys as ckeys
        from pcg_mpi_solver_tpu.cache.partition_cache import (
            cached_partition, cached_partition_shards)

        extra = dict(extra or {})
        if method == "auto" and elem_part is None:
            # 'auto' resolves to graph-or-RCB by native availability —
            # the resolved choice must key the entry, not the knob.
            from pcg_mpi_solver_tpu import native

            extra["native"] = bool(native.available())
        key_kw = dict(
            n_parts=int(n_parts), backend=backend_label,
            dtype=str(np.dtype(self.dtype)), method=method,
            elem_part_hash=(ckeys.array_hash(elem_part)
                            if elem_part is not None else None),
            extra=extra)
        legacy_key = ckeys.partition_cache_key(self._model_fp, **key_kw)
        # partition identity for DERIVED per-shard entries (the MG
        # hierarchy): its fine-transfer arrays are laid out in THIS
        # partition's node order, so anything cached against it must
        # re-key when the partition does (method/elem_part/knobs)
        self._partition_cache_id = legacy_key
        if not shard:
            return cached_partition(self._cache_dir, legacy_key,
                                    timed_build, recorder=self._rec,
                                    label=backend_label)
        from pcg_mpi_solver_tpu.cache.shards import (
            join_partition, split_partition)

        lo, hi = part_range if part_range is not None else (0, n_parts)
        part_keys = {p: ckeys.partition_shard_key(
            self._model_fp, part_idx=p, **key_kw) for p in range(lo, hi)}
        return cached_partition_shards(
            self._cache_dir,
            glue_key=ckeys.partition_glue_key(self._model_fp, **key_kw),
            part_keys=part_keys, builder=timed_build,
            split=split_partition, join=join_partition,
            legacy_key=legacy_key, comm=self._setup_comm,
            recorder=self._rec, label=backend_label)

    def _build_aot_step(self, shard_step, donate_step):
        """AOT-export path for the one-shot step program: deserialize the
        exported StableHLO for this abstract signature (warm — zero
        tracing of ``_step``) or export + persist it (cold — the one
        trace every warm run skips).  Returns the dispatchable jit of
        ``exported.call`` (which re-applies carry donation), or None when
        export is unsupported — the caller keeps the plain jit."""
        import dataclasses as _dc

        from pcg_mpi_solver_tpu.cache import aot
        from pcg_mpi_solver_tpu.cache.keys import step_cache_key

        data_abs = aot.abstract_like(self.data)
        psh = jax.sharding.NamedSharding(self.mesh, self._part_spec)
        rsh = jax.sharding.NamedSharding(self.mesh, self._rep_spec)
        un_abs = jax.ShapeDtypeStruct(
            (self.pm.n_parts, self.pm.n_loc), self.dtype, sharding=psh)
        delta_abs = jax.ShapeDtypeStruct((), self.dtype, sharding=rsh)
        abstract_args = (data_abs, un_abs, delta_abs)
        key = step_cache_key(
            abstract=aot.signature_repr(abstract_args),
            mesh=(sorted(self.mesh.shape.items()),
                  self.mesh.devices.flat[0].platform),
            backend=self.backend,
            # every SolverConfig scalar is baked into the traced program
            solver=_dc.asdict(self.config.solver),
            # also STRUCTURAL key components (cache/keys.py): the
            # variant reshapes the loop body and the carry pytree, the
            # precond reshapes the body's preconditioner apply (the mg
            # V-cycle), so those programs must never collide even if
            # the solver dict's serialization ever changes
            pcg_variant=self.config.solver.pcg_variant,
            precond=self.config.solver.precond,
            trace_len=self.trace_len,
            glob_n_dof_eff=int(self.pm.glob_n_dof_eff),
            donate=bool(donate_step),
            jax_version=jax.__version__,
            # MG-shape components (level count / smoothing degree /
            # lattice dims): they shape the traced V-cycle beyond what
            # the solver dict records
            extra={"mg": self._mg_meta,
                   "x64": bool(jax.config.jax_enable_x64)})
        exported = aot.cached_step(
            self._cache_dir, key, jax.jit(shard_step), abstract_args,
            recorder=self._rec)
        if exported is None:
            return None
        return jax.jit(exported.call, donate_argnums=donate_step)

    def warmup(self):
        """Compile the engaged solve path WITHOUT running a solve, so a
        later hardware window pays no setup: populates the AOT step cache
        and the persistent XLA compilation cache (both live under
        ``config.cache_dir`` when set — warmup works without it too, but
        then only this process benefits).  One-shot path: AOT
        lower+compile, zero execution.  Chunked path: the start programs
        execute once and each budget-loop program runs a single capped
        Krylov iteration (ChunkedEngine.warmup) — negligible runtime next
        to the minutes-scale compiles this front-loads.  ``self.un`` and
        all solve history are untouched.  CLI: ``pcg-tpu warmup``."""
        delta = jnp.asarray(1.0, self.dtype)
        with self._rec.span("warmup", emit=True):
            if self._dispatch_cap > 0:
                # same dispatch name as _step_chunked: warmup pays the
                # compile, so the real solve's start books warm
                with self._rec.dispatch("start"):
                    udi = self._start_pre_fn(self.data, delta)
                    kudi = self._amul64_fn(self.data, udi)
                    fext, x0 = self._start_mid_fn(self.data, self.un,
                                                  delta, kudi)
                    kx0 = self._amul64_fn(self.data, x0)
                    carry, normr0, n2b, prec = self._start_post_fn(
                        self.data, fext, x0, kx0)
                    jax.block_until_ready(n2b)
                # consumes carry (donated); all outputs are throwaway
                self._engine.warmup(self.data, fext, carry, normr0, n2b,
                                    prec)
                jax.block_until_ready(self._finish_fn(
                    jnp.zeros_like(udi), udi))
            else:
                self._step_fn.lower(self.data, self.un, delta).compile()
        self._rec.note("warmup complete (programs compiled, caches "
                       "populated)")

    # ------------------------------------------------------------------
    def _build_chunked(self, scfg, glob_n_eff):
        """Jitted start step + the shared ChunkedEngine (see __init__)."""
        mixed = self.mixed

        from pcg_mpi_solver_tpu.solver.chunked import ChunkedEngine
        from pcg_mpi_solver_tpu.solver.pcg import carry_part_specs, cold_carry

        P, R = self._part_spec, self._rep_spec
        # Direct mode threads the convergence ring through the dispatch
        # carry built here; in mixed mode the engine owns the ring (it
        # rides the f32 inner carries instead).  The recurrence variants
        # add their extra leaves to the carry schema (pcg.cold_carry).
        variant = scfg.pcg_variant
        trace_direct = self.trace_len > 0 and not mixed
        carry_specs = carry_part_specs(P, R, trace=trace_direct,
                                       variant=variant)

        # The ONE program holding the out-of-loop f64 stencil: Dirichlet
        # lifting, r0, and every refinement's true-residual matvec all
        # dispatch through it.  At octree-flagship scale each stencil
        # INSTANTIATION costs minutes of compile — the old single _start
        # program alone instantiated it twice.  The cost is a couple of
        # unfused vector round-trips per STEP/cycle (micro-ms at 10M
        # dofs), not per iteration.
        if self._refresh64 is not None:
            # PCG_TPU_HYBRID_F64_REFRESH=general: same contract
            # ((data, v) -> eff * K.v in f64), different operator
            # formulation — element gather/scatter over the full general
            # partition (identical dof layout; asserted at build).  The
            # passed-in data tree is ignored in favor of the refresh
            # tree; callers keep one signature either way.
            rops, rdev, rspecs = self._refresh64
            if self.f64_refresh == "bucketed":
                from pcg_mpi_solver_tpu.ops.matvec import bucketed_matvec

                @scoped("solve/residual64")
                def _amul64g(rd, v):
                    return rd["eff"] * bucketed_matvec(rops, rd, v)
            else:
                @scoped("solve/residual64")
                def _amul64g(rd, v):
                    return rd["eff"] * rops.matvec(rd, v)

            amul64g_jit = jax.jit(jax.shard_map(
                _amul64g, mesh=self.mesh, in_specs=(rspecs, P),
                out_specs=P, check_vma=False))
            self._amul64_fn = lambda data, v: amul64g_jit(rdev, v)
        else:
            @scoped("solve/residual64")
            def _amul64(data, v):
                d = data["f64"] if mixed else data
                return d["eff"] * self.ops.matvec(d, v)

            self._amul64_fn = jax.jit(jax.shard_map(
                _amul64, mesh=self.mesh, in_specs=(self._specs, P),
                out_specs=P, check_vma=False))

        @scoped("solve/start")
        def _start_pre(data, delta):
            data64 = data["f64"] if mixed else data
            return data64["Ud"] * delta

        self._start_pre_fn = jax.jit(jax.shard_map(
            _start_pre, mesh=self.mesh, in_specs=(self._specs, R),
            out_specs=P, check_vma=False))

        @scoped("solve/start")
        def _start_mid(data, un_prev, delta, kudi):
            data64 = data["f64"] if mixed else data
            eff = data64["eff"]
            # eff is idempotent: eff*(F*delta - K.udi) == eff*F*delta - kudi
            fext = eff * data64["F"] * delta - kudi
            x0 = eff * un_prev
            return fext, x0

        self._start_mid_fn = jax.jit(jax.shard_map(
            _start_mid, mesh=self.mesh, in_specs=(self._specs, P, R, P),
            out_specs=(P, P), check_vma=False))

        @scoped("solve/start")
        def _start_post(data, fext, x0, kx0):
            data64 = data["f64"] if mixed else data
            w = data64["weight"] * data64["eff"]
            r0 = fext - kx0
            n2b = jnp.sqrt(self.ops.wdot(w, fext, fext))
            normr0 = jnp.sqrt(self.ops.wdot(w, r0, r0))
            carry0 = cold_carry(
                x0, r0, normr0, self.ops.dot_dtype,
                trace=(trace_init(self.trace_len, self._trace_dtype)
                       if trace_direct else None),
                variant=variant)
            # preconditioner rebuild once per step (not per dispatch /
            # refinement cycle): f32 for the mixed inner solves.
            if mixed:
                prec = self._make_prec(self.ops32, data["f32"])
            else:
                prec = self._make_prec(self.ops, data64)
            return carry0, normr0, n2b, prec

        prec_spec = self._prec_operand_spec()
        self._start_post_fn = jax.jit(jax.shard_map(
            _start_post, mesh=self.mesh,
            in_specs=(self._specs, P, P, P),
            out_specs=(carry_specs, R, R, prec_spec), check_vma=False))

        self._engine = ChunkedEngine(
            mesh=self.mesh, data_specs=self._specs, part_spec=P,
            rep_spec=R, ops=self.ops, scfg=scfg,
            glob_n_dof_eff=glob_n_eff, cap=self._dispatch_cap,
            mixed=mixed, ops32=self.ops32 if mixed else None,
            amul_fn=self._amul64_fn, trace_len=self.trace_len,
            recorder=self._rec, donate=self._donate,
            prec_spec=prec_spec)
        self._finish_fn = jax.jit(
            scoped("solve/start")(lambda x, udi: x + udi))

    def _step_chunked(self, delta):
        """Host-driven solve: repeated capped-iteration dispatches.

        Semantics match the one-shot path (same fext/lifting, same inner
        PCG); the resumable carry makes direct-mode dispatches iteration-
        for-iteration identical to one long solve, and chunk boundaries
        align with refinement cycles in mixed mode.

        The recovery orchestration (breakdown ladder, device-loss
        restart) is the shared :func:`resilience.engine.run_with_recovery`
        harness — this method supplies the recovery PROGRAMS (restart
        carry through the shared out-of-loop amul, fallback
        preconditioner, f64 escalation engine, cold-start rebuild) as
        :class:`~pcg_mpi_solver_tpu.resilience.engine.RecoveryHooks`."""
        from pcg_mpi_solver_tpu.resilience.engine import (
            RecoveryHooks, run_with_recovery)

        rec = self._rec
        scfg = self.config.solver
        rec.note("start dispatch (lifting + r0; first call pays compile)")
        delta_dev = jnp.asarray(delta, self.dtype)
        with rec.dispatch("start"):
            udi = self._start_pre_fn(self.data, delta_dev)
            kudi = self._amul64_fn(self.data, udi)
            fext, x0 = self._start_mid_fn(self.data, self.un, delta_dev,
                                          kudi)
            kx0 = self._amul64_fn(self.data, x0)
            carry, normr0, n2b, prec = self._start_post_fn(
                self.data, fext, x0, kx0)
            n2b_f = float(n2b)
        rec.note(f"start_fn done; ||b||={n2b_f:.3e}")
        if n2b_f == 0.0:
            self.un = self._finish_fn(jnp.zeros_like(carry["x"]), udi)
            self.last_trace = empty_trace() if self.trace_len else None
            return 0, 0.0, 0
        ctx = self._make_resilience()

        def _restart(x):
            # min-residual-iterate restart: a cold Krylov carry at the
            # best iterate seen, through the SHARED out-of-loop amul
            # program (no extra stencil instantiation)
            with rec.dispatch("restart"):
                kx = self._amul64_fn(self.data, x)
                c, nr = self._restart_post()(self.data, fext, x, kx)
                jax.block_until_ready(nr)
            return c, nr

        def _cold_restart():
            # device loss: rebuild the step's cold start state (fext/x0/
            # kx0 are intact: the start programs never donate their
            # operands)
            with rec.dispatch("start"):
                c, nr, _n2b, prec0 = self._start_post_fn(
                    self.data, fext, x0, kx0)
            return c, nr, prec0

        engine, x_fin, flag, relres, total = run_with_recovery(
            self._engine, self.data, fext, carry, normr0, n2b, prec,
            scfg=scfg, mixed=self.mixed, recorder=rec,
            hooks=RecoveryHooks(restart=_restart,
                                cold_restart=_cold_restart,
                                fallback_prec=self._fallback_prec,
                                escalation=self._escalation),
            resilience=ctx)
        if self.trace_len:
            tr = engine.last_trace
            self.last_trace = (unpack_trace(tr) if tr is not None
                               else empty_trace())
        if ctx is not None:
            ctx.discard()               # the step is complete: its mid-
            #                             Krylov snapshot must not outlive it
        self.un = self._finish_fn(x_fin, udi)
        return flag, relres, total

    # ------------------------------------------------------------------
    # Resilience subsystem (resilience/): context + recovery programs
    # ------------------------------------------------------------------
    def _collective_comm(self):
        """Host-collective group for the dispatch path
        (resilience/distributed.GuardedComm), cached; None
        single-process.  Every multi-process run gets a REAL group: the
        consensus agreements (snapshot commit/resume epoch, recovery
        ladder, engage) are correctness-critical regardless of
        configuration, so they must never silently degrade to local
        verdicts.  Only the deadline WATCHDOG stays opt-in
        (PCG_TPU_COLLECTIVE_DEADLINE_S — a watchdog thread per
        collective is pure overhead on a healthy fleet); with no
        deadline armed the wrapper runs collectives inline but still
        classifies transport death as DeadPeerError."""
        if jax.process_count() <= 1:
            return None
        if self._group_comm is None:
            from pcg_mpi_solver_tpu.parallel.distributed import HostComm
            from pcg_mpi_solver_tpu.resilience.distributed import (
                GuardedComm, collective_deadline_s)

            self._group_comm = GuardedComm(
                self._setup_comm or HostComm(),
                deadline_s=collective_deadline_s(),
                recorder=self._rec, index=jax.process_index())
        return self._group_comm

    def _snapshot_store(self):
        """Per-step mid-Krylov snapshot store (lazy).  Multi-process —
        or an armed elastic resume reading a multi-process epoch — gets
        the group-consistent epoch store (two-phase commit markers,
        resilience/distributed.GroupSnapshotStore); single-process keeps
        the plain per-file SnapshotStore."""
        if self._snap_store is None:
            if jax.process_count() > 1 or self._elastic_dir is not None:
                from pcg_mpi_solver_tpu.resilience.distributed import (
                    GroupSnapshotStore)

                self._snap_store = GroupSnapshotStore.for_solver(
                    self, comm=self._collective_comm(),
                    recorder=self._rec,
                    elastic=self._elastic_dir is not None)
                if self._elastic_dir is not None:
                    # re-point at the dead fleet's directory and rescan:
                    # continuation epochs must number past the ones
                    # already committed there, not restart at 0
                    self._snap_store.path = self._elastic_dir
                    self._snap_store._epoch = \
                        self._snap_store._scan_next_epoch()
            else:
                from pcg_mpi_solver_tpu.utils.checkpoint import SnapshotStore

                self._snap_store = SnapshotStore.for_solver(self)
        return self._snap_store

    def _make_resilience(self):
        """Per-step resilience context for the chunked budget loop, or
        None when the subsystem is fully disabled (no ladder budget, no
        snapshot cadence, no fault plan, no collective deadline)."""
        scfg = self.config.solver
        every = int(getattr(self.config, "snapshot_every", 0))
        plan = self.fault_plan
        comm = self._collective_comm()
        if (scfg.max_recoveries <= 0 and every <= 0 and plan is None
                and comm is None):
            return None
        from pcg_mpi_solver_tpu.resilience.recovery import (
            DispatchGuard, ResilienceContext)

        store = self._snapshot_store() if every > 0 else None
        from pcg_mpi_solver_tpu.resilience.recovery import retry_deadline_s

        return ResilienceContext(
            store=store, step=len(self.flags) + 1, snapshot_every=every,
            fetch_state=self._fetch_state, put_state=self._put_state,
            guard=DispatchGuard(retries=scfg.dispatch_retries,
                                deadline_s=retry_deadline_s(),
                                recorder=self._rec),
            faults=plan, recorder=self._rec, resume=self._resume_pending,
            ladder_armed=scfg.max_recoveries > 0, comm=comm)

    def _fetch_state(self, state):
        """Device state pytree -> host numpy (collective on multi-host:
        every process participates in the vector all-gathers; only the
        primary later writes)."""
        from pcg_mpi_solver_tpu.parallel.distributed import fetch_global

        def rec(node):
            if isinstance(node, dict):
                return {k: rec(v) for k, v in node.items()}
            if isinstance(node, (int, float, bool, str)):
                return node
            return fetch_global(node, self.mesh)

        return rec(state)

    def _put_state(self, state):
        """Host numpy state pytree -> device, sharding-faithful: leading-
        axis-(n_parts) arrays go back parts-sharded, everything else
        replicated; non-numeric leaves (the ``kind`` tag) pass through."""
        from pcg_mpi_solver_tpu.parallel.distributed import put_sharded

        n_parts = self.pm.n_parts

        def rec(node):
            if isinstance(node, dict):
                return {k: rec(v) for k, v in node.items()}
            a = np.asarray(node)
            if a.dtype.kind in "OUS":
                return node
            spec = (self._part_spec
                    if a.ndim >= 2 and a.shape[0] == n_parts
                    else self._rep_spec)
            return put_sharded(a, self.mesh, spec)

        return rec(state)

    def _restart_post(self):
        """Lazily-built ladder restart program: ``(data, fext, x, kx) ->
        (cold carry at x, ||r||)`` with ``r = fext - kx`` — the kx matvec
        goes through the shared ``_amul64_fn``, so the restart costs no
        extra stencil instantiation and compiles only if a recovery ever
        fires.  Direct mode with tracing gets a FRESH ring (the poisoned
        solve's partial ring is superseded, not resumed)."""
        if self._restart_post_fn is None:
            from pcg_mpi_solver_tpu.solver.pcg import (
                carry_part_specs, cold_carry)

            mixed = self.mixed
            variant = self.config.solver.pcg_variant
            trace_direct = self.trace_len > 0 and not mixed
            P, R = self._part_spec, self._rep_spec
            carry_specs = carry_part_specs(P, R, trace=trace_direct,
                                           variant=variant)
            trace_len, trace_dtype = self.trace_len, self._trace_dtype

            @scoped("solve/start")
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
                _restart, mesh=self.mesh,
                in_specs=(self._specs, self._part_spec, self._part_spec,
                          self._part_spec),
                out_specs=(carry_specs, R), check_vma=False))
        return self._restart_post_fn

    def _fallback_prec(self):
        """Scalar-Jacobi fallback preconditioner inverse (ladder rung 2):
        weaker than block3/mg but its inverse is finite wherever the
        assembled diagonal is nonzero, so it cannot re-introduce the Inf
        a near-singular 3x3 block inverse produced — nor depend on an mg
        hierarchy that may itself be the broken ingredient.  Under
        precond='mg' the fallback keeps the mg PREC-OPERAND SHAPE with
        the ``fb`` demotion switch set (the compiled cycle's apply then
        takes the plain scalar-Jacobi branch — ops/mg.mg_apply — so a
        broken hierarchy DEGRADES without recompiling anything).
        Built/compiled only when the rung actually fires."""
        from pcg_mpi_solver_tpu.ops.precond import make_prec

        if self._fallback_prec_fn is None:
            mixed = self.mixed
            mg = self.config.solver.precond == "mg"

            @scoped("solve/start")
            def _fb(data):
                if mixed:
                    inv = make_prec(self.ops32, data["f32"], "jacobi")
                else:
                    inv = make_prec(self.ops, data, "jacobi")
                if mg:
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
        """f64 escalation (ladder rung 3, mixed mode): finish the solve
        with direct f64 Krylov cycles on the existing f64 ops/data — a
        second ChunkedEngine built lazily, so the extra compile is paid
        only when mixed-precision iteration itself is what keeps breaking
        (the classic case: an f32 preconditioner Inf that the f64
        assembly does not reproduce).  Returns (engine, data, prec)."""
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

            @scoped("solve/start")
            def _p64(data):
                # scalar Jacobi: the escalation rung sits after the
                # fallback-prec rung, so the safest inverse is the point
                return make_prec(self.ops, data, "jacobi")

            self._esc_prec_fn = jax.jit(jax.shard_map(
                _p64, mesh=self.mesh, in_specs=(specs64,),
                out_specs=self._part_spec, check_vma=False))
        with self._rec.dispatch("esc_prec"):
            prec = self._esc_prec_fn(self.data["f64"])
            jax.block_until_ready(prec)
        return self._esc_engine, self.data["f64"], prec

    def reset_state(self):
        """Zero the solution, preserving its device sharding (avoids a
        silent retrace on the next step)."""
        from pcg_mpi_solver_tpu.parallel.distributed import put_sharded

        with self._rec.span("reset"):
            self.un = put_sharded(
                np.zeros((self.pm.n_parts, self.pm.n_loc), self.dtype),
                self.mesh, self._part_spec)

    # ------------------------------------------------------------------
    # Batched multi-RHS solves (ISSUE 6): many load cases, ONE operator
    # ------------------------------------------------------------------
    def predicted_ms_per_iter(self, nrhs: int = 1) -> Optional[float]:
        """Cost-model-predicted ms/iter at block width ``nrhs`` — the
        serve/ admission-pricing hook (ISSUE 19).  None when the model
        degraded at construction (exotic platform / shape derivation
        failure): callers must treat None as "cannot price", never as
        zero.  Models are cached per width (one table walk each, all
        pure host arithmetic); an unknown variant/precond stays a loud
        KeyError (the single-source-table contract)."""
        if self._perf_shape is None:
            return None
        nrhs = max(1, int(nrhs))
        cm = self._cost_models_by_width.get(nrhs)
        if cm is None:
            from pcg_mpi_solver_tpu.obs import perf as _perf

            scfg = self.config.solver
            cm = _perf.cost_model(
                self._perf_shape, scfg.pcg_variant, scfg.precond,
                nrhs, self._perf_profile)
            self._cost_models_by_width[nrhs] = cm
        return float(cm["predicted_ms_per_iter"])

    def solve_many(self, fexts, resume: bool = False) -> ManySolveResult:
        """Solve ``K.x_j = fext_j`` for a BLOCK of load cases against the
        one shared partitioned operator — the multi-tenant solve path.

        ``fexts``: global load vectors as an (n_dof, nrhs) array (one
        column per load case; a list of (n_dof,) vectors or a single
        vector also work).  Homogeneous Dirichlet: loads act on the
        effective dofs, constrained dofs solve to 0 (lift prescribed
        displacements into the load columns yourself if needed).

        The block rides one lockstep Krylov loop (solver/pcg.pcg_many —
        per-RHS convergence mask, frozen converged columns, per-column
        flag taxonomy) with the per-type element matmul batched over the
        block and a per-iteration collective count INDEPENDENT of nrhs.
        Reuses every warm-path asset this solver already owns: the
        cached partition, the preconditioner build, and (one-shot path
        with ``cache_dir``) an AOT-exported blocked program keyed by
        nrhs — repeated blocks of the same width do zero partition
        builds and zero step re-traces.  Each request block is validated
        per column first (validate.check_rhs_block — the offending
        column index is named, the PR-4 preflight already vetted the
        model at construction).

        Direct-precision solves above the dispatch cap run as capped
        resumable dispatches with optional mid-solve snapshots
        (``config.snapshot_every`` chunk boundaries, ``many_*.npz``) and
        ``resume=True`` continues a killed blocked solve bit-identically;
        a resume against a different block width fails as a clear
        fingerprint mismatch.  Mixed-precision blocks run as one
        dispatch (the refinement loop is in-graph).

        Returns :class:`ManySolveResult` (per-RHS flags/relres/iters +
        the device-blocked solution)."""
        from pcg_mpi_solver_tpu.validate import PreflightError, check_rhs_block

        t0 = time.perf_counter()
        rdt = np.dtype(np.float64 if self.mixed else self.dtype)
        with self._rec.span("many.stage"):
            fb = normalize_rhs_block(fexts, self._model.n_dof, rdt)
            checks = check_rhs_block(fb, self._model.n_dof)
            bad = [c for c in checks if c.status == "fail"]
            if bad:
                raise PreflightError(
                    "solve_many rejected the rhs block: " + "; ".join(
                        f"[{c.name}] {c.detail}" for c in bad))
            R = fb.shape[1]
            self._rec.gauge("many.nrhs", R)

            # global columns -> part-local blocked (n_parts, n_loc,
            # nrhs); shared interface dofs replicate their value on
            # every part that carries them (the assembled-operator
            # convention), padded local slots (dof_gid < 0) read 0
            from pcg_mpi_solver_tpu.parallel.distributed import put_sharded

            gid = np.asarray(self.pm.dof_gid)
            loc = fb[np.clip(gid, 0, None), :] * (gid >= 0)[..., None]
            fb_dev = put_sharded(np.ascontiguousarray(loc, dtype=rdt),
                                 self.mesh, self._part_spec)
            jax.block_until_ready(fb_dev)

        progs = self._ensure_many_programs(R)
        t_solve0 = time.perf_counter()      # staging done: Krylov wall
        quarantined, recoveries, drift = (), 0, 0
        if "solve" in progs:
            if resume or int(getattr(self.config, "snapshot_every", 0)) > 0:
                # the one-shot blocked path (mixed precision, or below
                # the dispatch cap) has no chunk boundaries to snapshot
                # at — say so instead of silently ignoring the request
                self._rec.note(
                    "solve_many: snapshot/resume requested but this "
                    "blocked solve runs as ONE dispatch (mixed "
                    "precision, or below the dispatch cap) — no "
                    "mid-solve snapshots exist on this path")

            def _one_shot():
                x, flags, relres, iters, lock = progs["solve"](self.data,
                                                               fb_dev)
                # blocking fetches INSIDE the retry guard: a dispatch
                # that dies mid-execution must count as a failed attempt
                return (x, np.asarray(flags),
                        np.asarray(relres, dtype=np.float64),
                        np.asarray(iters), int(lock))

            # retry-guarded one-shot dispatch: the blocked program
            # donates nothing, so a device-loss failure re-dispatches
            # the identical stateless program instead of failing the
            # whole block request
            x, flags, relres, iters, lockstep = self._dispatch_with_retry(
                "solve_many", _one_shot)
            # one-shot quarantine semantics (recovery-exempt from the
            # ladder: a single stateless dispatch has no resumable carry
            # to restart columns from — the in-graph finalize already
            # handed failed columns their min-residual iterate): flag
            # 2/4/6 breakdowns, in-graph flag-5 poison, and any residual
            # non-finiteness report as quarantined columns + telemetry
            from pcg_mpi_solver_tpu.solver.pcg import (
                BREAKDOWN_FLAGS, QUARANTINE_FLAG)

            quar = (np.isin(flags, BREAKDOWN_FLAGS + (QUARANTINE_FLAG,))
                    | ~np.isfinite(relres))
            if quar.any():
                for j in np.flatnonzero(quar):
                    trig = ("nan_carry" if not np.isfinite(relres[j])
                            or int(flags[j]) == QUARANTINE_FLAG
                            else f"flag{int(flags[j])}")
                    self._rec.event("rhs_quarantine", rhs=int(j),
                                    trigger=trig,
                                    flag=QUARANTINE_FLAG, attempts=0)
                    self._rec.inc("resilience.rhs_quarantine")
                flags = np.where(quar, QUARANTINE_FLAG, flags)
                quarantined = tuple(int(j) for j in np.flatnonzero(quar))
        else:
            rhs_hash = ""
            if resume or int(getattr(self.config, "snapshot_every", 0)) > 0:
                # the hash exists only to fingerprint snapshots — never
                # scan the (potentially GB-scale) block when neither
                # snapshots nor resume can use it
                from pcg_mpi_solver_tpu.cache.keys import array_hash

                rhs_hash = array_hash(fb)
            (x, flags, relres, iters, quarantined, recoveries,
             drift, lockstep) = self._solve_many_chunked(
                fb_dev, R, progs, resume, rhs_hash=rhs_hash)
        wall = time.perf_counter() - t0
        res = ManySolveResult(flags=flags, relres=relres, iters=iters,
                              wall_s=wall, x=x,
                              solve_wall_s=time.perf_counter() - t_solve0,
                              quarantined=tuple(quarantined),
                              recoveries=int(recoveries),
                              drift=int(drift), lockstep_iters=lockstep)
        self._rec.inc("many.lockstep_iters", lockstep)
        if lockstep:
            self._rec.gauge("many.live_lane_frac",
                            float(np.sum(iters)) / (R * lockstep))
        self._rec.event("solve_many", nrhs=R, wall_s=round(wall, 6),
                        flags=[int(f) for f in flags],
                        iters_max=int(iters.max()) if R else 0,
                        quarantined=[int(j) for j in res.quarantined],
                        recoveries=int(recoveries))
        for j in range(R):
            # per-RHS telemetry: one event per tenant/load case
            self._rec.event("rhs_solve", rhs=j, flag=int(flags[j]),
                            relres=float(relres[j]), iters=int(iters[j]),
                            quarantined=bool(j in res.quarantined))
        return res

    def _dispatch_with_retry(self, name: str, fn):
        """Retry-with-backoff guard for a NON-DONATING device dispatch
        (resilience/recovery.DispatchGuard): a device-loss-shaped
        failure re-runs ``fn`` after backoff, bounded by
        ``solver.dispatch_retries`` and ``PCG_TPU_RETRY_DEADLINE_S``.
        Only stateless dispatches may pass through here — a program that
        donates an operand must never be re-dispatched with the same
        arguments (the donated buffer may already be consumed); those
        paths re-dispatch from a host snapshot instead
        (ResilienceContext.handle_dispatch_failure)."""
        from pcg_mpi_solver_tpu.resilience.recovery import (
            DispatchGuard, retry_deadline_s)

        plan = self.fault_plan
        guard = None
        while True:
            try:
                if plan is not None:
                    plan.on_dispatch()
                with self._rec.dispatch(name):
                    out = fn()
                if plan is not None:
                    plan.on_dispatch_done()
                return out
            except Exception as e:      # noqa: BLE001 — classified below
                if guard is None:
                    guard = DispatchGuard(
                        retries=self.config.solver.dispatch_retries,
                        deadline_s=retry_deadline_s(),
                        recorder=self._rec)
                if not guard.should_retry(e):
                    raise
                self._rec.event("recovery", action="redispatch",
                                attempt=guard.failures,
                                trigger="device_loss",
                                error=f"{type(e).__name__}: {e}")
                self._rec.inc("resilience.recovery.redispatch")
                guard.backoff()

    def displacement_global_many(self, x) -> np.ndarray:
        """Blocked device solution (n_parts, n_loc, nrhs) -> global host
        (n_dof, nrhs) array: ONE fetch of the whole block (one DCN
        all-gather on multi-host) + one owner-masked scatter, the scalar
        :meth:`displacement_global`'s two steps and spans
        (``fetch.transfer``, ``fetch.assemble``) with the trailing block
        axis carried through."""
        from pcg_mpi_solver_tpu.parallel.distributed import (
            fetch_global, scatter_owned_global)

        with self._rec.span("fetch.transfer"):
            xh = fetch_global(x, self.mesh)
        with self._rec.span("fetch.assemble"):
            return scatter_owned_global(self.pm, xh, np.dtype(self.dtype))

    def _ensure_many_programs(self, R: int) -> dict:
        """Build (once per block width) the jitted blocked programs.
        One-shot: a single ``solve`` program (AOT-cached under cache_dir
        keyed by nrhs).  Chunked direct: start/cycle/final programs
        mirroring the scalar chunked engine, with a donated resumable
        blocked carry."""
        if R in self._many_progs:
            return self._many_progs[R]
        from pcg_mpi_solver_tpu.solver.pcg import (
            LAGGED_VARIANTS, carry_part_specs, cold_carry_many, pcg_many,
            pcg_mixed_many, restart_carry_many, select_best_many)

        scfg = self.config.solver
        mixed = self.mixed
        variant = scfg.pcg_variant
        lagged_v = variant in LAGGED_VARIANTS
        glob_n_eff = self.pm.glob_n_dof_eff
        P, Rsp = self._part_spec, self._rep_spec
        cap = self._dispatch_cap
        chunked = cap > 0 and not mixed
        # per-column ladder rung 2 (fallback preconditioner): wire the
        # scalar-Jacobi inverse as a second cycle operand only when the
        # ladder can use it — with precond already "jacobi" (or the
        # ladder disabled) the selection is compiled out and the cycle
        # program is unchanged
        use_fb = chunked and self._many_use_fb()
        progs = {"has_fallback": use_fb} if chunked else {}

        def smap(f, in_specs, out_specs, donate=()):
            return jax.jit(jax.shard_map(
                f, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False),
                donate_argnums=donate if self._donate else ())

        if not chunked:
            def _solve_blk(data, fb):
                # warm-path contract: increments only inside a live
                # trace, like _step (tests assert zero on an AOT hit)
                self._rec.inc("trace.step")
                self._rec.inc("trace.solve_many")
                d64 = data["f64"] if mixed else data
                eff = d64["eff"]
                fext = eff[..., None] * fb
                x0 = jnp.zeros_like(fext)
                if mixed:
                    inv32 = self._make_prec(self.ops32, data["f32"])
                    res, lockstep = pcg_mixed_many(
                        self.ops32, data["f32"], self.ops, d64, fext, x0,
                        inv32, tol=scfg.tol, max_iter=scfg.max_iter,
                        glob_n_dof_eff=glob_n_eff,
                        max_stag_steps=scfg.max_stag_steps,
                        inner_tol=scfg.inner_tol,
                        plateau_window=scfg.mixed_plateau_window,
                        progress_window=scfg.mixed_progress_window,
                        progress_ratio=scfg.mixed_progress_ratio,
                        progress_min_gain=scfg.mixed_progress_min_gain,
                        variant=variant)
                else:
                    inv = self._make_prec(self.ops, d64)
                    res = pcg_many(
                        self.ops, d64, fext, x0, inv,
                        tol=scfg.tol, max_iter=scfg.max_iter,
                        glob_n_dof_eff=glob_n_eff,
                        max_stag_steps=scfg.max_stag_steps,
                        x0_zero=True, variant=variant)
                    # one Krylov cycle: the lockstep loop ran as long as
                    # its longest column
                    lockstep = jnp.max(res.iters)
                return res.x, res.flag, res.relres, res.iters, lockstep

            shard = jax.shard_map(
                _solve_blk, mesh=self.mesh, in_specs=(self._specs, P),
                out_specs=(P, Rsp, Rsp, Rsp, Rsp), check_vma=False)
            fn = jax.jit(shard)
            if self._cache_dir:
                aot_fn = self._build_aot_many(shard, R)
                if aot_fn is not None:
                    fn = aot_fn
            progs["solve"] = fn
        else:
            carry_specs = carry_part_specs(P, Rsp, variant=variant,
                                           many=True)
            # prec rides as ONE operand either way: the plain primary
            # inverse (array, or the mg prec dict), or the (primary,
            # scalar-Jacobi fallback) pair the per-column ladder selects
            # from via the carry's prec_sel (the fallback is always the
            # plain scalar array)
            pspec = self._prec_operand_spec()
            prec_specs = (pspec, P) if use_fb else pspec

            def _start(data, fb):
                self._rec.inc("trace.step")
                self._rec.inc("trace.solve_many")
                eff = data["eff"]
                w = data["weight"] * eff
                fext = eff[..., None] * fb
                # x0 = 0: r0 = fext exactly, ||r0|| = ||b|| (one psum)
                normr0 = jnp.sqrt(self.ops.wdot_many(w, fext, fext))
                carry0 = cold_carry_many(
                    jnp.zeros_like(fext), fext, normr0,
                    self.ops.dot_dtype, variant=variant)
                prec = self._make_prec(self.ops, data)
                if use_fb:
                    from pcg_mpi_solver_tpu.ops.precond import (
                        make_fallback_prec)

                    prec = (prec, make_fallback_prec(self.ops, data,
                                                     scfg.precond))
                return fext, carry0, normr0, prec

            progs["start"] = smap(_start, (self._specs, P),
                                  (P, carry_specs, Rsp, prec_specs))

            def _cycle(data, fext, prec, carry, budget):
                inv, inv_fb = prec if use_fb else (prec, None)
                res, carry2 = pcg_many(
                    self.ops, data, fext, carry["x"], inv,
                    tol=scfg.tol,
                    max_iter=jnp.minimum(cap, budget),
                    glob_n_dof_eff=glob_n_eff,
                    max_stag_steps=scfg.max_stag_steps,
                    max_iter_nominal=scfg.max_iter,
                    carry_in=carry, return_carry=True, variant=variant,
                    inv_diag_fb=inv_fb)
                return res.x, carry2

            progs["cycle"] = smap(
                _cycle, (self._specs, P, prec_specs, carry_specs, Rsp),
                (P, carry_specs), donate=(3,))

            def _recover(data, fext, carry, restart_m, fb_m, quar_m):
                # masked per-column ladder surgery (pcg.
                # restart_carry_many): ONE blocked matvec; unmasked
                # columns pass through bit-identically.  Compiled lazily
                # by jit — a healthy solve never pays for it.
                return restart_carry_many(
                    self.ops, data, fext, carry, restart_m, fb_m,
                    quar_m, variant=variant)

            progs["recover"] = smap(
                _recover,
                (self._specs, P, carry_specs, Rsp, Rsp, Rsp),
                carry_specs)

            def _final(data, fext, carry):
                # the ONE terminal per-column selection lives in
                # select_best_many(respect_flags=True): converged
                # columns keep their accepted iterate, zero-rhs columns
                # return zeros, failed columns take the MATLAB
                # min-residual fallback
                return select_best_many(self.ops, data, fext, carry,
                                        always_min=lagged_v,
                                        respect_flags=True)

            progs["final"] = smap(_final, (self._specs, P, carry_specs),
                                  (P, Rsp))
        self._many_progs[R] = progs
        return progs

    def _build_aot_many(self, shard, R: int):
        """AOT-export path for the one-shot blocked program, mirroring
        :meth:`_build_aot_step` with the block width as a structural key
        component: a warm run of the same (model, config, nrhs) block
        shape deserializes StableHLO — zero re-tracing."""
        import dataclasses as _dc

        from pcg_mpi_solver_tpu.cache import aot
        from pcg_mpi_solver_tpu.cache.keys import step_cache_key

        data_abs = aot.abstract_like(self.data)
        psh = jax.sharding.NamedSharding(self.mesh, self._part_spec)
        rdt = jnp.float64 if self.mixed else self.dtype
        fb_abs = jax.ShapeDtypeStruct(
            (self.pm.n_parts, self.pm.n_loc, R), rdt, sharding=psh)
        abstract_args = (data_abs, fb_abs)
        key = step_cache_key(
            abstract=aot.signature_repr(abstract_args),
            mesh=(sorted(self.mesh.shape.items()),
                  self.mesh.devices.flat[0].platform),
            backend=self.backend,
            solver=_dc.asdict(self.config.solver),
            pcg_variant=self.config.solver.pcg_variant,
            precond=self.config.solver.precond,
            nrhs=R,
            trace_len=0,
            glob_n_dof_eff=int(self.pm.glob_n_dof_eff),
            donate=False,
            jax_version=jax.__version__,
            extra={"many": True, "lockstep": True,
                   "mg": self._mg_meta,
                   "x64": bool(jax.config.jax_enable_x64)})
        exported = aot.cached_step(
            self._cache_dir, key, jax.jit(shard), abstract_args,
            recorder=self._rec)
        if exported is None:
            return None
        return jax.jit(exported.call)

    def _many_use_fb(self) -> bool:
        """Whether the blocked cycle programs carry the scalar-Jacobi
        FALLBACK preconditioner operand (per-column ladder rung 2).
        The ONE predicate shared by the program builder and the blocked
        snapshot fingerprint (``SnapshotStore.for_many_solver``): a
        carry whose ``prec_sel`` flipped a column to the fallback must
        never resume into a program compiled without one — that resume
        fails as a clear ``many_fallback`` fingerprint mismatch."""
        from pcg_mpi_solver_tpu.ops.precond import fallback_kind

        scfg = self.config.solver
        return bool(scfg.max_recoveries > 0
                    and fallback_kind(scfg.precond) is not None)

    def _many_snap_store(self, R: int, rhs_hash: str):
        """Blocked-solve snapshot store for one (width, rhs-content)
        request shape (lazy; the fingerprint embeds both, so a resume
        against a different width OR different load cases mismatches
        loudly instead of continuing the wrong Krylov space)."""
        key = (R, rhs_hash)
        if key not in self._many_snap:
            if jax.process_count() > 1 or self._elastic_dir is not None:
                from pcg_mpi_solver_tpu.resilience.distributed import (
                    GroupSnapshotStore)

                store = GroupSnapshotStore.for_many_solver(
                    self, R, rhs_hash=rhs_hash,
                    comm=self._collective_comm(), recorder=self._rec,
                    elastic=self._elastic_dir is not None)
                if self._elastic_dir is not None:
                    store.path = self._elastic_dir
                    store._epoch = store._scan_next_epoch()
                self._many_snap[key] = store
            else:
                from pcg_mpi_solver_tpu.utils.checkpoint import SnapshotStore

                self._many_snap[key] = SnapshotStore.for_many_solver(
                    self, R, rhs_hash=rhs_hash)
        return self._many_snap[key]

    def _make_many_resilience(self, store, resume: bool):
        """Blocked-solve resilience context (``kind="many"`` snapshot
        states at the fixed pseudo-step 1): the dispatch guard, the
        ``many_*.npz`` snapshot cadence, mid-solve resume, and the fault
        plan — the blocked twin of :meth:`_make_resilience`.  None when
        nothing is armed."""
        scfg = self.config.solver
        every = int(getattr(self.config, "snapshot_every", 0))
        plan = self.fault_plan
        comm = self._collective_comm()
        if (store is None and plan is None and scfg.max_recoveries <= 0
                and comm is None):
            return None
        from pcg_mpi_solver_tpu.resilience.recovery import (
            DispatchGuard, ResilienceContext, retry_deadline_s)

        def fetch(state):
            return {k: (self._fetch_state(v) if k == "carry"
                        else np.asarray(v)) for k, v in state.items()}

        return ResilienceContext(
            store=store, step=1, snapshot_every=every,
            fetch_state=fetch, put_state=self._put_state,
            guard=DispatchGuard(retries=scfg.dispatch_retries,
                                deadline_s=retry_deadline_s(),
                                recorder=self._rec),
            faults=plan, recorder=self._rec, resume=resume,
            ladder_armed=scfg.max_recoveries > 0, comm=comm)

    def _solve_many_chunked(self, fb_dev, R: int, progs, resume: bool,
                            rhs_hash: str = ""):
        """Host budget loop for a blocked direct solve: capped resumable
        dispatches of the blocked carry (donated in place), per-column
        flags deciding termination, optional mid-solve snapshots every
        ``config.snapshot_every`` chunk boundaries.  The snapshot is
        discarded only on successful completion — a crashed/killed solve
        leaves it for ``solve_many(..., resume=True)``.

        The loop itself — per-column breakdown/NaN classification, the
        bounded per-column recovery ladder, column quarantine, the
        guarded re-dispatch, snapshots and fault injection — is the
        shared :func:`resilience.engine.run_many_with_recovery` harness;
        this method supplies the blocked device programs (cycle,
        masked recover) as
        :class:`~pcg_mpi_solver_tpu.resilience.engine.ManyRecoveryHooks`."""
        from pcg_mpi_solver_tpu.resilience.engine import (
            ManyRecoveryHooks, run_many_with_recovery)

        scfg = self.config.solver
        rec = self._rec
        from pcg_mpi_solver_tpu.solver.pcg import LAGGED_VARIANTS
        lagged_v = scfg.pcg_variant in LAGGED_VARIANTS
        every = int(getattr(self.config, "snapshot_every", 0))
        store = (self._many_snap_store(R, rhs_hash)
                 if (every > 0 or resume) else None)
        with rec.dispatch("many_start"):
            fext, carry, normr0, prec = progs["start"](self.data, fb_dev)
            jax.block_until_ready(normr0)
        ctx = self._make_many_resilience(store, resume)

        def _cycle(carry, budget):
            with rec.dispatch("many_cycle"):
                x, c2 = progs["cycle"](self.data, fext, prec, carry,
                                       jnp.asarray(budget, jnp.int32))
                # blocking fetch inside the span (async dispatch)
                jax.block_until_ready(c2["exec"])
            return x, c2

        def _recover(carry, restart_m, fb_m, quar_m):
            with rec.dispatch("many_recover"):
                c2 = progs["recover"](self.data, fext, carry,
                                      restart_m, fb_m, quar_m)
                jax.block_until_ready(c2["flag"])
            return c2

        (x_fin, carry, flags, lockstep, iters_cols, quarantined,
         recoveries, drift_cols) = run_many_with_recovery(
            carry, scfg=scfg, nrhs=R, recorder=rec,
            hooks=ManyRecoveryHooks(cycle=_cycle, recover=_recover,
                                    has_fallback=bool(
                                        progs.get("has_fallback"))),
            resilience=ctx, resume=resume, lagged=lagged_v)
        with rec.dispatch("many_final"):
            x_fin, relres = progs["final"](self.data, fext, carry)
            relres = np.asarray(relres, dtype=np.float64)
        if ctx is not None:
            # the solve completed: its mid-solve snapshot must not
            # outlive it (a store always implies a ctx —
            # _make_many_resilience never returns None with one)
            ctx.discard()
        return (x_fin, flags, relres, iters_cols, quarantined,
                recoveries, int(drift_cols.sum()), int(lockstep))

    def step(self, delta: float) -> StepResult:
        # recovery-exempt: the one-shot step DONATES the previous
        # solution vector, so a failed dispatch must never be re-run
        # with the same (possibly consumed) operand, and a single
        # stateless dispatch has no resumable carry for the ladder to
        # restart from — resilience is the chunked path's job
        # (_step_chunked -> run_with_recovery); the except arm below
        # only restores a retryable zero state.
        # the request's span: every dispatch below nests under it, and
        # the host time between them is its own (``pcg-tpu/solve``)
        with self._rec.span("solve", step=len(self.flags) + 1):
            t0 = time.perf_counter()
            if self._dispatch_cap > 0:
                flag, relres, iters = self._step_chunked(delta)
            else:
                try:
                    with self._rec.dispatch("step"):
                        out = self._step_fn(
                            self.data, self.un,
                            jnp.asarray(delta, self.dtype))
                        un, flag, relres, iters = out[:4]
                        # Scalar fetch INSIDE the timed region and the
                        # dispatch span: async dispatch returns immediately;
                        # fetching the scalars cannot return before
                        # execution ends.
                        flag, relres, iters = (int(flag), float(relres),
                                               int(iters))
                except BaseException:
                    # The dispatch may have CONSUMED the donated self.un
                    # before failing (or before a KeyboardInterrupt
                    # landed) — restore a live zero state so the solver
                    # stays retryable instead of every later access dying
                    # on a deleted buffer.  Only when actually consumed:
                    # an error raised before the jitted call ran (bad
                    # delta, a sink raising) must keep the intact previous
                    # iterate.
                    if self._donate and getattr(self.un, "is_deleted",
                                                lambda: False)():
                        self.reset_state()
                        # the divergence from donate_carry=False (which
                        # would have kept the previous iterate) must be
                        # visible to whoever catches and retries
                        self._rec.note(
                            "failed dispatch consumed the donated solution "
                            "vector; state RESET TO ZERO — a retry resumes "
                            "from u=0, not the previous iterate")
                    raise
                # trace ring: the solve's ONE device->host trace transfer
                self.last_trace = (unpack_trace(out[4]) if self.trace_len
                                   else None)
                self.un = un
            wall = time.perf_counter() - t0
            res = StepResult(flag, relres, iters, wall)
            self.flags.append(res.flag)
            self.relres.append(res.relres)
            self.iters.append(res.iters)
            self.step_times.append(wall)
            self._proc_step_times.append(wall)
            step_i = len(self.flags)
            # time_to_tol_s: the ROADMAP-4 time-to-solution signal — wall
            # to CONVERGED-at-tol, null on any non-0 flag (additive field;
            # the bench stamps the same semantics on its result lines)
            self._rec.event("step", step=step_i, flag=flag, relres=relres,
                            iters=iters, wall_s=round(wall, 6),
                            time_to_tol_s=(round(wall, 6) if flag == 0
                                           else None))
            if self.trace_len and self.last_trace is not None:
                self._rec.event("resid_trace",
                                **self.last_trace.to_event_fields(step_i))
            return res

    def resume_elastic(self, snapshot_dir: Optional[str] = None,
                       **solve_kw):
        """Resume a MULTI-PROCESS run's persisted state on a DIFFERENT
        (typically smaller) process count — the elastic-resume path
        (ISSUE 18).

        Group-consistent snapshot epochs
        (resilience/distributed.GroupSnapshotStore) carry each shard's
        part rows, so a committed N-process epoch re-joins into the full
        global state on any process count; completed-step checkpoints
        are globally-fetched on the primary already.  Both resumes would
        normally refuse on the ``n_procs`` fingerprint mismatch — this
        entry point arms the NAMED elastic path instead: the mismatch
        (confined to ``n_procs``) becomes an ``elastic_resume``
        telemetry event and the solve continues bit-identically.

        ``snapshot_dir`` points at the dead fleet's checkpoint
        directory; None reads this config's ``checkpoint_path``.
        Remaining keywords pass through to :meth:`solve`."""
        self._elastic_dir = snapshot_dir or self.config.checkpoint_path
        # a store built before arming lacks the elastic marker (and, on
        # a shrunk fleet, possibly the epoch protocol entirely): rebuild
        self._snap_store = None
        self._many_snap = {}
        try:
            return self.solve(resume=True, **solve_kw)
        finally:
            self._elastic_dir = None
            self._snap_store = None
            self._many_snap = {}

    def solve(self, on_step: Optional[Callable[[int, StepResult], None]] = None,
              store=None, resume: bool = False):
        """Run the full quasi-static schedule (skips step 0, like the
        reference's ``range(1, RefMaxTimeStepCount)``, pcg_solver.py:1002),
        exporting contour frames / history / timing into ``store`` when
        exports are enabled.

        With ``resume=True``, restores the latest checkpoint under
        ``config.checkpoint_path`` (if any) and continues from the step
        after it; with ``config.checkpoint_every > 0``, writes a checkpoint
        every N completed steps and after the final one."""
        th = self.config.time_history
        deltas = th.time_step_delta
        do_export = store is not None and th.export_flag and not self.config.speed_test
        do_plot = store is not None and th.plot_flag and not self.config.speed_test
        if do_export and self._model.n_dof == self._model.n_node:
            bad = self._nodal_vars()            # includes NS
            if bad:
                # Scalar (Poisson) class: the strain/stress/nonlocal export
                # pipelines statically unpack 6 Voigt components — fail
                # loudly up front, not mid-solve with a shape error.
                raise ValueError(
                    f"export vars {bad} (strain/stress nodal fields) are "
                    "not available for the scalar problem class; export 'U'")

        ckpt_mgr = None
        t_start = 1
        if self.config.checkpoint_every > 0 or resume:
            from pcg_mpi_solver_tpu.utils.checkpoint import CheckpointManager

            ckpt_mgr = CheckpointManager(self._elastic_dir
                                         or self.config.checkpoint_path)
        if resume and ckpt_mgr is not None:
            t_done = ckpt_mgr.restore(
                self, elastic=self._elastic_dir is not None,
                recorder=self._rec)
            if t_done is not None:
                t_start = t_done + 1
        # Mid-Krylov snapshot resume (resilience/): only an EXPLICIT
        # --resume may continue a persisted in-step carry — a fresh solve
        # finding a stale snap_*.npz from a previous generation must
        # start cold (steps discard their snapshot on completion, so the
        # armed window closes as the resumed run advances past it).
        self._resume_pending = bool(resume)

        t_prep = time.perf_counter() - self._t_init0
        if do_export and t_start == 1:
            # On resume the run dir (maps + already-exported frames) must
            # survive; prepare() would rotate it away.
            store.prepare()
            if jax.process_count() > 1:
                # prepare() rotates a pre-existing run dir on the primary;
                # a non-primary shard write racing that rotation would be
                # stranded in the rotated dir.  Barrier before any writes.
                from jax.experimental import multihost_utils

                # consensus-exempt: plain barrier, unconditional on the
                # multi-process export path (no verdict to agree)
                multihost_utils.sync_global_devices("runstore_prepared")
            store.write_map("Dof", self.export_dof_map())
            if self._nodal_vars():
                store.write_map("NodeId", self.export_node_map())
            self._export_count = 0
            self._export_times = []
            self._maybe_export(store, 0)
        if t_start == 1:
            self._probe_u = []
        probe_u = self._probe_u

        profiling = bool(self.config.profile_dir) and not self.config.speed_test
        if self.config.profile_dir and self.config.speed_test:
            import warnings

            warnings.warn("profile_dir is ignored in speed-test mode "
                          "(speed_test disables all I/O)")
        prof_dir = self.config.profile_dir
        if profiling:
            # Multi-process: two hosts must not race one trace directory
            # (the profiler's run-dir naming is second-granular) — each
            # process captures into its own p<idx> subdir, the same
            # sharding rule the telemetry JSONL stream follows.
            if jax.process_count() > 1:
                prof_dir = os.path.join(prof_dir,
                                        f"p{jax.process_index()}")
            jax.profiler.start_trace(prof_dir)

        results = []
        try:
            for t in range(t_start, len(deltas)):
                res = self.step(deltas[t])
                results.append(res)
                if do_export:
                    self._maybe_export(store, t)
                if do_plot and len(th.probe_dofs) > 0:
                    u = self.displacement_global()
                    probe_u.append(u[np.asarray(th.probe_dofs)])
                every = self.config.checkpoint_every
                if ckpt_mgr is not None and every > 0 and (
                        t % every == 0 or t == len(deltas) - 1):
                    ckpt_mgr.save(self, t)
                if on_step is not None:
                    on_step(t, res)
        finally:
            self._resume_pending = False
            if profiling:
                jax.profiler.stop_trace()
                # profile_capture event: the pointer `pcg-tpu summary`
                # and post-mortems follow to the on-disk artifact
                # (obs/profview.newest_profile_artifact resolves the
                # run dir the profiler just wrote; best-effort — a
                # capture that wrote nothing still reports the root)
                try:
                    from pcg_mpi_solver_tpu.obs.profview import (
                        newest_profile_artifact)

                    art = newest_profile_artifact(prof_dir) or prof_dir
                except Exception:                       # noqa: BLE001
                    art = prof_dir
                self._rec.event("profile_capture", path=art,
                                source="solve",
                                steps=len(results))

        if do_export:
            store.write_time_list(self._export_times)
        if do_plot and probe_u:
            times = [i * th.dt for i in range(1, len(deltas))]
            store.write_plot_data(times, np.stack(probe_u, axis=1), th.probe_dofs)
        if store is not None and not self.config.speed_test:
            comm = (self.measure_comm_split()
                    if self.config.comm_probe_iters > 0 else None)
            store.write_time_data(self.pm.n_parts,
                                  self.time_data(t_prep, comm))
        # End-of-run snapshot (counters/gauges/dispatch attribution) as the
        # final JSONL event — also the data behind the CLI --summary table.
        self._rec.emit_run_summary()
        return results

    def _maybe_export(self, store, t: int):
        """Key-frame contour export (reference exportContourData,
        pcg_solver.py:841-896)."""
        th = self.config.time_history
        due = th.export_frame_rate > 0 and t % th.export_frame_rate == 0
        if t in tuple(th.export_frames):
            due = True
        if not due:
            return
        t0 = time.perf_counter()
        k = self._export_count
        if "U" in self._export_vars():
            if jax.process_count() > 1:
                # Parallel I/O: each process writes its own part block —
                # no DCN all-gather, no single-writer bottleneck
                # (reference writeMPIFile_parallel, pcg_solver.py:869).
                vals, p0, p1 = self.displacement_owned_local()
                store.write_frame_shard("U", k, vals, p0, p1,
                                        self.pm.n_parts)
            else:
                store.write_frame("U", k, self.displacement_owned())
        nodal = [v for v in self._nodal_vars() if v != "NS"]
        if nodal:
            fields = self._nodal_fields()
            mask = self.node_owner_mask()
            if jax.process_count() > 1:
                from pcg_mpi_solver_tpu.parallel.distributed import (
                    fetch_addressable)

                for var, arr in fields.items():
                    rows, p0, p1 = fetch_addressable(arr)
                    store.write_frame_shard(var, k, rows[mask[p0:p1]],
                                            p0, p1, self.pm.n_parts)
            else:
                for var, arr in fields.items():
                    store.write_frame(var, k, np.asarray(arr)[mask])
        if "NS" in self._export_vars():
            ns = self._nonlocal_field()
            store.write_frame("NS", k, ns[self.export_node_map()])
        self._export_times.append(t * th.dt)
        self._export_count = k + 1
        self._export_wall += time.perf_counter() - t0

    def _export_vars(self):
        ev = self.config.time_history.export_vars
        return ev.split() if " " in ev else [
            v for v in ("U", "D", "ES", "PS", "PE", "NS") if v in ev]

    def _nodal_vars(self):
        return [v for v in self._export_vars() if v != "U"]

    def _nonlocal_field(self) -> np.ndarray:
        """Nonlocal von-Mises stress, node-averaged, as a global (n_node,)
        field.  Element stresses are smoothed with the Gaussian neighborhood
        operator (reference config_NonlocalNeighbours, partition_mesh.py:
        1000-1299 — built there, never consumed; wired end-to-end here).
        Host-side: it is an export-path op, partition-layout agnostic."""
        from pcg_mpi_solver_tpu.ops.nonlocal_stress import (
            build_nonlocal_weights, elem_stress_host, nodal_average_host,
            von_mises_stress)

        if self._nonlocal is None:
            self._nonlocal = build_nonlocal_weights(self._model)
        sig = elem_stress_host(self._model, self.displacement_global())
        ns = self._nonlocal.apply(von_mises_stress(sig, axis=1))
        return nodal_average_host(self._model, ns)

    def _nodal_fields(self) -> dict:
        """Jitted nodal export fields of the current solution
        ({var: (P, n_node_loc)} split to PS1..3/PE1..3)."""
        if self._export_fn is None:
            from pcg_mpi_solver_tpu.ops.stress import nodal_export_fields

            nodal = tuple(v for v in self._nodal_vars() if v != "NS")
            if self._model.n_dof == self._model.n_node:
                # Scalar (Poisson) class: the strain/stress pipeline
                # statically unpacks 6 Voigt components — fail loudly like
                # the block3 layout guard, not with an IndexError at trace.
                raise ValueError(
                    f"export vars {nodal} (strain/stress nodal fields) are "
                    "not available for the scalar problem class; export 'U'")

            def _fields(data, un):
                data64 = data["f64"] if self.mixed else data
                return nodal_export_fields(self.ops, data64, un, nodal, self._nu)

            self._export_fn = jax.jit(jax.shard_map(
                _fields, mesh=self.mesh,
                in_specs=(self._specs, self._part_spec),
                out_specs=self._part_spec, check_vma=False))
        return self._export_fn(self.data, self.un)

    def measure_comm_split(self, n_iters: Optional[int] = None) -> dict:
        """Measured calc vs comm-wait attribution (the reference brackets
        every MPI call with host timers, pcg_solver.py:631-641; under XLA
        the collectives are compiled into the program, so we measure them
        differentially): time ``n_iters`` of the PCG iteration body — one
        assembled matvec + the iteration's three scalar reductions — once
        with real collectives and once with an ``axis_name=None`` clone of
        the ops (identical local compute, including the interface
        scatter/gather, but no psums).  The difference is collective time.

        Returns {"comm_frac", "full_s_per_iter", "calc_s_per_iter"}."""
        if n_iters is None:
            n_iters = max(self.config.comm_probe_iters, 1)
        if self.mesh.devices.size == 1:
            return {"comm_frac": 0.0, "full_s_per_iter": 0.0,
                    "calc_s_per_iter": 0.0}
        mixed = self.mixed
        ops = self.ops32 if mixed else self.ops
        P, R = self._part_spec, self._rep_spec
        probe_dtype = jnp.float32 if mixed else self.dtype

        def make(ops_):
            def run(data, x, n):
                d = data["f32"] if mixed else data
                eff = d["eff"]
                w = d["weight"] * eff

                def body(i, c):
                    x, acc = c
                    q = eff * ops_.matvec(d, x)           # iface psum
                    rho = ops_.wdot(w, x, q)              # psum 1
                    pq = ops_.wdot(w, q, q)               # psum 2
                    s3 = ops_.wdots(w, [(x, x), (q, q), (x, q)])  # psum 3
                    x2 = (q / jnp.sqrt(jnp.maximum(pq, 1e-30))).astype(x.dtype)
                    # acc consumes every reduction so none is dead code.
                    return x2, acc + rho + s3.sum()

                return jax.lax.fori_loop(0, n, body, (x, jnp.asarray(0.0, ops_.dot_dtype)))

            return jax.jit(jax.shard_map(
                run, mesh=self.mesh,
                in_specs=(self._specs, P, R),
                out_specs=(P, R), check_vma=False))

        import dataclasses as _dc

        from pcg_mpi_solver_tpu.parallel.distributed import put_sharded

        full_fn = make(ops)
        local_fn = make(_dc.replace(ops, axis_name=None))
        x0 = put_sharded(
            np.ones((self.pm.n_parts, self.pm.n_loc), probe_dtype),
            self.mesh, P)
        n = jnp.asarray(n_iters, jnp.int32)

        def timed(fn):
            jax.block_until_ready(fn(self.data, x0, jnp.asarray(2, jnp.int32)))
            t0 = time.perf_counter()
            out = fn(self.data, x0, n)
            # fetch a scalar: it cannot return before execution ends
            # (the same barrier as step()).
            float(out[1])
            return (time.perf_counter() - t0) / n_iters

        full_t = timed(full_fn)
        local_t = timed(local_fn)
        comm = max(full_t - local_t, 0.0)
        return {"comm_frac": comm / full_t if full_t > 0 else 0.0,
                "full_s_per_iter": full_t,
                "calc_s_per_iter": full_t - comm}

    def time_data(self, t_prep: float = 0.0,
                  comm_split: Optional[dict] = None) -> dict:
        """Solve metadata in the reference's TimeData schema
        (file_operations.py:72-172, pcg_solver.py:943-961), extended with a
        compile-time estimate, export-time bucket and per-part load-unbalance
        stats (reference LoadUnbalanceData, file_operations.py:118-128).

        ``comm_split`` (from :meth:`measure_comm_split`) apportions the
        measured step time into the reference's two buckets
        (Mean_CalcTime / Mean_CommWaitTime); without it the whole step time
        is reported as calc (per-op detail lives in the profiler trace,
        config.profile_dir)."""
        steps = np.asarray(self.step_times)
        # First step run IN THIS PROCESS pays the XLA compile; checkpoint-
        # restored step times never include this process's compile.
        proc = np.asarray(self._proc_step_times)
        compile_est = float(proc[0] - np.median(proc[1:])) if len(proc) > 1 else 0.0
        type_blocks = getattr(self.pm, "type_blocks", None)
        if type_blocks:
            elems_pp = np.sum([tb.n_elem for tb in type_blocks], axis=0)
        else:   # structured slab partition: identical cell count per part
            elems_pp = np.full(self.pm.n_parts,
                               self.pm.nxc * self.pm.ny * self.pm.nz)
        dofs_pp = np.asarray(self.pm.ndof_p)
        unbalance = {
            "ElemsPerPart": elems_pp,
            "DofsPerPart": dofs_pp,
            "MaxByMeanElems": float(elems_pp.max() / max(elems_pp.mean(), 1))
            if elems_pp.size else 1.0,
            "MaxByMeanDofs": float(dofs_pp.max() / max(dofs_pp.mean(), 1)),
            "IfaceDofFrac": float(self.pm.n_iface / max(self.pm.glob_n_dof, 1)),
        }
        total = float(np.sum(self.step_times))
        comm_frac = comm_split["comm_frac"] if comm_split else 0.0
        return {
            "Mean_FileReadTime": t_prep,
            "Mean_CalcTime": total * (1.0 - comm_frac),
            "Mean_CommWaitTime": total * comm_frac,
            "CommProbe": comm_split or {},
            "Compile_Time_Est": max(compile_est, 0.0),
            "Export_Time": float(self._export_wall),
            "TotalTime": t_prep + float(np.sum(self.step_times)),
            "Flag": np.asarray(self.flags),
            "Iter": np.asarray(self.iters),
            "RelRes": np.asarray(self.relres),
            "StepTimes": steps,
            "LoadUnbalanceData": unbalance,
            "MP_NDOF": self.pm.n_loc,
            "N_Parts": self.pm.n_parts,
        }

    # ------------------------------------------------------------------
    # Host-side views for export
    # ------------------------------------------------------------------
    def owner_mask(self) -> np.ndarray:
        """(P, n_loc) bool — dofs this part owns (reference
        DofWeightVector_Export, pcg_solver.py:198)."""
        return (self.pm.weight > 0) & (self.pm.dof_gid >= 0)

    def node_owner_mask(self) -> np.ndarray:
        """(P, n_node_loc) bool — nodes this part owns."""
        return (self.pm.node_weight > 0) & (self.pm.node_gid >= 0)

    def export_node_map(self) -> np.ndarray:
        """Global node ids in export order (reference 'NodeId' map,
        pcg_solver.py:202)."""
        return self.pm.node_gid[self.node_owner_mask()]

    def export_dof_map(self) -> np.ndarray:
        """Global dof ids in export order (reference writes this once as the
        'Dof' map, pcg_solver.py:201)."""
        m = self.owner_mask()
        return self.pm.dof_gid[m]

    def displacement_owned(self) -> np.ndarray:
        """Owner-masked local solution values, concatenated in part order
        (the per-frame 'U_i' payload, pcg_solver.py:869)."""
        from pcg_mpi_solver_tpu.parallel.distributed import fetch_global

        un = fetch_global(self.un, self.mesh)
        return un[self.owner_mask()]

    def displacement_owned_local(self):
        """This process's slice of :meth:`displacement_owned` without any
        collective: ``(values, p0, p1)`` where values covers parts
        [p0, p1).  Concatenating the slices in part order over all
        processes reproduces displacement_owned() exactly."""
        from pcg_mpi_solver_tpu.parallel.distributed import fetch_addressable

        rows, p0, p1 = fetch_addressable(self.un)
        return rows[self.owner_mask()[p0:p1]], p0, p1

    def displacement_global(self) -> np.ndarray:
        """Full global solution vector (n_dof,), assembled on host: the
        device->host copy is the span ``fetch.transfer``, the owner-mask
        scatter into the global array ``fetch.assemble``."""
        from pcg_mpi_solver_tpu.parallel.distributed import (
            fetch_global, scatter_owned_global)

        with self._rec.span("fetch.transfer"):
            un = fetch_global(self.un, self.mesh)
        with self._rec.span("fetch.assemble"):
            return scatter_owned_global(self.pm, un, np.dtype(self.dtype))


_REPLICATED_KEYS = frozenset(
    {"Ke", "diag_Ke", "Me", "Se", "Ke4", "diag_Ke4",
     "brick_Ke", "brick_diag", "brick_Se"})


def _data_specs(data):
    """PartitionSpec pytree for the device data: per-type constant matrices
    are replicated, everything else is sharded on the leading parts axis.
    The ``mg`` subtree (ops/mg.py) is special: only its ``fine``
    transfer arrays carry the parts axis — the whole coarse hierarchy is
    REPLICATED by design (that is what makes the coarse V-cycle
    collective-free)."""
    P = jax.sharding.PartitionSpec

    def const(node):
        if isinstance(node, dict):
            return {k: const(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(const(v) for v in node)
        return P()

    def rec(node):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if k in _REPLICATED_KEYS:
                    out[k] = P()
                elif k == "mg":
                    out[k] = {kk: (rec(vv) if kk == "fine"
                                   else const(vv))
                              for kk, vv in v.items()}
                else:
                    out[k] = rec(v)
            return out
        if isinstance(node, (list, tuple)):
            return type(node)(rec(v) for v in node)
        return P(PARTS_AXIS)

    return rec(data)
