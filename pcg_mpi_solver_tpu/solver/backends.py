"""Shared matvec-backend selection for the time-integration solvers.

DynamicsSolver (explicit) and NewmarkSolver (implicit) support the same
two backends — the hybrid level-grid path for octree models and the
general node-ELL path for everything else; this is the one copy of that
selection (the quasi-static Solver adds the structured slab path and its
dispatch-chunked machinery on top, driver.py:131-230)."""

from __future__ import annotations

import os

from pcg_mpi_solver_tpu.models.model_data import ModelData
from pcg_mpi_solver_tpu.ops.matvec import Ops, device_data
from pcg_mpi_solver_tpu.parallel.mesh import PARTS_AXIS
from pcg_mpi_solver_tpu.parallel.partition import partition_model


def select_time_backend(model: ModelData, n_parts: int, *,
                        partition_method: str, backend: str = "auto"):
    """Resolve ``backend`` ("auto" | "hybrid" | "general") for a model.

    Returns ``(name, pm, mk_ops, mk_data)`` with ``mk_ops(dot_dtype)`` an
    Ops factory and ``mk_data(dtype)`` the device-pytree factory.
    """
    from pcg_mpi_solver_tpu.parallel.hybrid import can_hybrid

    if backend not in ("auto", "hybrid", "general"):
        raise ValueError(f"backend must be 'auto'|'hybrid'|'general', "
                         f"got {backend!r}")
    if backend == "hybrid" and not can_hybrid(model):
        raise ValueError("hybrid backend requested but model has no "
                         "octree/brick metadata")
    if backend == "auto" and can_hybrid(model) \
            and os.environ.get("PCG_TPU_ENABLE_HYBRID") != "1":
        # hybrid demotion gate (ISSUE 14; same policy as the quasi-static
        # driver): AUTO selection needs the explicit opt-in — dry-runs
        # put the hybrid partition at 117-183 s where structured takes
        # 10.5 s, and its stencil compiles cost minutes per
        # instantiation (RUNBOOK "Scaling the setup path").  Loud like
        # the driver's note event — a silent reroute would make octree
        # dynamics perf regressions undiagnosable.
        import warnings

        warnings.warn(
            "model is hybrid-backend eligible but auto-selection is "
            "gated (set PCG_TPU_ENABLE_HYBRID=1 or pass "
            "backend='hybrid'); using the general backend — see "
            "RUNBOOK 'Scaling the setup path'")
        backend = "general"
    if backend in ("auto", "hybrid") and can_hybrid(model):
        from pcg_mpi_solver_tpu.parallel.hybrid import (
            HybridOps, device_data_hybrid, partition_hybrid)

        pm = partition_hybrid(model, n_parts, method=partition_method)
        mk_ops = lambda dd: HybridOps.from_hybrid(
            pm, dot_dtype=dd, axis_name=PARTS_AXIS)
        return "hybrid", pm, mk_ops, lambda dt: device_data_hybrid(pm, dt)

    pm = partition_model(model, n_parts, method=partition_method)
    mk_ops = lambda dd: Ops.from_model(pm, dot_dtype=dd,
                                       axis_name=PARTS_AXIS)
    return "general", pm, mk_ops, lambda dt: device_data(pm, dt)
