"""Analytic per-iteration cost model: attribute every ms/iter BEFORE a
hardware window opens.

Round-5 hardware data left ~45% of each iteration unattributed (24.994
ms/iter against 13.741 ms/matvec at 10.33M dofs) — the data-locality CG
literature (arXiv:2205.08909) shows those gaps are memory-bound phase
costs predictable from bytes moved, and the communication-reduced survey
(arXiv:2501.03743) does the same for collective payloads.  This module
turns the repo's existing single-source ops tables into that prediction:

* ``ops/matvec.PCG_SCALAR_PSUMS``    — per-variant reduction collectives,
* ``ops/matvec.PCG_VECTOR_AXPYS``    — per-variant vector updates,
* ``ops/matvec.precond_cycle_cost``  — per-precond extra matvecs/psums,
* ``parallel/structured.STENCIL_HALO_PPERMUTES`` — halo exchanges.

Per ``(pcg_variant, precond, nrhs, backend)`` combination the model
produces FLOPs, HBM bytes and collective count/payload for the four
phases of one PCG iteration — ``matvec`` / ``precond`` / ``reduction``
/ ``axpy`` — and converts them to predicted ms/iter through a hardware
roofline profile.  An UNKNOWN variant or preconditioner is a loud
``KeyError`` (the same contract as the source tables; the analysis/
``cost-model-completeness`` rule proves the enumeration is total).

The model is emitted as a schema-versioned ``cost_model`` telemetry
event plus ``perf.*`` gauges at solver construction, stamped on every
bench line as ``detail.predicted_ms_per_iter`` (with
``detail.model_ratio`` = measured/predicted), and compared against the
MEASURED phase probes (obs/phases.py) by ``pcg-tpu perf-report``.

Import-light by contract (no jax, no numpy at import): the ops tables
are imported lazily inside the functions, so bench.py and the analysis
rules can import this module before the accelerator environment is
configured.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, Optional, Tuple

from pcg_mpi_solver_tpu.config import PCG_VARIANTS, PRECONDS

#: the four attribution phases of one PCG iteration — the rows of the
#: measured-vs-model table (obs/phases.py measures the same four).
PHASES = ("matvec", "precond", "reduction", "axpy")

#: reduced scalars per iteration (rho, the p.Ap denominator, ||r||, the
#: two stagnation norms, the inf-prec flag) — every variant reduces the
#: same six, the variants differ only in how many psums carry them.
REDUCED_SCALARS = 6


@dataclasses.dataclass(frozen=True)
class ProblemShape:
    """The pure-python geometry the cost model consumes — derivable from
    a live Solver (:func:`shape_from_solver`) or constructed synthetically
    (the analysis rule, tests)."""

    n_dof: int                       # global effective-ish dof count
    n_parts: int = 1
    n_iface: int = 0                 # global interface dof count (psum payload)
    #: per pattern-type group: (element dof count d, total element count)
    elem_groups: Tuple[Tuple[int, int], ...] = ()
    backend: str = "general"         # general | structured | hybrid
    itemsize: int = 8                # iteration storage dtype bytes
    dot_itemsize: int = 8            # reduction accumulation dtype bytes
    mg_degree: int = 2
    mg_coarse_dofs: int = 0

    def matvec_flops(self) -> float:
        """One assembled matvec, nrhs=1: the per-type dense
        ``Ke @ (ck*u)`` einsums (2*d*d*N each).  Structured/hybrid
        backends report an equivalent-stencil group."""
        if self.elem_groups:
            return float(sum(2.0 * d * d * n for d, n in self.elem_groups))
        # fallback: brick elasticity, ~1 element per 3 dofs, d=24
        return 2.0 * 24 * 24 * (self.n_dof / 3.0)

    def matvec_bytes(self) -> float:
        """One assembled matvec, nrhs=1: element gather + scatter traffic
        (d values in, d values out per element) plus the in/out nodal
        vectors."""
        if self.elem_groups:
            elem = sum(2.0 * d * n for d, n in self.elem_groups)
        else:
            elem = 2.0 * 24 * (self.n_dof / 3.0)
        return (elem + 2.0 * self.n_dof) * self.itemsize


@dataclasses.dataclass(frozen=True)
class PhaseCost:
    """Resource cost of one phase of one iteration (already nrhs-wide)."""

    flops: float = 0.0
    hbm_bytes: float = 0.0
    coll_count: int = 0
    coll_bytes: float = 0.0

    def to_dict(self) -> dict:
        return {"flops": round(self.flops, 1),
                "hbm_bytes": round(self.hbm_bytes, 1),
                "coll_count": int(self.coll_count),
                "coll_bytes": round(self.coll_bytes, 1)}


@dataclasses.dataclass(frozen=True)
class HwProfile:
    """Roofline constants of one device kind, overridable per run via
    PCG_TPU_ROOFLINE_{FLOPS,HBM_GBS,ICI_GBS,COLL_LAT_US}."""

    name: str
    flops_per_s: float
    hbm_bytes_per_s: float
    ici_bytes_per_s: float
    coll_latency_s: float


_V5E = HwProfile(
    "TPU v5e",
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s (bf16), 16 GB
    # of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.
    # The collective latency is not published: an assumed 8 us.
    flops_per_s=1.97e14, hbm_bytes_per_s=8.19e11,
    ici_bytes_per_s=2.0e11, coll_latency_s=8e-6)

#: roofline profiles keyed by ``jax.Device.device_kind`` as the chip
#: reports it (the v5e says "TPU v5 lite").  The CPU entry
#: is rough effective rates of a small container, used by the CPU tests
#: and by serve admission pricing on CPU runs.
HW_PROFILES: Dict[str, HwProfile] = {
    "cpu": HwProfile("cpu", flops_per_s=6.0e9, hbm_bytes_per_s=1.5e10,
                     ici_bytes_per_s=1.5e10, coll_latency_s=2e-6),
    "TPU v5 lite": _V5E,
}


def resolve_profile(device_kind: str) -> HwProfile:
    """The HwProfile for a ``device_kind`` (any kind starting with "cpu"
    is the CPU), with the PCG_TPU_ROOFLINE_* env overrides applied.  An
    accelerator kind with no entry raises ValueError: no rate is
    invented for a device nobody has looked up."""
    kind = str(device_kind)
    key = "cpu" if kind.lower().startswith("cpu") else kind
    if key not in HW_PROFILES:
        raise ValueError(f"no roofline profile for device kind {kind!r} "
                         f"(known: {sorted(HW_PROFILES)})")
    p = HW_PROFILES[key]

    def env(name, default, scale=1.0):
        raw = os.environ.get(name)
        return default if raw is None else float(raw) * scale

    return HwProfile(
        name=p.name,
        flops_per_s=env("PCG_TPU_ROOFLINE_FLOPS", p.flops_per_s),
        hbm_bytes_per_s=env("PCG_TPU_ROOFLINE_HBM_GBS",
                            p.hbm_bytes_per_s, 1e9),
        ici_bytes_per_s=env("PCG_TPU_ROOFLINE_ICI_GBS",
                            p.ici_bytes_per_s, 1e9),
        coll_latency_s=env("PCG_TPU_ROOFLINE_COLL_LAT_US",
                           p.coll_latency_s, 1e-6),
    )


def _iface_collective(shape: ProblemShape, nrhs: int) -> Tuple[int, float]:
    """(count, payload bytes) of ONE assembled matvec's cross-part
    collective: the interface psum (general/hybrid) or the
    STENCIL_HALO_PPERMUTES halo exchange (structured)."""
    if shape.n_parts <= 1:
        return 0, 0.0
    if shape.backend == "structured":
        from pcg_mpi_solver_tpu.parallel.structured import (
            STENCIL_HALO_PPERMUTES)

        # halo payload: one boundary plane each way ~ n_dof^(2/3) rows
        plane = max(1.0, float(shape.n_dof) ** (2.0 / 3.0))
        return STENCIL_HALO_PPERMUTES, (STENCIL_HALO_PPERMUTES * plane
                                        * shape.itemsize * nrhs)
    if shape.n_iface <= 0:
        return 0, 0.0
    return 1, float(shape.n_iface) * shape.itemsize * nrhs


def phase_costs(shape: ProblemShape, variant: str, precond: str,
                nrhs: int = 1) -> Dict[str, PhaseCost]:
    """The per-phase resource model of ONE iteration of the
    ``(variant, precond)`` loop at block width ``nrhs``.

    Derived from the single-source ops tables — an unknown variant or
    preconditioner raises the same loud ``KeyError`` the tables
    themselves raise, never a silent default row (the
    cost-model-completeness rule and tests/test_perf_model.py hold this
    contract)."""
    from pcg_mpi_solver_tpu.ops.matvec import (
        PCG_SCALAR_PSUMS, PCG_VECTOR_AXPYS, precond_cycle_cost)

    R = max(1, int(nrhs))
    scalar_psums = PCG_SCALAR_PSUMS[variant]    # KeyError = the contract
    axpys = PCG_VECTOR_AXPYS[variant]
    mv_extra, ps_extra = precond_cycle_cost(precond, shape.mg_degree)

    mv_coll, mv_coll_bytes = _iface_collective(shape, R)
    matvec = PhaseCost(
        flops=shape.matvec_flops() * R,
        hbm_bytes=shape.matvec_bytes() * R,
        coll_count=mv_coll, coll_bytes=mv_coll_bytes)

    # -- preconditioner apply ------------------------------------------
    n = float(shape.n_dof)
    if precond == "jacobi":
        prec = PhaseCost(flops=n * R,
                         hbm_bytes=3.0 * n * shape.itemsize * R)
    elif precond == "block3":
        # batched (n/3) 3x3 block multiplies: 2*9 flops per node, block
        # operand ~3x the vector traffic
        prec = PhaseCost(flops=6.0 * n * R,
                         hbm_bytes=6.0 * n * shape.itemsize * R)
    elif precond == "mg":
        # 2*degree assembled FINE matvecs (each with its own interface
        # collective) + the replicated coarse cycle (geometric series of
        # 8x-coarser levels ~ 1/7 of one fine sweep, collective-free) +
        # the one restriction psum into the replicated coarse vector.
        fine = PhaseCost(flops=shape.matvec_flops() * R,
                         hbm_bytes=shape.matvec_bytes() * R)
        coarse_factor = 1.0 / 7.0
        smooth_bytes = (2 * shape.mg_degree + 2) * 3.0 * n \
            * shape.itemsize * R
        prec = PhaseCost(
            flops=fine.flops * mv_extra * (1.0 + coarse_factor),
            hbm_bytes=(fine.hbm_bytes * mv_extra * (1.0 + coarse_factor)
                       + smooth_bytes),
            coll_count=mv_coll * mv_extra
            + (ps_extra if shape.n_parts > 1 else 0),
            coll_bytes=mv_coll_bytes * mv_extra
            + (float(shape.mg_coarse_dofs) * shape.itemsize * R
               if shape.n_parts > 1 else 0.0))
    else:
        # same loudness as the source tables: a precond no table row
        # covers must never silently model as free
        raise KeyError(precond)

    reduction = PhaseCost(
        flops=2.0 * n * REDUCED_SCALARS * R,
        hbm_bytes=REDUCED_SCALARS * n * shape.itemsize * R,
        coll_count=scalar_psums if shape.n_parts > 1 else 0,
        # the SAME six scalars cross the wire whether one fused psum or
        # classic's three carry them — the variants differ in coll_count
        # (latency), not payload
        coll_bytes=(REDUCED_SCALARS * shape.dot_itemsize * R
                    if shape.n_parts > 1 else 0.0))

    axpy = PhaseCost(
        flops=2.0 * n * axpys * R,
        hbm_bytes=3.0 * n * shape.itemsize * axpys * R)

    return {"matvec": matvec, "precond": prec,
            "reduction": reduction, "axpy": axpy}


def predict_phase_ms(cost: PhaseCost, profile: HwProfile) -> float:
    """Roofline time of one phase: max(compute, HBM) + collective
    latency + collective payload wire time, in milliseconds."""
    t = max(cost.flops / profile.flops_per_s,
            cost.hbm_bytes / profile.hbm_bytes_per_s)
    t += cost.coll_count * profile.coll_latency_s
    t += cost.coll_bytes / profile.ici_bytes_per_s
    return t * 1e3


def cost_model(shape: ProblemShape, variant: str, precond: str,
               nrhs: int = 1,
               profile: Optional[HwProfile] = None) -> Dict[str, Any]:
    """The full model of one combination: per-phase resources + per-phase
    predicted ms + their total — the payload of the ``cost_model``
    telemetry event and the model column of ``pcg-tpu perf-report``."""
    profile = profile or resolve_profile("cpu")
    costs = phase_costs(shape, variant, precond, nrhs)
    phases = {}
    total = 0.0
    for ph in PHASES:
        ms = predict_phase_ms(costs[ph], profile)
        total += ms
        d = costs[ph].to_dict()
        d["model_ms"] = round(ms, 6)
        phases[ph] = d
    return {
        "pcg_variant": variant,
        "precond": precond,
        "nrhs": int(nrhs),
        "backend": shape.backend,
        "n_dof": int(shape.n_dof),
        "n_parts": int(shape.n_parts),
        "profile": profile.name,
        "phases": phases,
        "predicted_ms_per_iter": round(total, 6),
    }


def cost_model_table(shape: ProblemShape, nrhs_set=(1, 8),
                     profile: Optional[HwProfile] = None,
                     variants=PCG_VARIANTS,
                     preconds=PRECONDS) -> Dict[tuple, Dict[str, Any]]:
    """Models for EVERY ``variant x precond x nrhs`` combination — the
    enumeration the analysis/ cost-model-completeness rule proves total
    against the canonical name tables."""
    return {(v, p, int(r)): cost_model(shape, v, p, r, profile)
            for v in variants for p in preconds for r in nrhs_set}


def shape_from_detail(detail) -> Optional[ProblemShape]:
    """The cost-model geometry from a bench line's ``detail`` dict —
    a salvage/insurance line must be self-describing without a live
    solver in hand.  Returns None when the line carries no dof count
    (e.g. the zero-value error sentinel)."""
    n_dof = int(detail.get("n_dof", 0) or 0)
    if n_dof <= 0:
        return None
    mode = str(detail.get("mode", "direct"))
    dtype = str(detail.get("dtype", "float64"))
    return ProblemShape(
        n_dof=n_dof,
        n_parts=int(detail.get("n_parts", 1) or 1),
        # interface payload estimate: one boundary plane ~ n_dof^(2/3)
        # rows — the same heuristic _iface_collective's structured-halo
        # payload model uses (the general iface psum is comparable)
        n_iface=int(max(0.0, float(n_dof) ** (2.0 / 3.0))),
        backend=str(detail.get("backend", "general")),
        itemsize=4 if (mode == "mixed" or dtype == "float32") else 8,
        dot_itemsize=8)


def shape_from_solver(solver) -> ProblemShape:
    """Derive the cost-model geometry from a live Solver (any backend).
    Reads only host-side partition metadata — no device traffic."""
    pm = solver.pm
    scfg = solver.config.solver
    mixed = getattr(solver, "mixed", False)
    itemsize = 4 if (mixed or str(scfg.dtype) == "float32") else 8
    dot_itemsize = 4 if str(scfg.dot_dtype) == "float32" else 8
    groups = []
    for tb in getattr(pm, "type_blocks", None) or ():
        d = int(getattr(tb, "d", 0) or 0)
        node = getattr(tb, "node", None)
        if d and node is not None and getattr(node, "ndim", 0) >= 2:
            # (P, nn, N): total element slots across parts (padding
            # included — it is computed and moved like real elements)
            n_elem = int(node.shape[0]) * int(node.shape[-1])
        elif d:
            n_elem = int(getattr(pm, "glob_n_dof", 0)) // max(1, d // 8)
        else:
            continue
        if d and n_elem:
            groups.append((d, n_elem))
    ops = solver.ops
    return ProblemShape(
        n_dof=int(pm.glob_n_dof),
        n_parts=int(pm.n_parts),
        n_iface=int(getattr(ops, "n_iface", getattr(pm, "n_iface", 0))
                    or 0),
        elem_groups=tuple(groups),
        backend=str(solver.backend),
        itemsize=itemsize,
        dot_itemsize=dot_itemsize,
        mg_degree=int(getattr(ops, "mg_degree", scfg.mg_smooth_degree)),
        mg_coarse_dofs=int(getattr(ops, "mg_coarse_dofs", 0)),
    )


def emit_cost_model(recorder, model: Dict[str, Any]) -> None:
    """Emit one model as the schema-versioned ``cost_model`` event plus
    the ``perf.*`` gauges the run_summary snapshot carries."""
    recorder.event("cost_model", **model)
    recorder.gauge("perf.predicted_ms_per_iter",
                   model["predicted_ms_per_iter"])
    recorder.gauge("perf.model_profile", model["profile"])
    for ph in PHASES:
        recorder.gauge(f"perf.model.{ph}_ms",
                       model["phases"][ph]["model_ms"])
