"""Canonical traced-program matrix for the jaxpr-level contract rules.

Builds REAL solvers (solver/driver.py) on a small 2-device virtual CPU
mesh and traces the programs they would dispatch — the same loop bodies,
carry pytrees and donation wiring the flagship runs, at toy scale — so
the lint proves invariants of the actual code paths rather than of a
hand-mirrored copy that could drift.

The matrix (ISSUE 7): every ``pcg_variant`` x nrhs in {1, 8} x
{distributed ("general"), structured} backend, all direct-f64 (the
reference-parity numerics), plus one all-f32 direct program per backend
for the dtype-discipline rule.  ``fast=True`` reduces to the distributed
backend (both variants, both widths, plus its f32 program) — the
structural headline claims — for the sub-minute pre-hardware-window
gate.

This module imports jax at module load; it must only be imported from
rule execution paths (the analysis package ``__init__`` stays jax-free).
Callers are responsible for the backend environment (the CLI entry
points pin JAX_PLATFORMS=cpu before any jax import; under pytest the
repo conftest does).
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from pcg_mpi_solver_tpu.config import RunConfig, SolverConfig

#: folded-constant size (elements) above which a while-loop operand is a
#: hot-loop-purity violation: a captured operand array this big bloats
#: every AOT export and defeats the donated-carry aliasing.
LOOP_CONST_THRESHOLD_ELEMS = 4096

#: callback primitives that must never appear inside a hot loop body —
#: each one forces a host round-trip per iteration.
CALLBACK_PRIMITIVES = ("pure_callback", "io_callback", "debug_callback",
                       "outside_call", "host_callback")


@dataclasses.dataclass
class Program:
    """One traced canonical program plus its declared contracts."""

    name: str                     # e.g. "step[general,fused,nrhs=8,f64]"
    backend: str                  # "general" | "structured"
    variant: str                  # SolverConfig.pcg_variant
    nrhs: int
    role: str                     # "f64" | "f32" (dtype-discipline scope)
    jaxpr: Any                    # ClosedJaxpr of the dispatched program
    collective_budget: Dict[str, int]   # declared while-body budget
    n_iface: int


@dataclasses.dataclass
class DonationSurface:
    """One donating dispatch surface: the jitted program, example
    (abstract) arguments, and the pytree donated to XLA."""

    name: str
    fn: Any                       # the jitted callable (donation baked in)
    args: Tuple[Any, ...]         # concrete or ShapeDtypeStruct args
    donated: Any                  # the donated argument's pytree

    @property
    def donated_leaves(self) -> List[Any]:
        return jax.tree.leaves(self.donated)

    @property
    def vector_leaves(self) -> int:
        """Donated leaves of rank >= 2 — the partitioned (P, n_loc[,
        nrhs]) Krylov vectors whose in-place aliasing IS the donation
        contract.  Rank-0/1 leaves (per-column stats, budget counters)
        are exempt: copying a handful of scalars per dispatch is free,
        and write-only counters like the carry's ``exec`` leaf have a
        legally-dead input that jax prunes from the executable."""
        return sum(1 for l in self.donated_leaves
                   if len(getattr(l, "shape", ())) >= 2)


_MODEL_CACHE: dict = {}
_MATRIX_CACHE: Dict[bool, List[Program]] = {}


def _model(backend: str, nx: int = 0):
    """Small synthetic cube per backend: the structured slab path needs
    grid[0] divisible by n_parts (driver.py can_structured); mg
    programs need even dims (one 2:1 coarsening) and pass ``nx=4``."""
    from pcg_mpi_solver_tpu.models.synthetic import make_cube_model

    nx = nx or (4 if backend == "structured" else 3)
    if (backend, nx) not in _MODEL_CACHE:
        _MODEL_CACHE[(backend, nx)] = make_cube_model(nx, nx, nx)
    return _MODEL_CACHE[(backend, nx)]


def _mesh2():
    from pcg_mpi_solver_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 2:
        raise RuntimeError(
            "the contract lint traces 2-part SPMD programs; run with "
            "JAX_PLATFORMS=cpu and "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "(the CLI entry points set this up)")
    return make_mesh(2)


def build_solver(backend: str = "general", nx: int = 0,
                 **solver_overrides):
    """A real quasi-static Solver on the 2-device mesh.  One-shot
    dispatch (iters_per_dispatch=0) unless overridden, so ``_step_fn``
    is the single canonical program.  ``nx`` overrides the model size
    (mg programs need an even, coarsenable lattice)."""
    from pcg_mpi_solver_tpu.solver.driver import Solver

    kw = dict(iters_per_dispatch=0)
    kw.update(solver_overrides)
    cfg = RunConfig(solver=SolverConfig(**kw))
    return Solver(_model(backend, nx), cfg, mesh=_mesh2(), n_parts=2,
                  backend=backend)


def step_jaxpr(solver):
    """ClosedJaxpr of the one-shot quasi-static step program."""
    delta = jnp.asarray(1.0, solver.dtype)
    return jax.make_jaxpr(solver._step_fn)(solver.data, solver.un, delta)


def many_jaxpr(solver, nrhs: int):
    """ClosedJaxpr of the one-shot blocked (solve_many) program."""
    progs = solver._ensure_many_programs(nrhs)
    rdt = jnp.float64 if solver.mixed else solver.dtype
    fb = jax.ShapeDtypeStruct((solver.pm.n_parts, solver.pm.n_loc, nrhs),
                              rdt)
    data_abs = jax.eval_shape(lambda d: d, solver.data)
    return jax.make_jaxpr(progs["solve"])(data_abs, fb)


def program_signature(solver) -> str:
    """Content digest of the traced one-shot step: jaxpr text plus every
    folded constant's bytes (a config knob that only changes a baked
    array would not show in the pretty-printed text).  The
    fingerprint-completeness rule compares these across config
    perturbations."""
    jx = step_jaxpr(solver)
    h = hashlib.sha256(str(jx).encode())
    for c in jx.consts:
        a = np.asarray(c)
        h.update(f"{a.shape}:{a.dtype}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def build_programs(fast: bool = False) -> List[Program]:
    """The canonical matrix, cached per process (tracing only — nothing
    executes).  Full: 3 variants x nrhs {1,8} x 2 backends + one all-f32
    program per backend + the mg programs (20 traces, ~3 s).  Fast: the
    distributed backend, classic+fused only (incl. its f32 program, so
    every fast-tier rule has a non-vacuous surface)."""
    if fast in _MATRIX_CACHE:
        return _MATRIX_CACHE[fast]
    out: List[Program] = []
    backends = ("general",) if fast else ("general", "structured")
    # the full matrix carries all three loop formulations (the
    # psum-overlap rule needs the pipelined programs AND the
    # classic/fused negative controls); --fast keeps the pre-ISSUE-11
    # pair so the hardware-queue gate stays ~1s
    variants = (("classic", "fused") if fast
                else ("classic", "fused", "pipelined"))
    for backend in backends:
        for variant in variants:
            s = build_solver(backend, pcg_variant=variant)
            budget = s.ops.body_collective_budget(variant)
            for nrhs in (1, 8):
                jx = step_jaxpr(s) if nrhs == 1 else many_jaxpr(s, nrhs)
                out.append(Program(
                    name=(f"step[{backend},{variant},nrhs={nrhs},f64]"),
                    backend=backend, variant=variant, nrhs=nrhs,
                    role="f64", jaxpr=jx, collective_budget=budget,
                    n_iface=int(s.ops.n_iface)))
        s32 = build_solver(backend, dtype="float32", dot_dtype="float32")
        out.append(Program(
            name=f"step[{backend},classic,nrhs=1,f32]",
            backend=backend, variant="classic", nrhs=1, role="f32",
            jaxpr=step_jaxpr(s32),
            collective_budget=s32.ops.body_collective_budget("classic"),
            n_iface=int(s32.ops.n_iface)))
    if not fast:
        # MG-preconditioned programs (ISSUE 10): both variants x nrhs
        # {1, 8} on the general backend (the acceptance matrix — psum
        # budget gains 2*degree matvec assemblies + the restriction),
        # plus classic x {1, 8} on structured (ppermute accounting:
        # halo count x fine matvecs).  --fast stays general+jacobi
        # only: the mg traces add seconds the pre-window gate spends
        # elsewhere.
        mg_matrix = ([("general", v) for v in ("classic", "fused")]
                     + [("structured", "classic")])
        for backend, variant in mg_matrix:
            s = build_solver(backend, nx=4, precond="mg",
                             pcg_variant=variant)
            budget = s.ops.body_collective_budget(variant, precond="mg")
            for nrhs in (1, 8):
                jx = step_jaxpr(s) if nrhs == 1 else many_jaxpr(s, nrhs)
                out.append(Program(
                    name=f"step[{backend},{variant},mg,nrhs={nrhs},f64]",
                    backend=backend, variant=variant, nrhs=nrhs,
                    role="f64", jaxpr=jx, collective_budget=budget,
                    n_iface=int(s.ops.n_iface)))
    _MATRIX_CACHE[fast] = out
    return out


_MATVEC_CACHE: List = []


def matvec_programs() -> List:
    """(name, ClosedJaxpr) of ONE assembled matvec per backend — general,
    structured and hybrid (a small graded octree) — each a 2-part SPMD
    program, so the interface assembly is real work.  The scope-labels
    rule reads the ``mv/*`` stage labels from them."""
    if _MATVEC_CACHE:
        return _MATVEC_CACHE
    from pcg_mpi_solver_tpu.models.octree import make_octree_model
    from pcg_mpi_solver_tpu.solver import Solver

    octree = make_octree_model(2, 2, 2, max_level=2, n_incl=2, seed=3,
                               load="traction", load_value=1.0)
    for backend in ("general", "structured", "hybrid"):
        if backend == "hybrid":
            cfg = RunConfig(solver=SolverConfig(iters_per_dispatch=0))
            s = Solver(octree, cfg, mesh=_mesh2(), n_parts=2,
                       backend="hybrid")
        else:
            s = build_solver(backend)
        mv = jax.shard_map(s.ops.matvec, mesh=s.mesh,
                           in_specs=(s._specs, s._part_spec),
                           out_specs=s._part_spec, check_vma=False)
        _MATVEC_CACHE.append(
            (f"matvec[{backend}]", jax.make_jaxpr(mv)(s.data, s.un)))
    return _MATVEC_CACHE


# ---------------------------------------------------------------------------
# Donation surfaces (donation-integrity rule): every donate_carry
# dispatch surface of the real drivers, with example abstract arguments
# derived by eval_shape-chaining the surface's own upstream programs —
# no hand-built carry pytrees that could drift from the real schema.
# ---------------------------------------------------------------------------

def donation_surfaces() -> List[DonationSurface]:
    surfaces: List[DonationSurface] = []
    budget = jax.ShapeDtypeStruct((), jnp.int64)

    # 1. one-shot step: donated previous-solution vector (driver.py)
    s1 = build_solver("general")
    delta = jnp.asarray(1.0, s1.dtype)
    surfaces.append(DonationSurface(
        "one-shot step (donated un_prev)", s1._step_fn,
        (s1.data, s1.un, delta), s1.un))

    # 2./3. chunked direct dispatch: donated resumable Krylov carry,
    # scalar and blocked (chunked.py _cycle / driver.py many "cycle")
    s2 = build_solver("general", iters_per_dispatch=5)
    d2 = jnp.asarray(1.0, s2.dtype)
    udi = jax.eval_shape(s2._start_pre_fn, s2.data, d2)
    kudi = jax.eval_shape(s2._amul64_fn, s2.data, udi)
    fext, x0 = jax.eval_shape(s2._start_mid_fn, s2.data, s2.un, d2, kudi)
    kx0 = jax.eval_shape(s2._amul64_fn, s2.data, x0)
    carry, _normr0, _n2b, prec = jax.eval_shape(
        s2._start_post_fn, s2.data, fext, x0, kx0)
    surfaces.append(DonationSurface(
        "chunked direct cycle (donated carry)", s2._engine._cycle_fn,
        (s2.data, fext, prec, carry, budget), carry))

    many = s2._ensure_many_programs(4)
    fb = jax.ShapeDtypeStruct((s2.pm.n_parts, s2.pm.n_loc, 4), s2.dtype)
    mfext, mcarry, _mn, mprec = jax.eval_shape(many["start"], s2.data, fb)
    surfaces.append(DonationSurface(
        "chunked blocked cycle (donated blocked carry)", many["cycle"],
        (s2.data, mfext, mprec, mcarry, budget), mcarry))

    # 4./5. mixed engine: donated f32 inner carry + donated f64 iterate
    # across the refine step (chunked.py)
    s3 = build_solver("general", precision_mode="mixed",
                      iters_per_dispatch=5)
    eng = s3._engine
    r = jax.ShapeDtypeStruct((s3.pm.n_parts, s3.pm.n_loc), jnp.float64)
    sc = jax.ShapeDtypeStruct((), jnp.float64)
    rhat32, tol_cycle, carry32 = jax.eval_shape(
        eng._inner_start_fn, s3.data, r, sc, sc)
    prec32 = jax.ShapeDtypeStruct((s3.pm.n_parts, s3.pm.n_loc),
                                  jnp.float32)
    surfaces.append(DonationSurface(
        "mixed inner cycle (donated f32 carry)", eng._inner_cycle_fn,
        (s3.data, rhat32, prec32, tol_cycle, carry32, budget), carry32))
    xinc32 = jax.ShapeDtypeStruct((s3.pm.n_parts, s3.pm.n_loc),
                                  jnp.float32)
    if getattr(eng, "_refine_pre_fn", None) is not None:
        surfaces.append(DonationSurface(
            "mixed refine (donated f64 iterate)", eng._refine_pre_fn,
            (r, xinc32, sc), r))
    else:
        surfaces.append(DonationSurface(
            "mixed refine (donated f64 iterate)", eng._refine_fn,
            (s3.data, r, r, xinc32, sc), r))
    return surfaces


import re as _re


def _donor_vector_marks(lowered_text: str) -> int:
    """Donor/alias-marked entry arguments of rank >= 2 in the lowered
    StableHLO signature (rank from the tensor<AxBx..> dims prefix)."""
    m = _re.search(r"func\.func public @main\((.*?)\)\s*->", lowered_text,
                   _re.S)
    if m is None:
        return 0
    n = 0
    for arg in m.group(1).split("%arg"):
        tm = _re.search(r"tensor<((?:\d+x)+)\d*[a-z]", arg)
        if tm is None:
            continue
        rank = tm.group(1).count("x")
        if rank >= 2 and ("jax.buffer_donor" in arg
                          or "tf.aliasing_output" in arg):
            n += 1
    return n


def check_donation(surface: DonationSurface) -> List[str]:
    """Errors for one surface: the lowering must donor-mark every
    rank>=2 donated buffer (jax drops an unusable donation SILENTLY —
    no matching output means the dispatch copies instead of aliasing),
    and the COMPILED executable must carry at least one input/output
    alias pair per donated vector leaf."""
    lowered = surface.fn.lower(*surface.args)
    marked = _donor_vector_marks(lowered.as_text())
    want = surface.vector_leaves
    errs = []
    if marked < want:
        errs.append(
            f"{surface.name}: lowering donor-marks only {marked} of "
            f"{want} donated vector (rank>=2) leaves — donation was "
            "dropped (no matching output: the dispatch copies instead "
            "of aliasing)")
        return errs
    hlo = lowered.compile().as_text()
    pairs = hlo.count("may-alias") + hlo.count("must-alias")
    if pairs < want:
        errs.append(
            f"{surface.name}: compiled executable aliases only {pairs} "
            f"buffer(s) for {want} donated vector leaves — XLA did not "
            "honor the donation (silent copy per dispatch)")
    return errs
