"""The one matvec each backend runs, checked where nothing else reaches:
void (ck = 0) cells, the precision its dots request, the
``SolverConfig.pallas`` field that no longer selects anything, and the
structured slab operators against the general backend on a grid whose
flat cell plane crosses a 128-lane tile."""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pcg_mpi_solver_tpu import RunConfig, SolverConfig
from pcg_mpi_solver_tpu.models import make_cube_model
from pcg_mpi_solver_tpu.models.octree import make_octree_model
from pcg_mpi_solver_tpu.ops.matvec import Ops, device_data
from pcg_mpi_solver_tpu.parallel.hybrid import (
    HybridOps, device_data_hybrid, partition_hybrid)
from pcg_mpi_solver_tpu.parallel.mesh import PARTS_AXIS, make_mesh
from pcg_mpi_solver_tpu.parallel.partition import (
    make_elem_part, partition_model)
from pcg_mpi_solver_tpu.parallel.structured import (
    StructuredOps, device_data_structured, partition_structured)
from pcg_mpi_solver_tpu.solver import Solver
from pcg_mpi_solver_tpu.solver.driver import _data_specs
from pcg_mpi_solver_tpu.solver.numpy_ref import NumpyRefSolver

# ny*(nz+1) - 1 = 142 flat cell slots: the plane crosses a lane tile
LANE_CROSSING = (4, 11, 12)


def _octree():
    return make_octree_model(2, 2, 2, max_level=2, n_incl=2, seed=3,
                             load="traction", load_value=1.0)


def _to_parts(gid, x_glob):
    """Global vector -> (P, n_loc) local copies (padding reads 0)."""
    return np.where(gid >= 0, x_glob[np.maximum(gid, 0)], 0.0)


def _to_global(gid, weight, y, n):
    out = np.zeros((n,) + np.shape(y)[2:])
    m = np.asarray(weight) > 0
    out[gid[m]] = np.asarray(y)[m]
    return out


def _void_nodes(model):
    """Global ids of the nodes whose every element has ck = 0."""
    counts = np.diff(model.elem_nodes_offset)
    live = np.repeat(model.ck != 0, counts).astype(float)
    nodes = model.elem_nodes_flat
    touch = np.bincount(nodes, minlength=model.n_node)
    touch_live = np.bincount(nodes, weights=live, minlength=model.n_node)
    return np.flatnonzero((touch > 0) & (touch_live == 0))


def _check_void(model, gid, y_loc, x_glob, dt):
    """K.x against the numpy reference of the voided model, and every
    local copy of a dof whose node touches only void cells exactly 0."""
    y_ref = NumpyRefSolver(model).matvec(
        np.asarray(jnp.asarray(x_glob, dt), np.float64))
    scale = np.abs(y_ref).max()
    tol = 1e-12 if dt == jnp.float64 else 2e-5
    void = _void_nodes(model)
    void_dofs = (3 * void[:, None] + np.arange(3)).ravel()
    assert np.all(y_ref[void_dofs] == 0.0)
    on_void = np.isin(gid, void_dofs)
    assert np.all(np.asarray(y_loc)[on_void] == 0.0)
    return y_ref, scale, tol, len(void)


_STRUCTURED_VOIDS = {
    "z0_column": lambda ix, iy, iz, nx, ny, nz: iz == 0,
    "znz_column": lambda ix, iy, iz, nx, ny, nz: iz == nz - 1,
    "y0_row": lambda ix, iy, iz, nx, ny, nz: iy == 0,
    "yny_row": lambda ix, iy, iz, nx, ny, nz: iy == ny - 1,
    "slab_cut": lambda ix, iy, iz, nx, ny, nz: (ix == nx // 2 - 1)
    | (ix == nx // 2),
    "interior_cell": lambda ix, iy, iz, nx, ny, nz: (ix == 1) & (iy == 5)
    & (iz == 6),
}


_VOID_CASES = [("structured", where, dtype)
               for where in _STRUCTURED_VOIDS
               for dtype in ("float32", "float64")] + [
    ("hybrid", parts, "float64") for parts in (1, 4)]


@pytest.mark.parametrize(
    "backend,where,dtype", _VOID_CASES,
    ids=[f"{b}-{w}-{d}" if b == "structured" else f"{b}-{w}parts"
         for b, w, d in _VOID_CASES])
def test_void_cells_contribute_nothing(backend, where, dtype):
    """ck = 0 cells.  Structured: at a z = 0 column and at the z = nz-1
    column beside the ghost column of the flat plane, at the y = 0 and
    y = ny-1 rows (where the dy*nnz window crosses into the next row),
    on both sides of the slab cut, and at one interior cell.  Hybrid:
    one emptied block per level grid (every brick of it ck = 0, the
    block kept).  K.x equals the numpy reference with those cells
    removed, and a node that touches only void cells reads exactly
    0.0."""
    if backend == "hybrid":
        return _void_hybrid(where)
    nx, ny, nz = LANE_CROSSING
    model = make_cube_model(nx, ny, nz, heterogeneous=True, seed=4)
    ix, iy, iz = np.meshgrid(np.arange(nx), np.arange(ny), np.arange(nz),
                             indexing="ij")
    cut = _STRUCTURED_VOIDS[where](ix, iy, iz, nx, ny, nz)
    model.ck = np.asarray(model.ck, float).copy()
    model.ck[(ix + nx * (iy + ny * iz))[cut]] = 0.0
    dt = jnp.dtype(dtype)
    sp = partition_structured(model, 2)
    ops = StructuredOps.from_partition(sp, dot_dtype=dt)
    data = device_data_structured(sp, dt)
    x = np.random.default_rng(3).normal(size=model.n_dof)
    y = np.asarray(ops.matvec(data, jnp.asarray(_to_parts(sp.dof_gid, x),
                                                dt)))
    y_ref, scale, tol, n_void = _check_void(model, sp.dof_gid, y, x, dt)
    assert n_void > 0 or where == "interior_cell"
    np.testing.assert_allclose(
        _to_global(sp.dof_gid, sp.weight, y, model.n_dof), y_ref, rtol=0,
        atol=tol * scale)


def _void_hybrid(n_parts):
    model = _octree()
    ep = make_elem_part(model, n_parts, method="rcb")
    hp0 = partition_hybrid(model, n_parts, elem_part=ep)
    leaves = np.asarray(model.octree["leaves"])
    brick = model.elem_type == model.octree["brick_type"]
    emptied = []
    for lv in hp0.levels:
        p, b = np.unravel_index(
            np.argmax((lv.ck != 0).reshape(n_parts, lv.nb, -1).sum(-1)),
            (n_parts, lv.nb))
        rel = leaves[:, :3] // lv.size - lv.origin[p, b]
        inside = np.all((rel >= 0) & (rel < (lv.bx, lv.by, lv.bz)), axis=1)
        e = np.flatnonzero(brick & (leaves[:, 3] == lv.size) & (ep == p)
                           & inside)
        assert len(e)
        emptied.append((p, b, e))
    voided = copy.copy(model)
    voided.ck = np.asarray(model.ck, float).copy()
    for _p, _b, e in emptied:
        voided.ck[e] = 0.0
    hp = partition_hybrid(voided, n_parts, elem_part=ep)
    for lv, (p, b, _e) in zip(hp.levels, emptied):
        assert lv.ck[p, b].size and not lv.ck[p, b].any()
    ops = HybridOps.from_hybrid(hp)
    data = device_data_hybrid(hp)
    x = np.random.default_rng(5).normal(size=model.n_dof)
    gid = hp.pm.dof_gid
    y = np.asarray(ops.matvec(data, jnp.asarray(_to_parts(gid, x))))
    y_ref, scale, tol, n_void = _check_void(voided, gid, y, x, jnp.float64)
    assert n_void > 0
    np.testing.assert_allclose(
        _to_global(gid, hp.pm.weight, y, model.n_dof), y_ref, rtol=0,
        atol=tol * scale)


def _dot_precisions(jaxpr):
    """The precision param of every dot_general in a jaxpr, sub-jaxprs
    (loops, scans, shard_map, pjit) included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn.params["precision"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += _dot_precisions(inner)
    return out


def _structured(dt, parts=1):
    sp = partition_structured(make_cube_model(*LANE_CROSSING,
                                              heterogeneous=True), parts)
    return (StructuredOps.from_partition(sp, dot_dtype=dt),
            device_data_structured(sp, dt), sp.n_loc, parts)


def _hybrid(dt, parts):
    hp = partition_hybrid(_octree(), parts)
    return (HybridOps.from_hybrid(hp, dot_dtype=dt),
            device_data_hybrid(hp, dt), hp.pm.n_loc, parts)


def _general(dt, parts=2):
    pm = partition_model(make_cube_model(*LANE_CROSSING,
                                         heterogeneous=True), parts)
    return Ops.from_model(pm, dot_dtype=dt), device_data(pm, dt), \
        pm.n_loc, parts


_PRECISION_CASES = {
    "structured": (lambda: _structured(jnp.float32), jnp.float32, 0, None),
    "structured_nrhs3": (lambda: _structured(jnp.float32), jnp.float32, 3,
                         None),
    "structured_f64_chunked": (lambda: _structured(jnp.float64),
                               jnp.float64, 0, 1),
    "hybrid_1part": (lambda: _hybrid(jnp.float32, 1), jnp.float32, 0, None),
    "hybrid_4parts": (lambda: _hybrid(jnp.float32, 4), jnp.float32, 0, None),
    "general": (lambda: _general(jnp.float32), jnp.float32, 0, None),
    "general_nrhs2": (lambda: _general(jnp.float32), jnp.float32, 2, None),
}


@pytest.mark.parametrize("case", list(_PRECISION_CASES))
def test_matvec_requests_highest_precision(case):
    """Every dot of the matvec requests Precision.HIGHEST: a default f32
    dot on the TPU is one bf16 pass, ~4e-3 off, and stalls the f32
    Krylov iterations (found on the chip)."""
    import dataclasses

    build, dt, nrhs, chunk = _PRECISION_CASES[case]
    ops, data, n_loc, parts = build()
    if chunk is not None:
        ops = dataclasses.replace(ops, chunk_threshold=chunk)
        assert ops._chunk_planes(dt) > 0
    x = jnp.zeros((parts, n_loc) + ((nrhs,) if nrhs else ()), dt)
    precs = _dot_precisions(jax.make_jaxpr(ops.matvec)(data, x).jaxpr)
    assert precs
    highest = jax.lax.Precision.HIGHEST
    for p in precs:
        assert p is not None and all(q == highest for q in p), p


def _f32_matvec_jaxpr(s):
    """The f32 matvec a mixed solver iterates with, traced under its own
    mesh and data specs."""
    P = jax.sharding.PartitionSpec
    data32 = s.data["f32"]
    f = jax.shard_map(lambda d, v: s.ops32.matvec(d, v), mesh=s.mesh,
                      in_specs=(_data_specs(data32), P(PARTS_AXIS)),
                      out_specs=P(PARTS_AXIS), check_vma=False)
    x = jnp.zeros((s.pm.n_parts, s.pm.n_loc), jnp.float32)
    return str(jax.make_jaxpr(f)(data32, x))


@pytest.mark.parametrize("parts", [1, 4])
@pytest.mark.parametrize("backend", ["structured", "hybrid"])
def test_pallas_auto_and_off_build_the_same_solver(backend, parts):
    """``pallas`` selects nothing: "auto" and "off" build the same ops
    and trace the same f32 matvec."""
    model = (make_cube_model(8, 4, 4, heterogeneous=True)
             if backend == "structured" else _octree())
    built = []
    for mode in ("auto", "off"):
        cfg = RunConfig(solver=SolverConfig(precision_mode="mixed",
                                            pallas=mode))
        s = Solver(model, cfg, mesh=make_mesh(parts), n_parts=parts,
                   backend=backend)
        assert s.backend == backend
        built.append((s.ops, s.ops32, _f32_matvec_jaxpr(s)))
    assert built[0] == built[1]


@pytest.mark.parametrize("mode", ["on", "interpret"])
def test_pallas_kernel_modes_refused(mode):
    with pytest.raises(ValueError, match="no Pallas matvec kernel"):
        SolverConfig(pallas=mode)


def test_hybrid_newmark_builds_with_pallas_auto():
    from pcg_mpi_solver_tpu.solver.newmark import NewmarkSolver

    cfg = RunConfig(solver=SolverConfig(tol=1e-8, pallas="auto"))
    s = NewmarkSolver(_octree(), cfg, mesh=make_mesh(2), n_parts=2,
                      dt=0.1, backend="hybrid")
    # the Newmark operator wraps the backend's ops with its mass shift
    assert s.backend == "hybrid" and isinstance(s.ops.base, HybridOps)


@pytest.fixture(scope="module", params=[1, 2], ids=["1part", "2parts"])
def slab_and_general(request):
    """The structured slab ops and the general ops over the same
    heterogeneous lane-crossing cube."""
    P = request.param
    model = make_cube_model(*LANE_CROSSING, heterogeneous=True, seed=6)
    sp = partition_structured(model, P)
    pm = partition_model(model, P)
    return (model,
            (StructuredOps.from_partition(sp), device_data_structured(sp),
             sp),
            (Ops.from_model(pm), device_data(pm), pm))


def _nodes_global(pm, y, n_node):
    return _to_global(pm.node_gid, pm.node_weight, y, n_node)


def _elem_ids(model, backend, pm):
    """Global element id of each (P, cells) slot of elem_strain/
    nodal_average: the slab's (x, y, z) cell order, or the general type
    block's ascending-id order (-1 on padding)."""
    if backend == "structured":
        nx, ny, nz, _h = model.grid
        P = pm.n_parts
        ix, iy, iz = np.meshgrid(np.arange(pm.nxc), np.arange(ny),
                                 np.arange(nz), indexing="ij")
        return np.stack([((ix + p * pm.nxc) + nx * (iy + ny * iz)).ravel()
                         for p in range(P)])
    N = pm.type_blocks[0].ck.shape[1]
    out = np.full((pm.n_parts, N), -1)
    for p in range(pm.n_parts):
        e = np.flatnonzero(pm.elem_part == p)
        out[p, :len(e)] = e
    return out


_OPERATORS = ["diag", "node_block_diag", "iface_assemble", "nodal_average",
              "elem_strain"]


@pytest.mark.parametrize("op", _OPERATORS)
def test_structured_operators_match_general(slab_and_general, op):
    """Every slab operator besides the matvec against the general
    backend's on the same model, compared in global numbering."""
    model, *pairs = slab_and_general
    rng = np.random.default_rng(8)
    x = rng.normal(size=model.n_dof)
    vals = rng.normal(size=(model.n_elem, 6))
    got = []
    for backend, (ops, data, pm) in zip(("structured", "general"), pairs):
        xl = jnp.asarray(_to_parts(pm.dof_gid, x))
        if op == "diag":
            got.append(_to_global(pm.dof_gid, pm.weight,
                                  ops.diag(data), model.n_dof))
        elif op == "node_block_diag":
            got.append(_nodes_global(pm, ops.node_block_diag(data),
                                     model.n_node))
        elif op == "iface_assemble":
            y = np.asarray(ops.iface_assemble(data,
                                              ops.matvec_local(data, xl)))
            # every local copy of a shared dof holds the assembled sum
            y_glob = _to_global(pm.dof_gid, pm.weight, y, model.n_dof)
            on = pm.dof_gid >= 0
            np.testing.assert_allclose(
                y[on], y_glob[pm.dof_gid[on]], rtol=0,
                atol=1e-12 * np.abs(y_glob).max())
            got.append(y_glob)
        else:
            ids = _elem_ids(model, backend, pm)
            if op == "nodal_average":
                v = np.where(ids[:, None, :] >= 0,
                             vals[np.maximum(ids, 0)].transpose(0, 2, 1),
                             0.0)
                avg = ops.nodal_average(data, [jnp.asarray(v)])
                got.append(_nodes_global(
                    pm, np.asarray(avg).transpose(0, 2, 1), model.n_node))
            else:
                eps = np.asarray(ops.elem_strain(data, xl)[0])
                out = np.zeros((model.n_elem, 6))
                m = ids >= 0
                out[ids[m]] = eps.transpose(0, 2, 1)[m]
                got.append(out)
    scale = np.abs(got[1]).max()
    assert scale > 0
    np.testing.assert_allclose(got[0], got[1], rtol=0, atol=1e-12 * scale)
