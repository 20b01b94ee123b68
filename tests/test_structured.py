"""Structured slab fast path vs dense assembly and vs the general backend."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pcg_mpi_solver_tpu.config import RunConfig, SolverConfig, TimeHistoryConfig
from pcg_mpi_solver_tpu.models.synthetic import make_cube_model
from pcg_mpi_solver_tpu.parallel.mesh import PARTS_AXIS, make_mesh
from pcg_mpi_solver_tpu.parallel.structured import (
    StructuredOps,
    device_data_structured,
    partition_structured,
)
from pcg_mpi_solver_tpu.solver.driver import Solver, _data_specs


def to_parts(sp, x_glob):
    out = np.zeros((sp.n_parts, sp.n_loc))
    for p in range(sp.n_parts):
        out[p] = x_glob[sp.dof_gid[p]]
    return out


def to_global(sp, y):
    out = np.zeros(sp.glob_n_dof)
    m = sp.weight > 0
    out[sp.dof_gid[m]] = np.asarray(y)[m]
    return out


@pytest.mark.parametrize("n_parts", [1, 4])
def test_structured_matvec_vs_dense(n_parts):
    model = make_cube_model(8, 3, 5, h=0.5, nu=0.3, heterogeneous=True)
    sp = partition_structured(model, n_parts)
    ops = StructuredOps.from_partition(sp)  # unsharded (roll-based halo)
    data = device_data_structured(sp)

    x = np.random.default_rng(0).normal(size=model.n_dof)
    y = ops.matvec(data, jnp.asarray(to_parts(sp, x)))
    y_ref = model.assemble_csr() @ x
    np.testing.assert_allclose(to_global(sp, y), y_ref, rtol=1e-10, atol=1e-10)
    # every duplicated plane copy fully assembled
    for p in range(n_parts):
        np.testing.assert_allclose(np.asarray(y)[p], y_ref[sp.dof_gid[p]],
                                   rtol=1e-10, atol=1e-10)


def test_structured_diag_vs_assembled():
    model = make_cube_model(4, 3, 3, heterogeneous=True)
    sp = partition_structured(model, 2)
    ops = StructuredOps.from_partition(sp)
    d = ops.diag(device_data_structured(sp))
    np.testing.assert_allclose(to_global(sp, d), model.assemble_diag(), rtol=1e-12)


def test_structured_matvec_sharded_8dev():
    model = make_cube_model(16, 4, 4, heterogeneous=True)
    sp = partition_structured(model, 8)
    mesh = make_mesh(8)
    ops = StructuredOps.from_partition(sp, axis_name=PARTS_AXIS)
    data = device_data_structured(sp)
    P = jax.sharding.PartitionSpec
    f = jax.jit(jax.shard_map(lambda d, v: ops.matvec(d, v), mesh=mesh,
                              in_specs=(_data_specs(data), P(PARTS_AXIS)),
                              out_specs=P(PARTS_AXIS), check_vma=False))
    x = np.random.default_rng(1).normal(size=model.n_dof)
    y = f(data, jnp.asarray(to_parts(sp, x)))
    y_ref = model.assemble_csr() @ x
    np.testing.assert_allclose(to_global(sp, y), y_ref, rtol=1e-9, atol=1e-9)


@pytest.mark.parametrize("mode", ["direct", "mixed"])
def test_structured_solver_matches_general(mode):
    """Full solve through the driver: structured backend == general backend
    (same displacements, comparable iteration count)."""
    model = make_cube_model(8, 4, 4, E=5.0, load="traction", heterogeneous=True)
    cfg = RunConfig(
        solver=SolverConfig(tol=1e-9, max_iter=3000, precision_mode=mode),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 1.0]),
    )
    mesh = make_mesh(4)
    s_st = Solver(model, cfg, mesh=mesh, n_parts=4, backend="structured")
    assert s_st.backend == "structured"
    r_st = s_st.step(1.0)
    s_gen = Solver(model, cfg, mesh=mesh, n_parts=4, backend="general")
    r_gen = s_gen.step(1.0)
    assert r_st.flag == 0 and r_gen.flag == 0
    u_gen = s_gen.displacement_global()
    np.testing.assert_allclose(s_st.displacement_global(), u_gen,
                               rtol=1e-6, atol=1e-9 * np.abs(u_gen).max())
    assert abs(r_st.iters - r_gen.iters) <= max(3, 0.05 * r_gen.iters)


def test_auto_backend_selection():
    model = make_cube_model(8, 4, 4)
    mesh = make_mesh(4)
    assert Solver(model, RunConfig(), mesh=mesh, n_parts=4).backend == "structured"
    # multi-type model has no grid metadata -> general
    model2 = make_cube_model(8, 4, 4, n_types=2)
    assert Solver(model2, RunConfig(), mesh=mesh, n_parts=4).backend == "general"
    # nx not divisible by parts -> general
    model3 = make_cube_model(6, 4, 4)
    assert Solver(model3, RunConfig(), mesh=mesh, n_parts=4).backend == "general"

def test_chunked_f64_matvec_matches_unchunked():
    """The x-slab-chunked f64 matvec (memory-bounded path for big meshes)
    must agree with the one-shot path exactly."""
    import dataclasses

    from pcg_mpi_solver_tpu.parallel.structured import (
        StructuredOps, device_data_structured, partition_structured)

    model = make_cube_model(12, 6, 5, heterogeneous=True)
    sp = partition_structured(model, 2)
    data = device_data_structured(sp, jnp.float64)
    ops = StructuredOps.from_partition(sp)
    ops_chunked = dataclasses.replace(ops, chunk_threshold=1)
    assert ops_chunked._chunk_planes(jnp.float64) > 0
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, sp.n_loc)))
    y0 = ops.matvec_local(data, x)
    y1 = ops_chunked.matvec_local(data, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               rtol=1e-13, atol=1e-13)


def _gse_5d_local(ops, data, x):
    """The gse stencil on the 5D cell grid (z the minor axis), built from
    the ops' grid helpers: what the flat (y, z) plane must reproduce."""
    from pcg_mpi_solver_tpu.models.element import HEX_CORNERS

    blk = data["blocks"][0]
    u = ops._gather_cells(ops._grid(x))
    ck = ops._ck_grid(blk["ck"])
    v = jnp.einsum("de,pexyz->pdxyz", blk["Ke"], ck[:, None] * u,
                   precision=ops.precision)
    y = None
    for a, (dx, dy, dz) in enumerate(HEX_CORNERS.astype(int)):
        t = jnp.pad(v[:, 3 * a:3 * a + 3],
                    ((0, 0), (0, 0), (dx, 1 - dx), (dy, 1 - dy),
                     (dz, 1 - dz)))
        y = t if y is None else y + t
    return y


# the shapes the flat plane is checked at: odd, non-cubic grids, the
# fused-kernel tests' former shapes, and (4, 11, 12), whose cell plane
# ny*(nz+1) - 1 = 142 slots crosses a 128-lane tile
_PATHS = {"f32": ("float32", False, 1), "f64": ("float64", False, 1),
          "f64_chunked": ("float64", True, 1),
          "f32_nrhs3": ("float32", False, 3),
          "f64_nrhs3": ("float64", False, 3)}
_FLAT_CASES = [
    ((5, 4, 3), 1, "float32", False, 1),
    ((5, 4, 3), 1, "float64", False, 1),
    ((6, 5, 3), 2, "float32", False, 1),
    ((6, 5, 3), 2, "float64", False, 1),
    ((6, 5, 3), 2, "float64", True, 1),
    ((5, 4, 3), 1, "float64", False, 3),
    ((6, 5, 3), 2, "float32", False, 3),
]
_FLAT_IDS = ["5x4x3_f32", "5x4x3_f64", "6x5x3_2parts_f32",
             "6x5x3_2parts_f64", "6x5x3_2parts_f64_chunked",
             "5x4x3_f64_nrhs3", "6x5x3_2parts_f32_nrhs3"]
for _dims, _parts in [((6, 5, 4), 1), ((6, 5, 4), 2), ((4, 4, 4), 1),
                      ((4, 4, 4), 2), ((7, 3, 5), 1), ((4, 11, 12), 1),
                      ((4, 11, 12), 2)]:
    for _name, (_dt, _chunked, _nrhs) in _PATHS.items():
        _FLAT_CASES.append((_dims, _parts, _dt, _chunked, _nrhs))
        _FLAT_IDS.append("x".join(map(str, _dims))
                         + (f"_{_parts}parts" if _parts > 1 else "")
                         + f"_{_name}")


@pytest.mark.parametrize("dims,n_parts,dtype,chunked,nrhs", _FLAT_CASES,
                         ids=_FLAT_IDS)
def test_flat_plane_gse_matches_5d_and_numpy(dims, n_parts, dtype, chunked,
                                             nrhs):
    """The gse matvec on the flattened (y, z) node plane against the
    same stencil on the 5D grid and against the numpy reference, on odd,
    non-cubic grids, unsharded (roll halo) with 1 and 2 parts, through
    the f64 x-chunked loop and on an RHS block."""
    import dataclasses

    from pcg_mpi_solver_tpu.solver.numpy_ref import NumpyRefSolver

    model = make_cube_model(*dims, h=0.5, nu=0.3, heterogeneous=True, seed=3)
    sp = partition_structured(model, n_parts)
    dt = jnp.dtype(dtype)
    data = device_data_structured(sp, dt)
    ops = StructuredOps.from_partition(sp, dot_dtype=dt)
    if chunked:
        ops = dataclasses.replace(ops, chunk_threshold=1)
        assert ops._chunk_planes(dt) > 0

    # the flat cell plane: ghost column exactly 0, every real cell its ck
    nx, ny, nz = dims
    ck = np.asarray(data["blocks"][0]["ck"])
    assert ck.shape == (n_parts, nx // n_parts, ny * (nz + 1) - 1)
    ghost = np.arange(ck.shape[2]) % (nz + 1) == nz
    assert np.all(ck[..., ghost] == 0.0)
    np.testing.assert_array_equal(
        ck[..., ~ghost], np.asarray(sp.ck, dt).reshape(n_parts, nx // n_parts,
                                                       -1))

    rng = np.random.default_rng(7)
    xs = rng.normal(size=(nrhs, model.n_dof))
    xp = np.stack([to_parts(sp, c) for c in xs], axis=-1)
    x = jnp.asarray(xp[..., 0] if nrhs == 1 else xp, dt)
    y_loc = np.asarray(ops.matvec_local(data, x)).reshape(xp.shape)
    y = np.asarray(ops.matvec(data, x)).reshape(xp.shape)

    ref = NumpyRefSolver(model)
    ops5 = StructuredOps.from_partition(sp, dot_dtype=dt)
    rtol = 1e-12 if dtype == "float64" else 2e-6
    for j in range(nrhs):
        xj = jnp.asarray(xp[..., j], dt)
        y5_loc = _gse_5d_local(ops5, data, xj)
        y5 = np.asarray(ops5._halo(y5_loc)).reshape(n_parts, -1)
        y5_loc = np.asarray(y5_loc).reshape(n_parts, -1)
        y_ref = ref.matvec(np.asarray(jnp.asarray(xs[j], dt), np.float64))
        scale = np.abs(y_ref).max()
        np.testing.assert_allclose(y_loc[..., j], y5_loc, rtol=0,
                                   atol=rtol * scale)
        np.testing.assert_allclose(y[..., j], y5, rtol=0, atol=rtol * scale)
        np.testing.assert_allclose(to_global(sp, y[..., j]), y_ref, rtol=0,
                                   atol=(10 * rtol) * scale)


@pytest.mark.parametrize("fill", [
    4 * 5 / 128,                          # ny*nz over ceil(ny*(nz+1)-1, 128)
], ids=["gse"])
def test_stencil_lane_fill_gauge(fill):
    """Structured solves record the lane fill of their stencil layout;
    the general backend records none."""
    from pcg_mpi_solver_tpu.obs.metrics import MetricsRecorder

    model = make_cube_model(8, 4, 5)
    rec = MetricsRecorder()
    Solver(model, RunConfig(), mesh=make_mesh(2), n_parts=2,
           backend="structured", recorder=rec)
    assert rec.gauges["structured.stencil_lane_fill"] == round(fill, 4)
    gen = MetricsRecorder()
    Solver(model, RunConfig(), mesh=make_mesh(2), n_parts=2,
           backend="general", recorder=gen)
    assert "structured.stencil_lane_fill" not in gen.gauges
