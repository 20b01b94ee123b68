"""Export pipeline: run store, frames, VTU writing/readback, end-to-end."""

import numpy as np
import pytest

from pcg_mpi_solver_tpu.config import RunConfig, SolverConfig, TimeHistoryConfig
from pcg_mpi_solver_tpu.models.synthetic import make_cube_model
from pcg_mpi_solver_tpu.parallel.mesh import make_mesh
from pcg_mpi_solver_tpu.solver.driver import Solver
from pcg_mpi_solver_tpu.utils.io import RunStore
from pcg_mpi_solver_tpu.vtk.export import export_vtk
from pcg_mpi_solver_tpu.vtk.writer import read_vtu_arrays, write_vtu, VTK_QUAD


def test_vtu_roundtrip(tmp_path):
    pts = np.array([[0, 0, 0], [1, 0, 0], [1, 1, 0], [0, 1, 0]], float)
    conn = np.array([0, 1, 2, 3])
    offs = np.array([4])
    path = write_vtu(str(tmp_path / "quad"), pts, conn, offs,
                     np.array([VTK_QUAD]),
                     point_data={"T": np.array([1.0, 2.0, 3.0, 4.0]),
                                 "U": (pts[:, 0], pts[:, 1], pts[:, 2])})
    arrs = read_vtu_arrays(path)
    np.testing.assert_allclose(arrs["Points"], pts)
    np.testing.assert_array_equal(arrs["connectivity"], conn)
    np.testing.assert_allclose(arrs["T"], [1, 2, 3, 4])
    assert arrs["U"].shape == (4, 3)


def test_vtu_paraview_header(tmp_path):
    """File begins with a valid VTKFile XML declaration ParaView accepts."""
    pts = np.zeros((3, 3))
    path = write_vtu(str(tmp_path / "t"), pts, np.array([0, 1, 2]),
                     np.array([3]), np.array([5]))
    head = open(path, "rb").read(200)
    assert b"<VTKFile type=" in head and b"UnstructuredGrid" in head


def test_solve_with_export_roundtrip(tmp_path):
    """Full pipeline: solve -> store frames -> reassemble global U."""
    model = make_cube_model(4, 4, 4, load="dirichlet")
    cfg = RunConfig(
        scratch_path=str(tmp_path), run_id="7",
        solver=SolverConfig(tol=1e-9, max_iter=2000),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 0.5, 1.0],
                                       export_frame_rate=1),
    )
    s = Solver(model, cfg, mesh=make_mesh(4), n_parts=4)
    store = RunStore(cfg.result_path, cfg.model_name)
    s.solve(store=store)

    # Dof map covers every dof exactly once
    dof_map = store.read_map("Dof")
    assert sorted(dof_map) == list(range(model.n_dof))
    # 3 frames: initial state + 2 steps
    assert store.n_frames("U") == 3
    u2 = np.zeros(model.n_dof)
    u2[dof_map] = store.read_frame("U", 2)
    np.testing.assert_allclose(u2, s.displacement_global(), rtol=1e-12)
    # frame 1 at delta=0.5 is half of frame 2 (linear problem)
    u1 = np.zeros(model.n_dof)
    u1[dof_map] = store.read_frame("U", 1)
    np.testing.assert_allclose(u1, 0.5 * u2, rtol=1e-5, atol=1e-10)
    # time data recorded
    td = store.read_time_data(4)
    assert list(td["Flag"]) == [0, 0] and len(td["Iter"]) == 2

    # VTK export (Full + MidSlices)
    files = export_vtk(model, store, ["U"], "Full")
    assert len(files) == 3
    arrs = read_vtu_arrays(files[2])
    assert arrs["U"].shape == (model.n_node, 3)
    np.testing.assert_allclose(arrs["U"].ravel(),
                               u2.reshape(-1, 3).ravel(), rtol=1e-6)
    files_mid = export_vtk(model, store, ["U"], "MidSlices")
    assert len(files_mid) == 3


def test_boundary_mode_differs_from_full_on_octree(tmp_path):
    """Real Boundary mode (face-incidence counting, export_vtk.py:105-113):
    the octree model stores EVERY element face, so Full includes interior
    faces and Boundary must be a strict subset."""
    from pcg_mpi_solver_tpu.models.octree import make_octree_model
    from pcg_mpi_solver_tpu.vtk.export import _select_faces

    model = make_octree_model(2, 2, 2, max_level=2, n_incl=2, seed=3)
    full = _select_faces(model, "Full")
    bnd = _select_faces(model, "Boundary")
    assert 0 < len(bnd) < len(full)
    # every boundary face has all nodes on the domain hull OR is a
    # coarse/fine mismatch face... for this conforming face list, incidence-1
    # quads must lie on the axis-aligned hull:
    coords = model.node_coords
    flat, offset = model.faces_flat, model.faces_offset
    hull = np.zeros(len(coords), dtype=bool)
    for ax in range(3):
        hull |= (np.abs(coords[:, ax] - coords[:, ax].min()) < 1e-12)
        hull |= (np.abs(coords[:, ax] - coords[:, ax].max()) < 1e-12)
    for f in bnd[:50]:
        nodes = flat[offset[f]:offset[f + 1]]
        assert hull[nodes].all()

    # end-to-end: solve 1 step, export Boundary, check vtu face count
    cfg = RunConfig(
        scratch_path=str(tmp_path), run_id="oct",
        solver=SolverConfig(tol=1e-7, max_iter=2000),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 1.0]),
    )
    s = Solver(model, cfg, mesh=make_mesh(4), n_parts=4)
    store = RunStore(cfg.result_path, cfg.model_name)
    s.solve(store=store)
    # (both modes write the same frame filenames — read each before the next)
    files_b = export_vtk(model, store, ["U"], "Boundary")
    nb = len(read_vtu_arrays(files_b[0])["offsets"])
    files_f = export_vtk(model, store, ["U"], "Full")
    nf = len(read_vtu_arrays(files_f[0])["offsets"])
    assert nb == len(bnd) and nf == len(full) and nb < nf


def test_frame_pool_matches_serial(tmp_path):
    """The multiprocessing frame pool produces byte-identical .vtu files to
    the serial loop."""
    model = make_cube_model(3, 3, 3, load="dirichlet")
    cfg = RunConfig(
        scratch_path=str(tmp_path), run_id="pool",
        solver=SolverConfig(tol=1e-9, max_iter=1000),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 0.25, 0.5, 1.0],
                                       export_frame_rate=1),
    )
    s = Solver(model, cfg, mesh=make_mesh(1), n_parts=1)
    store = RunStore(cfg.result_path, cfg.model_name)
    s.solve(store=store)
    serial = export_vtk(model, store, ["U"], "Full")
    blobs = [open(f, "rb").read() for f in serial]
    pooled = export_vtk(model, store, ["U"], "Full", n_workers=3)
    assert pooled == serial
    for f, blob in zip(pooled, blobs):
        assert open(f, "rb").read() == blob


def test_existing_run_dir_renamed(tmp_path):
    store = RunStore(str(tmp_path / "Results_Run1"), "m")
    store.prepare()
    store.write_map("Dof", np.arange(3))
    store2 = RunStore(str(tmp_path / "Results_Run1"), "m")
    store2.prepare()  # must not clobber; old dir renamed with timestamp
    import glob
    assert len(glob.glob(str(tmp_path / "Results_Run1_*"))) == 1


def test_probe_dof_history(tmp_path):
    model = make_cube_model(3, 3, 3, load="traction")
    probe = [3 * (model.n_node - 1)]  # ux of the last node
    cfg = RunConfig(
        scratch_path=str(tmp_path), run_id="2",
        solver=SolverConfig(tol=1e-9, max_iter=2000),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 0.25, 1.0],
                                       plot_flag=True, probe_dofs=probe),
    )
    s = Solver(model, cfg, mesh=make_mesh(1), n_parts=1)
    store = RunStore(cfg.result_path, cfg.model_name)
    s.solve(store=store)
    import numpy as np
    dat = np.load(f"{store.plot_path}/{cfg.model_name}_PlotData.npz",
                  allow_pickle=True)["PlotData"].item()
    u_hist = dat["Plot_U"]
    assert u_hist.shape == (1, 2)
    np.testing.assert_allclose(u_hist[0, 0] * 4.0, u_hist[0, 1], rtol=1e-5)


def test_frame_shard_validation(tmp_path):
    """read_frame must reject stale, incomplete, or mixed-generation
    shard sets instead of merging them into a garbled frame."""
    import pytest

    from pcg_mpi_solver_tpu.utils.io import RunStore

    store = RunStore(str(tmp_path / "res"))
    store.prepare()
    store.write_frame_shard("U", 0, np.arange(3.0), 0, 4, 8)
    # incomplete: missing parts 4..8
    with pytest.raises(ValueError, match="incomplete"):
        store.read_frame("U", 0)
    store.write_frame_shard("U", 0, np.arange(2.0), 4, 8, 8)
    np.testing.assert_array_equal(store.read_frame("U", 0),
                                  [0.0, 1.0, 2.0, 0.0, 1.0])
    assert store.n_frames("U") == 1
    # mixed generation: stale shard from an older 4-part layout
    store.write_frame_shard("U", 0, np.arange(1.0), 0, 2, 4)
    with pytest.raises(ValueError, match="mixed-generation"):
        store.read_frame("U", 0)


def test_frame_shard_gap_detected(tmp_path):
    import pytest

    from pcg_mpi_solver_tpu.utils.io import RunStore

    store = RunStore(str(tmp_path / "res"))
    store.prepare()
    store.write_frame_shard("U", 1, np.arange(3.0), 0, 2, 6)
    store.write_frame_shard("U", 1, np.arange(2.0), 4, 6, 6)
    with pytest.raises(ValueError, match="tile contiguously"):
        store.read_frame("U", 1)


def test_backend_probe_skips(monkeypatch):
    """The probe must not spawn subprocesses when it cannot add info."""
    import os

    import jax

    from pcg_mpi_solver_tpu.utils.backend_probe import probe_backend

    # conftest pins JAX_PLATFORMS=cpu for the test session
    assert os.environ.get("JAX_PLATFORMS", "").lower() == "cpu"
    ok, detail = probe_backend(timeout_s=1.0)
    assert ok and "skipped" in detail
    # with a live backend in this process the probe is skipped too
    jax.devices()
    monkeypatch.setenv("JAX_PLATFORMS", "")
    ok, detail = probe_backend(timeout_s=1.0)
    assert ok and "already live" in detail
