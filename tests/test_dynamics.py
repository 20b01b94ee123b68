"""Explicit central-difference dynamics: parity with an independent numpy
integrator, energy sanity, partition-count independence, and the crack-tip
post-processing chain on dynamic frames (reference's vestigial dynamics era
made live: DiagM/Vd/Cm/Me/dt, partition_mesh.py:324-330, 172-175)."""

import numpy as np
import pytest

from pcg_mpi_solver_tpu import RunConfig
from pcg_mpi_solver_tpu.models import make_cube_model
from pcg_mpi_solver_tpu.parallel.mesh import make_mesh
from pcg_mpi_solver_tpu.solver.dynamics import DynamicsSolver, stable_dt
from pcg_mpi_solver_tpu.solver.numpy_ref import NumpyRefSolver


def numpy_central_difference(model, dt, n_steps, damping=0.0, delta=1.0):
    """Independent host-side integrator (same scheme, plain numpy)."""
    ref = NumpyRefSolver(model)
    n = model.n_dof
    eff = np.zeros(n, dtype=bool)
    eff[model.dof_eff] = True
    inv_m = np.where(model.diag_M > 0, 1.0 / model.diag_M, 0.0)
    u = np.zeros(n)
    v = np.zeros(n)
    out = []
    for s in range(n_steps):
        a = inv_m * (model.F * delta - ref.matvec(u)) - damping * v
        v = v + dt * a
        u = u + dt * v
        u[~eff] = model.Ud[~eff] * delta
        v[~eff] = model.Vd[~eff] * delta
        out.append(u.copy())
    return np.stack(out)


@pytest.fixture(scope="module")
def model():
    return make_cube_model(4, 3, 3, E=100.0, nu=0.25, rho=1.0,
                           load="traction", load_value=1.0,
                           heterogeneous=True)


def test_matches_numpy_integrator(model):
    dt = stable_dt(model, safety=0.5)
    n_steps = 25
    ref_traj = numpy_central_difference(model, dt, n_steps, damping=0.05)

    dyn = DynamicsSolver(model, RunConfig(), mesh=make_mesh(4), n_parts=4,
                         dt=dt, damping=0.05,
                         probe_dofs=(6, 13))
    res = dyn.run(n_steps, export_every=5)
    np.testing.assert_allclose(res.u, ref_traj[-1], rtol=1e-9, atol=1e-12)
    # probe history matches the reference trajectory at those dofs
    np.testing.assert_allclose(res.probe_u[0], ref_traj[:, 6],
                               rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(res.probe_u[1], ref_traj[:, 13],
                               rtol=1e-9, atol=1e-12)
    # frames every 5 steps
    assert len(res.frames) == 5
    np.testing.assert_allclose(res.frames[1], ref_traj[9],
                               rtol=1e-9, atol=1e-12)


def test_partition_independence(model):
    dt = stable_dt(model, safety=0.5)
    r1 = DynamicsSolver(model, RunConfig(), mesh=make_mesh(1), n_parts=1,
                        dt=dt).run(20)
    r8 = DynamicsSolver(model, RunConfig(), mesh=make_mesh(8), n_parts=8,
                        dt=dt).run(20)
    np.testing.assert_allclose(r8.u, r1.u, rtol=1e-10, atol=1e-13)


def test_stability_and_damping(model):
    """Undamped: bounded oscillation.  Damped: decays toward the static
    solution (long-time limit of mass-damped dynamics)."""
    dt = stable_dt(model, safety=0.5)
    dyn = DynamicsSolver(model, RunConfig(), mesh=make_mesh(4), n_parts=4,
                         dt=dt, damping=2.0)
    res = dyn.run(4000)
    from pcg_mpi_solver_tpu.solver.numpy_ref import NumpyRefSolver

    stat = NumpyRefSolver(model).solve(tol=1e-10)
    np.testing.assert_allclose(res.u, stat.u, rtol=0,
                               atol=5e-3 * np.abs(stat.u).max())


def test_crack_tip_chain(model):
    """Dynamic frames feed the crack-tip post-processing utilities."""
    from pcg_mpi_solver_tpu.utils.postproc import smooth_moving_average

    dt = stable_dt(model, safety=0.5)
    dyn = DynamicsSolver(model, RunConfig(), mesh=make_mesh(4), n_parts=4,
                         dt=dt, probe_dofs=(3,))
    res = dyn.run(50)
    sm = smooth_moving_average(res.probe_u[0], half_window=5)
    assert sm.shape == res.probe_u[0].shape
    assert np.isfinite(sm).all()


def test_dynamics_hybrid_matches_general():
    """Octree dynamics on the hybrid level-grid backend: identical
    trajectory to the general gather/scatter path."""
    from pcg_mpi_solver_tpu.models.octree import make_octree_model
    from pcg_mpi_solver_tpu.solver.dynamics import DynamicsSolver, stable_dt

    model = make_octree_model(2, 2, 2, max_level=2, n_incl=2, seed=3,
                              load="traction", load_value=1.0)
    dt = 0.5 * stable_dt(model)
    out = {}
    for b in ("general", "hybrid"):
        dyn = DynamicsSolver(model, RunConfig(), mesh=make_mesh(4),
                             n_parts=4, dt=dt, damping=0.1, backend=b)
        assert dyn.backend == b
        res = dyn.run(50)
        out[b] = np.asarray(res.u)
    scale = max(np.abs(out["general"]).max(), 1e-30)
    np.testing.assert_allclose(out["hybrid"], out["general"],
                               rtol=0, atol=1e-11 * scale)
