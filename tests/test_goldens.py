"""Pinned golden numbers for fixed flagship models: the reference's
committed notebook outputs play this role (solver_demo.ipynb cell 12);
here the goldens are asserted in CI so any numerics regression fails a
test.  A deliberate algorithm change that moves
one of these must re-pin it with justification in the commit message.

The octree golden lives in tests/test_octree.py (same pattern)."""

import numpy as np

from pcg_mpi_solver_tpu.config import RunConfig, SolverConfig, TimeHistoryConfig
from pcg_mpi_solver_tpu.models import make_cube_model
from pcg_mpi_solver_tpu.parallel.mesh import make_mesh
from pcg_mpi_solver_tpu.solver.driver import Solver

# Cube 6x5x5 (h=0.5, nu=0.3, heterogeneous seed 0), tol=1e-8, Jacobi,
# 4 parts on 4 devices.  Pinned at round 2.
GOLDEN_CUBE = {
    "direct": {"iters": 115, "checksum": 2535.2226603195363},
    "mixed": {"iters": 168, "checksum": 2535.222664843344},
}


def _solve(mode):
    model = make_cube_model(6, 5, 5, h=0.5, nu=0.3, heterogeneous=True, seed=0)
    cfg = RunConfig(
        solver=SolverConfig(tol=1e-8, max_iter=2000, precision_mode=mode),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 1.0]),
    )
    s = Solver(model, cfg, mesh=make_mesh(4), n_parts=4)
    res = s.step(1.0)
    return res, float(np.abs(s.displacement_global()).sum())


def test_golden_cube_direct():
    res, checksum = _solve("direct")
    g = GOLDEN_CUBE["direct"]
    assert res.flag == 0
    assert res.relres <= 1e-8
    assert abs(res.iters - g["iters"]) <= 1, res.iters
    assert np.isclose(checksum, g["checksum"], rtol=1e-6), checksum


def test_golden_cube_mixed():
    """Mixed precision must land on the same solution (checksum agrees with
    the direct golden to ~tol) at its own pinned iteration count."""
    res, checksum = _solve("mixed")
    g = GOLDEN_CUBE["mixed"]
    assert res.flag == 0
    assert abs(res.iters - g["iters"]) <= 2, res.iters
    assert np.isclose(checksum, g["checksum"], rtol=1e-6), checksum
    assert np.isclose(checksum, GOLDEN_CUBE["direct"]["checksum"], rtol=1e-7)
