"""PCG correctness: solves the system, matches scipy, iteration-count parity
across partition counts (the invariant the reference preserves when scaling
ranks), and MATLAB-compatible edge-case flags."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pcg_mpi_solver_tpu.config import RunConfig, SolverConfig, TimeHistoryConfig
from pcg_mpi_solver_tpu.models.synthetic import make_cube_model
from pcg_mpi_solver_tpu.parallel.mesh import make_mesh
from pcg_mpi_solver_tpu.solver.driver import Solver


def scipy_solution(model, tol=1e-10):
    """Direct sparse solve on effective dofs with Dirichlet lifting."""
    from scipy.sparse.linalg import spsolve

    K = model.assemble_csr()
    eff = model.dof_eff
    rhs = (model.F - K @ model.Ud)[eff]
    u = np.array(model.Ud)
    u[eff] += spsolve(K[eff][:, eff].tocsc(), rhs)
    return u


def make_solver(model, n_parts, tol=1e-8, mesh=None, **kw):
    cfg = RunConfig(
        solver=SolverConfig(tol=tol, max_iter=2000),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 1.0]),
    )
    mesh = mesh or make_mesh(1)
    return Solver(model, cfg, mesh=mesh, n_parts=n_parts, **kw)


@pytest.mark.parametrize("load", ["traction", "dirichlet"])
def test_pcg_matches_direct_solve(load):
    model = make_cube_model(4, 3, 3, h=0.5, nu=0.3, load=load, heterogeneous=True)
    s = make_solver(model, 1)
    res = s.step(1.0)
    assert res.flag == 0
    assert res.relres <= 1e-8
    u = s.displacement_global()
    u_ref = scipy_solution(model)
    np.testing.assert_allclose(u, u_ref, rtol=1e-5, atol=1e-8 * np.abs(u_ref).max())


def test_iteration_parity_across_partitions():
    """Same iteration count and residual for 1, 4, 8 parts — domain
    decomposition must not change the math (SURVEY.md §7 step 2)."""
    model = make_cube_model(5, 4, 4, heterogeneous=True)
    results = {}
    for n_parts, n_dev in [(1, 1), (4, 4), (8, 8)]:
        s = make_solver(model, n_parts, mesh=make_mesh(n_dev))
        results[n_parts] = s.step(1.0)
    i1 = results[1].iters
    for n_parts in (4, 8):
        assert results[n_parts].flag == 0
        assert abs(results[n_parts].iters - i1) <= 1
        assert np.isclose(results[n_parts].relres, results[1].relres, rtol=0.5)


def test_pcg_zero_rhs():
    """All-zero rhs => all-zero solution, flag 0, 0 iterations
    (reference pcg_solver.py:387-395)."""
    model = make_cube_model(3, 3, 3)
    model.F[:] = 0.0
    model.Ud[:] = 0.0
    s = make_solver(model, 1)
    res = s.step(1.0)
    assert res.flag == 0 and res.iters == 0 and res.relres == 0.0
    assert np.all(s.displacement_global() == 0.0)


def test_pcg_warm_start_early_exit():
    """Re-solving from the converged state exits immediately
    (good-initial-guess path, pcg_solver.py:421-426)."""
    model = make_cube_model(3, 3, 3)
    s = make_solver(model, 1)
    r1 = s.step(1.0)
    assert r1.flag == 0
    r2 = s.step(1.0)
    assert r2.flag == 0
    assert r2.iters <= 1


def test_multistep_dirichlet_lifting():
    """Ramped prescribed displacement: u scales linearly with delta(t) in a
    linear problem (reference updateBC, pcg_solver.py:226-238)."""
    model = make_cube_model(3, 3, 3, load="dirichlet")
    cfg = RunConfig(
        solver=SolverConfig(tol=1e-10, max_iter=2000),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 0.5, 1.0]),
    )
    s = Solver(model, cfg, mesh=make_mesh(1), n_parts=1)
    res = s.solve()
    assert all(r.flag == 0 for r in res)
    u_half = None
    # step through manually to capture intermediate states
    s2 = Solver(model, cfg, mesh=make_mesh(1), n_parts=1)
    s2.step(0.5)
    u_half = s2.displacement_global()
    s2.step(1.0)
    u_full = s2.displacement_global()
    np.testing.assert_allclose(u_full, 2.0 * u_half, rtol=1e-5, atol=1e-10)


def test_plateau_window_mechanism():
    """The experimental plateau exit (off by default): a short window cuts
    an f32 solve at floor earlier than MATLAB's stagnation protocol and
    returns the min-residual iterate; window=0 is exactly the MATLAB
    behavior.  Also pins WHY it is off by default: a too-short window
    false-triggers during CG's non-monotone pre-asymptotic phase."""
    from pcg_mpi_solver_tpu.ops.matvec import Ops, device_data
    from pcg_mpi_solver_tpu.parallel.partition import partition_model
    from pcg_mpi_solver_tpu.solver.pcg import pcg

    model = make_cube_model(6, 5, 5, h=0.5, nu=0.3, load="traction",
                            heterogeneous=True)
    pm = partition_model(model, 1)
    data = device_data(pm, jnp.float32)
    ops = Ops.from_model(pm, dot_dtype=jnp.float32)
    eff = data["eff"]
    fext = eff * data["F"]
    x0 = jnp.zeros_like(fext)
    d = eff * ops.diag(data)
    inv_diag = jnp.where(d != 0, 1.0 / jnp.maximum(d, 1e-30), 0.0)
    kw = dict(tol=1e-14, max_iter=1500,
              glob_n_dof_eff=int(model.dof_eff.sum()))
    res_full = pcg(ops, data, fext, x0, inv_diag, plateau_window=0, **kw)
    res_plat = pcg(ops, data, fext, x0, inv_diag, plateau_window=10, **kw)
    res_tiny = pcg(ops, data, fext, x0, inv_diag, plateau_window=5, **kw)
    # MATLAB protocol alone: stagnation + MoreSteps end the grind
    assert int(res_full.flag) == 3
    # window=10 exits earlier with a min-residual iterate of useful quality
    assert int(res_plat.flag) == 3
    assert int(res_plat.iters) < int(res_full.iters)
    assert float(res_plat.relres) < 1e-2
    # the false-trigger hazard (the reason the default is off): a 5-iter
    # window fires inside the pre-asymptotic residual wander
    assert int(res_tiny.iters) < 10


def test_progress_exit_mechanism():
    """The progress-rate exit (mixed-mode inner cycles): plumbing fires
    when armed with hair-trigger thresholds, and the min-gain gate keeps
    it unreachable before the cycle has done real work."""
    from pcg_mpi_solver_tpu.ops.matvec import Ops, device_data
    from pcg_mpi_solver_tpu.parallel.partition import partition_model
    from pcg_mpi_solver_tpu.solver.pcg import pcg

    model = make_cube_model(6, 5, 5, h=0.5, nu=0.3, load="traction",
                            heterogeneous=True)
    pm = partition_model(model, 1)
    data = device_data(pm, jnp.float32)
    ops = Ops.from_model(pm, dot_dtype=jnp.float32)
    eff = data["eff"]
    fext = eff * data["F"]
    x0 = jnp.zeros_like(fext)
    d = eff * ops.diag(data)
    inv_diag = jnp.where(d != 0, 1.0 / jnp.maximum(d, 1e-30), 0.0)
    kw = dict(tol=1e-14, max_iter=1500,
              glob_n_dof_eff=int(model.dof_eff.sum()))
    res_off = pcg(ops, data, fext, x0, inv_diag, **kw)
    # hair-trigger: 1-iter window, any ratio counts as weak, gate at 1.5x
    # achieved contraction -> exits very early with the min-residual
    # iterate (proves the window/gate plumbing end to end)
    res_trip = pcg(ops, data, fext, x0, inv_diag, progress_window=1,
                   progress_ratio=1e-9, progress_min_gain=1.5, **kw)
    assert int(res_trip.flag) == 3
    assert int(res_trip.iters) < int(res_off.iters)
    # production thresholds: the min-gain gate (30x) plus the long window
    # must leave this small f32-floor grind to MATLAB's own stagnation
    # protocol — identical iteration count and flag as knob-off
    res_prod = pcg(ops, data, fext, x0, inv_diag, progress_window=150,
                   progress_ratio=0.7, progress_min_gain=30.0, **kw)
    assert int(res_prod.flag) == int(res_off.flag)
    assert int(res_prod.iters) == int(res_off.iters)
    assert float(res_prod.relres) == float(res_off.relres)


def test_mixed_progress_default_no_small_scale_regression():
    """mixed_progress_window (opt-in since the negative 96^3 A/B): a
    small mixed solve must converge
    identically (flag 0, same tol) with it on or off — the min-gain gate
    keeps pre-asymptotic windows unreachable."""
    model = make_cube_model(5, 4, 4, h=0.5, nu=0.3, load="traction",
                            heterogeneous=True)
    results = {}
    for win in (0, 150):
        cfg = RunConfig(
            solver=SolverConfig(tol=1e-9, max_iter=4000, dtype="float32",
                                dot_dtype="float64", precision_mode="mixed",
                                mixed_progress_window=win),
            time_history=TimeHistoryConfig(time_step_delta=[0.0, 1.0]),
        )
        s = Solver(model, cfg, mesh=make_mesh(1), n_parts=1)
        results[win] = s.step(1.0)
    assert results[150].flag == 0
    assert results[150].iters == results[0].iters
    assert np.isclose(results[150].relres, results[0].relres, rtol=1e-6)


@pytest.mark.parametrize("fault,flag_name", [("rho0@1", "flag4"),
                                             ("inf@1", "flag2")])
def test_breakdown_ladder_recovers_to_converged(fault, flag_name):
    """Engineered flag-4 (rho/pq breakdown via a zeroed carry rho — the
    resumed beta recurrence divides by zero) and flag-2 (Inf
    preconditioner via an Inf residual) inputs on the chunked path: the
    recovery ladder (resilience/) must restart from the min-residual
    iterate and finish at flag=0 within the default retry budget, with
    the recovery visible as a telemetry event (ISSUE 3 acceptance b)."""
    from pcg_mpi_solver_tpu.obs.metrics import MetricsRecorder
    from pcg_mpi_solver_tpu.resilience import FaultPlan

    class Cap:
        def __init__(self):
            self.events = []

        def emit(self, ev):
            self.events.append(ev)

        def close(self):
            pass

    model = make_cube_model(4, 3, 3, h=0.5, nu=0.3, load="traction",
                            heterogeneous=True)
    cfg = RunConfig(
        solver=SolverConfig(tol=1e-8, max_iter=2000, iters_per_dispatch=15),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 1.0]),
    )
    cap = Cap()
    s = Solver(model, cfg, mesh=make_mesh(1), n_parts=1,
               recorder=MetricsRecorder(sinks=[cap]))
    s.fault_plan = FaultPlan(fault, recorder=s.recorder)
    res = s.step(1.0)
    assert res.flag == 0
    assert res.relres <= 1e-8
    recoveries = [e for e in cap.events if e["kind"] == "recovery"]
    assert [(e["action"], e["trigger"]) for e in recoveries] == \
        [("restart_minres", flag_name)]
    # the recovered solution is the true solution, not just a flag
    u_ref = scipy_solution(model)
    np.testing.assert_allclose(s.displacement_global(), u_ref, rtol=1e-5,
                               atol=1e-8 * np.abs(u_ref).max())


def test_mixed_converges_with_plateau_default():
    model = make_cube_model(5, 4, 4, h=0.5, nu=0.3, load="traction",
                            heterogeneous=True)
    cfg = RunConfig(
        solver=SolverConfig(tol=1e-9, max_iter=4000, dtype="float32",
                            dot_dtype="float64", precision_mode="mixed"),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 1.0]),
    )
    s = Solver(model, cfg, mesh=make_mesh(1), n_parts=1)
    r = s.step(1.0)
    assert r.flag == 0 and r.relres <= 1e-9
    u = np.asarray(s.displacement_global())
    np.testing.assert_allclose(u, scipy_solution(model), rtol=0,
                               atol=1e-7 * np.abs(scipy_solution(model)).max())
