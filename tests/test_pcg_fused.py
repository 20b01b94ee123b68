"""Fused-collective (Chronopoulos–Gear) PCG variant
(SolverConfig.pcg_variant="fused"): convergence parity with classic on
the golden model, chunked-dispatch and kill-and-resume bit-identity,
recovery-ladder compatibility under fault injection, and the end-to-end
config plumbing (CLI flag, cache key, bench detail field).  The
single-psum-per-iteration claim itself is proven statically in
tests/test_collectives.py."""

import numpy as np
import pytest

from pcg_mpi_solver_tpu.config import RunConfig, SolverConfig, TimeHistoryConfig
from pcg_mpi_solver_tpu.models.synthetic import make_cube_model
from pcg_mpi_solver_tpu.obs.metrics import MetricsRecorder
from pcg_mpi_solver_tpu.parallel.mesh import make_mesh
from pcg_mpi_solver_tpu.resilience import FaultPlan, SimulatedKill
from pcg_mpi_solver_tpu.solver.driver import Solver


@pytest.fixture(scope="module")
def model():
    # the golden cube (tests/test_goldens.py): 6x5x5 heterogeneous
    return make_cube_model(6, 5, 5, h=0.5, nu=0.3, heterogeneous=True,
                           seed=0)


def _cfg(variant, tmp_path=None, run_id="1", **solver_kw):
    solver_kw.setdefault("tol", 1e-8)
    solver_kw.setdefault("max_iter", 2000)
    cfg = RunConfig(
        solver=SolverConfig(pcg_variant=variant, **solver_kw),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 1.0],
                                       export_flag=False),
    )
    cfg.run_id = run_id
    if tmp_path is not None:
        cfg.scratch_path = str(tmp_path)
    return cfg


def _iters_close(fused, classic):
    """Acceptance bar: fused iteration count within +/-5% of classic
    (+2 absolute slack for the pipelined one-trip lag on tiny counts)."""
    assert abs(fused - classic) <= max(2, int(0.05 * classic) + 1), \
        (fused, classic)


# ----------------------------------------------------------------------
# Convergence parity (golden + scipy)
# ----------------------------------------------------------------------

def test_fused_parity_direct_golden(model):
    """flag=0 on the golden model, iteration count within the documented
    tolerance of classic, identical solution to ~tol."""
    rs = {}
    for variant in ("classic", "fused"):
        s = Solver(model, _cfg(variant), mesh=make_mesh(4), n_parts=4)
        rs[variant] = (s.step(1.0),
                       float(np.abs(s.displacement_global()).sum()))
    rc, cc = rs["classic"]
    rf, cf = rs["fused"]
    assert rc.flag == 0 and rf.flag == 0
    assert rf.relres <= 1e-8 * 1.001
    _iters_close(rf.iters, rc.iters)
    assert np.isclose(cf, cc, rtol=1e-6)


def test_fused_parity_mixed(model):
    """Mixed precision with fused f32 inner cycles: converges to the
    outer tolerance with a comparable total inner-iteration count."""
    rs = {}
    for variant in ("classic", "fused"):
        s = Solver(model, _cfg(variant, precision_mode="mixed"),
                   mesh=make_mesh(4), n_parts=4)
        rs[variant] = s.step(1.0)
    assert rs["classic"].flag == 0 and rs["fused"].flag == 0
    assert rs["fused"].relres <= 1e-8 * 1.001
    _iters_close(rs["fused"].iters, rs["classic"].iters)


def test_fused_matches_scipy():
    from scipy.sparse.linalg import spsolve

    model = make_cube_model(4, 3, 3, h=0.5, nu=0.3, load="traction",
                            heterogeneous=True)
    s = Solver(model, _cfg("fused"), mesh=make_mesh(1), n_parts=1)
    res = s.step(1.0)
    assert res.flag == 0
    K = model.assemble_csr()
    eff = model.dof_eff
    rhs = (model.F - K @ model.Ud)[eff]
    u_ref = np.array(model.Ud)
    u_ref[eff] += spsolve(K[eff][:, eff].tocsc(), rhs)
    np.testing.assert_allclose(s.displacement_global(), u_ref,
                               rtol=1e-5, atol=1e-8 * np.abs(u_ref).max())


def test_fused_trace_ring(model):
    """The in-graph convergence ring works unchanged under the fused
    body (one slot per resolved iteration, monotone-ish tail)."""
    s = Solver(model, _cfg("fused", trace_resid=64),
               mesh=make_mesh(1), n_parts=1)
    res = s.step(1.0)
    assert res.flag == 0
    tr = s.last_trace
    assert tr is not None and tr.n_recorded > 0
    assert tr.flag[-1] == 0                    # converged slot recorded
    assert tr.normr[-1] < tr.normr[0]


# ----------------------------------------------------------------------
# Resumable dispatch: chunked bit-identity, kill-and-resume
# ----------------------------------------------------------------------

def test_fused_chunked_bit_identical_to_oneshot(model):
    """The q/alpha/fresh recurrence state rides the resumable carry, so
    capped fused dispatches are bit-identical to one long fused solve
    (the classic chunked contract, tests/test_chunked.py)."""
    s1 = Solver(model, _cfg("fused"), mesh=make_mesh(4), n_parts=4)
    r1 = s1.step(1.0)
    s2 = Solver(model, _cfg("fused", iters_per_dispatch=12),
                mesh=make_mesh(4), n_parts=4)
    r2 = s2.step(1.0)
    assert r1.flag == r2.flag == 0
    assert r1.iters == r2.iters
    assert r1.relres == r2.relres
    np.testing.assert_array_equal(s1.displacement_global(),
                                  s2.displacement_global())


def test_fused_snapshot_kill_resume_bit_identity(model, tmp_path):
    """Mid-Krylov snapshot/resume on the chunked path round-trips the
    fused carry (incl. q/alpha/fresh): a kill at a chunk boundary plus
    --resume reproduces the uninterrupted solve bit-identically."""
    def cfg(run_id):
        c = _cfg("fused", tmp_path, run_id=run_id,
                 iters_per_dispatch=12)
        c.checkpoint_every = 1
        c.snapshot_every = 1
        return c

    sa = Solver(model, cfg("fa"), mesh=make_mesh(4), n_parts=4)
    sa.solve()
    ck = cfg("fk")
    sk = Solver(model, ck, mesh=make_mesh(4), n_parts=4)
    sk.fault_plan = FaultPlan("kill@2")
    with pytest.raises(SimulatedKill):
        sk.solve()
    sk2 = Solver(model, ck, mesh=make_mesh(4), n_parts=4)
    sk2.solve(resume=True)
    assert sk2.flags == sa.flags and sk2.iters == sa.iters
    assert sk2.relres == sa.relres
    np.testing.assert_array_equal(sk2.displacement_global(),
                                  sa.displacement_global())


# ----------------------------------------------------------------------
# Recovery-ladder compatibility (PR-3/PR-4 stack)
# ----------------------------------------------------------------------

class _Capture:
    def __init__(self):
        self.events = []

    def emit(self, ev):
        self.events.append(ev)

    def close(self):
        pass


@pytest.mark.parametrize("fault,trigger", [
    ("rho0@1", "flag4"),       # zeroed rho => beta Inf => flag-4 breakdown
    ("nan@1", "nan_carry"),    # NaN trips no in-graph flag; host detects
])
def test_fused_fault_recovery(model, fault, trigger):
    """flag-2/4 breakdowns and NaN poisoning climb the same recovery
    ladder under the fused recurrence and still converge."""
    cap = _Capture()
    s = Solver(model, _cfg("fused", iters_per_dispatch=12),
               mesh=make_mesh(1), n_parts=1,
               recorder=MetricsRecorder(sinks=[cap]))
    s.fault_plan = FaultPlan(fault, recorder=s.recorder)
    res = s.step(1.0)
    assert res.flag == 0 and res.relres <= 1e-8
    recs = [(e["action"], e["trigger"]) for e in cap.events
            if e["kind"] == "recovery"]
    assert ("restart_minres", trigger) in recs


def test_fused_mixed_escalates_to_f64(model):
    """Ladder rung 3 under fused: repeated mixed-path corruption
    escalates to direct-f64 cycles (themselves fused) and converges."""
    cap = _Capture()
    cfg = _cfg("fused", precision_mode="mixed", dtype="float32",
               dot_dtype="float64", tol=1e-9, max_iter=4000,
               inner_tol=0.1, max_recoveries=3, iters_per_dispatch=12)
    s = Solver(model, cfg, mesh=make_mesh(1), n_parts=1,
               recorder=MetricsRecorder(sinks=[cap]))
    s.fault_plan = FaultPlan("inf@0,inf@1", recorder=s.recorder)
    res = s.step(1.0)
    assert res.flag == 0 and res.relres <= 1e-9
    recs = [(e["action"], e["trigger"]) for e in cap.events
            if e["kind"] == "recovery"]
    assert ("escalate_f64", "nan_carry") in recs


# ----------------------------------------------------------------------
# Newmark per-step solves
# ----------------------------------------------------------------------

def test_fused_newmark_steps_match_classic():
    from pcg_mpi_solver_tpu.solver.newmark import NewmarkSolver

    model = make_cube_model(4, 3, 3, h=0.5, nu=0.3, load="traction",
                            heterogeneous=True)
    us = {}
    for variant in ("classic", "fused"):
        s = NewmarkSolver(model, _cfg(variant), mesh=make_mesh(1),
                          n_parts=1, dt=1e-5)
        res = s.run([1.0, 1.0, 1.0])
        assert all(r.flag == 0 for r in res), variant
        us[variant] = s.displacement_global()
    np.testing.assert_allclose(us["fused"], us["classic"], rtol=1e-5,
                               atol=1e-10 * np.abs(us["classic"]).max())


# ----------------------------------------------------------------------
# Config plumbing surfaces
# ----------------------------------------------------------------------

def test_invalid_variant_rejected(model):
    with pytest.raises(ValueError, match="pcg_variant"):
        Solver(model, _cfg("frobnicate"), mesh=make_mesh(1), n_parts=1)

    import jax.numpy as jnp

    from pcg_mpi_solver_tpu.solver.pcg import pcg

    with pytest.raises(ValueError, match="variant"):
        pcg(None, None, jnp.zeros((1, 3)), jnp.zeros((1, 3)),
            jnp.ones((1, 3)), tol=1e-8, max_iter=5, glob_n_dof_eff=3,
            variant="bogus")


def test_cache_key_separates_variants():
    from pcg_mpi_solver_tpu.cache.keys import step_cache_key

    kw = dict(abstract="sig", mesh=("m", "cpu"), backend="general",
              solver={"tol": 1e-8}, trace_len=0, glob_n_dof_eff=100,
              donate=True, jax_version="x")
    assert step_cache_key(pcg_variant="classic", **kw) != \
        step_cache_key(pcg_variant="fused", **kw)


def test_cli_flag_plumbs_variant():
    from types import SimpleNamespace

    from pcg_mpi_solver_tpu.cli import _load_settings

    args = SimpleNamespace(settings=None, tol=None, max_iter=None,
                           precision=None, precond=None,
                           pcg_variant="fused")
    cfg = _load_settings(None, args)
    assert cfg.solver.pcg_variant == "fused"
    args.pcg_variant = None
    assert _load_settings(None, args).solver.pcg_variant == "classic"


def test_bench_detail_reports_variant():
    from types import SimpleNamespace

    from pcg_mpi_solver_tpu.bench import _run_config_extra

    solver = SimpleNamespace(
        backend="general", ops=SimpleNamespace(),
        config=SimpleNamespace(solver=SimpleNamespace(
            pcg_variant="fused")))
    extra = _run_config_extra(solver, "float32", "mixed", 1, 0.1, "cpu")
    assert extra["pcg_variant"] == "fused"


def test_run_summary_carries_variant_gauge(model):
    cap = _Capture()
    s = Solver(model, _cfg("fused"), mesh=make_mesh(1), n_parts=1,
               recorder=MetricsRecorder(sinks=[cap]))
    s.step(1.0)
    s.recorder.emit_run_summary()
    summaries = [e for e in cap.events if e["kind"] == "run_summary"]
    assert summaries and summaries[-1]["gauges"]["pcg_variant"] == "fused"
    assert summaries[-1]["gauges"]["comm.pcg_variant"] == "fused"
    # fused drops the two serialized scalar psums from the gauge too
    assert summaries[-1]["gauges"]["comm.psums_per_iter"] == \
        s.ops.comm_estimate(variant="fused")["psums_per_iter"]


def test_cross_variant_resume_rejected_by_fingerprint(model, tmp_path):
    """A checkpoint written under one variant must be rejected on resume
    under the other with a clear fingerprint mismatch — the fused carry
    rides extra pytree leaves (q/alpha/fresh), so without the guard the
    failure would be an obscure shard_map structure error (or a silently
    different iteration sequence)."""
    cfg_f = _cfg("fused", tmp_path, run_id="xv",
                 iters_per_dispatch=12)
    cfg_f.checkpoint_every = 1
    s = Solver(model, cfg_f, mesh=make_mesh(1), n_parts=1)
    s.solve()

    cfg_c = _cfg("classic", tmp_path, run_id="xv",
                 iters_per_dispatch=12)
    cfg_c.checkpoint_every = 1
    s2 = Solver(model, cfg_c, mesh=make_mesh(1), n_parts=1)
    with pytest.raises(ValueError, match="pcg_variant"):
        s2.solve(resume=True)


# ----------------------------------------------------------------------
# Residual-drift guard (ISSUE 9 satellite, arXiv:2501.03743): the fused
# deferred true-residual check counts disagreements with the recurrence
# norm and exits recoverably (flag 6) on sustained drift.
# ----------------------------------------------------------------------

_DRIFT_SETUP = {}


def _direct_pcg_setup(nx=5):
    import jax.numpy as jnp

    from pcg_mpi_solver_tpu.ops.matvec import Ops, device_data
    from pcg_mpi_solver_tpu.parallel.partition import partition_model

    if nx in _DRIFT_SETUP:
        return _DRIFT_SETUP[nx]
    m = make_cube_model(nx, 4, 4, h=0.5, nu=0.3, load="traction",
                        heterogeneous=True)
    pm = partition_model(m, 1)
    data = device_data(pm, jnp.float64)
    ops = Ops.from_model(pm, dot_dtype=jnp.float64)
    eff = data["eff"]
    fext = eff * data["F"]
    d = eff * ops.diag(data)
    inv = jnp.where(d != 0, 1.0 / jnp.where(d != 0, d, 1.0), 0.0)
    _DRIFT_SETUP[nx] = (m, ops, data, fext, inv)
    return _DRIFT_SETUP[nx]


def test_fused_drift_guard_exits_flag6_and_counts():
    """Recurrence drift re-emerges after every self-correcting deferred
    check (the check resets r to truth, but a drifting recurrence lies
    again) — emulated by re-poisoning the carry residual before each
    capped dispatch.  Each poisoned dispatch's check disagrees (>2x)
    and counts into the resumable ``drift`` leaf; at FUSED_DRIFT_LIMIT
    the solve exits with the recoverable DRIFT_FLAG instead of grinding
    on the stale norm, and breakdown_trigger routes it to the ladder."""
    import jax.numpy as jnp

    from pcg_mpi_solver_tpu.resilience import breakdown_trigger
    from pcg_mpi_solver_tpu.solver.pcg import (
        DRIFT_FLAG, FUSED_DRIFT_LIMIT, pcg)

    import jax

    m, ops, data, fext, inv = _direct_pcg_setup()
    kw = dict(tol=1e-8, max_iter=1, max_iter_nominal=200,
              glob_n_dof_eff=int(np.asarray(m.dof_eff).sum()),
              variant="fused", return_carry=True)
    res, carry = pcg(ops, data, fext, jnp.zeros_like(fext), inv,
                     **dict(kw, max_iter=5))
    assert int(carry["drift"]) == 0, "healthy fused solve: no drift"
    # one jitted resumable dispatch, re-run per poisoned carry (the
    # shapes never change, so the loop pays one trace)
    step = jax.jit(lambda c: pcg(ops, data, fext, jnp.zeros_like(fext),
                                 inv, carry_in=c, **kw))
    for k in range(FUSED_DRIFT_LIMIT):
        # the recurrence claims convergence; the true residual disagrees
        carry = dict(carry)
        carry["r"] = carry["r"] * 1e-14
        res, carry = step(carry)
        assert int(carry["drift"]) == k + 1
    assert int(res.flag) == DRIFT_FLAG
    assert breakdown_trigger(int(res.flag), float(res.relres)) == "flag6"


def test_fused_drift_guard_per_column():
    """Blocked twin: only the column whose recurrence keeps lying exits
    flag 6 and counts drift; the healthy column's state is untouched
    (per-column drift isolation)."""
    import jax.numpy as jnp

    from pcg_mpi_solver_tpu.solver.pcg import (
        DRIFT_FLAG, FUSED_DRIFT_LIMIT, pcg_many)

    import jax

    m, ops, data, fext1, inv = _direct_pcg_setup()
    fb = jnp.stack([fext1, 0.5 * fext1], axis=-1)
    kw = dict(tol=1e-8, max_iter=1, max_iter_nominal=200,
              glob_n_dof_eff=int(np.asarray(m.dof_eff).sum()),
              variant="fused", return_carry=True)
    res, carry = pcg_many(ops, data, fb, jnp.zeros_like(fb), inv,
                          **dict(kw, max_iter=5))
    lie = jnp.asarray([1e-14, 1.0])
    step = jax.jit(lambda c: pcg_many(ops, data, fb,
                                      jnp.zeros_like(fb), inv,
                                      carry_in=c, **kw))
    for _ in range(FUSED_DRIFT_LIMIT):
        carry = dict(carry)
        carry["r"] = carry["r"] * lie[None, None, :]
        res, carry = step(carry)
    assert int(res.flag[0]) == DRIFT_FLAG
    assert int(carry["drift"][0]) >= FUSED_DRIFT_LIMIT
    assert int(res.flag[1]) != DRIFT_FLAG
    assert int(carry["drift"][1]) == 0
