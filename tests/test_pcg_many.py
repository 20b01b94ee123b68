"""Batched multi-RHS PCG (ISSUE 6): the blocked Krylov loop
(solver/pcg.pcg_many), the Solver.solve_many dispatch path, and the
plumbing it threads through — validate/, cache keys, snapshots,
telemetry, CLI.

The headline contracts:

* a blocked CLASSIC solve on CPU reproduces each column of the
  equivalent single-RHS solves BIT-IDENTICALLY (frozen converged
  columns included) — the per-column lockstep merge only reorders which
  trip a column's arithmetic runs on, never the arithmetic;
* the fused variant agrees per column to rounding (it is documented
  non-bit-exact even against the scalar reference);
* psum count independent of nrhs is proven in tests/test_collectives.py;
* the warm path does zero partition builds and zero step re-traces for
  repeated blocks of the same shape (BUILD_CALLS + trace.step, the PR-2
  contract extended to the blocked program);
* a killed blocked solve resumes bit-identically, and a cross-nrhs
  resume is rejected as a clear fingerprint mismatch naming ``nrhs``.
"""

import glob
import os
import shutil

import numpy as np
import pytest

from pcg_mpi_solver_tpu.config import (RunConfig, SolverConfig,
                                       TimeHistoryConfig)
from pcg_mpi_solver_tpu.models.synthetic import make_cube_model
from pcg_mpi_solver_tpu.obs.metrics import MetricsRecorder
from pcg_mpi_solver_tpu.parallel.mesh import make_mesh
from pcg_mpi_solver_tpu.parallel.partition import BUILD_CALLS
from pcg_mpi_solver_tpu.resilience import FaultPlan, SimulatedKill
from pcg_mpi_solver_tpu.solver.driver import Solver
from pcg_mpi_solver_tpu.validate import PreflightError, check_rhs_block


class _Cap:
    """Metrics sink collecting events for assertions."""

    def __init__(self):
        self.events = []

    def emit(self, ev):
        self.events.append(ev)

    def close(self):
        pass


def _cfg(*, mode="direct", tol=1e-8, ipd=-1, cache_dir="", snap=0,
         variant="classic", scratch=""):
    cfg = RunConfig(
        solver=SolverConfig(tol=tol, max_iter=2000, precision_mode=mode,
                            iters_per_dispatch=ipd, pcg_variant=variant),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 1.0]),
        cache_dir=cache_dir, snapshot_every=snap,
    )
    if scratch:
        cfg.scratch_path = scratch
    return cfg


@pytest.fixture
def model():
    return make_cube_model(4, 3, 3, heterogeneous=True)


def _hard_load(model, seed=5):
    """A load case that converges SLOWER than the smooth traction F: a
    random field restricted to effective dofs (rough right-hand sides
    excite the high modes Jacobi damps worst)."""
    rng = np.random.default_rng(seed)
    f = np.zeros(model.n_dof)
    eff = np.asarray(model.dof_eff)
    f[eff] = rng.standard_normal(eff.size)
    return f


# ----------------------------------------------------------------------
# Column-for-column parity with single-RHS solves
# ----------------------------------------------------------------------

def test_classic_block_matches_single_rhs_bit_identical(model):
    """Width-3 classic block (easy, scaled, zero columns) == the three
    width-1 solves, bit for bit, per column."""
    s = Solver(model, _cfg(), mesh=make_mesh(2), n_parts=2,
               backend="general")
    F = np.asarray(model.F)
    cols = [F, 0.5 * F, np.zeros_like(F)]
    blk = s.solve_many(np.stack(cols, axis=-1))
    xb = np.asarray(blk.x)
    for j, col in enumerate(cols):
        single = s.solve_many(col)
        assert int(single.flags[0]) == int(blk.flags[j])
        assert int(single.iters[0]) == int(blk.iters[j])
        np.testing.assert_array_equal(np.asarray(single.x)[..., 0],
                                      xb[..., j])
    # zero column: flag 0, zero iterations, zero solution
    assert int(blk.flags[2]) == 0 and int(blk.iters[2]) == 0
    assert not xb[..., 2].any()


def test_fused_block_matches_single_rhs_to_rounding(model):
    """The fused variant is documented non-bit-exact; per column the
    blocked solve must still take the same iteration path (flags and
    iteration counts equal) and agree to rounding."""
    s = Solver(model, _cfg(variant="fused"), mesh=make_mesh(2), n_parts=2,
               backend="general")
    F = np.asarray(model.F)
    cols = [F, 0.25 * F]
    blk = s.solve_many(np.stack(cols, axis=-1))
    xb = np.asarray(blk.x)
    for j, col in enumerate(cols):
        single = s.solve_many(col)
        assert int(single.flags[0]) == int(blk.flags[j]) == 0
        assert int(single.iters[0]) == int(blk.iters[j])
        np.testing.assert_allclose(np.asarray(single.x)[..., 0],
                                   xb[..., j], rtol=1e-7, atol=1e-12)


def test_mixed_convergence_rates_freeze_converged_columns(model):
    """One easy + one hard RHS: the hard column keeps iterating after
    the easy one converged, and the frozen easy column is bit-identical
    to its solo solve — proof the mask really freezes it.  Easy = the
    image of a smooth ramp displacement (low-mode content: CG's
    residual polynomial kills it in fewer iterations); hard = the
    smooth-traction reference load."""
    from pcg_mpi_solver_tpu.solver.numpy_ref import NumpyRefSolver

    s = Solver(model, _cfg(tol=1e-10), mesh=make_mesh(2), n_parts=2,
               backend="general")
    eff_mask = np.zeros(model.n_dof)
    eff_mask[np.asarray(model.dof_eff)] = 1.0
    ramp = np.zeros(model.n_dof)
    ramp[0::3] = np.asarray(model.node_coords)[:, 0]
    easy = NumpyRefSolver(model).matvec(ramp * eff_mask) * eff_mask
    hard = np.asarray(model.F)
    blk = s.solve_many(np.stack([easy, hard], axis=-1))
    assert list(blk.flags) == [0, 0]
    assert int(blk.iters[1]) > int(blk.iters[0]), \
        "hard column should need more iterations than the easy one"
    solo = s.solve_many(easy)
    np.testing.assert_array_equal(np.asarray(solo.x)[..., 0],
                                  np.asarray(blk.x)[..., 0])


def test_mixed_precision_block_matches_width1(model):
    """Blocked mixed-precision refinement (pcg_mixed_many): per-column
    flags 0 at tol and column parity with the width-1 blocked solve."""
    s = Solver(model, _cfg(mode="mixed", tol=1e-9), mesh=make_mesh(2),
               n_parts=2, backend="general")
    F = np.asarray(model.F)
    hard = _hard_load(model)
    blk = s.solve_many(np.stack([F, hard], axis=-1))
    assert list(blk.flags) == [0, 0]
    assert float(blk.relres.max()) <= 1e-9
    solo = s.solve_many(F)
    np.testing.assert_array_equal(np.asarray(solo.x)[..., 0],
                                  np.asarray(blk.x)[..., 0])


def test_structured_backend_block(model):
    """The stencil backend's vmapped block axis: same per-column parity
    contract on the structured slab partition."""
    m = make_cube_model(4, 4, 4, heterogeneous=False)
    s = Solver(m, _cfg(), mesh=make_mesh(2), n_parts=2)
    assert s.backend == "structured"
    F = np.asarray(m.F)
    blk = s.solve_many(np.stack([F, 2.0 * F], axis=-1))
    assert list(blk.flags) == [0, 0]
    solo = s.solve_many(F)
    np.testing.assert_array_equal(np.asarray(solo.x)[..., 0],
                                  np.asarray(blk.x)[..., 0])
    xg = s.displacement_global_many(blk.x)
    assert xg.shape == (m.n_dof, 2)
    np.testing.assert_allclose(xg[:, 1], 2.0 * xg[:, 0], rtol=1e-6)


# ----------------------------------------------------------------------
# A design load set as one block: the six unit stress resultants of the
# loaded x = L face and two combinations, on the heterogeneous 8^3 cube
# ----------------------------------------------------------------------

def _load_set(m):
    """Eight load columns on the x = L face, nodal forces w * t(y, z)
    with area-consistent weights w: N, Vy, Vz, T, My, Mz (linear over
    the face's centroid and half-widths), C1 = N + 0.25 Vy - 0.15 Vz,
    C2 = 0.5 My + 0.5 Mz + 0.3 T."""
    nx, ny, nz, h = m.grid
    xyz = np.asarray(m.node_coords)
    face = np.flatnonzero(np.isclose(xyz[:, 0], nx * h))
    y, z = xyz[face, 1], xyz[face, 2]
    j, k = np.rint(y / h), np.rint(z / h)
    w = (np.where((j == 0) | (j == ny), 0.5, 1.0)
         * np.where((k == 0) | (k == nz), 0.5, 1.0) * h * h)
    eta = (y - 0.5 * ny * h) / (0.5 * ny * h)
    zeta = (z - 0.5 * nz * h) / (0.5 * nz * h)
    one, zero = np.ones_like(y), np.zeros_like(y)
    t = {"N": (one, zero, zero), "Vy": (zero, one, zero),
         "Vz": (zero, zero, one), "T": (zero, -zeta, eta),
         "My": (zeta, zero, zero), "Mz": (eta, zero, zero)}
    f = {}
    for name, comps in t.items():
        F = np.zeros(m.n_dof)
        for c in range(3):
            F[3 * face + c] = 1e6 * w * comps[c]
        f[name] = F
    f["C1"] = f["N"] + 0.25 * f["Vy"] - 0.15 * f["Vz"]
    f["C2"] = 0.5 * f["My"] + 0.5 * f["Mz"] + 0.3 * f["T"]
    return np.stack(list(f.values()), axis=-1)


@pytest.fixture(scope="module")
def loadcase_solver():
    m = make_cube_model(8, 8, 8, heterogeneous=True)
    s = Solver(m, _cfg(mode="mixed", tol=1e-7), mesh=make_mesh(1),
               n_parts=1)
    assert s.backend == "structured"
    return m, s, _load_set(m)


def test_load_set_block_converges_against_the_f64_operator(loadcase_solver):
    """Every column of the width-8 mixed block: flag 0 and an f64
    residual within tol under the numpy reference operator."""
    from pcg_mpi_solver_tpu.solver.numpy_ref import NumpyRefSolver

    m, s, fb = loadcase_solver
    blk = s.solve_many(fb)
    assert list(blk.flags) == [0] * 8
    u = s.displacement_global_many(blk.x)
    K = NumpyRefSolver(m)
    eff = np.asarray(m.dof_eff)
    for j in range(8):
        r = (fb[:, j] - K.matvec(u[:, j]))[eff]
        assert np.linalg.norm(r) / np.linalg.norm(fb[eff, j]) <= 1e-7, j


def test_load_set_columns_match_width1_bit_for_bit(loadcase_solver):
    """Column j of the width-8 block is the width-1 solve of column j,
    bit for bit (the lockstep merge never touches a column's
    arithmetic), and the block reports its lockstep iterations."""
    m, s, fb = loadcase_solver
    blk = s.solve_many(fb)
    xb = np.asarray(blk.x)
    assert len(set(int(i) for i in blk.iters)) > 1, \
        "the load set's columns should converge at different counts"
    for j in range(8):
        solo = s.solve_many(fb[:, j])
        assert int(solo.flags[0]) == int(blk.flags[j])
        assert int(solo.iters[0]) == int(blk.iters[j])
        # a lone column's lockstep loop is its own iterations
        assert solo.lockstep_iters == int(solo.iters[0])
        np.testing.assert_array_equal(np.asarray(solo.x)[..., 0],
                                      xb[..., j])
    assert int(np.max(blk.iters)) <= blk.lockstep_iters \
        <= int(np.sum(blk.iters))


def test_lockstep_count_and_live_lanes_by_hand(loadcase_solver):
    """Hand counts of the lockstep iterations and the live-lane share.

    A block (F, 2F, 0): 2F repeats F's arithmetic scaled by an exact
    power of two, so in every refinement cycle both run the same inner
    iterations and the zero column none; lockstep = iters(F), live share
    2/3.  A block of the eight load cases at a tolerance one refinement
    cycle reaches (its inner tolerance is half the outer one):
    lockstep = the most iterations any column ran."""
    m, s, fb = loadcase_solver
    F = fb[:, 6]
    blk = s.solve_many(np.stack([F, 2.0 * F, np.zeros_like(F)], axis=-1))
    n = int(blk.iters[0])
    assert [int(i) for i in blk.iters] == [n, n, 0]
    assert blk.lockstep_iters == n
    assert s.recorder.gauges["many.live_lane_frac"] == pytest.approx(2 / 3)

    one = Solver(m, _cfg(mode="mixed", tol=1e-4), mesh=make_mesh(1),
                 n_parts=1)
    blk = one.solve_many(fb)
    iters = [int(i) for i in blk.iters]
    assert list(blk.flags) == [0] * 8 and len(set(iters)) > 1
    assert blk.lockstep_iters == max(iters)
    assert one.recorder.counters["many.lockstep_iters"] == max(iters)
    assert one.recorder.gauges["many.live_lane_frac"] == pytest.approx(
        sum(iters) / (8 * max(iters)))


def test_blocked_staging_and_fetch_are_spans(loadcase_solver):
    """The block's host staging and its answer's fetch are recorder
    spans, with the scalar fetch's names."""
    m, s, fb = loadcase_solver
    before = s.recorder.span_stats()
    blk = s.solve_many(fb[:, :2])
    s.displacement_global_many(blk.x)
    after = s.recorder.span_stats()
    for name in ("many.stage", "fetch.transfer", "fetch.assemble"):
        n0 = before.get(name, {}).get("calls", 0)
        assert after[name]["calls"] == n0 + 1, name
    assert "many.lockstep_iters" in s.recorder.snapshot()["counters"]


# ----------------------------------------------------------------------
# Chunked dispatch: kill-and-resume, cross-nrhs rejection
# ----------------------------------------------------------------------

def _chunked_solver(model, tmp_path, snap=1):
    return Solver(model, _cfg(ipd=20, snap=snap, scratch=str(tmp_path)),
                  mesh=make_mesh(2), n_parts=2, backend="general")


def _kill_after(solver, nrhs, n_dispatches):
    """Replace the blocked cycle program with one that raises after
    ``n_dispatches`` capped dispatches — the deterministic stand-in for
    a mid-solve kill/preemption."""
    progs = solver._ensure_many_programs(nrhs)
    real = progs["cycle"]
    count = {"n": 0}

    def bomb(*a):
        count["n"] += 1
        if count["n"] > n_dispatches:
            raise RuntimeError("simulated kill")
        return real(*a)

    progs["cycle"] = bomb


def test_chunked_block_kill_and_resume_bit_identical(model, tmp_path):
    F = np.asarray(model.F)
    fb = np.stack([F, _hard_load(model)], axis=-1)
    ref = _chunked_solver(model, tmp_path / "ref").solve_many(fb)
    assert list(ref.flags) == [0, 0]
    assert int(np.asarray(ref.iters).max()) > 20, \
        "solve must span several capped dispatches for the test to bite"

    s2 = _chunked_solver(model, tmp_path / "run")
    _kill_after(s2, 2, n_dispatches=2)
    with pytest.raises(RuntimeError, match="simulated kill"):
        s2.solve_many(fb)
    snaps = glob.glob(os.path.join(s2.config.checkpoint_path,
                                   "many_*.npz"))
    assert snaps, "the killed solve must leave its mid-solve snapshot"

    s3 = _chunked_solver(model, tmp_path / "run")
    res = s3.solve_many(fb, resume=True)
    assert list(res.flags) == [0, 0]
    np.testing.assert_array_equal(np.asarray(res.iters),
                                  np.asarray(ref.iters))
    np.testing.assert_array_equal(np.asarray(res.x), np.asarray(ref.x))
    # completion discards the snapshot: a later resume starts cold
    assert not glob.glob(os.path.join(s3.config.checkpoint_path,
                                      "many_*.npz"))


def test_cross_nrhs_resume_is_a_clear_fingerprint_mismatch(model,
                                                           tmp_path):
    F = np.asarray(model.F)
    fb2 = np.stack([F, 0.5 * F], axis=-1)
    s = _chunked_solver(model, tmp_path)
    _kill_after(s, 2, n_dispatches=2)
    with pytest.raises(RuntimeError):
        s.solve_many(fb2)

    s2 = _chunked_solver(model, tmp_path)
    fb3 = np.stack([F, 0.5 * F, 0.25 * F], axis=-1)
    with pytest.raises(ValueError, match="nrhs"):
        s2.solve_many(fb3, resume=True)


def test_same_width_different_rhs_resume_rejected(model, tmp_path):
    """A resumed blocked carry belongs to ONE rhs block: a same-width
    block of different load cases must mismatch on the rhs content hash
    (the scalar paths derive their rhs from the fingerprinted model;
    solve_many's rhs is a per-request input and is fingerprinted too)."""
    F = np.asarray(model.F)
    s = _chunked_solver(model, tmp_path)
    _kill_after(s, 2, n_dispatches=2)
    with pytest.raises(RuntimeError):
        s.solve_many(np.stack([F, 0.5 * F], axis=-1))

    s2 = _chunked_solver(model, tmp_path)
    with pytest.raises(ValueError, match="rhs_hash"):
        s2.solve_many(np.stack([F, 0.25 * F], axis=-1), resume=True)


# ----------------------------------------------------------------------
# Per-column resilience (ISSUE 9): recovery ladder, quarantine, fault
# isolation between columns
# ----------------------------------------------------------------------

def _res_solver(model, tmp_path, *, variant="classic", maxrec=2, snap=0,
                fault=None, cap=None, ipd=20, precond="jacobi"):
    cfg = _cfg(ipd=ipd, snap=snap, variant=variant,
               scratch=str(tmp_path))
    cfg.solver.max_recoveries = maxrec
    cfg.solver.precond = precond
    rec = MetricsRecorder(sinks=[cap]) if cap is not None else None
    s = Solver(model, cfg, mesh=make_mesh(2), n_parts=2,
               backend="general", recorder=rec)
    if fault:
        s.fault_plan = FaultPlan(fault, recorder=s.recorder)
    return s


@pytest.fixture(autouse=True)
def _fast_backoff(monkeypatch):
    monkeypatch.setenv("PCG_TPU_RETRY_BACKOFF_S", "0.01")


@pytest.mark.parametrize("variant", ["classic", "fused", "pipelined"])
def test_chunked_column_fault_chaos_matrix(model, tmp_path, variant):
    """Chaos matrix, chunked blocked path: each of {nan, inf, rho0}
    injected into ONE column engages that column's recovery ladder
    (restart from its min-residual iterate) while the block completes —
    and under classic the HEALTHY column's solution and iteration count
    are bit-identical to a fault-free block run (fault isolation).
    With the ladder disabled the same poison QUARANTINES the column
    (flag 5 + telemetry) and healthy-column isolation still holds.
    One solver runs every leg: the fault plan and the recovery budget
    are host-side state, so the compiled blocked programs are shared."""
    from pcg_mpi_solver_tpu.obs.schema import validate_event

    F = np.asarray(model.F)
    fb = np.stack([F, _hard_load(model)], axis=-1)
    # the bit-identity reference is only consumed by the classic legs
    # (fused is documented non-bit-exact) — skip its solve under fused
    ref = (_res_solver(model, tmp_path / "ref").solve_many(fb)
           if variant == "classic" else None)
    if ref is not None:
        assert list(ref.flags) == [0, 0] and ref.recoveries == 0

    cap = _Cap()
    s = _res_solver(model, tmp_path / "run", variant=variant, cap=cap)
    for mode in ("nan", "inf", "rho0"):
        n0 = len(cap.events)
        s.fault_plan = FaultPlan(f"{mode}@col:1", recorder=s.recorder)
        res = s.solve_many(fb)
        ev = cap.events[n0:]
        assert list(res.flags) == [0, 0], \
            f"{mode}: poisoned column must recover"
        assert res.recoveries >= 1 and res.quarantined == ()
        recs = [e for e in ev if e["kind"] == "recovery"]
        assert recs and all(e["rhs"] == 1 for e in recs), \
            "recovery events must name the poisoned column"
        fired = [e for e in ev if e["kind"] == "fault"]
        assert [(e["mode"], e["point"], e["at"]) for e in fired] == \
            [(mode, "col", 1)]
        if ref is not None:
            np.testing.assert_array_equal(np.asarray(res.x)[..., 0],
                                          np.asarray(ref.x)[..., 0])
            assert int(res.iters[0]) == int(ref.iters[0])

    # ladder disabled: quarantine isolation on the same programs
    s.config.solver.max_recoveries = 0
    n0 = len(cap.events)
    s.fault_plan = FaultPlan("nan@col:1", recorder=s.recorder)
    res = s.solve_many(fb)
    ev = cap.events[n0:]
    assert list(res.flags) == [0, 5] and res.quarantined == (1,)
    assert np.isfinite(res.relres[1]), \
        "a quarantined column must report its min-residual truth"
    q = [e for e in ev if e["kind"] == "rhs_quarantine"]
    assert len(q) == 1 and q[0]["rhs"] == 1 \
        and q[0]["trigger"] == "nan_carry"
    assert validate_event(q[0]) == []
    rhs_ev = {e["rhs"]: e for e in ev if e["kind"] == "rhs_solve"}
    assert rhs_ev[1]["quarantined"] and not rhs_ev[0]["quarantined"]
    if ref is not None:
        np.testing.assert_array_equal(np.asarray(res.x)[..., 0],
                                      np.asarray(ref.x)[..., 0])


def test_blocked_kill_and_resume_mid_recovery_bit_identical(model,
                                                            tmp_path):
    """Satellite 4(a): a blocked solve killed AFTER a per-column
    recovery resumes bit-identically — the recovery state (per-column
    flag, prec_sel) rides the snapshotted carry, so the resumed run
    reproduces the uninterrupted faulted run exactly and re-runs no
    ladder attempts."""
    F = np.asarray(model.F)
    fb = np.stack([F, _hard_load(model)], axis=-1)
    ref = _res_solver(model, tmp_path / "ref", snap=1,
                      fault="rho0@col:1").solve_many(fb)
    assert list(ref.flags) == [0, 0] and ref.recoveries >= 1

    s2 = _res_solver(model, tmp_path / "run", snap=1,
                     fault="rho0@col:1, kill@2")
    with pytest.raises(SimulatedKill):
        s2.solve_many(fb)
    assert glob.glob(os.path.join(s2.config.checkpoint_path,
                                  "many_*.npz"))

    cap = _Cap()
    s3 = _res_solver(model, tmp_path / "run", snap=1, cap=cap)
    res = s3.solve_many(fb, resume=True)
    assert list(res.flags) == [0, 0]
    # the recovery happened BEFORE the kill: the resumed run continues
    # the post-restart Krylov space without consuming new attempts
    assert res.recoveries == 0
    np.testing.assert_array_equal(np.asarray(res.iters),
                                  np.asarray(ref.iters))
    np.testing.assert_array_equal(np.asarray(res.x), np.asarray(ref.x))


def test_one_shot_retry_guard_and_unlandable_column_fault(model,
                                                          tmp_path):
    """One-shot blocked path (ipd=0), both legs on one solver: (a) an
    injected device-loss exception before the dispatch is retried by
    the guard (the program donates nothing, so re-dispatch is safe) and
    the block completes; (b) column faults fire at blocked chunk
    boundaries, of which the one-shot path has NONE — the plan stays
    armed and NOT fired (a chaos drill must never read 'exercised' off
    an injection that could not land), and the solve is untouched."""
    cap = _Cap()
    s = _res_solver(model, tmp_path, ipd=0, fault="exc@0", cap=cap)
    F = np.asarray(model.F)
    fb = np.stack([F, 0.5 * F], axis=-1)
    res = s.solve_many(fb)
    assert list(res.flags) == [0, 0]
    recs = [e for e in cap.events if e["kind"] == "recovery"]
    assert [e["action"] for e in recs] == ["redispatch"]
    assert [f["mode"] for f in s.fault_plan.fired] == ["exc"]

    s.fault_plan = FaultPlan("nan@col:1", recorder=s.recorder)
    res = s.solve_many(fb)
    assert list(res.flags) == [0, 0] and res.quarantined == ()
    assert s.fault_plan.fired == [] and s.fault_plan.col_armed


def test_many_snapshot_retention_and_latest(model, tmp_path,
                                            monkeypatch):
    """Satellite: PCG_TPU_SNAP_KEEP retention pruning and the
    corrupt-tolerant latest() pointer are PREFIX-scoped, so they govern
    the ``many_*`` namespace exactly like ``snap_*``/``step_*``."""
    from pcg_mpi_solver_tpu.utils.checkpoint import SnapshotStore

    monkeypatch.setenv("PCG_TPU_SNAP_KEEP", "2")
    s = _chunked_solver(model, tmp_path)
    store = SnapshotStore.for_many_solver(s, 2, rhs_hash="h")
    other = SnapshotStore.for_solver(s)     # snap_* neighbor namespace
    other.save(7, {"kind": "direct", "total": np.int64(1)})
    for t in (1, 2, 3, 4):
        store.save(t, {"kind": "many", "total": np.int64(t)})
    files = sorted(os.path.basename(p) for p in glob.glob(
        os.path.join(store.path, "many_*.npz")))
    assert files == ["many_000003.npz", "many_000004.npz"], \
        "retention must prune the many_* namespace to the newest K"
    # the neighbor namespace is untouched by many_* pruning
    assert glob.glob(os.path.join(store.path, "snap_*.npz"))
    assert store.latest() == 4
    # corrupt newest -> latest() falls back to the next valid snapshot
    with open(store._file(4), "wb") as f:
        f.write(b"torn")
    assert store.latest() == 3
    assert store.load(4) is None    # corrupt reads as absent, loudly-ish


def test_many_snapshot_fingerprint_tracks_fallback_wiring(model,
                                                          tmp_path):
    """A blocked carry whose ``prec_sel`` flipped a column to the
    fallback preconditioner must never resume into programs compiled
    WITHOUT the fallback operand (the selection would silently compile
    out): the many-snapshot fingerprint records the wiring, so such a
    resume mismatches loudly on ``many_fallback``."""
    from pcg_mpi_solver_tpu.utils.checkpoint import SnapshotStore

    s = _res_solver(model, tmp_path, precond="block3", maxrec=2)
    fp_on = SnapshotStore.for_many_solver(s, 2, rhs_hash="h").fingerprint
    assert fp_on["many_fallback"] is True
    s.config.solver.max_recoveries = 0      # ladder (and operand) off
    fp_off = SnapshotStore.for_many_solver(s, 2,
                                           rhs_hash="h").fingerprint
    assert fp_off["many_fallback"] is False
    # the mismatch names the field (same posture as nrhs/rhs_hash)
    store_on = SnapshotStore(s.config.checkpoint_path, fp_on,
                             prefix="many")
    store_on.save(1, {"kind": "many", "total": np.int64(0)})
    store_off = SnapshotStore(s.config.checkpoint_path, fp_off,
                              prefix="many")
    with pytest.raises(ValueError, match="many_fallback"):
        store_off.load(1)

@pytest.fixture
def cache_dir(tmp_path):
    return str(tmp_path / "warm")


def test_solve_many_warm_zero_builds_zero_traces(model, cache_dir):
    F = np.asarray(model.F)
    fb = np.stack([F, 0.5 * F], axis=-1)

    rec_cold = MetricsRecorder()
    s1 = Solver(model, _cfg(cache_dir=cache_dir), mesh=make_mesh(2),
                n_parts=2, backend="general", recorder=rec_cold)
    r1 = s1.solve_many(fb)
    assert list(r1.flags) == [0, 0]
    assert rec_cold.counters.get("trace.solve_many", 0) == 1
    x1 = np.asarray(r1.x)
    calls_after_cold = dict(BUILD_CALLS)

    rec_warm = MetricsRecorder()
    s2 = Solver(model, _cfg(cache_dir=cache_dir), mesh=make_mesh(2),
                n_parts=2, backend="general", recorder=rec_warm)
    assert dict(BUILD_CALLS) == calls_after_cold, \
        "warm construction must do zero partition builds"
    assert s2.setup_cache == "warm"
    r2 = s2.solve_many(fb)
    # zero jit tracing of the blocked program: the AOT entry was
    # deserialized (the counters increment only inside a live trace)
    assert rec_warm.counters.get("trace.step", 0) == 0
    assert rec_warm.counters.get("trace.solve_many", 0) == 0
    assert rec_warm.counters.get("cache.aot.hit", 0) >= 1
    assert dict(BUILD_CALLS) == calls_after_cold
    np.testing.assert_array_equal(np.asarray(r2.x), x1)
    # a repeated same-shape block on the SAME solver is also trace-free
    s2.solve_many(fb)
    assert rec_warm.counters.get("trace.solve_many", 0) == 0


def test_step_cache_key_carries_nrhs():
    from pcg_mpi_solver_tpu.cache.keys import step_cache_key

    kw = dict(abstract="sig", mesh="m", backend="general",
              solver={"tol": 1e-8}, trace_len=0, glob_n_dof_eff=100,
              donate=False, jax_version="x")
    assert step_cache_key(nrhs=1, **kw) != step_cache_key(nrhs=8, **kw)
    assert step_cache_key(nrhs=8, **kw) == step_cache_key(nrhs=8, **kw)


# ----------------------------------------------------------------------
# Per-request validation (validate/): offending column index
# ----------------------------------------------------------------------

def test_check_rhs_block_names_offending_column():
    good = np.ones((30, 3))
    assert all(r.status in ("ok", "warn")
               for r in check_rhs_block(good, 30))
    bad = good.copy()
    bad[7, 2] = np.nan
    res = {r.name: r for r in check_rhs_block(bad, 30)}
    assert res["rhs_block_finite"].status == "fail"
    assert "rhs 2" in res["rhs_block_finite"].detail
    # shape contract per RHS
    assert check_rhs_block(np.ones((29, 3)), 30)[0].status == "fail"
    assert check_rhs_block(np.ones(30), 30)[0].status == "fail"
    # all-zero column: usable but flagged
    zero_col = good.copy()
    zero_col[:, 1] = 0
    res = {r.name: r for r in check_rhs_block(zero_col, 30)}
    assert res["rhs_block_zero"].status == "warn"
    assert "1" in res["rhs_block_zero"].detail


def test_solve_many_rejects_bad_column(model):
    s = Solver(model, _cfg(), mesh=make_mesh(2), n_parts=2,
               backend="general")
    fb = np.stack([np.asarray(model.F)] * 3, axis=-1)
    fb[11, 1] = np.inf
    with pytest.raises(PreflightError, match="rhs 1"):
        s.solve_many(fb)


# ----------------------------------------------------------------------
# Telemetry plumbing: per-RHS events, schema-valid
# ----------------------------------------------------------------------

def test_solve_many_emits_schema_valid_per_rhs_events(model):
    from pcg_mpi_solver_tpu.obs.schema import validate_event

    class Capture:
        def __init__(self):
            self.events = []

        def emit(self, ev):
            self.events.append(ev)

        def close(self):
            pass

    cap = Capture()
    rec = MetricsRecorder(sinks=[cap])
    s = Solver(model, _cfg(), mesh=make_mesh(2), n_parts=2,
               backend="general", recorder=rec)
    F = np.asarray(model.F)
    s.solve_many(np.stack([F, 0.5 * F], axis=-1))
    kinds = [e["kind"] for e in cap.events]
    assert "solve_many" in kinds
    rhs_events = [e for e in cap.events if e["kind"] == "rhs_solve"]
    assert [e["rhs"] for e in rhs_events] == [0, 1]
    for e in cap.events:
        assert validate_event(e) == [], e
    many = next(e for e in cap.events if e["kind"] == "solve_many")
    assert many["nrhs"] == 2 and many["flags"] == [0, 0]
    assert rec.gauges.get("many.nrhs") == 2


# ----------------------------------------------------------------------
# CLI front-end
# ----------------------------------------------------------------------

def test_cli_solve_many(tmp_path, capsys):
    from pcg_mpi_solver_tpu.cli import main
    from pcg_mpi_solver_tpu.models.mdf import write_mdf

    model = make_cube_model(4, 3, 3, load="traction", heterogeneous=True)
    src = tmp_path / "src"
    write_mdf(model, str(src))
    archive = shutil.make_archive(str(tmp_path / "cube"), "zip", src)
    scratch = str(tmp_path / "scratch")
    main(["ingest", archive, scratch])
    capsys.readouterr()

    main(["solve-many", scratch, "1", "--scales", "1.0,0.5,2.0",
          "--n-parts", "2", "--tol", "1e-8", "--precision", "direct"])
    out = capsys.readouterr().out
    assert ">rhs 0: flag=0" in out and ">rhs 2: flag=0" in out
    assert ">success!" in out
    u = np.load(os.path.join(scratch, "Results_Run1", "u_many.npy"))
    assert u.shape[1] == 3
    np.testing.assert_allclose(u[:, 2], 2.0 * u[:, 0], rtol=1e-6)

    # --rhs file path: a transposed block is accepted
    rhs = np.stack([np.asarray(model.F), 0.5 * np.asarray(model.F)])
    rhs_file = str(tmp_path / "loads.npy")
    np.save(rhs_file, rhs)
    main(["solve-many", scratch, "2", "--rhs", rhs_file,
          "--n-parts", "2", "--tol", "1e-8", "--precision", "direct"])
    out = capsys.readouterr().out
    assert ">rhs 1: flag=0" in out and ">success!" in out


def test_cli_solve_many_max_recoveries_bites(tmp_path, capsys,
                                             monkeypatch):
    """Satellite: --max-recoveries now rides blocked solves for REAL —
    with the ladder on, an injected per-column fault recovers to flag 0;
    with --max-recoveries 0 the same fault quarantines the column (flag
    5) — and the old '--max-recoveries does not yet apply' warning is
    gone."""
    import json

    from pcg_mpi_solver_tpu.cli import main
    from pcg_mpi_solver_tpu.models.mdf import write_mdf

    model = make_cube_model(4, 3, 3, load="traction", heterogeneous=True)
    src = tmp_path / "src"
    write_mdf(model, str(src))
    archive = shutil.make_archive(str(tmp_path / "cube"), "zip", src)
    scratch = str(tmp_path / "scratch")
    main(["ingest", archive, scratch])
    capsys.readouterr()

    # force the chunked/resumable blocked path below the auto-engage
    # size (settings-only override), so boundary faults can land
    settings = str(tmp_path / "settings.json")
    with open(settings, "w") as f:
        json.dump({"SolverParam": {"ItersPerDispatch": 20}}, f)
    monkeypatch.setenv("PCG_TPU_FAULTS", "rho0@col:1")
    monkeypatch.setenv("PCG_TPU_RETRY_BACKOFF_S", "0.01")

    common = ["solve-many", scratch, "--scales", "1.0,0.5",
              "--n-parts", "2", "--tol", "1e-8", "--precision",
              "direct", "--settings", settings]
    main([common[0], common[1], "r1"] + common[2:]
         + ["--max-recoveries", "2"])
    out = capsys.readouterr().out
    assert "does not yet apply" not in out
    assert ">rhs 1: flag=0" in out
    assert ">recoveries: 1" in out

    main([common[0], common[1], "r2"] + common[2:]
         + ["--max-recoveries", "0"])
    out = capsys.readouterr().out
    assert ">rhs 1: flag=5" in out and "[QUARANTINED]" in out
    assert ">quarantined columns: [1]" in out


# ----------------------------------------------------------------------
# bench plumbing: detail fields present + schema-valid
# ----------------------------------------------------------------------

def test_bench_detail_carries_nrhs_fields(monkeypatch):
    import json

    from pcg_mpi_solver_tpu import bench
    from pcg_mpi_solver_tpu.obs.schema import validate_bench_line

    monkeypatch.setenv("BENCH_NRHS", "4")
    model = make_cube_model(3, 3, 3)

    class R:
        flag, relres, iters, wall_s = 0, 1e-9, 10, 0.5

    line = bench._result_json(model, "cube", R, 10, 100.0, "note",
                              {"nrhs": 4})
    d = json.loads(line)
    assert validate_bench_line(d) == []
    assert d["detail"]["nrhs"] == 4
    assert d["detail"]["dof_iter_rhs_per_s"] == pytest.approx(
        4 * d["value"], rel=1e-6)
