"""Checkpoint/resume: a multi-step solve interrupted after step k and
resumed must reproduce the uninterrupted run exactly (histories, solution,
export frames).  The reference has no in-solve checkpointing (SURVEY.md §5)
— this is a capability the TPU framework adds."""

import numpy as np
import pytest

from pcg_mpi_solver_tpu import RunConfig, SolverConfig, TimeHistoryConfig
from pcg_mpi_solver_tpu.models import make_cube_model
from pcg_mpi_solver_tpu.parallel.mesh import make_mesh
from pcg_mpi_solver_tpu.solver import Solver
from pcg_mpi_solver_tpu.utils.checkpoint import CheckpointManager
from pcg_mpi_solver_tpu.utils.io import RunStore


def _cfg(tmp_path, run_id="1", every=1, plot=False):
    return RunConfig(
        scratch_path=str(tmp_path),
        run_id=run_id,
        checkpoint_every=every,
        solver=SolverConfig(tol=1e-8, max_iter=500),
        time_history=TimeHistoryConfig(
            time_step_delta=[0.0, 0.25, 0.5, 1.0],
            export_frame_rate=1,
            plot_flag=plot,
            probe_dofs=(3, 10) if plot else (),
        ),
    )


@pytest.fixture(scope="module")
def model():
    return make_cube_model(5, 4, 4, heterogeneous=True)


def test_resume_matches_uninterrupted(tmp_path, model):
    # Full uninterrupted run.
    cfg_a = _cfg(tmp_path, run_id="a", every=0)
    sa = Solver(model, cfg_a, mesh=make_mesh(4), n_parts=4)
    store_a = RunStore(cfg_a.result_path)
    sa.solve(store=store_a)

    # Interrupted run: stop after step 2 (simulated by a truncated schedule
    # sharing the same checkpoint dir), then resume with the full schedule.
    cfg_b = _cfg(tmp_path, run_id="b", every=1)
    sb1 = Solver(model, cfg_b, mesh=make_mesh(4), n_parts=4)
    store_b = RunStore(cfg_b.result_path)
    steps_run = []

    def interrupt_after_2(t, r):
        steps_run.append(t)
        if t == 2:
            raise KeyboardInterrupt

    with pytest.raises(KeyboardInterrupt):
        sb1.solve(store=store_b, on_step=interrupt_after_2)
    assert max(steps_run) == 2

    sb2 = Solver(model, cfg_b, mesh=make_mesh(4), n_parts=4)
    resumed = []
    sb2.solve(store=store_b, resume=True, on_step=lambda t, r: resumed.append(t))
    assert resumed == [3]

    # Histories identical to the uninterrupted run.
    assert sb2.iters == sa.iters
    assert sb2.flags == sa.flags
    np.testing.assert_allclose(sb2.relres, sa.relres, rtol=1e-12)
    np.testing.assert_allclose(sb2.displacement_global(),
                               sa.displacement_global(), rtol=1e-12, atol=0)

    # Export frames identical (frame 0 + 3 steps).
    assert store_b.n_frames("U") == store_a.n_frames("U") == 4
    for k in range(4):
        np.testing.assert_allclose(store_b.read_frame("U", k),
                                   store_a.read_frame("U", k),
                                   rtol=1e-12, atol=0)


def test_fingerprint_mismatch_raises(tmp_path, model):
    cfg = _cfg(tmp_path, run_id="c", every=1)
    s = Solver(model, cfg, mesh=make_mesh(4), n_parts=4)
    s.solve()

    cfg2 = _cfg(tmp_path, run_id="c", every=1)
    cfg2.solver = SolverConfig(tol=1e-4, max_iter=500)   # different tol
    s2 = Solver(model, cfg2, mesh=make_mesh(4), n_parts=4)
    mgr = CheckpointManager(cfg2.checkpoint_path)
    with pytest.raises(ValueError, match="mismatch"):
        mgr.restore(s2)


def test_model_content_mismatch_raises(tmp_path, model):
    """Resuming against a model of identical shapes but different content
    (here: a perturbed stiffness field) must be rejected — shape-only
    fingerprints would silently produce garbage."""
    import dataclasses

    cfg = _cfg(tmp_path, run_id="cm", every=1)
    s = Solver(model, cfg, mesh=make_mesh(4), n_parts=4)
    s.solve()

    mutated = dataclasses.replace(model, ck=model.ck * 1.5)
    cfg2 = _cfg(tmp_path, run_id="cm", every=1)
    s2 = Solver(mutated, cfg2, mesh=make_mesh(4), n_parts=4)
    mgr = CheckpointManager(cfg2.checkpoint_path)
    with pytest.raises(ValueError, match="mismatch"):
        mgr.restore(s2)


def test_material_law_mismatch_raises(tmp_path, model):
    """A different Poisson ratio changes only the element library (ck/F/Ud
    etc. are byte-identical), so the fingerprint must hash Ke/mat_prop too."""
    cfg = _cfg(tmp_path, run_id="nu", every=1)
    s = Solver(model, cfg, mesh=make_mesh(4), n_parts=4)
    s.solve()

    mutated = make_cube_model(5, 4, 4, nu=0.25, heterogeneous=True)
    cfg2 = _cfg(tmp_path, run_id="nu", every=1)
    s2 = Solver(mutated, cfg2, mesh=make_mesh(4), n_parts=4)
    mgr = CheckpointManager(cfg2.checkpoint_path)
    with pytest.raises(ValueError, match="mismatch"):
        mgr.restore(s2)


def test_resume_without_checkpoint_is_fresh(tmp_path, model):
    cfg = _cfg(tmp_path, run_id="d", every=0)
    s = Solver(model, cfg, mesh=make_mesh(4), n_parts=4)
    res = s.solve(resume=True)       # no checkpoint dir -> full run
    assert len(res) == 3


def test_checkpoint_files_and_latest(tmp_path, model):
    cfg = _cfg(tmp_path, run_id="e", every=2)
    s = Solver(model, cfg, mesh=make_mesh(4), n_parts=4)
    s.solve()
    mgr = CheckpointManager(cfg.checkpoint_path)
    # steps 2 (every=2) and 3 (final) are checkpointed
    assert mgr.latest_step() == 3
    assert mgr.restore(Solver(model, cfg, mesh=make_mesh(4), n_parts=4)) == 3


def test_probe_history_survives_resume(tmp_path, model):
    cfg_a = _cfg(tmp_path, run_id="f", every=0, plot=True)
    sa = Solver(model, cfg_a, mesh=make_mesh(4), n_parts=4)
    store_a = RunStore(cfg_a.result_path)
    sa.solve(store=store_a)

    cfg_b = _cfg(tmp_path, run_id="g", every=1, plot=True)
    sb = Solver(model, cfg_b, mesh=make_mesh(4), n_parts=4)
    store_b = RunStore(cfg_b.result_path)
    try:
        sb.solve(store=store_b,
                 on_step=lambda t, r: (_ for _ in ()).throw(KeyboardInterrupt)
                 if t == 2 else None)
    except KeyboardInterrupt:
        pass
    sb2 = Solver(model, cfg_b, mesh=make_mesh(4), n_parts=4)
    sb2.solve(store=store_b, resume=True)

    def plot_u(path):
        z = np.load(f"{path}/model_PlotData.npz", allow_pickle=True)
        return z["PlotData"].item()["Plot_U"]

    np.testing.assert_allclose(plot_u(cfg_b.plot_path),
                               plot_u(cfg_a.plot_path), rtol=1e-12)


def test_latest_pointer_fallback_to_newest_valid(tmp_path, model):
    """When the `latest` pointer references a missing or corrupt
    ckpt_*.npz, resume falls back to the newest VALID checkpoint instead
    of silently starting fresh (ISSUE 3 satellite)."""
    import os

    cfg = _cfg(tmp_path, run_id="fb", every=1)
    s = Solver(model, cfg, mesh=make_mesh(4), n_parts=4)
    s.solve()
    mgr = CheckpointManager(cfg.checkpoint_path)
    assert mgr.latest_step() == 3

    # corrupt the pointer's target (truncated write): fall back to t=2
    latest = os.path.join(cfg.checkpoint_path, "ckpt_000003.npz")
    blob = open(latest, "rb").read()
    with open(latest, "wb") as f:
        f.write(blob[: len(blob) // 3])
    with pytest.warns(UserWarning, match="falling back"):
        assert mgr.latest_step() == 2
    s2 = Solver(model, cfg, mesh=make_mesh(4), n_parts=4)
    with pytest.warns(UserWarning, match="falling back"):
        assert mgr.restore(s2) == 2

    # remove it entirely (dangling pointer): same fallback
    os.remove(latest)
    with pytest.warns(UserWarning, match="falling back"):
        assert mgr.latest_step() == 2

    # no valid checkpoint at all -> None (fresh run), not a crash
    for f in os.listdir(cfg.checkpoint_path):
        if f.startswith("ckpt_"):
            os.remove(os.path.join(cfg.checkpoint_path, f))
    assert mgr.latest_step() is None


def test_kill_and_resume_mid_solve_parity(tmp_path, model):
    """ISSUE 3 acceptance (a): a chunked solve killed at a chunk
    boundary (injected SimulatedKill) and resumed produces the same
    final flag/relres and BIT-IDENTICAL convergence history as an
    uninterrupted solve — the mid-Krylov snapshot loses at most one
    chunk and the resumed Krylov recurrence replays exactly."""
    from pcg_mpi_solver_tpu.resilience import FaultPlan, SimulatedKill

    def _cfg_chunked(run_id):
        cfg = _cfg(tmp_path, run_id=run_id, every=1)
        cfg.solver.iters_per_dispatch = 12   # force the chunked path
        cfg.solver.trace_resid = 64          # ring rides the snapshots
        cfg.snapshot_every = 1
        return cfg

    cfg_a = _cfg_chunked("ka")
    sa = Solver(model, cfg_a, mesh=make_mesh(4), n_parts=4)
    sa.solve()
    trace_a = sa.last_trace

    cfg_b = _cfg_chunked("kb")
    sb = Solver(model, cfg_b, mesh=make_mesh(4), n_parts=4)
    sb.fault_plan = FaultPlan("kill@3")      # die mid-step at boundary 3
    with pytest.raises(SimulatedKill):
        sb.solve()
    import os

    snaps = [f for f in os.listdir(cfg_b.checkpoint_path)
             if f.startswith("snap_")]
    assert snaps, "the kill must leave a mid-Krylov snapshot behind"

    sb2 = Solver(model, cfg_b, mesh=make_mesh(4), n_parts=4)
    sb2.solve(resume=True)

    # bit-identical history: same flags, EXACT relres/iters equality,
    # exact solution bytes, and the in-graph convergence ring (which
    # rode the snapshot across the kill) matches sample for sample
    assert sb2.flags == sa.flags
    assert sb2.iters == sa.iters
    assert sb2.relres == sa.relres
    np.testing.assert_array_equal(sb2.displacement_global(),
                                  sa.displacement_global())
    trace_b = sb2.last_trace
    assert trace_b.n_recorded == trace_a.n_recorded
    np.testing.assert_array_equal(trace_b.normr, trace_a.normr)
    # completed steps discarded their snapshots
    assert not [f for f in os.listdir(cfg_b.checkpoint_path)
                if f.startswith("snap_")]


def test_resume_rejects_flipped_stencil_knobs(tmp_path):
    """The hybrid block layout changes the stencil's summation order: a
    resume under a flipped layout must be refused, not silently drift.
    A checkpoint recorded by a removed matvec path (a stencil form, the
    Pallas kernel, the scatter combine) is refused by the path's name;
    one whose record names the paths that stay restores."""
    import json
    import os

    from pcg_mpi_solver_tpu.models.octree import make_octree_model

    model = make_octree_model(2, 2, 2, max_level=2, n_incl=2, seed=3)
    cfg = RunConfig(scratch_path=str(tmp_path), checkpoint_every=1,
                    solver=SolverConfig(tol=1e-8, max_iter=50),
                    time_history=TimeHistoryConfig(
                        time_step_delta=[0.0, 1.0]))
    mgr = CheckpointManager(str(tmp_path / "ck"))

    def build():
        return Solver(model, cfg, mesh=make_mesh(1), n_parts=1,
                      backend="hybrid")

    def record(**entries):
        """Rewrite the saved fingerprint with ``entries`` added."""
        path = mgr._ckpt_file(1)
        with np.load(path) as z:
            payload = {k: z[k] for k in z.files}
        fp = json.loads(bytes(payload["fingerprint"]).decode())
        fp.update(entries)
        payload["fingerprint"] = np.frombuffer(
            json.dumps(fp, sort_keys=True).encode(), dtype=np.uint8)
        np.savez(path, **payload)

    prev = os.environ.get("PCG_TPU_HYBRID_BLOCK")
    try:
        os.environ["PCG_TPU_HYBRID_BLOCK"] = "2"
        s = build()
        s.step(1.0)
        mgr.save(s, 1)

        # same env: restores fine
        assert mgr.restore(build(), 1) == 1

        # the paths that stay, as older checkpoints recorded them
        record(matvec_form="gse", pallas="off", combine="gather")
        assert mgr.restore(build(), 1) == 1

        # a removed stencil form, kernel or combine: refused by name
        for entry, path in (({"matvec_form": "corner"}, "corner/gsplit"),
                            ({"pallas": "v6"}, "Pallas"),
                            ({"combine": "scatter"}, "scatter combine")):
            record(**entry)
            with pytest.raises(ValueError, match=path):
                mgr.restore(build(), 1)
            record(matvec_form="gse", pallas="off", combine="gather")

        # flipped block layout: refused
        os.environ["PCG_TPU_HYBRID_BLOCK"] = "1000000"
        with pytest.raises(ValueError, match="level_dims"):
            mgr.restore(build(), 1)
    finally:
        if prev is None:
            os.environ.pop("PCG_TPU_HYBRID_BLOCK", None)
        else:
            os.environ["PCG_TPU_HYBRID_BLOCK"] = prev
