"""Preflight subsystem (validate/): pathological models/configs are
rejected BEFORE any partition build or compile — asserted against
parallel/partition.BUILD_CALLS, the same warm-path work counters the
cache contract uses — under the fail/warn/off policy
(PCG_TPU_PREFLIGHT / RunConfig.preflight / --preflight)."""

import os
import shutil

import numpy as np
import pytest

from pcg_mpi_solver_tpu.config import RunConfig, SolverConfig
from pcg_mpi_solver_tpu.models.synthetic import make_cube_model
from pcg_mpi_solver_tpu.obs.metrics import MetricsRecorder
from pcg_mpi_solver_tpu.parallel import partition
from pcg_mpi_solver_tpu.parallel.mesh import make_mesh
from pcg_mpi_solver_tpu.solver.driver import Solver
from pcg_mpi_solver_tpu.validate import (
    PreflightError, preflight_checks, resolve_policy, run_preflight)


class _Capture:
    def __init__(self):
        self.events = []

    def emit(self, ev):
        self.events.append(ev)

    def close(self):
        pass


@pytest.fixture(scope="module")
def model():
    return make_cube_model(4, 3, 3, heterogeneous=True)


def _nan_load_model():
    m = make_cube_model(3, 3, 3)
    m.F[5] = float("nan")
    return m


def _status(results, name):
    return {r.name: r for r in results}[name].status


# ----------------------------------------------------------------------
# Check taxonomy
# ----------------------------------------------------------------------

def test_healthy_model_passes_every_check(model):
    results = preflight_checks(model, RunConfig())
    assert results and all(r.status == "ok" for r in results), \
        [(r.name, r.status, r.detail) for r in results]


def test_nan_everywhere_is_caught():
    for field, check in (("F", "finite_loads"), ("Ud", "finite_loads"),
                         ("Vd", "finite_loads"), ("diag_M", "finite_mass"),
                         ("ck", "finite_scales")):
        m = make_cube_model(3, 3, 3)
        getattr(m, field)[2] = float("inf")
        assert _status(preflight_checks(m), check) == "fail", field
    m = make_cube_model(3, 3, 3)
    m.node_coords[0, 1] = float("nan")
    assert _status(preflight_checks(m), "finite_coords") == "fail"


def test_degenerate_elements_and_constraints():
    m = make_cube_model(3, 3, 3)
    m.level[4] = 0.0
    assert _status(preflight_checks(m), "element_volume") == "fail"
    m2 = make_cube_model(3, 3, 3)
    m2.ck[1] = -1.0
    assert _status(preflight_checks(m2), "element_volume") == "fail"
    m3 = make_cube_model(3, 3, 3)
    m3.fixed_dof = np.zeros(0, dtype=m3.fixed_dof.dtype)
    res = preflight_checks(m3)
    assert _status(res, "constraints") == "fail"
    assert "rigid body" in {r.name: r for r in res}["constraints"].detail


def test_connectivity_contract():
    m = make_cube_model(3, 3, 3)
    m.elem_dofs_flat[7] = m.n_dof + 3      # out-of-range dof id
    assert _status(preflight_checks(m), "connectivity") == "fail"


def test_config_cross_checks(model):
    # mixed tol below the refinement floor: warn, not fail
    cfg = RunConfig(solver=SolverConfig(precision_mode="mixed", tol=1e-15))
    assert _status(preflight_checks(model, cfg), "tol_floor") == "warn"
    # direct f32 below the f32 floor
    cfg = RunConfig(solver=SolverConfig(dtype="float32", tol=1e-9))
    assert _status(preflight_checks(model, cfg), "tol_floor") == "warn"
    # nonsense solver params are fail-class
    cfg = RunConfig(solver=SolverConfig(tol=-1.0))
    assert _status(preflight_checks(model, cfg), "solver_params") == "fail"
    # snapshot cadence beyond the schedule never fires
    cfg = RunConfig()
    cfg.snapshot_every = 50
    res = preflight_checks(model, cfg, context={"n_steps": 5})
    assert _status(res, "snapshot_cadence") == "warn"


def test_explicit_dt_margin(model):
    from pcg_mpi_solver_tpu.solver.dynamics import stable_dt

    bound = stable_dt(model, safety=1.0)
    ctx = {"kind": "dynamics", "dt": 2 * bound, "dt_source": "arg"}
    assert _status(preflight_checks(model, None, ctx),
                   "explicit_dt") == "fail"
    # a model-file dt placeholder only warns (legacy MDF bundles)
    ctx = {"kind": "dynamics", "dt": 2 * bound, "dt_source": "model"}
    assert _status(preflight_checks(model, None, ctx),
                   "explicit_dt") == "warn"
    ctx = {"kind": "dynamics", "dt": 0.5 * bound, "dt_source": "arg"}
    assert _status(preflight_checks(model, None, ctx),
                   "explicit_dt") == "ok"


# ----------------------------------------------------------------------
# Policy: fail / warn / off
# ----------------------------------------------------------------------

def test_policy_resolution(monkeypatch):
    monkeypatch.delenv("PCG_TPU_PREFLIGHT", raising=False)
    assert resolve_policy() == "fail"
    assert resolve_policy("warn") == "warn"
    monkeypatch.setenv("PCG_TPU_PREFLIGHT", "off")
    assert resolve_policy() == "off"
    assert resolve_policy("fail") == "fail"     # arg beats env
    monkeypatch.setenv("PCG_TPU_PREFLIGHT", "frobnicate")
    with pytest.raises(ValueError, match="policy"):
        resolve_policy()


def test_fail_policy_rejects_before_partition_build():
    """ISSUE 4 acceptance: a ModelData with NaN loads (or an
    unconstrained mesh) is rejected by preflight before any partition
    build or compile, asserted via parallel/partition.BUILD_CALLS."""
    before = dict(partition.BUILD_CALLS)
    with pytest.raises(PreflightError, match="finite_loads"):
        Solver(_nan_load_model(), RunConfig(), mesh=make_mesh(1),
               n_parts=1, backend="general")
    m = make_cube_model(3, 3, 3)
    m.fixed_dof = np.zeros(0, dtype=m.fixed_dof.dtype)
    with pytest.raises(PreflightError, match="constraints"):
        Solver(m, RunConfig(), mesh=make_mesh(1), n_parts=1,
               backend="general")
    assert partition.BUILD_CALLS == before


def test_time_drivers_reject_before_partition_build():
    from pcg_mpi_solver_tpu.solver.dynamics import DynamicsSolver
    from pcg_mpi_solver_tpu.solver.newmark import NewmarkSolver

    before = dict(partition.BUILD_CALLS)
    with pytest.raises(PreflightError):
        NewmarkSolver(_nan_load_model(), RunConfig(), mesh=make_mesh(1),
                      n_parts=1, dt=0.1)
    with pytest.raises(PreflightError):
        DynamicsSolver(_nan_load_model(), RunConfig(), mesh=make_mesh(1),
                       n_parts=1)
    assert partition.BUILD_CALLS == before


def test_warn_policy_proceeds_with_warning():
    cfg = RunConfig()
    cfg.preflight = "warn"
    before = partition.BUILD_CALLS["partition_model"]
    with pytest.warns(UserWarning, match="preflight rejected"):
        s = Solver(_nan_load_model(), cfg, mesh=make_mesh(1), n_parts=1,
                   backend="general")
    assert s.backend == "general"
    assert partition.BUILD_CALLS["partition_model"] == before + 1


def test_off_policy_skips_scans(model):
    cfg = RunConfig()
    cfg.preflight = "off"
    assert run_preflight(_nan_load_model(), cfg) == []
    cap = _Capture()
    run_preflight(model, cfg, recorder=MetricsRecorder(sinks=[cap]))
    assert cap.events == []         # off emits nothing, scans nothing


def test_env_policy_drives_constructors(model, monkeypatch):
    monkeypatch.setenv("PCG_TPU_PREFLIGHT", "off")
    s = Solver(_nan_load_model(), RunConfig(), mesh=make_mesh(1),
               n_parts=1, backend="general")     # no gate, no raise
    assert s.backend == "general"


# ----------------------------------------------------------------------
# Telemetry event
# ----------------------------------------------------------------------

def test_preflight_event_schema(model):
    from pcg_mpi_solver_tpu.obs.schema import validate_event

    cap = _Capture()
    run_preflight(model, RunConfig(),
                  recorder=MetricsRecorder(sinks=[cap]),
                  context={"kind": "quasi_static"})
    evs = [e for e in cap.events if e["kind"] == "preflight"]
    assert len(evs) == 1
    ev = evs[0]
    assert validate_event(ev) == []
    assert ev["policy"] == "fail" and ev["failed"] == 0
    assert {c["name"] for c in ev["checks"]} >= {
        "finite_loads", "constraints", "element_volume", "connectivity"}


def test_rejected_event_still_emitted():
    cap = _Capture()
    with pytest.raises(PreflightError):
        run_preflight(_nan_load_model(), RunConfig(),
                      recorder=MetricsRecorder(sinks=[cap]))
    ev = [e for e in cap.events if e["kind"] == "preflight"][0]
    assert ev["failed"] == 1        # the post-mortem survives the raise


# ----------------------------------------------------------------------
# CLI: validate subcommand + --preflight plumbing
# ----------------------------------------------------------------------

def test_cli_validate_subcommand(tmp_path, capsys):
    from pcg_mpi_solver_tpu.cli import main
    from pcg_mpi_solver_tpu.models.mdf import write_mdf

    model = make_cube_model(3, 3, 3, load="traction")
    src = tmp_path / "src"
    write_mdf(model, str(src))
    archive = shutil.make_archive(str(tmp_path / "cube"), "zip", src)
    scratch = str(tmp_path / "scratch")
    main(["ingest", archive, scratch])
    main(["validate", scratch])
    out = capsys.readouterr().out
    assert ">validate: all checks passed" in out

    # poison the scratch model: validate must exit non-zero
    bad = make_cube_model(3, 3, 3, load="traction")
    bad.F[0] = float("nan")
    src2 = tmp_path / "src2"
    write_mdf(bad, str(src2))
    archive2 = shutil.make_archive(str(tmp_path / "bad"), "zip", src2)
    scratch2 = str(tmp_path / "scratch2")
    main(["ingest", archive2, scratch2])
    with pytest.raises(SystemExit, match="failed check"):
        main(["validate", scratch2])
    out = capsys.readouterr().out
    assert "FAIL" in out and "finite_loads" in out


def test_cli_preflight_flag(tmp_path, capsys):
    """--preflight=off reaches the Solver: a NaN model solves far enough
    to fail later (or not at all for ingest-only paths) instead of being
    gated — here we just assert the flag lands in the RunConfig."""
    import argparse

    from pcg_mpi_solver_tpu.cli import _load_settings

    args = argparse.Namespace(preflight="warn", tol=None, max_iter=None,
                              precision=None, precond=None,
                              telemetry_out=None, trace_resid=None,
                              cache_dir=None)
    cfg = _load_settings(None, args)
    assert cfg.preflight == "warn"
