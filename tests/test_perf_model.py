"""Analytic per-iteration cost model (obs/perf.py) + measured phase
attribution (obs/phases.py) — ISSUE 12.

The model side is pure python over the single-source ops tables, so the
full variant x precond enumeration is cheap to pin; the probe side is
exercised on a small CPU cube through the real Solver (same ops, same
shard_map programs) and through the ``pcg-tpu perf-report`` CLI verb.
"""

import json

import numpy as np
import pytest

from pcg_mpi_solver_tpu.config import (
    PCG_VARIANTS, PRECONDS, RunConfig, SolverConfig)
from pcg_mpi_solver_tpu.models.synthetic import make_cube_model
from pcg_mpi_solver_tpu.obs import perf
from pcg_mpi_solver_tpu.obs.schema import (
    BENCH_DETAIL_NUMERIC, validate_jsonl_text)
from pcg_mpi_solver_tpu.parallel.mesh import make_mesh
from pcg_mpi_solver_tpu.solver.driver import Solver

#: multi-part synthetic geometry: collective terms engage (n_parts > 1),
#: element groups present so matvec flops/bytes come from the groups.
MP_SHAPE = perf.ProblemShape(n_dof=30_000, n_parts=8, n_iface=2_000,
                             elem_groups=((24, 9_000),),
                             mg_coarse_dofs=4_000)
SP_SHAPE = perf.ProblemShape(n_dof=30_000, n_parts=1,
                             elem_groups=((24, 9_000),))


# ---------------------------------------------------------------- model
def test_cost_model_every_combo_positive_and_complete():
    """The full canonical enumeration (the cost-model-completeness
    analysis rule proves the same totality in the lint tier): every
    variant x precond x nrhs entry has all four phases and a finite
    positive prediction."""
    table = perf.cost_model_table(MP_SHAPE, nrhs_set=(1, 8))
    assert len(table) == len(PCG_VARIANTS) * len(PRECONDS) * 2
    for (v, p, r), cm in table.items():
        assert tuple(cm["phases"]) == perf.PHASES, (v, p, r)
        pred = cm["predicted_ms_per_iter"]
        assert np.isfinite(pred) and pred > 0, (v, p, r, pred)
        assert pred == pytest.approx(
            sum(cm["phases"][ph]["model_ms"] for ph in perf.PHASES),
            rel=1e-6)


def test_single_part_has_no_collective_terms():
    for v in PCG_VARIANTS:
        costs = perf.phase_costs(SP_SHAPE, v, "jacobi")
        for ph, c in costs.items():
            assert c.coll_count == 0 and c.coll_bytes == 0, (v, ph)


def test_reduction_collectives_follow_variant_table():
    """The model's reduction-phase psum count IS the declared
    PCG_SCALAR_PSUMS row — classic's 3 serialized reductions vs the one
    fused/pipelined psum show up as collective latency the fused
    variants don't pay."""
    from pcg_mpi_solver_tpu.ops.matvec import (
        PCG_SCALAR_PSUMS, PCG_VECTOR_AXPYS)

    for v in PCG_VARIANTS:
        costs = perf.phase_costs(MP_SHAPE, v, "jacobi")
        assert costs["reduction"].coll_count == PCG_SCALAR_PSUMS[v], v
        # axpy flops scale with the declared vector-update count
        assert costs["axpy"].flops == pytest.approx(
            2.0 * MP_SHAPE.n_dof * PCG_VECTOR_AXPYS[v])
    classic = perf.phase_costs(MP_SHAPE, "classic", "jacobi")["reduction"]
    fused = perf.phase_costs(MP_SHAPE, "fused", "jacobi")["reduction"]
    assert classic.coll_count > fused.coll_count


def test_unknown_variant_and_precond_raise_keyerror():
    """The single-source-table loudness contract: an out-of-sync name
    must never model as a silent default row."""
    with pytest.raises(KeyError):
        perf.phase_costs(MP_SHAPE, "no_such_variant", "jacobi")
    with pytest.raises(KeyError):
        perf.phase_costs(MP_SHAPE, "classic", "no_such_precond")
    with pytest.raises(KeyError):
        perf.cost_model(MP_SHAPE, "classic", "no_such_precond")


def test_nrhs_widens_memory_bound_phases_linearly():
    one = perf.phase_costs(MP_SHAPE, "fused", "jacobi", nrhs=1)
    eight = perf.phase_costs(MP_SHAPE, "fused", "jacobi", nrhs=8)
    for ph in perf.PHASES:
        assert eight[ph].flops == pytest.approx(8 * one[ph].flops)
        assert eight[ph].hbm_bytes == pytest.approx(8 * one[ph].hbm_bytes)
    # psum COUNT does not grow with the block width (payload does)
    assert eight["reduction"].coll_count == one["reduction"].coll_count
    assert eight["reduction"].coll_bytes == pytest.approx(
        8 * one["reduction"].coll_bytes)


def test_mg_predicts_costlier_iterations_than_jacobi():
    """The V-cycle's extra fine matvecs must show up in the precond
    phase — an mg iteration that models cheaper than jacobi would
    invert every measured A/B in the repo."""
    for v in PCG_VARIANTS:
        mg = perf.cost_model(MP_SHAPE, v, "mg")
        ja = perf.cost_model(MP_SHAPE, v, "jacobi")
        assert mg["phases"]["precond"]["model_ms"] > \
            3 * ja["phases"]["precond"]["model_ms"]
        assert mg["predicted_ms_per_iter"] > ja["predicted_ms_per_iter"]


def test_resolve_profile_device_kind_and_env_overrides(monkeypatch):
    assert perf.resolve_profile("cpu").name == "cpu"
    assert perf.resolve_profile("CPU (x86)").name == "cpu"
    monkeypatch.setenv("PCG_TPU_ROOFLINE_HBM_GBS", "123")
    monkeypatch.setenv("PCG_TPU_ROOFLINE_COLL_LAT_US", "7")
    prof = perf.resolve_profile("TPU v5 lite")
    assert prof.hbm_bytes_per_s == pytest.approx(123e9)
    assert prof.coll_latency_s == pytest.approx(7e-6)
    # overridden HBM rate must move a memory-bound prediction
    base = perf.cost_model(SP_SHAPE, "classic", "jacobi",
                           profile=perf.HW_PROFILES["TPU v5 lite"])
    fast = perf.cost_model(SP_SHAPE, "classic", "jacobi", profile=prof)
    assert fast["predicted_ms_per_iter"] != \
        base["predicted_ms_per_iter"]


def test_resolve_profile_v5e_published_rates():
    """The v5e entry carries the published peaks (Google Cloud "TPU
    v5e"): its HBM rate never exceeds 819 GB/s."""
    p = perf.resolve_profile("TPU v5 lite")   # the v5e's device_kind
    assert p.name == "TPU v5e"
    assert p.hbm_bytes_per_s <= 819e9
    assert p.flops_per_s == pytest.approx(197e12)


@pytest.mark.parametrize("kind", ["TPU v4", "tpu", "TPU v5e", "TPU v6 lite"])
def test_resolve_profile_unknown_accelerator_raises(kind):
    """No rate is invented for an accelerator kind without an entry."""
    with pytest.raises(ValueError, match="no roofline profile"):
        perf.resolve_profile(kind)


def test_bench_detail_schema_covers_model_fields():
    assert "predicted_ms_per_iter" in BENCH_DETAIL_NUMERIC
    assert "model_ratio" in BENCH_DETAIL_NUMERIC


def test_bench_line_prediction_from_detail_fields():
    """bench._predict_ms_per_iter builds the model from a line's OWN
    detail dict (salvage lines have no live solver): known combo ->
    positive number, no dofs -> null, unknown variant -> loud
    KeyError."""
    from pcg_mpi_solver_tpu.bench import _predict_ms_per_iter

    detail = {"n_dof": 3_000_000, "n_parts": 8, "backend": "structured",
              "mode": "mixed", "dtype": "float64", "platform": "tpu",
              "device_kind": "TPU v5 lite",
              "pcg_variant": "fused", "precond": "jacobi", "nrhs": 1}
    pred = _predict_ms_per_iter(detail)
    assert pred and np.isfinite(pred) and pred > 0
    assert _predict_ms_per_iter({**detail, "n_dof": 0}) is None
    # an accelerator kind with no profile gets no invented number
    assert _predict_ms_per_iter({**detail, "device_kind": "TPU v6e"}) \
        is None
    with pytest.raises(KeyError):
        _predict_ms_per_iter({**detail, "pcg_variant": "mislabeled"})


# ---------------------------------------------------------------- probes
@pytest.fixture(scope="module")
def probed_solver(tmp_path_factory):
    """One small heterogeneous cube Solver with a telemetry JSONL sink —
    shared by the probe tests (construction emits the cost_model
    event)."""
    out = str(tmp_path_factory.mktemp("perf") / "run.jsonl")
    model = make_cube_model(8, 0, 0, E=30e9, nu=0.2, load="traction",
                            load_value=1e6, heterogeneous=True)
    cfg = RunConfig(telemetry_path=out,
                    solver=SolverConfig(tol=1e-8, max_iter=400))
    s = Solver(model, cfg, mesh=make_mesh(1), n_parts=1,
               backend="general")
    return s, out


def _events(path):
    text = open(path).read()
    assert validate_jsonl_text(text) == []
    return [json.loads(ln) for ln in text.splitlines()]


def test_solver_emits_cost_model_event_and_gauges(probed_solver):
    s, out = probed_solver
    assert s._cost_model is not None
    events = [e for e in _events(out) if e["kind"] == "cost_model"]
    assert len(events) == 1
    cm = events[0]
    assert cm["pcg_variant"] == "classic" and cm["precond"] == "jacobi"
    assert cm["backend"] == s.backend
    assert tuple(cm["phases"]) == perf.PHASES
    assert cm["predicted_ms_per_iter"] > 0
    assert cm["predicted_ms_per_iter"] == \
        s._cost_model["predicted_ms_per_iter"]
    assert s.recorder.gauges["perf.predicted_ms_per_iter"] == \
        cm["predicted_ms_per_iter"]
    # the derived geometry reflects the real model
    assert s._perf_shape.n_dof == s.pm.glob_n_dof
    assert s._perf_shape.elem_groups, "element groups not derived"


def test_solver_degrades_on_shape_derivation_keyerror(
        tmp_path, monkeypatch):
    """The loud-KeyError contract belongs to the cost_model() name
    tables ONLY: a KeyError thrown by shape derivation (e.g. a refactor
    that switches a getattr to dict indexing) must degrade to a note,
    not abort Solver construction — observability is not a solve
    dependency."""
    from pcg_mpi_solver_tpu.config import RunConfig, SolverConfig
    from pcg_mpi_solver_tpu.solver.driver import Solver

    def boom(_s):
        raise KeyError("some_internal_field")

    monkeypatch.setattr(perf, "shape_from_solver", boom)
    model = make_cube_model(4, 0, 0, E=30e9, nu=0.2, load="traction",
                            load_value=1e6, heterogeneous=True)
    out = str(tmp_path / "t.jsonl")
    s = Solver(model, RunConfig(solver=SolverConfig(tol=1e-6),
                                telemetry_path=out),
               mesh=make_mesh(1), n_parts=1, backend="general")
    assert s._cost_model is None and s._perf_shape is None
    notes = [e for e in _events(out) if e["kind"] == "note"]
    assert any("cost_model unavailable" in str(e.get("msg", ""))
               for e in notes), notes


def test_phase_probe_sum_approximates_whole_iteration(probed_solver):
    """The acceptance shape on the CPU golden model: four positive
    measured phases whose sum lands in the same regime as the real
    whole-iteration time.  The band is deliberately generous (the CI
    container is shared and this cube is small); `pcg-tpu perf-report`
    at its default size is the calibrated surface."""
    from pcg_mpi_solver_tpu.obs.phases import run_phase_probe

    s, out = probed_solver
    payload = run_phase_probe(s, reps=2, inner=8)
    assert tuple(payload["phases"]) == perf.PHASES
    assert all(v > 0 for v in payload["phases"].values())
    assert payload["sum_ms_per_iter"] == pytest.approx(
        sum(payload["phases"].values()), rel=1e-6)
    assert payload["whole_ms_per_iter"] > 0
    assert payload["whole_iters"] >= 1
    assert 0.25 < payload["attribution"] < 3.0, payload
    # emitted as a schema-valid phase_probe event with perf gauges
    events = [e for e in _events(out) if e["kind"] == "phase_probe"]
    assert events and events[-1]["sum_ms_per_iter"] == \
        payload["sum_ms_per_iter"]
    assert s.recorder.gauges["perf.measured.matvec_ms"] == \
        payload["phases"]["matvec"]


def test_phase_probe_counts_no_extra_collectives(probed_solver):
    """Probe fidelity: the reduction program must execute the VARIANT's
    declared psum count — the trace-level proof is the jaxpr psum count
    of the built reduction program on a 2-part mesh."""
    import jax

    from pcg_mpi_solver_tpu.analysis.jaxpr_utils import (
        collective_histogram)
    from pcg_mpi_solver_tpu.obs.phases import PhaseProbe
    from pcg_mpi_solver_tpu.ops.matvec import PCG_SCALAR_PSUMS

    s, _ = probed_solver
    probe = PhaseProbe(s, inner=4)
    probe._build()
    jaxpr = jax.make_jaxpr(probe._progs["reduction"])(s.data)
    # the fori_loop body traces ONCE, so the histogram is exactly the
    # per-iteration-equivalent collective count the phase quotes
    assert collective_histogram(jaxpr).get("psum", 0) == \
        PCG_SCALAR_PSUMS["classic"]


def test_phase_probe_rejects_mixed_mode():
    from pcg_mpi_solver_tpu.obs.phases import PhaseProbe

    model = make_cube_model(4, 0, 0, E=30e9, nu=0.2, load="traction",
                            load_value=1e6, heterogeneous=True)
    cfg = RunConfig(solver=SolverConfig(tol=1e-8, precision_mode="mixed"))
    s = Solver(model, cfg, mesh=make_mesh(1), n_parts=1,
               backend="general")
    with pytest.raises(ValueError, match="direct-mode"):
        PhaseProbe(s)


def test_perf_report_cli_end_to_end(tmp_path, capsys):
    """The acceptance verb: `pcg-tpu perf-report` on a CPU golden solve
    prints the measured-vs-model table for all four phases, the
    whole-iteration anchor and the attribution ratio, and leaves a
    schema-valid telemetry stream carrying cost_model + phase_probe."""
    from pcg_mpi_solver_tpu.cli import main

    out = str(tmp_path / "perf.jsonl")
    main(["perf-report", "--nx", "8", "--reps", "1", "--inner", "6",
          "--telemetry-out", out])
    stdout = capsys.readouterr().out
    for ph in perf.PHASES:
        assert f"\n{ph}" in stdout, stdout
    assert ">whole-iteration anchor:" in stdout
    assert ">attribution (phase sum / whole):" in stdout
    assert ">model ratio (measured whole / predicted):" in stdout
    kinds = [e["kind"] for e in _events(out)]
    assert "cost_model" in kinds and "phase_probe" in kinds


def test_perf_report_cli_measured_only_when_model_degrades(
        tmp_path, capsys, monkeypatch):
    """When the cost-model derivation raises on an exotic model the
    Solver degrades to _cost_model=None with a note; perf-report must
    then print the MEASURED-only table instead of re-raising the same
    exception through its fallback recompute."""
    from pcg_mpi_solver_tpu.cli import main

    def boom(*a, **k):
        raise RuntimeError("synthetic cost-model failure")

    monkeypatch.setattr(perf, "cost_model", boom)
    main(["perf-report", "--nx", "8", "--reps", "1", "--inner", "6"])
    stdout = capsys.readouterr().out
    assert ">cost model unavailable (RuntimeError: synthetic " \
           "cost-model failure) — measured-only table" in stdout
    for ph in perf.PHASES:
        assert f"\n{ph}" in stdout, stdout       # measured rows printed
    # every model cell AND the sum print '-' — never a fabricated 0.0000
    table = [ln for ln in stdout.splitlines()
             if ln.split(" ")[0] in perf.PHASES + ("sum",)]
    assert len(table) == 5 and all(ln.split()[1] == "-" for ln in table)
    assert ">whole-iteration anchor:" in stdout
    assert ">model ratio" not in stdout          # no model to compare
