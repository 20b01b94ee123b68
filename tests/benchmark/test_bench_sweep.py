"""The load-case cell ``cube.sweep8``: a block of eight load cases per
``Solver.solve_many``, checked column by column against the reference.
Whole rehearsal runs with the timed path broken underneath must fail
the comparison; the load set's fields are what the mix says."""

import json
import os
import time

import numpy as np
import pytest

from harness import cell
from harness.spec import Spec

from pcg_mpi_solver_tpu.solver.driver import Solver

SEED = 2 ** 31 + 29


def _rehearse():
    result, ok = cell.run("cube.sweep8", SEED, 0.0, True,
                          time.perf_counter(), rehearsal=True)
    assert result["correct"] is False          # a rehearsal never is
    assert result["attempted"] == 8
    return result, ok


def one_column_off(mp):
    """Column 3 of the block's solution is scaled by 1 + 1e-5 where it
    is produced."""
    solve_many = Solver.solve_many

    def off(self, fexts, resume=False):
        r = solve_many(self, fexts, resume)
        r.x = r.x.at[..., 3].multiply(1.0 + 1e-5)
        return r

    mp.setattr(Solver, "solve_many", off)


def columns_crossed(mp):
    """Column 5 is answered with column 4's solution."""
    solve_many = Solver.solve_many

    def crossed(self, fexts, resume=False):
        r = solve_many(self, fexts, resume)
        r.x = r.x.at[..., 5].set(r.x[..., 4])
        return r

    mp.setattr(Solver, "solve_many", crossed)


def test_sound_rehearsal_is_within_the_limit():
    result, ok = _rehearse()
    assert ok, result["compared"]
    assert result["failed"] == 0
    assert result["distinct_answers"] == 8
    assert result["window_compiles"] == 0
    m = result["metrics"]
    lock = m["many.lockstep_iters_per_block"]["value"]
    assert max(result["solves"]["iters"]) <= lock
    assert 0 < m["many.live_lane_frac"]["value"] <= 1
    # per load case: the window over the block's eight columns
    assert m["solver.iters_per_solve"]["value"] <= lock


@pytest.mark.parametrize("fault", [one_column_off, columns_crossed],
                         ids=lambda f: f.__name__)
def test_fault_in_one_column_is_caught(monkeypatch, fault):
    fault(monkeypatch)
    result, ok = _rehearse()
    assert not ok, result["compared"]
    c = result["compared"]["ref_relres"]
    assert c["value"] > 10 * c["limit"]


def _problem_and_mix():
    s = Spec()
    cfg = s.config(s.cell("cube.sweep8")["config"])
    gen = s.generator(cfg["generator"]).make(cfg["model"],
                                             cfg["rehearsal_sizes"]["1"])
    return s, gen.problem, s.traffic("sweep8"), s.traffic_kind("sweep")


def test_unit_fields_are_independent_and_c1_is_the_single_direction():
    s, problem, mix, kind = _problem_and_mix()
    fields = kind.unit_fields(problem)
    assert list(fields) == list(kind.UNIT)
    A = np.stack(list(fields.values()), axis=-1)
    assert np.linalg.matrix_rank(A) == 6
    cases = kind.load_set(problem, mix)
    assert list(cases) == list(kind.UNIT) + ["C1", "C2"]
    single = s.traffic("single")["traction_direction"]
    np.testing.assert_array_equal(cases["C1"], problem.load_vector(single))
    # the resultant of each unit field: N is the face's area, My and Mz
    # and the torsion's force carry no net force
    area = problem.face_weights.sum()
    for name in ("T", "My", "Mz"):
        assert abs(fields[name].sum()) < 1e-9 * area, name
    assert fields["N"][0::3].sum() == pytest.approx(area)


def test_every_seed_draws_the_same_load_directions():
    _, problem, mix, kind = _problem_and_mix()
    a, b = kind.plan(problem, mix, 1), kind.plan(problem, mix, 2 ** 33 + 7)
    assert a.FB.shape == (problem.n_dof, mix["block"])
    lo, hi = mix["magnitude_pa"]
    for p in (a, b):
        assert np.all((p.peaks >= lo) & (p.peaks <= hi))
    # each column is its unit case times a peak drawn from the seed
    ratio = (b.FB / b.peaks) - (a.FB / a.peaks)
    assert np.abs(ratio).max() < 1e-9 * np.abs(a.FB / a.peaks).max()
    again = kind.plan(problem, mix, 2 ** 33 + 7)
    np.testing.assert_array_equal(again.FB, b.FB)


def _reader(name):
    return Spec().reader(name)


def test_block_metrics_read_records_and_skip_a_program_without_the_count():
    recs = [{"iters": i, "block": 4, "lockstep_iters": 10}
            for i in (10, 8, 6, 0)]
    recs += [{"iters": i, "block": 2, "lockstep_iters": 20}
             for i in (20, 10)]
    run = {"solves": recs}
    assert _reader("many.lockstep_iters_per_block")(run) == 15.0
    assert _reader("many.live_lane_frac")(run) == pytest.approx(
        54 / (4 * 10 + 2 * 20))
    old = {"solves": [{"iters": 5, "block": 4, "lockstep_iters": None}]}
    assert _reader("many.lockstep_iters_per_block")(old) is None
    assert _reader("many.live_lane_frac")(old) is None


def test_blocked_roofline_counts_each_block_once():
    peak = {"hbm_bytes_per_s": 1e9, "flops_per_s": 1e18}
    recs = [{"iters": 9, "block": 8, "lockstep_iters": 10}] * 8
    run = {"trace": {"scoped_ops": 1, "phase_s": {"matvec": 1.0}},
           "traced_solves": recs, "peak": peak, "chips": 1,
           "n_dof": 1000, "type_sizes": [(24, 100)]}
    # bytes bind: (8 * 2 * 1000 * 4 + 100 * 4) / 1e9 s a matvec, 10 of them
    want = 100.0 * 10 * (8 * 2 * 1000 * 4 + 100 * 4) / 1e9
    assert _reader("many.matvec_roofline")(run) == pytest.approx(want)
    run["traced_solves"] = [dict(r, lockstep_iters=None) for r in recs]
    assert _reader("many.matvec_roofline")(run) is None


def test_the_cell_is_declared_with_its_metrics():
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))),
            "BENCHMARK.json")) as f:
        b = json.load(f)
    cellw = [w for w in b["workloads"] if w["name"] == "cube.sweep8"]
    assert cellw and cellw[0]["chips"] == 1
    traced = {m["name"] for m, _ in Spec().metrics("cube.sweep8", True)}
    assert {"many.lockstep_iters_per_block", "many.live_lane_frac",
            "many.matvec_roofline", "matvec_roofline",
            "solver.iters_per_solve"} <= traced
    assert "many.live_lane_frac" not in {
        m["name"] for m, _ in Spec().metrics("cube.single", True)}
