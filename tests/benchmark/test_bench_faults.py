"""A whole run with the timed path broken underneath: the comparison
with the reference has to come out false.  The chip check is skipped
(``rehearsal``); everything else is the benchmark's own run."""

import time

import jax.numpy as jnp
import pytest

from harness import cell

from pcg_mpi_solver_tpu.parallel.structured import StructuredOps
from pcg_mpi_solver_tpu.solver.driver import Solver, StepResult


def state_unchanged(mp):
    """The step claims convergence and leaves the solution as it was."""
    mp.setattr(Solver, "step",
               lambda self, delta: StepResult(0, 0.0, 0, 0.0))


def answer_altered(mp):
    """The step's solution is scaled by 1 + 1e-5 where it is produced."""
    step = Solver.step

    def altered(self, delta):
        r = step(self, delta)
        self.un = self.un * (1.0 + 1e-5)
        return r

    mp.setattr(Solver, "step", altered)


def half_cells_left_out(mp):
    """The stencil applies the stiffness of half the cells only."""
    gse = StructuredOps._gse

    def half(self, blk, xg, ck):
        keep = jnp.arange(ck.shape[1]) < ck.shape[1] // 2
        return gse(self, blk, xg, ck * keep[None, :, None, None])

    mp.setattr(StructuredOps, "_gse", half)


def exchange_left_out(mp):
    """The slab halo exchange between chips is skipped."""
    mp.setattr(StructuredOps, "_halo", lambda self, yg: yg)


def sound(mp):
    pass


def _rehearse(workload):
    result, ok = cell.run(workload, 2 ** 31 + 11, 0.0, False,
                          time.perf_counter(), rehearsal=True)
    assert result["correct"] is False          # a rehearsal never is
    assert result["attempted"] == 1
    return result, ok


@pytest.mark.parametrize("workload,fault", [
    ("cube.single", sound),
    ("cube.single", state_unchanged),
    ("cube.single", answer_altered),
    ("cube.single", half_cells_left_out),
    ("cube.weak4", sound),
    ("cube.weak4", exchange_left_out),
    ("octree.single", state_unchanged),
    ("octree.single", answer_altered),
], ids=lambda v: getattr(v, "__name__", v))
def test_fault_is_caught_by_the_comparison(monkeypatch, workload, fault):
    fault(monkeypatch)
    result, ok = _rehearse(workload)
    value = result["compared"]["ref_relres"]["value"]
    if fault is sound:
        assert ok, result["compared"]
        assert result["failed"] == 0
    else:
        assert not ok, result["compared"]
        assert value > 10 * result["compared"]["ref_relres"]["limit"]
