"""The four-chip octree cell, rehearsed on four virtual CPU devices at
n0 = 2: the whole run passes the comparison with the reference, the
same run with the cross-part interface assembly skipped fails it, and
the configuration's pin refuses a moved mesh."""

import time

import pytest

from generators import octree
from harness import cell
from harness.spec import Spec

from pcg_mpi_solver_tpu.ops.matvec import Ops

WORKLOAD = "octree.demo4"


def _rehearse():
    result, ok = cell.run(WORKLOAD, 2 ** 31 + 29, 0.0, False,
                          time.perf_counter(), rehearsal=True)
    assert result["correct"] is False          # a rehearsal never is
    assert result["attempted"] == 1
    assert result["device"]["count"] == 4
    return result, ok


def test_rehearsal_passes_the_comparison():
    result, ok = _rehearse()
    assert ok, result["compared"]
    assert result["failed"] == 0


def test_skipped_interface_assembly_fails_the_comparison(monkeypatch):
    """Each part's matvec without the sums of the dofs it shares with
    the other parts: the answer is off by far more than the limit."""
    monkeypatch.setattr(Ops, "iface_assemble", lambda self, data, y: y)
    result, ok = _rehearse()
    compared = result["compared"]["ref_relres"]
    assert not ok, compared
    assert compared["value"] > 10 * compared["limit"]


def test_pin_refuses_a_moved_mesh():
    cfg = Spec().config(Spec().cell(WORKLOAD)["config"])
    size = dict(cfg["rehearsal_sizes"]["4"])
    size["pin"] = dict(size["pin"], n_elem=size["pin"]["n_elem"] + 1)
    with pytest.raises(RuntimeError, match="n_elem"):
        octree.make(cfg["model"], size)
