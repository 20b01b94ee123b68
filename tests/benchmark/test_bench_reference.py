"""The benchmark's generators and its f64 reference operator, against
the program's own generators and global assembly."""

import numpy as np
import pytest

from generators import cube, octree
from harness import reference, traffic
from harness.spec import Spec

from pcg_mpi_solver_tpu.models import make_cube_model

MODEL = Spec().config("cube_hetero")["model"]


def test_cube_copy_matches_the_program_generator():
    c = cube.make(MODEL, {"cells": [9, 7, 6]})
    m = make_cube_model(9, 7, 6, E=MODEL["E"], nu=MODEL["nu"],
                        heterogeneous=True, seed=MODEL["field_seed"])
    mine = c.model(np.zeros(c.problem.n_dof))
    for k in ("elem_dofs_flat", "elem_nodes_flat", "fixed_dof", "dof_eff",
              "elem_type", "poly_mat"):
        np.testing.assert_array_equal(getattr(mine, k), getattr(m, k), k)
    for k in ("ck", "cm", "ce", "level", "diag_M", "node_coords", "sctrs"):
        np.testing.assert_allclose(getattr(mine, k), getattr(m, k),
                                   rtol=1e-12, err_msg=k)
    for k in ("Ke", "Me", "Se", "diagKe"):
        np.testing.assert_allclose(mine.elem_lib[0][k], m.elem_lib[0][k],
                                   rtol=1e-12, atol=1e-14, err_msg=k)
    assert mine.grid == m.grid
    assert (c.E_elem > MODEL["E"]).any()          # the field has inclusions


def test_cube_face_weights_are_area_consistent():
    c = cube.make(MODEL, {"cells": [4, 3, 5]})
    p = c.problem
    assert p.face_weights.sum() == pytest.approx(3 * 5)   # face area
    F = p.load_vector([2.0, 0.5, -0.25])
    assert F[0::3].sum() == pytest.approx(2.0 * 15)
    assert F[2::3].sum() == pytest.approx(-0.25 * 15)


def _check_operator(problem, model):
    K = model.assemble_csr()
    rng = np.random.default_rng(0)
    x = rng.standard_normal(problem.n_dof)
    y = reference.matvec(problem, x)
    np.testing.assert_allclose(y, K @ x, rtol=1e-10,
                               atol=1e-10 * np.abs(K @ x).max())


def test_reference_operator_matches_the_assembled_cube():
    c = cube.make(MODEL, {"cells": [5, 4, 3]})
    _check_operator(c.problem, c.model(np.zeros(c.problem.n_dof)))


def test_reference_operator_blocks_elements(monkeypatch):
    c = cube.make(MODEL, {"cells": [5, 4, 3]})
    x = np.random.default_rng(1).standard_normal(c.problem.n_dof)
    whole = reference.matvec(c.problem, x)
    monkeypatch.setattr(reference, "BLOCK_ELEMS", 7)
    np.testing.assert_allclose(reference.matvec(c.problem, x), whole,
                               rtol=1e-12, atol=1e-3)


def _octree():
    cfg = Spec().config("octree_graded")
    return cfg, octree.make(cfg["model"], cfg["rehearsal_sizes"]["1"])


def test_reference_operator_matches_the_assembled_pinned_octree():
    """The operator the benchmark derives from the mesh's geometry equals
    the program's globally assembled K: the program's pattern types,
    reflections and signs map every element as the geometry says."""
    cfg, o = _octree()
    # every pattern in its own orientation: more groups than the
    # program's canonical types, none with reflection signs
    assert len(o.problem.groups) > cfg["rehearsal_sizes"]["1"]["pin"][
        "n_types"]
    assert all(g.signs is None for g in o.problem.groups)
    _check_operator(o.problem, o.base)
    # the face weights tile the x = L face, whose area is 2 x 2 cells
    assert o.problem.face_weights.sum() == pytest.approx(4.0)
    assert (o.problem.face_weights > 0).all()


@pytest.mark.parametrize("mask", [0, 1, 4095, 2 ** 18 - 1,
                                  0b101100111000101101])
def test_octree_elements_match_the_program_pattern_library(mask):
    from pcg_mpi_solver_tpu.models.octree import transition_element

    mine = octree.transition_stiffness(mask, 0.2)
    theirs = transition_element(mask, 0.2)["Ke"]
    np.testing.assert_allclose(mine, theirs, rtol=0,
                               atol=1e-13 * np.abs(theirs).max())


def test_octree_pin_refuses_a_moved_mesh():
    cfg = Spec().config("octree_graded")
    size = dict(cfg["rehearsal_sizes"]["1"])
    size["pin"] = dict(size["pin"], ck_sum=size["pin"]["ck_sum"] * 1.001)
    with pytest.raises(RuntimeError, match="ck_sum"):
        octree.make(cfg["model"], size)


def _single():
    spec = Spec()
    mix = spec.traffic("single")
    return mix, spec.traffic_kind(mix["kind"])


def test_traffic_changes_the_answer_not_the_work():
    mix, kind = _single()
    problem = cube.make(MODEL, {"cells": [4, 3, 5]}).problem
    a = kind.plan(problem, mix, 1).traction
    b = kind.plan(problem, mix, 2 ** 31 + 5).traction
    np.testing.assert_array_equal(kind.plan(problem, mix, 1).traction, a)
    assert not np.allclose(a, b)
    np.testing.assert_allclose(a / a[0], b / b[0])
    lo, hi = mix["magnitude_pa"]
    assert lo <= a[0] <= hi and lo <= b[0] <= hi
    np.testing.assert_array_equal(kind.plan(problem, mix, 1).F,
                                  problem.load_vector(a))


def test_traffic_refuses_parameters_it_does_not_know():
    mix, kind = _single()
    problem = cube.make(MODEL, {"cells": [4, 3, 5]}).problem
    with pytest.raises(ValueError, match="burst"):
        kind.plan(problem, dict(mix, burst=3), 1)
    with pytest.raises(ValueError, match="magnitude"):
        traffic.traction(dict(mix, magnitude_pa=[2.0, 1.0]), traffic.rng(1))
