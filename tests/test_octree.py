"""Octree capability: graded 2:1 meshes with real multi-type transition
patterns (the reference's actual problem class — partition_mesh.py:1074
pattern types, :420-493 type groups, :546 per-type Ke, sign vectors
pcg_solver.py:277-280).

Covers: generator invariants, reflection/sign canonicalization equivalence,
device matvec vs dense assembly on mixed-d type blocks, PCG vs scipy,
partition-count parity under 8-way SPMD, and a pinned iteration golden."""

import collections

import numpy as np
import pytest

from pcg_mpi_solver_tpu.config import RunConfig, SolverConfig, TimeHistoryConfig
from pcg_mpi_solver_tpu.models.octree import (
    canonical_mask, make_octree_model, transition_element)
from pcg_mpi_solver_tpu.parallel.mesh import make_mesh
from pcg_mpi_solver_tpu.solver.driver import Solver


@pytest.fixture(scope="module")
def model():
    return make_octree_model(2, 2, 2, max_level=2, n_incl=2, seed=3,
                             load="traction", load_value=1.0)


def test_generator_is_genuinely_multitype(model):
    m = model
    d_set = sorted({3 * lib["n_nodes"] for lib in m.elem_lib.values()})
    assert len(m.elem_lib) >= 4, "expected several transition pattern types"
    assert len(d_set) >= 3, f"expected heterogeneous dofs-per-element, got {d_set}"
    assert d_set[0] == 24 and d_set[-1] > 24
    assert m.elem_sign_flat.any(), "mirrored patterns must carry sign flips"
    assert len(np.unique(m.level)) >= 2, "expected a graded (2:1) mesh"


def test_transition_element_spd_with_rigid_modes():
    """Each pattern Ke is symmetric PSD with EXACTLY 6 zero-energy (rigid
    body) modes — the macro construction must not add spurious modes."""
    m = make_octree_model(2, 2, 2, max_level=2, n_incl=2, seed=3)
    for t, lib in m.elem_lib.items():
        Ke = lib["Ke"]
        assert np.allclose(Ke, Ke.T, atol=1e-12)
        w = np.linalg.eigvalsh(Ke)
        assert np.all(w > -1e-10)
        n_zero = int(np.sum(w < 1e-10 * max(w.max(), 1)))
        assert n_zero == 6, f"type {t}: {n_zero} zero modes"


def test_patch_test_linear_completeness():
    """Homogeneous linear displacement field => zero internal force at every
    interior node: the variable-node basis is conforming across coarse/fine
    interfaces (hanging nodes are real dofs, no constraint residual)."""
    m = make_octree_model(2, 2, 2, max_level=2, n_incl=2, seed=3,
                          incl_stiff=1.0)
    K = m.assemble_csr()
    B = np.array([[0.3, 0.1, 0.0], [0.05, -0.2, 0.1], [0.0, 0.12, 0.25]])
    u = (m.node_coords @ B.T + 0.5).ravel()
    f = K @ u
    c = m.node_coords
    interior = ((c[:, 0] > 0) & (c[:, 0] < c[:, 0].max())
                & (c[:, 1] > 0) & (c[:, 1] < c[:, 1].max())
                & (c[:, 2] > 0) & (c[:, 2] < c[:, 2].max()))
    assert abs(f[np.repeat(interior, 3)]).max() < 1e-12 * abs(f).max() + 1e-13


def test_canonicalized_signs_match_raw_assembly():
    """Reflection canonicalization (fewer types + sign vectors) must produce
    EXACTLY the same global K as one-type-per-raw-mask with no signs — this
    proves the mirrored-pattern sign semantics (pcg_solver.py:277-280)."""
    kw = dict(max_level=2, n_incl=2, seed=3)
    mc = make_octree_model(2, 2, 2, canonicalize=True, **kw)
    mr = make_octree_model(2, 2, 2, canonicalize=False, **kw)
    assert len(mc.elem_lib) < len(mr.elem_lib)
    assert not mr.elem_sign_flat.any()
    Kc, Kr = mc.assemble_csr(), mr.assemble_csr()
    err = abs(Kc - Kr).max()
    assert err < 1e-11 * abs(Kr).max()


def test_face_incidence(model):
    """Interior faces appear exactly twice, boundary faces once (the
    invariant the exporter's Boundary mode relies on,
    export_vtk.py:105-113); subdivided coarse faces are emitted as their 4
    sub-quads so they pair with the fine neighbors' faces."""
    faces = model.faces_flat.reshape(-1, 4)
    cnt = collections.Counter(tuple(sorted(f)) for f in faces)
    hist = collections.Counter(cnt.values())
    assert set(hist) == {1, 2}
    assert hist[1] > 0 and hist[2] > 0


def test_canonical_mask_involution():
    rng = np.random.default_rng(0)
    for m in rng.integers(0, 1 << 18, 50):
        cm, r = canonical_mask(int(m))
        cm2, _ = canonical_mask(cm)
        assert cm2 == cm  # canonical is a fixed point


def _solver(model, n_parts, n_dev=None, tol=1e-8, **kw):
    cfg = RunConfig(
        solver=SolverConfig(tol=tol, max_iter=2000),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 1.0]),
    )
    return Solver(model, cfg, mesh=make_mesh(n_dev or n_parts),
                  n_parts=n_parts, **kw)


def test_matvec_matches_dense_mixed_d_blocks(model):
    """Device matvec on the general path vs scipy assembly — on a model
    whose type blocks have DIFFERENT d (24..51 dofs/elem), proving the
    per-block generality of ops/matvec.py."""
    import jax.numpy as jnp

    K = model.assemble_csr()
    rng = np.random.default_rng(1)
    x = rng.standard_normal(model.n_dof)

    for n_parts in (1, 8):
        s = _solver(model, n_parts)
        data = s.data
        xs = np.zeros((s.pm.n_parts, s.pm.n_loc))
        gid = s.pm.dof_gid
        xs = np.where(gid >= 0, x[np.maximum(gid, 0)], 0.0)
        import jax

        yfn = jax.jit(jax.shard_map(
            lambda d, v: s.ops.matvec(d, v), mesh=s.mesh,
            in_specs=(s._specs, s._part_spec), out_specs=s._part_spec,
            check_vma=False))
        y = np.asarray(yfn(data, jnp.asarray(xs)))
        y_glob = np.zeros(model.n_dof)
        mask = s.owner_mask()
        y_glob[gid[mask]] = y[mask]
        np.testing.assert_allclose(y_glob, K @ x, rtol=1e-9,
                                   atol=1e-10 * abs(K @ x).max())


def test_pcg_matches_scipy(model):
    from scipy.sparse.linalg import spsolve

    s = _solver(model, 1)
    res = s.step(1.0)
    assert res.flag == 0 and res.relres <= 1e-8
    K = model.assemble_csr()
    eff = model.dof_eff
    rhs = (model.F - K @ model.Ud)[eff]
    u_ref = np.array(model.Ud)
    u_ref[eff] += spsolve(K[eff][:, eff].tocsc(), rhs)
    u = s.displacement_global()
    np.testing.assert_allclose(u, u_ref, rtol=1e-5,
                               atol=1e-8 * np.abs(u_ref).max())


def test_partition_parity_8way_spmd(model):
    """Iteration count must not change with the partition count (domain
    decomposition preserves the math) — on the octree model under real
    8-way SPMD."""
    results = {}
    for n_parts in (1, 4, 8):
        s = _solver(model, n_parts)
        results[n_parts] = s.step(1.0)
    for n_parts in (4, 8):
        assert results[n_parts].flag == 0
        assert abs(results[n_parts].iters - results[1].iters) <= 1


# Pinned at round 2 (tol=1e-8, Jacobi, f64 direct, 4 parts); the solution
# checksum guards against silent numerics drift with unchanged iters.
GOLDEN_OCTREE_ITERS = 85
GOLDEN_OCTREE_CHECKSUM = 243.89247971925158


def test_golden_iteration_count(model):
    """Pinned golden for the flagship octree model: numerics drift between
    rounds must fail loudly.  If a deliberate
    algorithm change moves this, re-pin with justification."""
    s = _solver(model, 4)
    res = s.step(1.0)
    assert res.flag == 0
    assert abs(res.iters - GOLDEN_OCTREE_ITERS) <= 1, res.iters
    checksum = float(np.abs(s.displacement_global()).sum())
    assert np.isclose(checksum, GOLDEN_OCTREE_CHECKSUM, rtol=1e-6), checksum
