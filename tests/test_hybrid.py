"""Hybrid level-grid backend vs the general node-ELL path.

Both backends share partition_model's local numbering (block_filter only
removes brick elements from the type blocks, not from the local sets), so
operator outputs are directly comparable part-by-part."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pcg_mpi_solver_tpu import RunConfig, SolverConfig, TimeHistoryConfig
from pcg_mpi_solver_tpu.models.octree import make_octree_model
from pcg_mpi_solver_tpu.ops.matvec import Ops, device_data
from pcg_mpi_solver_tpu.parallel import make_mesh
from pcg_mpi_solver_tpu.parallel.hybrid import (
    HybridOps, device_data_hybrid, partition_hybrid)
from pcg_mpi_solver_tpu.parallel.partition import make_elem_part, partition_model
from pcg_mpi_solver_tpu.solver import Solver


@pytest.fixture(scope="module")
def model():
    return make_octree_model(2, 2, 2, max_level=2, n_incl=2, seed=3,
                             load="traction", load_value=1.0)


@pytest.fixture(scope="module", params=[1, 4])
def pair(model, request):
    """(general ops+data, hybrid ops+data) on the SAME partition."""
    P = request.param
    ep = make_elem_part(model, P, method="rcb")
    pm_g = partition_model(model, P, elem_part=ep)
    ops_g = Ops.from_model(pm_g)
    data_g = device_data(pm_g)
    hp = partition_hybrid(model, P, elem_part=ep)
    ops_h = HybridOps.from_hybrid(hp)
    data_h = device_data_hybrid(hp)
    return (ops_g, data_g), (ops_h, data_h), pm_g, hp


def test_brick_metadata(model):
    meta = model.octree
    assert meta["brick_type"] is not None
    from pcg_mpi_solver_tpu.models.element import HEX_CORNERS

    np.testing.assert_array_equal(meta["brick_corners"],
                                  HEX_CORNERS.astype(np.int64))
    # a graded octree is mostly bricks
    n_brick = int((model.elem_type == meta["brick_type"]).sum())
    assert n_brick > 0.5 * model.n_elem


def test_hybrid_blocks_shrunk(pair):
    _, (ops_h, data_h), pm_g, hp = pair
    n_gen = sum(int(tb.n_elem.sum()) for tb in pm_g.type_blocks)
    n_hyb = sum(int(tb.n_elem.sum()) for tb in hp.pm.type_blocks)
    n_grid = sum(int(lv.n_cells.sum()) for lv in hp.levels)
    assert n_hyb + n_grid == n_gen
    assert n_grid > 0


def test_matvec_matches_general(pair):
    (ops_g, data_g), (ops_h, data_h), pm_g, hp = pair
    P = pm_g.n_parts
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(P, pm_g.n_loc)))
    yg = np.asarray(ops_g.matvec(data_g, x))
    yh = np.asarray(ops_h.matvec(data_h, x))
    scale = np.abs(yg).max()
    np.testing.assert_allclose(yh, yg, rtol=0, atol=1e-12 * scale)


def test_diag_matches_general(pair):
    (ops_g, data_g), (ops_h, data_h), pm_g, hp = pair
    dg = np.asarray(ops_g.diag(data_g))
    dh = np.asarray(ops_h.diag(data_h))
    np.testing.assert_allclose(dh, dg, rtol=0, atol=1e-12 * np.abs(dg).max())


def test_nodal_average_matches_general(pair):
    (ops_g, data_g), (ops_h, data_h), pm_g, hp = pair
    P = pm_g.n_parts
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(P, pm_g.n_loc)))
    eg = ops_g.elem_strain(data_g, x)
    eh = ops_h.elem_strain(data_h, x)
    ag = np.asarray(ops_g.nodal_average(data_g, eg))
    ah = np.asarray(ops_h.nodal_average(data_h, eh))
    scale = max(np.abs(ag).max(), 1e-30)
    np.testing.assert_allclose(ah, ag, rtol=0, atol=1e-11 * scale)


def test_solve_matches_general(model):
    """Full quasi-static solve: identical iteration count and solution."""
    results = {}
    for backend in ("general", "hybrid"):
        cfg = RunConfig(
            solver=SolverConfig(tol=1e-9, max_iter=3000, dtype="float64"),
            time_history=TimeHistoryConfig(time_step_delta=[0.0, 1.0]))
        s = Solver(model, cfg, mesh=make_mesh(4), n_parts=4, backend=backend)
        assert s.backend == backend
        res = s.step(1.0)
        assert res.flag == 0
        results[backend] = (res.iters, s.displacement_global())
    ig, ug = results["general"]
    ih, uh = results["hybrid"]
    assert abs(ig - ih) <= 1, (ig, ih)
    np.testing.assert_allclose(uh, ug, rtol=0,
                               atol=1e-9 * np.abs(ug).max())


def _strip_fastpath_meta(model):
    import copy

    m = copy.deepcopy(model)
    m.octree = None
    m.grid = None
    return m


def test_reconstruct_octree_meta_roundtrip(model, monkeypatch):
    monkeypatch.setenv("PCG_TPU_ENABLE_HYBRID", "1")
    """A bundle WITHOUT the Octree.npz sidecar (a genuine reference
    bundle) must reconstruct lattice metadata from pure geometry and
    route to the hybrid backend with iteration parity vs the general
    path."""
    from pcg_mpi_solver_tpu.models.octree import reconstruct_lattice_meta

    m = _strip_fastpath_meta(model)
    assert reconstruct_lattice_meta(m)
    ot, ref = m.octree, model.octree
    assert ot["brick_type"] == ref["brick_type"]
    assert ot["dims"] == ref["dims"]
    assert ot["strides"] == ref["strides"]
    np.testing.assert_array_equal(ot["leaves"], ref["leaves"])
    np.testing.assert_array_equal(ot["node_keys"], ref["node_keys"])
    np.testing.assert_array_equal(ot["brick_corners"], ref["brick_corners"])

    # end to end: auto backend prefers hybrid on the reconstructed model
    s = Solver(m, RunConfig(), mesh=make_mesh(4), n_parts=4)
    assert s.backend == "hybrid"
    res = s.step(1.0)
    sg = Solver(model, RunConfig(), mesh=make_mesh(4), n_parts=4,
                backend="general")
    rg = sg.step(1.0)
    assert res.flag == 0 and abs(int(res.iters) - int(rg.iters)) <= 1
    np.testing.assert_allclose(
        s.displacement_global(), sg.displacement_global(), rtol=0,
        atol=1e-9 * np.abs(sg.displacement_global()).max())


def test_reconstruct_handles_arbitrary_node_numbering(model):
    """Reconstruction + partition must not assume sorted-key node
    numbering: permute the node ids of the octree model and check the
    hybrid solve still matches the general backend exactly."""
    import copy

    m = copy.deepcopy(model)
    rng = np.random.default_rng(7)
    perm = rng.permutation(m.n_node)         # new id of old node i
    inv = np.argsort(perm)
    m.node_coords = m.node_coords[inv]
    old_dofs = np.asarray(m.elem_dofs_flat)
    m.elem_dofs_flat = 3 * perm[old_dofs // 3] + old_dofs % 3
    m.elem_nodes_flat = perm[m.elem_nodes_flat]
    dof_perm = (3 * perm[:, None] + np.arange(3)[None]).ravel()
    dof_inv = np.argsort(dof_perm)
    for name in ("F", "Ud", "Vd", "diag_M"):
        setattr(m, name, getattr(m, name)[dof_inv])
    m.fixed_dof = np.sort(dof_perm[m.fixed_dof])
    m.dof_eff = np.sort(dof_perm[m.dof_eff])
    m.faces_flat = perm[m.faces_flat]
    m.octree = None
    m.grid = None
    from pcg_mpi_solver_tpu.models.octree import reconstruct_lattice_meta

    assert reconstruct_lattice_meta(m)
    # node_keys now follow the permuted numbering (NOT sorted)
    assert not np.all(np.diff(m.octree["node_keys"]) > 0)
    cfg = RunConfig(
        solver=SolverConfig(tol=1e-9, max_iter=3000, dtype="float64"),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 1.0]))
    sh = Solver(m, cfg, mesh=make_mesh(4), n_parts=4, backend="hybrid")
    rh = sh.step(1.0)
    sg = Solver(m, cfg, mesh=make_mesh(4), n_parts=4, backend="general")
    rg = sg.step(1.0)
    assert rh.flag == 0 and abs(int(rh.iters) - int(rg.iters)) <= 1
    np.testing.assert_allclose(
        sh.displacement_global(), sg.displacement_global(), rtol=0,
        atol=1e-9 * np.abs(sg.displacement_global()).max())


def test_reconstruct_declines_foreign_corner_order(model):
    """A bundle whose hex connectivity uses a valid but DIFFERENT corner
    order must decline (stay on the general path) — engaging would crash
    partition_hybrid's _CORNERS assertion (r04 review finding)."""
    import copy

    from pcg_mpi_solver_tpu.models.octree import reconstruct_lattice_meta

    m = copy.deepcopy(model)
    bt = m.octree["brick_type"]
    m.octree = None
    m.grid = None
    sel = np.where(m.elem_type == bt)[0]
    perm8 = np.array([0, 2, 1, 3, 4, 6, 5, 7])      # consistent, non-canon
    idx = m.elem_nodes_offset[sel, None] + perm8[None]
    m.elem_nodes_flat[m.elem_nodes_offset[sel, None] + np.arange(8)[None]] \
        = m.elem_nodes_flat[idx].copy()
    didx = (m.elem_dofs_offset[sel, None, None]
            + 3 * perm8[None, :, None] + np.arange(3)[None, None])
    base = (m.elem_dofs_offset[sel, None, None]
            + 3 * np.arange(8)[None, :, None] + np.arange(3)[None, None])
    m.elem_dofs_flat[base.reshape(len(sel), -1)] = \
        m.elem_dofs_flat[didx.reshape(len(sel), -1)].copy()
    assert not reconstruct_lattice_meta(m)
    assert m.octree is None


def test_reconstruct_declines_non_lattice(model):
    """Perturbed geometry must leave the model on the general path."""
    from pcg_mpi_solver_tpu.models.octree import reconstruct_lattice_meta

    m = _strip_fastpath_meta(model)
    m.node_coords = m.node_coords + 0.01 * np.sin(
        np.arange(m.node_coords.size)).reshape(m.node_coords.shape)
    assert not reconstruct_lattice_meta(m)
    assert m.octree is None and m.grid is None


def test_merged_levels_match_unmerged(model):
    """PCG_TPU_HYBRID_MERGE (default OFF: measured compile-negative)
    folds all level grids into ONE
    block batch — the matvec, diagonal, node blocks and strain must be
    identical to the per-level layout, and the merged partition must
    carry exactly one level."""
    import os

    from pcg_mpi_solver_tpu.parallel.partition import make_elem_part

    ep = make_elem_part(model, 2, method="rcb")
    prev = os.environ.get("PCG_TPU_HYBRID_MERGE")
    try:
        os.environ["PCG_TPU_HYBRID_MERGE"] = "0"
        hp_u = partition_hybrid(model, 2, elem_part=ep)
        os.environ["PCG_TPU_HYBRID_MERGE"] = "1"
        hp_m = partition_hybrid(model, 2, elem_part=ep)
    finally:
        if prev is None:
            os.environ.pop("PCG_TPU_HYBRID_MERGE", None)
        else:
            os.environ["PCG_TPU_HYBRID_MERGE"] = prev
    assert len(hp_u.levels) > 1
    assert len(hp_m.levels) == 1 and hp_m.levels[0].size == 0
    assert (sum(int(lv.n_cells.sum()) for lv in hp_u.levels)
            == int(hp_m.levels[0].n_cells.sum()))
    ops_u = HybridOps.from_hybrid(hp_u)
    ops_m = HybridOps.from_hybrid(hp_m)
    data_u = device_data_hybrid(hp_u)
    data_m = device_data_hybrid(hp_m)
    rng = np.random.default_rng(21)
    x = jnp.asarray(rng.standard_normal((2, hp_u.pm.n_loc)))
    y_u = np.asarray(ops_u.matvec_local(data_u, x))
    y_m = np.asarray(ops_m.matvec_local(data_m, x))
    assert np.abs(y_m - y_u).max() / np.abs(y_u).max() < 1e-12
    d_u = np.asarray(ops_u.diag_local(data_u))
    d_m = np.asarray(ops_m.diag_local(data_m))
    assert np.abs(d_m - d_u).max() / np.abs(d_u).max() < 1e-12
    b_u = np.asarray(ops_u._node_block_local(data_u))
    b_m = np.asarray(ops_m._node_block_local(data_m))
    assert np.abs(b_m - b_u).max() / (np.abs(b_u).max() + 1e-30) < 1e-12


def _np_level_grid(ck, corner_vals):
    """numpy pad-translate of per-cell corner values onto a level's node
    lattice: ck (nb, bx, by, bz); corner_vals(a) -> (nb, bx, by, bz, w)
    for corner a.  Returns (nb*(bx+1)*(by+1)*(bz+1), w) rows in nidx
    order."""
    from pcg_mpi_solver_tpu.parallel.hybrid import _CORNERS

    nb, bx, by, bz = ck.shape
    g = None
    for a, (dx, dy, dz) in enumerate(_CORNERS):
        c = corner_vals(a)
        if g is None:
            g = np.zeros((nb, bx + 1, by + 1, bz + 1, c.shape[-1]))
        g[:, dx:dx + bx, dy:dy + by, dz:dz + bz] += c
    return g.reshape(-1, g.shape[-1])


def _np_level_matvec(Ke, ck, xg):
    """numpy element loop of the brick stencil on one block batch:
    xg (nb, bx+1, by+1, bz+1, 3) node values -> node lattice rows."""
    from pcg_mpi_solver_tpu.parallel.hybrid import _CORNERS

    nb, bx, by, bz = ck.shape
    u = np.concatenate([xg[:, dx:dx + bx, dy:dy + by, dz:dz + bz]
                        for dx, dy, dz in _CORNERS], axis=-1)
    v = (ck[..., None] * u) @ np.asarray(Ke).T         # (nb, ..., 24)
    return _np_level_grid(ck, lambda a: v[..., 3 * a:3 * a + 3])


def _np_add_rows(n_node, pairs):
    """np.add.at of (node ids, rows) pairs into (n_node, w); ids at or
    past n_node (padding) drop."""
    w = pairs[0][1].shape[-1]
    pad = max(n_node, *(int(ids.max()) + 1 for ids, _ in pairs))
    y = np.zeros((pad, w))
    for ids, rows in pairs:
        np.add.at(y, ids, rows)
    return y[:n_node]


def _pin_kd(model, pair, kd, monkeypatch):
    """The pair's hybrid (ops, data, partition), rebuilt on the same
    element partition with PCG_TPU_HYBRID_KD pinned to ``kd`` (None:
    the fixture's own, the width picked from the histogram)."""
    _, (ops_h, data_h), pm_g, hp = pair
    if kd is None:
        return ops_h, data_h, hp
    monkeypatch.setenv("PCG_TPU_HYBRID_KD", str(kd))
    P = pm_g.n_parts
    hp = partition_hybrid(model, P,
                          elem_part=make_elem_part(model, P, method="rcb"))
    assert hp.combine.kd == kd
    return HybridOps.from_hybrid(hp), device_data_hybrid(hp), hp


@pytest.mark.parametrize("kd", [None, 1, 3], ids=["auto", "kd1", "kd3"])
def test_combine_gather_matches_scatter(model, pair, kd, monkeypatch):
    """The gather combine against an independent numpy np.add.at sum of
    the same level and transition rows into their nodes, for matvec,
    diag, node blocks and nodal averaging, at the picked dense width
    and at pinned ones."""
    from pcg_mpi_solver_tpu.parallel.hybrid import _CORNERS

    pm_g = pair[2]
    ops_h, data_h, hp = _pin_kd(model, pair, kd, monkeypatch)
    assert hp.combine.bands
    assert "combine" in data_h and ops_h._trans_in_combine(data_h)
    P, nn = hp.pm.n_parts, hp.pm.n_node_loc
    Ke = np.asarray(data_h["brick_Ke"])
    dk = np.asarray(data_h["brick_diag"])
    rng = np.random.default_rng(11)
    x = rng.normal(size=(P, pm_g.n_loc))
    xj = jnp.asarray(x)
    rows_trans = [np.asarray(r) for r in ops_h._element_rows(data_h, xj)]
    springs = np.asarray(ops_h._apply_springs(data_h, xj, jnp.zeros_like(xj)))
    diag_t = np.asarray(Ops.diag_local(ops_h, data_h))
    blocks_t = np.asarray(Ops._node_block_local(ops_h, data_h))
    for p in range(P):
        x3 = np.concatenate([x[p].reshape(nn, 3), np.zeros((1, 3))])
        mv, dg, nbk = [], [], []
        for lv in hp.levels:
            ck = lv.ck[p]
            nb, bx, by, bz = ck.shape
            nid = lv.nidx[p].reshape(-1)
            xg = x3[np.minimum(nid, nn)].reshape(nb, bx + 1, by + 1, bz + 1,
                                                  3)
            mv.append((nid, _np_level_matvec(Ke, ck, xg)))
            dg.append((nid, _np_level_grid(
                ck, lambda a: ck[..., None] * dk[3 * a:3 * a + 3])))
            nbk.append((nid, _np_level_grid(
                ck, lambda a: ck[..., None]
                * Ke[3 * a:3 * a + 3, 3 * a:3 * a + 3].reshape(-1))))
        trans = [(np.asarray(b["node"][p]).reshape(-1), r[p])
                 for b, r in zip(data_h["blocks"], rows_trans)]
        y_ref = _np_add_rows(nn, mv + trans).reshape(-1) + springs[p]
        y = np.asarray(ops_h.matvec_local(data_h, xj))[p]
        np.testing.assert_allclose(y, y_ref, rtol=0,
                                   atol=1e-12 * np.abs(y_ref).max())
        d_ref = _np_add_rows(nn, dg).reshape(-1) + diag_t[p]
        d = np.asarray(ops_h.diag_local(data_h))[p]
        np.testing.assert_allclose(d, d_ref, rtol=0,
                                   atol=1e-12 * np.abs(d_ref).max())
        b_ref = _np_add_rows(nn, nbk).reshape(nn, 3, 3) \
            + blocks_t[p].reshape(nn, 3, 3)
        b = np.asarray(ops_h._node_block_local(data_h))[p].reshape(nn, 3, 3)
        np.testing.assert_allclose(b, b_ref, rtol=0,
                                   atol=1e-12 * np.abs(b_ref).max())

    # nodal averaging: per-cell values summed with counts onto nodes
    eps = ops_h.elem_strain(data_h, xj)
    k = eps[0].shape[1]
    nbl = len(data_h["blocks"])
    joined = np.zeros((P, nn, k + 1))
    for p in range(P):
        pairs = []
        for b, v in zip(data_h["blocks"], eps[:nbl]):
            node = np.asarray(b["node"][p])                 # (nnb, N)
            vals = np.asarray(v[p]).T                       # (N, k)
            for a in range(node.shape[0]):
                pairs.append((node[a], np.concatenate(
                    [vals, np.ones((len(vals), 1))], axis=1)))
        for lv, v in zip(hp.levels, eps[nbl:]):
            ck = lv.ck[p]
            nb, bx, by, bz = ck.shape
            valid = (ck != 0)[..., None].astype(float)
            vals = np.asarray(v[p]).reshape(k, nb, bx, by, bz) \
                .transpose(1, 2, 3, 4, 0)
            cell = np.concatenate([vals * valid, valid], axis=-1)
            pairs.append((lv.nidx[p].reshape(-1),
                          _np_level_grid(ck, lambda a: cell)))
        joined[p] = _np_add_rows(nn, pairs)
    both = np.asarray(ops_h.niface_assemble(
        data_h, jnp.asarray(joined.transpose(0, 2, 1))))
    avg_ref = both[:, :k] / (both[:, k:] + 1e-15)
    avg = np.asarray(ops_h.nodal_average(data_h, eps))
    np.testing.assert_allclose(avg, avg_ref, rtol=0,
                               atol=1e-11 * max(np.abs(avg_ref).max(), 1e-30))


def _slot_targets(hp, p):
    """Part p's lattice slots and transition rows as the combine sees
    them, from the level grids and type blocks alone: (lattice node ids,
    per-slot count of ck != 0 cells touched, every slot's target node,
    live mask: lattice slots of real nodes that touch such a cell, and
    real transition rows)."""
    from pcg_mpi_solver_tpu.parallel.hybrid import _CORNERS

    nn = hp.pm.n_node_loc
    touch = []
    for lv in hp.levels:
        ck = lv.ck[p] != 0                             # (nb, bx, by, bz)
        nb, bx, by, bz = ck.shape
        cnt = np.zeros((nb, bx + 1, by + 1, bz + 1), np.int64)
        for dx, dy, dz in _CORNERS:
            cnt[:, dx:dx + bx, dy:dy + by, dz:dz + bz] += ck
        touch.append(cnt.reshape(-1))
    touch = np.concatenate(touch)
    lvl = np.concatenate([lv.nidx[p].reshape(-1) for lv in hp.levels])
    trans = np.concatenate(
        [tb.node[p].reshape(-1) for tb in hp.pm.type_blocks]
        or [np.zeros(0, np.int64)])
    tgt = np.concatenate([lvl, trans]).astype(np.int64)
    live = np.concatenate([(lvl < nn) & (touch > 0), trans < nn])
    return lvl, touch, tgt, live


def test_combine_maps_cover_every_slot_once(pair):
    """Every lattice slot that touches a ck != 0 cell of its block and
    every real transition row appears in exactly one dense or heavy-band
    cell, under the node it belongs to; every dropped slot of a real
    node touches no such cell; pad cells all point at the zero row.  The
    heavy list runs by descending slot count (node ids rising among
    equal counts), band k is its prefix of nodes with more than KD + k
    slots, band lengths never increase, and every hpos points into the
    list or at the zero row after it."""
    _, (ops_h, data_h), pm_g, hp = pair
    cm = hp.combine
    nn = hp.pm.n_node_loc
    assert cm.n_slots == sum(lv.nidx[0].size for lv in hp.levels) + sum(
        tb.node.shape[1] * tb.node.shape[2] for tb in hp.pm.type_blocks)
    bands = cm.bands
    H = bands[0] if bands else 0
    assert list(bands) == sorted(bands, reverse=True)
    assert cm.hgidx.shape == (hp.pm.n_parts, sum(bands))
    assert ops_h.combine_bands == bands
    band_fill = np.zeros((hp.pm.n_parts, len(bands)), np.int64)
    n_dropped = 0
    for p in range(hp.pm.n_parts):
        lvl, touch, tgt, live = _slot_targets(hp, p)
        lens = np.bincount(tgt[live], minlength=nn)
        # a ck != 0 brick's corners are all real local nodes
        assert (lvl[touch > 0] < nn).all()
        # the heavy list, from hpos: exactly the nodes past KD
        assert (cm.hpos[p] <= H).all()
        is_heavy = cm.hpos[p] < H
        np.testing.assert_array_equal(is_heavy, lens > cm.kd)
        assert (cm.hpos[p][~is_heavy] == H).all()
        heavy_node = np.full(H, nn)
        heavy_node[cm.hpos[p][is_heavy]] = np.flatnonzero(is_heavy)
        n_heavy = int(is_heavy.sum())
        assert (heavy_node[:n_heavy] < nn).all()
        order = heavy_node[:n_heavy]
        np.testing.assert_array_equal(
            np.lexsort((order, -lens[order])), np.arange(n_heavy))
        band_fill[p] = [(lens > cm.kd + k).sum() for k in range(len(bands))]
        # the node each map cell adds into: gidx by its column, band k
        # by the first bands[k] rows of the heavy list
        slot_node = np.concatenate(
            [np.broadcast_to(np.arange(nn), cm.gidx[p].shape).reshape(-1)]
            + [heavy_node[:h] for h in bands])
        used = np.concatenate([cm.gidx[p].reshape(-1), cm.hgidx[p]])
        real = used < cm.n_slots
        # exactly the live slots and real transition rows, each once
        np.testing.assert_array_equal(np.sort(used[real]),
                                      np.flatnonzero(live))
        # ... each under its own node
        np.testing.assert_array_equal(slot_node[real], tgt[used[real]])
        # every lattice slot of a real node left out touches no ck != 0
        # cell: its rows only ever carry exact zeros
        in_map = np.zeros(len(tgt), bool)
        in_map[used[real]] = True
        dropped = (lvl < nn) & ~in_map[:len(lvl)]
        assert (touch[dropped] == 0).all()
        n_dropped += int(dropped.sum())
    # each band is as long as the part with the most such nodes needs
    np.testing.assert_array_equal(band_fill.max(axis=0), bands)
    assert n_dropped == cm.n_dropped
    assert cm.n_dropped > 0


def test_banded_rows_never_exceed_rectangular(pair, monkeypatch):
    """At every dense width, the banded combine gathers no more rows
    than a rectangular heavy residual padded to the heaviest node
    (``KD*N + KE*H + N``), and the width it picks gathers strictly fewer
    rows than the best rectangular layout on this partition."""
    from pcg_mpi_solver_tpu.parallel.hybrid import build_combine_maps

    hp = pair[3]
    nn, P = hp.pm.n_node_loc, hp.pm.n_parts
    lens = [np.bincount(tgt[live], minlength=nn)
            for _, _, tgt, live in (_slot_targets(hp, p) for p in range(P))]
    lmax = max(int(ln.max()) for ln in lens)
    trans = [tb.node for tb in hp.pm.type_blocks]
    rect = {}
    for kd in range(1, lmax + 1):
        H = max(int((ln > kd).sum()) for ln in lens)
        rect[kd] = kd * nn + ((lmax - kd) * H + nn if H else 0)
        monkeypatch.setenv("PCG_TPU_HYBRID_KD", str(kd))
        cm = build_combine_maps(hp.levels, nn, P, trans)
        assert cm.kd == kd
        assert cm.rows_per_matvec <= rect[kd]
    assert hp.combine.rows_per_matvec < min(rect.values())


@pytest.mark.parametrize("nrhs", [0, 2], ids=["scalar", "nrhs2"])
def test_gather_combine_matvec_has_no_scatter(pair, nrhs):
    """The default combine accumulates element->node with gathers only
    (no scatter-add anywhere in matvec_local, blocked or not), and it
    gathers fewer rows than the node-ELL sum over every node plus the
    all-slot level combine it replaces (KD = 2, heavy rows scattered)."""
    _, (ops_h, data_h), pm_g, hp = pair
    assert ops_h._trans_in_combine(data_h)
    shape = (hp.pm.n_parts, hp.pm.n_loc) + ((nrhs,) if nrhs else ())
    x = jnp.zeros(shape)
    jaxpr = str(jax.make_jaxpr(ops_h.matvec_local)(data_h, x))
    assert "gather" in jaxpr and "scatter" not in jaxpr
    cm, nn = hp.combine, hp.pm.n_node_loc
    lens = [np.bincount(np.concatenate(
        [lv.nidx[p].reshape(-1) for lv in hp.levels]), minlength=nn + 1)[:nn]
        for p in range(hp.pm.n_parts)]
    H = max(int((ln > 2).sum()) for ln in lens)
    KE = max(int(ln.max()) for ln in lens) - 2
    old_rows = hp.pm.ell.shape[-1] * nn + 2 * nn + KE * H + H
    assert 0 < cm.rows_per_matvec < old_rows
    gauges = cm.gauges()
    assert gauges["hybrid.combine_rows_per_matvec"] == cm.rows_per_matvec
    assert gauges["hybrid.combine_kd"] == cm.gidx.shape[1]
    assert gauges["hybrid.combine_heavy_nodes"] == cm.bands[0]
    assert gauges["hybrid.combine_heavy_rows"] == sum(cm.bands) \
        == cm.hgidx.shape[1]
    assert cm.rows_per_matvec == (cm.kd * nn
                                  + gauges["hybrid.combine_heavy_rows"] + nn)


def test_auto_backend_prefers_hybrid(model, monkeypatch):
    # ISSUE 14: hybrid auto-selection is deprecation-gated behind the
    # explicit opt-in (RUNBOOK "Scaling the setup path")...
    monkeypatch.setenv("PCG_TPU_ENABLE_HYBRID", "1")
    s = Solver(model, RunConfig(), mesh=make_mesh(4), n_parts=4)
    assert s.backend == "hybrid"


def test_auto_backend_hybrid_gate_defaults_general(model, monkeypatch):
    """...and WITHOUT the opt-in an octree model auto-routes to the
    general backend (explicit backend='hybrid' still honored)."""
    monkeypatch.delenv("PCG_TPU_ENABLE_HYBRID", raising=False)
    s = Solver(model, RunConfig(), mesh=make_mesh(4), n_parts=4)
    assert s.backend == "general"
    s2 = Solver(model, RunConfig(), mesh=make_mesh(4), n_parts=4,
                backend="hybrid")
    assert s2.backend == "hybrid"


def test_level_stencil_matches_numpy(pair):
    """Each level grid's stencil (the part*block batch the matvec runs)
    against a numpy element loop over that level's cells."""
    _, (ops_h, data_h), _, hp = pair
    rng = np.random.default_rng(2)
    Ke = data_h["brick_Ke"]
    assert ops_h.level_dims
    for lv, (nb, bx, by, bz) in zip(data_h["levels"], ops_h.level_dims):
        B = lv["ck"].shape[0] * nb
        ck = np.asarray(lv["ck"]).reshape(B, bx, by, bz)
        xg = rng.normal(size=(B, 3, bx + 1, by + 1, bz + 1))
        y = np.asarray(ops_h._stencil(Ke, jnp.asarray(ck), jnp.asarray(xg)))
        y_ref = _np_level_matvec(Ke, ck, xg.transpose(0, 2, 3, 4, 1))
        y = y.transpose(0, 2, 3, 4, 1).reshape(-1, 3)
        np.testing.assert_allclose(y, y_ref, rtol=0,
                                   atol=1e-12 * np.abs(y_ref).max())


def test_general_f64_refresh_matches_stencil(model, monkeypatch):
    """PCG_TPU_HYBRID_F64_REFRESH=general swaps the out-of-loop f64
    matvecs onto a full general gather/scatter partition (compile-cost
    escape hatch for the octree flagship's 999 s stencil amul).  The
    operator must agree with the stencil form to f64 roundoff on the
    same partition, and a mixed solve must converge to the same answer."""
    cfg = RunConfig(
        solver=SolverConfig(tol=1e-8, max_iter=4000,
                            precision_mode="mixed"),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 1.0]))
    monkeypatch.setenv("PCG_TPU_HYBRID_F64_REFRESH", "stencil")
    s0 = Solver(model, cfg, mesh=make_mesh(4), n_parts=4, backend="hybrid")
    assert s0.f64_refresh == "stencil"
    r0 = s0.step(1.0)
    monkeypatch.setenv("PCG_TPU_HYBRID_F64_REFRESH", "general")
    s1 = Solver(model, cfg, mesh=make_mesh(4), n_parts=4, backend="hybrid")
    assert s1.f64_refresh == "general" and s1._refresh64 is not None

    # operator identity on a random f64 vector (padding is eff-masked)
    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.standard_normal((s1.pm.n_parts, s1.pm.n_loc)))
    y_sten = np.asarray(s0._amul64_fn(s0.data, v))
    y_gen = np.asarray(s1._amul64_fn(s1.data, v))
    np.testing.assert_allclose(
        y_gen, y_sten, rtol=1e-12,
        atol=1e-12 * max(1.0, np.abs(y_sten).max()))

    r1 = s1.step(1.0)
    assert r1.flag == 0 and r1.relres <= 1e-8
    assert r0.flag == 0
    u0 = np.asarray(s0.displacement_global())
    u1 = np.asarray(s1.displacement_global())
    np.testing.assert_allclose(u1, u0, rtol=1e-7,
                               atol=1e-9 * max(1.0, np.abs(u0).max()))

    # bucketed arm: types stacked into a few padded batched einsums
    # (compile-structure count ~8 instead of one per type)
    monkeypatch.setenv("PCG_TPU_HYBRID_F64_REFRESH", "bucketed")
    s2 = Solver(model, cfg, mesh=make_mesh(4), n_parts=4, backend="hybrid")
    assert s2.f64_refresh == "bucketed"
    y_bkt = np.asarray(s2._amul64_fn(s2.data, v))
    np.testing.assert_allclose(
        y_bkt, y_sten, rtol=1e-12,
        atol=1e-12 * max(1.0, np.abs(y_sten).max()))
    r2 = s2.step(1.0)
    assert r2.flag == 0 and r2.relres <= 1e-8
    u2 = np.asarray(s2.displacement_global())
    np.testing.assert_allclose(u2, u0, rtol=1e-7,
                               atol=1e-9 * max(1.0, np.abs(u0).max()))


def test_mixed_precision_hybrid(model):
    cfg = RunConfig(
        solver=SolverConfig(tol=1e-8, max_iter=4000, precision_mode="mixed"),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 1.0]))
    s = Solver(model, cfg, mesh=make_mesh(4), n_parts=4, backend="hybrid")
    res = s.step(1.0)
    assert res.flag == 0
    assert res.relres <= 1e-8


def test_tiled_blocks_match_dense(model):
    """Force block tiling (PCG_TPU_HYBRID_BLOCK=2 on a small model) and
    assert the tiled level grids produce the SAME matvec as the dense-
    bbox layout — block decomposition must not change the math, and
    block-boundary lattice nodes (shared by adjacent blocks) must
    accumulate exactly once per brick."""
    import os

    from pcg_mpi_solver_tpu.parallel.partition import make_elem_part

    ep = make_elem_part(model, 2, method="rcb")
    prev = os.environ.get("PCG_TPU_HYBRID_BLOCK")
    prev_m = os.environ.get("PCG_TPU_HYBRID_MERGE")
    try:
        # this test exercises the per-level dense-vs-tiled machinery; the
        # level merge (tested separately) would fold both into one batch
        os.environ["PCG_TPU_HYBRID_MERGE"] = "0"
        os.environ["PCG_TPU_HYBRID_BLOCK"] = "1000000"   # force dense
        hp_d = partition_hybrid(model, 2, elem_part=ep)
        os.environ["PCG_TPU_HYBRID_BLOCK"] = "2"         # force tiling
        hp_t = partition_hybrid(model, 2, elem_part=ep)
    finally:
        if prev is None:
            os.environ.pop("PCG_TPU_HYBRID_BLOCK", None)
        else:
            os.environ["PCG_TPU_HYBRID_BLOCK"] = prev
        if prev_m is None:
            os.environ.pop("PCG_TPU_HYBRID_MERGE", None)
        else:
            os.environ["PCG_TPU_HYBRID_MERGE"] = prev_m
    assert all(lv.nb == 1 for lv in hp_d.levels)
    assert any(lv.nb > 1 for lv in hp_t.levels), (
        "tiling did not engage — the tiled path is untested")
    ops_d = HybridOps.from_hybrid(hp_d)
    ops_t = HybridOps.from_hybrid(hp_t)
    data_d = device_data_hybrid(hp_d)
    data_t = device_data_hybrid(hp_t)
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((2, hp_d.pm.n_loc)))
    y_d = np.asarray(ops_d.matvec_local(data_d, x))
    y_t = np.asarray(ops_t.matvec_local(data_t, x))
    scale = np.abs(y_d).max()
    assert np.abs(y_t - y_d).max() / scale < 1e-12
    # diagonal and node-block assembly agree too
    d_d = np.asarray(ops_d.diag_local(data_d))
    d_t = np.asarray(ops_t.diag_local(data_t))
    assert np.abs(d_t - d_d).max() / np.abs(d_d).max() < 1e-12
    b_d = np.asarray(ops_d._node_block_local(data_d))
    b_t = np.asarray(ops_t._node_block_local(data_t))
    assert np.abs(b_t - b_d).max() / (np.abs(b_d).max() + 1e-30) < 1e-12
    # strain -> nodal averaging path agrees (exercises elem_strain,
    # elem_scale and nodal_average over tiled blocks)
    e_d = ops_d.elem_strain(data_d, x)
    e_t = ops_t.elem_strain(data_t, x)
    a_d = np.asarray(ops_d.nodal_average(data_d, e_d))
    a_t = np.asarray(ops_t.nodal_average(data_t, e_t))
    assert np.abs(a_t - a_d).max() / (np.abs(a_d).max() + 1e-30) < 1e-10
