"""Graded octree: the program's own generator
(models/octree.make_octree_model) makes the mesh, like a scan; the
benchmark derives its operator from the mesh's geometry alone.

The generator pulls in the program's pattern library (transition
elements with hanging nodes), which is not copied here.  What the
benchmark takes from the generated model is data: the node coordinates
(which fix the numbering of the answer, dof ``3 * node + c``), each
element's cell (centre and size) and its phase (matrix or inclusion).
From these it finds, by coordinate, the node at each of an element's 27
half-spacing lattice points, so each element's hanging-node pattern, in
its own orientation; its stiffness is assembled here from that pattern
(``transition_stiffness``), and the clamped dofs and the loaded face's
consistent nodal weights follow from the coordinates.  Nothing of the
program's connectivity, pattern types, reflection signs, stiffness
scales or load enters the reference.

Every run also checks the generated model against the ``pin`` recorded
with its size in the configuration's file, so that no later change to
the program's generator can move the workload: a SHA-256 of the model's
integer arrays (element types, pattern masks, dof ids, offsets,
reflection signs, free dofs), which must match exactly, and sums of its
stiffness scales, load and coordinates, which must match to 1e-9.
"""

from __future__ import annotations

import functools
import hashlib

import numpy as np

from generators.cube import CORNERS, unit_element
from harness.reference import Group, Problem

FLOAT_RTOL = 1e-9

# the 27-point lattice of the unit cube at half spacing, (x, y, z) in
# {0, 1, 2}^3 with id x + 3y + 9z; edges and faces in np.ndindex order,
# which is the order of a pattern mask's bits
_LAT = np.array(list(np.ndindex(3, 3, 3)))
_CORNER_P = (2 * CORNERS).astype(int)
_EDGE_P = np.array([p for p in _LAT if (p == 1).sum() == 1])
_FACE_P = np.array([p for p in _LAT if (p == 1).sum() == 2])


def _lid(p) -> int:
    return int(p[0] + 3 * p[1] + 9 * p[2])


_CORNER_IDS = [_lid(p) for p in _CORNER_P]
_BY_ID = np.array([[l % 3, l // 3 % 3, l // 9] for l in range(27)])
_MID_IDS = [_lid(p) for p in _EDGE_P] + [_lid(p) for p in _FACE_P]


def _slots(mask: int) -> list:
    """Lattice ids of a pattern's nodes: the 8 corners, then the edge
    midpoints and face centres whose bits are set."""
    return _CORNER_IDS + [l for b, l in enumerate(_MID_IDS) if mask >> b & 1]


@functools.lru_cache(maxsize=None)
def _interp(mask: int) -> np.ndarray:
    """(27, n_nodes): each lattice point as a combination of the
    pattern's nodes.  An absent edge midpoint averages its two ends, an
    absent face centre its four edge midpoints, the centre the six face
    centres."""
    slot = {l: i for i, l in enumerate(_slots(mask))}
    A = np.zeros((27, len(slot)))
    for p in _CORNER_P:
        A[_lid(p), slot[_lid(p)]] = 1.0
    for p in _EDGE_P:
        if _lid(p) in slot:
            A[_lid(p), slot[_lid(p)]] = 1.0
            continue
        ax = int(np.flatnonzero(p == 1)[0])
        lo, hi = p.copy(), p.copy()
        lo[ax], hi[ax] = 0, 2
        A[_lid(p)] = 0.5 * (A[_lid(lo)] + A[_lid(hi)])
    for p in _FACE_P:
        if _lid(p) in slot:
            A[_lid(p), slot[_lid(p)]] = 1.0
            continue
        ax = int(np.flatnonzero(p != 1)[0])
        mids = []
        for t in (t for t in range(3) if t != ax):
            for v in (0, 2):
                q = p.copy()
                q[t] = v
                mids.append(A[_lid(q)])
        A[_lid(p)] = np.mean(mids, axis=0)
    A[_lid((1, 1, 1))] = np.mean([A[_lid(p)] for p in _FACE_P], axis=0)
    return A


@functools.lru_cache(maxsize=None)
def _octant(nu: float) -> np.ndarray:
    return 0.5 * unit_element(nu)["Ke"]            # an h = 1/2 octant


def transition_stiffness(mask: int, nu: float) -> np.ndarray:
    """Unit (h = 1, E = 1) stiffness of one hanging-node pattern."""
    A = _interp(mask)
    Ke_oct = _octant(nu)
    Ke = np.zeros((3 * A.shape[1],) * 2)
    for o in np.ndindex(2, 2, 2):
        G = np.kron(A[[_lid(np.asarray(o) + c) for c in _CORNER_P // 2]],
                    np.eye(3))
        Ke += G.T @ Ke_oct @ G
    return Ke


# consistent nodal weights of a uniform traction on the lattice face x = 2
# of a unit cell: four h/2 x h/2 quadrants, each a quarter of its area to
# each of its corners
_XFACE = [_lid((2, y, z)) for z in range(3) for y in range(3)]
_XFACE_W = np.array([1, 2, 1, 2, 4, 2, 1, 2, 1]) / 16.0


def lattice_problem(coords, centres, sizes, phase, E, stiffness,
                    nu) -> Problem:
    """The plain problem of an octree mesh from its geometry: node
    coordinates, element cells (centre, size) and phases (0 matrix, 1
    inclusion); clamped at x = 0, loaded on x = L."""
    coords = np.asarray(coords, float)
    sizes = np.asarray(sizes, float)
    q = sizes.min() / 2                            # lattice unit
    ic = np.rint(coords / q).astype(np.int64)
    lo = np.rint((np.asarray(centres) - sizes[:, None] / 2) / q).astype(
        np.int64)
    half = np.rint(sizes / (2 * q)).astype(np.int64)
    K = int(max(ic.max(), (lo + 2 * half[:, None]).max())) + 1

    def key(p):
        return p[..., 0] + K * (p[..., 1] + K * p[..., 2])

    node_key = key(ic)
    order = np.argsort(node_key)
    skey = node_key[order]
    pts = lo[:, None, :] + _BY_ID[None] * half[:, None, None]  # (n, 27, 3)
    k = key(pts)
    pos = np.minimum(np.searchsorted(skey, k), len(skey) - 1)
    present = skey[pos] == k
    node_at = np.where(present, order[pos], -1)
    if not present[:, _CORNER_IDS].all():
        raise RuntimeError("an element corner is not a mesh node")
    if present[:, _lid((1, 1, 1))].any():
        raise RuntimeError("a mesh node lies inside an element")
    masks = (present[:, _MID_IDS].astype(np.int64)
             << np.arange(len(_MID_IDS))).sum(axis=1)

    n_dof = 3 * len(coords)
    Lx = ic[:, 0].max()
    on_face = lo[:, 0] + 2 * half == Lx
    ck = E * np.where(np.asarray(phase) > 0, stiffness, 1.0) * sizes
    groups, fw = [], np.zeros(len(coords))
    for m in np.unique(masks):
        e = np.flatnonzero(masks == m)
        nodes = node_at[e][:, _slots(int(m))]                   # (ne, nn)
        groups.append(Group(
            Ke=transition_stiffness(int(m), nu),
            dofs=(3 * nodes[:, :, None] + np.arange(3)).reshape(len(e), -1),
            ck=ck[e]))
        f = on_face[e]
        if f.any():
            w = _interp(int(m))[_XFACE].T @ _XFACE_W            # (nn,)
            np.add.at(fw, nodes[f], sizes[e[f], None] ** 2 * w[None])
    fixed = np.flatnonzero(ic[:, 0] == 0)
    eff = np.setdiff1d(np.arange(n_dof),
                       (3 * fixed[:, None] + np.arange(3)).ravel())
    face = np.flatnonzero(fw)
    return Problem(n_dof=n_dof, groups=groups, eff=eff, face_nodes=face,
                   face_weights=fw[face])


def fingerprint(m) -> dict:
    h = hashlib.sha256()
    masks = np.array([m.elem_lib[t]["mask"] for t in sorted(m.elem_lib)])
    for a in (m.elem_type, masks, m.elem_dofs_flat, m.elem_dofs_offset,
              m.elem_sign_flat, m.dof_eff):
        a = np.ascontiguousarray(a)
        h.update(str((a.dtype.str, a.shape)).encode())
        h.update(a.tobytes())
    return {
        "n_dof": int(m.n_dof),
        "n_elem": int(m.n_elem),
        "n_types": len(m.elem_lib),
        "int_sha256": h.hexdigest(),
        "ck_sum": float(np.sum(m.ck)),
        "load_sum": float(np.sum(m.F)),
        "coord_sum": float(np.sum(m.node_coords)),
    }


def check_pin(fp: dict, pin: dict) -> None:
    bad = [k for k in ("n_dof", "n_elem", "n_types", "int_sha256")
           if fp[k] != pin[k]]
    bad += [k for k in ("ck_sum", "load_sum", "coord_sum")
            if not abs(fp[k] - pin[k]) <= FLOAT_RTOL * abs(pin[k])]
    if bad:
        raise RuntimeError(
            f"generated octree differs from the pinned one in {bad}: "
            f"generated {fp}, pinned {pin}")


class Octree:
    def __init__(self, model_cfg: dict, size: dict):
        from pcg_mpi_solver_tpu.models.octree import make_octree_model

        n0 = size["n0"]
        self.base = make_octree_model(
            nx0=n0, ny0=n0, nz0=n0, h0=model_cfg["h0"],
            max_level=model_cfg["max_level"], E=model_cfg["E"],
            nu=model_cfg["nu"], rho=model_cfg["rho"], load="traction",
            load_value=1.0, n_incl=model_cfg["n_incl"],
            incl_stiff=model_cfg["inclusion_stiffness"],
            seed=model_cfg["field_seed"])
        self.fp = fingerprint(self.base)
        check_pin(self.fp, size["pin"])
        m = self.base
        if np.any(m.Ud):
            raise RuntimeError("pinned octree has prescribed displacements")
        self.problem = lattice_problem(
            m.node_coords, m.sctrs, m.level, m.poly_mat, model_cfg["E"],
            model_cfg["inclusion_stiffness"], model_cfg["nu"])
        if self.problem.n_dof != m.n_dof:
            raise RuntimeError("octree dof count differs from 3 per node")

    def model(self, F: np.ndarray):
        import dataclasses

        return dataclasses.replace(self.base, F=F)


def make(model_cfg: dict, size: dict) -> Octree:
    return Octree(model_cfg, size)
