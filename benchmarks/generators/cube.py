"""Heterogeneous voxel cube: the benchmark's own copy of the program's
cube generator (models/synthetic.make_cube_model with heterogeneous=True
and the unit hexahedron of models/element.py).

A structured nx x ny x nz block of 8-node hexahedra (h-sized voxels),
clamped at x = 0, with a two-phase Young's modulus field: spherical
inclusions ``inclusion_stiffness`` times stiffer than the matrix, one per
~500 elements, placed by a fixed generator seed (the field is part of the
configuration, like a CT scan).  The load is a uniform traction on the
x = L face with area-consistent nodal weights.

The element stiffness is computed here from scratch (2x2x2 Gauss), so
the reference operator takes nothing that the program made.
"""

from __future__ import annotations

import numpy as np

from harness.reference import Group, Problem

# VTK hexahedron corner order
CORNERS = np.array([[0, 0, 0], [1, 0, 0], [1, 1, 0], [0, 1, 0],
                    [0, 0, 1], [1, 0, 1], [1, 1, 1], [0, 1, 1]], float)


def _grad_natural(xi):
    s = 2.0 * CORNERS - 1.0
    f = 0.5 * (1.0 + s * xi)                          # (8, 3)
    g = np.empty((8, 3))
    g[:, 0] = 0.5 * s[:, 0] * f[:, 1] * f[:, 2]
    g[:, 1] = f[:, 0] * 0.5 * s[:, 1] * f[:, 2]
    g[:, 2] = f[:, 0] * f[:, 1] * 0.5 * s[:, 2]
    return g


def _b_matrix(dn):
    """Strain-displacement matrix, Voigt (XX, YY, ZZ, YZ, XZ, XY)."""
    B = np.zeros((6, 24))
    for i, (bx, by, bz) in enumerate(dn):
        c = 3 * i
        B[0, c], B[1, c + 1], B[2, c + 2] = bx, by, bz
        B[3, c + 1], B[3, c + 2] = bz, by
        B[4, c], B[4, c + 2] = bz, bx
        B[5, c], B[5, c + 1] = by, bx
    return B


def _gauss():
    g = 1.0 / np.sqrt(3.0)
    return [np.array([a, b, c]) for c in (-g, g) for b in (-g, g)
            for a in (-g, g)]


def unit_element(nu: float) -> dict:
    """Unit (h = 1, E = 1) hexahedron: stiffness, consistent mass and the
    centre-point strain mode."""
    c = 1.0 / ((1.0 + nu) * (1.0 - 2.0 * nu))
    D = np.zeros((6, 6))
    D[:3, :3] = c * nu
    np.fill_diagonal(D[:3, :3], c * (1.0 - nu))
    D[3, 3] = D[4, 4] = D[5, 5] = c * (1.0 - 2.0 * nu) / 2.0
    J = 0.5                                           # dx/dxi, h = 1
    Ke = np.zeros((24, 24))
    Me = np.zeros((24, 24))
    s = 2.0 * CORNERS - 1.0
    for xi in _gauss():
        B = _b_matrix(_grad_natural(xi) / J)
        Ke += B.T @ D @ B * J ** 3
        N = np.prod(0.5 * (1.0 + s * xi), axis=1)
        Nm = np.kron(N[None, :], np.eye(3))           # (3, 24)
        Me += Nm.T @ Nm * J ** 3
    Se = _b_matrix(_grad_natural(np.zeros(3)) / J)
    return {"Ke": Ke, "Me": Me, "Se": Se, "diagKe": np.diag(Ke).copy(),
            "n_nodes": 8}


def _mesh(nx, ny, nz, h):
    nnx, nny = nx + 1, ny + 1
    nid = np.arange(nnx * nny * (nz + 1))
    coords = np.stack([(nid % nnx) * h, ((nid // nnx) % nny) * h,
                       (nid // (nnx * nny)) * h], axis=1)
    ex, ey, ez = np.meshgrid(np.arange(nx), np.arange(ny), np.arange(nz),
                             indexing="ij")
    ex, ey, ez = (a.ravel(order="F") for a in (ex, ey, ez))
    n0 = ex + nnx * (ey + nny * ez)
    conn = np.stack([n0, n0 + 1, n0 + 1 + nnx, n0 + nnx,
                     n0 + nnx * nny, n0 + 1 + nnx * nny,
                     n0 + 1 + nnx + nnx * nny, n0 + nnx + nnx * nny], axis=1)
    return coords, conn


def _modulus(nx, ny, nz, h, E, stiff, seed):
    """Two-phase field: spheres of radius 1.5-3.5 h, one per ~500 cells."""
    rng = np.random.default_rng(seed)
    n_incl = max(1, nx * ny * nz // 500)
    c_incl = rng.uniform(0, 1, (n_incl, 3)) * (np.array([nx, ny, nz]) * h)
    r_incl = rng.uniform(1.5, 3.5, n_incl) * h
    E3 = np.full((nz, ny, nx), float(E))
    ax, ay, az = ((np.arange(n) + 0.5) * h for n in (nx, ny, nz))
    for c, r in zip(c_incl, r_incl):
        i0, i1 = np.searchsorted(ax, [c[0] - r, c[0] + r])
        j0, j1 = np.searchsorted(ay, [c[1] - r, c[1] + r])
        k0, k1 = np.searchsorted(az, [c[2] - r, c[2] + r])
        if i0 >= i1 or j0 >= j1 or k0 >= k1:
            continue
        d2 = ((ax[i0:i1][None, None, :] - c[0]) ** 2
              + (ay[j0:j1][None, :, None] - c[1]) ** 2
              + (az[k0:k1][:, None, None] - c[2]) ** 2)
        E3[k0:k1, j0:j1, i0:i1][d2 < r * r] = stiff * E
    return E3.reshape(-1)


class Cube:
    """The generated cube: the plain problem for the reference, and the
    program's model for a given load vector."""

    def __init__(self, model_cfg: dict, size: dict):
        nx, ny, nz = size["cells"]
        h, E, nu = model_cfg["h"], model_cfg["E"], model_cfg["nu"]
        self.grid = (nx, ny, nz, h)
        self.nu, self.rho = nu, model_cfg["rho"]
        coords, conn = _mesh(nx, ny, nz, h)
        self.coords, self.conn = coords, conn
        self.E_elem = _modulus(nx, ny, nz, h, E,
                               model_cfg["inclusion_stiffness"],
                               model_cfg["field_seed"])
        self.E = E
        self.lib = unit_element(nu)
        n_node = len(coords)
        self.dofs = (3 * conn[:, :, None] + np.arange(3)).reshape(-1, 24)
        on_x0 = np.flatnonzero(coords[:, 0] == 0.0)
        fixed = (3 * on_x0[:, None] + np.arange(3)).ravel()
        eff = np.setdiff1d(np.arange(3 * n_node), fixed, assume_unique=True)
        self.fixed = np.sort(fixed)
        # area-consistent weights: each h x h face quad gives h^2/4 to
        # each of its corners
        nnx, nny = nx + 1, ny + 1
        face = np.flatnonzero(coords[:, 0] == nx * h)
        j = (face // nnx) % nny
        k = face // (nnx * nny)
        wy = np.where((j == 0) | (j == ny), 0.5, 1.0)
        wz = np.where((k == 0) | (k == nz), 0.5, 1.0)
        self.problem = Problem(
            n_dof=3 * n_node,
            groups=[Group(Ke=self.lib["Ke"], dofs=self.dofs,
                          ck=self.E_elem * h)],
            eff=eff, face_nodes=face, face_weights=wy * wz * h * h)

    def model(self, F: np.ndarray):
        """The program's ModelData of this cube under load ``F``."""
        from pcg_mpi_solver_tpu.models.model_data import ModelData

        nx, ny, nz, h = self.grid
        n_elem = nx * ny * nz
        n_dof = self.problem.n_dof
        cm = self.rho * np.full(n_elem, h ** 3)
        diag_M = np.bincount(
            self.dofs.ravel(),
            weights=(cm[:, None] * self.lib["Me"].sum(axis=1)).ravel(),
            minlength=n_dof)
        stiff = self.E_elem > self.E
        mat_prop = [{"E": self.E, "Pos": self.nu, "Rho": self.rho,
                     "NonLocStressParam": {"Lc": 2.0 * h}},
                    {"E": float(self.E_elem.max()), "Pos": self.nu,
                     "Rho": self.rho, "NonLocStressParam": {"Lc": 2.0 * h}}]
        return ModelData(
            n_elem=n_elem, n_node=len(self.coords), n_dof=n_dof,
            node_coords=self.coords, F=F, Ud=np.zeros(n_dof),
            Vd=np.zeros(n_dof), diag_M=diag_M, fixed_dof=self.fixed,
            dof_eff=self.problem.eff,
            elem_type=np.zeros(n_elem, np.int32),
            elem_nodes_flat=self.conn.ravel(),
            elem_nodes_offset=np.arange(n_elem + 1) * 8,
            elem_dofs_flat=self.dofs.ravel(),
            elem_dofs_offset=np.arange(n_elem + 1) * 24,
            elem_sign_flat=np.zeros(n_elem * 24, bool),
            ck=self.E_elem * h, cm=cm, ce=np.full(n_elem, 1.0 / h),
            level=np.full(n_elem, h), poly_mat=stiff.astype(np.int32),
            sctrs=self.coords[self.conn[:, 0]] + 0.5 * h,
            elem_lib={0: self.lib}, mat_prop=mat_prop, dt=1.0,
            grid=self.grid)


def make(model_cfg: dict, size: dict) -> Cube:
    return Cube(model_cfg, size)
