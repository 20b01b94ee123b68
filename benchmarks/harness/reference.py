"""The plain problem and its f64 reference operator.

A problem is what a generator makes from a configuration: element groups
(one per element type, each a d x d unit stiffness, the d dof ids and
reflection signs of every element, and a stiffness scale per element),
the free dofs, and the loaded face's area-consistent nodal weights.  The
reference applies ``K = sum_e P_e^T S_e (ck_e Ke) S_e P_e`` in float64
numpy, element block by element block so that it fits in memory.  It
imports nothing of the program under test; its type-grouped form follows
the program's own numpy oracle (solver/numpy_ref.py), copied here so that
no later change to the program can move it.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

BLOCK_ELEMS = 1 << 18


@dataclasses.dataclass
class Group:
    Ke: np.ndarray                  # (d, d) unit element stiffness
    dofs: np.ndarray                # (n, d) global dof ids
    ck: np.ndarray                  # (n,) stiffness scale
    signs: Optional[np.ndarray] = None   # (n, d) bool: reflected dofs


@dataclasses.dataclass
class Problem:
    n_dof: int
    groups: List[Group]
    eff: np.ndarray                 # free dof ids
    face_nodes: np.ndarray          # nodes of the loaded face
    face_weights: np.ndarray        # their area-consistent weights

    def type_sizes(self):
        return [(g.Ke.shape[0], len(g.ck)) for g in self.groups]

    @property
    def n_elem(self) -> int:
        return sum(len(g.ck) for g in self.groups)

    def load_vector(self, traction) -> np.ndarray:
        """Nodal forces of a uniform traction vector on the loaded face."""
        F = np.zeros(self.n_dof)
        for c in range(3):
            F[3 * self.face_nodes + c] = self.face_weights * traction[c]
        return F


def matvec(problem: Problem, x: np.ndarray) -> np.ndarray:
    """K @ x in float64."""
    y = np.zeros(problem.n_dof)
    for g in problem.groups:
        Ke = np.asarray(g.Ke, np.float64)
        for a in range(0, len(g.ck), BLOCK_ELEMS):
            b = a + BLOCK_ELEMS
            dofs = g.dofs[a:b]
            u = x[dofs] * g.ck[a:b, None]
            if g.signs is not None:
                u = np.where(g.signs[a:b], -u, u)
            v = u @ Ke.T
            if g.signs is not None:
                v = np.where(g.signs[a:b], -v, v)
            y += np.bincount(dofs.ravel(), weights=v.ravel(),
                             minlength=problem.n_dof)
    return y


def relres(problem: Problem, F: np.ndarray, u: np.ndarray) -> float:
    """||F - K u|| / ||F|| over the free dofs (the prescribed
    displacements are zero)."""
    r = (F - matvec(problem, u))[problem.eff]
    return float(np.linalg.norm(r) / np.linalg.norm(F[problem.eff]))
