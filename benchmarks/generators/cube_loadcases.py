"""Heterogeneous voxel cube for a load-case set: the ``cube`` generator's
problem unchanged (mesh, modulus field, clamp, loaded face and its
area-consistent weights), whose problem also carries the loaded face's
node coordinates (y, z), which linearly varying tractions need.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import os

import numpy as np

from harness.reference import Problem


def _cube_module():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "cube.py")
    spec = importlib.util.spec_from_file_location("bench_generator_cube_base",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cube = _cube_module()


@dataclasses.dataclass
class FaceProblem(Problem):
    face_yz: np.ndarray = None      # (n_face, 2) y, z of the face's nodes


class LoadcaseCube(cube.Cube):
    def __init__(self, model_cfg: dict, size: dict):
        super().__init__(model_cfg, size)
        p = self.problem
        self.problem = FaceProblem(
            **{f.name: getattr(p, f.name) for f in dataclasses.fields(p)},
            face_yz=self.coords[p.face_nodes][:, 1:3].copy())


def make(model_cfg: dict, size: dict) -> LoadcaseCube:
    return LoadcaseCube(model_cfg, size)
