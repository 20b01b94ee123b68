"""Kind ``sweep``: one caller checks one specimen against a design load
set, a block of ``block`` load cases per request, each block a cold
``Solver.solve_many`` from a zero initial guess, the next sent when the
previous block's answers are in hand.

The columns are the six unit stress-resultant tractions on the loaded
face (x = L), as nodal forces ``w_node * t(y, z)`` with the face's
area-consistent weights, its centroid (yc, zc) and half-widths (by, bz):

- N: t = (1, 0, 0); Vy: t = (0, 1, 0); Vz: t = (0, 0, 1);
- T: t = (0, -(z - zc)/bz, (y - yc)/by);
- My: t = ((z - zc)/bz, 0, 0); Mz: t = ((y - yc)/by, 0, 0);

then the mix's ``combinations`` of them, in the mix file's order.  The
seed draws each column's peak traction log-uniform from ``magnitude_pa``;
a relative tolerance makes the iterations independent of it, so every
seed does the same work.  A request is ``solve_many`` (which fetches the
per-column flags, residuals and iterations) and
``displacement_global_many`` (the block's answers in the caller's
hands); each column's record carries the block's wall over its width and
the block's lockstep iterations.
"""

from __future__ import annotations

import time
import types

import numpy as np

from harness import traffic

PARAMS = {"block", "combinations", "magnitude_pa"}
UNIT = ("N", "Vy", "Vz", "T", "My", "Mz")


def unit_fields(problem) -> dict:
    """The six unit stress-resultant load vectors of the loaded face."""
    y, z = problem.face_yz[:, 0], problem.face_yz[:, 1]
    w = problem.face_weights
    yc, zc = np.sum(w * y) / np.sum(w), np.sum(w * z) / np.sum(w)
    by, bz = (y.max() - y.min()) / 2.0, (z.max() - z.min()) / 2.0
    eta, zeta = (y - yc) / by, (z - zc) / bz
    one, zero = np.ones_like(y), np.zeros_like(y)
    tractions = {"N": (one, zero, zero), "Vy": (zero, one, zero),
                 "Vz": (zero, zero, one), "T": (zero, -zeta, eta),
                 "My": (zeta, zero, zero), "Mz": (eta, zero, zero)}
    out = {}
    for name, t in tractions.items():
        F = np.zeros(problem.n_dof)
        for c in range(3):
            F[3 * problem.face_nodes + c] = w * t[c]
        out[name] = F
    return out


def load_set(problem, mix: dict) -> dict:
    """Name -> unit load vector: the six fields, then the combinations."""
    fields = unit_fields(problem)
    cases = dict(fields)
    for name, coef in mix["combinations"].items():
        unknown = set(coef) - set(UNIT)
        if unknown:
            raise ValueError(f"combination {name!r} names {sorted(unknown)}, "
                             f"not among {UNIT}")
        cases[name] = sum(coef.get(k, 0.0) * fields[k] for k in UNIT)
    if len(cases) != mix["block"]:
        raise ValueError(f"the load set has {len(cases)} cases, the block "
                         f"is {mix['block']} wide")
    return cases


def plan(problem, mix: dict, seed: int):
    traffic.check(mix, PARAMS)
    lo, hi = mix["magnitude_pa"]
    if not 0 < lo <= hi:
        raise ValueError(f"bad magnitude range {mix['magnitude_pa']}")
    cases = load_set(problem, mix)
    gen = traffic.rng(seed)
    peaks = np.exp(gen.uniform(np.log(lo), np.log(hi), len(cases)))
    FB = np.stack([p * F for p, F in zip(peaks, cases.values())], axis=-1)
    return types.SimpleNamespace(F=FB[:, 0], FB=FB, names=list(cases),
                                 peaks=peaks)


def warm(jax, s, plan) -> None:
    # a zero block of the same width runs the blocked program and the
    # block's fetch, with no iteration
    r = s.solve_many(np.zeros_like(plan.FB))
    s.displacement_global_many(r.x)


def _recoveries(s) -> dict:
    return {k: v for k, v in s.recorder.counters.items()
            if k.startswith("resilience.recovery.")}


def request(jax, s, plan):
    before = _recoveries(s)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("dispatch"):
        r = s.solve_many(plan.FB)
    with jax.profiler.TraceAnnotation("fetch"):
        u = s.displacement_global_many(r.x)
    wall = time.perf_counter() - t0
    fired = {k: v - before.get(k, 0) for k, v in _recoveries(s).items()
             if v != before.get(k, 0)}
    R = plan.FB.shape[1]
    lockstep = getattr(r, "lockstep_iters", None)
    records = [{"flag": int(r.flags[j]), "relres": float(r.relres[j]),
                "iters": int(r.iters[j]), "wall_s": wall / R,
                "recoveries": fired, "block": R,
                "lockstep_iters": (None if lockstep is None
                                   else int(lockstep))}
               for j in range(R)]
    return records, [(plan.FB[:, j], u[:, j]) for j in range(R)]
