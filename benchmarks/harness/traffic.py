"""Traffic: a mix is a data file, ``traffic/<mix>.json``, of parameters;
its ``kind`` names the module, ``traffic/<kind>.py``, that turns the
parameters and the seed into requests.  A new mix of a kind is a data
file alone; a new kind is one more module, found by name
(harness/spec.py).

A kind module provides:

- ``PARAMS``: the parameters it reads, besides ``kind`` and ``why``;
- ``plan(problem, mix, seed)``: what the seed draws, an object whose
  ``F`` is the load vector the model is built with;
- ``warm(jax, s, plan)``: the cheapest calls that run every program a
  request runs, so that none compiles or loads inside the window;
- ``request(jax, s, plan)``: one request, as ``(records, answers)``: a
  record per load case solved (``flag``, ``relres``, ``iters``,
  ``wall_s``, ``recoveries``) and an ``(F, u)`` pair per answer to check
  against the reference.

The helpers below are the draws kinds share.
"""

from __future__ import annotations

import numpy as np

COMMON = {"kind", "why"}


def check(mix: dict, params) -> None:
    unknown = set(mix) - COMMON - set(params)
    if unknown:
        raise ValueError(f"traffic mix of kind {mix.get('kind')!r} has "
                         f"parameters it does not read: {sorted(unknown)}")


def rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed) % 2 ** 64)


def traction(mix: dict, gen: np.random.Generator) -> np.ndarray:
    """A traction vector (Pa): ``traction_direction`` (normal component
    first) scaled so that its normal component is drawn log-uniform from
    ``magnitude_pa`` = [lo, hi]."""
    lo, hi = mix["magnitude_pa"]
    if not 0 < lo <= hi:
        raise ValueError(f"bad magnitude range {mix['magnitude_pa']}")
    tn = float(np.exp(gen.uniform(np.log(lo), np.log(hi))))
    d = np.asarray(mix["traction_direction"], float)
    return tn * d / d[0]
