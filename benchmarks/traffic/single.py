"""Kind ``single``: one caller sends one load case after another, each a
cold solve from a zero initial guess, the next sent when the previous
answer is in hand.

The seed draws the traction's magnitude (``magnitude_pa``); its
direction (``traction_direction``) is the mix's.  A relative tolerance
makes the iterations independent of the magnitude, so every seed does
the same work and changes only the answer that is checked.  A request is
``reset_state()``, ``step(1.0)`` (which fetches the flag, residual and
iterations) and ``displacement_global()`` (the answer in the caller's
hands).
"""

from __future__ import annotations

import types

from harness import traffic
from harness.cell import solve_once

PARAMS = {"traction_direction", "magnitude_pa"}


def plan(problem, mix: dict, seed: int):
    traffic.check(mix, PARAMS)
    t = traffic.traction(mix, traffic.rng(seed))
    return types.SimpleNamespace(F=problem.load_vector(t), traction=t)


def warm(jax, s, plan) -> None:
    import jax.numpy as jnp

    # a request at zero load runs the request's programs, with no
    # iteration: warmup() compiles them but leaves their first run
    s.reset_state()
    s.step(0.0)
    s.displacement_global()
    # the chunked path copies the iterate (jnp.copy) in a solve's first
    # refinement cycle, which warmup() does not compile
    jax.block_until_ready(jnp.copy(s.un))


def request(jax, s, plan):
    rec, u = solve_once(jax, s)
    return [rec], [(plan.F, u)]
