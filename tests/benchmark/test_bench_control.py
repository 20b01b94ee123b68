"""The control: the program's own float32 path, one precision below the
float64-grade answer the configurations state, fails the reference
comparison that the mixed-precision path passes."""

import jax
import pytest

from harness import cell
from harness.spec import Spec


@pytest.mark.parametrize("config,size", [
    ("cube_hetero", {"cells": [12, 10, 10]}),
    ("octree_graded", None),
])
def test_float32_control_fails_where_the_program_passes(config, size):
    spec = Spec()
    cfg = spec.config(config)
    size = size or cfg["rehearsal_sizes"]["1"]
    gen = spec.generator(cfg["generator"]).make(cfg["model"], size)
    mix = spec.traffic("single")
    kind = spec.traffic_kind(mix["kind"])
    plan = kind.plan(gen.problem, mix, 3)
    limit = cfg["solver"]["tol"]
    readings = {}
    for name, overrides in (("program", None), ("control", cfg["control"])):
        s, _, _ = cell.build(jax, cfg, gen, plan.F, jax.devices()[:1],
                             overrides)
        _, answers = kind.request(jax, s, plan)
        compared, _ = cell.check_answers(gen.problem, answers, limit)
        readings[name] = compared["ref_relres"]["value"]
    assert readings["program"] <= limit
    # the upper reading sits well above the limit, not at its edge
    assert readings["control"] > 30 * limit, readings
