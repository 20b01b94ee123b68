"""A cell, a configuration, a traffic mix and a metric are found by name
from new files, with no file of the harness edited."""

import json
import os
import shutil

import time

import pytest

from harness import cell
from harness import spec as specmod
from harness.spec import Spec

# a traffic kind of its own: each request solves ``block`` load cases
# drawn from the seed in one Solver.solve_many call
BLOCK_KIND = '''
import types

import numpy as np

from harness import traffic

PARAMS = {"traction_direction", "magnitude_pa", "block"}


def plan(problem, mix, seed):
    traffic.check(mix, PARAMS)
    gen = traffic.rng(seed)
    Fs = np.stack([problem.load_vector(traffic.traction(mix, gen))
                   for _ in range(mix["block"])], axis=-1)
    return types.SimpleNamespace(F=Fs[:, 0], Fs=Fs)


def warm(jax, s, plan):
    s.solve_many(np.zeros_like(plan.Fs))


def request(jax, s, plan):
    r = s.solve_many(plan.Fs)
    u = s.displacement_global_many(r.x)
    recs = [{"flag": int(f), "relres": float(rr), "iters": int(i),
             "wall_s": r.wall_s / len(r.flags), "recoveries": {}}
            for f, rr, i in zip(r.flags, r.relres, r.iters)]
    return recs, [(plan.Fs[:, k], u[:, k]) for k in range(len(r.flags))]
'''


def test_every_entry_of_the_benchmark_resolves():
    s = Spec()
    for c in s.bench["configs"]:
        cfg = s.config(c["name"])
        assert cfg["name"] == c["name"]
        s.generator(cfg["generator"])
        assert set(c["reduced"]) <= set(cfg)
    for w in s.bench["workloads"]:
        s.traffic(w["traffic"])
        assert str(w["chips"]) in s.config(w["config"])["sizes"]
        names = {m["name"] for m, _ in s.metrics(w["name"], traced=False)}
        assert {"time_to_tol_s", "setup_s", "peak_hbm_gb"} <= names
        assert s.metrics(w["name"], traced=True)
    for m in s.bench["end_to_end"] + s.bench["per_layer"]:
        assert callable(s.reader(m["name"]))


def _checkout(tmp_path):
    root = tmp_path / "checkout"
    bench = root / "benchmarks"
    shutil.copytree(specmod.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(specmod.ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    return root, bench, b


def test_new_files_add_a_cell_a_mix_and_a_metric(tmp_path):
    root, bench, b = _checkout(tmp_path)
    # new files only: a configuration, a traffic mix and a metric reader
    cfg = json.loads((bench / "configs" / "cube_hetero.json").read_text())
    cfg["name"] = "cube_soft"
    cfg["model"]["inclusion_stiffness"] = 3.0
    (bench / "configs" / "cube_soft.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "single.json").read_text())
    mix["magnitude_pa"] = [1.0, 2.0]
    (bench / "traffic" / "tiny_loads.json").write_text(json.dumps(mix))
    (bench / "metrics" / "solver.solves.py").write_text(
        "def read(run):\n    return len(run['solves'])\n")
    # and their entries in BENCHMARK.json
    b["configs"].append(dict(b["configs"][0], name="cube_soft",
                             file="benchmarks/configs/cube_soft.json"))
    b["workloads"].append({"name": "cube.soft", "config": "cube_soft",
                           "traffic": "tiny_loads", "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "solver.solves", "unit": "solves",
                           "better": "higher", "source": "program_counter",
                           "layer": "Krylov numerics",
                           "moves": "time_to_tol_s",
                           "workloads": ["cube.soft"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    s = Spec(root=str(root), bench_dir=str(bench))
    cell = s.cell("cube.soft")
    assert s.config(cell["config"])["model"]["inclusion_stiffness"] == 3.0
    assert s.traffic(cell["traffic"])["magnitude_pa"] == [1.0, 2.0]
    traced = dict((m["name"], r) for m, r in s.metrics("cube.soft", True))
    assert traced["solver.solves"]({"solves": [1, 2, 3]}) == 3
    assert "matvec_roofline" in traced
    # a metric listed for one cell stays out of the others
    assert "solver.solves" not in {
        m["name"] for m, _ in s.metrics("cube.single", True)}
    with pytest.raises(KeyError):
        s.cell("cube.none")


def test_a_new_traffic_kind_runs_a_whole_cell(tmp_path):
    """A kind module and a mix of it, new files only, drive a whole
    rehearsal run: set-up, window, check against the reference."""
    root, bench, b = _checkout(tmp_path)
    (bench / "traffic" / "block.py").write_text(BLOCK_KIND)
    mix = json.loads((bench / "traffic" / "single.json").read_text())
    (bench / "traffic" / "block3.json").write_text(json.dumps(
        dict(mix, kind="block", block=3, why="three load cases a call")))
    b["workloads"].append({"name": "cube.block3", "config": "cube_hetero",
                           "traffic": "block3", "chips": 1, "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    s = Spec(root=str(root), bench_dir=str(bench))
    result, ok = cell.run("cube.block3", 2 ** 31 + 3, 0.0, False,
                          time.perf_counter(), rehearsal=True, spec=s)
    assert ok, result["compared"]
    assert result["attempted"] == 3 and result["failed"] == 0
    assert result["distinct_answers"] == 3
    assert result["window_compiles"] == 0
