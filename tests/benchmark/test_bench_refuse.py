"""Off a TPU the benchmark command prints no result and fails."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from harness import spec as specmod


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "cube.single",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(p):
    assert p.returncode != 0, p.stdout
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_refuses_off_a_chip():
    p = _run(specmod.ROOT)
    _no_result(p)
    assert "no TPU" in p.stderr


def test_refuses_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(os.path.join(specmod.ROOT, "BENCHMARK.json"), tmp_path)
    with open(os.path.join(specmod.ROOT, "BENCHMARK.json")) as f:
        paths = json.load(f)["paths"]
    for p in paths:
        shutil.copytree(os.path.join(specmod.ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run(tmp_path))
    # without the program even a rehearsal cannot produce a result
    _no_result(_run(tmp_path, "--cpu-rehearsal"))
