"""The solver's trace labels: ``mv/*`` matvec stages and ``solve/*``
scopes are HLO metadata only (the compiled programs are unchanged), and
the recorder's ``pcg-tpu/*`` spans are always on, around every solve,
refinement cycle, reset and both halves of the answer's fetch."""

import contextlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pcg_mpi_solver_tpu import RunConfig, SolverConfig
from pcg_mpi_solver_tpu.models.synthetic import make_cube_model
from pcg_mpi_solver_tpu.obs.metrics import MetricsRecorder
from pcg_mpi_solver_tpu.parallel import make_mesh
from pcg_mpi_solver_tpu.solver import Solver

_META = re.compile(r",?\s*metadata=\{[^}]*\}")


def _strip(text: str) -> str:
    """Compiled HLO text without op metadata and the source-location
    tables that metadata indexes."""
    return _META.sub("", text.split("\nFileNames")[0])


def _solver(ipd: int, backend: str = "general", recorder=None):
    model = make_cube_model(4, 3, 3, heterogeneous=True)
    cfg = RunConfig(solver=SolverConfig(precision_mode="mixed",
                                        iters_per_dispatch=ipd))
    return Solver(model, cfg, mesh=make_mesh(2), n_parts=2,
                  backend=backend, recorder=recorder)


def _compiled_texts() -> dict:
    """Optimized HLO of the one-shot step (both stencil backends) and of
    the chunked inner cycle and refinement programs."""
    out = {}
    for backend in ("general", "structured"):
        s = _solver(0, backend)
        delta = jnp.asarray(1.0, s.dtype)
        out[f"step[{backend}]"] = s._step_fn.lower(
            s.data, s.un, delta).compile().as_text()
    s = _solver(20)
    delta = jnp.asarray(1.0, s.dtype)
    udi = s._start_pre_fn(s.data, delta)
    fext, x0 = s._start_mid_fn(s.data, s.un, delta,
                               s._amul64_fn(s.data, udi))
    kx0 = s._amul64_fn(s.data, x0)
    carry, normr0, n2b, prec = s._start_post_fn(s.data, fext, x0, kx0)
    e = s._engine
    rhat32, tol_cycle, c32 = e._inner_start_fn(s.data, carry["r"], normr0,
                                               n2b)
    budget = jnp.asarray(5, jnp.int32)
    out["inner_cycle"] = e._inner_cycle_fn.lower(
        s.data, rhat32, prec, tol_cycle, c32, budget).compile().as_text()
    out["refine_pre"] = e._refine_pre_fn.lower(
        x0, rhat32, normr0).compile().as_text()
    out["refine_amul64"] = s._amul64_fn.lower(s.data, x0).compile().as_text()
    out["refine_post"] = e._refine_post_fn.lower(
        s.data, fext, kx0).compile().as_text()
    return out


def test_scopes_leave_compiled_programs_unchanged(monkeypatch):
    """With op metadata stripped, the step, inner-cycle and refinement
    programs compile byte-identically with every ``jax.named_scope``
    turned into a null context: the labels are metadata, not code."""
    labelled = _compiled_texts()
    assert "mv/gather" in labelled["step[general]"]
    assert "solve/track" in labelled["inner_cycle"]
    assert "solve/residual64" in labelled["refine_post"]
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _compiled_texts()
    assert "mv/gather" not in bare["step[general]"]
    for name, text in labelled.items():
        assert _strip(text) == _strip(bare[name]), name


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: records the
    (name, args) of each annotation entered, and the nesting depth."""

    def __init__(self):
        self.entered, self.depth = [], 0

    def __call__(self, name, **args):
        rec = self

        class Ann:
            def __enter__(self):
                rec.entered.append((name, args, rec.depth))
                rec.depth += 1

            def __exit__(self, *exc):
                rec.depth -= 1

        return Ann()


def test_recorder_spans_are_trace_annotations(monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    rec = MetricsRecorder()
    with rec.span("solve", step=4):
        with rec.dispatch("cycle"):
            pass
    assert ann.entered == [("pcg-tpu/solve", {"step": 4}, 0),
                           ("pcg-tpu/cycle", {}, 1)]
    assert rec.span_stats()["solve"]["calls"] == 1
    assert rec.dispatch_stats()["cycle"]["calls"] == 1


def test_recorder_imports_no_jax(monkeypatch):
    """Before jax is loaded no trace can be collecting: spans open no
    annotation and do not import jax (bench.py configures the platform
    after importing the recorder)."""
    monkeypatch.delitem(sys.modules, "jax")
    rec = MetricsRecorder()
    with rec.span("solve", step=1):
        with rec.dispatch("step"):
            pass
    assert "jax" not in sys.modules
    assert rec.span_stats()["solve"]["calls"] == 1


def test_no_option_turns_the_spans_on(capsys):
    """The annotations are always on: no config field, recorder
    argument or CLI flag selects them (profile_dir still starts a
    trace collection)."""
    import dataclasses

    from pcg_mpi_solver_tpu.cli import main

    assert {f.name for f in dataclasses.fields(RunConfig)
            if "profile" in f.name} == {"profile_dir"}
    with pytest.raises(TypeError):
        MetricsRecorder(profile_spans=True)
    with pytest.raises(TypeError):
        MetricsRecorder.default(profile=True)
    with pytest.raises(SystemExit) as exc:
        main(["solve", "--help"])
    assert exc.value.code == 0
    out = capsys.readouterr().out
    assert "--profile-dir" in out and "spans" not in out


def test_solver_host_spans(monkeypatch):
    """A chunked mixed solve nests its dispatches under ``pcg-tpu/solve``
    (carrying the step number) and each refinement cycle's under
    ``pcg-tpu/refine_cycle``; reset and both fetch halves are spans."""
    ann = _Annotations()
    rec = MetricsRecorder()
    s = _solver(20, recorder=rec)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    s.reset_state()
    r = s.step(1.0)
    u = s.displacement_global()
    assert r.flag == 0 and np.all(np.isfinite(u))
    names = [n for n, _, _ in ann.entered]
    assert names[0] == "pcg-tpu/reset"
    assert names[-2:] == ["pcg-tpu/fetch.transfer", "pcg-tpu/fetch.assemble"]
    solve = [(a, d) for n, a, d in ann.entered if n == "pcg-tpu/solve"]
    assert solve == [({"step": 1}, 0)]
    cycles = [d for n, _, d in ann.entered if n == "pcg-tpu/refine_cycle"]
    assert cycles and set(cycles) == {1}
    inner = [d for n, _, d in ann.entered
             if n in ("pcg-tpu/inner_start", "pcg-tpu/inner_cycle",
                      "pcg-tpu/refine")]
    assert inner and set(inner) == {2}
    st = rec.span_stats()
    for name in ("solve", "reset", "fetch.transfer", "fetch.assemble"):
        assert st[name]["calls"] == 1, name
    assert st["refine_cycle"]["calls"] == len(cycles)


def test_scatter_owned_global_is_the_host_half_of_the_gather():
    from pcg_mpi_solver_tpu.parallel.distributed import (
        fetch_global, gather_owned_global, scatter_owned_global)

    s = _solver(0)
    s.step(1.0)
    whole = gather_owned_global(s.pm, s.un, s.mesh, np.float64)
    halves = scatter_owned_global(s.pm, fetch_global(s.un, s.mesh),
                                  np.float64)
    assert np.array_equal(whole, halves)
    assert np.array_equal(s.displacement_global(), whole)


def test_matvec_stages_rule_fires_on_a_missing_stage():
    """Each backend's matvec carries all four ``mv/*`` stages; a seeded
    stage no program has fires once, and an unlabelled matvec fires on
    all four."""
    from pcg_mpi_solver_tpu.analysis.programs import matvec_programs
    from pcg_mpi_solver_tpu.analysis.rules_jaxpr import check_matvec_stages

    progs = matvec_programs()
    assert [n for n, _ in progs] == ["matvec[general]", "matvec[structured]",
                                     "matvec[hybrid]"]
    for name, jx in progs:
        assert check_matvec_stages(name, jx) == [], name
    name, jx = progs[0]
    found = check_matvec_stages(name, jx, stages=("mv/gather", "mv/ghost"))
    assert len(found) == 1 and "mv/ghost" in found[0].message
    bare = jax.make_jaxpr(lambda x: x * 2.0)(jnp.ones(3))
    assert len(check_matvec_stages("bare", bare)) == 4
