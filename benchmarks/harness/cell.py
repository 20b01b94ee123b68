"""One run of one cell: set-up, the measured window, the trace, the
check against the reference, and the result line.

The window drives the program through its public API, as its README
does: ``Solver(model, RunConfig(solver=SolverConfig(...)), mesh,
n_parts, backend)`` and ``warmup()`` in set-up, then the traffic kind's
warm-up calls (traffic/<kind>.py), then its requests back to back.  A
request still running when the window's seconds are up is finished and
counted.  A traced run traces the window's first request.  Every answer
is then compared with the reference operator (harness/reference.py).
"""

from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time

import numpy as np

from harness import reference, roofline, trace
from harness.spec import Spec


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def devices_for(jax, chips: int, rehearsal: bool):
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not rehearsal:
        raise NoChip(f"no TPU: JAX platform is {platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, {len(devs)} present")
    return devs[:chips]


def solver_config(cfg: dict, overrides: dict = None):
    from pcg_mpi_solver_tpu import RunConfig, SolverConfig

    kw = {k: v for k, v in cfg["solver"].items() if k != "backend"}
    kw.update(overrides or {})
    return RunConfig(solver=SolverConfig(**kw))


def build(jax, cfg, generated, F, devices, overrides=None):
    """Construct the solver (``build_s``) and compile it (``compile_s``)."""
    from pcg_mpi_solver_tpu.parallel import make_mesh
    from pcg_mpi_solver_tpu.solver import Solver

    t0 = time.perf_counter()
    s = Solver(generated.model(F), solver_config(cfg, overrides),
               mesh=make_mesh(devices=devices), n_parts=len(devices),
               backend=cfg["solver"]["backend"])
    jax.block_until_ready(jax.tree_util.tree_leaves(s.data))
    t1 = time.perf_counter()
    s.warmup()
    return s, t1 - t0, time.perf_counter() - t1


def _recoveries(s) -> dict:
    return {k: v for k, v in s.recorder.counters.items()
            if k.startswith("resilience.recovery.")}


def solve_once(jax, s):
    """One cold solve through the public API: (record, answer)."""
    before = _recoveries(s)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("reset"):
        s.reset_state()
    with jax.profiler.TraceAnnotation("dispatch"):
        r = s.step(1.0)
    with jax.profiler.TraceAnnotation("fetch"):
        u = s.displacement_global()
    fired = {k: v - before.get(k, 0) for k, v in _recoveries(s).items()
             if v != before.get(k, 0)}
    return {"flag": int(r.flag), "relres": float(r.relres),
            "iters": int(r.iters), "wall_s": time.perf_counter() - t0,
            "recoveries": fired}, u


def check_answers(problem, answers, limit):
    """The largest reference residual over every ``(F, u)`` answer
    (identical answers are computed once)."""
    worst, seen = 0.0, []
    for F, u in answers:
        for G, v, rr in seen:
            if np.array_equal(u, v) and np.array_equal(F, G):
                break
        else:
            rr = reference.relres(problem, F, u)
            seen.append((F, u, rr))
        worst = max(worst, rr) if np.isfinite(rr) else float("inf")
    return {"ref_relres": {"value": worst, "limit": limit}}, len(seen)


def run(cell_name: str, seed: int, seconds: float, traced: bool,
        t_start: float, rehearsal: bool = False, keep_trace: str = None,
        spec: Spec = None):
    """Returns (result line, whether every compared number is within its
    limit).  ``t_start`` is the process's start on the host clock."""
    import jax

    spec = spec or Spec()
    cell = spec.cell(cell_name)
    devices = devices_for(jax, int(cell["chips"]), rehearsal)
    init_s = time.perf_counter() - t_start
    compiles = []

    def on_event(name, dur, **kw):
        if name.endswith("backend_compile_duration"):
            compiles.append(name)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        return _run(jax, spec, cell_name, cell, seed, seconds, traced,
                    t_start, init_s, rehearsal, keep_trace, devices,
                    compiles)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


def _run(jax, spec, cell_name, cell, seed, seconds, traced, t_start,
         init_s, rehearsal, keep_trace, devices, compiles):
    cfg = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    kind = spec.traffic_kind(mix["kind"])
    chips = int(cell["chips"])

    t0 = time.perf_counter()
    sizes = cfg["rehearsal_sizes" if rehearsal else "sizes"]
    generated = spec.generator(cfg["generator"]).make(
        cfg["model"], sizes[str(chips)])
    problem = generated.problem
    plan = kind.plan(problem, mix, seed)
    model_s = time.perf_counter() - t0
    s, build_s, compile_s = build(jax, cfg, generated, plan.F, devices)
    t1 = time.perf_counter()
    kind.warm(jax, s, plan)
    warm_s = time.perf_counter() - t1
    setup_s = time.perf_counter() - t_start

    tol = float(cfg["solver"]["tol"])
    solves, answers, traced_solves = [], [], []
    n_compiles = len(compiles)
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    w0 = time.perf_counter()
    if traced:
        # the trace covers the window's first whole request
        jax.profiler.start_trace(tdir)
        try:
            with jax.profiler.TraceAnnotation("window"):
                traced_solves, ans = kind.request(jax, s, plan)
        finally:
            jax.profiler.stop_trace()
        solves += traced_solves
        answers += ans
    while not solves or time.perf_counter() - w0 < seconds:
        recs, ans = kind.request(jax, s, plan)
        solves += recs
        answers += ans
    window_s = time.perf_counter() - w0
    window_compiles = len(compiles) - n_compiles

    stats = [d.memory_stats() or {} for d in devices]
    peaks = [st.get("peak_bytes_in_use") for st in stats]
    mem_peak = max(peaks) if all(p is not None for p in peaks) else None
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem_peak}
    del s
    gc.collect()

    t_trace = time.perf_counter()
    red = None
    if traced:
        try:
            ops, spans = trace.read(tdir)
            red = trace.reduce(ops, spans)
            if keep_trace:
                shutil.copytree(tdir, keep_trace, dirs_exist_ok=True)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]

    failed = sum(1 for r in solves if r["flag"] != 0 or not r["relres"] <= tol
                 or r["recoveries"])
    try:
        peak = roofline.peaks(device["kind"])
    except KeyError:
        if not rehearsal:
            raise
        peak = None
    facts = {
        "chips": chips, "solves": solves, "window_s": window_s,
        "traced_solves": traced_solves,
        "setup": {"total_s": setup_s, "init_s": init_s, "model_s": model_s,
                  "build_s": build_s, "compile_s": compile_s,
                  "warm_s": warm_s},
        "memory_peak_bytes": mem_peak, "trace": red, "peak": peak,
        "n_dof": problem.n_dof, "type_sizes": problem.type_sizes(),
    }
    metrics = {}
    for entry, read in spec.metrics(cell_name, traced):
        v = read(facts)
        if v is not None:
            metrics[entry["name"]] = {"value": v, "unit": entry["unit"]}

    t_ref = time.perf_counter()
    compared, n_distinct = check_answers(problem, answers, tol)
    print(f"timing: set-up {setup_s:.1f} s, window {window_s:.1f} s, "
          f"trace read {t_ref - t_trace:.1f} s, reference "
          f"{time.perf_counter() - t_ref:.1f} s", file=sys.stderr)
    ok = all(c["value"] <= c["limit"] for c in compared.values())
    result = {
        "correct": bool(ok and device["platform"] == "tpu" and not rehearsal),
        "attempted": len(solves),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if traced:
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["solves"] = {
        "iters": sorted({r["iters"] for r in solves}),
        "flags": sorted({r["flag"] for r in solves}),
        "relres_max": max(r["relres"] for r in solves),
        "wall_s": [min(r["wall_s"] for r in solves),
                   max(r["wall_s"] for r in solves)]}
    result["window_compiles"] = window_compiles
    result["distinct_answers"] = n_distinct
    # set-up by phase in every run, untraced ones too: the end-to-end
    # set-up is noisy, and this says which phase the noise is in
    result["setup_phases_s"] = facts["setup"]
    if rehearsal:
        result["rehearsal"] = True
    result["compared"] = compared
    if window_compiles:
        print(f"warning: {window_compiles} compile(s) inside the window",
              file=sys.stderr)
    return result, ok
