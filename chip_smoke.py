"""Smoke run of the solve path on the chip, through the public API.

    python chip_smoke.py               # one TPU chip: phases (a) and (b)
    python chip_smoke.py --chips 4     # four chips vs one: sharded parity
    python chip_smoke.py --cpu-rehearsal [--chips 4]   # toy sizes, CPU

Phases on one chip, all in this one process:

(a) flagship cube, 150^3 cells (10,328,853 dofs), mixed precision,
    Jacobi, classic PCG, structured backend;
(b) graded octree (bench generator arguments, max_level=4) on the hybrid
    backend, cut from n0=12 to n0=8 (704,355 dofs): see OCTREE_N0.

Each phase checks flag 0, relres <= tol, an independent f64 host residual
with the numpy reference operator (<= 10 tol), the 1-D bar-estimate
physics window, and that no recovery or redispatch fired.  One JSON
object per phase goes to stdout; the last line is the verdict.

``--chips 4`` runs only the sharded path and what it is compared with:
the 152x150x150 cube on the structured slab backend (ppermute halo) and
the phase (b) octree on the hybrid backend (interface psum), each on four
devices and on one, checking iteration parity (+-1), solution agreement
(< 50 tol) and that the sharded state really spans four devices.

Off a TPU the script prints no result and exits 2.  ``--cpu-rehearsal``
runs the same code at toy sizes on the CPU and ends with "ok": false.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

TOL = 1e-7
E, NU, LOAD = 30e9, 0.2, 1e6
# Octree base cells, cut from the issue's n0=12 (1,635,108 dofs) to n0=8
# (704,355 dofs) by compile time.  The octree's programs compile in
# ~65 s each on one v5e at n0=8 (276.8 s for the four) and ~2x that at
# n0=12 (chipless); --chips 4 compiles this octree for one device and
# for four in one call, which at n0=12 outgrows the chip budget the
# builder had.  The one n0=12 run on the chip (v6 then engaged, and
# wrong) took 652 s to compile and 422 s to solve.
OCTREE_N0 = 8


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def device_info(jax, count: int) -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": count}


def memory(devices) -> list:
    """(bytes_in_use, peak_bytes_in_use) per device, where reported."""
    out = []
    for d in devices:
        st = d.memory_stats() or {}
        out.append([st.get("bytes_in_use"), st.get("peak_bytes_in_use")])
    return out


def cube_model(nx, ny, nz):
    from pcg_mpi_solver_tpu.models import make_cube_model

    m = make_cube_model(nx, ny, nz, E=E, nu=NU, load="traction",
                        load_value=LOAD, heterogeneous=True)
    # nodal force LOAD on each of the (ny+1)(nz+1) loaded-face nodes
    sigma = LOAD * (ny + 1) * (nz + 1) / (ny * nz)
    return m, sigma * nx / E


def octree_model(n0):
    from pcg_mpi_solver_tpu.models.octree import make_octree_model

    # the generator arguments of bench.py's octree cell
    m = make_octree_model(nx0=n0, ny0=n0, nz0=n0, max_level=4, n_incl=6,
                          seed=2, E=E, nu=NU, load="traction",
                          load_value=LOAD)
    return m, LOAD * n0 / E


def build(jax, model, devices, backend, n_parts):
    """Solver construction through the README's entry points.
    Returns (solver, set-up seconds)."""
    from pcg_mpi_solver_tpu import RunConfig, SolverConfig
    from pcg_mpi_solver_tpu.parallel import make_mesh
    from pcg_mpi_solver_tpu.solver import Solver

    cfg = RunConfig(solver=SolverConfig(
        tol=TOL, precision_mode="mixed", precond="jacobi",
        pcg_variant="classic"))
    t0 = time.perf_counter()
    s = Solver(model, cfg, mesh=make_mesh(devices=devices),
               n_parts=n_parts, backend=backend)
    jax.block_until_ready(jax.tree_util.tree_leaves(s.data))
    return s, time.perf_counter() - t0


def warmup(s) -> float:
    """Compile every program of the solve; returns its seconds."""
    t0 = time.perf_counter()
    s.warmup()
    return time.perf_counter() - t0


def run(s, model, n_devices, setup_s, compile_s):
    """Solve once (the step fetches its scalars).  Returns (record, u)."""
    t0 = time.perf_counter()
    res = s.solve()[0]
    solve_s = time.perf_counter() - t0
    u = s.displacement_global()
    rec = {
        "n_dof": int(model.n_dof), "backend": s.backend,
        "n_parts": int(s.pm.n_parts), "n_devices": n_devices,
        "flag": int(res.flag), "iters": int(res.iters),
        "relres": float(res.relres),
        "setup_s": setup_s, "compile_s": compile_s, "solve_s": solve_s,
        "recoveries": {k: v for k, v in s.recorder.counters.items()
                       if k.startswith("resilience.recovery.") and v},
    }
    return rec, u


def solve(jax, model, devices, backend, n_parts):
    """Build, compile and solve.  Returns (record, solver, u_global)."""
    s, setup_s = build(jax, model, devices, backend, n_parts)
    rec, u = run(s, model, len(devices), setup_s, warmup(s))
    return rec, s, u


def host_checks(model, u, bar, rec) -> list:
    """Independent f64 residual with the numpy reference operator, and
    the bar-estimate physics window.  Returns the failed checks."""
    import numpy as np

    from pcg_mpi_solver_tpu.solver.numpy_ref import NumpyRefSolver

    ref = NumpyRefSolver(model)
    eff = model.dof_eff
    f = (model.F - ref.matvec(model.Ud))[eff]
    r = (model.F - ref.matvec(u))[eff]
    rec["host_relres"] = float(np.linalg.norm(r) / np.linalg.norm(f))
    tip = float(np.asarray(u)[0::3].max())
    rec["tip_over_bar"] = tip / bar
    fails = []
    if rec["flag"] != 0:
        fails.append(f"flag {rec['flag']}")
    if not rec["relres"] <= TOL:
        fails.append(f"relres {rec['relres']:.3e} > {TOL}")
    if not rec["host_relres"] <= 10 * TOL:
        fails.append(f"host residual {rec['host_relres']:.3e} > {10 * TOL}")
    # heterogeneous stiff inclusions spread the 1-D bar estimate ~2x
    if not 1 / 3 <= rec["tip_over_bar"] <= 3:
        fails.append(f"tip/bar {rec['tip_over_bar']:.3f} outside [1/3, 3]")
    if rec["recoveries"]:
        fails.append(f"recovery fired: {rec['recoveries']}")
    return fails


def phase(jax, name, build, devices, backend, n_parts, reduced=None):
    t0 = time.perf_counter()
    model, bar = build()
    build_s = time.perf_counter() - t0
    rec, s, u = solve(jax, model, devices, backend, n_parts)
    rec["model_build_s"] = build_s
    t0 = time.perf_counter()
    fails = host_checks(model, u, bar, rec)
    rec["host_check_s"] = time.perf_counter() - t0
    rec["memory"] = memory(devices)
    if reduced:
        rec["reduced"] = reduced
    emit({"phase": name, **rec, "failed": fails})
    del s
    gc.collect()
    return fails


def shard_bytes(s, devices) -> list:
    """Bytes of the solver's own device arrays (data and solution) held
    on each device: whether its state really spans the mesh, apart from
    what other solvers of this process keep on device 0."""
    import jax

    per = {d: 0 for d in devices}
    for leaf in jax.tree_util.tree_leaves((s.data, s.un)):
        for sh in getattr(leaf, "addressable_shards", ()):
            if sh.device in per:
                per[sh.device] += sh.data.nbytes
    return [per[d] for d in devices]


def sharded(jax, legs, devices) -> list:
    """Each leg on all devices against one: parity, agreement, spread.

    Every solver is built first, then all compile concurrently in
    threads of this process, and each leg solves as soon as its two
    compiles are done: compiling is host work, and the one-device
    octree's (~4 min cold) would otherwise be paid after everything
    else.  compile_s is each solver's own (overlapped) wall; solve_s
    may share the host with compiles still running."""
    import threading

    import numpy as np

    n = len(devices)
    solvers = {}
    for name, model, backend in legs:
        for tag, devs in (("n", devices), ("1", devices[:1])):
            solvers[name, tag] = build(jax, model, devs, backend,
                                       len(devs))
    compile_s, errors = {}, {}

    def compile_one(key):
        try:
            compile_s[key] = warmup(solvers[key][0])
        except BaseException as e:                      # noqa: BLE001
            errors[key] = e

    threads = {k: threading.Thread(target=compile_one, args=(k,))
               for k in solvers}
    for t in threads.values():
        t.start()

    fails = []
    for name, model, backend in legs:
        threads[name, "n"].join()
        threads[name, "1"].join()
        if errors:
            for t in threads.values():
                t.join()
            raise next(iter(errors.values()))
        out = {}
        for tag, devs in (("n", devices), ("1", devices[:1])):
            s, setup_s = solvers.pop((name, tag))
            out[tag] = run(s, model, len(devs), setup_s,
                           compile_s[name, tag])
            if tag == "n":
                spread = sorted(str(d) for d in s.un.sharding.device_set)
                held = shard_bytes(s, devices)
            del s
            gc.collect()
        (rec_n, u_n), (rec_1, u_1) = out["n"], out["1"]
        err = float(np.abs(u_n - u_1).max() / np.abs(u_1).max())
        f = [f"{k}: flag {r['flag']}" for k, r in (("n", rec_n), ("1", rec_1))
             if r["flag"] != 0]
        f += [f"{k}: recovery fired {r['recoveries']}"
              for k, r in (("n", rec_n), ("1", rec_1)) if r["recoveries"]]
        if abs(rec_n["iters"] - rec_1["iters"]) > 1:
            f.append(f"iters {rec_n['iters']} on {n} vs {rec_1['iters']} "
                     f"on 1 device")
        if not err < 50 * TOL:
            f.append(f"max rel solution diff {err:.3e} >= {50 * TOL}")
        if len(spread) != n:
            f.append(f"solution on {len(spread)} device(s), not {n}")
        if min(held) < 0.25 * max(held):
            f.append(f"solver state not spread: {held} bytes per device")
        emit({"phase": name, "sharded": rec_n, "one_device": rec_1,
              "max_rel_diff": err, "device_set": spread,
              "solver_bytes": held, "memory": memory(devices),
              "failed": f})
        fails += f
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="toy sizes on the CPU; never reports ok")
    args = ap.parse_args(argv)

    import jax

    if args.cpu_rehearsal:
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke: no TPU (JAX platform {platform!r}); "
              "no result", file=sys.stderr)
        return 2
    devices = jax.devices()[:args.chips]
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} devices requested, "
              f"{len(devices)} present", file=sys.stderr)
        return 2

    from pcg_mpi_solver_tpu import native

    # never trust a library copied with the tree: rebuild from source
    built = native.build(force=True)
    emit({"device": device_info(jax, len(devices)),
          "native_built": built, "native_partitioning": native.available()})

    toy = args.cpu_rehearsal
    cube_n, oct_n0 = (12, 4) if toy else (150, OCTREE_N0)
    fails = []
    if args.chips == 1:
        fails += phase(jax, "a_cube",
                       lambda: cube_model(cube_n, cube_n, cube_n),
                       devices, "auto", 1)
        fails += phase(jax, "b_octree", lambda: octree_model(oct_n0),
                       devices, "hybrid", 1,
                       reduced=f"octree n0 12 -> {oct_n0}: compile time")
    else:
        # slab decomposition needs nx % n_devices == 0: 150 -> 152
        cube, _ = cube_model(-(-cube_n // 4) * 4, cube_n, cube_n)
        octree, _ = octree_model(oct_n0)
        fails += sharded(jax, [("cube_slabs", cube, "structured"),
                               ("octree_hybrid", octree, "hybrid")],
                         devices)

    emit({"ok": not fails and platform == "tpu",
          "device": device_info(jax, len(devices))})
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
