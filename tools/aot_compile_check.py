"""CHIPLESS TPU compile checks: run the full Mosaic/XLA v5e compile
locally, with no device.

``jax.experimental.topologies`` + the locally installed libtpu give the
chip's own compile pipeline for a described (not attached) v5e — so
Pallas lowering rejections and XLA buffer-assignment failures reproduce
here instead of costing chip time.  tests/test_tpu_compile.py keeps the
main path's kernels compiled this way in the test suite.

Usage:
    python tools/aot_compile_check.py kernel [--variants 6,7] [--nx 150]
    python tools/aot_compile_check.py f64matvec [--nx 150]

``kernel``    — Pallas matvec variants at small + given shape.
``f64matvec`` — the XLA chunked f64 matvec at the given shape (the
                remote-compile failure mode of the f64-direct anchor).
``pcg``       — the FULL f64 PCG while_loop program at the given shape.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _topo_sharding():
    import numpy as np
    import jax
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices)[:1], ("x",))
    return NamedSharding(mesh, PartitionSpec())


def _compile_structs(fn, args, label):
    """Lower+compile against prebuilt ShapeDtypeStruct pytrees."""
    import jax

    t0 = time.perf_counter()
    try:
        jax.jit(fn).lower(*args).compile()
    except Exception as e:                              # noqa: BLE001
        msg = " ".join(str(e).split())[:400]
        print(f"{label}: FAIL {type(e).__name__}: {msg}", flush=True)
        return False
    print(f"{label}: OK ({time.perf_counter()-t0:.1f}s)", flush=True)
    return True


def _compile(fn, shapes_dtypes, sharding, label):
    import jax

    return _compile_structs(
        fn, [jax.ShapeDtypeStruct(s, d, sharding=sharding)
             for s, d in shapes_dtypes], label)


def check_kernel(args):
    import jax.numpy as jnp

    from pcg_mpi_solver_tpu.ops import pallas_matvec as pm

    s = _topo_sharding()
    nx = args.nx
    ok = True
    for v in [int(x) for x in args.variants.split(",")]:
        fn = getattr(pm, "structured_matvec_pallas_v%d" % v
                     if v > 1 else "structured_matvec_pallas")
        for dims in [(8, 6, 5), (nx, nx, nx)]:
            nxn = tuple(d + 1 for d in dims)
            ok &= _compile(
                lambda xg, ck, Ke, f=fn: f(xg, ck, Ke),
                [((3,) + nxn, jnp.float32),
                 (dims, jnp.float32), ((24, 24), jnp.float32)],
                s, f"v{v} {dims}")
    return ok


def check_f64matvec(args):
    import jax.numpy as jnp

    from pcg_mpi_solver_tpu.models import make_cube_model
    from pcg_mpi_solver_tpu.parallel.structured import (
        StructuredOps, partition_structured)

    s = _topo_sharding()
    n = args.nx
    # tiny model just to build ops with the right dims; the compile input
    # shapes are what matter, and they depend only on (nx, ny, nz)
    model = make_cube_model(4, 4, 4)
    sp = partition_structured(model, 1)
    import dataclasses

    ops = dataclasses.replace(
        StructuredOps.from_partition(sp, dot_dtype=jnp.float64),
        nxc=n, ny=n, nz=n)
    nn = n + 1

    def fn(xg_flat, ck, Ke, diag_ke):
        data = {"blocks": [{"ck": ck, "Ke": Ke, "diag_Ke": diag_ke}]}
        return ops.matvec_local(data, xg_flat)

    return _compile(
        fn,
        [((1, 3 * nn * nn * nn), jnp.float64),
         ((1, n, n * nn - 1), jnp.float64), ((24, 24), jnp.float64),
         ((24,), jnp.float64)],
        s, f"f64 chunked matvec {n}^3")


def _hybrid_setup(args):
    """Shared setup for the hybrid checks: topology sharding, flagship
    octree partition (cached model), ops + f32 data structs."""
    import jax
    import jax.numpy as jnp

    # topology FIRST (needs libtpu), THEN pin the CPU backend so the
    # conversions below never take a device
    s = _topo_sharding()
    jax.config.update("jax_platforms", "cpu")

    from pcg_mpi_solver_tpu.bench import cached_model
    from pcg_mpi_solver_tpu.parallel.hybrid import (
        HybridOps, device_data_hybrid, partition_hybrid)

    n0 = args.nx if args.nx is not None else 22   # flagship octree
    model = cached_model("octree", nx0=n0, ny0=n0, nz0=n0,
                         max_level=4, n_incl=6, seed=2, E=30e9, nu=0.2,
                         load="traction", load_value=1e6)
    t0 = time.perf_counter()
    hp = partition_hybrid(model, 1)
    ops = HybridOps.from_hybrid(hp, dot_dtype=jnp.float64,
                                use_pallas=args.pallas == "on")
    data = device_data_hybrid(hp, jnp.float32)
    print(f"# octree {model.n_dof} dofs, {len(hp.levels)} levels "
          f"(partition {time.perf_counter()-t0:.0f}s)", flush=True)
    structs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), data)
    return s, hp, ops, structs, n0


def check_hybridcycle(args):
    """Compile the CHUNKED inner-cycle program — the program the bench
    actually compiles at flagship scale (hybrid force-engages the
    chunked path; solver/chunked.py _inner_cycle): warm resumable pcg,
    ONE stencil instantiation in the loop body after the round-4
    restructure."""
    import jax
    import jax.numpy as jnp

    from pcg_mpi_solver_tpu.solver.pcg import cold_carry, pcg

    s, hp, ops, structs, n0 = _hybrid_setup(args)
    n_loc = ops.n_loc

    def fn(data, rhat32, prec32, tol_cycle, carry32, budget):
        res, carry2 = pcg(
            ops, data, rhat32, carry32["x"], prec32,
            tol=tol_cycle, max_iter=jnp.minimum(500, budget),
            glob_n_dof_eff=n_loc, max_iter_nominal=20000,
            carry_in=carry32, return_carry=True, progress_window=0)
        return res.x, carry2, res.flag

    vec = jax.ShapeDtypeStruct((1, n_loc), jnp.float32, sharding=s)
    carry = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        cold_carry(jnp.zeros((1, n_loc), jnp.float32),
                   jnp.zeros((1, n_loc), jnp.float32),
                   jnp.asarray(1.0, ops.dot_dtype), ops.dot_dtype))
    scal32 = jax.ShapeDtypeStruct((), jnp.float32, sharding=s)
    bud = jax.ShapeDtypeStruct((), jnp.int32, sharding=s)
    return _compile_structs(
        fn, [structs, vec, vec, scal32, carry, bud],
        f"hybrid CHUNKED inner-cycle octree {n0}^3/L4")


def check_hybridamul64(args):
    """Compile the shared out-of-loop f64 hybrid matvec program (driver
    _amul64_fn) — the ONE f64 stencil instantiation the chunked driver
    now pays (was 3: lifting + r0 in _start, plus _refine)."""
    import jax
    import jax.numpy as jnp

    from pcg_mpi_solver_tpu.parallel.hybrid import device_data_hybrid

    s, hp, ops64, _structs32, n0 = _hybrid_setup(args)
    data64 = device_data_hybrid(hp, jnp.float64)
    structs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        data64)
    n_loc = ops64.n_loc

    def fn(data, v):
        return data["eff"] * ops64.matvec(data, v)

    vec = jax.ShapeDtypeStruct((1, n_loc), jnp.float64, sharding=s)
    return _compile_structs(fn, [structs, vec],
                            f"hybrid f64 amul octree {n0}^3/L4")


def check_bktamul64(args):
    """Compile the BUCKETED f64 amul at the flagship octree partition
    (PCG_TPU_HYBRID_F64_REFRESH=bucketed): same operator as genamul64
    but with the 200+ per-type structures stacked into a few padded
    buckets — the compile-cost hypothesis is that cost tracks structure
    count (general 1343 s vs stencil 999 s)."""
    import jax
    import jax.numpy as jnp

    s = _topo_sharding()
    jax.config.update("jax_platforms", "cpu")

    from pcg_mpi_solver_tpu.bench import cached_model
    from pcg_mpi_solver_tpu.ops.matvec import (
        Ops, bucketed_matvec, build_bucketed_blocks, device_data)
    from pcg_mpi_solver_tpu.parallel.partition import partition_model

    n0 = args.nx if args.nx is not None else 22
    model = cached_model("octree", nx0=n0, ny0=n0, nz0=n0,
                         max_level=4, n_incl=6, seed=2, E=30e9, nu=0.2,
                         load="traction", load_value=1e6)
    t0 = time.perf_counter()
    pm = partition_model(model, 1)
    ops = Ops.from_model(pm, dot_dtype=jnp.float64)
    data = device_data(pm, jnp.float64, blocks=False)
    data["buckets"] = build_bucketed_blocks(pm, jnp.float64)
    print(f"# octree {model.n_dof} dofs, {len(pm.type_blocks)} types -> "
          f"{len(data['buckets'])} buckets of T="
          f"{[b['Ke'].shape[0] for b in data['buckets']]} "
          f"(partition {time.perf_counter()-t0:.0f}s)", flush=True)
    structs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), data)

    def fn(data, v):
        return data["eff"] * bucketed_matvec(ops, data, v)

    vec = jax.ShapeDtypeStruct((1, pm.n_loc), jnp.float64, sharding=s)
    return _compile_structs(fn, [structs, vec],
                            f"BUCKETED f64 amul octree {n0}^3/L4")


def check_genamul64(args):
    """Compile the GENERAL-form f64 amul at the flagship octree partition
    (PCG_TPU_HYBRID_F64_REFRESH=general, driver _amul64g) — the
    compile-cost alternative to the 999 s stencil amul above.  Same
    elem_part/numbering as the hybrid partition."""
    import jax
    import jax.numpy as jnp

    s = _topo_sharding()
    jax.config.update("jax_platforms", "cpu")

    from pcg_mpi_solver_tpu.bench import cached_model
    from pcg_mpi_solver_tpu.ops.matvec import Ops, device_data
    from pcg_mpi_solver_tpu.parallel.partition import partition_model

    n0 = args.nx if args.nx is not None else 22
    model = cached_model("octree", nx0=n0, ny0=n0, nz0=n0,
                         max_level=4, n_incl=6, seed=2, E=30e9, nu=0.2,
                         load="traction", load_value=1e6)
    t0 = time.perf_counter()
    pm = partition_model(model, 1)
    ops = Ops.from_model(pm, dot_dtype=jnp.float64)
    data = device_data(pm, jnp.float64)
    print(f"# octree {model.n_dof} dofs, {len(pm.type_blocks)} type "
          f"blocks (partition {time.perf_counter()-t0:.0f}s)", flush=True)
    structs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), data)

    def fn(data, v):
        return data["eff"] * ops.matvec(data, v)

    vec = jax.ShapeDtypeStruct((1, pm.n_loc), jnp.float64, sharding=s)
    return _compile_structs(fn, [structs, vec],
                            f"GENERAL f64 amul octree {n0}^3/L4")


def check_cubecycle(args):
    """Chunked inner-cycle program for the STRUCTURED (cube) flagship —
    the program bench.py compiles at 150^3 (10.33M dofs > 4M engages the
    chunked path): warm resumable pcg over the slab stencil.  With
    ``--dtype float32 --pallas on`` this is the v6-FUSED chunked cycle,
    which has never been compiled anywhere (round 3 verified the fused
    ONE-SHOT program only)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from pcg_mpi_solver_tpu.models import make_cube_model
    from pcg_mpi_solver_tpu.parallel.structured import (
        StructuredOps, partition_structured)
    from pcg_mpi_solver_tpu.solver.pcg import cold_carry, pcg

    # topology FIRST, then pin the CPU backend: the cold_carry template
    # below materializes REAL arrays, which must not take a device
    s = _topo_sharding()
    jax.config.update("jax_platforms", "cpu")
    n = args.nx
    dt = jnp.dtype(args.dtype)
    model = make_cube_model(4, 4, 4)
    sp = partition_structured(model, 1)
    ops = dataclasses.replace(
        StructuredOps.from_partition(sp, dot_dtype=jnp.float64,
                                     use_pallas=args.pallas == "on"),
        nxc=n, ny=n, nz=n)
    nn = n + 1
    n_loc = 3 * nn * nn * nn

    def fn(x, ck, Ke, diag_ke, eff, weight, fext, inv_diag, carry, budget):
        data = {"blocks": [{"ck": ck, "Ke": Ke, "diag_Ke": diag_ke}],
                "eff": eff, "weight": weight}
        res, c2 = pcg(ops, data, fext=fext, x0=carry["x"],
                      inv_diag=inv_diag,
                      tol=1e-5, max_iter=jnp.minimum(500, budget),
                      glob_n_dof_eff=n_loc, max_iter_nominal=20000,
                      carry_in=carry, return_carry=True,
                      progress_window=0)
        return res.x, c2, res.flag

    sds = lambda shape, d: jax.ShapeDtypeStruct(shape, d, sharding=s)
    carry = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        cold_carry(jnp.zeros((1, n_loc), dt), jnp.zeros((1, n_loc), dt),
                   jnp.asarray(1.0, ops.dot_dtype), ops.dot_dtype))
    shapes = [sds((1, n_loc), dt), sds((1, n, n * nn - 1), dt),
              sds((24, 24), dt), sds((24,), dt), sds((1, n_loc), dt),
              sds((1, n_loc), dt), sds((1, n_loc), dt), sds((1, n_loc), dt),
              carry, sds((), jnp.int32)]
    label = (f"{args.dtype} CHUNKED cycle"
             + (" +pallas" if args.pallas == "on" else "") + f" {n}^3")
    return _compile_structs(fn, shapes, label)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=["kernel", "f64matvec", "pcg",
                                     "hybridpcg", "hybridcycle",
                                     "hybridamul64", "genamul64",
                                     "bktamul64", "cubecycle"])
    ap.add_argument("--variants", default="6,7")
    ap.add_argument("--nx", type=int, default=None,
                    help="cells per edge (default: 150; hybridpcg: 22 "
                         "octree base cells)")
    ap.add_argument("--dtype", default="float64",
                    help="f64matvec/pcg input dtype")
    ap.add_argument("--pallas", default="off", choices=["off", "on"],
                    help="pcg mode: engage the fused Pallas matvec")
    args = ap.parse_args()
    if args.what in ("pcg", "cubecycle") and args.pallas == "on" \
            and args.dtype != "float32":
        # the pallas dispatch is f32-gated (structured.matvec_local);
        # with f64 inputs the flag would silently validate the XLA path
        ap.error("--pallas on requires --dtype float32")
    if args.nx is None and args.what not in ("hybridpcg", "hybridcycle",
                                             "hybridamul64", "genamul64",
                                             "bktamul64"):
        args.nx = 150
    # the topology API needs libtpu, which JAX_PLATFORMS=cpu would hide;
    # each check pins the CPU itself before it makes any array
    os.environ.pop("JAX_PLATFORMS", None)
    if args.what in ("f64matvec", "pcg", "hybridpcg", "hybridcycle",
                     "hybridamul64", "genamul64", "bktamul64", "cubecycle"):
        # without x64, the float64 ShapeDtypeStructs canonicalize to f32
        # and the chunked-path gate (dtype == float64) never engages —
        # the check would silently validate a different program
        import jax

        jax.config.update("jax_enable_x64", True)
    ok = {"kernel": check_kernel, "f64matvec": check_f64matvec,
          "pcg": check_pcg, "hybridpcg": check_hybridpcg,
          "hybridcycle": check_hybridcycle,
          "hybridamul64": check_hybridamul64,
          "genamul64": check_genamul64,
          "bktamul64": check_bktamul64,
          "cubecycle": check_cubecycle}[args.what](args)
    sys.exit(0 if ok else 1)




def check_pcg(args):
    """Compile the FULL PCG while_loop program (matvec + fused dots +
    preconditioner + convergence control) at the given size — the actual
    program whose REMOTE compile failed UNAVAILABLE at 150^3/128^3 f64
    in waves 2-3.  With --dtype float32 --pallas on this is the HEADLINE
    mixed-mode inner program with the fused v6 kernel engaged."""
    import jax.numpy as jnp

    from pcg_mpi_solver_tpu.models import make_cube_model
    from pcg_mpi_solver_tpu.parallel.structured import (
        StructuredOps, partition_structured)
    from pcg_mpi_solver_tpu.solver.pcg import pcg

    s = _topo_sharding()
    n = args.nx
    dt = jnp.dtype(args.dtype)
    model = make_cube_model(4, 4, 4)
    sp = partition_structured(model, 1)
    import dataclasses

    ops = dataclasses.replace(
        StructuredOps.from_partition(sp, dot_dtype=jnp.float64,
                                     use_pallas=args.pallas == "on"),
        nxc=n, ny=n, nz=n)
    nn = n + 1
    n_loc = 3 * nn * nn * nn

    def fn(x, ck, Ke, diag_ke, eff, weight, fext, inv_diag):
        data = {"blocks": [{"ck": ck, "Ke": Ke, "diag_Ke": diag_ke}],
                "eff": eff, "weight": weight}
        r = pcg(ops, data, fext=fext, x0=x, inv_diag=inv_diag,
                tol=1e-7, max_iter=2000, glob_n_dof_eff=n_loc)
        return r.x, r.flag, r.relres, r.iters

    shapes = [((1, n_loc), dt), ((1, n, n * nn - 1), dt), ((24, 24), dt),
              ((24,), dt), ((1, n_loc), dt), ((1, n_loc), dt),
              ((1, n_loc), dt), ((1, n_loc), dt)]
    label = (f"{args.dtype} PCG program"
             + (" +pallas" if args.pallas == "on" else "") + f" {n}^3")
    return _compile(fn, shapes, s, label)




def check_hybridpcg(args):
    """Compile the hybrid (octree) f32 PCG program at a REAL graded-octree
    flagship partition — the program whose REMOTE compile failed
    UNAVAILABLE in wave 1 (then under the scatter combine; the gather
    combine is now default).  Builds the real partition (cached model),
    converts the device-data pytree to ShapeDtypeStructs, compiles
    chiplessly."""
    import jax
    import jax.numpy as jnp

    # topology FIRST (needs libtpu), THEN pin the CPU backend so the
    # numpy->jnp conversions below never take a device; lowering uses
    # the topology shardings only
    s = _topo_sharding()
    jax.config.update("jax_platforms", "cpu")

    from pcg_mpi_solver_tpu.bench import cached_model
    from pcg_mpi_solver_tpu.parallel.hybrid import (
        HybridOps, device_data_hybrid, partition_hybrid)
    from pcg_mpi_solver_tpu.solver.pcg import pcg

    n0 = args.nx if args.nx is not None else 22   # flagship octree
    model = cached_model("octree", nx0=n0, ny0=n0, nz0=n0,
                         max_level=4, n_incl=6, seed=2, E=30e9, nu=0.2,
                         load="traction", load_value=1e6)
    t0 = time.perf_counter()
    hp = partition_hybrid(model, 1)
    ops = HybridOps.from_hybrid(hp, dot_dtype=jnp.float64,
                                use_pallas=args.pallas == "on")
    data = device_data_hybrid(hp, jnp.float32)
    print(f"# octree {model.n_dof} dofs, {len(hp.levels)} levels "
          f"(partition {time.perf_counter()-t0:.0f}s)", flush=True)

    structs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), data)
    n_loc = ops.n_loc

    def fn(data, fext, x0, inv_diag):
        r = pcg(ops, data, fext=fext, x0=x0, inv_diag=inv_diag,
                tol=1e-7, max_iter=2000, glob_n_dof_eff=n_loc)
        return r.x, r.flag, r.relres, r.iters

    vec = jax.ShapeDtypeStruct((1, n_loc), jnp.float32, sharding=s)
    label = (f"hybrid f32 PCG octree {n0}^3/L4"
             + (" +pallas" if args.pallas == "on" else ""))
    return _compile_structs(fn, [structs, vec, vec, vec], label)


if __name__ == "__main__":
    main()
