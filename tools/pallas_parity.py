"""On-chip parity of the Pallas structured matvec against the XLA path.

    python tools/pallas_parity.py [--shapes 8,8,8:150,150,150] [--variant 6]
    JAX_PLATFORMS=cpu python tools/pallas_parity.py --interpret   # rehearsal
    TPU_LOG_DIR=disabled JAX_PLATFORMS=cpu python tools/pallas_parity.py \
        --chipless          # compile every kernel for a described v5e only

For each cube shape: one heterogeneous slab, one random f32 vector, the
kernel's product against StructuredOps.matvec_local (XLA gse, f32,
HIGHEST) and an f64 numpy product.  Before the shapes it checks the Mosaic
primitives the v6 kernel is built from (lane roll, unaligned lane slice,
f32 dot, slab DMA into a lane-sliced buffer) against their jnp meaning, so
a wrong kernel is traced to the op that differs.  One JSON line per check.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def _rel(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-300))


_CHIPLESS = None     # sharding on a described v5e when --chipless


def run(fn, *args):
    """fn(*args) — or, chipless, only its compile for the described v5e
    (returns None)."""
    import jax

    if _CHIPLESS is None:
        return fn(*args)
    jax.jit(fn).lower(*[jax.ShapeDtypeStruct(a.shape, a.dtype,
                                             sharding=_CHIPLESS)
                        for a in args]).compile()
    return None


def primitives(interpret: bool) -> None:
    """Each primitive's result on the device against its jnp meaning
    (chipless: only that each compiles)."""
    def emit(rec):
        globals()["emit"]({"check": rec["check"], "compiled": True}
                          if _CHIPLESS is not None else rec)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, 512)), jnp.float32)

    def call(kernel, out_shape, *args):
        # traced with x64 off, as the kernels are (_planes_env): Mosaic
        # takes only i32 indices and shifts
        def f(*a):
            with jax.enable_x64(False):
                return pl.pallas_call(
                    kernel,
                    out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
                    interpret=interpret)(*a)
        out = run(f, *args)
        return np.zeros(out_shape, np.float32) if out is None else out

    for shift in (1, 151, 152):
        def k(x_ref, o_ref, shift=shift):
            o_ref[...] = pltpu.roll(x_ref[...], shift, 1)
        y = np.asarray(call(k, (3, 512), x))
        emit({"check": "roll", "shift": shift,
              "eq_jnp_roll": bool(np.array_equal(y, np.roll(x, shift, 1))),
              "eq_reverse": bool(np.array_equal(y, np.roll(x, -shift, 1)))})

    for off in (1, 151, 152):
        def k(x_ref, o_ref, off=off):
            v = x_ref[...]
            o_ref[...] = v[:, off:off + 256]
        y = np.asarray(call(k, (3, 256), x))
        emit({"check": "lane_slice", "off": off,
              "eq": bool(np.array_equal(y, np.asarray(x)[:, off:off + 256]))})

    a = jnp.asarray(rng.normal(size=(3, 24)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(24, 512)), jnp.float32)
    ref = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    for prec in (None, jax.lax.Precision.HIGHEST):
        def k(a_ref, b_ref, o_ref, prec=prec):
            o_ref[...] = jax.lax.dot_general(
                a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
                precision=prec, preferred_element_type=jnp.float32)
        y = np.asarray(call(k, (3, 512), a, b))
        emit({"check": "dot", "precision": str(prec), "rel_err": _rel(y, ref)})

    # slab DMA (8-aligned planes, full lanes) into a wider lane-sliced
    # buffer, as v6's for_chunk does
    xs = jnp.asarray(rng.normal(size=(3, 24, 256)), jnp.float32)

    def k(x_hbm, o_ref, buf, sem):
        buf[...] = jnp.zeros_like(buf)
        cp = pltpu.make_async_copy(
            x_hbm.at[:, pl.ds(jnp.asarray(8, jnp.int32), 16), :],
            buf.at[:, :, pl.ds(jnp.asarray(0, jnp.int32), 256)], sem)
        cp.start()
        cp.wait()
        o_ref[...] = buf[...]
    def f(a):
        with jax.enable_x64(False):
            return pl.pallas_call(
                k, out_shape=jax.ShapeDtypeStruct((3, 16, 384), jnp.float32),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                scratch_shapes=[pltpu.VMEM((3, 16, 384), jnp.float32),
                                pltpu.SemaphoreType.DMA],
                interpret=interpret)(a)
    y = run(f, xs)
    y = np.zeros((3, 16, 384), np.float32) if y is None else np.asarray(y)
    want = np.zeros((3, 16, 384), np.float32)
    want[:, :, :256] = np.asarray(xs)[:, 8:24]
    emit({"check": "slab_dma", "eq": bool(np.array_equal(y, want))})


def shape_parity(shape, variant: str, interpret: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pcg_mpi_solver_tpu.models import make_cube_model
    from pcg_mpi_solver_tpu.ops import pallas_matvec as pm
    from pcg_mpi_solver_tpu.parallel.structured import (
        StructuredOps, device_data_structured, partition_structured)

    nx, ny, nz = shape
    model = make_cube_model(nx, ny, nz, heterogeneous=True, seed=11)
    sp = partition_structured(model, 1)
    data = device_data_structured(sp, jnp.float32)
    ops = StructuredOps.from_partition(sp, dot_dtype=jnp.float32)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(1, sp.n_loc)), jnp.float32)
    blk = data["blocks"][0]
    ck = ops._ck_grid(blk["ck"])[0]             # (nx, ny, nz) cell grid
    xg = x.reshape(3, nx + 1, ny + 1, nz + 1)

    y_xla = np.asarray(jax.jit(ops.matvec_local)(data, x))[0]
    os.environ["PCG_TPU_PALLAS_V"] = variant
    fn = jax.jit(functools.partial(pm.selected_variant()[1],
                                   interpret=interpret))
    t0 = time.perf_counter()
    if _CHIPLESS is not None:
        run(fn, xg, ck, blk["Ke"])
        return {"check": "matvec_compile", "variant": variant,
                "shape": list(shape),
                "compile_s": time.perf_counter() - t0}
    comp = fn.lower(xg, ck, blk["Ke"]).compile()
    compile_s = time.perf_counter() - t0
    y = np.asarray(comp(xg, ck, blk["Ke"])).reshape(-1)

    # f64 numpy reference of the same product (independent of both)
    from pcg_mpi_solver_tpu.solver.numpy_ref import NumpyRefSolver

    gid = np.asarray(sp.dof_gid[0])            # local -> global dof
    xs = np.zeros(model.n_dof)
    xs[gid] = np.asarray(x[0], np.float64)
    y64 = NumpyRefSolver(model).matvec(xs)[gid]

    err = np.abs(y - y_xla).reshape(3, nx + 1, ny + 1, nz + 1)
    scale = float(np.abs(y_xla).max())
    bad = err > 1e-3 * scale
    rec = {"check": "matvec", "variant": variant, "shape": list(shape),
           "compile_s": compile_s,
           "rel_err_vs_xla": _rel(y, y_xla),
           "xla_rel_err_vs_f64": _rel(y_xla, y64),
           "pallas_rel_err_vs_f64": _rel(y, y64),
           "nan": int(np.isnan(y).sum()), "zero_frac": float((y == 0).mean()),
           "bad_frac": float(bad.mean())}
    if bad.any():
        idx = np.argwhere(bad)
        rec["bad_planes_x"] = sorted(set(int(i) for i in idx[:, 1]))[:40]
        rec["bad_y"] = sorted(set(int(i) for i in idx[:, 2]))[:20]
        rec["bad_z"] = sorted(set(int(i) for i in idx[:, 3]))[:20]
        rec["bad_comp"] = sorted(set(int(i) for i in idx[:, 0]))
        rec["err_by_plane"] = [float(e) / scale
                               for e in err.max(axis=(0, 2, 3))[:24]]
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shapes", default="8,8,8:16,12,10:24,24,24:150,150,150")
    ap.add_argument("--variant", default="6")
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--no-primitives", action="store_true")
    ap.add_argument("--chipless", action="store_true")
    args = ap.parse_args()

    import jax

    if args.chipless:
        global _CHIPLESS
        from aot_compile_check import _topo_sharding

        _CHIPLESS = _topo_sharding()
        jax.config.update("jax_platforms", "cpu")

    jax.config.update("jax_enable_x64", True)
    emit({"device": jax.devices()[0].device_kind,
          "platform": jax.devices()[0].platform})
    if not args.no_primitives:
        primitives(args.interpret)
    for s in args.shapes.split(":"):
        for v in args.variant.split(","):
            emit(shape_parity(tuple(int(t) for t in s.split(",")), v,
                              args.interpret))
    return 0


if __name__ == "__main__":
    sys.exit(main())
