"""Matvec microbenchmark: XLA vs Pallas v1 (per-plane VPU), v2 (per-plane
MXU), and v3 (chunked double-buffered MXU, swept over chunk sizes).

Times the structured-slab matvec formulations in isolation on the current
default device.  Usage: python examples/bench_matvec.py [nx [ny [nz]]]
"""

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from pcg_mpi_solver_tpu.bench import cached_model
from pcg_mpi_solver_tpu.ops.pallas_matvec import (
    structured_matvec_pallas, structured_matvec_pallas_v2,
    structured_matvec_pallas_v3, structured_matvec_pallas_v4)
from pcg_mpi_solver_tpu.parallel.structured import (
    StructuredOps, device_data_structured, partition_structured)



def timeit(fn, *args, n=20):
    y = fn(*args)
    jax.block_until_ready(y)
    float(jnp.asarray(y).ravel()[0])     # scalar fetch: device sync
    t0 = time.perf_counter()
    for _ in range(n):
        y = fn(*args)
    float(jnp.asarray(y).ravel()[0])
    return (time.perf_counter() - t0) / n, y


def main():
    nx = int(sys.argv[1]) if len(sys.argv) > 1 else 150
    ny = int(sys.argv[2]) if len(sys.argv) > 2 else nx
    nz = int(sys.argv[3]) if len(sys.argv) > 3 else nx
    model = cached_model("cube", nx=nx, ny=ny, nz=nz,
                         heterogeneous=True)
    sp = partition_structured(model, 1)
    data = device_data_structured(sp, jnp.float32)
    ops = StructuredOps.from_partition(sp, dot_dtype=jnp.float32)
    blk = data["blocks"][0]
    ck = ops._ck_grid(blk["ck"])[0]             # (nx, ny, nz) cell grid
    print(f"{model.n_dof} dofs on {jax.devices()[0]}", flush=True)

    rng = np.random.default_rng(0)
    x = jax.device_put(
        jnp.asarray(rng.normal(size=(1, sp.n_loc)), jnp.float32))
    xg = x.reshape(1, 3, nx + 1, ny + 1, nz + 1)[0]

    # the form is pinned per-ops instance, so the A/B is explicit — an
    # inherited PCG_TPU_MATVEC_FORM cannot make this compare a form
    # against itself
    import dataclasses

    ops_gse = dataclasses.replace(ops, form="gse")
    xla = jax.jit(lambda d, xx: ops_gse.matvec_local(d, xx))
    t_xla, y0 = timeit(xla, data, x)
    print(f"xla (gse):    {t_xla*1e3:8.3f} ms/matvec", flush=True)

    # alternative XLA formulations: gsplit (gse minus the gather concat —
    # one fewer (24, cells) HBM round-trip) and corner (no (24, cells)
    # intermediates at all; scalar-FMA-bound, 0.57x on v5e in wave 2/3)
    for form in ("gsplit", "corner"):
        ops_f = dataclasses.replace(ops, form=form)
        fn = jax.jit(lambda d, xx, o=ops_f: o.matvec_local(d, xx))
        try:
            t_c, y_c = timeit(fn, data, x)
            err = float(jnp.abs(y_c - y0).max() / jnp.abs(y0).max())
            print(f"xla ({form}): {t_c*1e3:8.3f} ms/matvec  "
                  f"(vs gse {t_xla/t_c:5.2f}x, maxrelerr {err:.2e})",
                  flush=True)
        except Exception as e:                      # noqa: BLE001
            print(f"xla ({form}): FAILED {type(e).__name__}: {e}",
                  flush=True)

    variants = [("pallas v1", structured_matvec_pallas),
                ("pallas v2", structured_matvec_pallas_v2)]
    for c in (8, 16):
        variants.append((f"pallas v3 C={c}", functools.partial(
            structured_matvec_pallas_v3, planes=c)))
    # C=16 is expected to exceed the ~16 MB VMEM budget at flagship m —
    # included because its failure mode (fast alloc error) is cheap and
    # pins the ceiling; C=24 would only repeat it
    for c in (8, 16):
        variants.append((f"pallas v4 C={c}", functools.partial(
            structured_matvec_pallas_v4, planes=c)))
    from pcg_mpi_solver_tpu.ops.pallas_matvec import (
        structured_matvec_pallas_v5, structured_matvec_pallas_v6,
        structured_matvec_pallas_v7, structured_matvec_pallas_v8,
        structured_matvec_pallas_v9)
    for c in (8, 16):
        variants.append((f"pallas v5 C={c}", functools.partial(
            structured_matvec_pallas_v5, planes=c)))
    # v6 at C=16 exceeds the ~16 MB VMEM budget at flagship m (slab
    # buffers are (2,3,C+8,mt128)); only C=8 is expected to fit
    variants.append(("pallas v6 C=8", functools.partial(
        structured_matvec_pallas_v6, planes=8)))
    variants.append(("pallas v7 C=8", functools.partial(
        structured_matvec_pallas_v7, planes=8)))
    variants.append(("pallas v8 C=8", functools.partial(
        structured_matvec_pallas_v8, planes=8)))
    variants.append(("pallas v9 C=8", functools.partial(
        structured_matvec_pallas_v9, planes=8)))
    # BENCH_MATVEC_VARIANTS="v6,v8" runs only those Pallas variants:
    # v1-v5/v7 are chipless-pinned failures at flagship scale, so a
    # session should skip straight to the candidates.
    import os

    only = [v.strip() for v in
            os.environ.get("BENCH_MATVEC_VARIANTS", "").split(",")
            if v.strip()]
    if only:
        variants = [(n, f) for n, f in variants
                    if any(f"pallas {v} " in n + " " or n.endswith(v)
                           for v in only)]
    for name, fn in variants:
        try:
            t, y = timeit(fn, xg, ck, blk["Ke"])
            err = float(jnp.abs(y.reshape(-1) - y0[0]).max()
                        / jnp.abs(y0).max())
            print(f"{name}: {t*1e3:8.3f} ms/matvec  "
                  f"(vs xla {t_xla/t:5.2f}x, maxrelerr {err:.2e})",
                  flush=True)
        except Exception as e:                      # noqa: BLE001
            print(f"{name}: FAILED {type(e).__name__}: {e}", flush=True)


if __name__ == "__main__":
    main()
