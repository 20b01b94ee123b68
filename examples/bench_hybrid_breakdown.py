"""Per-matvec cost breakdown of the HYBRID (octree) backend: isolates
the per-level row gathers, the block stencils, and the row scatters that
make up matvec_local, so the octree flagship's bottleneck is attributable
on real hardware (RUNBOOK on-hardware checklist, octree leg).

Usage: python examples/bench_hybrid_breakdown.py [n0 [level [n_incl]]]
(default 22 4 6 — the 5.67M-dof flagship; use 10 3 6 for a quick run)
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from pcg_mpi_solver_tpu.bench import cached_model
from pcg_mpi_solver_tpu.parallel.hybrid import (
    HybridOps, device_data_hybrid, partition_hybrid)



def _sync(y):
    float(jnp.asarray(jax.tree.leaves(y)[0]).ravel()[0])


def timeit(f, *args, reps=10):
    y = f(*args)
    _sync(y)
    t0 = time.perf_counter()
    for _ in range(reps):
        y = f(*args)
    _sync(y)
    return (time.perf_counter() - t0) / reps * 1e3


def main():
    n0 = int(sys.argv[1]) if len(sys.argv) > 1 else 22
    level = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    incl = int(sys.argv[3]) if len(sys.argv) > 3 else 6
    t0 = time.perf_counter()
    model = cached_model("octree", nx0=n0, ny0=n0, nz0=n0, max_level=level,
                         n_incl=incl, seed=2, E=30e9, nu=0.2,
                         load="traction", load_value=1e6)
    print(f"# model {model.n_dof} dofs / {model.n_elem} elems "
          f"(gen {time.perf_counter()-t0:.1f}s)", flush=True)
    t0 = time.perf_counter()
    hp = partition_hybrid(model, 1)
    ops = HybridOps.from_hybrid(hp, dot_dtype=jnp.float32)
    data = device_data_hybrid(hp, jnp.float32)
    print(f"# partition {time.perf_counter()-t0:.1f}s; levels: "
          + ", ".join(f"s={lv.size} nb={lv.nb} {lv.bx}x{lv.by}x{lv.bz}"
                      for lv in hp.levels), flush=True)

    rng = np.random.default_rng(0)
    x = jax.device_put(
        jnp.asarray(rng.normal(size=(1, hp.pm.n_loc)), jnp.float32))

    t_mv = timeit(jax.jit(lambda d, xx: ops.matvec_local(d, xx)), data, x)
    print(f"matvec_local (all):    {t_mv:8.3f} ms", flush=True)

    # per-level pieces (jitted separately — sums can exceed the fused
    # whole; the split still attributes the dominant cost)
    for i, dims in enumerate(ops.level_dims):
        lv = data["levels"][i]

        def g_fn(d, xx, i=i, dims=dims):
            return ops._level_gather(ops._rows_pad(xx), d["levels"][i],
                                     dims, 1)

        jg = jax.jit(g_fn)
        t_g = timeit(jg, data, x)
        xg = jg(data, x)

        def s_fn(d, xg_, i=i, dims=dims):
            ck = d["levels"][i]["ck"]
            ck = ck.reshape((dims[0],) + ck.shape[2:])
            return ops._stencil(d["brick_Ke"], ck, xg_)

        js = jax.jit(s_fn)
        t_s = timeit(js, data, xg)
        yg = js(data, xg)
        del xg     # free this level's lattice batch before the next

        def sc_fn(d, yg_, i=i, dims=dims):
            y0 = jnp.zeros((1, ops.n_loc), yg_.dtype)
            return ops._level_scatter_add(y0, yg_, d["levels"][i], dims, 1)

        t_sc = timeit(jax.jit(sc_fn), data, yg)
        del yg
        nrows = int(np.prod(lv["nidx"].shape))
        print(f"level {i} (nb={dims[0]} {dims[1]}x{dims[2]}x{dims[3]}, "
              f"{nrows/1e6:.2f}M rows): gather {t_g:7.3f}  stencil "
              f"{t_s:7.3f}  scatter {t_sc:7.3f} ms", flush=True)


if __name__ == "__main__":
    main()
