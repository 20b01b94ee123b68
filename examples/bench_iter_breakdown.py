"""Per-iteration cost breakdown of the structured-backend PCG on the
current accelerator: isolates the matvec, the f64-accumulated weighted
dots, and a full synthetic iteration body, so regressions or wins can be
attributed (RUNBOOK "performance triage order" step 1.5 — between the
matvec microbench and the end-to-end bench).

Usage: python examples/bench_iter_breakdown.py [n]      (default 150)
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from pcg_mpi_solver_tpu.bench import cached_model
from pcg_mpi_solver_tpu.parallel.structured import (
    StructuredOps, device_data_structured, partition_structured)



def _sync(y):
    """Force a value transfer: a scalar fetch cannot return before the
    device finishes (examples/bench_matvec.py reads inline the same
    way)."""
    leaf = jax.tree.leaves(y)[0]
    float(jnp.asarray(leaf).ravel()[0])


def timeit(f, *args, reps=10):
    y = f(*args)
    _sync(y)
    t0 = time.perf_counter()
    for _ in range(reps):
        y = f(*args)
    _sync(y)
    return (time.perf_counter() - t0) / reps * 1e3


def main():
    # f64-accumulated dots are the thing being measured — enable x64
    jax.config.update("jax_enable_x64", True)
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 150
    t0 = time.perf_counter()
    model = cached_model("cube", nx=n, ny=n, nz=n, E=30e9, nu=0.2,
                         load="traction", load_value=1e6)
    print(f"# model {model.n_dof} dofs (gen {time.perf_counter()-t0:.1f}s)",
          flush=True)
    sp = partition_structured(model, 1)
    d32 = device_data_structured(sp, jnp.float32)
    ops32 = StructuredOps.from_partition(sp, dot_dtype=jnp.float32)
    ops64 = StructuredOps.from_partition(sp, dot_dtype=jnp.float64)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, sp.n_loc)),
                    jnp.float32)
    w = d32["weight"] * d32["eff"]

    mv = jax.jit(lambda d, x: ops32.matvec(d, x))
    print(f"matvec f32:        {timeit(mv, d32, x):8.3f} ms", flush=True)
    for name, ops in (("f32", ops32), ("f64", ops64)):
        dot = jax.jit(lambda w, a, b, o=ops: o.wdot(w, a, b))
        print(f"wdot {name} acc:      {timeit(dot, w, x, x):8.3f} ms",
              flush=True)
        dots3 = jax.jit(lambda w, a, b, o=ops: o.wdots(w, [(a, a), (b, b),
                                                          (a, b)]))
        print(f"fused 3-dot {name}:   {timeit(dots3, w, x, x):8.3f} ms",
              flush=True)

    def make_body(ops):
        def iter_body(d, x):
            eff = d["eff"]
            w = d["weight"] * eff
            q = eff * ops.matvec(d, x)
            rho = ops.wdot(w, x, q)
            pq = ops.wdot(w, q, q)
            s3 = ops.wdots(w, [(x, x), (q, q), (x, q)])
            ax = x + 0.5 * q
            z = eff * (q - 0.3 * x)
            return ax + z, rho + pq + s3.sum()

        return jax.jit(iter_body)

    for name, ops in (("f64", ops64), ("f32", ops32)):
        print(f"iter body ({name} dots): {timeit(make_body(ops), d32, x):8.3f} ms",
              flush=True)


if __name__ == "__main__":
    main()
