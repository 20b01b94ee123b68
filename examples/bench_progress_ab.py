"""A/B the mixed-mode progress-rate inner exit
(SolverConfig.mixed_progress_window) at a given cube size: window 150
(the round-4 design value) vs 0 (off — the default since the negative
96^3 measurement).

The knob's design target was the f32 inner-cycle grind at the 10.33M-dof
flagship (~670 iterations of sub-linear residual progress before the
cycle tolerance); the default once went ON with zero measurements at any
scale where the exit fires.  This script measured exactly that: at 64^3
the exit never fires (bit-identical); at 96^3 it fires and COSTS +24%
total iterations — hence the default flip.  Kept for the true-flagship
hardware A/B.

Usage: python examples/bench_progress_ab.py [nx] [--window W]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_one(model, window):
    import jax

    from pcg_mpi_solver_tpu import RunConfig, SolverConfig
    from pcg_mpi_solver_tpu.parallel import make_mesh
    from pcg_mpi_solver_tpu.solver import Solver

    cfg = RunConfig(solver=SolverConfig(
        tol=1e-7, max_iter=20000, precision_mode="mixed",
        mixed_progress_window=window))
    s = Solver(model, cfg, mesh=make_mesh(1), n_parts=1)
    r0 = s.step(1.0)                    # warm (compile)
    s.reset_state()
    t0 = time.perf_counter()
    r = s.step(1.0)
    wall = time.perf_counter() - t0
    del s
    return dict(flag=int(r.flag), iters=int(r.iters),
                relres=float(r.relres), wall_s=round(wall, 2),
                warm_iters=int(r0.iters))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("nx", nargs="?", type=int, default=64)
    ap.add_argument("--window", type=int, default=150,
                    help="ON-arm window (default 150, the round-4 design "
                         "value — NOT the SolverConfig default, which is "
                         "0/off since the negative 96^3 A/B)")
    ap.add_argument("--tpu", action="store_true",
                    help="run on the accelerator (default: pin the CPU; "
                         "iteration counts do not depend on the platform)")
    args = ap.parse_args()

    import jax

    if not args.tpu:
        # iteration STRUCTURE (counts/cycles) is platform-independent;
        # the pin must land before the first device touch
        jax.config.update("jax_platforms", "cpu")
    print("# running on", jax.devices()[0].platform, flush=True)

    from pcg_mpi_solver_tpu.bench import cached_model

    n = args.nx
    model = cached_model("cube", nx=n, ny=n, nz=n, E=30e9, nu=0.2,
                         load="traction", load_value=1e6,
                         heterogeneous=True)
    print(f"# model {model.n_dof} dofs ({n}^3)", flush=True)
    for label, window in (("progress_on", args.window), ("progress_off", 0)):
        res = run_one(model, window)
        print(f"{label} (window={window}): {res}", flush=True)


if __name__ == "__main__":
    main()
