"""Readings that set a cell's limit: the program's, and its control's.

    python benchmarks/control.py --workload cube.single --seeds 1,2,3 \
        --precision control

For each seed, builds the cell at its own size through the same path as
run.py, makes one request of the cell's traffic and prints the compared number (the
reference residual of the answer) as one JSON line.  ``--precision
program`` runs the configuration as stated; ``--precision control``
runs it with the configuration's ``control`` overrides: the program's
own float32 path, one precision below the float64-grade answer the
configuration states.  The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--precision", choices=("program", "control"),
                    required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    import jax

    from harness import cell
    from harness.spec import Spec

    spec = Spec()
    w = spec.cell(args.workload)
    cfg = spec.config(w["config"])
    chips = int(w["chips"])
    try:
        devices = cell.devices_for(jax, chips, rehearsal=False)
    except cell.NoChip as e:
        print(f"control: {e}; no result", file=sys.stderr)
        return 3
    gen = spec.generator(cfg["generator"]).make(cfg["model"],
                                                cfg["sizes"][str(chips)])
    overrides = cfg["control"] if args.precision == "control" else None
    limit = float(cfg["solver"]["tol"])
    mix = spec.traffic(w["traffic"])
    kind = spec.traffic_kind(mix["kind"])
    for seed in (int(s) for s in args.seeds.split(",")):
        plan = kind.plan(gen.problem, mix, seed)
        s, build_s, compile_s = cell.build(jax, cfg, gen, plan.F, devices,
                                           overrides)
        recs, answers = kind.request(jax, s, plan)
        rec = recs[0]
        del s
        t0 = time.perf_counter()
        compared, _ = cell.check_answers(gen.problem, answers, limit)
        print(json.dumps({
            "workload": args.workload, "precision": args.precision,
            "seed": seed, "ref_relres": compared["ref_relres"]["value"],
            "limit": limit, "flag": rec["flag"], "iters": rec["iters"],
            "program_relres": rec["relres"], "solve_s": rec["wall_s"],
            "build_s": build_s, "compile_s": compile_s,
            "reference_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
