"""Benchmark of the PCG solver on the chip: one run of one cell.

    python3 benchmarks/run.py --workload cube.single --seed 7 \
        --seconds 10 --trace 0

Builds the cell's configuration (``BENCHMARK.json``), sends the requests
of its traffic, drawn from ``--seed``, back to back for ``--seconds``
seconds, checks every answer against the benchmark's own f64 reference
operator, and
prints one JSON result as the last line of standard output.  With
``--trace 1`` the window runs under the JAX profiler and the result
carries the per-layer metrics and a breakdown instead of the end-to-end
metrics.

Off a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 3.  ``--cpu-rehearsal`` runs the same path on the CPU
at the configuration's rehearsal sizes; such a run never reports
``correct: true``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# the compile cache lives at a fixed path inside the checkout, so only
# a cell's first run there compiles
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="rehearsal sizes on the CPU; never reports correct")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="also copy the raw trace to DIR, to read by hand")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if args.cpu_rehearsal:
        from harness.spec import Spec

        chips = int(Spec().cell(args.workload)["chips"])
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "xla_force_host_platform_device_count" not in os.environ.get(
                "XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={chips}").strip()
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
        os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)

    from harness import cell

    try:
        result, _ = cell.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), T_START,
                             rehearsal=args.cpu_rehearsal,
                             keep_trace=args.keep_trace)
    except cell.NoChip as e:
        print(f"benchmark: {e}; no result", file=sys.stderr)
        return 3
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
