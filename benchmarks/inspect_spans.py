"""Read a kept trace's program spans, matvec stages and solve scopes.

    python3 benchmarks/run.py --workload cube.single --seed 7 \
        --seconds 10 --trace 1 --keep-trace DIR > result.json
    python3 benchmarks/inspect_spans.py DIR --result result.json

Prints one JSON object: the stage and scope split of the traced device
time with the ops that take most of each part, the program spans with
their self and idle time, the idle gaps named by the innermost span,
the program runs in the window, and the per-layer readings of
``harness/spans.metrics``.  The traced request's
inner iterations and solves come from the run's result line (its last
line), or from ``--iters`` and ``--solves``.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import spans, trace  # noqa: E402


def top_ops(ops, sp, n=8) -> dict:
    """The ``n`` ops that take most device time (s, mean over devices)
    in each stage of the matvec and each scope outside the phases."""
    (w0, w1), = [(s, e) for name, s, e, _ in sp if name == "window"]
    parts = collections.defaultdict(collections.Counter)
    for dev_ops in ops.values():
        for s, e, name, ph, prog, stage, scope in dev_ops:
            s, e = max(s, w0), min(e, w1)
            if e <= s or trace.base_name(name) in trace.CONTAINERS:
                continue
            if ph == "matvec":
                parts[f"mv/{stage or 'unstaged'}"][f"{prog}/{name}"] += e - s
            elif ph is None:
                parts[f"solve/{scope or 'none'}"][f"{prog}/{name}"] += e - s
    return {part: [[k, v / len(ops) / 1e9] for k, v in c.most_common(n)]
            for part, c in sorted(parts.items())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("dir")
    ap.add_argument("--result", help="the run's output; its last line is "
                                     "the result")
    ap.add_argument("--iters", type=int, help="inner iterations traced")
    ap.add_argument("--solves", type=int, default=1, help="solves traced")
    args = ap.parse_args(argv)
    iters = args.iters
    if args.result:
        with open(args.result, encoding="utf-8") as f:
            line = json.loads(f.read().strip().splitlines()[-1])
        (iters,) = line["solves"]["iters"]
    ops, sp, launches = spans.read(args.dir)
    red = spans.reduce(ops, sp, launches)
    keys = ("window_s", "busy_s", "phase_s", "other_s", "matvec_stage_s",
            "solve_scope_s", "spans", "host_idle_s", "programs",
            "idle_gaps")
    out = {k: red[k] for k in keys}
    out["top_ops"] = top_ops(ops, sp)
    out["iters"], out["solves"] = iters, args.solves
    if iters:
        out["metrics"] = spans.metrics(red, iters, args.solves)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
