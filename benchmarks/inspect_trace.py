"""Print the structure of a profiler trace, to read it by hand.

    python benchmarks/inspect_trace.py DIR [--events N]

DIR holds one ``.xplane.pb`` (``run.py --keep-trace DIR`` leaves one).
For each plane and line: its name and event count, then the first N
events with their own stats and their metadata's stats (bytes shown by
length), and how many events name a ``pcg/*`` scope in either.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import xplane  # noqa: E402


def _show(d: dict) -> dict:
    return {k: (f"<{len(v)} bytes>" if isinstance(v, bytes) else str(v)[:300])
            for k, v in d.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("dir")
    ap.add_argument("--events", type=int, default=8)
    args = ap.parse_args(argv)
    (path,) = glob.glob(os.path.join(args.dir, "**", "*.xplane.pb"),
                        recursive=True)
    for p in xplane.planes(path):
        print(f"PLANE {p.name!r} event_metadata={len(p.meta)}")
        if not p.lines():
            for mid in list(p.meta)[:args.events]:
                print(f"  META {mid} {p.event_name(mid)[:200]!r} "
                      f"{_show(p.metadata_stats(mid))}")
        for name in p.lines():
            n = scoped = 0
            shown = []
            for _, s, e, mid, ev in p.events(name):
                n += 1
                text = p.event_name(mid) + " " + " ".join(
                    str(v) for v in p.metadata_stats(mid).values())
                scoped += "pcg/" in text
                if len(shown) < args.events:
                    shown.append({"name": p.event_name(mid)[:200],
                                  "start_ns": s, "dur_ns": e - s,
                                  "stats": _show(p.event_stats(ev)),
                                  "metadata": _show(p.metadata_stats(mid))})
            print(f"  LINE {name!r} events={n} pcg_scoped={scoped}")
            for d in shown:
                print("    " + json.dumps(d))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
