"""Everything the harness runs is found by name, from data.

- a cell: its entry in ``BENCHMARK.json`` (configuration, traffic, chips);
- a configuration: the file its ``configs`` entry names, whose
  ``generator`` names ``generators/<generator>.py``;
- a traffic mix: ``traffic/<name>.json``, whose ``kind`` names
  ``traffic/<kind>.py`` (harness/traffic.py);
- a metric, end-to-end or per-layer: ``metrics/<name>.py``, whose
  ``read(run)`` returns the value or None.

Adding any of them takes new files and an entry in ``BENCHMARK.json``;
no file of the harness changes.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {name} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{name}".replace(".", "_").replace("/", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Spec:
    def __init__(self, root: str = ROOT, bench_dir: str = BENCH_DIR):
        self.root, self.bench_dir = root, bench_dir
        with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
            self.bench = json.load(f)

    def cell(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        known = [w["name"] for w in self.bench["workloads"]]
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {known})")

    def config(self, name: str) -> dict:
        for c in self.bench["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"]),
                          encoding="utf-8") as f:
                    return json.load(f)
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        path = os.path.join(self.bench_dir, "traffic", f"{name}.json")
        with open(path, encoding="utf-8") as f:
            return json.load(f)

    def traffic_kind(self, kind: str):
        return _load_module(
            os.path.join(self.bench_dir, "traffic", f"{kind}.py"),
            f"traffic.{kind}")

    def generator(self, name: str):
        return _load_module(
            os.path.join(self.bench_dir, "generators", f"{name}.py"),
            f"generator.{name}")

    def metrics(self, cell: str, traced: bool) -> list:
        """(entry, read) of every metric the cell reports in this mode:
        end-to-end metrics untraced, per-layer metrics traced.  A metric
        without ``workloads`` goes to every cell that reports the
        end-to-end metric it moves."""
        e2e = [m for m in self.bench["end_to_end"]
               if cell in m.get("workloads", [cell])]
        entries = e2e
        if traced:
            names = {m["name"] for m in e2e}
            entries = [m for m in self.bench["per_layer"]
                       if (cell in m["workloads"] if "workloads" in m
                           else m["moves"] in names)]
        return [(m, self.reader(m["name"])) for m in entries]

    def reader(self, name: str):
        return _load_module(
            os.path.join(self.bench_dir, "metrics", f"{name}.py"),
            f"metric.{name}").read
