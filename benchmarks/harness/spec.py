"""Where everything is found: BENCHMARK.json names cells, configurations,
traffic mixes and metrics; each is a file of its own that this module
finds by that name. Nothing here names a cell, a query or a metric."""

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_module(kind, name):
    """benchmarks/<kind>/<name>.py as a module (gen, reference, queries,
    layer_metrics)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Query:
    """One file pair under queries/: <name>.json (entry, generator,
    reference, the columns read, the limits of the comparison) and the
    text: <name>.sql, or <name>.py with plan() and collect()."""

    def __init__(self, name):
        self.name = name
        self.meta = _json(os.path.join(BENCH_DIR, "queries", name + ".json"))
        self.entry = self.meta["entry"]
        self.reads = self.meta["reads"]
        self.limits = self.meta["limits"]
        if self.entry == "sql":
            with open(os.path.join(BENCH_DIR, "queries", name + ".sql")) as f:
                self.text = f.read()
        elif self.entry == "pandas_api":
            self.module = load_module("queries", name)
        else:
            raise ValueError(f"query {name}: unknown entry {self.entry!r}")

    def reference(self):
        return load_module("reference", self.meta["reference"])


class Cell:
    """One entry of BENCHMARK.json's `workloads` with its files."""

    def __init__(self, name, bench=None):
        self.bench = bench or _json(os.path.join(ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                             f"it has {sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        conf = {c["name"]: c for c in self.bench["configs"]}
        self.config = _json(os.path.join(
            ROOT, conf[self.entry["config"]]["file"]))
        self.traffic = _json(os.path.join(
            BENCH_DIR, "traffic", self.entry["traffic"] + ".json"))
        self.queries = {q["query"]: Query(q["query"])
                        for q in self.traffic["queries"]}
        gens = {q.meta["generator"] for q in self.queries.values()}
        if gens != {self.config["generator"]}:
            raise SystemExit(f"cell {name}: queries want generators {gens}, "
                             f"its configuration has "
                             f"{self.config['generator']!r}")
        self.generator = load_module("gen", self.config["generator"])

    def _reported(self, metric):
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self):
        return [m for m in self.bench["end_to_end"] if self._reported(m)]

    def per_layer(self):
        return [m for m in self.bench["per_layer"] if self._reported(m)]
