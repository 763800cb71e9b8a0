"""Finds what a cell names: its configuration, its traffic mix, its generator
and the readers of its per-layer metrics. Everything is looked up by the name
in BENCHMARK.json, so a later PR adds a cell by adding files and entries.
"""
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


def load_module(path, name):
    """Import one file of the benchmark by path (metric readers have dots in
    their names, so they cannot be imported by name)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path):
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of `workloads` with everything it names, resolved."""

    def __init__(self, name, root=ROOT, rehearse=False):
        self.root = root
        self.bench = read_json(os.path.join(root, "BENCHMARK.json"))
        rows = [w for w in self.bench["workloads"] if w["name"] == name]
        if not rows:
            raise SystemExit("no workload %r in BENCHMARK.json (have: %s)" % (
                name, ", ".join(w["name"] for w in self.bench["workloads"])))
        self.row = rows[0]
        self.name = name
        self.chips = int(self.row["chips"])
        cfg_row = [c for c in self.bench["configs"]
                   if c["name"] == self.row["config"]][0]
        self.config_dir = os.path.dirname(os.path.join(root, cfg_row["file"]))
        self.config = read_json(os.path.join(root, cfg_row["file"]))
        self.traffic = read_json(os.path.join(
            root, "benchmark", "traffic", self.row["traffic"] + ".json"))
        if rehearse:
            self.config.update(self.config.get("rehearsal", {}))
            self.traffic.update(self.traffic.get("rehearsal", {}))
        limits = read_json(os.path.join(root, "benchmark", "cells",
                                        name + ".json"))
        self.limits = limits["limits"]
        if rehearse:    # the tiny sizes have noise of their own
            self.limits = limits.get("rehearsal", {}).get("limits", self.limits)

    def config_module(self, stem):
        """`program`, `reference` or `flops` of the cell's configuration."""
        return load_module(os.path.join(self.config_dir, stem + ".py"),
                           "benchmark_config_%s_%s" % (
                               self.row["config"].replace("-", "_").replace(".", "_"), stem))

    def generator(self):
        kind = self.traffic["kind"].replace("-", "_")
        return load_module(os.path.join(self.root, "benchmark", "generators",
                                        kind + ".py"),
                           "benchmark_generator_" + kind)

    def _reports(self, metric):
        if "workloads" in metric:
            return self.name in metric["workloads"]
        return True

    def end_to_end(self):
        return [m for m in self.bench["end_to_end"] if self._reports(m)]

    def per_layer(self):
        """Per-layer metrics due in this cell: those that list it, and those
        without a list whose end-to-end metric this cell reports."""
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if m["moves"] in mine and self._reports(m)]

    def reader(self, metric_name):
        path = os.path.join(self.root, "benchmark", "metrics",
                            metric_name + ".py")
        return load_module(path, "benchmark_metric_" +
                           metric_name.replace(".", "_").replace("-", "_"))
