"""The serving cell is parked (benchmark/parked/serving.json: its entries as
BENCHMARK.json would hold them, left out of it because its size was fitted
to the program; PERF.md). The tests of its generator, bundle and comparison
read the manifest with those entries added."""
import os

import pytest

from benchmark import manifest


@pytest.fixture()
def parked(monkeypatch):
    real = manifest.read_json
    extra = real(os.path.join(manifest.HERE, "parked", "serving.json"))

    def read_json(path):
        got = real(path)
        if os.path.basename(path) == "BENCHMARK.json":
            for key in ("configs", "workloads", "end_to_end", "per_layer"):
                got[key] = got[key] + extra[key]
        return got

    monkeypatch.setattr(manifest, "read_json", read_json)
