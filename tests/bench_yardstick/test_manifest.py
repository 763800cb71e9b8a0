"""A later PR adds a configuration, a traffic mix, a per-layer metric and a
cell as new files and entries only: the loader finds each by its name."""
import json
import os
import shutil

import pytest

from benchmark import manifest

ROOT = manifest.ROOT


@pytest.fixture()
def grown(tmp_path):
    """A copy of the benchmark with one of each added, nothing edited."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    bench = manifest.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cfg_dir = root / "benchmark" / "configs" / "newnet"
    cfg_dir.mkdir()
    (cfg_dir / "config.json").write_text(json.dumps({"hidden_size": 8}))
    (cfg_dir / "flops.py").write_text("def train_flops_per_item(cfg, traffic):\n    return 42\n")
    (root / "benchmark" / "traffic" / "new-mix.json").write_text(json.dumps(
        {"kind": "fit-device-batch", "batch_per_chip": 2, "rehearsal": {"batch_per_chip": 1}}))
    (root / "benchmark" / "metrics" / "new_share.py").write_text(
        "def read(facts):\n    return facts.get('new')\n")
    (root / "benchmark" / "cells" / "newnet-fit.json").write_text(
        json.dumps({"limits": {"loss_step1": 0.5}}))
    bench["configs"].append({"name": "newnet", "source": "a paper",
                             "file": "benchmark/configs/newnet/config.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "newnet-fit", "config": "newnet",
                               "traffic": "new-mix", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_throughput":
            m["workloads"].append("newnet-fit")
    bench["per_layer"].append({"name": "new_share", "unit": "%", "better": "higher",
                               "source": "program_counter", "layer": "input",
                               "moves": "train_throughput",
                               "workloads": ["newnet-fit"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def test_a_new_cell_is_found_by_name(grown):
    cell = manifest.Cell("newnet-fit", root=grown)
    assert cell.config == {"hidden_size": 8} and cell.chips == 1
    assert cell.traffic["batch_per_chip"] == 2
    assert cell.limits == {"loss_step1": 0.5}
    assert cell.config_module("flops").train_flops_per_item({}, {}) == 42
    assert cell.generator().__name__.endswith("fit_device_batch")
    assert [m["name"] for m in cell.end_to_end()] == ["train_throughput", "setup_s"]
    due = [m["name"] for m in cell.per_layer()]
    # its own metric, and those without a list that move what it reports
    assert "new_share" in due and "compile_s" in due and "window_compiles" in due
    assert "flash_fwd_roofline" not in due and "ttft_p50_ms" not in due
    assert cell.reader("new_share").read({"new": 7}) == 7
    assert cell.reader("new_share").read({}) is None
    assert manifest.Cell("newnet-fit", root=grown, rehearse=True).traffic["batch_per_chip"] == 1


def test_the_cells_that_were_there_are_untouched(grown):
    for w in manifest.read_json(os.path.join(ROOT, "BENCHMARK.json"))["workloads"]:
        a, b = manifest.Cell(w["name"]), manifest.Cell(w["name"], root=grown)
        assert a.config == b.config and a.traffic == b.traffic
        assert [m["name"] for m in a.per_layer()] == [m["name"] for m in b.per_layer()]
        assert "new_share" not in [m["name"] for m in b.per_layer()]


def _every_cell_resolves():
    bench = manifest.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    for w in bench["workloads"]:
        cell = manifest.Cell(w["name"])
        e2e = [m["name"] for m in cell.end_to_end()]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer() and cell.limits
        for m in cell.per_layer():
            assert callable(cell.reader(m["name"]).read)
            assert m["moves"] in e2e
        assert callable(cell.generator().run)
        for stem in ("program", "reference", "flops"):
            cell.config_module(stem)
    return [w["name"] for w in bench["workloads"]]


def test_every_cell_resolves_and_reports_what_the_contract_asks():
    assert "opt-1.3b-serve-chat" not in _every_cell_resolves()
    with pytest.raises(SystemExit):
        manifest.Cell("no-such-cell")
    with pytest.raises(SystemExit):
        manifest.Cell("opt-1.3b-serve-chat")


def test_the_parked_serving_cell_resolves_as_an_entry_would(parked):
    assert "opt-1.3b-serve-chat" in _every_cell_resolves()


def test_a_parked_reader_with_nothing_to_read_returns_nothing(parked):
    test_a_reader_with_nothing_to_read_returns_nothing()


def test_a_reader_with_nothing_to_read_returns_nothing():
    bench = manifest.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = manifest.Cell(bench["workloads"][0]["name"])
    for m in bench["per_layer"]:
        assert cell.reader(m["name"]).read({}) is None, m["name"]
