"""A configuration, a cell and a metric reader dropped into a copy of
bench/ are found by name, with no code edited; bad names are refused."""
from __future__ import annotations

import json
import shutil

import pytest

from small import REPO

import loader


@pytest.fixture
def root(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


def _add(root, spec_edit):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec_edit(spec)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def test_new_files_are_found_by_name(root):
    cfg = json.loads((root / "bench/configs/rcv1.json").read_text())
    cfg["features"] = 1000
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    (root / "bench/workloads/tiny.half.json").write_text(json.dumps(
        {"config": "tiny", "assign": {"rule": "uniform"},
         "rounds": 3, "rel_gap": 1e-2, "chips": 1,
         "limits": {"final_gap": 1e-3}}))
    (root / "bench/metrics/nnz_share.py").write_text(
        "def read(ctx):\n    return ctx['nnz'] / 2\n")

    def edit(spec):
        spec["configs"].append({"name": "tiny", "source": "a paper",
                                "file": "bench/configs/tiny.json",
                                "reduced": ["features"], "why": "test"})
        spec["workloads"].append({"name": "tiny.half", "config": "tiny",
                                  "traffic": "half", "chips": 1,
                                  "why": "test"})
        spec["per_layer"].append({"name": "nnz_share", "unit": "%",
                                  "better": "higher",
                                  "source": "program_counter",
                                  "layer": "pSCOPE solver",
                                  "moves": "time_to_gap_s",
                                  "workloads": ["tiny.half"]})
    _add(root, edit)

    bench = loader.Benchmark(root)
    cell = bench.cell("tiny.half")
    assert cell["rounds"] == 3 and cell["traffic"] == "half"
    assert bench.config(cell["config"])["features"] == 1000
    names = [m["name"] for m in bench.cell_metrics("tiny.half", "per_layer")]
    assert names == ["nnz_share"]
    assert bench.reader("nnz_share")({"nnz": 8}) == 4
    # the committed cells do not pick up the new metric
    assert "nnz_share" not in [m["name"] for m in
                               bench.cell_metrics("rcv1.uniform",
                                                  "per_layer")]


@pytest.mark.parametrize("bad", ["has space", "comma,name", "a/b", ".dot",
                                 "µs_per_step", "x" * 65])
def test_bad_metric_name_is_refused(root, bad):
    _add(root, lambda s: s["per_layer"][0].update(name=bad))
    with pytest.raises(ValueError, match="metric"):
        loader.Benchmark(root)


@pytest.mark.parametrize("bad", ["tokens per second", "µs", "",
                                 "x" * 17])
def test_bad_unit_is_refused(root, bad):
    _add(root, lambda s: s["end_to_end"][0].update(unit=bad))
    with pytest.raises(ValueError, match="unit"):
        loader.Benchmark(root)


def test_bad_cell_name_is_refused(root):
    _add(root, lambda s: s["workloads"][0].update(name="rcv1 uniform"))
    with pytest.raises(ValueError, match="cell"):
        loader.Benchmark(root)


def test_committed_cells_resolve():
    bench = loader.Benchmark(REPO)
    for w in bench.spec["workloads"]:
        cell = bench.cell(w["name"])
        assert bench.config(cell["config"])["workers"] >= 1
        assert set(cell["limits"]) == {"final_gap", "value_err"}
    for m in bench.spec["per_layer"]:
        assert callable(bench.reader(m["name"]))
