"""Finds everything of a cell by the names in `BENCHMARK.json`.

A configuration is `bench/configs/<config>.json` (the path its entry
gives), a cell is `bench/workloads/<cell>.json`, a per-layer metric is
read by `bench/metrics/<metric>.py`, whose `read(ctx)` returns a number
or None.  Adding a configuration, a cell or a metric is adding files and
entries; no code here names one.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
BENCH_DIR = "bench"

_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
_UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


def check_name(name: str, what: str) -> str:
    if not isinstance(name, str) or not _NAME.fullmatch(name):
        raise ValueError(f"{what} {name!r}: a name is 1-64 of A-Z a-z 0-9 "
                         f"_ . - and starts with a letter, digit or _")
    return name


def check_unit(unit: str, what: str) -> str:
    if not isinstance(unit, str) or not _UNIT.fullmatch(unit):
        raise ValueError(f"{what} unit {unit!r}: a unit is 1-16 of A-Z a-z "
                         f"0-9 _ / % . -")
    return unit


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Benchmark:
    """`BENCHMARK.json` at `root`, with its names checked."""

    def __init__(self, root: Path = REPO):
        self.root = Path(root)
        self.spec = _read_json(self.root / "BENCHMARK.json")
        for c in self.spec["configs"]:
            check_name(c["name"], "configuration")
            for key in c.get("reduced", []):
                check_name(key, "reduced key")
        for w in self.spec["workloads"]:
            check_name(w["name"], "cell")
            check_name(w["config"], "cell configuration")
            check_name(w["traffic"], "cell traffic")
        for m in self.metrics():
            check_name(m["name"], "metric")
            check_unit(m["unit"], m["name"])

    @property
    def bench_dir(self) -> Path:
        return self.root / BENCH_DIR

    def metrics(self):
        return self.spec["end_to_end"] + self.spec["per_layer"]

    def cell(self, name: str) -> dict:
        """The cell's `BENCHMARK.json` entry merged with its own file."""
        entry = _find(self.spec["workloads"], name, "cell")
        spec = _read_json(self.bench_dir / "workloads" / f"{name}.json")
        if spec["config"] != entry["config"]:
            raise ValueError(f"cell {name}: its file names configuration "
                             f"{spec['config']!r}, BENCHMARK.json "
                             f"{entry['config']!r}")
        return {**spec, **entry}

    def config(self, name: str) -> dict:
        entry = _find(self.spec["configs"], name, "configuration")
        return _read_json(self.root / entry["file"])

    def cell_metrics(self, cell: str, kind: str):
        """The `end_to_end` or `per_layer` entries the cell reports."""
        return [m for m in self.spec[kind]
                if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        """`read(ctx)` of bench/metrics/<metric>.py."""
        path = self.bench_dir / "metrics" / f"{check_name(metric, 'metric')}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
        if spec is None or not path.is_file():
            raise FileNotFoundError(f"metric {metric}: no reader at {path}")
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read

    def peak(self, device_kind: str) -> dict:
        """The chip's peaks; a device that is not in the table is an
        error, never a default."""
        table = _read_json(self.bench_dir / "peaks.json")["devices"]
        if device_kind not in table:
            raise KeyError(f"device kind {device_kind!r} is not in "
                           f"bench/peaks.json (have {sorted(table)})")
        return table[device_kind]


def _find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")
