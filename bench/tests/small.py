"""A copy of the benchmark at a size a CPU test run can hold.

`small_root(tmp)` copies `BENCHMARK.json` and `bench/` into `tmp`, links
the program's `src/`, and shrinks every configuration: fewer rows and
features, 20 nonzeros a row, and weights ten times larger so that a
solve of the cell's own rounds converges as far at this size as the
full size does.  The cells, their rounds, gaps and limits are the
committed ones.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

SMALL = {"rows": 2400, "features": 3000, "nnz_per_row": 20,
         "lam1": 1e-3, "lam2": 1e-4, "eta": 1 / (2 * (0.25 + 1e-3))}


def small_root(tmp) -> Path:
    root = Path(tmp) / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    (root / "src").symlink_to(REPO / "src")
    for path in (root / "bench" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg.update(SMALL)
        path.write_text(json.dumps(cfg))
    return root


def last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])
