"""Roofline report: reads the cross-PR perf trails (BENCH_*.json at the
repo root) and emits summary CSV rows plus a %-of-peak markdown table.

It keys on the bench-rows/v2 schema written by
``benchmarks/run.py --json``: each row's `pct_peak` (modeled traffic
vs the measured host roofline, see repro.obs.roofline) and the file's
`host` fingerprint."""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_bench(pattern="BENCH_*.json") -> List[dict]:
    """The perf-trail snapshots at the repo root (any schema version;
    pre-v2 rows simply have no pct_peak to report)."""
    out = []
    for f in sorted(glob.glob(os.path.join(ROOT, pattern))):
        try:
            with open(f) as fh:
                doc = json.load(fh)
        except (OSError, ValueError):
            continue
        if isinstance(doc, dict) and isinstance(doc.get("rows"), list):
            doc["_file"] = os.path.basename(f)
            out.append(doc)
    return out


def _trail_summary(doc: dict) -> Dict:
    rows = doc.get("rows", [])
    annotated = [(r["name"], float(r["pct_peak"])) for r in rows
                 if isinstance(r.get("pct_peak"), (int, float))]
    host = doc.get("host", {})
    derived = (f"schema={doc.get('schema')};rows={len(rows)};"
               f"annotated={len(annotated)}")
    if annotated:
        top = max(annotated, key=lambda t: t[1])
        derived += (f";max_pct_peak={top[1] * 100:.1f}%"
                    f";max_at={top[0]}")
    if host:
        derived += (f";backend={host.get('backend', '?')}"
                    f";host={host.get('host', '?')}")
    return {"name": f"roofline/trail/{doc['_file']}",
            "us_per_call": "", "derived": derived}


def main() -> List[Dict]:
    return [_trail_summary(doc) for doc in load_bench()]


def bench_markdown_table(pattern="BENCH_*.json") -> str:
    """%-of-peak table over every annotated perf-trail row."""
    lines = [
        "| trail | row | us/call | %-peak | bound | backend | host |",
        "|---|---|---|---|---|---|---|",
    ]
    for doc in load_bench(pattern):
        host = doc.get("host", {})
        for r in doc.get("rows", []):
            pct = r.get("pct_peak")
            pct_s = (f"{pct * 100:.1f}%"
                     if isinstance(pct, (int, float)) else "—")
            lines.append(
                f"| {doc['_file']} | {r.get('name', '?')} | "
                f"{r.get('us_per_call', '')} | {pct_s} | "
                f"{r.get('roofline_bound', '—')} | "
                f"{r.get('backend', host.get('backend', '?'))} | "
                f"{r.get('host', host.get('host', '?'))} |")
    return "\n".join(lines)


if __name__ == "__main__":
    from benchmarks.common import emit
    emit(main())
    print()
    print(bench_markdown_table())
