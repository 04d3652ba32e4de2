"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only substr]
    PYTHONPATH=src python -m benchmarks.run --list-solvers
    PYTHONPATH=src python -m benchmarks.run --only lazy_inner --json

Every solver-comparison figure sweeps the `core.solvers` registry via
its single `solvers.run` entry point; `--list-solvers` prints the
registry.  Emits ``name,us_per_call,derived`` CSV (one row per
measurement).  ``--json`` additionally writes the machine-readable
perf-trail snapshots (us_per_call per row) so the perf trajectory is
diffable across PRs: BENCH_inner_loop.json from the ``inner_loop/*``
rows — ``dense``, the PR-2 ``lazy`` reference scan, the epoch-planned
``fused`` engine, and the cost-model ``auto`` dispatch: four rows per
(d, density) cell — BENCH_partition.json from the ``partition/*`` rows,
and BENCH_ingest.json from the ``ingest/*`` LIBSVM-pipeline throughput
rows.  ``--dataset rcv1-like`` reroutes fig1/table2 through the
`repro.datasets` registry (real LIBSVM text -> mmap shards).
"""
import argparse
import json
import sys
import traceback


def list_solvers() -> None:
    from repro.core import solvers
    print(f"{'name':12s} {'dist':5s} {'paper ref':46s} communication")
    for name in solvers.available():
        spec = solvers.get(name)
        dist = "p-way" if spec.distributed else "flat"
        print(f"{name:12s} {dist:5s} {spec.paper_ref:46s} {spec.comm_model}")


# cross-PR perf trails: row-name prefix -> snapshot file.  Each file
# only ever absorbs its own prefix, so a `--json` run that selected
# other suites cannot clobber an unrelated trail.
JSON_TRAILS = {
    "inner_loop/": "BENCH_inner_loop.json",
    "partition/": "BENCH_partition.json",
    "ingest/": "BENCH_ingest.json",
    "comm/": "BENCH_comm.json",
    "elastic/": "BENCH_elastic.json",
}


def write_json(rows, path) -> None:
    """Write every perf trail whose prefix collected rows.

    `path` overrides the destination when exactly one trail matched
    (the historical --json PATH behavior); with several trails matched
    the per-trail default filenames are used.

    Schema bench-rows/v2: every row is stamped through
    `benchmarks.common.stamp_row` — host fingerprint, backend,
    timestamp, and a `pct_peak` roofline annotation (None when the row
    carries no byte model) — and the file carries one shared
    `host` block so a trail diff can tell code from container.
    """
    from benchmarks.common import host_fingerprint, stamp_row
    matched = {}
    for prefix, default_path in JSON_TRAILS.items():
        trail_rows = [r for r in rows if r["name"].startswith(prefix)]
        if trail_rows:
            matched[default_path] = trail_rows
    if not matched:
        trails = ", ".join(JSON_TRAILS)
        print(f"no perf-trail rows collected (prefixes: {trails}); "
              "not writing JSON (run with --only lazy_inner or "
              "--only partition)", file=sys.stderr)
        return
    for default_path, trail_rows in matched.items():
        out = path if (path and len(matched) == 1) else default_path
        trail_rows = [stamp_row(r) for r in trail_rows]
        us = {}
        for r in trail_rows:
            try:
                us[r["name"]] = float(r.get("us_per_call", ""))
            except (TypeError, ValueError):
                continue
        doc = {"schema": "bench-rows/v2", "us_per_call": us,
               "host": host_fingerprint(), "rows": trail_rows}
        with open(out, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {out} ({len(us)} timed rows)", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="include the large avazu/kdd-like datasets")
    ap.add_argument("--only", default="")
    ap.add_argument("--list-solvers", action="store_true",
                    help="print the solver registry and exit")
    ap.add_argument("--json", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="also snapshot the perf-trail rows as JSON "
                         "(BENCH_inner_loop.json / BENCH_partition.json / "
                         "BENCH_ingest.json; PATH overrides when a single "
                         "trail matched)")
    ap.add_argument("--dataset", default=None, metavar="NAME",
                    help="run fig1/table2 on a repro.datasets registry "
                         "dataset (e.g. rcv1-like): real LIBSVM text "
                         "through the mmap ingestion path")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fast cells (CI): fig1 runs two solvers "
                         "few rounds, lazy_inner one small cell")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the run's telemetry spans/counters as "
                         "Chrome-trace JSON (Perfetto-loadable)")
    args = ap.parse_args()

    if args.list_solvers:
        list_solvers()
        return

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (fig1_convergence, table2_timing, fig2a_speedup,
                            fig2b_partition, recovery_bench, roofline_report,
                            bench_lazy_inner, bench_partition, bench_ingest,
                            bench_shard_codec, bench_comm, bench_elastic)
    suites = [
        ("fig1", lambda: fig1_convergence.main(full=args.full,
                                               dataset=args.dataset,
                                               smoke=args.smoke)),
        ("table2", lambda: table2_timing.main(dataset=args.dataset)),
        ("fig2a", fig2a_speedup.main),
        ("fig2b", fig2b_partition.main),
        ("recovery", recovery_bench.main),
        ("roofline", roofline_report.main),
        ("lazy_inner", lambda: bench_lazy_inner.main(full=args.full,
                                                     smoke=args.smoke)),
        ("partition", lambda: bench_partition.main(full=args.full)),
        ("ingest", lambda: bench_ingest.main(full=args.full)),
        ("ingest_codec", lambda: bench_shard_codec.main(full=args.full)),
        ("comm", lambda: bench_comm.main(full=args.full)),
        ("elastic", lambda: bench_elastic.main(full=args.full)),
    ]
    rows = []
    for name, fn in suites:
        if args.only and args.only not in name:
            continue
        try:
            rows.extend(fn())
        except Exception:
            traceback.print_exc()
            rows.append({"name": f"{name}/FAILED", "us_per_call": "",
                         "derived": "see stderr"})
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r.get('us_per_call', '')},"
              f"{r.get('derived', '')}")
    if args.json is not None:
        write_json(rows, args.json or None)
    if args.trace_out:
        from repro import obs
        obs.write_trace(args.trace_out)
        print(f"wrote {args.trace_out} "
              f"({len(obs.get_collector().events())} telemetry events)",
              file=sys.stderr)


if __name__ == "__main__":
    main()
