#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--control-seeds 1,2,3] [--fault NAME]

For each seed, in one process: one solve of the program at the cell's
own size, through the same call the window times, and the numbers the
check compares (bench/reference.py `compare`).  For each control seed:
the control, which is the plain reference put in the program's place
and computed in bfloat16, the precision below the float32 the
configuration states.  The limit of each number lies above the largest
program reading and below the smallest control reading.  With `--fault`
the program's solves run with that fault of bench/faults.py planted
under them, and their readings have to fail a limit.  One JSON line per
reading on stdout.  Benchmark runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import cell as cell_mod  # noqa: E402
import faults  # noqa: E402
import loader  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402


def main(argv=None, root=loader.REPO, devices=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]

    bench = loader.Benchmark(root)
    spec = bench.cell(args.workload)
    config = bench.config(spec["config"])
    if devices is None:
        run.require_chips(spec["chips"])
    run.enable_compile_cache()
    if args.fault:
        faults.FAULTS[args.fault](setattr)
    for seed in sorted(set(seeds) | set(control)):
        cell = cell_mod.Cell.build(spec, config, seed)
        out = {"workload": args.workload, "seed": seed,
               "fault": args.fault}
        solves = []
        if seed in seeds:
            t0 = time.perf_counter()
            solves = [cell.solve()]
            out["first_solve_s"] = time.perf_counter() - t0
        _, p_star, out["polish"] = cell.reference()
        out["p_star"] = p_star
        if solves:
            out["program"] = cell.compare(solves, p_star)
            out["rounds_to_gap"] = reference.rounds_to_gap(
                solves[0].values, p_star, spec["rel_gap"])
            out["gaps"] = list(reference.rel_gaps(solves[0].values, p_star))
        if seed in control:
            w_c, hist_c = cell.control()
            out["control"] = cell.compare(
                [cell_mod.Solve(0.0, 0, hist_c, w_c)], p_star)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
