#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the cell's rows and worker assignment from the seed, hands
them to the program, and warms up the cell's own solve once.  The window
then calls `repro.core.solvers.run` again and again, starting no new
solve once `--seconds` have passed; every solve it started counts.  After
the window the plain reference (bench/reference.py) is solved on the
same rows and every timed solve is compared with it.

With `--trace 0` the result carries the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, read from a profiler trace of whole
solves.  The last line of stdout is one JSON object:
{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
"checks"}; the numbers compared, each beside its limit, are also the
last lines of stderr.  The run exits non-zero, and prints no result,
where JAX finds no accelerator or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
# libtpu logs to a fixed /tmp/tpu_logs unless told otherwise; a run
# writes only inside its checkout and the directories it is given
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import loader  # noqa: E402
import reference  # noqa: E402
import tracereduce  # noqa: E402
import work  # noqa: E402

# whole solves are traced until this many seconds have passed
TRACE_SECONDS = 5.0
TRACE_DIR = HERE.parent / ".bench_out" / "trace"
WINDOW = "bench_window"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_chips(n: int):
    """The devices to run on.  Exits non-zero where JAX finds no
    accelerator or fewer than `n` chips: no measurement falls back to
    the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        sys.exit(f"bench: needs an accelerator, JAX found platform "
                 f"{devs[0].platform!r} ({len(devs)} device(s))")
    if len(devs) < n:
        sys.exit(f"bench: the cell needs {n} chips, JAX found {len(devs)} "
                 f"{devs[0].platform} device(s)")
    return devs


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (a fixed directory in
    the checkout unless JAX_COMPILATION_CACHE_DIR names one), with every
    program kept, so that only a cell's first run compiles."""
    import jax
    sys.path.insert(0, str(HERE.parent / "src"))
    from repro.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def window(cell, seconds: float):
    """Solves until `seconds` have passed; the last may run over."""
    solves = []
    t0 = time.perf_counter()
    while not solves or time.perf_counter() - t0 < seconds:
        solves.append(cell.solve())
    return solves, time.perf_counter() - t0


def traced_window(cell, seconds: float):
    """Whole solves under the profiler; returns them and the xplane."""
    import jax
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    with jax.profiler.trace(str(TRACE_DIR)):
        with jax.profiler.TraceAnnotation(WINDOW):
            solves, _ = window(cell, min(seconds, TRACE_SECONDS))
    return solves, tracereduce.find_xplane(TRACE_DIR)


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def free_device_memory() -> None:
    """Drop every array the program left on the device, so the reference
    runs on an empty chip."""
    import jax
    for x in jax.live_arrays():
        x.delete()


def end_to_end(solves, rtg, setup_s: float) -> dict:
    round_s = sum(s.seconds for s in solves) / sum(s.rounds for s in solves)
    out = {"round_s": round_s, "setup_s": setup_s}
    if rtg is not None:
        out["time_to_gap_s"] = rtg * round_s
    return out


def per_layer(bench, cell, solves, rtg, trace, kind: str, chips: int):
    """Reads every per-layer metric of the cell from the loaded trace."""
    spec, config = cell.spec, cell.config
    stats = tracereduce.summarize(trace, WINDOW,
                                  kernel=config["epoch_kernel"])
    per_chip = stats["chips"][:chips]
    top = max(per_chip, key=lambda c: c["busy_ns"])
    n_k = len(cell.rows[2]) // config["workers"]
    steps = max(1, int(config["inner_epochs"] * n_k))
    workers_per_chip = config["workers"] // chips
    ctx = {
        "chip": top,
        "rounds": sum(s.rounds for s in solves),
        "rounds_to_gap": rtg,
        "epoch_bytes_per_chip_round": workers_per_chip * work.epoch_bytes(
            steps, config["inner_batch"], config["nnz_per_row"],
            config["features"]),
        "peak": bench.peak(kind),
    }
    metrics, each_chip = {}, {}
    for m in bench.cell_metrics(spec["name"], "per_layer"):
        read = bench.reader(m["name"])
        each_chip[m["name"]] = [read({**ctx, "chip": c}) for c in per_chip]
        value = each_chip[m["name"]][per_chip.index(top)]
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if chips > 1:   # the result reports the chip with the most busy time
        print(json.dumps({"each_chip": each_chip}), flush=True)
    busy_s = sum(c["busy_ns"] for c in per_chip) / len(per_chip) / 1e9
    return metrics, busy_s, top["window_ns"] / 1e9, stats["breakdown"]


def main(argv=None, root=loader.REPO, devices=None) -> int:
    """`devices` skips the look for a chip (tests only)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = loader.Benchmark(root)
    spec = bench.cell(args.workload)
    config = bench.config(spec["config"])
    chips = spec["chips"]
    devs = require_chips(chips) if devices is None else devices
    cache = enable_compile_cache()
    import cell as cell_mod
    cell = cell_mod.Cell.build(spec, config, args.seed)
    warm = cell.solve()
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s (warm-up solve {warm.seconds:.3f} s), "
        f"compile cache {cache}")

    if args.trace:
        solves, xplane = traced_window(cell, args.seconds)
    else:
        solves, elapsed = window(cell, args.seconds)
        log(f"window: {len(solves)} solves in {elapsed:.3f} s")
    peak = memory_peak(devs[:chips])
    free_device_memory()

    t_ref = time.perf_counter()
    _, p_star, polish = cell.reference()
    t_ref = time.perf_counter() - t_ref
    eps = spec["rel_gap"]
    rtgs = [reference.rounds_to_gap(s.values, p_star, eps) for s in solves]
    failed = sum(r is None for r in rtgs)
    reached = [r for r in rtgs if r is not None]
    rtg = float(sorted(reached)[len(reached) // 2]) if reached else None
    numbers = cell.compare(solves, p_star)
    limits = spec["limits"]
    correct = all(numbers[k] <= limits[k] for k in limits)
    log(f"reference: P* {p_star!r} in {t_ref:.3f} s; the float64 polish "
        f"moved P by {polish:.3e} relative; rounds to gap {eps}: {rtgs}")

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(solves),
              "failed": failed}
    if args.trace:
        trace = tracereduce.load(xplane)
        metrics, busy_s, window_s, breakdown = per_layer(
            bench, cell, solves, rtg, trace, devs[0].device_kind, chips)
        device.update(busy_s=busy_s, window_s=window_s)
        result.update(metrics=metrics, device=device, breakdown=breakdown)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        units = {m["name"]: m["unit"] for m in bench.spec["end_to_end"]}
        result.update(metrics={k: {"value": v, "unit": units[k]}
                               for k, v in end_to_end(solves, rtg,
                                                      setup_s).items()},
                      device=device)
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in limits}
    for k in limits:
        log(f"check {k} {numbers[k]!r} limit {limits[k]!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
