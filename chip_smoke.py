#!/usr/bin/env python3
"""Smoke run of pSCOPE's sparse solve on a TPU, through `solvers.run`.

    python chip_smoke.py [--seed S]            # one chip: phases A and B
    python chip_smoke.py --chips 4 [--seed S]  # the four-chip CALL mesh only

Phase A runs `pscope_lazy` (the fused lazy-epoch Pallas kernel) at the
shape of the LIBSVM rcv1.binary training set -- n = 20,242 rows,
d = 47,236 features, 74 nonzeros per row, labels +-1 -- generated from
the seed, partitioned uniformly over p = 8 workers, and holds it to
FISTA on the same partition.  The regularizer is the default logistic
one with the L1 weight cut to 1e-5 (see `LAM2`).  Phase B runs the dense inner path
(`pscope`, the fused prox-SVRG Pallas kernel over 376 x 128 tiles, a
ragged kernel grid) against `pscope_lazy` on the first 2,048 rows at
the same width.  `--chips 4` runs only `pscope_mesh` with one worker per
chip against one-chip `pscope_lazy` on the same partition.

Every check that fails ends the run with a non-zero exit.  The script
refuses to run anywhere but on a TPU.  The times it prints are smoke
timings, not benchmark numbers.  The last line of stdout is one JSON
object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

# the LIBSVM rcv1.binary training set
RCV1_ROWS, RCV1_FEATURES, RCV1_NNZ_PER_ROW = 20_242, 47_236, 74
PHASE_B_ROWS = 2_048
ROUNDS = 10
FISTA_ITERS = 500
# L1 weight.  The default logistic lam2 = 1e-4 is above lam_max =
# ||grad f(0)||_inf (9.0e-5 at seed 0) on this data: its columns are
# drawn uniformly, so each feature sits in only ~32 of the 20,242 rows.
# Then w = 0 is the exact minimizer and no solver moves.  1e-5 keeps a
# sparse, nonzero solution.
LAM2 = 1e-5

# kernel_name of each Pallas kernel in the lowered program
EPOCH_KERNEL = "fused_lazy_epoch"
DENSE_KERNEL = "fused_prox_svrg_diff"


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    sys.exit(f"chip_smoke: FAILED: {msg}")


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def require_tpu(chips: int):
    """The devices to run on; exits when JAX finds no TPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"needs a TPU, JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        fail(f"--chips {chips} needs {chips} devices, JAX found {len(devs)}")
    return devs[:chips]


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def regularizer():
    """The default logistic regularizer with the L1 weight `LAM2`."""
    from repro.core.prox import Regularizer
    from repro.datasets.registry import default_regularizer
    return Regularizer(lam1=default_regularizer("logistic").lam1, lam2=LAM2)


def lam_max(csr, y) -> float:
    """||grad f(0)||_inf of the mean logistic loss: the smallest L1
    weight at which w = 0 is the minimizer."""
    import numpy as np
    grad = np.zeros(csr.d)
    np.add.at(grad, np.asarray(csr.cols).ravel(),
              (-0.5 * y[:, None] * np.asarray(csr.vals)).ravel())
    return float(np.max(np.abs(grad)) / len(y))


def make_data(n: int, d: int, nnz_per_row: int, seed: int):
    """Seeded CSR rows of the given shape with +-1 labels."""
    import numpy as np
    from repro.data.sparse import make_csr_classification
    t0 = time.perf_counter()
    csr, y, _ = make_csr_classification(n, d, density=nnz_per_row / d,
                                        seed=seed)
    check(csr.vals.shape == (n, nnz_per_row),
          f"generated {csr.vals.shape} values, want ({n}, {nnz_per_row})")
    check(bool(np.all(np.abs(y) == 1.0)), "labels are not all +-1")
    log(f"data: n={n} d={d} nnz/row={nnz_per_row} seed={seed}, made in "
        f"{time.perf_counter() - t0:.3f} s")
    return csr, y


def uniform_partition(csr, y, p: int, seed: int, rows=None):
    """The first `rows` rows (all by default) split uniformly over p."""
    from repro.data.sparse import CSRMatrix
    from repro.partition.schemes import build_partition
    if rows is not None:
        csr = CSRMatrix(vals=csr.vals[:rows], cols=csr.cols[:rows],
                        row_nnz=csr.row_nnz[:rows], d=csr.d)
        y = y[:rows]
    return build_partition("uniform", csr, y, p, seed)


def timed_run(solver: str, obj, reg, part, cfg, steady: bool = True):
    """`solvers.run`, twice where `steady`: the first call compiles, the
    second is the steady run.  Each ends with the trace's host transfer."""
    from repro.core import solvers
    t0 = time.perf_counter()
    trace = solvers.run(solver, obj, reg, part, cfg)
    first = time.perf_counter() - t0
    if steady:
        t0 = time.perf_counter()
        trace = solvers.run(solver, obj, reg, part, cfg)
        again = time.perf_counter() - t0
        log(f"  {solver}: first call {first:.3f} s, steady call "
            f"{again:.3f} s, compile ~{max(first - again, 0.0):.3f} s "
            f"(smoke timing, not a benchmark)")
    else:
        log(f"  {solver}: one call {first:.3f} s, compile included (smoke "
            f"timing, not a benchmark)")
    log(f"  {solver}: objective by round {[float(v) for v in trace.values]}")
    log(f"  {solver}: nnz by round {[int(v) for v in trace.nnz]}")
    return trace


def kernels_in_program(obj, reg, part, cfg, inner_path: str):
    """kernel_name -> number of `tpu_custom_call`s in the lowered
    whole-trajectory program `solvers.run` compiles for this path."""
    import re

    import jax
    import jax.numpy as jnp
    from repro.core import pscope, solvers
    pcfg = solvers._pscope_config(obj, reg, part, cfg, inner_path)
    data = part.Xp if inner_path == "dense" else part.csr_p
    pcfg, Xp, yp, statics = pscope._prepare_sim(obj, reg, data, part.yp, pcfg)
    fn = pscope._sim_trajectory_fn(obj, reg, pcfg, 1, True)
    text = fn.lower(jnp.zeros((part.d,), jnp.float32),
                    jax.random.PRNGKey(pcfg.seed), Xp, yp, None,
                    statics).as_text()
    calls = text.count("tpu_custom_call")
    names = re.findall(r'kernel_name = "([^"]+)"', text)
    found = {name: names.count(name) for name in sorted(set(names))}
    log(f"  lowered {inner_path} program: {calls} tpu_custom_call, "
        f"kernels {found}")
    return found


def check_trace(name: str, trace) -> None:
    import numpy as np
    v = np.asarray(trace.values)
    check(bool(np.all(np.isfinite(v))), f"{name}: non-finite objective {v}")
    check(bool(np.all(np.isfinite(np.asarray(trace.w_final)))),
          f"{name}: non-finite iterate")


def phase_a(csr, y, p: int, seed: int,
            fista_iters: int = FISTA_ITERS) -> None:
    """pscope_lazy on all rows, held to FISTA."""
    from repro.core import LOGISTIC, solvers
    part = uniform_partition(csr, y, p, seed)
    reg = regularizer()
    top = lam_max(csr, y)
    log(f"phase A: pscope_lazy, p={p} rounds={ROUNDS}, worker shards "
        f"{tuple(part.csr_p.vals.shape)}, lambda=({reg.lam1}, {reg.lam2}), "
        f"lam_max {top!r}")
    check(reg.lam2 < top, f"lam2 {reg.lam2} >= lam_max {top}: w = 0 is "
                          f"the minimizer, the run would show nothing")
    cfg = solvers.SolverConfig(rounds=ROUNDS, inner_epochs=1.0, seed=seed)
    kernels = kernels_in_program(LOGISTIC, reg, part, cfg, "lazy")
    check(kernels.get(EPOCH_KERNEL, 0) >= 1,
          f"no {EPOCH_KERNEL} tpu_custom_call in the pscope_lazy program")
    tr = timed_run("pscope_lazy", LOGISTIC, reg, part, cfg)
    check_trace("pscope_lazy", tr)
    v0, vT = float(tr.values[0]), float(tr.values[-1])
    check(vT < 0.99 * v0, f"objective fell only from {v0} to {vT}")
    check(int(tr.nnz[-1]) > 0, "final iterate has no nonzero")

    every = max(1, fista_iters // ROUNDS)
    fcfg = solvers.SolverConfig(rounds=ROUNDS, record_every=every, seed=seed)
    ref = timed_run("fista", LOGISTIC, reg, part, fcfg, steady=False)
    check_trace("fista", ref)
    vf = float(ref.values[-1])
    log(f"  final objective: pscope_lazy {vT!r}, fista ({ROUNDS * every} "
        f"iterations) {vf!r}, relative difference {rel(vT, vf):.3e}")
    check(vT <= vf or rel(vT, vf) <= 1e-3,
          f"pscope_lazy {vT} is not within 1e-3 of fista {vf}")
    log("phase A: ok")


def phase_b(csr, y, p: int, seed: int, rows: int) -> None:
    """The dense inner path at a ragged tile count vs the lazy one."""
    from repro.core import LOGISTIC, solvers
    part = uniform_partition(csr, y, p, seed, rows)
    tile_rows = -(-csr.d // 1024) * 8      # as kernels.ops._to_tiles pads
    log(f"phase B: pscope (dense) vs pscope_lazy, first {rows} rows, "
        f"{tile_rows} tile rows of 128, p={p} rounds={ROUNDS}, dense "
        f"worker shards {tuple(part.Xp.shape)}")
    reg = regularizer()
    cfg = solvers.SolverConfig(rounds=ROUNDS, inner_epochs=1.0, seed=seed)
    kernels = kernels_in_program(LOGISTIC, reg, part, cfg, "dense")
    check(kernels.get(DENSE_KERNEL, 0) >= 1,
          f"no {DENSE_KERNEL} tpu_custom_call in the pscope program")
    dense = timed_run("pscope", LOGISTIC, reg, part, cfg)
    lazy = timed_run("pscope_lazy", LOGISTIC, reg, part, cfg)
    check_trace("pscope", dense)
    check_trace("pscope_lazy", lazy)
    vd, vl = float(dense.values[-1]), float(lazy.values[-1])
    log(f"  final objective: pscope {vd!r}, pscope_lazy {vl!r}, relative "
        f"difference {rel(vd, vl):.3e}")
    check(rel(vd, vl) <= 1e-4, f"dense {vd} and lazy {vl} differ by more "
                               f"than 1e-4 relative")
    log("phase B: ok")


def phase_mesh(csr, y, p: int, seed: int) -> None:
    """pscope_mesh, one worker per chip, vs one-chip pscope_lazy."""
    from repro.core import LOGISTIC, solvers
    from repro.launch.mesh import MeshSpec
    part = uniform_partition(csr, y, p, seed)
    log(f"phase mesh: pscope_mesh on {p} chips vs one-chip pscope_lazy, "
        f"rounds={ROUNDS}, worker shards {tuple(part.csr_p.vals.shape)}")
    reg = regularizer()
    cfg = solvers.SolverConfig(rounds=ROUNDS, inner_epochs=1.0, seed=seed)
    mcfg = cfg.with_(extras={"mesh_spec": MeshSpec.for_workers(p)})
    mesh = timed_run("pscope_mesh", LOGISTIC, reg, part, mcfg)
    devices = mesh.meta["mesh"]["devices"]
    log(f"  worker devices: {devices}")
    check(len(set(devices)) == p == len(devices),
          f"{p} workers sit on devices {devices}, want {p} distinct")
    lazy = timed_run("pscope_lazy", LOGISTIC, reg, part, cfg)
    check_trace("pscope_mesh", mesh)
    check_trace("pscope_lazy", lazy)
    vm, vl = float(mesh.values[-1]), float(lazy.values[-1])
    log(f"  final objective: pscope_mesh {vm!r}, pscope_lazy {vl!r}, "
        f"relative difference {rel(vm, vl):.3e}")
    check(rel(vm, vl) <= 1e-4, f"mesh {vm} and one-chip {vl} differ by "
                               f"more than 1e-4 relative")
    log("phase mesh: ok")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip pscope_mesh phase")
    args = ap.parse_args(argv)

    devs = require_tpu(args.chips)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import enable_compile_cache
    cache = Path(enable_compile_cache())
    entries = len(list(cache.iterdir())) if cache.is_dir() else 0
    kind = devs[0].device_kind
    log(f"device: {devs[0].platform} {kind!r} x{len(devs)}; compile cache "
        f"{cache} ({entries} entries at start)")

    csr, y = make_data(RCV1_ROWS, RCV1_FEATURES, RCV1_NNZ_PER_ROW, args.seed)
    if args.chips == 4:
        phase_mesh(csr, y, p=4, seed=args.seed)
    else:
        phase_a(csr, y, p=8, seed=args.seed)
        phase_b(csr, y, p=8, seed=args.seed, rows=PHASE_B_ROWS)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
