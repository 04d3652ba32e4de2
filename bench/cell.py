"""One cell's inputs, its solve through the program, and its check.

`Cell.build` makes the rows and the worker assignment from the seed and
hands them to the program as a `Partition`; `Cell.solve` is one call of
`repro.core.solvers.run`, the path the window times; `Cell.reference`
solves the plain reference after the window and `Cell.compare` holds
the solves to it.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from pathlib import Path
from typing import Callable

import numpy as np

import reference
import traffic

SRC = Path(__file__).resolve().parent.parent / "src"


def lam_max(vals, cols, y, d: int) -> float:
    """|grad F(0)|_inf of the mean logistic loss: the smallest L1 weight
    at which w = 0 is the minimizer."""
    grad = np.bincount(np.asarray(cols).ravel(),
                       weights=(-0.5 * y[:, None] * vals).ravel(),
                       minlength=d)
    return float(np.max(np.abs(grad)) / len(y))


@dataclasses.dataclass
class Solve:
    seconds: float
    rounds: int
    values: np.ndarray      # recorded P(w_t), t = 0..T
    w: np.ndarray           # final iterate


@dataclasses.dataclass
class Cell:
    spec: dict              # the cell: BENCHMARK.json entry + its file
    config: dict
    rows: tuple             # (vals, cols, y) of the rows the workers hold
    solve: Callable[[], Solve]   # one timed solve through the program

    @classmethod
    def build(cls, spec: dict, config: dict, seed: int) -> "Cell":
        if config["loss"] != "logistic":
            raise ValueError(f"the reference solves logistic loss, the "
                             f"configuration states {config['loss']!r}")
        vals, cols, y = traffic.make_rows(config, seed)
        idx = traffic.assign(y, config["workers"], spec["assign"], seed)
        held = idx.reshape(-1)
        rows = (vals[held], cols[held], y[held])
        top = lam_max(*rows, config["features"])
        if config["lam2"] >= top:
            raise SystemExit(
                f"bench: lam2 {config['lam2']} >= lam_max {top} for seed "
                f"{seed}: w = 0 is the minimizer and the solve shows "
                f"nothing")
        return cls(spec=spec, config=config, rows=rows,
                   solve=_program_solve(spec, config, seed, vals, cols, y,
                                        idx))

    def reference(self):
        """(w*, P*, polish) of the plain reference: w* solved in float32
        and polished by a few float64 steps, P* its objective in float64,
        and how far the polish moved P, relative: the float32 solve's
        own error."""
        c = self.config
        w, _, eta = reference.solve(*self.rows, c["features"], c["lam1"],
                                    c["lam2"], dtype=np.float32,
                                    iters=c["reference_iters"])
        before = self._p(w)
        w = reference.polish(*self.rows, w, c["lam1"], c["lam2"], eta,
                             c["reference_polish"])
        p_star = self._p(w)
        return w, p_star, (before - p_star) / p_star

    def control(self):
        """The control: the reference solved in bfloat16, the precision
        below the configuration's float32, as (w, its own history)."""
        import jax.numpy as jnp
        c = self.config
        w, hist, _ = reference.solve(*self.rows, c["features"], c["lam1"],
                                     c["lam2"], dtype=jnp.bfloat16,
                                     iters=c["reference_iters"])
        return w, hist

    def _p(self, w) -> float:
        return reference.objective64(*self.rows, w, self.config["lam1"],
                                     self.config["lam2"])

    def compare(self, solves, p_star: float) -> dict:
        return reference.compare([(s.values, s.w) for s in solves],
                                 self.rows, p_star, self.config["lam1"],
                                 self.config["lam2"])


def _program_solve(spec, config, seed, vals, cols, y, idx):
    """The program's inputs and a closure that runs one timed solve."""
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    import jax.numpy as jnp
    from repro.core import OBJECTIVES, Regularizer, solvers
    from repro.data.sparse import CSRMatrix
    from repro.partition.container import make_partition

    csr = CSRMatrix(vals=jnp.asarray(vals), cols=jnp.asarray(cols),
                    row_nnz=jnp.full((len(y),), vals.shape[1], jnp.int32),
                    d=config["features"])
    part = make_partition(csr, y, idx, name=spec["assign"]["rule"])
    extras = {"inner_batch": config["inner_batch"]}
    if config.get("mesh_workers"):
        from repro.launch.mesh import MeshSpec
        extras["mesh_spec"] = MeshSpec.for_workers(config["mesh_workers"])
    scfg = solvers.SolverConfig(rounds=spec["rounds"], eta=config["eta"],
                                inner_epochs=config["inner_epochs"],
                                seed=seed % 2**31, extras=extras)
    obj = OBJECTIVES[config["loss"]]
    reg = Regularizer(lam1=config["lam1"], lam2=config["lam2"])

    def run() -> Solve:
        t0 = time.perf_counter()
        tr = solvers.run(config["solver"], obj, reg, part, scfg)
        w = np.asarray(tr.w_final)
        return Solve(seconds=time.perf_counter() - t0, rounds=tr.rounds,
                     values=np.asarray(tr.values, np.float64), w=w)

    return run
