"""Distributed execution tests (multi host-device, subprocess isolated —
jax locks the device count at first init, so these run in child
processes with XLA_FLAGS set)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(devices: int, code: str) -> str:
    env = dict(os.environ,
               PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-2500:]
    return proc.stdout


@pytest.mark.parametrize("driver", ["python", "scan", "mesh"])
def test_pscope_distributed_equals_simulation(driver):
    """Every distributed driver over 4 devices == vmap simulation.

    All three share `make_distributed_outer_step_core`, whose per-worker
    key is split(key, p)[worker] — the simulation's own derivation — so
    the trajectories agree to fp32 reassociation, not just statistically
    (tolerance 1e-4 on the final objective; it was 5e-3 back when the
    distributed body used fold_in)."""
    out = _run(4, f"""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import Regularizer, LOGISTIC, PScopeConfig
        from repro.core.pscope import run, run_distributed
        from repro.core.partition import stack_partition
        from repro.data.synthetic import make_sparse_classification

        driver = {driver!r}
        X, y, _ = make_sparse_classification(256, 32, density=0.3, seed=0)
        X, y = jnp.asarray(X), jnp.asarray(y)
        reg = Regularizer(1e-3, 1e-3)
        cfg = PScopeConfig(eta=0.5, inner_steps=64, inner_batch=2,
                           outer_steps=6)
        idx = np.arange(256).reshape(4, 64)
        Xp, yp = stack_partition(X, y, idx)
        if driver == "mesh":
            from repro.launch.mesh import run_mesh
            res = run_mesh(LOGISTIC, reg, np.asarray(Xp), np.asarray(yp),
                           jnp.zeros(32), cfg)
            hist = list(res.values)
        else:
            mesh = jax.make_mesh((4,), ("data",))
            _, hist = run_distributed(LOGISTIC, reg, X, y, jnp.zeros(32),
                                      cfg, mesh, axis="data",
                                      driver=driver)
        _, hist_sim = run(LOGISTIC, reg, Xp, yp, jnp.zeros(32), cfg)
        print("RESULT", hist[-1], hist_sim[-1], hist[0])
        assert hist[-1] < hist[0] - 0.02
        assert abs(hist[-1] - hist_sim[-1]) < 1e-4
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.parametrize("layout", ["dense", "lazy", "encoded"])
def test_call_round_collectives(layout):
    """The compiled CALL round over a 4-device mesh holds exactly
    COMM_ALLREDUCES_PER_ROUND all-reduces, each of d float32, and none
    inside a loop body: the inner epoch is collective-free.  `lazy` is
    the cells' layout (flat CSR rows with sharded statics); `encoded`
    is the storage codec's body."""
    out = _run(4, f"""
        import re
        import jax, jax.numpy as jnp
        from repro.core import Regularizer, LOGISTIC, PScopeConfig
        from repro.core import pscope
        from repro.data.sparse import dense_to_csr, encode_csr
        from repro.data.synthetic import make_sparse_classification
        from repro.launch import roofline as rf

        layout = {layout!r}
        n, d = 256, 40
        X, y, _ = make_sparse_classification(n, d, density=0.2, seed=0)
        X, y = jnp.asarray(X), jnp.asarray(y)
        if layout != "dense":
            X = dense_to_csr(X)
        if layout == "encoded":
            X = encode_csr(X)
        mesh = jax.make_mesh((4,), ("workers",))
        reg = Regularizer(1e-3, 1e-3)
        cfg = PScopeConfig(eta=0.5, inner_steps=16, inner_batch=2,
                           outer_steps=1,
                           inner_path="dense" if layout == "dense"
                           else "lazy")
        cfg, X, statics = pscope._prepare_distributed(
            LOGISTIC, reg, X, y, cfg, mesh, "workers")
        assert (statics is not None) == (layout == "lazy")
        step = pscope.make_distributed_outer_step_core(
            LOGISTIC, reg, cfg, mesh, "workers")
        c = (jax.jit(step).lower(pscope.init_state(jnp.zeros(d)), X, y,
                                 statics).compile())
        model = rf.HloCostModel(c.as_text())

        # every computation a while loop runs, through any nesting
        def callees(ins):
            return [n for n in re.findall(r"%([\\w.\\-]+)", ins.line)
                    if n in model.comps]

        looped = set()
        todo = [n for comp in model.comps.values() for i in comp
                if i.opcode == "while" for n in callees(i)]
        while todo:
            name = todo.pop()
            if name not in looped:
                looped.add(name)
                todo += [n for i in model.comps[name] for n in callees(i)]

        ars = [(comp, ins) for comp, body in model.comps.items()
               for ins in body if ins.opcode.startswith("all-reduce")
               and not ins.opcode.endswith("-done")]
        assert len(ars) == pscope.COMM_ALLREDUCES_PER_ROUND, ars
        for comp, ins in ars:
            assert (ins.dtype, ins.dims) == ("f32", (d,)), ins.line
            assert comp not in looped, (comp, ins.line)
        assert looped, "no while loop: the inner epoch was not scanned"
        print("OK", len(ars))
    """)
    assert "OK" in out


def test_elastic_mesh_resize_checkpoint():
    """Train 2 steps on 4 devices, checkpoint, resume on 2 devices."""
    out = _run(4, """
        import numpy as np, jax, jax.numpy as jnp, tempfile
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.train.checkpoint import save_checkpoint, restore_checkpoint
        from repro.train.elastic import (reshard_tree, failure_plan,
                                         initial_ownership)

        mesh4 = jax.make_mesh((4,), ("data",))
        w = jax.device_put(jnp.arange(16.0).reshape(4, 4),
                           NamedSharding(mesh4, P("data")))
        d = tempfile.mkdtemp()
        save_checkpoint(d, 1, {"w": w})
        # simulate losing one of the two hosts: the survivor adopts
        # every orphaned worker (p stays 4, the mesh shrinks to 2)
        plan = failure_plan(initial_ownership(4, 2), dead={1})
        assert plan == {0: (0, 1, 2, 3)}, plan
        mesh2 = jax.make_mesh((2,), ("data",))
        tree, _ = restore_checkpoint(d)
        out = reshard_tree(tree, mesh2, {"w": P("data")})
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.arange(16.0).reshape(4, 4))
        print("OK")
    """)
    assert "OK" in out
