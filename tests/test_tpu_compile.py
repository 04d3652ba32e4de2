"""Compile the main path's Pallas kernels for one described TPU v5e chip.

The TPU compiler is installed even where no chip is attached: a kernel
that Mosaic refuses (an unaligned block, an op with no lowering, too
much VMEM) fails here instead of on the chip.  The shapes are the
LIBSVM rcv1 width, d = 47,236 (376 x 128 tiles), and one epoch plan of
M = 2,530 steps x 128 slots, 74 of them real, with a fold bound a step.
The epoch-plan build is compiled at the benchmark cells' shapes too.
Nothing runs; each test only compiles.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and the test
workers all import this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.plan import build_epoch_plan
from repro.core.svrg import logistic_h_prime
from repro.kernels.fused_prox_svrg import (fused_prox_svrg_diff_pallas,
                                           fused_prox_svrg_pallas)
from repro.kernels.lazy_prox import lazy_prox_pallas
from repro.kernels.sparse_inner import fused_lazy_epoch_pallas

D = 47_236
ROWS = 376                  # ceil(D / 128), rounded up to a multiple of 8
TILES = -(-(D + 1) // 1024)  # (8, 128) tiles with a spare tail slot
M, B, KP = 2_530, 1, 128    # steps x samples x padded slots per sample
K = 74                      # rcv1's nonzeros a row: the real slots
KW = dict(eta=2.0, lam1=1e-4, lam2=1e-4, interpret=False)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _assert_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["lazy_prox", "fused_prox_svrg",
                                    "fused_prox_svrg_diff"])
def test_elementwise_kernel_compiles_at_rcv1_width(spec, kernel):
    f32 = spec((ROWS, 128), jnp.float32)
    if kernel == "lazy_prox":
        _assert_kernel(lambda u, z, q: lazy_prox_pallas(u, z, q, **KW),
                       f32, f32, spec((ROWS, 128), jnp.int32))
    elif kernel == "fused_prox_svrg":
        _assert_kernel(lambda *a: fused_prox_svrg_pallas(*a, **KW),
                       f32, f32, f32, f32)
    else:
        _assert_kernel(lambda *a: fused_prox_svrg_diff_pallas(*a, **KW),
                       f32, f32, f32)


@pytest.mark.parametrize("vals,workers", [("f32", None),
                                          ("bf16_bits", None), ("f32", 8)],
                         ids=["f32", "bf16_bits", "f32-vmap8"])
def test_fused_lazy_epoch_compiles_at_rcv1_width(spec, vals, workers):
    """One worker's epoch, and 8 workers' under `jax.vmap` as
    `rcv1.uniform` runs them (the batch becomes a grid axis)."""
    lead = () if workers is None else (workers,)
    at = lambda shape, dtype: spec(lead + shape, dtype)  # noqa: E731
    tile_f32 = at((TILES, 8, 128), jnp.float32)
    slots = at((M, 1, B * KP), jnp.int32)
    step_f32 = at((M, B, KP), jnp.float32)
    vb = at((M, B, KP), jnp.float32 if vals == "f32" else jnp.uint16)
    per_sample = at((M, B, 1), jnp.float32)

    fold_n = at((M, 1, 1), jnp.int32)

    def epoch(*a):
        return fused_lazy_epoch_pallas(
            *a, h_prime=logistic_h_prime, eta=2.0,
            eta_eff=2.0 / (1.0 + 2e-4), lam1=1e-4, lam2=1e-4, n_cols=K,
            interpret=False)

    _assert_kernel(epoch if workers is None else jax.vmap(epoch),
                   tile_f32, tile_f32, at((TILES, 8, 128), jnp.int32), slots,
                   at((M, B, KP), jnp.int32), slots, fold_n, vb, per_sample,
                   step_f32, per_sample)


@pytest.mark.parametrize("workers,rows", [(8, 2_530), (None, 5_060)],
                         ids=["rcv1.uniform", "rcv1-mesh4.uniform"])
def test_epoch_plan_compiles_without_a_loop(spec, workers, rows):
    """The sort-based plan at the cells' shapes (8 workers vmapped on
    one chip; one worker of the four-chip mesh) is straight-line code:
    a sort by key and one by position, no `while` of a binary search
    and no scatter."""
    lead = () if workers is None else (workers,)
    plan = lambda c, i: build_epoch_plan(c, i, D)  # noqa: E731
    if workers is not None:
        plan = jax.vmap(plan)
    text = jax.jit(plan).lower(spec(lead + (rows, K), jnp.int32),
                               spec(lead + (rows, 1), jnp.int32)
                               ).compile().as_text()
    assert not re.search(r" (while|scatter)\(", text)
    assert len(re.findall(r" sort\(", text)) == 2
