"""Compile the main path's Pallas kernels for one described TPU v5e chip.

The TPU compiler is installed even where no chip is attached: a kernel
that Mosaic refuses (an unaligned block, an op with no lowering, too
much VMEM) fails here instead of on the chip.  The shapes are the
LIBSVM rcv1 width, d = 47,236 (376 x 128 tiles), and one epoch plan of
M = 2,530 steps x 128 slots, 74 of them real, with a fold bound a step.
The epoch-plan build is compiled at the benchmark cells' shapes too.
news20.binary's width, d = 1,355,191 (1,324 tiles) with M = 2,499
steps of 512 slots, 455 of them real, checks the kernel past Mosaic's
default VMEM limit and the wide plan past the packed int32 key.
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

from repro.core.plan import SCOPE_PLAN_WIDE, build_epoch_plan
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
# news20.binary: features, tiles, steps of a worker (8 workers of the
# 19,996 rows), padded and real slots a row
D_NEWS20 = 1_355_191
TILES_NEWS20 = -(-(D_NEWS20 + 1) // 1024)
M_NEWS20, KP_NEWS20, K_NEWS20 = 2_499, 512, 455


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


def _assert_epoch_kernel(spec, vals, workers, tiles, m, kp, k):
    """The fused epoch kernel over `tiles` iterate tiles and an `m`-step
    plan of `kp` slots, `k` real, for one worker or `workers` vmapped."""
    lead = () if workers is None else (workers,)
    at = lambda shape, dtype: spec(lead + shape, dtype)  # noqa: E731
    tile_f32 = at((tiles, 8, 128), jnp.float32)
    slots = at((m, 1, B * kp), jnp.int32)
    step_f32 = at((m, B, kp), jnp.float32)
    vb = at((m, B, kp), jnp.float32 if vals == "f32" else jnp.uint16)
    per_sample = at((m, B, 1), jnp.float32)

    fold_n = at((m, 1, 1), jnp.int32)

    def epoch(*a):
        return fused_lazy_epoch_pallas(
            *a, h_prime=logistic_h_prime, eta=2.0,
            eta_eff=2.0 / (1.0 + 2e-4), lam1=1e-4, lam2=1e-4, n_cols=k,
            interpret=False)

    _assert_kernel(epoch if workers is None else jax.vmap(epoch),
                   tile_f32, tile_f32, at((tiles, 8, 128), jnp.int32), slots,
                   at((m, B, kp), jnp.int32), slots, fold_n, vb, per_sample,
                   step_f32, per_sample)


@pytest.mark.parametrize("vals,workers", [("f32", None),
                                          ("bf16_bits", None), ("f32", 8)],
                         ids=["f32", "bf16_bits", "f32-vmap8"])
def test_fused_lazy_epoch_compiles_at_rcv1_width(spec, vals, workers):
    """One worker's epoch, and 8 workers' under `jax.vmap` as
    `rcv1.uniform` runs them (the batch becomes a grid axis)."""
    _assert_epoch_kernel(spec, vals, workers, TILES, M, KP, K)


@pytest.mark.parametrize("vals", ["f32", "bf16_bits"])
def test_fused_lazy_epoch_compiles_at_news20_width(spec, vals):
    """8 workers' epochs vmapped at news20.binary's width, as
    `news20.uniform` runs them: the iterate blocks take 41.4 MiB of
    VMEM, past Mosaic's default limit, which the kernel raises."""
    _assert_epoch_kernel(spec, vals, 8, TILES_NEWS20, M_NEWS20, KP_NEWS20,
                         K_NEWS20)


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


def _loop_bodies(text):
    """{condition name: body text} of every `while` in compiled HLO."""
    comps = {c.split(" ", 1)[0].lstrip("%"): c
             for c in re.split(r"\n(?=\S)", text)}
    return {cond: comps.get(body, "") for cond, body in re.findall(
        r" while\(.*?condition=%([\w.]+), body=%([\w.]+)", text)}


def test_wide_epoch_plan_compiles_without_a_loop(spec):
    """The wide sort plan at `news20.uniform`'s shape (8 workers of
    2,499 rows vmapped, d * (M + 1) = 3.4e9 past int32) under its own
    scope: a sort by column and one by position, one gather, no scatter,
    and no loop of its own.  The only loops are the TPU compiler's
    chunked copies (`wide.*`: dynamic slices and reshapes) that move
    the (M, 455) plan arrays to and from their flat layout; the packed
    plan at this (M, S) compiles the same copies."""
    plan = jax.vmap(lambda c, i: build_epoch_plan(c, i, D_NEWS20))
    text = jax.jit(plan).lower(spec((8, M_NEWS20, K_NEWS20), jnp.int32),
                               spec((8, M_NEWS20, 1), jnp.int32)
                               ).compile().as_text()
    for cond, body in _loop_bodies(text).items():
        assert cond.startswith("wide.cond"), cond
        assert not re.search(r" (sort|gather|scatter|compare)\(", body)
    assert not re.search(r" scatter\(", text)
    assert len(re.findall(r" sort\(", text)) == 2
    assert SCOPE_PLAN_WIDE in text
