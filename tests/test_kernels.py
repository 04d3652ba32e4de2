"""Pallas kernels vs ref.py oracles: shape/dtype sweeps (interpret)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref


@pytest.mark.parametrize("d", [64, 128, 1000, 4096, 12345])
@pytest.mark.parametrize("lam1", [0.0, 1e-3])
def test_lazy_prox_shapes(d, lam1):
    rng = np.random.RandomState(d)
    u = jnp.asarray(rng.randn(d).astype(np.float32))
    z = jnp.asarray(rng.randn(d).astype(np.float32) * 0.02)
    q = jnp.asarray(rng.randint(0, 64, d).astype(np.int32))
    got = ops.lazy_prox(u, z, q, eta=0.1, lam1=lam1, lam2=5e-3)
    want = ref.lazy_prox_ref(u, z, q, eta=0.1, lam1=lam1, lam2=5e-3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_lazy_prox_matches_sequential_truth():
    rng = np.random.RandomState(0)
    u = jnp.asarray(rng.randn(256).astype(np.float32))
    z = jnp.asarray(rng.randn(256).astype(np.float32) * 0.05)
    q = jnp.asarray(rng.randint(0, 40, 256).astype(np.int32))
    got = ops.lazy_prox(u, z, q, eta=0.05, lam1=1e-2, lam2=1e-2)
    want = ref.lazy_prox_sequential_ref(u, z, q, eta=0.05, lam1=1e-2,
                                        lam2=1e-2, max_steps=40)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("shape", [(128,), (1000,), (64, 33), (3, 5, 7)])
def test_fused_prox_svrg_shapes(shape):
    rng = np.random.RandomState(1)
    mk = lambda: jnp.asarray(rng.randn(*shape).astype(np.float32))
    u, gu, gw, z = mk(), mk(), mk(), mk()
    got = ops.fused_prox_svrg(u, gu, gw, z, eta=0.2, lam1=1e-2, lam2=1e-2)
    want = ref.fused_prox_svrg_ref(u, gu, gw, z, eta=0.2, lam1=1e-2,
                                   lam2=1e-2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("shape", [(128,), (1000,), (64, 33)])
def test_fused_prox_svrg_diff_shapes(shape):
    rng = np.random.RandomState(2)
    mk = lambda: jnp.asarray(rng.randn(*shape).astype(np.float32))
    u, dv, z = mk(), mk(), mk()
    got = ops.fused_prox_svrg_diff(u, dv, z, eta=0.2, lam1=1e-2, lam2=1e-2)
    want = ref.fused_prox_svrg_diff_ref(u, dv, z, eta=0.2, lam1=1e-2,
                                        lam2=1e-2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_fused_diff_equals_four_operand():
    """The 3-operand kernel is the 4-operand one at dv = g_u - g_w."""
    rng = np.random.RandomState(4)
    mk = lambda: jnp.asarray(rng.randn(512).astype(np.float32))
    u, gu, gw, z = mk(), mk(), mk(), mk()
    got3 = ops.fused_prox_svrg_diff(u, gu - gw, z, eta=0.3, lam1=1e-3,
                                    lam2=5e-3)
    got4 = ops.fused_prox_svrg(u, gu, gw, z, eta=0.3, lam1=1e-3, lam2=5e-3)
    np.testing.assert_allclose(np.asarray(got3), np.asarray(got4),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kernel", ["lazy_prox", "fused_prox_svrg",
                                    "fused_prox_svrg_diff"])
def test_elementwise_kernels_cover_a_ragged_grid(kernel):
    """d = 47,236 (rcv1) is 376 tile rows: one whole 256-row block and a
    partial one.  Every coordinate, the last block's included, matches
    the oracle."""
    d = 47_236
    rng = np.random.RandomState(6)
    mk = lambda: jnp.asarray(rng.randn(d).astype(np.float32))
    kw = dict(eta=0.1, lam1=1e-3, lam2=5e-3)
    if kernel == "lazy_prox":
        u, z = mk(), mk() * 0.02
        q = jnp.asarray(rng.randint(0, 64, d).astype(np.int32))
        got = ops.lazy_prox(u, z, q, **kw)
        want = ref.lazy_prox_ref(u, z, q, **kw)
    elif kernel == "fused_prox_svrg":
        args = (mk(), mk(), mk(), mk())
        got = ops.fused_prox_svrg(*args, **kw)
        want = ref.fused_prox_svrg_ref(*args, **kw)
    else:
        args = (mk(), mk(), mk())
        got = ops.fused_prox_svrg_diff(*args, **kw)
        want = ref.fused_prox_svrg_diff_ref(*args, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@given(st.floats(1e-3, 1.0), st.floats(0.0, 1.0), st.floats(0.0, 1.0))
@settings(max_examples=20, deadline=None)
def test_fused_prox_svrg_hyperparams(eta, lam1, lam2):
    rng = np.random.RandomState(7)
    mk = lambda: jnp.asarray(rng.randn(300).astype(np.float32))
    u, gu, gw, z = mk(), mk(), mk(), mk()
    got = ops.fused_prox_svrg(u, gu, gw, z, eta=eta, lam1=lam1, lam2=lam2)
    want = ref.fused_prox_svrg_ref(u, gu, gw, z, eta=eta, lam1=lam1,
                                   lam2=lam2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
