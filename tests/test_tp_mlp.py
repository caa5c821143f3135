"""TP_MLP layer vs single-device golden (reference test/nvidia/test_tp_mlp.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.layers import TPMLP

H, I, M = 64, 128, 16


def golden(params, x):
    wg = np.asarray(params["w_gate"], np.float32)
    wu = np.asarray(params["w_up"], np.float32)
    wd = np.asarray(params["w_down"], np.float32)
    xf = np.asarray(x, np.float32)
    gate = xf @ wg
    act = (gate / (1 + np.exp(-gate))) * (xf @ wu)
    return act.astype(np.float32) @ wd


@pytest.fixture()
def mlp(mesh8):
    return TPMLP(H, I, mesh=mesh8, dtype=jnp.float32)


@pytest.fixture()
def setup(mlp, key):
    params = mlp.init(key)
    x = jax.random.normal(jax.random.PRNGKey(7), (M, H), jnp.float32)
    return params, x, golden(params, x)


@pytest.mark.parametrize("mode", ["xla", "ag_rs", "xla_ar", "gemm_ar"])
def test_tp_mlp_modes(mlp, setup, mode):
    params, x, ref = setup
    out = mlp(params, x, mode=mode)
    np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                               rtol=2e-4, atol=2e-4)


def test_modes_agree(mlp, setup):
    params, x, _ = setup
    a = mlp(params, x, mode="xla")
    b = mlp(params, x, mode="ag_rs")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("m,h,i", [(16, 64, 128), (40, 72, 144)])
def test_tp_mlp_shape_dtype_sweep(mesh8, key, dtype, m, h, i):
    """Reference test_tp_mlp.py sweeps (M, dtype) per fwd mode; the
    second shape is deliberately non-tile-aligned (M=40, H=72)."""
    mlp = TPMLP(h, i, mesh=mesh8, dtype=dtype)
    params = mlp.init(key)
    x = jax.random.normal(jax.random.PRNGKey(9), (m, h), dtype)
    ref = golden(params, x)
    tol = 2e-4 if dtype == jnp.float32 else 8e-2
    for mode in ("xla", "ag_rs", "xla_ar", "gemm_ar"):
        out = mlp(params, x, mode=mode)
        assert out.dtype == dtype and out.shape == (m, h)
        np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                                   rtol=tol, atol=tol * 8,
                                   err_msg=f"mode={mode}")


def test_tp_mlp_grads_fused_vs_xla(mesh8, key):
    """Layer-level grad parity: the fused ag_rs backward (transpose
    kernels, ops/autodiff.py) must match the xla-collective backward."""
    mlp = TPMLP(H, I, mesh=mesh8, dtype=jnp.float32)
    params = mlp.init(key)
    x = jax.random.normal(jax.random.PRNGKey(11), (M, H), jnp.float32)

    def loss(p, mode):
        y = mlp(p, x, mode=mode).astype(jnp.float32)
        return jnp.mean(y * y)

    g_ref = jax.grad(lambda p: loss(p, "xla"))(params)
    g_fused = jax.grad(lambda p: loss(p, "ag_rs"))(params)
    for name in g_ref:
        np.testing.assert_allclose(
            np.asarray(g_fused[name]), np.asarray(g_ref[name]),
            rtol=1e-4, atol=1e-4, err_msg=name)


def test_tp_mlp_set_fwd_roundtrip(mlp, setup):
    """set_fwd switches the default mode (reference TP_MLP.set_fwd)."""
    params, x, ref = setup
    mlp.set_fwd("gemm_ar")
    out = mlp(params, x)
    np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                               rtol=2e-4, atol=2e-4)
    mlp.set_fwd("ag_rs")
