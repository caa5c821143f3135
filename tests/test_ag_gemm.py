"""Fused AG-GEMM / GEMM-RS / GEMM-AR tests vs XLA goldens (reference
analogs: test_ag_gemm.py:72-197, test_gemm_rs.py, test_gemm_ar.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from triton_dist_tpu.ops.allgather_gemm import ag_gemm, create_ag_gemm_context
from triton_dist_tpu.ops.gemm_reduce_scatter import (
    create_gemm_rs_context, gemm_ar, gemm_rs)
from triton_dist_tpu.runtime.utils import assert_allclose

#: Heavy interpret-mode numerics -> full tier only (quick tier: pytest -m 'not slow').
pytestmark = pytest.mark.slow

WORLD = 8
M, K, N = 64, 32, 256   # per-device: (8, 32) x (32, 32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ag_gemm(mesh8, key, dtype):
    ka, kb = jax.random.split(key)
    a = (jax.random.normal(ka, (M, K)) / 4).astype(dtype)
    b = (jax.random.normal(kb, (K, N)) / 4).astype(dtype)
    ctx = create_ag_gemm_context(mesh8)
    got = ag_gemm(a, b, ctx, impl="pallas")
    ref = ag_gemm(a, b, ctx, impl="xla")
    assert got.shape == (M, N)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    assert_allclose(got, ref, rtol=tol, atol=tol)
    # analytic golden
    full = np.asarray(a, np.float32) @ np.asarray(b, np.float32)
    assert_allclose(got, full, rtol=2e-2, atol=2e-1)


def test_ag_gemm_return_gathered(mesh8, key):
    ka, kb = jax.random.split(key)
    a = (jax.random.normal(ka, (M, K)) / 4).astype(jnp.float32)
    b = (jax.random.normal(kb, (K, N)) / 4).astype(jnp.float32)
    ctx = create_ag_gemm_context(mesh8, return_gathered=True)
    c, ag = ag_gemm(a, b, ctx, impl="pallas")
    assert c.shape == (M, N)
    ag = np.asarray(ag).reshape(WORLD, M, K)
    for d in range(WORLD):
        assert np.array_equal(ag[d], np.asarray(a)), f"device {d}"


@pytest.mark.parametrize("dtype", [jnp.float32])
def test_gemm_rs(mesh8, key, dtype):
    ka, kb = jax.random.split(key)
    a = (jax.random.normal(ka, (M, K)) / 4).astype(dtype)   # col-sharded
    b = (jax.random.normal(kb, (K, N)) / 4).astype(dtype)   # row-sharded
    ctx = create_gemm_rs_context(mesh8)
    got = gemm_rs(a, b, ctx, impl="pallas")
    ref = gemm_rs(a, b, ctx, impl="xla")
    assert got.shape == (M, N)
    assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
    full = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    assert_allclose(got, full, rtol=1e-3, atol=1e-3)


def test_gemm_ar(mesh8, key):
    ka, kb = jax.random.split(key)
    a = (jax.random.normal(ka, (M, K)) / 4).astype(jnp.float32)
    b = (jax.random.normal(kb, (K, N)) / 4).astype(jnp.float32)
    ctx = create_gemm_rs_context(mesh8)
    got = gemm_ar(a, b, ctx, impl="pallas")
    assert got.shape == (M, N)
    full = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    assert_allclose(got, full, rtol=1e-3, atol=1e-3)


def test_ag_gemm_hbm_variant(mesh8, key):
    """HBM-resident tiled kernel matches the golden (large-shape path)."""
    from triton_dist_tpu.ops.allgather_gemm import ag_gemm_multi
    m, k, n = 32, 256, 256
    a = jax.device_put(jax.random.normal(key, (m, k), jnp.float32),
                       jax.sharding.NamedSharding(
                           mesh8, jax.sharding.PartitionSpec("tp")))
    b1 = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32),
        jax.sharding.NamedSharding(
            mesh8, jax.sharding.PartitionSpec(None, "tp")))
    b2 = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(2), (k, n // 2), jnp.float32),
        jax.sharding.NamedSharding(
            mesh8, jax.sharding.PartitionSpec(None, "tp")))
    ctx = create_ag_gemm_context(mesh8, "tp")
    ctx.variant = "hbm"
    ctx.block_k = 64
    ctx.block_m = 4
    outs = ag_gemm_multi(a, [b1, b2], ctx, impl="pallas")
    golds = ag_gemm_multi(a, [b1, b2], ctx, impl="xla")
    for o, g in zip(outs, golds):
        np.testing.assert_allclose(np.asarray(o), np.asarray(g),
                                   rtol=1e-4, atol=1e-4)


def test_ag_gemm_hbm_kt_variant(mesh8, key):
    """k-tiled fallback kernel (huge-K path) matches the golden."""
    from triton_dist_tpu.ops.allgather_gemm import ag_gemm_multi
    m, k, n = 32, 256, 256
    a = jax.device_put(jax.random.normal(key, (m, k), jnp.float32),
                       NamedSharding(mesh8, P("tp")))
    b1 = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32),
        NamedSharding(mesh8, P(None, "tp")))
    ctx = create_ag_gemm_context(mesh8, "tp")
    ctx.variant = "hbm_kt"
    ctx.block_k = 64
    ctx.block_m = 4
    outs = ag_gemm_multi(a, [b1], ctx, impl="pallas")
    golds = ag_gemm_multi(a, [b1], ctx, impl="xla")
    for o, g in zip(outs, golds):
        np.testing.assert_allclose(np.asarray(o), np.asarray(g),
                                   rtol=1e-4, atol=1e-4)


def test_gemm_ar_hbm_variant(mesh8, key):
    """N-blocked hbm GEMM-AR (ring-AG epilogue over the HBM output)
    matches the replicated golden (VERDICT r2 weak 8: decode GEMM-AR at
    production widths must not need VMEM residency)."""
    from triton_dist_tpu.ops.gemm_reduce_scatter import (
        create_gemm_rs_context, gemm_ar)
    m, k, n = 64, 128, 256
    ctx = create_gemm_rs_context(mesh8, "tp")
    ctx.variant = "hbm"
    ctx.block_m, ctx.block_n = 8, 128
    a = jax.random.normal(key, (m, k), jnp.float32) / 4
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32) / 4
    a_s = jax.device_put(a, NamedSharding(mesh8, P(None, "tp")))
    b_s = jax.device_put(b, NamedSharding(mesh8, P("tp")))
    out = gemm_ar(a_s, b_s, ctx, impl="pallas")
    assert out.shape == (m, n)
    full = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    np.testing.assert_allclose(np.asarray(out), full, rtol=1e-3, atol=1e-3)


def test_gemm_rs_hbm_kt_variant(mesh8, key):
    """k-tiled GEMM-RS fallback matches the xla golden."""
    from triton_dist_tpu.ops.gemm_reduce_scatter import (
        create_gemm_rs_context, gemm_rs)
    m, k, n = 64, 128, 256
    ctx = create_gemm_rs_context(mesh8, "tp")
    ctx.variant = "hbm_kt"
    ctx.block_m, ctx.block_k = 8, 8
    a = jax.random.normal(key, (m, k), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
    a_s = jax.device_put(a, NamedSharding(mesh8, P(None, "tp")))
    b_s = jax.device_put(b, NamedSharding(mesh8, P("tp")))
    out = gemm_rs(a_s, b_s, ctx, impl="pallas")
    ref = gemm_rs(a_s, b_s, create_gemm_rs_context(mesh8, "tp"),
                  impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ag_gemm_jit_grad_composes(mesh8, key):
    """The fused op must compose under jit; the XLA impl must also be
    differentiable (training use beyond the reference's inference-only
    scope)."""
    ka, kb = jax.random.split(key)
    a = (jax.random.normal(ka, (M, K)) / 4).astype(jnp.float32)
    b = (jax.random.normal(kb, (K, N)) / 4).astype(jnp.float32)
    ctx = create_ag_gemm_context(mesh8)

    @jax.jit
    def f(a, b):
        return ag_gemm(a, b, ctx, impl="pallas").sum()

    @jax.jit
    def g(a, b):
        return ag_gemm(a, b, ctx, impl="xla").sum()

    assert_allclose(f(a, b), g(a, b), rtol=1e-4, atol=1e-2)
    da = jax.grad(lambda a, b: ag_gemm(a, b, ctx, impl="xla").sum(),
                  argnums=0)(a, b)
    assert da.shape == a.shape


def test_gemm_rs_hbm_variant(mesh8, key):
    """HBM-streaming GEMM-RS (tiled K/M loops, travelling partials in
    HBM) matches the xla golden."""
    from triton_dist_tpu.ops.gemm_reduce_scatter import (
        create_gemm_rs_context, gemm_rs)
    m, k, n = 64, 128, 256
    ctx = create_gemm_rs_context(mesh8, "tp")
    ctx.variant = "hbm"
    ctx.block_m, ctx.block_k = 8, 8
    a = jax.random.normal(key, (m, k), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
    a_s = jax.device_put(a, NamedSharding(mesh8, P(None, "tp")))
    b_s = jax.device_put(b, NamedSharding(mesh8, P("tp")))
    out = gemm_rs(a_s, b_s, ctx, impl="pallas")
    ref = gemm_rs(a_s, b_s, create_gemm_rs_context(mesh8, "tp"),
                  impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ag_gemm_autotune_caches(mesh8, key):
    """Autotune sweeps the config table on the first eager call and
    caches the winner by shape (VERDICT r1 item 5)."""
    from triton_dist_tpu.ops import allgather_gemm as agm
    m, k, n = 32, 64, 128
    ctx = agm.create_ag_gemm_context(mesh8, "tp")
    ctx.autotune = True
    a = jax.random.normal(key, (m, k), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
    a_s = jax.device_put(a, NamedSharding(mesh8, P("tp")))
    b_s = jax.device_put(b, NamedSharding(mesh8, P(None, "tp")))
    agm._TUNED.clear()
    out = agm.ag_gemm(a_s, b_s, ctx, impl="pallas")
    key_ = (m, k, n // 8, "float32", 8)
    assert key_ in agm._TUNED, agm._TUNED
    cfg = agm._TUNED[key_]
    assert cfg["variant"] in ("vmem", "hbm")
    ref = agm.ag_gemm(a_s, b_s, agm.create_ag_gemm_context(mesh8, "tp"),
                      impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    # jitted call reuses the cache (no eager sweep possible inside trace)
    out2 = jax.jit(lambda x, w: agm.ag_gemm(x, w, ctx))(a_s, b_s)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_gemm_rs_configs_table():
    from triton_dist_tpu.ops.gemm_reduce_scatter import gemm_rs_configs
    cfgs = gemm_rs_configs(2048, 2048, 4096, 4096, 2, 1)
    # too big for vmem; N-blocked hbm configs ranked before the k-tiled
    # fallback
    assert all(c["variant"] in ("hbm", "hbm_kt") for c in cfgs)
    assert cfgs[0]["variant"] == "hbm"
    assert len(cfgs) >= 1
    cfgs2 = gemm_rs_configs(2048, 2048, 4096, 1024, 2, 1)
    assert len(cfgs2) >= 2  # smaller N admits several tilings
    small = gemm_rs_configs(64, 8, 16, 32, 4, 8)
    assert small[0]["variant"] == "vmem"


def test_aggressive_blocks_reach_kernel_unclamped(mesh8, key, monkeypatch):
    """Blocks with a footprint between the soft vmem_budget and
    HARD_FOOTPRINT_CAP must be HONORED — this is how the config table's
    aggressive tier reaches Mosaic at all (review r5i finding 1: a
    soft-budget clamp silently rewrote every swept aggressive config
    back to the budget kernel, so the tier benchmarked duplicates).
    Blocks beyond the hard cap must still be clamped to an in-budget
    config (an uncompilable config never reaches the compiler). Budgets are shrunk so 'aggressive' stays tiny in
    interpret mode."""
    import triton_dist_tpu.ops.allgather_gemm as agm

    seen = []
    seen_kt = []
    orig = agm._ag_gemm_hbm_nb_kernel
    orig_kt = agm._ag_gemm_hbm_kernel

    def spy(*a, **kw):
        seen.append((kw["m_blk"], kw["n_blk"]))
        return orig(*a, **kw)

    def spy_kt(*a, **kw):
        seen_kt.append((kw["m_blk"], kw["k_blk"]))
        return orig_kt(*a, **kw)

    monkeypatch.setattr(agm, "_ag_gemm_hbm_nb_kernel", spy)
    monkeypatch.setattr(agm, "_ag_gemm_hbm_kernel", spy_kt)

    m, k, n = 64, 32, 256
    a = (jax.random.normal(key, (m, k)) / 4).astype(jnp.float32)
    b = (jax.random.normal(jax.random.PRNGKey(1), (k, n)) / 4
         ).astype(jnp.float32)
    a_s = jax.device_put(a, NamedSharding(mesh8, P("tp")))
    b_s = jax.device_put(b, NamedSharding(mesh8, P(None, "tp")))
    golden = np.asarray(a, np.float64) @ np.asarray(b, np.float64)

    # rows=8, n_loc=32, fp(8, 32) = 4*(2*8*32 + 2*32*32 + 2*8*32) = 12 KB
    ctx = create_ag_gemm_context(mesh8)
    ctx.variant = "hbm"
    ctx.block_m, ctx.block_n = 8, 32
    ctx.vmem_budget = 8 * 1024          # over-budget...
    assert agm._hbm_footprint(8, 32, k, 4) > ctx.vmem_budget

    # Without trust_blocks (default path), the soft-budget clamp holds:
    # no in-budget hbm config exists, so the entry degrades to hbm_kt.
    out = agm.ag_gemm(a_s, b_s, ctx, impl="pallas")
    np.testing.assert_allclose(np.asarray(out), golden, rtol=1e-3,
                               atol=1e-3)
    assert not seen and seen_kt, "default path honored over-budget blocks"

    # With trust_blocks (how the sweep and tuned winners run), blocks up
    # to HARD_FOOTPRINT_CAP are honored.
    ctx.trust_blocks = True
    out = agm.ag_gemm(a_s, b_s, ctx, impl="pallas")
    np.testing.assert_allclose(np.asarray(out), golden, rtol=1e-3,
                               atol=1e-3)
    assert seen and seen[-1] == (8, 32), "aggressive blocks were clamped"

    # ...but over the hard cap: no in-budget NB config exists at this
    # shrunken budget, so the entry degrades to the k-tiled kernel with
    # SHAPE-CLAMPED blocks (the unclamped 128/256 table fallback used
    # to reach the kernel with block_k > K here: k_tiles = 0 ->
    # ZeroDivisionError in the ring schedule).
    monkeypatch.setattr(agm, "HARD_FOOTPRINT_CAP", 10 * 1024)
    n_nb = len(seen)
    out = agm.ag_gemm(a_s, b_s, ctx, impl="pallas")
    np.testing.assert_allclose(np.asarray(out), golden, rtol=1e-3,
                               atol=1e-3)
    assert len(seen) == n_nb, "over-cap blocks still ran the NB kernel"
    rows = m // 8
    assert seen_kt and seen_kt[-1][0] <= rows and seen_kt[-1][1] <= k, \
        seen_kt


def test_gemm_ar_infeasible_config_degrades(mesh8, key):
    """When no resident-B-panel config fits the VMEM budget, GEMM-AR must
    degrade to the XLA path rather than fall through to the
    full-residency vmem kernel, whose scratch Mosaic would refuse."""
    from triton_dist_tpu.ops.gemm_reduce_scatter import (
        create_gemm_rs_context, gemm_ar)
    m, k, n = 64, 128, 256
    ctx = create_gemm_rs_context(mesh8, "tp")
    ctx.vmem_budget = 1024     # nothing fits -> hbm -> hbm_kt -> xla
    a = jax.random.normal(key, (m, k), jnp.float32) / 4
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32) / 4
    a_s = jax.device_put(a, NamedSharding(mesh8, P(None, "tp")))
    b_s = jax.device_put(b, NamedSharding(mesh8, P("tp")))
    out = gemm_ar(a_s, b_s, ctx, impl="pallas")
    full = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    np.testing.assert_allclose(np.asarray(out), full, rtol=1e-3, atol=1e-3)


class TestAgSwiglu:
    """Fused AG + dual-GEMM + SwiGLU (beyond-reference fusion; the
    reference's TP_MLP runs AG-GEMM then a separate silu-mul,
    tp_mlp.py:147-270)."""

    @staticmethod
    def _golden(a, wg, wu):
        ag = np.asarray(a, np.float32)
        g = ag @ np.asarray(wg, np.float32)
        u = ag @ np.asarray(wu, np.float32)
        return (g / (1 + np.exp(-g))) * u

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_fallback_shape(self, mesh8, key, dtype):
        """Small shards route through the composed fallback."""
        from triton_dist_tpu.ops.allgather_gemm import ag_swiglu
        ka, kg, ku = jax.random.split(key, 3)
        a = (jax.random.normal(ka, (M, K)) / 4).astype(dtype)
        wg = (jax.random.normal(kg, (K, N)) / 4).astype(dtype)
        wu = (jax.random.normal(ku, (K, N)) / 4).astype(dtype)
        ctx = create_ag_gemm_context(mesh8)
        got = ag_swiglu(a, wg, wu, ctx, impl="pallas")
        ref = ag_swiglu(a, wg, wu, ctx, impl="xla")
        assert got.shape == (M, N)
        tol = 1e-5 if dtype == jnp.float32 else 5e-2
        assert_allclose(got, ref, rtol=tol, atol=tol)
        assert_allclose(got, self._golden(a, wg, wu), rtol=2e-2, atol=2e-1)

    def test_kernel_shape(self, mesh8, key):
        """128-divisible shards engage the single fused kernel."""
        from triton_dist_tpu.ops.allgather_gemm import ag_swiglu
        m, k, n = 1024, 64, 1024          # rows=128, n_loc=128
        ka, kg, ku = jax.random.split(key, 3)
        a = (jax.random.normal(ka, (m, k)) / 4).astype(jnp.float32)
        wg = (jax.random.normal(kg, (k, n)) / 4).astype(jnp.float32)
        wu = (jax.random.normal(ku, (k, n)) / 4).astype(jnp.float32)
        ctx = create_ag_gemm_context(mesh8)
        got = ag_swiglu(a, wg, wu, ctx, impl="pallas")
        assert got.shape == (m, n)
        assert_allclose(got, self._golden(a, wg, wu), rtol=1e-3,
                        atol=1e-3)

    def test_grad_parity(self, mesh8, key):
        """VJP grads equal the differentiable composition's."""
        from triton_dist_tpu.ops import autodiff as ad
        ka, kg, ku, kd = jax.random.split(key, 4)
        a = (jax.random.normal(ka, (M, K)) / 4).astype(jnp.float32)
        wg = (jax.random.normal(kg, (K, N)) / 4).astype(jnp.float32)
        wu = (jax.random.normal(ku, (K, N)) / 4).astype(jnp.float32)
        ctx = create_ag_gemm_context(mesh8)

        def fused(a, wg, wu):
            return jnp.sum(ad.ag_swiglu(a, wg, wu, ctx, "pallas") ** 2)

        def composed(a, wg, wu):
            g, u = ad.ag_gemm_multi(a, [wg, wu], ctx, "pallas")
            act = jax.nn.silu(g.astype(jnp.float32)).astype(a.dtype) * u
            return jnp.sum(act.astype(jnp.float32) ** 2)

        gf = jax.grad(fused, argnums=(0, 1, 2))(a, wg, wu)
        gc = jax.grad(composed, argnums=(0, 1, 2))(a, wg, wu)
        for x, y, name in zip(gf, gc, ("da", "dwg", "dwu")):
            assert_allclose(x, y, rtol=2e-3, atol=2e-3)


def test_ag_swiglu_autotune_sweep(mesh8, key):
    """Eager sweep + winner application end-to-end in interpret mode:
    numerics must match the XLA golden and a winner must be cached."""
    import dataclasses as dc
    from triton_dist_tpu.ops import allgather_gemm as agm

    m, k, n = 1024, 128, 2048
    ka, kg, ku = jax.random.split(key, 3)
    a = jax.device_put((jax.random.normal(ka, (m, k)) / 4
                        ).astype(jnp.bfloat16),
                       NamedSharding(mesh8, P("tp")))
    wg = jax.device_put((jax.random.normal(kg, (k, n)) / 4
                         ).astype(jnp.bfloat16),
                        NamedSharding(mesh8, P(None, "tp")))
    wu = jax.device_put((jax.random.normal(ku, (k, n)) / 4
                         ).astype(jnp.bfloat16),
                        NamedSharding(mesh8, P(None, "tp")))
    ctx = dc.replace(agm.create_ag_gemm_context(mesh8), autotune=True)
    got = agm.ag_swiglu(a, wg, wu, ctx, impl="pallas")
    ref = agm.ag_swiglu(a, wg, wu, dc.replace(ctx, autotune=False),
                        impl="xla")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)
    assert any(kk[-1] == "swiglu" for kk in agm._TUNED), agm._TUNED
