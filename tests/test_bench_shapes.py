"""Bench-config interpret tests (VERDICT r3 next-9).

test_vmem_budget checks that the bench-shape configs FIT; these check
that they COMPUTE CORRECTLY: each fused op runs in interpret mode on the
world=8 mesh with the exact variant + block config its default path
resolves at the per-op sweep's shape (world=1, 2048x4096x4096 bf16), so
a schedule/config regression fails here in CI instead of on the chip
(reference analog: test/nvidia/test_ag_gemm.py:72-197's shape sweep).

Shapes are scaled (K, and N where it only multiplies work) to keep the
interpreter fast, but the BLOCK sizes — what the kernel schedule
actually tiles by — are pinned to the bench-resolved config, and the
per-rank row/column counts keep multiple blocks live per rank.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

#: Heavy interpret-mode numerics -> full tier only (quick tier: pytest -m 'not slow').
pytestmark = pytest.mark.slow

bf16 = jnp.bfloat16


def _mesh8():
    return Mesh(np.array(jax.devices()[:8]), ("tp",))


def _put(mesh, x, spec):
    return jax.device_put(x, NamedSharding(mesh, spec))


def _randn(shape, k=0, dtype=bf16):
    return jax.random.normal(jax.random.PRNGKey(k), shape,
                             jnp.float32).astype(dtype)


def test_ag_gemm_bench_config_numerics():
    from triton_dist_tpu.ops.allgather_gemm import (
        ag_gemm, ag_gemm_configs, create_ag_gemm_context)
    # The config the world=1 bench default path resolves (first feasible
    # table entry at m=2048, rows=2048, k=4096, n_tot_loc=4096).
    cfg = ag_gemm_configs(2048, 2048, 4096, 4096, 2)[0]
    assert cfg["variant"] in ("hbm", "hbm_kt"), cfg
    mesh = _mesh8()
    # Scaled run: keep block sizes; K shrinks (it only multiplies
    # interpreter work), per-rank rows/cols hold >= 1 block.
    k = 512
    m = max(2 * cfg.get("block_m", 128), 256) * 8
    n = 512 * 8
    ctx = create_ag_gemm_context(mesh, "tp", interpret=True)
    ctx = dataclasses.replace(ctx, **cfg)
    a = _put(mesh, _randn((m, k)), P("tp"))
    b = _put(mesh, _randn((k, n), k=1), P(None, "tp"))
    out = ag_gemm(a, b, ctx, impl="pallas")
    ref = ag_gemm(a, b, ctx, impl="xla")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_gemm_rs_bench_config_numerics():
    from triton_dist_tpu.ops.gemm_reduce_scatter import (
        create_gemm_rs_context, gemm_rs, gemm_rs_configs)
    cfg = gemm_rs_configs(2048, 2048, 4096, 4096, 2, 1)[0]
    assert cfg["variant"] in ("hbm", "hbm_kt"), cfg
    mesh = _mesh8()
    bm = cfg.get("block_m", 128)
    m = max(2 * bm, 256) * 8          # rows/rank >= 2 blocks
    k, n = 512 * 8, 512
    ctx = create_gemm_rs_context(mesh, "tp", interpret=True)
    keys = {f.name for f in dataclasses.fields(ctx)}
    ctx = dataclasses.replace(
        ctx, **{kk: v for kk, v in cfg.items() if kk in keys})
    a = _put(mesh, _randn((m, k)), P(None, "tp"))
    b = _put(mesh, _randn((k, n), k=1), P("tp"))
    out = gemm_rs(a, b, ctx, impl="pallas")
    ref = gemm_rs(a, b, ctx, impl="xla")
    # K = 4096 here: |out| ~ 128, so the bf16 output quantization step
    # is ~1.0 — atol covers two ulps at that magnitude (the pallas and
    # xla paths partition the contraction differently, and with the
    # 24 MB-budget default tiles a lone element can land two roundings
    # apart: observed 1/2^21 elements past one ulp).
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=2.0)


def test_ag_swiglu_bench_blocks_numerics():
    """The tp_mlp bench line rides ag_swiglu; same block-pinning check
    (golden: the xla shard_map MLP front half)."""
    from triton_dist_tpu.ops.allgather_gemm import (
        ag_swiglu, create_ag_gemm_context)
    mesh = _mesh8()
    m, k, n = 256 * 8, 512, 512 * 8
    ctx = create_ag_gemm_context(mesh, "tp", interpret=True)
    x = _put(mesh, _randn((m, k)), P("tp"))
    wg = _put(mesh, _randn((k, n), k=1), P(None, "tp"))
    wu = _put(mesh, _randn((k, n), k=2), P(None, "tp"))
    act = ag_swiglu(x, wg, wu, ctx, impl="pallas")

    def body(xs, g, u):
        from jax import lax
        ag = lax.all_gather(xs, "tp", tiled=True)
        gate = jnp.dot(ag, g, preferred_element_type=jnp.float32)
        up = jnp.dot(ag, u, preferred_element_type=jnp.float32)
        return (jax.nn.silu(gate) * up).astype(xs.dtype)
    from triton_dist_tpu.ops.common import nestable_shard_map
    ref = nestable_shard_map(
        body, mesh=mesh, in_specs=(P("tp"), P(None, "tp"), P(None, "tp")),
        out_specs=P(None, "tp"), check_vma=False)(x, wg, wu)
    np.testing.assert_allclose(np.asarray(act, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("t_blk", [512, 1024])
def test_flash_decode_bench_tblk_numerics(t_blk):
    """The serving-shape flash-decode line's tiled variant at the bench
    t_blk values, world=8 (cross-rank LSE combine live)."""
    from triton_dist_tpu.ops.flash_decode import (
        create_flash_decode_context, gqa_fwd_batch_decode)
    mesh = _mesh8()
    b, hq, hkv, d, t = 2, 32, 8, 64, 8 * 2 * t_blk // 4
    ctx = create_flash_decode_context(mesh, "tp", variant="tiled",
                                      t_blk=t_blk // 4, interpret=True)
    q = _randn((b, hq, d))
    kc = _put(mesh, _randn((b, t, hkv, d), k=1), P(None, "tp"))
    vc = _put(mesh, _randn((b, t, hkv, d), k=2), P(None, "tp"))
    out = gqa_fwd_batch_decode(q, kc, vc, jnp.int32(t - 5), ctx,
                               impl="pallas")
    ref = gqa_fwd_batch_decode(q, kc, vc, jnp.int32(t - 5), ctx,
                               impl="xla")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)
