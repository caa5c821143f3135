"""Fused ops at deliberately awkward shapes (VERDICT r2 #10: round-shape
tests at M=64/K=32 miss tile-clamp and tail bugs).

Every case uses dimensions that are NOT multiples of the preferred
128/256/512 tiles, so the divisor-clamping (`_pick_block_k`), config
fallback, and padding paths all execute. Goldens are the ops' own
``impl="xla"`` bodies (reference analog: per-shape sweep loops in
test/nvidia/test_ag_gemm.py:72-197).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from triton_dist_tpu.ops.allgather_gemm import (
    ag_gemm, ag_swiglu, create_ag_gemm_context)
from triton_dist_tpu.ops.gemm_reduce_scatter import (
    create_gemm_rs_context, gemm_ar, gemm_rs)
from triton_dist_tpu.runtime.utils import assert_allclose

#: Heavy interpret-mode numerics -> full tier only (quick tier: pytest -m 'not slow').
pytestmark = pytest.mark.slow

WORLD = 8


@pytest.mark.parametrize("variant", ["vmem", "hbm", "hbm_kt"])
@pytest.mark.parametrize("m,k,n", [(192, 96, 160), (24, 40, 48)])
def test_ag_gemm_odd(mesh8, key, variant, m, k, n):
    ka, kb = jax.random.split(key)
    a = (jax.random.normal(ka, (m, k)) / 4).astype(jnp.float32)
    b = (jax.random.normal(kb, (k, n)) / 4).astype(jnp.float32)
    ctx = dataclasses.replace(create_ag_gemm_context(mesh8),
                              variant=variant)
    got = ag_gemm(a, b, ctx, impl="pallas")
    ref = ag_gemm(a, b, ctx, impl="xla")
    assert got.shape == (m, n)
    assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
    full = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    assert_allclose(got, full, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("variant", ["vmem", "hbm"])
def test_gemm_rs_odd(mesh8, key, variant):
    m, k, n = 136, 72, 104     # none 128-multiples; m/world = 17 rows
    ka, kb = jax.random.split(key)
    a = (jax.random.normal(ka, (m, k)) / 4).astype(jnp.float32)
    b = (jax.random.normal(kb, (k, n)) / 4).astype(jnp.float32)
    ctx = dataclasses.replace(create_gemm_rs_context(mesh8),
                              variant=variant)
    got = gemm_rs(a, b, ctx, impl="pallas")
    ref = gemm_rs(a, b, ctx, impl="xla")
    assert got.shape == (m, n)
    assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_gemm_ar_nondivisible_m(mesh8, key):
    # M=100 is not divisible by world=8: exercises the zero-pad + slice
    # path (the reference's tile-padded GEMM grids).
    m, k, n = 100, 48, 56
    ka, kb = jax.random.split(key)
    a = (jax.random.normal(ka, (m, k)) / 4).astype(jnp.float32)
    b = (jax.random.normal(kb, (k, n)) / 4).astype(jnp.float32)
    ctx = create_gemm_rs_context(mesh8)
    got = gemm_ar(a, b, ctx, impl="pallas")
    ref = gemm_ar(a, b, ctx, impl="xla")
    assert got.shape == (m, n)
    assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_ag_swiglu_odd(mesh8, key):
    m, h, inter = 48, 56, 80    # inter/world = 10 cols per shard
    ka, kg, ku = jax.random.split(key, 3)
    x = (jax.random.normal(ka, (m, h)) / 4).astype(jnp.float32)
    wg = (jax.random.normal(kg, (h, inter)) / 4).astype(jnp.float32)
    wu = (jax.random.normal(ku, (h, inter)) / 4).astype(jnp.float32)
    ctx = create_ag_gemm_context(mesh8)
    got = ag_swiglu(x, wg, wu, ctx, impl="pallas")
    ref = ag_swiglu(x, wg, wu, ctx, impl="xla")
    assert got.shape == ref.shape
    assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_flash_decode_partial_tail(mesh8, key):
    # kv_len lands mid-tile AND mid-rank: live tiles are a strict prefix
    # on early ranks, zero on late ranks (split-KV early-exit).
    from triton_dist_tpu.ops.flash_decode import (
        create_flash_decode_context, gqa_fwd_batch_decode)
    b, hq, hkv, d = 2, 8, 2, 64
    t_loc = 96                 # not a t_blk multiple after clamping
    ctx = dataclasses.replace(
        create_flash_decode_context(mesh8, axis="tp", variant="tiled"),
        t_blk=64)
    kq, kk, kv = jax.random.split(key, 3)
    q = (jax.random.normal(kq, (b, hq, d)) / 4).astype(jnp.bfloat16)
    k = (jax.random.normal(kk, (b, WORLD * t_loc, hkv, d)) / 4
         ).astype(jnp.bfloat16)
    v = (jax.random.normal(kv, (b, WORLD * t_loc, hkv, d)) / 4
         ).astype(jnp.bfloat16)
    kv_len = 3 * t_loc + 17    # rank 3 partial, ranks 4..7 empty
    got = gqa_fwd_batch_decode(q, k, v, kv_len, ctx)
    ctx_e = dataclasses.replace(ctx, variant="einsum")
    ref = gqa_fwd_batch_decode(q, k, v, kv_len, ctx_e)
    assert_allclose(got, ref, rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("method", ["one_shot", "two_shot",
                                    "recursive_doubling"])
def test_allreduce_odd_partials(mesh8, key, method):
    # (w, 136, 72): M=136 does not split into 8 chunks of whole row
    # tiles, so TWO_SHOT pads its row split; the others take it directly.
    from triton_dist_tpu.ops.allreduce import (
        AllReduceMethod, create_allreduce_context, all_reduce)
    x = (jax.random.normal(key, (WORLD, 136, 72)) / 4).astype(jnp.float32)
    ctx = create_allreduce_context(mesh8, "tp",
                                   method=AllReduceMethod(method))
    got = all_reduce(x, ctx, impl="pallas")
    ref = all_reduce(x, ctx, impl="xla")
    assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_a2a_unaligned_capacity(mesh8, key):
    # capacity=12 has no sublane-aligned divisor -> chunk falls back to
    # the full slab; live-count masking still must hold.
    from triton_dist_tpu.ops.all_to_all import (
        create_all_to_all_context, fast_all_to_all)
    cap, h = 12, 128
    ctx = create_all_to_all_context(mesh8, "tp", capacity=cap)
    buf = jax.random.normal(key, (WORLD * WORLD, cap, h), jnp.float32)
    counts = jax.random.randint(jax.random.PRNGKey(1), (WORLD * WORLD,),
                                0, cap + 1, jnp.int32)
    bufs = jax.device_put(buf, NamedSharding(mesh8, P("tp")))
    counts_s = jax.device_put(counts, NamedSharding(mesh8, P("tp")))
    recv, rc = fast_all_to_all(bufs, counts_s, ctx, impl="pallas")
    ref, rc2 = fast_all_to_all(bufs, counts_s, ctx, impl="xla")
    recv = np.asarray(recv).reshape(WORLD, WORLD, cap, h)
    ref = np.asarray(ref).reshape(WORLD, WORLD, cap, h)
    rcn = np.asarray(rc).reshape(WORLD, WORLD)
    np.testing.assert_array_equal(np.asarray(rc), np.asarray(rc2))
    for dst in range(WORLD):
        for src in range(WORLD):
            n = rcn[dst, src]
            np.testing.assert_array_equal(recv[dst, src, :n],
                                          ref[dst, src, :n])


def test_hierarchical_nd_odd_payload(key):
    # 2x2x2 mesh with a (24, 40) payload — no 128-multiples anywhere.
    import numpy as _np
    from jax.sharding import Mesh
    from triton_dist_tpu.ops.hierarchical import (
        all_gather_nd, all_reduce_nd)
    devs = jax.devices()
    mesh = Mesh(_np.array(devs).reshape(2, 2, 2), ("x", "y", "z"))
    x = (jax.random.normal(key, (24, 40)) / 4).astype(jnp.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P("x")))
    ag = all_gather_nd(xs, mesh, ("x",))
    np.testing.assert_allclose(np.asarray(ag)[:24], np.asarray(x),
                               rtol=0, atol=0)
    # all_reduce_nd sums the per-device views of a replicated input
    # (in_specs=P(); see test_hierarchical.py) — replicated x sums to
    # 8*x. The odd (24, 40) payload stresses the RS-ladder slicing
    # (24 -> 12 -> 6 rows down the x/y rungs).
    ar = all_reduce_nd(x, mesh, ("x", "y", "z"))
    np.testing.assert_allclose(np.asarray(ar), 8 * np.asarray(x),
                               rtol=1e-5, atol=1e-5)


def test_flash_decode_per_row_lengths(mesh8, key):
    """Per-sequence kv lengths (reference kv_length_ptr + bid): each
    row masked to its own length must equal serving that row alone with
    a scalar length."""
    from triton_dist_tpu.ops.flash_decode import (
        create_flash_decode_context, gqa_fwd_batch_decode)
    b, hq, hkv, d, t_loc = 4, 8, 2, 64, 64
    t = WORLD * t_loc
    kq, kk, kv = jax.random.split(key, 3)
    q = (jax.random.normal(kq, (b, hq, d)) / 4).astype(jnp.bfloat16)
    k = (jax.random.normal(kk, (b, t, hkv, d)) / 4).astype(jnp.bfloat16)
    v = (jax.random.normal(kv, (b, t, hkv, d)) / 4).astype(jnp.bfloat16)
    lens = jnp.asarray([t, t // 2 + 3, 17, t_loc], jnp.int32)
    for variant in ("einsum", "tiled"):
        ctx = dataclasses.replace(
            create_flash_decode_context(mesh8, axis="tp",
                                        variant=variant), t_blk=32)
        got = gqa_fwd_batch_decode(q, k, v, lens, ctx)
        for r in range(b):
            ref = gqa_fwd_batch_decode(
                q[r:r + 1], k[r:r + 1], v[r:r + 1],
                jnp.int32(lens[r]), ctx)
            assert_allclose(got[r:r + 1], ref, rtol=4e-2, atol=4e-2)


def test_sp_attention_pallas_odd_block_shrink(mesh8, key):
    # s_loc=160 forces both sq_blk and t_sub to shrink (128 -> 32) via
    # the divisor loops; checks the clamped tiling end-to-end.
    from triton_dist_tpu.ops.sp_attention import (
        create_sp_attention_context, sp_ag_attention)
    b, s, hq, hkv, d = 1, WORLD * 160, 4, 2, 64
    ctx = create_sp_attention_context(mesh8, axis="tp", causal=True)
    kq, kk, kv = jax.random.split(key, 3)
    q = (jax.random.normal(kq, (b, s, hq, d)) / 4).astype(jnp.bfloat16)
    k = (jax.random.normal(kk, (b, s, hkv, d)) / 4).astype(jnp.bfloat16)
    v = (jax.random.normal(kv, (b, s, hkv, d)) / 4).astype(jnp.bfloat16)
    got = sp_ag_attention(q, k, v, ctx, impl="pallas")
    ref = sp_ag_attention(q, k, v, ctx, impl="xla")
    assert_allclose(got, ref, rtol=5e-2, atol=5e-2)
