"""Bench-shape VMEM-budget checks.

A default config whose declared VMEM scratch the chip's compiler
refuses must fail HERE, in CI on any host, not on the chip. The gate asserts against HARD_FOOTPRINT_CAP (26 MB declared):
the library's comm kernels request a 64 MB Mosaic scoped-VMEM limit via
``comm_params`` and Mosaic's scoped accounting carries ~2.2x overhead
over declared buffers (constants in ops/common.py).
A kernel built WITHOUT ``comm_params`` keeps Mosaic's 16 MB default and
needs the tighter ``limit=`` argument. ``check_entry_vmem`` traces each
op's ``impl="pallas"`` entry at the shapes the chip is given with
``jax.eval_shape`` (no execution) and asserts the static footprint of
every ``pallas_call`` it contains. World=1 (the bench environment) and
world=8 are both checked: round 2's failure was world=1-specific
(n_loc = N, the largest B panel).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.testing.vmem import (
    VmemBudgetError, assert_vmem_within, check_entry_vmem)

bf16 = jnp.bfloat16


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("tp",))


@pytest.mark.parametrize("world", [1, 8])
def test_ag_gemm_bench_shape_fits(world):
    from triton_dist_tpu.ops.allgather_gemm import (
        create_ag_gemm_context, ag_gemm)
    mesh = _mesh(world)
    ctx = create_ag_gemm_context(mesh, "tp", interpret=True)
    m, k, n = 2048, 4096, 4096  # the per-op sweep's shape
    check_entry_vmem(
        lambda a, b: ag_gemm(a, b, ctx, impl="pallas"),
        jax.ShapeDtypeStruct((m, k), bf16),
        jax.ShapeDtypeStruct((k, n), bf16))


@pytest.mark.parametrize("world", [1, 8])
def test_gemm_rs_bench_shape_fits(world):
    from triton_dist_tpu.ops.gemm_reduce_scatter import (
        create_gemm_rs_context, gemm_rs)
    mesh = _mesh(world)
    ctx = create_gemm_rs_context(mesh, "tp", interpret=True)
    m, k, n = 2048, 4096, 4096
    check_entry_vmem(
        lambda a, b: gemm_rs(a, b, ctx, impl="pallas"),
        jax.ShapeDtypeStruct((m, k), bf16),
        jax.ShapeDtypeStruct((k, n), bf16))


@pytest.mark.parametrize("world", [1, 8])
def test_gemm_ar_bench_shape_fits(world):
    from triton_dist_tpu.ops.gemm_reduce_scatter import (
        create_gemm_rs_context, gemm_ar)
    mesh = _mesh(world)
    ctx = create_gemm_rs_context(mesh, "tp", interpret=True)
    m, k, n = 128, 4096, 4096  # decode GEMM-AR bench shape
    check_entry_vmem(
        lambda a, b: gemm_ar(a, b, ctx, impl="pallas"),
        jax.ShapeDtypeStruct((m, k), bf16),
        jax.ShapeDtypeStruct((k, n), bf16))


@pytest.mark.parametrize("world", [1, 8])
def test_flash_decode_serving_shape_fits(world):
    from triton_dist_tpu.ops.flash_decode import (
        create_flash_decode_context, gqa_fwd_batch_decode)
    mesh = _mesh(world)
    ctx = create_flash_decode_context(mesh, "tp", interpret=True,
                                      variant="tiled", t_blk=512)
    b, hq, hkv, d, t = 8, 32, 8, 128, 8192  # serving shape
    check_entry_vmem(
        lambda q, kc, vc, n: gqa_fwd_batch_decode(q, kc, vc, n, ctx,
                                                  impl="pallas"),
        jax.ShapeDtypeStruct((b, hq, d), bf16),
        jax.ShapeDtypeStruct((b, t, hkv, d), bf16),
        jax.ShapeDtypeStruct((b, t, hkv, d), bf16),
        jax.ShapeDtypeStruct((), jnp.int32))


def test_sp_attention_fused_prefill_shape_fits():
    """The fused SP kernel streams q in resident groups, so ANY prefill
    shape must fit the budget — checked at a realistic distributed
    shape (16k positions over 8 ranks)."""
    from triton_dist_tpu.ops.sp_attention import (
        create_sp_attention_context, sp_ag_attention_fused)
    mesh = _mesh(8)
    ctx = create_sp_attention_context(mesh, "tp", causal=True,
                                      interpret=True)
    b, s, hq, hkv, d = 1, 16384, 8, 2, 128   # s_loc = 2048
    check_entry_vmem(
        lambda q, k, v: sp_ag_attention_fused(q, k, v, ctx),
        jax.ShapeDtypeStruct((b, s, hq, d), bf16),
        jax.ShapeDtypeStruct((b, s, hkv, d), bf16),
        jax.ShapeDtypeStruct((b, s, hkv, d), bf16))


def test_sp_attention_fused_bench_shape_fits():
    """The sp_attn shape at world=1 (s_loc=4096, hq=16): q +
    state total ~50 MB — the q-group residency must bound what reaches
    VMEM."""
    from triton_dist_tpu.ops.sp_attention import (
        create_sp_attention_context, sp_ag_attention_fused)
    mesh = _mesh(1)
    ctx = create_sp_attention_context(mesh, "tp", causal=True,
                                      interpret=True)
    b, s, hq, hkv, d = 1, 4096, 16, 8, 128
    check_entry_vmem(
        lambda q, k, v: sp_ag_attention_fused(q, k, v, ctx),
        jax.ShapeDtypeStruct((b, s, hq, d), bf16),
        jax.ShapeDtypeStruct((b, s, hkv, d), bf16),
        jax.ShapeDtypeStruct((b, s, hkv, d), bf16))


def test_train_step_bench_config_fits():
    """Trace the WHOLE fused train step (fwd + transpose-kernel bwd +
    optax update) at the train config below and assert every
    pallas_call inside fits — forward gates alone miss the backward's
    transposed shapes (e.g. gemm_rs contractions over inter=8192)."""
    from triton_dist_tpu.models import DenseLLM, ModelConfig
    from triton_dist_tpu.models.train import make_train_step
    mesh = _mesh(1)   # the bench chip
    cfg = ModelConfig(hidden_size=2048, intermediate_size=8192,
                      num_hidden_layers=1,  # layers share kernel shapes
                      num_attention_heads=16, num_key_value_heads=8,
                      head_dim=128, vocab_size=32768,
                      max_position_embeddings=1024, dtype=bf16)
    model = DenseLLM(cfg, mesh=mesh, axis="tp", impl="pallas",
                     fwd_mode="ag_rs")
    for layer in (model.attn, model.mlp):
        layer.ag_ctx.interpret = True
        layer.rs_ctx.interpret = True
    step, init_opt = make_train_step(model, mode="ag_rs", donate=False)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    # Shape-only optimizer state: trace the STEP, not init_opt (which
    # device_puts concrete arrays).
    import optax
    opt_shapes = jax.eval_shape(lambda p: optax.adamw(1e-4).init(p),
                                params)
    batch = {"input_ids": jax.ShapeDtypeStruct((4, 512), jnp.int32)}
    check_entry_vmem(lambda p, o, bt: step(p, o, bt),
                     params, opt_shapes, batch)


def test_vmem_budget_catches_oversized_kernel():
    """The helper itself must detect an oversized kernel — 16.5 MB of
    scratch against a 16 MB cap, reproduced in miniature."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(x_ref, o_ref, big):
        o_ref[:] = x_ref[:]

    def entry(x):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((128, 128), jnp.float32),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((2, 2048, 4096), jnp.float32)],
            interpret=True,
        )(x)

    with pytest.raises(VmemBudgetError):
        with assert_vmem_within(16 * 1024 * 1024):
            jax.eval_shape(entry, jax.ShapeDtypeStruct((128, 128),
                                                       jnp.float32))


def test_vmem_budget_ignores_any_and_semaphores():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(x_hbm, o_hbm, sem):
        pass

    def entry(x):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((8192, 8192), jnp.float32),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA((8,))],
            interpret=True,
        )(x)

    # 256 MB operands in ANY (HBM) space must not trip the VMEM budget.
    with assert_vmem_within(16 * 1024 * 1024):
        jax.eval_shape(entry, jax.ShapeDtypeStruct((8192, 8192),
                                                   jnp.float32))


@pytest.mark.parametrize("world", [1, 8])
def test_ag_group_gemm_fused_bench_shape_fits(world):
    from triton_dist_tpu.ops.group_gemm import (
        create_ag_group_gemm_context, ag_group_gemm)
    mesh = _mesh(world)
    ctx = create_ag_group_gemm_context(mesh, "tp")
    ctx.interpret = True
    m, k, n, e = 2048, 4096, 4096, 8
    check_entry_vmem(
        lambda x, w, ids: ag_group_gemm(x, w, ids, e, ctx, impl="fused"),
        jax.ShapeDtypeStruct((m, k), bf16),
        jax.ShapeDtypeStruct((e, k, n), bf16),
        jax.ShapeDtypeStruct((m,), jnp.int32))


@pytest.mark.parametrize("world", [1, 8])
def test_moe_reduce_rs_fused_bench_shape_fits(world):
    from triton_dist_tpu.ops.moe_reduce_rs import (
        create_moe_rs_context, moe_reduce_rs)
    mesh = _mesh(world)
    t, topk, inter, hid, e = 2048, 2, 4096, 4096, 8
    ctx = create_moe_rs_context(mesh, "tp", num_experts=e, topk=topk)
    ctx.interpret = True
    check_entry_vmem(
        lambda a, w, ids, wts: moe_reduce_rs(a, w, ids, wts, ctx,
                                             impl="fused"),
        jax.ShapeDtypeStruct((t * topk, inter), bf16),
        jax.ShapeDtypeStruct((e, inter, hid), bf16),
        jax.ShapeDtypeStruct((t * topk,), jnp.int32),
        jax.ShapeDtypeStruct((t, topk), jnp.float32))


@pytest.mark.parametrize("world", [1, 8])
def test_ag_swiglu_bench_shape_fits(world):
    from triton_dist_tpu.ops.allgather_gemm import (
        create_ag_gemm_context, ag_swiglu)
    mesh = _mesh(world)
    ctx = create_ag_gemm_context(mesh, "tp", interpret=True)
    m, k = 2048, 4096
    # ag_swiglu takes the GLOBAL weight width (n_loc = n // world
    # inside). Gate (a) the exact width a tp_mlp runs at this
    # world (inter = 12288 // max(n,8) * n → per-chip 1536), and (b) a
    # 12288-global stress width (per-chip 12288 at world=1) so a config
    # that only fits scaled-down stand-ins cannot pass CI (review r3i:
    # the first version of this gate divided by world twice and tested
    # an 8x-smaller kernel than a chip would run).
    for n in (4096, 12288 // max(world, 8) * world,
              3072 * world, 12288):
        check_entry_vmem(
            lambda a, wg, wu: ag_swiglu(a, wg, wu, ctx, impl="pallas"),
            jax.ShapeDtypeStruct((m, k), bf16),
            jax.ShapeDtypeStruct((k, n), bf16),
            jax.ShapeDtypeStruct((k, n), bf16))


@pytest.mark.parametrize("world", [1, 8])
@pytest.mark.parametrize("dims", [
    ("8b", 4096, 4, 1, 128, 1536), ("32b", 5120, 8, 1, 128, 3200)])
def test_layer_bench_dims_fit(world, dims):
    """Decoder-layer dims of Qwen3-8B/32B (per-chip TP8 slice, prefill M=2048
    + decode M=128): every Pallas kernel in the fused decoder-layer
    step must fit the chip budget at both worlds."""
    from triton_dist_tpu.layers import TPAttn, precompute_rope_cache
    from triton_dist_tpu.layers.tp_mlp import TPMLP
    tag, h, nq, nkv, d, inter = dims
    mesh = _mesh(world)
    nq, nkv, inter = nq * world, nkv * world, inter * world
    attn = TPAttn(h, nq, nkv, d, mesh=mesh, axis="tp", dtype=bf16)
    mlp = TPMLP(h, inter, mesh=mesh, axis="tp", dtype=bf16)
    rope = precompute_rope_cache(d, 512)
    pa = jax.eval_shape(attn.init, jax.random.PRNGKey(0))
    pm = jax.eval_shape(mlp.init, jax.random.PRNGKey(1))
    for phase, b, s, mode in (("prefill", 16, 128, "ag_rs"),
                              ("decode", 128, 1, "gemm_ar")):
        m = b * s
        pos = jnp.zeros((b, s), jnp.int32)
        offset = jnp.int32(0 if phase == "prefill" else 256)

        def f(x, pa, pm, kc, vc, mode=mode, pos=pos, offset=offset):
            a_out, _ = attn(pa, x, pos, rope, (kc, vc), offset, mode=mode)
            y = x + a_out
            return y + mlp(pm, y, mode=mode)
        check_entry_vmem(
            f, jax.ShapeDtypeStruct((m, h), bf16), pa, pm,
            jax.ShapeDtypeStruct((b, 512, nkv, d), bf16),
            jax.ShapeDtypeStruct((b, 512, nkv, d), bf16))


def test_ag_swiglu_configs_table():
    from triton_dist_tpu.ops.allgather_gemm import (
        ag_swiglu_configs, _swiglu_footprint)
    from triton_dist_tpu.ops.common import (DEFAULT_VMEM_BUDGET,
                                            HARD_FOOTPRINT_CAP)
    # Bench tp_mlp_big shape class: m=2048, w=1, k=4096, n_loc=3072.
    cfgs = ag_swiglu_configs(2048, 4096, 3072, 2)
    assert cfgs, "no swiglu configs at the bench shape"
    seen = set()
    budget_tier_ended = False
    for c in cfgs:
        bm, bn = c["block_m"], c["block_n"]
        assert 2048 % bm == 0 and 3072 % bn == 0, c
        fp = _swiglu_footprint(bm, bn, 4096, 2)
        assert fp <= HARD_FOOTPRINT_CAP, c
        if fp > DEFAULT_VMEM_BUDGET:
            budget_tier_ended = True
        else:
            # budget-tier entries must all precede aggressive ones
            assert not budget_tier_ended, cfgs
        assert (bm, bn) not in seen
        seen.add((bm, bn))
    # the sweep must have aggressive candidates to explore here
    assert budget_tier_ended, cfgs
    # tiny shard: no feasible kernel tiling -> empty table (entry then
    # composes from ag_gemm_multi), never an invalid config
    assert ag_swiglu_configs(8, 32, 32, 4) == []


@pytest.mark.slow
def test_deep_mega_bench_config_fits():
    """The 32-layer fused mega step at the deep TPU config: every
    pallas_call within the declared cap. Run offline after the round-5
    on-chip mega MosaicError (HTTP 500 during the deep compile): the
    static footprint is clean, so the failure class was Mosaic's old
    16 MB scoped limit (~2.2x overhead over declared — the same class
    that rejected the SP kernel), which comm_params' 64 MB request now
    covers."""
    from triton_dist_tpu.mega import MegaQwen3
    from triton_dist_tpu.models import DenseLLM, ModelConfig
    from triton_dist_tpu.models.kv_cache import KVCacheManager
    mesh = _mesh(1)
    cfg = ModelConfig(hidden_size=4096, intermediate_size=1536,
                      num_hidden_layers=32, num_attention_heads=4,
                      num_key_value_heads=1, head_dim=128,
                      vocab_size=32768, max_position_embeddings=512,
                      dtype=bf16)
    model = DenseLLM(cfg, mesh=mesh, axis="tp", impl="pallas")
    for layer in (model.attn, model.mlp):
        layer.ag_ctx.interpret = True
        layer.rs_ctx.interpret = True
    kv = KVCacheManager(cfg.num_hidden_layers, 1,
                        cfg.max_position_embeddings,
                        cfg.num_key_value_heads, cfg.head_dim, mesh=mesh,
                        axis="tp", dtype=cfg.dtype)
    mega = MegaQwen3(model, decode_mode="gemm_ar")
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    caches = jax.eval_shape(kv.init)
    token = jax.ShapeDtypeStruct((1, 1), jnp.int32)
    check_entry_vmem(lambda p, t, c: mega.step(p, t, c, 4)[0],
                     params, token, caches)
