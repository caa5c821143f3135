"""Fused MoE second-projection + topk-reduce + ReduceScatter.

TPU-native redesign of the reference's MoE-RS
(python/triton_dist/kernels/nvidia/moe_reduce_rs.py: grouped GEMM producer
gathering rows by top-k assignment :167, topk-reduce kernels :293/:380,
dispatcher ``moe_reduce_rs`` :546).

Math: per device, activations ``act`` (T*topk, I/w) hold one row per
(token, k) pair against the local intermediate shard; ``w_down``
(E, I/w, H). The op computes the per-pair down-projection (grouped GEMM),
reduces over top-k with routing weights, and reduce-scatters the
rank-partial sums so each device ends with its T/w token rows.

``impl="ring"`` is the overlapped schedule: the ring reduce-scatter is
interleaved with per-row-block grouped dots — block c's MXU work happens
at the step its accumulator passes through this rank, so every ICI hop
rides under compute (the reference's producer GEMM + ring-reduce consumer
split, moe_reduce_rs.py:380-546, re-expressed as a collective matmul).

Why ring is the TPU default (VERDICT r3 next-8, measured on chip r3:
fused 3.191 ms vs ring 2.217 ms at T=2048, topk=2, I=4096, H=4096):

* **MXU occupancy.** The fused kernel folds the topk scatter-reduce
  into a second MXU dot against a (rows, m_blk) selection tile — the
  only scatter-free formulation a TPU kernel has (strided VPU scatter
  adds would serialize). That dot costs ``rows / I_loc`` extra FLOPs
  relative to the down-projection itself (~50% at serving shapes where
  T ≈ I), plus expert-alignment padding (~1.25x at T*topk=4096, E=8,
  m_blk=128). The ring instead lets XLA run the grouped GEMM as
  ``ragged_dot`` (dense MXU tiles over expert-sorted rows) and the
  topk-reduce as a segment-sum at full VPU width — no selection matmul,
  no per-tile padding.
* **Comm volume is identical** ((w-1)/w · T·H per device either way),
  and the ring's ppermute hop rides under the next block's dots just
  like the fused kernel's remote DMA — there is no overlap the fused
  form adds that the ring lacks.
* The GPU reference wins with its fused form because CUDA atomics make
  the scatter-reduce free and its grouped GEMM reads gathered rows at
  full bandwidth (moe_reduce_rs.py:167-380); neither property holds on
  TPU. Hence: ring default, fused kept and selectable — ``impl="auto"``
  measures both once per shape (tools/autotuner, disk-cached) and picks
  the winner, so shapes where ``rows << I_loc`` (deep EP slicing) can
  still choose the fused kernel.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

import triton_dist_tpu.language as dl
from triton_dist_tpu.resilience import resilient
from triton_dist_tpu.ops.common import (
    DEFAULT_VMEM_BUDGET,
    any_spec,
    comm_params,
    nestable_shard_map,
    resolve_interpret,
    sync_interpret)
from triton_dist_tpu.ops.group_gemm import (
    align_tokens_for_tiles, grouped_matmul)
from triton_dist_tpu.ops.moe_utils import topk_reduce


def _moe_rs_fused_kernel(act_hbm, w_hbm, sel_hbm, te_ref, o_hbm, send_hbm,
                         recv_hbm, a_tile, b_panel, sel_tile, acc, r_tile,
                         c_stage, a_sem, b_sem, s_sem, r_sem, c_sem,
                         send_sem, recv_sem, *, axis: str, world: int,
                         rows: int, m_pad: int, i_loc: int, h: int,
                         m_blk: int, h_blk: int):
    """Single-kernel MoE second-projection + topk-reduce + ring RS.

    The TPU answer to the reference's fused producer/reducer
    (moe_reduce_rs.py:167-546, VERDICT r2 next 7 second half): per ring
    step the kernel computes one token-chunk's rank-partial — streaming
    expert-aligned (m_blk, I_loc) pair tiles through VMEM, one full-K
    dot per tile with the expert's resident (I_loc, h_blk) down-proj
    panel — and folds the topk scatter-reduce into a second small MXU
    dot against a precomputed (rows, m_blk) routing-weight selection
    tile (≈ rows/I_loc extra FLOPs, no in-kernel scatter). The reduced
    chunk rides the ring under the next chunk's compute, exactly the
    GEMM-RS schedule.
    """
    me = lax.axis_index(axis)
    right = lax.rem(me + 1, world)
    m_tiles = m_pad // m_blk
    n_blocks = h // h_blk
    per = n_blocks * m_tiles

    def rs_copy(s):
        return dl.remote_copy(send_hbm.at[s], recv_hbm.at[s], right,
                              send_sem.at[s], recv_sem.at[s], axis=axis)

    def chunk_gemm(chunk, s, dst):
        def tile_of(i):
            return chunk * m_tiles + lax.rem(i, m_tiles)

        def a_dma(slot, i):
            row0 = chunk * m_pad + lax.rem(i, m_tiles) * m_blk
            return pltpu.make_async_copy(
                act_hbm.at[pl.ds(row0, m_blk), :], a_tile.at[slot],
                a_sem.at[slot])

        def sel_dma(slot, i):
            return pltpu.make_async_copy(
                sel_hbm.at[tile_of(i)], sel_tile.at[slot], s_sem.at[slot])

        def b_dma(slot, i):
            e = te_ref[tile_of(i)]
            return pltpu.make_async_copy(
                w_hbm.at[e, :, pl.ds((i // m_tiles) * h_blk, h_blk)],
                b_panel.at[slot], b_sem.at[slot])

        def need_b(i):
            prev = jnp.maximum(i - 1, 0)
            return (lax.rem(i, m_tiles) == 0) | (
                te_ref[tile_of(i)] != te_ref[tile_of(prev)])

        def r_dma(nb):
            return pltpu.make_async_copy(
                recv_hbm.at[jnp.maximum(s - 1, 0), :,
                            pl.ds(nb * h_blk, h_blk)],
                r_tile, r_sem)

        def c_dma(nb):
            return pltpu.make_async_copy(
                c_stage, dst.at[:, pl.ds(nb * h_blk, h_blk)], c_sem)

        a_dma(0, 0).start()
        sel_dma(0, 0).start()
        b_dma(0, 0).start()

        def istep(i, cur):
            # ``cur`` = slot of the current B panel; the next reload is
            # prefetched one tile ahead so panel fetches overlap dots
            # (code-review r3b finding 4).
            slot = lax.rem(i, 2)
            nb = i // m_tiles

            @pl.when(i + 1 < per)
            def _():
                a_dma(lax.rem(i + 1, 2), i + 1).start()
                sel_dma(lax.rem(i + 1, 2), i + 1).start()

            @pl.when((lax.rem(i, m_tiles) == 0) & (s > 0))
            def _():
                r_dma(nb).start()   # travelling partial for this h-block

            nb_i = need_b(i)

            @pl.when(nb_i)
            def _():
                b_dma(1 - cur, i).wait()
            cur = jnp.where(nb_i, 1 - cur, cur)

            @pl.when((i + 1 < per) & need_b(i + 1))
            def _():
                b_dma(1 - cur, i + 1).start()   # prefetch next panel

            a_dma(slot, i).wait()
            sel_dma(slot, i).wait()
            pair_out = jnp.dot(a_tile[slot], b_panel[cur],
                               preferred_element_type=jnp.float32)
            contrib = jnp.dot(sel_tile[slot], pair_out,
                              preferred_element_type=jnp.float32)

            @pl.when(lax.rem(i, m_tiles) == 0)
            def _():
                acc[:] = contrib

            @pl.when(lax.rem(i, m_tiles) > 0)
            def _():
                acc[:] = acc[:] + contrib

            @pl.when(lax.rem(i, m_tiles) == m_tiles - 1)
            def _():
                @pl.when(nb > 0)
                def _():
                    c_dma(nb - 1).wait()

                @pl.when(s > 0)
                def _():
                    r_dma(nb).wait()
                    c_stage[:] = (acc[:] + r_tile[:].astype(jnp.float32)
                                  ).astype(c_stage.dtype)

                @pl.when(s == 0)
                def _():
                    c_stage[:] = acc[:].astype(c_stage.dtype)
                c_dma(nb).start()
            return cur

        lax.fori_loop(0, per, istep, jnp.int32(1))
        c_dma(n_blocks - 1).wait()

    if world == 1:
        chunk_gemm(jnp.int32(0), jnp.int32(0), o_hbm)
        return

    dl.barrier_all(axis)

    def rs_step(s, _):
        send_idx = lax.rem(me - s - 1 + world, world)

        @pl.when(s > 0)
        def _():
            rs_copy(jnp.maximum(s - 1, 0)).wait_recv()
        chunk_gemm(send_idx, s, send_hbm.at[s])
        rs_copy(s).start()
        return _

    lax.fori_loop(0, world - 1, rs_step, None)
    rs_copy(world - 2).wait_recv()
    chunk_gemm(me, jnp.int32(world - 1), o_hbm)

    def drain(s, _):
        rs_copy(s).wait_send()
        return _

    lax.fori_loop(0, world - 1, drain, None)


def moe_rs_fused_footprint(m_blk: int, i_loc: int, h_blk: int,
                           rows: int, itemsize: int) -> int:
    """Declared VMEM bytes of the fused kernel's scratch at one tile
    config: double-buffered (m_blk, I_loc) pair tiles + (I_loc, h_blk)
    down-proj panels, f32 selection tiles and accumulator, and the
    travelling-partial / output stages. This is the exact expression
    the kernel entry clamps ``h_blk`` against and the static
    ``vmem-budget`` sweep (analysis/vmem.py) vets — one formula, two
    consumers, so they cannot drift."""
    return ((2 * m_blk * i_loc + 2 * i_loc * h_blk) * itemsize
            + 4 * (2 * rows * m_blk + rows * h_blk)
            + 2 * rows * h_blk * itemsize)


def moe_rs_resolve_h_blk(h: int, block_h: int, m_blk: int, i_loc: int,
                         rows: int, itemsize: int, budget: int) -> int:
    """The h-block the fused kernel will actually run: ``block_h``
    halved until it divides ``h``, then halved (floor 128) until the
    declared footprint fits ``budget`` — mirrored by the static sweep
    so the vet prices the kernel's real tiling, not the requested
    one."""
    h_blk = block_h
    while h_blk > h or h % h_blk:
        h_blk //= 2
    h_blk = max(h_blk, 1)
    while h_blk > 128 and moe_rs_fused_footprint(
            m_blk, i_loc, h_blk, rows, itemsize) > budget:
        h_blk //= 2
    return h_blk


@dataclasses.dataclass
class MoEReduceRSContext:
    """Analog of ``create_moe_rs_context`` (moe_reduce_rs.py): mesh/axis +
    topology; workspaces collapse into the traced program."""
    mesh: Mesh
    axis: str = "tp"
    num_experts: int = 8
    topk: int = 2
    interpret: bool | None = None
    # Tile sizes for the fused Pallas kernel (impl="fused").
    block_m: int = 128
    block_h: int = 512
    vmem_budget: int = DEFAULT_VMEM_BUDGET

    @property
    def world_size(self) -> int:
        return self.mesh.shape[self.axis]


def create_moe_rs_context(mesh: Mesh | None = None, axis: str = "tp",
                          num_experts: int = 8, topk: int = 2
                          ) -> MoEReduceRSContext:
    if mesh is None:
        from triton_dist_tpu.runtime.dist import get_mesh
        mesh = get_mesh()
    return MoEReduceRSContext(mesh=mesh, axis=axis, num_experts=num_experts,
                              topk=topk)


#: impl="auto" winners keyed by problem shape (in-process; the autotuner
#: adds the cross-run disk cache).
_IMPL_TUNED: dict = {}


@resilient("moe_reduce_rs", fused_impls=("fused", "auto"))
def moe_reduce_rs(act: jax.Array, w_down: jax.Array, expert_ids: jax.Array,
                  weights: jax.Array, ctx: MoEReduceRSContext,
                  impl: str = "ring") -> jax.Array:
    """out = reduce_scatter( topk_reduce( grouped_gemm(act, w_down) ) ).

    Args:
      act: (T*topk, I) with I sharded over ``ctx.axis`` (each device holds
        its I/w slice of every pair row).
      w_down: (E, I, H), I sharded the same way.
      expert_ids: (T*topk,) int32, replicated.
      weights: (T, topk) routing weights, replicated.
      impl: "ring" (default; see module docstring for why) | "fused" |
        "xla" | "auto" (measure ring vs fused once per shape, cached).
    Returns:
      (T/w, H) row-sharded token outputs (reference ``moe_reduce_rs``
      :546 returns the same layout).
    """
    mesh, axis, world = ctx.mesh, ctx.axis, ctx.world_size
    tk = act.shape[0]
    t, topk = weights.shape
    assert tk == t * topk
    assert t % world == 0
    rows = t // world
    n_exp = ctx.num_experts

    def pair_down(a_shard, wd, ids):
        """(T*topk, I/w) → per-token rank-partial (T, H)."""
        partial = grouped_matmul(a_shard, wd, ids, n_exp)
        return topk_reduce(partial.reshape(t, topk, -1), weights)

    def oneshot(a_shard, wd, ids, wts):
        del wts
        tok = pair_down(a_shard, wd, ids)
        return lax.psum_scatter(tok, axis, scatter_dimension=0, tiled=True)

    def ring(a_shard, wd, ids, wts):
        me = lax.axis_index(axis)
        h = wd.shape[-1]
        perm = [(i, (i + 1) % world) for i in range(world)]

        def block_partial(c):
            """Rank-partial down-proj of token row block c ((T/w, H))."""
            sl_act = lax.dynamic_slice_in_dim(
                a_shard.reshape(t, topk, -1), c * rows, rows, 0
            ).reshape(rows * topk, -1)
            sl_ids = lax.dynamic_slice_in_dim(
                ids.reshape(t, topk), c * rows, rows, 0).reshape(-1)
            sl_w = lax.dynamic_slice_in_dim(wts, c * rows, rows, 0)
            part = grouped_matmul(sl_act, wd, sl_ids, n_exp)
            return topk_reduce(part.reshape(rows, topk, h), sl_w)

        def step(s, acc):
            c = lax.rem(me + world - 1 - s, world)
            nxt = lax.ppermute(acc, axis, perm)  # overlaps the dots below
            mine = block_partial(c).astype(jnp.float32)
            return jnp.where(s == 0, mine, nxt + mine)

        acc = lax.fori_loop(0, world, step,
                            jnp.zeros((rows, h), jnp.float32))
        return acc.astype(act.dtype)

    if impl == "auto":
        shape_key = (t, topk, act.shape[1], w_down.shape[-1], n_exp, world)
        tune_key = f"moe_rs_impl:{shape_key}"
        choice = _IMPL_TUNED.get(shape_key)
        if choice is None and not isinstance(act, jax.core.Tracer):
            from triton_dist_tpu.tools.autotuner import autotune

            def make_fn(impl):
                fn = jax.jit(lambda a: moe_reduce_rs(
                    a, w_down, expert_ids, weights, ctx, impl=impl))
                return lambda: fn(act)

            res = autotune(make_fn, [{"impl": "ring"}, {"impl": "fused"}],
                           key=tune_key, iters=8, warmup_iters=2)
            choice = _IMPL_TUNED[shape_key] = res.config["impl"]
        elif choice is None:
            # Traced call (no eager sweep possible): a prior run's
            # winner in the autotuner's disk cache still counts — the
            # docstring's "measured once per shape, disk-cached"
            # promise must hold under jit too (review r4b-5).
            from triton_dist_tpu.tools.autotuner import (
                consult_disk_for_trace)
            hit = consult_disk_for_trace(tune_key)
            if hit is not None:
                choice = _IMPL_TUNED[shape_key] = hit.config["impl"]
        impl = choice or "ring"   # no sweep, no cache: ring default

    if impl == "fused":
        return _moe_rs_fused(act, w_down, expert_ids, weights, ctx)

    body = oneshot if (impl == "xla" or world == 1) else ring
    f = nestable_shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis), P(None, axis, None), P(), P()),
        out_specs=P(axis), check_vma=False)
    return f(act, w_down, expert_ids, weights)


def _moe_rs_fused(act, w_down, expert_ids, weights, ctx):
    """Entry for :func:`_moe_rs_fused_kernel`: builds the expert-aligned
    pair layout and the per-tile routing-weight selection tensors
    (traced; the analog of the reference's gather_a_ptrs + topk-reduce
    planning, moe_reduce_rs.py:167-380), then runs the single kernel."""
    mesh, axis, world = ctx.mesh, ctx.axis, ctx.world_size
    t, topk = weights.shape
    rows = t // world
    n_exp = ctx.num_experts
    m_blk = ctx.block_m
    pairs = rows * topk
    from triton_dist_tpu.ops.common import round_up
    m_pad = round_up(pairs + n_exp * (m_blk - 1), m_blk) + m_blk
    m_tiles = m_pad // m_blk
    interpret = resolve_interpret(ctx.interpret)

    def body(a_shard, wd, ids, wts):
        i_loc = a_shard.shape[1]
        h = wd.shape[-1]
        item = a_shard.dtype.itemsize
        h_blk = moe_rs_resolve_h_blk(h, ctx.block_h, m_blk, i_loc,
                                     rows, item, ctx.vmem_budget)

        # Per token-chunk alignment (identical on every device: ids and
        # weights are replicated; only the I-slice of act differs).
        a_chunks = a_shard.reshape(world, pairs, i_loc)
        id_chunks = ids.reshape(world, pairs)
        padded, tile_e, dest = jax.vmap(
            lambda av, iv: align_tokens_for_tiles(av, iv, n_exp, m_blk)
        )(a_chunks, id_chunks)
        padded_all = padded.reshape(world * m_pad, i_loc)
        te_all = tile_e.reshape(world * m_tiles)

        # Selection tensors: sel[tile, tok, col] = routing weight of the
        # pair that landed at aligned position (tile, col), for its
        # token row within the chunk; 0 elsewhere.
        p_idx = jnp.arange(pairs)
        chunk_idx = jnp.arange(world)[:, None]
        tile_idx = chunk_idx * m_tiles + dest // m_blk       # (w, pairs)
        col_idx = dest % m_blk
        tok_idx = jnp.broadcast_to(p_idx // topk, (world, pairs))
        w_vals = wts.reshape(world, rows, topk).reshape(world, pairs)
        sel = jnp.zeros((world * m_tiles, rows, m_blk), jnp.float32)
        sel = sel.at[tile_idx.ravel(), tok_idx.ravel(),
                     col_idx.ravel()].add(w_vals.ravel())

        kernel = functools.partial(
            _moe_rs_fused_kernel, axis=axis, world=world, rows=rows,
            m_pad=m_pad, i_loc=i_loc, h=h, m_blk=m_blk, h_blk=h_blk)

        out, *_ = pl.pallas_call(
            kernel,
            name="moe_reduce_rs",
            out_shape=(
                jax.ShapeDtypeStruct((rows, h), act.dtype),
                jax.ShapeDtypeStruct((max(world - 1, 1), rows, h),
                                     act.dtype),
                jax.ShapeDtypeStruct((max(world - 1, 1), rows, h),
                                     act.dtype)),
            in_specs=[any_spec(), any_spec(), any_spec(),
                      pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_specs=(any_spec(),) * 3,
            scratch_shapes=[
                pltpu.VMEM((2, m_blk, i_loc), act.dtype),
                pltpu.VMEM((2, i_loc, h_blk), act.dtype),
                pltpu.VMEM((2, rows, m_blk), jnp.float32),
                pltpu.VMEM((rows, h_blk), jnp.float32),
                pltpu.VMEM((rows, h_blk), act.dtype),
                pltpu.VMEM((rows, h_blk), act.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA((max(world - 1, 1),)),
                pltpu.SemaphoreType.DMA((max(world - 1, 1),)),
            ],
            compiler_params=comm_params(collective_id=9, world=world),
            interpret=interpret,
        )(padded_all, wd, sel, te_all)
        return out

    f = nestable_shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis), P(None, axis, None), P(), P()),
        out_specs=P(axis), check_vma=False)
    return sync_interpret(f(act, w_down, expert_ids, weights), interpret)
