"""Grouped (per-expert) GEMM building blocks for MoE.

TPU-native redesign of the reference's AG-MoE grouped GEMM
(python/triton_dist/kernels/nvidia/allgather_group_gemm.py:608
``ag_group_gemm``: AllGather + group GEMM whose tile schedule follows the
token→expert alignment from csrc/lib/moe_utils.cu:61) and the expert
compute inside MoE-RS (moe_reduce_rs.py:167 gather-grouped GEMM producer).

On TPU the token→block alignment machinery collapses into
``jax.lax.ragged_dot``: tokens sorted by expert + ``group_sizes`` is the
native grouped-GEMM form XLA tiles onto the MXU (see ops/moe_utils.py
``sort_by_group``). What remains of the reference's design is the
*overlap*: the ring variant interleaves ``ppermute`` hops of the token
shards with per-chunk ragged dots so ICI transfers ride under MXU work —
the collective-matmul schedule XLA's latency-hiding scheduler can overlap
(the analog of the reference's producer-AG + consumer-group-GEMM split).
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
    round_up,
    sync_interpret)
from triton_dist_tpu.ops.moe_utils import sort_by_group


def grouped_matmul(tokens: jax.Array, w: jax.Array, expert_ids: jax.Array,
                   num_experts: int, acc_dtype=jnp.float32) -> jax.Array:
    """out[i] = tokens[i] @ w[expert_ids[i]] with static shapes.

    Sort-by-expert + ``ragged_dot`` + unsort (the whole
    ``moe_ag_scatter_align_block_size`` pipeline in three ops). Rows with
    ``expert_ids == num_experts`` (invalid/padding) produce garbage rows
    that callers must mask — they are routed through the LAST expert's
    (``num_experts - 1``) weights.
    """
    sorted_tokens, group_sizes, unsort = sort_by_group(
        tokens, expert_ids, num_experts)
    # ragged_dot requires sum(group_sizes) == rows; padding rows (sentinel
    # group) are folded into the last real group, so they run through
    # expert num_experts-1's weights — masked by callers via `valid`.
    pad = tokens.shape[0] - jnp.sum(group_sizes)
    group_sizes = group_sizes.at[num_experts - 1].add(pad)
    out = lax.ragged_dot(
        sorted_tokens, w, group_sizes,
        preferred_element_type=acc_dtype).astype(tokens.dtype)
    return out[unsort]


def grouped_expert_ffn(tokens: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                       w_down: jax.Array, expert_ids: jax.Array,
                       num_experts: int) -> jax.Array:
    """Per-expert SwiGLU FFN over a flat token list (the expert compute of
    Qwen3-MoE, reference models/qwen_moe.py:50-108).

    w_gate/w_up: (E, H, I), w_down: (E, I, H); expert_ids: (T,) int32 with
    ``num_experts`` as the invalid sentinel.
    """
    sorted_tokens, group_sizes, unsort = sort_by_group(
        tokens, expert_ids, num_experts)
    pad = tokens.shape[0] - jnp.sum(group_sizes)
    group_sizes = group_sizes.at[num_experts - 1].add(pad)
    gate = lax.ragged_dot(sorted_tokens, w_gate, group_sizes,
                          preferred_element_type=jnp.float32)
    up = lax.ragged_dot(sorted_tokens, w_up, group_sizes,
                        preferred_element_type=jnp.float32)
    act = (jax.nn.silu(gate) * up).astype(tokens.dtype)
    down = lax.ragged_dot(act, w_down, group_sizes,
                          preferred_element_type=jnp.float32)
    return down.astype(tokens.dtype)[unsort]


def held_expert_ffn(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                    w_down: jax.Array, local_ids: jax.Array,
                    weights: jax.Array, expected_share: float):
    """The routed part of a MoE layer that ONE holder of ``n`` experts
    computes: the weighted sum, per token, of the held experts among the
    token's selected ones. Dropless, and only held pairs are gathered:
    a (token, expert) pair whose expert lives elsewhere passes through no
    expert's weights here.

    x: (T, H). w_gate/w_up: (n, H, I), w_down: (n, I, H): the held
    experts. local_ids: (T, K) int32, a pair's expert as its index among
    the held ones, ``n`` for a pair that is not held (or whose token is
    not live). weights: (T, K) float32. ``expected_share``: the share of
    all pairs a balanced router sends here (held / routed experts).

    The held pairs are sorted by expert in front of the rest and the
    first ``rows`` pairs run through ``ragged_dot``. ``rows`` is static,
    so it is chosen in-graph between two sizes: twice the expected
    number of held pairs, or all T*K (a ``lax.cond``; a router that
    overloads this holder costs time, never a pair). Rows past the held
    pairs ride in the last expert's group (``ragged_dot`` wants the
    groups to cover its rows) and are zeroed.

    Returns (out (T, H) float32, pairs per held expert (n,) int32, the
    rows the expert matmuls ran () int32)."""
    t, k = local_ids.shape
    n = w_gate.shape[0]
    flat = local_ids.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    sizes = jnp.zeros((n,), jnp.int32).at[flat].add(1, mode="drop")
    held = jnp.sum(sizes)
    flat_w = weights.reshape(-1)
    # Each pair's place in the sorted order; a token GATHERS its k pairs'
    # rows (the zero row for a pair past ``rows``). A scatter-add of the
    # rows to their tokens reads the same on paper, and on the v5e XLA
    # fuses it with the weighting into a program that drops rows (PR 34's
    # chip runs); the gather also sums in one fixed order.
    place = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32)).reshape(t, k)

    def run(rows: int):
        def f():
            pair = order[:rows]
            tok = pair // k
            xs = x[tok]
            groups = sizes.at[n - 1].add(rows - held)
            gate = lax.ragged_dot(xs, w_gate, groups,
                                  preferred_element_type=jnp.float32)
            up = lax.ragged_dot(xs, w_up, groups,
                                preferred_element_type=jnp.float32)
            act = (jax.nn.silu(gate) * up).astype(x.dtype)
            down = lax.ragged_dot(act, w_down, groups,
                                  preferred_element_type=jnp.float32)
            w = jnp.where(jnp.arange(rows) < held, flat_w[pair], 0.0)
            y = jnp.concatenate([down * w[:, None],
                                 jnp.zeros((1, x.shape[1]), jnp.float32)])
            return (jnp.sum(y[jnp.minimum(place, rows)], axis=1),
                    jnp.int32(rows))
        return f

    full = t * k
    small = min(round_up(max(int(2 * full * expected_share), 8), 8), full)
    if small == full:
        out, rows = run(full)()
    else:
        out, rows = lax.cond(held <= small, run(small), run(full))
    return out, sizes, rows


def align_tokens_for_tiles(tokens: jax.Array, ids: jax.Array,
                           num_experts: int, m_blk: int):
    """Tile-align tokens by expert (traced; static shapes).

    The TPU analog of the reference's token→tile alignment
    (``moe_ag_scatter_align_block_size`` csrc/lib/moe_utils.cu:61 +
    threadblock_swizzle_ag_moe): rows are expert-sorted and each expert
    group is padded to an ``m_blk`` boundary, so every (m_blk, K) tile of
    the padded layout touches EXACTLY ONE expert — the schedule the fused
    kernel iterates.

    Returns:
      padded: (M_pad, K) expert-sorted, group-padded tokens (pad rows 0).
      tile_experts: (M_pad // m_blk,) int32 expert of each tile.
      dest: (M,) int32 — padded row index of each original row (invalid
        rows, ``ids == num_experts``, collide into the trailing trash
        tile and must be masked by callers).
    """
    m, k = tokens.shape
    e = num_experts
    # Worst case: every group padded by m_blk-1, plus one trash tile.
    m_pad = round_up(m + e * (m_blk - 1), m_blk) + m_blk
    valid = ids < e
    eids = jnp.clip(ids, 0, e - 1)
    sizes = jnp.sum(
        jax.nn.one_hot(jnp.where(valid, eids, e), e + 1, dtype=jnp.int32),
        axis=0)[:e]                                    # live rows per expert
    gs_pad = ((sizes + m_blk - 1) // m_blk) * m_blk
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(sizes)[:-1]])
    offs_pad = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                jnp.cumsum(gs_pad)[:-1]])
    order = jnp.argsort(jnp.where(valid, eids, e), stable=True)
    e_sorted = eids[order]
    valid_sorted = valid[order]
    rank_in_group = jnp.arange(m, dtype=jnp.int32) - offs[e_sorted]
    dest_sorted = jnp.where(valid_sorted,
                            offs_pad[e_sorted] + rank_in_group,
                            m_pad - 1)                 # trash slot
    padded = jnp.zeros((m_pad, k), tokens.dtype).at[dest_sorted].set(
        tokens[order])
    dest = jnp.zeros((m,), jnp.int32).at[order].set(dest_sorted)
    tile_starts = jnp.arange(m_pad // m_blk, dtype=jnp.int32) * m_blk
    tile_experts = jnp.clip(
        jnp.searchsorted(jnp.cumsum(gs_pad), tile_starts, side="right"),
        0, e - 1).astype(jnp.int32)
    return padded, tile_experts, dest


def _ag_group_gemm_kernel(x_hbm, te_ref, w_hbm, ag_hbm, c_hbm, a_tile,
                          b_panel, c_stage, copy_sem, a_sem, b_sem, c_sem,
                          send_sem, recv_sem, *, axis: str, world: int,
                          m_pad: int, k: int, n_loc: int, m_blk: int,
                          n_blk: int, acc_dtype):
    """Fused ring-AG + grouped GEMM over the tile-aligned schedule.

    One Pallas kernel per device (VERDICT r2 next 7: the answer to the
    reference's fused producer/consumer, allgather_group_gemm.py:608):
    the ring AG of aligned token chunks runs during the first N-block
    (chunk-boundary ``wait_recv`` ≙ the reference's per-rank signal
    wait); every (m_blk, K) A tile belongs to a single expert, whose
    (K, n_blk) B panel stays resident until the expert RUN ends — the
    sorted schedule makes panel reloads O(#experts), not O(#tiles).
    """
    me = lax.axis_index(axis)
    right = lax.rem(me + 1, world)
    m_tiles = m_pad // m_blk
    n_blocks = n_loc // n_blk
    per_nb = world * m_tiles
    total = n_blocks * per_nb

    cp = pltpu.make_async_copy(
        x_hbm, ag_hbm.at[pl.ds(me * m_pad, m_pad), :], copy_sem)
    cp.start()
    cp.wait()
    if world > 1:
        dl.barrier_all(axis)

    def chunk_idx(i):
        return lax.rem(me - lax.rem(i, per_nb) // m_tiles + world, world)

    def tile_of(i):
        return chunk_idx(i) * m_tiles + lax.rem(i, m_tiles)

    def row_of(i):
        return chunk_idx(i) * m_pad + lax.rem(i, m_tiles) * m_blk

    def chunk_copy(idx):
        return dl.remote_copy(
            ag_hbm.at[pl.ds(idx * m_pad, m_pad), :],
            ag_hbm.at[pl.ds(idx * m_pad, m_pad), :],
            right, send_sem.at[idx], recv_sem.at[idx], axis=axis)

    def a_dma(slot, i):
        return pltpu.make_async_copy(
            ag_hbm.at[pl.ds(row_of(i), m_blk), :], a_tile.at[slot],
            a_sem.at[slot])

    def b_dma(slot, i):
        e = te_ref[tile_of(i)]
        return pltpu.make_async_copy(
            w_hbm.at[e, :, pl.ds((i // per_nb) * n_blk, n_blk)],
            b_panel.at[slot], b_sem.at[slot])

    def need_b(i):
        # Panel reloads happen at N-block starts and expert-run
        # boundaries only (the point of the aligned schedule).
        prev = jnp.maximum(i - 1, 0)
        return (lax.rem(i, per_nb) == 0) | (
            te_ref[tile_of(i)] != te_ref[tile_of(prev)])

    def c_dma(slot, i):
        return pltpu.make_async_copy(
            c_stage.at[slot],
            c_hbm.at[pl.ds(row_of(i), m_blk),
                     pl.ds((i // per_nb) * n_blk, n_blk)],
            c_sem.at[slot])

    def ring_advance(i):
        if world == 1:
            return

        @pl.when((i < per_nb) & (lax.rem(i, m_tiles) == 0))
        def _():
            s = i // m_tiles

            @pl.when(s > 0)
            def _():
                chunk_copy(chunk_idx(i)).wait_recv()

            @pl.when(s < world - 1)
            def _():
                chunk_copy(chunk_idx(i)).start()

    ring_advance(0)
    a_dma(0, 0).start()
    b_dma(0, 0).start()

    def step(i, cur):
        """``cur`` carries the slot holding tile i-1's panel; reloads
        alternate slots, and the NEXT reload is prefetched one tile
        ahead (the expert schedule is known in te_ref), so panel
        fetches ride under the current run's dots instead of stalling
        the MXU (code-review r3b finding 4)."""
        slot = lax.rem(i, 2)
        ring_advance(i + 1)

        @pl.when(i + 1 < total)
        def _():
            a_dma(lax.rem(i + 1, 2), i + 1).start()

        nb_i = need_b(i)

        @pl.when(nb_i)
        def _():
            b_dma(1 - cur, i).wait()
        cur = jnp.where(nb_i, 1 - cur, cur)

        @pl.when((i + 1 < total) & need_b(i + 1))
        def _():
            b_dma(1 - cur, i + 1).start()   # prefetch next panel

        a_dma(slot, i).wait()
        out = jnp.dot(a_tile[slot], b_panel[cur],
                      preferred_element_type=acc_dtype)

        @pl.when(i >= 2)
        def _():
            c_dma(slot, i - 2).wait()
        c_stage[slot] = out.astype(c_stage.dtype)
        c_dma(slot, i).start()
        return cur

    lax.fori_loop(0, total, step, jnp.int32(1))
    for i_last in range(max(0, total - 2), total):
        c_dma(i_last % 2, i_last).wait()

    if world > 1:
        def drain(s, _):
            chunk_copy(lax.rem(me - s + world, world)).wait_send()
            return _
        lax.fori_loop(0, world - 1, drain, None)


@dataclasses.dataclass
class AGGroupGEMMContext:
    """Analog of ``create_ag_group_gemm_context``
    (allgather_group_gemm.py): mesh/axis + schedule choice."""
    mesh: Mesh
    axis: str = "tp"
    ring: bool = True   # ring-overlap schedule vs one-shot AG
    interpret: bool | None = None
    # Tile sizes for the fused Pallas kernel (impl="fused").
    block_m: int = 128
    block_n: int = 512
    vmem_budget: int = DEFAULT_VMEM_BUDGET

    @property
    def world_size(self) -> int:
        return self.mesh.shape[self.axis]


def create_ag_group_gemm_context(mesh: Mesh | None = None, axis: str = "tp",
                                 ring: bool = True) -> AGGroupGEMMContext:
    if mesh is None:
        from triton_dist_tpu.runtime.dist import get_mesh
        mesh = get_mesh()
    return AGGroupGEMMContext(mesh=mesh, axis=axis, ring=ring)


#: impl="auto" winners keyed by problem shape (in-process; the autotuner
#: adds the cross-run disk cache).
_IMPL_TUNED: dict = {}


@resilient("ag_group_gemm", fused_impls=("fused", "auto"))
def ag_group_gemm(x: jax.Array, w: jax.Array, expert_ids: jax.Array,
                  num_experts: int, ctx: AGGroupGEMMContext | None = None,
                  impl: str = "ring") -> jax.Array:
    """C = group_gemm(allgather(x), w) — TP-MoE first projection
    (reference ``ag_group_gemm`` allgather_group_gemm.py:608).

    Args:
      x: (M, K) row-sharded over ``ctx.axis``; one expert id per row.
      w: (E, K, N) with N column-sharded over ``ctx.axis``.
      expert_ids: (M,) int32 row→expert, row-sharded like x.
    Returns:
      (M, N/world) per device — full gathered M rows against the local
      N-shard, column-sharded overall.

    ``impl="ring"``: w-1 ``ppermute`` hops; chunk s's ragged dot runs
    while chunk s+1 is in flight (collective matmul — the overlap the
    reference gets from its producer/consumer split).
    ``impl="fused"``: ONE Pallas kernel — in-kernel ring AG of
    tile-aligned expert-sorted chunks feeding tiled MXU dots
    (:func:`_ag_group_gemm_kernel`; the reference's fused design,
    allgather_group_gemm.py:608).
    ``impl="xla"``: one-shot all-gather golden.
    ``impl="auto"``: measure ring vs fused once per shape (autotuner,
    disk-cached across processes) and use the winner — the r3 chip
    measurement had fused ahead (1.224 vs 1.344 ms at bench shape),
    but the winner is shape-dependent.
    """
    ctx = ctx or create_ag_group_gemm_context()
    mesh, axis, world = ctx.mesh, ctx.axis, ctx.world_size
    m, k = x.shape
    assert w.ndim == 3 and w.shape[1] == k

    if impl == "auto":
        shape_key = (m, k, w.shape[0], w.shape[2], str(x.dtype), world)
        tune_key = f"ag_gg_impl:{shape_key}"
        choice = _IMPL_TUNED.get(shape_key)
        if choice is None and not isinstance(x, jax.core.Tracer):
            from triton_dist_tpu.tools.autotuner import autotune

            def make_fn(impl):
                fn = jax.jit(lambda xv: ag_group_gemm(
                    xv, w, expert_ids, num_experts, ctx, impl=impl))
                return lambda: fn(x)

            res = autotune(make_fn, [{"impl": "ring"}, {"impl": "fused"}],
                           key=tune_key, iters=8, warmup_iters=2)
            choice = _IMPL_TUNED[shape_key] = res.config["impl"]
        elif choice is None:
            # Traced: a prior run's disk-cached winner still counts —
            # single-controller only, warns once on a miss (ADVICE r4;
            # see consult_disk_for_trace).
            from triton_dist_tpu.tools.autotuner import (
                consult_disk_for_trace)
            hit = consult_disk_for_trace(tune_key)
            if hit is not None:
                choice = _IMPL_TUNED[shape_key] = hit.config["impl"]
        impl = choice or "ring"   # no sweep, no cache: ring default

    if impl == "fused":
        return _ag_group_gemm_fused(x, w, expert_ids, num_experts, ctx)

    def oneshot(xs, ids, ws):
        ag = lax.all_gather(xs, axis, tiled=True)
        ag_ids = lax.all_gather(ids, axis, tiled=True)
        return grouped_matmul(ag, ws, ag_ids, num_experts)

    def ring(xs, ids, ws):
        me = lax.axis_index(axis)
        rows = xs.shape[0]
        out = jnp.zeros((rows * world, ws.shape[-1]), xs.dtype)

        def step(s, carry):
            out, cur_x, cur_ids = carry
            src = lax.rem(me - s + world, world)
            # Launch the next hop first so XLA can overlap it with the dot.
            perm = [(i, (i + 1) % world) for i in range(world)]
            nxt_x = lax.ppermute(cur_x, axis, perm)
            nxt_ids = lax.ppermute(cur_ids, axis, perm)
            chunk_out = grouped_matmul(cur_x, ws, cur_ids, num_experts)
            out = lax.dynamic_update_slice(out, chunk_out,
                                           (src * rows, jnp.int32(0)))
            return out, nxt_x, nxt_ids

        out, last_x, last_ids = lax.fori_loop(
            0, world - 1, step, (out, xs, ids))
        src = lax.rem(me - (world - 1) + world, world)
        chunk_out = grouped_matmul(last_x, ws, last_ids, num_experts)
        out = lax.dynamic_update_slice(out, chunk_out,
                                       (src * rows, jnp.int32(0)))
        return out

    body = oneshot if (impl == "xla" or world == 1) else ring
    f = nestable_shard_map(body, mesh=mesh,
                      in_specs=(P(axis), P(axis), P(None, None, axis)),
                      out_specs=P(None, axis), check_vma=False)
    return f(x, expert_ids, w)


def _ag_group_gemm_fused(x, w, expert_ids, num_experts, ctx):
    """Entry for the fused Pallas AG + grouped-GEMM kernel."""
    mesh, axis, world = ctx.mesh, ctx.axis, ctx.world_size
    m, k = x.shape
    e, _, n = w.shape
    n_loc = n // world
    m_loc = m // world
    interpret = resolve_interpret(ctx.interpret)

    # m_blk need not divide m_loc — the alignment pass pads per group.
    m_blk = ctx.block_m
    m_pad = round_up(m_loc + num_experts * (m_blk - 1), m_blk) + m_blk
    n_blk = ctx.block_n
    while n_blk > n_loc or n_loc % n_blk:
        n_blk //= 2
    n_blk = max(n_blk, 1)
    # 2 B panels (double-buffered prefetch) + A tiles + C stages must
    # fit the budget.
    item = x.dtype.itemsize
    while n_blk > 128 and (2 * k * n_blk + 2 * m_blk * k
                           + 2 * m_blk * n_blk) * item > ctx.vmem_budget:
        n_blk //= 2

    kernel = functools.partial(
        _ag_group_gemm_kernel, axis=axis, world=world, m_pad=m_pad, k=k,
        n_loc=n_loc, m_blk=m_blk, n_blk=n_blk, acc_dtype=jnp.float32)

    def body(xs, ids_s, ws):
        padded, tile_e, dest = align_tokens_for_tiles(
            xs, ids_s, num_experts, m_blk)
        tile_e_all = lax.all_gather(tile_e, axis, tiled=True)
        dest_all = lax.all_gather(dest, axis, tiled=True)
        _, cpad = pl.pallas_call(
            kernel,
            name="ag_group_gemm",
            out_shape=(jax.ShapeDtypeStruct((world * m_pad, k), x.dtype),
                       jax.ShapeDtypeStruct((world * m_pad, n_loc),
                                            x.dtype)),
            in_specs=[any_spec(),
                      pl.BlockSpec(memory_space=pltpu.SMEM),
                      any_spec()],
            out_specs=(any_spec(), any_spec()),
            scratch_shapes=[
                pltpu.VMEM((2, m_blk, k), x.dtype),
                pltpu.VMEM((2, k, n_blk), x.dtype),
                pltpu.VMEM((2, m_blk, n_blk), x.dtype),
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((world,)),
                pltpu.SemaphoreType.DMA((world,)),
            ],
            compiler_params=comm_params(collective_id=8, world=world),
            interpret=interpret,
        )(padded, tile_e_all, ws)
        # Unsort: global row j lives at chunk(j)*m_pad + dest_all[j].
        rows = (jnp.arange(world * m_loc) // m_loc) * m_pad + dest_all
        return cpad[rows]

    f = nestable_shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(axis), P(None, None, axis)),
        out_specs=P(None, axis), check_vma=False)
    return sync_interpret(f(x, expert_ids, w), interpret)
