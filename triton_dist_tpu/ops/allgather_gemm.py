"""Fused AllGather-GEMM (tensor-parallel column-linear forward).

TPU-native redesign of the reference's flagship overlapped op
(python/triton_dist/kernels/nvidia/allgather_gemm.py: ``create_ag_gemm_context``
:489, ``ag_gemm`` :534, consumer GEMM that per-M-tile ``dl.wait``s on
per-rank ready flags :158-264, rank-rotated tile swizzle :221-229).

Math: A is row-sharded over the axis ((M/w, K) per device), B is
column-sharded ((K, N/w) per device). Every device computes
``C_local = allgather(A) @ B_local`` — full M rows of its N-columns.

The TPU design is a *collective matmul*: one Pallas kernel per device runs
the ring all-gather of A chunks and, as each chunk lands (semaphore wait —
the analog of the reference's per-rank ``dl.wait``), feeds it to the MXU.
The remote DMA of chunk s+1 overlaps the dot of chunk s. Consumption starts
with the device's own chunk, so compute order is naturally rank-rotated
(reference swizzle allgather_gemm.py:221-229).

``impl="xla"``: ``lax.all_gather`` + ``jnp.dot`` — the unfused golden
(XLA's latency-hiding scheduler may still overlap at coarse grain; it is
also what overlap efficiency is measured against).
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
    HARD_FOOTPRINT_CAP,
    TUNED_VMEM_BUDGET,
    any_spec,
    cap_config_tiers,
    comm_params,
    maybe_noise,
    maybe_straggle,
    nestable_shard_map,
    record_comm,
    record_overlap,
    resolve_interpret,
    resolve_ring_dirs,
    ring_chunk_schedule,
    ring_hop_counts,
    sync_interpret)


@dataclasses.dataclass
class AllGatherGEMMContext:
    """Analog of ``AllGatherGEMMTensorParallelContext``
    (allgather_gemm.py:417-456): owns tuning params; the symmetric
    workspace/barrier allocation collapses into kernel buffers on TPU."""
    mesh: Mesh
    axis: str = "tp"
    # Dot accumulation dtype on the MXU.
    acc_dtype: jnp.dtype = jnp.float32
    interpret: bool | None = None
    # Return the gathered A alongside C (the reference reuses the AG
    # workspace for attention, tp_attn.py).
    return_gathered: bool = False
    # Kernel variant: "vmem" holds whole operands in VMEM (small shapes,
    # lowest latency); "hbm" keeps A/C in HBM, holds a (K, block_n) B
    # panel resident in VMEM and streams (block_m, K) A tiles — B is read
    # from HBM exactly once and every dot contracts the full K on the MXU
    # (VERDICT r2 weak 4: the round-2 k-tiled kernel re-DMA'd the whole B
    # panel per m-tile, ~16x minimal B traffic); "hbm_kt" is that k-tiled
    # kernel, kept for K too large for a resident panel; "auto" picks by
    # VMEM footprint.
    variant: str = "auto"
    # Tile sizes (auto-clamped to divisors and the VMEM budget; the entry
    # falls back to the first feasible ag_gemm_configs entry otherwise).
    block_k: int = 512
    block_m: int = 256
    block_n: int = 512
    # Soft VMEM budget for the auto choice and the default-path block
    # clamp (bytes) — sizing rationale on the shared constant.
    vmem_budget: int = DEFAULT_VMEM_BUDGET
    # Honor block hints past the soft budget (up to HARD_FOOTPRINT_CAP).
    # Set by the autotune sweep and tuned-winner application so the
    # config table's aggressive tier reaches Mosaic (review r5i finding
    # 1); the DEFAULT path keeps the conservative soft-budget clamp.
    trust_blocks: bool = False
    # Autotune (variant, block_m, block_k) on first *eager* call per
    # shape via tools.autotuner (reference ContextualAutoTuner +
    # matmul_get_configs, allgather_gemm.py:396); jitted calls reuse the
    # shape-keyed cache.
    autotune: bool = False
    # Ring directions for the fused AG schedule: 2 = bidirectional
    # (chunks travel the shorter way round, both full-duplex ICI links
    # active — the ops/allgather.py RING_BIDIR win the fused ops never
    # had), 1 = the unidirectional proven-on-chip fallback, 0 = consult
    # TDT_RING_DIRS (default 2).
    ring_dirs: int = 0
    # Correctness-debug injection (reference for_correctness sleeps
    # allgather_gemm.py:507-508 and straggler_option): see ops/common.py.
    straggler_option: tuple[int, int] | None = None
    for_correctness: bool = False

    @property
    def world_size(self) -> int:
        return self.mesh.shape[self.axis]

    def resolve_variant(self, m: int, k: int, n_tot: int,
                        itemsize: int) -> str:
        if self.variant != "auto":
            return self.variant
        # vmem kernel holds ag(M,K) + Bs(K,N) + Cs(M,N) + x(M/w,K)
        footprint = itemsize * (m * k + k * n_tot + m * n_tot
                                + (m // max(self.world_size, 1)) * k)
        return "vmem" if footprint <= self.vmem_budget else "hbm"


def create_ag_gemm_context(mesh: Mesh | None = None, axis: str = "tp",
                           acc_dtype=jnp.float32,
                           interpret: bool | None = None,
                           return_gathered: bool = False
                           ) -> AllGatherGEMMContext:
    if mesh is None:
        from triton_dist_tpu.runtime.dist import get_mesh
        mesh = get_mesh()
    return AllGatherGEMMContext(mesh=mesh, axis=axis, acc_dtype=acc_dtype,
                                interpret=interpret,
                                return_gathered=return_gathered)


def _make_ring(chunk_ref, me, axis: str, world: int, dirs: int,
               send_sem, recv_sem):
    """Ring bookkeeping for the rank-rotated AG consumption schedule,
    shared by every fused AG-GEMM kernel.

    ``chunk_ref(idx)`` returns the workspace slice of chunk ``idx``;
    semaphores are per (direction, chunk) — delivery is not FIFO, and a
    fast neighbor may run several hops ahead (same hazard note as
    ``ops/allgather._ring_ag_kernel``). With ``dirs=2`` the forward
    ring (rightward sends) carries chunks me-1..me-n_fwd and the
    backward ring (leftward) me+1..me+n_bwd, halving the hop count on
    the full-duplex ICI links; ``dirs=1`` reproduces the round-5
    proven unidirectional schedule exactly.

    Returns ``(chunk_of, advance, drain)``: ``chunk_of(s)`` is the
    chunk consumed at schedule position s; ``advance(s)`` waits for
    position s's arrival and keeps it travelling onward (position 0
    launches the local chunk both ways — each later hop then overlaps
    a whole chunk's compute); ``drain()`` waits out the send
    semaphores before the kernel retires.
    """
    right = lax.rem(me + 1, world)
    left = lax.rem(me - 1 + world, world)
    n_fwd, n_bwd = ring_hop_counts(world, dirs)

    def chunk_copy(idx, d):
        peer = jnp.where(jnp.asarray(d) == 1, left, right)
        ref = chunk_ref(idx)
        return dl.remote_copy(ref, ref, peer, send_sem.at[d, idx],
                              recv_sem.at[d, idx], axis=axis)

    def chunk_of(s):
        return ring_chunk_schedule(me, s, world, dirs)[0]

    def advance(s):
        if world == 1:
            return
        chunk, is_bwd, off = ring_chunk_schedule(me, s, world, dirs)
        s = jnp.asarray(s, jnp.int32)
        d = is_bwd.astype(jnp.int32)

        @pl.when(s == 0)
        def _():
            if n_fwd > 0:
                chunk_copy(me, 0).start()
            if n_bwd > 0:
                chunk_copy(me, 1).start()

        @pl.when((s > 0) & (s < world))
        def _():
            chunk_copy(chunk, d).wait_recv()   # the reference dl.wait
            onward = jnp.where(is_bwd, off < n_bwd, off < n_fwd)

            @pl.when(onward)
            def _():
                chunk_copy(chunk, d).start()

    def drain():
        if world == 1:
            return

        def wait_one(s, _):
            @pl.when(s < n_fwd)
            def _():
                chunk_copy(lax.rem(me - s + world, world), 0).wait_send()
            if n_bwd > 0:
                @pl.when(s < n_bwd)
                def _():
                    chunk_copy(lax.rem(me + s, world), 1).wait_send()
            return _

        lax.fori_loop(0, max(n_fwd, n_bwd), wait_one, None)

    return chunk_of, advance, drain


def _ag_gemm_kernel(x_ref, *rest, axis: str, world: int, rows: int,
                    acc_dtype, n_b: int, dirs: int = 1,
                    straggler_option=None,
                    for_correctness=False, interp=False):
    """Ring AG of A chunks fused with per-chunk GEMM(s).

    Per step: the chunk-boundary ``advance`` waits for the chunk's
    arrival and immediately keeps it travelling (DMA on ICI), then the
    MXU runs on it (overlap) — the wait is the reference's
    ``dl.wait(ready_ptr + rank, ...)`` (allgather_gemm.py:236). With
    ``dirs=2`` chunks ride both ICI directions (``_make_ring``).

    Supports ``n_b`` weight matrices sharing the gathered A (one AG feeding
    several GEMMs — the QKV / gate+up projections of a TP transformer
    layer, reference tp_attn.py wqkv concat / tp_mlp.py gate_up concat).
    On TPU separate B operands beat a concatenated one because each B keeps
    a clean column sharding."""
    w_refs = rest[:n_b]
    ag_ref = rest[n_b]
    c_refs = rest[n_b + 1:2 * n_b + 1]
    send_sem, recv_sem = rest[2 * n_b + 1:2 * n_b + 3]
    me = lax.axis_index(axis)

    ag_ref[pl.ds(me * rows, rows), :] = x_ref[:]
    if world > 1:
        dl.barrier_all(axis)
        maybe_straggle(straggler_option, axis, interp)
        maybe_noise(for_correctness, axis, world, salt=3, interpret=interp)

    def gemm_chunk(idx):
        for w_ref, c_ref in zip(w_refs, c_refs):
            c_ref[pl.ds(idx * rows, rows), :] = jnp.dot(
                ag_ref[pl.ds(idx * rows, rows), :], w_ref[:],
                preferred_element_type=acc_dtype).astype(c_ref.dtype)

    if world == 1:
        gemm_chunk(me)
        return

    chunk_of, advance, drain = _make_ring(
        lambda idx: ag_ref.at[pl.ds(idx * rows, rows), :], me, axis,
        world, dirs, send_sem, recv_sem)

    advance(0)

    def step(s, _):
        gemm_chunk(chunk_of(s))           # MXU on current chunk
        advance(s + 1)                    # next chunk: wait + forward
        return _

    lax.fori_loop(0, world, step, None)
    drain()


def _ag_gemm_hbm_nb_kernel(x_hbm, b_hbm, ag_hbm, c_hbm, a_tile, b_panel,
                           c_stage, copy_sem, a_sem, b_sem, c_sem,
                           send_sem, recv_sem, *, axis: str, world: int,
                           rows: int, k: int, n_loc: int, m_blk: int,
                           n_blk: int, acc_dtype, dirs: int = 1,
                           straggler_option=None,
                           for_correctness=False, interp=False):
    """N-blocked HBM AG-GEMM: resident B panel, full-K MXU dots.

    Per N-block: the (K, n_blk) B panel is DMA'd into VMEM ONCE (B total
    traffic = K·N — round 2's k-tiled kernel re-read it per m-tile,
    VERDICT r2 weak 4), then (m_blk, K) A tiles stream through a double
    buffer and each tile is one full-K ``jnp.dot`` — no k-accumulator,
    no per-k-tile writeback. The ring AG of A chunks runs during the
    FIRST N-block only (its chunk-boundary ``wait_recv`` is the
    reference's per-rank ``dl.wait``, allgather_gemm.py:236); by the
    time panel 0's compute drains, every chunk has landed, so later
    panels read the workspace freely. Rank-rotated consumption order is
    preserved (reference swizzle allgather_gemm.py:221-229).
    """
    me = lax.axis_index(axis)
    m_tiles = rows // m_blk
    n_blocks = n_loc // n_blk
    per_nb = world * m_tiles       # iterations per N-block
    total = n_blocks * per_nb

    # local shard → ag[me] (HBM→HBM DMA)
    cp = pltpu.make_async_copy(x_hbm, ag_hbm.at[pl.ds(me * rows, rows), :],
                               copy_sem)
    cp.start()
    cp.wait()
    if world > 1:
        dl.barrier_all(axis)
        maybe_straggle(straggler_option, axis, interp)
        maybe_noise(for_correctness, axis, world, salt=4, interpret=interp)

    chunk_of, advance, ring_drain = _make_ring(
        lambda idx: ag_hbm.at[pl.ds(idx * rows, rows), :], me, axis,
        world, dirs, send_sem, recv_sem)

    def chunk_idx(i):
        return chunk_of(lax.rem(i, per_nb) // m_tiles)

    def row_of(i):
        mt = lax.rem(i, m_tiles)
        return chunk_idx(i) * rows + mt * m_blk

    def a_dma(slot, i):
        return pltpu.make_async_copy(
            ag_hbm.at[pl.ds(row_of(i), m_blk), :], a_tile.at[slot],
            a_sem.at[slot])

    def b_dma(slot, nb):
        return pltpu.make_async_copy(
            b_hbm.at[:, pl.ds(nb * n_blk, n_blk)], b_panel.at[slot],
            b_sem.at[slot])

    def c_dma(slot, i):
        return pltpu.make_async_copy(
            c_stage.at[slot],
            c_hbm.at[pl.ds(row_of(i), m_blk),
                     pl.ds((i // per_nb) * n_blk, n_blk)],
            c_sem.at[slot])

    def ring_advance(i):
        """Chunk-boundary ring bookkeeping — N-block 0 only."""
        if world == 1:
            return

        @pl.when((i < per_nb) & (lax.rem(i, m_tiles) == 0))
        def _():
            advance(i // m_tiles)

    ring_advance(0)
    b_dma(0, 0).start()
    a_dma(0, 0).start()

    def step(i, _):
        slot = lax.rem(i, 2)
        nb = i // per_nb
        bslot = lax.rem(nb, 2)
        ring_advance(i + 1)

        @pl.when(i + 1 < total)
        def _():
            a_dma(lax.rem(i + 1, 2), i + 1).start()

        @pl.when((lax.rem(i, per_nb) == 0) & (nb + 1 < n_blocks))
        def _():
            b_dma(lax.rem(nb + 1, 2), nb + 1).start()  # prefetch panel

        @pl.when(lax.rem(i, per_nb) == 0)
        def _():
            b_dma(bslot, nb).wait()
        a_dma(slot, i).wait()

        out = jnp.dot(a_tile[slot], b_panel[bslot],
                      preferred_element_type=acc_dtype)

        @pl.when(i >= 2)
        def _():
            c_dma(slot, i - 2).wait()   # this slot's previous writeback
        c_stage[slot] = out.astype(c_stage.dtype)
        c_dma(slot, i).start()
        return _

    lax.fori_loop(0, total, step, None)

    for i_last in range(max(0, total - 2), total):
        c_dma(i_last % 2, i_last).wait()

    ring_drain()


def _ag_gemm_hbm_kernel(x_hbm, b_hbm, ag_hbm, c_hbm, a_tile, b_tile, acc,
                        c_stage, copy_sem, a_sem, b_sem, c_sem, send_sem,
                        recv_sem, *, axis: str, world: int, rows: int,
                        k: int, k_blk: int, m_blk: int, acc_dtype,
                        dirs: int = 1, straggler_option=None,
                        for_correctness=False, interp=False):
    """HBM-resident ring AG-GEMM: operands never fully enter VMEM.

    Ring protocol identical to ``_ag_gemm_kernel`` (per-chunk DMA
    semaphores, barrier before first remote write) but the AG workspace
    lives in HBM and each chunk's GEMM streams (m_blk, k_blk)·(k_blk, N)
    tiles through double-buffered VMEM — the TPU shape of the reference's
    persistent tiled consumer (kernel_consumer_gemm_persistent,
    allgather_gemm.py:158-264): its ``dl.wait`` per M-tile becomes the
    chunk-boundary ``wait_recv``; its BLOCK_M/BLOCK_K loops become the
    tile DMA pipeline; rank-rotated consumption order is preserved.
    """
    me = lax.axis_index(axis)
    k_tiles = k // k_blk
    m_tiles = rows // m_blk
    per_chunk = m_tiles * k_tiles
    total = world * per_chunk

    # local shard → ag[me] (HBM→HBM DMA)
    cp = pltpu.make_async_copy(x_hbm, ag_hbm.at[pl.ds(me * rows, rows), :],
                               copy_sem)
    cp.start()
    cp.wait()
    if world > 1:
        dl.barrier_all(axis)
        maybe_straggle(straggler_option, axis, interp)
        maybe_noise(for_correctness, axis, world, salt=5, interpret=interp)

    chunk_pos, advance, ring_drain = _make_ring(
        lambda idx: ag_hbm.at[pl.ds(idx * rows, rows), :], me, axis,
        world, dirs, send_sem, recv_sem)

    def chunk_of(i):
        return chunk_pos(i // per_chunk)

    def row_of(i):
        """First AG row of iteration i's (chunk, m-tile)."""
        mt = lax.rem(i, per_chunk) // k_tiles
        return chunk_of(i) * rows + mt * m_blk

    def a_dma(slot, i):
        return pltpu.make_async_copy(
            ag_hbm.at[pl.ds(row_of(i), m_blk),
                      pl.ds(lax.rem(i, k_tiles) * k_blk, k_blk)],
            a_tile.at[slot], a_sem.at[slot])

    def b_dma(slot, i):
        return pltpu.make_async_copy(
            b_hbm.at[pl.ds(lax.rem(i, k_tiles) * k_blk, k_blk), :],
            b_tile.at[slot], b_sem.at[slot])

    def c_dma(slot, row):
        return pltpu.make_async_copy(
            c_stage.at[slot], c_hbm.at[pl.ds(row, m_blk), :], c_sem.at[slot])

    def ring_advance(j):
        """At chunk boundary j: ensure the chunk has arrived, then keep it
        moving round the ring — the forward overlaps this whole chunk's
        tile compute."""
        if world == 1:
            return

        @pl.when((j < total) & (lax.rem(j, per_chunk) == 0))
        def _():
            advance(j // per_chunk)

    ring_advance(0)
    a_dma(0, 0).start()
    b_dma(0, 0).start()

    def step(i, _):
        slot = lax.rem(i, 2)
        nxt = lax.rem(i + 1, 2)
        ring_advance(i + 1)

        @pl.when(i + 1 < total)
        def _():
            a_dma(nxt, i + 1).start()
            b_dma(nxt, i + 1).start()

        a_dma(slot, i).wait()
        b_dma(slot, i).wait()
        kt = lax.rem(i, k_tiles)

        partial = jnp.dot(a_tile[slot], b_tile[slot],
                          preferred_element_type=acc_dtype)

        @pl.when(kt == 0)
        def _():
            acc[:] = partial

        @pl.when(kt > 0)
        def _():
            acc[:] = acc[:] + partial

        @pl.when(kt == k_tiles - 1)
        def _():
            # Double-buffered writeback: stage into the alternate slot and
            # let the DMA drain while the next m-tile computes; only wait
            # for this slot's *previous* writeback (2 m-tiles ago).
            mi = i // k_tiles
            cslot = lax.rem(mi, 2)

            @pl.when(mi >= 2)
            def _():
                c_dma(cslot, row_of(i)).wait()
            c_stage[cslot] = acc[:].astype(c_stage.dtype)
            c_dma(cslot, row_of(i)).start()
        return _

    lax.fori_loop(0, total, step, None)

    # Drain the outstanding C writebacks (one per slot in flight).
    for s in range(min(2, world * m_tiles)):
        c_dma(s, 0).wait()

    ring_drain()


def _pick_block_k(k: int, want: int) -> int:
    for cand in (want, 512, 256, 128):
        if cand <= k and k % cand == 0:
            return cand
    return k


def _hbm_footprint(bm: int, bn: int, k: int, itemsize: int) -> int:
    """VMEM bytes of the N-blocked hbm kernel: 2 A tiles (bm, K) + 2 B
    panels (K, bn) + 2 C stages (bm, bn)."""
    return itemsize * (2 * bm * k + 2 * k * bn + 2 * bm * bn)


# Shape-keyed tuned configs: (m, k, n_tot_loc, dtype, world) → config dict.
# The analog of the reference's per-op static config tables + autotuner
# cache (allgather_gemm.py:396, autotuner.py:43-250).
_TUNED: dict[tuple, dict] = {}


def ag_gemm_configs(m: int, rows: int, k: int, n_tot_loc: int,
                    itemsize: int,
                    vmem_budget: int = DEFAULT_VMEM_BUDGET,
                    tier_caps: bool = True) -> list[dict]:
    """Candidate config table for the fused AG-GEMM (reference
    ``matmul_get_configs`` allgather_gemm.py:396, pruned to shapes that
    fit the hardware constraints). Ordered best-first: every entry point
    (default, autotune) consults this table, so an infeasible default can
    never reach the compiler (16.5 MB of declared scratch once met
    Mosaic's 16 MB default cap and took the program down).
    ``tier_caps=False`` skips the blind per-tier prefix caps and
    returns the FULL feasible space — the autotune path then prunes it
    with the perf_model cost model instead (docs/autotuner.md)."""
    vmem_cfgs: list[dict] = []
    vmem_fp = itemsize * (m * k + k * n_tot_loc + m * n_tot_loc + rows * k)
    if vmem_fp <= vmem_budget:
        vmem_cfgs.append({"variant": "vmem"})
    # N-blocked resident-B kernel: larger block_n first (A is re-read
    # n_tot_loc/block_n times; B exactly once). Large tiles are listed
    # in BOTH tiers: the budget tier when they fit (making them the
    # default where they are free), the aggressive tier when only the
    # raised compile cap admits them (review r5j finding 1).
    hbm_budget: list[dict] = []
    aggressive: list[dict] = []
    for bn in (2048, 1024, 512, 256, 128):
        if bn > n_tot_loc or n_tot_loc % bn:
            continue
        for bm in (1024, 512, 256, 128):
            if bm > rows or rows % bm:
                continue
            fp = _hbm_footprint(bm, bn, k, itemsize)
            if fp <= vmem_budget:
                hbm_budget.append({"variant": "hbm", "block_m": bm,
                                   "block_n": bn})
            elif fp <= HARD_FOOTPRINT_CAP:
                # Aggressive tier — concatenated LAST so the default
                # path (first feasible) never picks these; the
                # autotuner sweeps them under per-config failure
                # isolation (see HARD_FOOTPRINT_CAP in ops/common.py).
                aggressive.append({"variant": "hbm", "block_m": bm,
                                   "block_n": bn})
    # k-tiled fallback (huge K: no resident panel fits). Kept OUTSIDE
    # the tier cap: the entry-point clamps re-filter to these when a
    # hinted config is infeasible, so pruning must never drop them
    # (review r5l finding 1).
    kt_cfgs: list[dict] = []
    for bm in (128, 256, 512):
        if bm > rows:
            continue
        for bk in (256, 512, 1024):
            if bk > k:
                continue
            # tile footprint: 2 A-tiles + 2 B-tiles + acc + 2 C-stages
            fp = (2 * bm * bk + 2 * bk * n_tot_loc) * itemsize \
                + bm * n_tot_loc * (4 + 2 * itemsize)
            if fp <= vmem_budget:
                kt_cfgs.append({"variant": "hbm_kt", "block_m": bm,
                                "block_k": bk})
    if tier_caps:
        cfgs = (vmem_cfgs
                + cap_config_tiers(hbm_budget, [], n_budget=4)
                + kt_cfgs[:2]
                + cap_config_tiers([], aggressive))
    else:
        cfgs = vmem_cfgs + hbm_budget + kt_cfgs + aggressive
    return cfgs or [{"variant": "hbm_kt",
                     "block_m": _pick_block_k(rows, 128),
                     "block_k": _pick_block_k(k, 256)}]


def _autotune_ag_gemm(a, bs, ctx, key, n_tot_loc):
    """Eager sweep over :func:`ag_gemm_configs`; winner cached by shape
    and agreed across processes (tools/autotuner broadcast).

    The candidate space is the FULL feasible table (big tiles up to
    HARD_FOOTPRINT_CAP, generated against :data:`TUNED_VMEM_BUDGET` —
    the sweep has per-config failure isolation, so aggressive entries
    are safe to list without any global budget raise), pruned by the
    perf_model roofline cost model before any Mosaic compile is paid.
    """
    from triton_dist_tpu.tools.autotuner import autotune, record_prune
    from triton_dist_tpu.tools import perf_model as _pm

    m, k = a.shape
    rows = m // ctx.world_size
    item = a.dtype.itemsize
    world = ctx.world_size
    dirs = resolve_ring_dirs(ctx.ring_dirs)
    cfgs = ag_gemm_configs(m, rows, k, n_tot_loc, item,
                           max(ctx.vmem_budget, TUNED_VMEM_BUDGET),
                           tier_caps=False)
    cfgs, n_before = _pm.prune_configs(
        cfgs,
        lambda c: _pm.estimate_ag_gemm_cost(
            c, m=m, rows=rows, k=k, n_loc=n_tot_loc, itemsize=item,
            world=world, ring_dirs=dirs).total_ms,
        always_keep=lambda c: c["variant"] == "hbm_kt")
    record_prune("ag_gemm", n_before, len(cfgs))
    if len(cfgs) == 1:
        _TUNED[key] = cfgs[0]
        return cfgs[0]

    def make_fn(**cfg):
        ctx2 = dataclasses.replace(ctx, autotune=False,
                                   trust_blocks=True, **cfg)
        fn = jax.jit(lambda x, ws: ag_gemm_multi(x, ws, ctx2,
                                                 impl="pallas"))
        return lambda: fn(a, list(bs))

    result = autotune(make_fn, cfgs, key=f"ag_gemm:{key}", iters=8,
                      warmup_iters=2,
                      vet=lambda c: _pm.vet_vmem(
                          "ag_gemm", c, rows=rows, m=m, k=k,
                          n_loc=n_tot_loc, itemsize=item, world=world))
    _TUNED[key] = result.config
    return result.config


@resilient("ag_gemm", env_keys=("TDT_RING_DIRS",))
def ag_gemm_multi(a: jax.Array, bs,
                  ctx: AllGatherGEMMContext | None = None,
                  impl: str = "pallas"):
    """[C_i = allgather(a) @ b_i] sharing one fused all-gather.

    Args:
      a: (M, K) row-sharded over ``ctx.axis``.
      bs: sequence of (K, N_i), each column-sharded over ``ctx.axis``.
    Returns:
      list of C_i (M, N_i) column-sharded; with ``ctx.return_gathered``
      also the gathered A as the last element.
    """
    ctx = ctx or create_ag_gemm_context()
    mesh, axis, world = ctx.mesh, ctx.axis, ctx.world_size
    record_comm("ag_gemm", a)   # the gathered operand is the payload
    bs = list(bs)
    n_b = len(bs)
    m, k = a.shape
    for b in bs:
        assert b.shape[0] == k and b.shape[1] % world == 0
    assert m % world == 0
    rows = m // world
    c_spec = [P(None, axis)] * n_b
    out_specs = tuple(c_spec) + ((P(axis),) if ctx.return_gathered else ())

    if impl == "xla":
        def body(xs, *ws):
            ag = lax.all_gather(xs, axis, tiled=True)
            cs = [jnp.dot(ag, w, preferred_element_type=ctx.acc_dtype
                          ).astype(xs.dtype) for w in ws]
            return tuple(cs) + ((ag,) if ctx.return_gathered else ())
        f = nestable_shard_map(body, mesh=mesh,
                          in_specs=(P(axis),) + (P(None, axis),) * n_b,
                          out_specs=out_specs, check_vma=False)
        return list(f(a, *bs))

    interpret = resolve_interpret(ctx.interpret)
    n_tot_loc = sum(b.shape[1] // world for b in bs)

    if ctx.autotune:
        tune_key = (m, k, n_tot_loc, str(a.dtype), world)
        tuned = _TUNED.get(tune_key)
        if tuned is None and not isinstance(a, jax.core.Tracer):
            tuned = _autotune_ag_gemm(a, bs, ctx, tune_key, n_tot_loc)
        if tuned is not None:
            ctx = dataclasses.replace(ctx, autotune=False,
                                      trust_blocks=True, **tuned)

    variant = ctx.resolve_variant(m, k, n_tot_loc, a.dtype.itemsize)
    item = a.dtype.itemsize
    dirs = resolve_ring_dirs(ctx.ring_dirs)
    inject = dict(straggler_option=ctx.straggler_option,
                  for_correctness=ctx.for_correctness,
                  interp=bool(interpret))

    def emit_overlap(cfg):
        from triton_dist_tpu.tools import perf_model as _pm
        record_overlap("ag_gemm", _pm.estimate_ag_gemm_cost(
            cfg, m=m, rows=rows, k=k, n_loc=n_tot_loc, itemsize=item,
            world=world, ring_dirs=dirs), world=world, dirs=dirs)

    if variant == "hbm":
        # Clamp the ctx hint to divisors + the VMEM budget; fall back to
        # the first feasible table config, then to the k-tiled kernel —
        # an infeasible default must never reach Mosaic.
        m_blk = _pick_block_k(rows, ctx.block_m)
        n_blk = _pick_block_k(n_tot_loc, ctx.block_n)
        clamp_at = (HARD_FOOTPRINT_CAP if ctx.trust_blocks
                    else ctx.vmem_budget)
        if _hbm_footprint(m_blk, n_blk, k, item) > clamp_at:
            # Re-filter to a conservative in-budget config. With
            # trust_blocks (autotune sweep / tuned winner) the ceiling
            # is the hard COMPILE cap so the table's aggressive tier
            # reaches Mosaic at all (review r5i finding 1: a
            # soft-budget clamp here silently rewrote every swept
            # aggressive config back to the budget kernel); the default
            # path keeps the soft budget.
            cand = [c for c in ag_gemm_configs(m, rows, k, n_tot_loc,
                                               item, ctx.vmem_budget)
                    if c["variant"] == "hbm"
                    and _hbm_footprint(c["block_m"], c["block_n"], k,
                                       item) <= ctx.vmem_budget]
            if cand:
                m_blk, n_blk = cand[0]["block_m"], cand[0]["block_n"]
            else:
                variant = "hbm_kt"

    if variant == "hbm":
        emit_overlap({"variant": "hbm", "block_m": m_blk,
                      "block_n": n_blk})
        nb_kernel = functools.partial(
            _ag_gemm_hbm_nb_kernel, axis=axis, world=world, rows=rows,
            k=k, n_loc=n_tot_loc, m_blk=m_blk, n_blk=n_blk,
            acc_dtype=ctx.acc_dtype, dirs=dirs, **inject)

        def body(xs, *ws):
            wcat = ws[0] if n_b == 1 else jnp.concatenate(ws, axis=1)
            ag, ccat = pl.pallas_call(
                nb_kernel,
                name="ag_gemm_hbm",
                out_shape=(jax.ShapeDtypeStruct((m, k), a.dtype),
                           jax.ShapeDtypeStruct((m, n_tot_loc), a.dtype)),
                in_specs=[any_spec()] * 2,
                out_specs=(any_spec(),) * 2,
                scratch_shapes=[
                    pltpu.VMEM((2, m_blk, k), a.dtype),
                    pltpu.VMEM((2, k, n_blk), a.dtype),
                    pltpu.VMEM((2, m_blk, n_blk), a.dtype),
                    pltpu.SemaphoreType.DMA,
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SemaphoreType.DMA((dirs, world)),
                    pltpu.SemaphoreType.DMA((dirs, world)),
                ],
                compiler_params=comm_params(collective_id=4, world=world),
                interpret=interpret,
            )(xs, wcat)
            widths = [b.shape[1] // world for b in bs]
            cs, off = [], 0
            for wdt in widths:
                cs.append(lax.slice_in_dim(ccat, off, off + wdt, axis=1))
                off += wdt
            return tuple(cs) + ((ag,) if ctx.return_gathered else ())

        f = nestable_shard_map(body, mesh=mesh,
                          in_specs=(P(axis),) + (P(None, axis),) * n_b,
                          out_specs=out_specs, check_vma=False)
        return list(sync_interpret(f(a, *bs), interpret))

    if variant == "hbm_kt":
        k_blk = _pick_block_k(k, ctx.block_k)
        m_blk = _pick_block_k(rows, ctx.block_m)
        fp = (2 * m_blk * k_blk + 2 * k_blk * n_tot_loc) * item \
            + m_blk * n_tot_loc * (4 + 2 * item)
        if fp > ctx.vmem_budget:
            cand = [c for c in ag_gemm_configs(m, rows, k, n_tot_loc,
                                               item, ctx.vmem_budget)
                    if c["variant"] == "hbm_kt"]
            if cand:
                m_blk, k_blk = cand[0]["block_m"], cand[0]["block_k"]
        emit_overlap({"variant": "hbm_kt", "block_m": m_blk,
                      "block_k": k_blk})
        hbm_kernel = functools.partial(
            _ag_gemm_hbm_kernel, axis=axis, world=world, rows=rows, k=k,
            k_blk=k_blk, m_blk=m_blk, acc_dtype=ctx.acc_dtype, dirs=dirs,
            **inject)

        def body(xs, *ws):
            wcat = ws[0] if n_b == 1 else jnp.concatenate(ws, axis=1)
            ag, ccat = pl.pallas_call(
                hbm_kernel,
                name="ag_gemm_hbm_kt",
                out_shape=(jax.ShapeDtypeStruct((m, k), a.dtype),
                           jax.ShapeDtypeStruct((m, n_tot_loc), a.dtype)),
                in_specs=[any_spec()] * 2,
                out_specs=(any_spec(),) * 2,
                scratch_shapes=[
                    pltpu.VMEM((2, m_blk, k_blk), a.dtype),
                    pltpu.VMEM((2, k_blk, n_tot_loc), a.dtype),
                    pltpu.VMEM((m_blk, n_tot_loc), ctx.acc_dtype),
                    pltpu.VMEM((2, m_blk, n_tot_loc), a.dtype),
                    pltpu.SemaphoreType.DMA,
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SemaphoreType.DMA((dirs, world)),
                    pltpu.SemaphoreType.DMA((dirs, world)),
                ],
                compiler_params=comm_params(collective_id=4, world=world),
                interpret=interpret,
            )(xs, wcat)
            widths = [b.shape[1] // world for b in bs]
            cs, off = [], 0
            for wdt in widths:
                cs.append(lax.slice_in_dim(ccat, off, off + wdt, axis=1))
                off += wdt
            return tuple(cs) + ((ag,) if ctx.return_gathered else ())

        f = nestable_shard_map(body, mesh=mesh,
                          in_specs=(P(axis),) + (P(None, axis),) * n_b,
                          out_specs=out_specs, check_vma=False)
        return list(sync_interpret(f(a, *bs), interpret))

    emit_overlap({"variant": "vmem"})
    kernel = functools.partial(_ag_gemm_kernel, axis=axis, world=world,
                               rows=rows, acc_dtype=ctx.acc_dtype, n_b=n_b,
                               dirs=dirs, **inject)

    def body(xs, *ws):
        out = pl.pallas_call(
            kernel,
            name="ag_gemm_vmem",
            out_shape=tuple(
                [jax.ShapeDtypeStruct((m, k), a.dtype)] +
                [jax.ShapeDtypeStruct((m, b.shape[1] // world), a.dtype)
                 for b in bs]),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * (1 + n_b),
            out_specs=tuple([pl.BlockSpec(memory_space=pltpu.VMEM)]
                            * (1 + n_b)),
            scratch_shapes=[pltpu.SemaphoreType.DMA((dirs, world)),
                            pltpu.SemaphoreType.DMA((dirs, world))],
            compiler_params=comm_params(collective_id=4, world=world),
            interpret=interpret,
        )(xs, *ws)
        ag, cs = out[0], out[1:]
        return tuple(cs) + ((ag,) if ctx.return_gathered else ())

    f = nestable_shard_map(body, mesh=mesh,
                      in_specs=(P(axis),) + (P(None, axis),) * n_b,
                      out_specs=out_specs, check_vma=False)
    return list(sync_interpret(f(a, *bs), interpret))


def ag_gemm(a: jax.Array, b: jax.Array,
            ctx: AllGatherGEMMContext | None = None,
            impl: str = "pallas"):
    """C = allgather(a) @ b (functional entry, reference ``ag_gemm``
    allgather_gemm.py:534).

    Args:
      a: (M, K) row-sharded over ``ctx.axis``.
      b: (K, N) column-sharded over ``ctx.axis``.
    Returns:
      C: (M, N) column-sharded; with ``ctx.return_gathered`` also the
      gathered A (stacked per device: (w*M, K) sharded).
    """
    out = ag_gemm_multi(a, [b], ctx, impl)
    if len(out) == 2:
        return out[0], out[1]
    return out[0]


def _swiglu_footprint(bm: int, bn: int, k: int, itemsize: int) -> int:
    """VMEM bytes of the SwiGLU hbm kernel: 2 A tiles (bm, K) + 2x2 B
    panels (K, bn) (gate AND up resident) + 2 act stages (bm, bn)."""
    return itemsize * (2 * bm * k + 4 * k * bn + 2 * bm * bn)


def ag_swiglu_configs(rows: int, k: int, n_loc: int,
                      itemsize: int,
                      vmem_budget: int = DEFAULT_VMEM_BUDGET,
                      tier_caps: bool = True) -> list[dict]:
    """Candidate (block_m, block_n) table for the fused SwiGLU kernel,
    ordered best-first; same two-tier structure as
    :func:`ag_gemm_configs` (budget tier, then an aggressive tier up to
    HARD_FOOTPRINT_CAP for the autotuner — the dual gate+up panel
    doubles B residency, so feasible tiles are smaller than the plain
    AG-GEMM's at equal budget). ``tier_caps=False`` returns the full
    feasible space for cost-model pruning."""
    budget: list[dict] = []
    aggressive: list[dict] = []
    for bn in (2048, 1024, 512, 256, 128):
        if bn > n_loc or n_loc % bn:
            continue
        for bm in (1024, 512, 256, 128):
            if bm > rows or rows % bm:
                continue
            fp = _swiglu_footprint(bm, bn, k, itemsize)
            if fp <= vmem_budget:
                budget.append({"block_m": bm, "block_n": bn})
            elif fp <= HARD_FOOTPRINT_CAP:
                aggressive.append({"block_m": bm, "block_n": bn})
    if not tier_caps:
        return budget + aggressive
    return cap_config_tiers(budget, aggressive)


def _autotune_ag_swiglu(a, w_gate, w_up, ctx, key):
    """Eager sweep over :func:`ag_swiglu_configs`; winner cached by
    shape alongside the ag_gemm winners (same _TUNED map, distinct
    key tag). Candidates are the full feasible table (generated
    against TUNED_VMEM_BUDGET; the sweep's per-config isolation makes
    aggressive tiles safe), cost-model pruned before any compile."""
    from triton_dist_tpu.tools.autotuner import autotune, record_prune
    from triton_dist_tpu.tools import perf_model as _pm

    m, k = a.shape
    rows = m // ctx.world_size
    item = a.dtype.itemsize
    n_loc = w_gate.shape[1] // ctx.world_size
    dirs = resolve_ring_dirs(ctx.ring_dirs)
    cfgs = ag_swiglu_configs(rows, k, n_loc, item,
                             max(ctx.vmem_budget, TUNED_VMEM_BUDGET),
                             tier_caps=False)
    if not cfgs:
        return None
    cfgs, n_before = _pm.prune_configs(
        cfgs,
        lambda c: _pm.estimate_ag_swiglu_cost(
            c, m=m, rows=rows, k=k, n_loc=n_loc, itemsize=item,
            world=ctx.world_size, ring_dirs=dirs).total_ms)
    record_prune("ag_swiglu", n_before, len(cfgs))
    if len(cfgs) == 1:
        _TUNED[key] = cfgs[0]
        return cfgs[0]

    def make_fn(**cfg):
        ctx2 = dataclasses.replace(ctx, autotune=False,
                                   trust_blocks=True, **cfg)
        fn = jax.jit(lambda x, wg, wu: ag_swiglu(x, wg, wu, ctx2,
                                                 impl="pallas"))
        return lambda: fn(a, w_gate, w_up)

    result = autotune(make_fn, cfgs, key=f"ag_swiglu:{key}", iters=8,
                      warmup_iters=2,
                      vet=lambda c: _pm.vet_vmem(
                          "ag_swiglu", c, rows=rows, k=k,
                          itemsize=item))
    _TUNED[key] = result.config
    return result.config


def _ag_swiglu_hbm_kernel(x_hbm, wg_hbm, wu_hbm, *rest, axis: str,
                          world: int, rows: int, k: int, n_loc: int,
                          m_blk: int, n_blk: int, acc_dtype,
                          dirs: int = 1, has_bias: bool = False,
                          straggler_option=None,
                          for_correctness=False, interp=False):
    """AG + dual GEMM + bias + SwiGLU epilogue in ONE kernel.

    Same ring/double-buffer structure as :func:`_ag_gemm_hbm_nb_kernel`
    (incl. the bidirectional schedule via ``_make_ring``), but each
    N-block holds BOTH the gate and up B panels (separate HBM inputs —
    no concatenated copy) and writes
    ``silu(A@Wg + bg) * (A@Wu + bu)`` directly — the (M, 2*n_loc)
    gate/up intermediate never exists in HBM and the whole TP-MLP front
    epilogue (bias add + SwiGLU gate) needs no separate XLA kernel.
    This is what XLA's fusion does for the unsharded MLP; the round-3
    chip bench measured the 3-dispatch fused path at 0.77x of XLA's
    single fused program at world=1, and this kernel removes exactly
    that overhead (reference TP_MLP runs AG-GEMM then a separate
    silu-mul, tp_mlp.py:147-270 — fusing past it is a TPU-side win,
    not a parity requirement). Biases are tiny (1, n_loc) VMEM
    residents; ``has_bias=False`` omits the operands entirely.
    """
    n_bias = 2 if has_bias else 0
    bg_ref = rest[0] if has_bias else None
    bu_ref = rest[1] if has_bias else None
    ag_hbm, act_hbm = rest[n_bias], rest[n_bias + 1]
    (a_tile, b_panel, c_stage, copy_sem, a_sem, b_sem, c_sem,
     send_sem, recv_sem) = rest[n_bias + 2:]
    me = lax.axis_index(axis)
    m_tiles = rows // m_blk
    n_blocks = n_loc // n_blk
    per_nb = world * m_tiles
    total = n_blocks * per_nb

    cp = pltpu.make_async_copy(x_hbm, ag_hbm.at[pl.ds(me * rows, rows), :],
                               copy_sem)
    cp.start()
    cp.wait()
    if world > 1:
        dl.barrier_all(axis)
        maybe_straggle(straggler_option, axis, interp)
        maybe_noise(for_correctness, axis, world, salt=4, interpret=interp)

    chunk_of, advance, ring_drain = _make_ring(
        lambda idx: ag_hbm.at[pl.ds(idx * rows, rows), :], me, axis,
        world, dirs, send_sem, recv_sem)

    def chunk_idx(i):
        return chunk_of(lax.rem(i, per_nb) // m_tiles)

    def row_of(i):
        mt = lax.rem(i, m_tiles)
        return chunk_idx(i) * rows + mt * m_blk

    def a_dma(slot, i):
        return pltpu.make_async_copy(
            ag_hbm.at[pl.ds(row_of(i), m_blk), :], a_tile.at[slot],
            a_sem.at[slot])

    def b_dma(slot, half, nb):
        """half 0 = gate panel, half 1 = up panel (static Python int)."""
        src = wg_hbm if half == 0 else wu_hbm
        return pltpu.make_async_copy(
            src.at[:, pl.ds(nb * n_blk, n_blk)],
            b_panel.at[slot, half], b_sem.at[slot, half])

    def c_dma(slot, i):
        return pltpu.make_async_copy(
            c_stage.at[slot],
            act_hbm.at[pl.ds(row_of(i), m_blk),
                       pl.ds((i // per_nb) * n_blk, n_blk)],
            c_sem.at[slot])

    def ring_advance(i):
        if world == 1:
            return

        @pl.when((i < per_nb) & (lax.rem(i, m_tiles) == 0))
        def _():
            advance(i // m_tiles)

    ring_advance(0)
    b_dma(0, 0, 0).start()
    b_dma(0, 1, 0).start()
    a_dma(0, 0).start()

    def step(i, _):
        slot = lax.rem(i, 2)
        nb = i // per_nb
        bslot = lax.rem(nb, 2)
        ring_advance(i + 1)

        @pl.when(i + 1 < total)
        def _():
            a_dma(lax.rem(i + 1, 2), i + 1).start()

        @pl.when((lax.rem(i, per_nb) == 0) & (nb + 1 < n_blocks))
        def _():
            b_dma(lax.rem(nb + 1, 2), 0, nb + 1).start()
            b_dma(lax.rem(nb + 1, 2), 1, nb + 1).start()

        @pl.when(lax.rem(i, per_nb) == 0)
        def _():
            b_dma(bslot, 0, nb).wait()
            b_dma(bslot, 1, nb).wait()
        a_dma(slot, i).wait()

        gate = jnp.dot(a_tile[slot], b_panel[bslot, 0],
                       preferred_element_type=acc_dtype)
        up = jnp.dot(a_tile[slot], b_panel[bslot, 1],
                     preferred_element_type=acc_dtype)
        if has_bias:
            col = pl.ds(nb * n_blk, n_blk)
            gate = gate + bg_ref[0:1, col].astype(acc_dtype)
            up = up + bu_ref[0:1, col].astype(acc_dtype)
        act = gate * jax.nn.sigmoid(gate) * up      # SwiGLU in acc dtype

        @pl.when(i >= 2)
        def _():
            c_dma(slot, i - 2).wait()
        c_stage[slot] = act.astype(c_stage.dtype)
        c_dma(slot, i).start()
        return _

    lax.fori_loop(0, total, step, None)

    for i_last in range(max(0, total - 2), total):
        c_dma(i_last % 2, i_last).wait()

    ring_drain()


@resilient("ag_swiglu", env_keys=("TDT_RING_DIRS",))
def ag_swiglu(a: jax.Array, w_gate: jax.Array, w_up: jax.Array,
              ctx: AllGatherGEMMContext | None = None,
              impl: str = "pallas",
              b_gate: jax.Array | None = None,
              b_up: jax.Array | None = None) -> jax.Array:
    """``silu(allgather(a) @ w_gate + b_gate) * (allgather(a) @ w_up +
    b_up)`` fused.

    The MLP front half as ONE kernel (AG + both GEMMs + bias +
    activation — the whole TP-MLP epilogue lives in the consumer tile
    loop, so the activation never makes an extra HBM round trip).
    Not differentiable directly — training wraps it in
    :func:`triton_dist_tpu.ops.autodiff.ag_swiglu`, whose backward
    recomputes gate/up through the differentiable composition (bias-free
    form; the biased epilogue is the inference path).

    Args:
      a: (M, K) row-sharded over ``ctx.axis``.
      w_gate/w_up: (K, N) column-sharded over ``ctx.axis``.
      b_gate/b_up: optional (N,) biases, column-sharded like the
        weights; pass both or neither.
    Returns:
      act: (M, N_loc-per-shard) column-sharded, a.dtype.
    """
    ctx = ctx or create_ag_gemm_context()
    if ctx.return_gathered:  # same convention as autodiff.ag_gemm_multi
        raise ValueError("ag_swiglu does not support return_gathered "
                         "(the gathered A is a workspace, not an output)")
    if (b_gate is None) != (b_up is None):
        raise ValueError("pass both biases or neither")
    mesh, axis, world = ctx.mesh, ctx.axis, ctx.world_size
    record_comm("ag_swiglu", a)
    m, k = a.shape
    assert w_gate.shape == w_up.shape and w_gate.shape[0] == k
    assert w_gate.shape[1] % world == 0 and m % world == 0
    n_loc = w_gate.shape[1] // world
    rows = m // world
    has_bias = b_gate is not None
    if has_bias:
        assert b_gate.shape[-1] == w_gate.shape[1], (b_gate.shape,
                                                     w_gate.shape)
        # (1, N) keeps the lane-major layout; sharded like the weights.
        biases = (jnp.reshape(b_gate, (1, -1)),
                  jnp.reshape(b_up, (1, -1)))
    else:
        biases = ()

    if impl == "xla":
        def body(xs, wg, wu, *bs):
            ag = lax.all_gather(xs, axis, tiled=True)
            gate = jnp.dot(ag, wg, preferred_element_type=ctx.acc_dtype)
            up = jnp.dot(ag, wu, preferred_element_type=ctx.acc_dtype)
            if bs:
                gate = gate + bs[0].astype(ctx.acc_dtype)
                up = up + bs[1].astype(ctx.acc_dtype)
            return (jax.nn.silu(gate) * up).astype(xs.dtype)
        f = nestable_shard_map(body, mesh=mesh,
                               in_specs=(P(axis), P(None, axis),
                                         P(None, axis))
                               + (P(None, axis),) * len(biases),
                               out_specs=P(None, axis), check_vma=False)
        return f(a, w_gate, w_up, *biases)

    interpret = resolve_interpret(ctx.interpret)
    item = a.dtype.itemsize
    dirs = resolve_ring_dirs(ctx.ring_dirs)

    if ctx.autotune:
        tune_key = (m, k, n_loc, str(a.dtype), world, "swiglu")
        tuned = _TUNED.get(tune_key)
        if tuned is None and not isinstance(a, jax.core.Tracer):
            tuned = _autotune_ag_swiglu(a, w_gate, w_up, ctx, tune_key)
        if tuned is not None:
            ctx = dataclasses.replace(ctx, autotune=False,
                                      trust_blocks=True, **tuned)

    # trust_blocks (sweep / tuned winner) honors the HINT blocks up to
    # the hard compile cap — only the hint: the descending fallbacks
    # below stay under the soft budget, so an infeasible trusted hint
    # degrades to a conservative config rather than to an unswept
    # aggressive one (review r5k finding 1; same contract as the
    # ag_gemm entry's re-filter).
    choice = None
    if ctx.trust_blocks:
        bm_h = _pick_block_k(rows, ctx.block_m)
        bn_h = _pick_block_k(n_loc, ctx.block_n)
        if (bn_h <= n_loc and n_loc % bn_h == 0 and bm_h <= rows
                and rows % bm_h == 0
                and _swiglu_footprint(bm_h, bn_h, k,
                                      item) <= HARD_FOOTPRINT_CAP):
            choice = (bm_h, bn_h)
    # First feasible (m_blk, n_blk) under the soft budget; the gate+up
    # dual panel doubles B residency vs the plain hbm kernel.
    if choice is None:
        for bn in (_pick_block_k(n_loc, ctx.block_n), 512, 256, 128):
            if bn > n_loc or n_loc % bn:
                continue
            for bm in (_pick_block_k(rows, ctx.block_m), 256, 128):
                if bm > rows or rows % bm:
                    continue
                if _swiglu_footprint(bm, bn, k, item) <= ctx.vmem_budget:
                    choice = (bm, bn)
                    break
            if choice:
                break
    if choice is None or rows % 128 or n_loc % 128:
        # No feasible single-kernel tiling (huge K or tiny shards):
        # compose from the proven pieces — still fused AG, unfused act.
        gate, up = ag_gemm_multi(a, [w_gate, w_up], ctx, impl=impl)
        if has_bias:
            # gate/up are (M, N) column-sharded globals; the (1, N)
            # biases broadcast — XLA inserts the matching sharding.
            gate = (gate.astype(jnp.float32)
                    + biases[0].astype(jnp.float32)).astype(a.dtype)
            up = (up.astype(jnp.float32)
                  + biases[1].astype(jnp.float32)).astype(a.dtype)
        return (jax.nn.silu(gate.astype(jnp.float32))
                ).astype(a.dtype) * up
    m_blk, n_blk = choice

    from triton_dist_tpu.tools import perf_model as _pm
    record_overlap("ag_swiglu", _pm.estimate_ag_swiglu_cost(
        {"block_m": m_blk, "block_n": n_blk}, m=m, rows=rows, k=k,
        n_loc=n_loc, itemsize=item, world=world, ring_dirs=dirs),
        world=world, dirs=dirs)

    kernel = functools.partial(
        _ag_swiglu_hbm_kernel, axis=axis, world=world, rows=rows, k=k,
        n_loc=n_loc, m_blk=m_blk, n_blk=n_blk, acc_dtype=ctx.acc_dtype,
        dirs=dirs, has_bias=has_bias,
        straggler_option=ctx.straggler_option,
        for_correctness=ctx.for_correctness, interp=bool(interpret))

    def body(xs, wg, wu, *bs):
        out = pl.pallas_call(
            kernel,
            name="ag_swiglu",
            out_shape=(jax.ShapeDtypeStruct((m, k), a.dtype),
                       jax.ShapeDtypeStruct((m, n_loc), a.dtype)),
            in_specs=[any_spec()] * 3
            + [pl.BlockSpec(memory_space=pltpu.VMEM)] * len(bs),
            out_specs=(any_spec(),) * 2,
            scratch_shapes=[
                pltpu.VMEM((2, m_blk, k), a.dtype),
                pltpu.VMEM((2, 2, k, n_blk), a.dtype),
                pltpu.VMEM((2, m_blk, n_blk), a.dtype),
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((dirs, world)),
                pltpu.SemaphoreType.DMA((dirs, world)),
            ],
            compiler_params=comm_params(collective_id=4, world=world),
            interpret=interpret,
        )(xs, wg, wu, *bs)
        return out[1]

    f = nestable_shard_map(body, mesh=mesh,
                           in_specs=(P(axis), P(None, axis),
                                     P(None, axis))
                           + (P(None, axis),) * len(biases),
                           out_specs=P(None, axis), check_vma=False)
    return sync_interpret(f(a, w_gate, w_up, *biases), interpret)
