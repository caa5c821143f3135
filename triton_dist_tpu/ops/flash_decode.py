"""Distributed flash-decode: tiled split-KV GQA decode with paged KV and
cross-rank partial-softmax combine.

TPU-native redesign of the reference's distributed flash-decode
(python/triton_dist/kernels/nvidia/flash_decode.py: split-KV batch decode
kernels :130-393 with paged KV via block_table/page_size :136,:203,
persistent variant :587, intra-rank combine :393-482, **inter-rank
combine** merging (m, l, acc) partial softmax states through symmetric
buffers :482-566; host wrappers :763-1130; scaling claim 1→32 GPUs
README.md:203).

Design: the KV cache is sequence-sharded over the SP axis. Each device
computes an *unnormalized* flash partial over its shard:

    m = max_t s_t,   l = Σ_t e^{s_t - m},   a = Σ_t e^{s_t - m} v_t

and the cross-rank combine is the associative log-sum-exp merge

    out = Σ_r a_r e^{m_r - m*} / Σ_r l_r e^{m_r - m*},  m* = max_r m_r.

Local-partial variants (``FlashDecodeContext.variant``):
  * ``tiled``  — the real kernel: KV stays in HBM; (B, t_blk, D) tiles
    per KV head stream through double-buffered VMEM feeding an
    online-softmax loop. Never materializes (B, K, G, T) scores, so
    T ≥ 64k per device fits. The single long-running kernel is the
    analog of the reference's persistent variant (:587); the tile DMA
    pipeline replaces its split-KV grid.
  * ``einsum`` — whole-shard scores in one batched einsum; lowest
    latency for short caches that fit VMEM.
  * ``auto``   — picks by KV-shard byte size.

Paged KV (``gqa_fwd_batch_decode_paged``): the cache is a physical page
pool (P, page_size, Hkv, D); ``block_table[b, i]`` maps sequence b's
i-th logical page to a pool slot (reference block_table/page_table
indirection, flash_decode.py:136,:203). Tiles are DMA'd page-by-page via
the table — t_blk == page_size.

``impl="xla"``: partials via one batched einsum; merge via ``pmax`` +
``psum`` (3 scalar-sized collectives — the reference needs a second
kernel + symmetric buffers for the same merge).
``impl="pallas"``: one kernel per device — computes its partial, pushes
(a, l, m) to every peer by remote DMA (the symmetric-buffer exchange,
flash_decode.py:482-566), waits, and merges locally.
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
    any_spec,
    comm_params,
    nestable_shard_map,
    resolve_interpret,
    sync_interpret)

_NEG = -1e30


@dataclasses.dataclass
class FlashDecodeContext:
    """Analog of the reference's flash-decode context/workspace
    (flash_decode.py:763-850): axis + combine buffers (kernel-owned)."""
    mesh: Mesh
    axis: str = "sp"
    interpret: bool | None = None
    # Local-partial variant: "tiled" | "einsum" | "auto" (by shard bytes).
    variant: str = "auto"
    # KV positions per VMEM tile for the tiled variant (dense path);
    # auto-shrunk so the two double-buffered (B, t_blk, Hkv, D) K/V tiles
    # fit ``vmem_budget`` (an infeasible tile size must never reach
    # the compiler — tests/test_vmem_budget.py).
    t_blk: int = 512
    vmem_budget: int = 10 * 1024 * 1024
    # Byte threshold for auto: einsum below (shard fits VMEM comfortably).
    einsum_max_bytes: int = 4 * 1024 * 1024
    # Paged-KV kernel path: "direct" streams pages into the tiled
    # kernel via block-table indirection (one DMA per batch row per
    # tile); "gathered" reconstructs the contiguous per-device KV view
    # with an XLA gather and runs the PROVEN dense tiled kernel.
    # DEFAULT is "gathered" (ADVICE r5 medium): the direct kernel's
    # round-5 on-chip Mosaic compile hang (tpu_smoke_r5_bulk.log:
    # flash_decode/paged, >40 min) is still un-root-caused, and a
    # production paged server must not wedge by default. "direct" is
    # the opt-in — via this field or the TDT_PAGED_VARIANT env var,
    # which overrides the field so a deployment can flip paths without
    # code changes — until the hang is fixed. (Its smoke-queue canary
    # is retired: docs/resilience.md "Retired canary".)
    paged_variant: str = "gathered"

    @property
    def world_size(self) -> int:
        return self.mesh.shape[self.axis]

    def resolve_variant(self, shard_bytes: int) -> str:
        if self.variant != "auto":
            return self.variant
        return "einsum" if shard_bytes <= self.einsum_max_bytes else "tiled"


def create_flash_decode_context(mesh: Mesh | None = None, axis: str = "sp",
                                interpret: bool | None = None,
                                variant: str = "auto",
                                t_blk: int = 512) -> FlashDecodeContext:
    if mesh is None:
        from triton_dist_tpu.runtime.dist import get_mesh
        mesh = get_mesh()
    return FlashDecodeContext(mesh=mesh, axis=axis, interpret=interpret,
                              variant=variant, t_blk=t_blk)


def _qk_scores(qg, kt):
    """(B, K, G, D) x (B, T, K, D) -> (B, K, G, T) scores.

    Mosaic's ``tpu.matmul`` supports at most ONE batch dimension
    (VERDICT r2: the two-batch-dim ``bkgd,btkd->bkgt`` einsum fails to
    compile), so the KV-head dimension is unrolled as a static Python
    loop — each per-head dot keeps only B as the batch dim.
    """
    hkv = qg.shape[1]
    outs = [lax.dot_general(qg[:, h], kt[:, :, h],
                            (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
            for h in range(hkv)]
    return jnp.stack(outs, axis=1)


def _pv_accum(p, vt):
    """(B, K, G, T) x (B, T, K, D) -> (B, K, G, D), one batch dim per dot
    (same Mosaic constraint as :func:`_qk_scores`)."""
    hkv = p.shape[1]
    outs = [lax.dot_general(p[:, h], vt[:, :, h],
                            (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
            for h in range(hkv)]
    return jnp.stack(outs, axis=1)


def _local_partials(q, k, v, first_pos, kv_len, groups: int,
                    mosaic: bool = False):
    """Unnormalized flash partial over one KV shard (einsum variant).

    q: (B, Hq, D); k/v: (B, T, Hkv, D); positions of the shard are
    ``first_pos + [0, T)``; only positions < ``kv_len`` are live.
    ``kv_len`` is PER-BATCH (B,) — the reference loads
    ``kv_length_ptr + bid`` per sequence (flash_decode.py:182); a
    scalar is broadcast. Returns a (B, K, G, D), l (B, K, G),
    m (B, K, G) in fp32. ``mosaic=True`` routes the contractions
    through the per-head single-batch-dim dots (required inside Pallas
    kernels).
    """
    b, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    # QK in the cache dtype when q matches it (MXU-native; f32
    # accumulation makes the scores bit-identical to an upcast-first
    # dot); precision-mismatched callers keep the exact f32 path —
    # see the tiled kernel (review r4b-4).
    dt = k.dtype if q.dtype == k.dtype else jnp.float32
    qg = q.reshape(b, hkv, groups, d).astype(dt)
    kc = k.astype(dt)
    if mosaic:
        scores = _qk_scores(qg, kc) * (d ** -0.5)
    else:
        scores = jnp.einsum("bkgd,btkd->bkgt", qg, kc,
                            preferred_element_type=jnp.float32
                            ) * (d ** -0.5)
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))
    live = (first_pos + jnp.arange(t))[None, :] < lens[:, None]  # (B, T)
    live4 = live[:, None, None, :]
    scores = jnp.where(live4, scores, _NEG)
    m = jnp.max(scores, axis=-1)
    p = jnp.exp(scores - m[..., None]) * live4
    l = jnp.sum(p, axis=-1)
    pv_in = p.astype(dt)   # PV in the compute dtype, f32 accumulate
    vc = v.astype(dt)
    if mosaic:
        a = _pv_accum(pv_in, vc)
    else:
        a = jnp.einsum("bkgt,btkd->bkgd", pv_in, vc,
                       preferred_element_type=jnp.float32)
    return a, l, m


#: Lane width of the combine's (l, m) exchange buffers. The softmax
#: statistics are (B, K, G) scalars, but a remote DMA moves whole
#: (sublane, 128-lane) tiles: laid out with ``groups`` (2 or 4) as the
#: minor dimension Mosaic refuses the per-rank slice ("Slice shape along
#: dimension 3 must be aligned to tiling (128), but is 4"). So each
#: statistic is broadcast along a trailing 128-lane dimension, like the
#: head dimension of the ``a`` partial beside it.
_STAT_LANES = 128


def _lane_bcast(x):
    return jnp.broadcast_to(x[..., None], x.shape + (_STAT_LANES,))


def _merge(a, l, m):
    """Merge per-rank partials stacked on the leading axis: ``a``
    (w, B, K, G, D); ``l``/``m`` (w, B, K, G, _STAT_LANES), every lane
    holding the same value."""
    m_star = jnp.max(m, axis=0, keepdims=True)
    scale = jnp.exp(m - m_star)
    num = jnp.sum(a * scale[..., :1], axis=0)
    den = jnp.sum(l * scale, axis=0)
    return num / jnp.maximum(den[..., :1], 1e-20)


def combine_peer(me, p, world: int):
    """Peer targeted at combine send position ``p`` (1..world-1).
    Exposed for symbolic execution — the flash-decode-protocol model
    checker (analysis/flash_model.py) executes this with concrete
    ranks; ``_exchange_and_merge`` calls it with traced values so the
    checker and the kernel cannot drift apart."""
    return lax.rem(me + p, world)


def combine_src(me, p, world: int):
    """Source waited on at combine wait position ``p`` (1..world-1) —
    the left-rotation mirror of :func:`combine_peer`."""
    return lax.rem(me - p + world, world)


def _exchange_and_merge(abuf, lbuf, mbuf, send_sem, recv_sem, o_ref, *,
                        axis: str, world: int):
    """Full-mesh push of this rank's (a, l, m) partial into every peer's
    combine-buffer slot, wait for all peers, then merge locally — the
    symmetric-buffer exchange of the reference's inter-rank combine
    (flash_decode.py:482-566)."""
    me = lax.axis_index(axis)
    if world > 1:
        # Peers' buffers must exist before remote writes land.
        dl.barrier_all(axis)

        def copies(p):
            peer = combine_peer(me, p, world)
            return [dl.remote_copy(ref.at[me], ref.at[me], peer,
                                   send_sem.at[peer, i], recv_sem.at[me, i],
                                   axis=axis)
                    for i, ref in enumerate((abuf, lbuf, mbuf))]

        def send(p, _):
            for c in copies(p):
                c.start()
            return _
        lax.fori_loop(1, world, send, None)

        def wait(p, _):
            src = combine_src(me, p, world)
            for i, ref in enumerate((abuf, lbuf, mbuf)):
                dl.remote_copy(ref.at[src], ref.at[src], me,
                               send_sem.at[src, i], recv_sem.at[src, i],
                               axis=axis).wait_recv()
            return _
        lax.fori_loop(1, world, wait, None)

        def drain(p, _):
            for c in copies(p):
                c.wait_send()
            return _
        lax.fori_loop(1, world, drain, None)

    out = _merge(abuf[:], lbuf[:], mbuf[:])
    b = out.shape[0]
    o_ref[:] = out.reshape(b, -1, out.shape[-1]).astype(o_ref.dtype)


def _decode_kernel(q_ref, k_ref, v_ref, len_ref, o_ref, abuf, lbuf, mbuf,
                   send_sem, recv_sem, *, axis: str, world: int,
                   groups: int, t_loc: int):
    """Einsum-variant distributed decode: whole-shard partial in VMEM →
    cross-rank combine. Lowest latency for short caches."""
    me = lax.axis_index(axis)
    # (B,) per-sequence lengths — SMEM loads must be scalar (Mosaic),
    # so unroll the small batch dim.
    kv_len = jnp.stack([len_ref[i] for i in range(q_ref.shape[0])])
    a, l, m = _local_partials(q_ref[:], k_ref[:], v_ref[:],
                              me * t_loc, kv_len, groups, mosaic=True)
    abuf[me] = a
    lbuf[me] = _lane_bcast(l)
    mbuf[me] = _lane_bcast(m)
    _exchange_and_merge(abuf, lbuf, mbuf, send_sem, recv_sem, o_ref,
                        axis=axis, world=world)


def _tiled_decode_kernel(q_ref, len_ref, table_ref, k_hbm, v_hbm, o_ref,
                         abuf, lbuf, mbuf, k_tile, v_tile, k_sem, v_sem,
                         send_sem, recv_sem, *, axis: str, world: int,
                         batch: int, hkv: int, groups: int, d: int,
                         t_loc: int, t_blk: int, paged: bool):
    """Tiled split-KV partial: stream (B, t_blk, D) K/V tiles per KV head
    through double-buffered VMEM with an online-softmax carry, then the
    cross-rank combine.

    The KV refs live in HBM (``pl.ANY``):
      dense: (B, T_loc, Hkv, D); tile DMA slices rows [ts, ts+t_blk).
      paged: pool (P, page_size, Hkv, D) + ``table_ref`` (B, n_pages)
        int32 in SMEM; tile i of sequence b reads pool[table[b, i]]
        (reference block_table indirection, flash_decode.py:136,:203).

    Per-tile trip count is *dynamic* (ceil of the live positions in this
    rank's shard), so ranks whose shard lies past ``kv_len`` skip all
    DMAs and compute — the split-KV early-exit of the reference's
    persistent kernel (:587).
    """
    me = lax.axis_index(axis)
    scale = d ** -0.5

    # Per-sequence lengths (reference kv_length_ptr + bid,
    # flash_decode.py:182); the DMA trip count covers the longest live
    # row, per-row tails are masked per tile below. SMEM loads must be
    # scalar (Mosaic), so unroll the small batch dim.
    lens = jnp.stack([len_ref[i] for i in range(batch)])
    kv_max = jnp.max(lens)
    first_pos = me * t_loc
    live_here = jnp.clip(kv_max - first_pos, 0, t_loc)
    n_tiles = lax.div(live_here + t_blk - 1, t_blk)

    def paged_dma(hbm, tile, sem, slot, ti, b):
        # Paged: each sequence's tile lives on its own page → one DMA
        # per batch row (block_table indirection).
        page = table_ref[b, ti]
        return pltpu.make_async_copy(hbm.at[page, :, :, :],
                                     tile.at[slot, b], sem.at[slot, b])

    def dense_dma(hbm, tile, sem, slot, ti):
        # Dense cache: the whole (B, t_blk, Hkv, D) tile is one strided
        # DMA — 2 descriptors per tile instead of 2*B (B=8 serving
        # batches were paying 16 issue latencies per tile).
        return pltpu.make_async_copy(
            hbm.at[:, pl.ds(ti * t_blk, t_blk), :, :], tile.at[slot],
            sem.at[slot, 0])

    _kv = ((k_hbm, k_tile, k_sem), (v_hbm, v_tile, v_sem))

    def tile_dmas(slot, ti):
        if paged:
            return [paged_dma(*refs, slot, ti, b)
                    for refs in _kv for b in range(batch)]
        return [dense_dma(*refs, slot, ti) for refs in _kv]

    def start_tile(slot, ti):
        for dma in tile_dmas(slot, ti):
            dma.start()

    def wait_tile(slot, ti):
        for dma in tile_dmas(slot, ti):
            dma.wait()

    @pl.when(n_tiles > 0)
    def _():
        start_tile(0, 0)

    def tile_step(ti, carry):
        m_run, l_run, acc = carry
        slot = lax.rem(ti, 2)

        @pl.when(ti + 1 < n_tiles)
        def _():
            start_tile(lax.rem(ti + 1, 2), ti + 1)
        wait_tile(slot, ti)

        # Dots run in the CACHE dtype when q matches it (MXU-native: a
        # bf16 matmul is up to 3x an f32 one on TPU and skips two
        # full-tile f32 conversions per step; bf16->f32 upcast before
        # the dot would produce bit-identical scores anyway since the
        # accumulation is f32 either way — r4, targeting the 0.90x
        # bench line). A precision-MISMATCHED caller (e.g. f32 q over a
        # bf16 cache) keeps the exact f32 path: casting q down would
        # silently change results (review r4b-4).
        dt = k_tile.dtype if q_ref.dtype == k_tile.dtype else jnp.float32
        kt = k_tile[slot].astype(dt)            # (B, t_blk, Hkv, D)
        vt = v_tile[slot].astype(dt)
        q = q_ref[:].reshape(batch, hkv, groups, d).astype(dt)
        # (B, K, G, D) x (B, t_blk, K, D) -> (B, K, G, t_blk); per-head
        # dots keep Mosaic's one-batch-dim matmul constraint.
        scores = _qk_scores(q, kt) * scale
        pos = first_pos + ti * t_blk + jnp.arange(t_blk)
        live = pos[None, :] < lens[:, None]                  # (B, t_blk)
        live4 = live[:, None, None, :]
        scores = jnp.where(live4, scores, _NEG)

        m_new = jnp.maximum(m_run, jnp.max(scores, axis=-1))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(scores - m_new[..., None]) * live4
        l_new = l_run * alpha + jnp.sum(p, axis=-1)
        # PV in the cache dtype with f32 accumulation (standard flash
        # practice; p in [0,1] loses <0.5% per element to bf16 and the
        # f32 accumulate keeps the sum exact). No-op for f32 caches.
        pv = _pv_accum(p.astype(vt.dtype), vt)
        acc_new = acc * alpha[..., None] + pv
        return m_new, l_new, acc_new

    m0 = jnp.full((batch, hkv, groups), _NEG, jnp.float32)
    l0 = jnp.zeros((batch, hkv, groups), jnp.float32)
    a0 = jnp.zeros((batch, hkv, groups, d), jnp.float32)
    m_f, l_f, a_f = lax.fori_loop(0, n_tiles, tile_step, (m0, l0, a0))

    abuf[me] = a_f
    lbuf[me] = _lane_bcast(l_f)
    mbuf[me] = _lane_bcast(m_f)
    _exchange_and_merge(abuf, lbuf, mbuf, send_sem, recv_sem, o_ref,
                        axis=axis, world=world)


def _combine_shapes(world, b, hkv, groups, d):
    stat = jax.ShapeDtypeStruct((world, b, hkv, groups, _STAT_LANES),
                                jnp.float32)
    return (jax.ShapeDtypeStruct((world, b, hkv, groups, d), jnp.float32),
            stat, stat)


@resilient("flash_decode")
def gqa_fwd_batch_decode(q: jax.Array, cache_k: jax.Array,
                         cache_v: jax.Array, kv_len: jax.Array,
                         ctx: FlashDecodeContext | None = None,
                         impl: str = "pallas") -> jax.Array:
    """Decode-time GQA over a sequence-sharded KV cache (functional entry,
    reference ``gqa_fwd_batch_decode`` flash_decode.py:763).

    Args:
      q: (B, Hq, D) current-step queries, replicated over the SP axis.
      cache_k/cache_v: (B, T, Hkv, D) with T sequence-sharded over
        ``ctx.axis`` (each device holds T/w positions).
      kv_len: int32 number of live positions (decode offset + 1) —
        scalar, or PER-SEQUENCE (B,) like the reference's kv_length
        array (flash_decode.py:182).
    Returns:
      (B, Hq, D) attention outputs, replicated.
    """
    ctx = ctx or create_flash_decode_context()
    mesh, axis, world = ctx.mesh, ctx.axis, ctx.world_size
    b, hq, d = q.shape
    t, hkv = cache_k.shape[1], cache_k.shape[2]
    assert t % world == 0
    t_loc = t // world
    groups = hq // hkv
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))

    if impl == "xla":
        def body(qs, ks, vs, n):
            me = lax.axis_index(axis)
            a, l, m = _local_partials(qs, ks, vs, me * t_loc, n, groups)
            m_star = lax.pmax(m, axis)
            sc = jnp.exp(m - m_star)
            num = lax.psum(a * sc[..., None], axis)
            den = lax.psum(l * sc, axis)
            out = num / jnp.maximum(den, 1e-20)[..., None]
            return out.reshape(b, hq, d).astype(qs.dtype)

        f = nestable_shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(None, axis), P(None, axis), P()),
            out_specs=P(), check_vma=False)
        return f(q, cache_k, cache_v, kv_len)

    interpret = resolve_interpret(ctx.interpret)
    shard_bytes = t_loc * hkv * d * cache_k.dtype.itemsize * b
    variant = ctx.resolve_variant(shard_bytes)

    if variant == "einsum":
        kernel = functools.partial(_decode_kernel, axis=axis, world=world,
                                   groups=groups, t_loc=t_loc)

        def body(qs, ks, vs, n):
            out, *_ = pl.pallas_call(
                kernel,
                name="flash_decode_einsum",
                out_shape=(jax.ShapeDtypeStruct((b, hq, d), q.dtype),)
                + _combine_shapes(world, b, hkv, groups, d),
                in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 3 +
                         [pl.BlockSpec(memory_space=pltpu.SMEM)],
                out_specs=tuple([pl.BlockSpec(memory_space=pltpu.VMEM)] * 4),
                scratch_shapes=[pltpu.SemaphoreType.DMA((world, 3)),
                                pltpu.SemaphoreType.DMA((world, 3))],
                compiler_params=comm_params(collective_id=7, world=world),
                interpret=interpret,
            )(qs, ks, vs, n)
            return out

        f = nestable_shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(None, axis), P(None, axis), P()),
            out_specs=P(), check_vma=False)
        return sync_interpret(f(q, cache_k, cache_v, kv_len),
                              interpret)

    # tiled variant: KV stays in HBM, dummy 1x1 table (dense addressing).
    def _div_leq(cap: int) -> int:
        # Largest divisor of t_loc <= cap — tile slicing and the
        # liveness mask both assume t_blk | t_loc.
        cap = max(min(cap, t_loc), 1)
        while t_loc % cap:
            cap -= 1
        return cap

    t_blk = _div_leq(ctx.t_blk)
    # 4 tiles (K+V, double-buffered) must fit the VMEM budget.
    per_pos = 4 * b * hkv * d * cache_k.dtype.itemsize
    while t_blk > 8 and t_blk * per_pos > ctx.vmem_budget:
        t_blk = _div_leq(t_blk // 2)
    kernel = functools.partial(
        _tiled_decode_kernel, axis=axis, world=world, batch=b, hkv=hkv,
        groups=groups, d=d, t_loc=t_loc, t_blk=t_blk, paged=False)

    def body(qs, n, ks, vs):
        table = jnp.zeros((1, 1), jnp.int32)
        out, *_ = pl.pallas_call(
            kernel,
            name="flash_decode_tiled",
            out_shape=(jax.ShapeDtypeStruct((b, hq, d), q.dtype),)
            + _combine_shapes(world, b, hkv, groups, d),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pltpu.SMEM),
                      any_spec(),
                      any_spec()],
            out_specs=tuple([pl.BlockSpec(memory_space=pltpu.VMEM)] * 4),
            scratch_shapes=[
                pltpu.VMEM((2, b, t_blk, hkv, d), cache_k.dtype),
                pltpu.VMEM((2, b, t_blk, hkv, d), cache_v.dtype),
                # Dense path: one whole-tile DMA per slot — only sem
                # [slot, 0] is used (paged keeps per-batch sems).
                pltpu.SemaphoreType.DMA((2, 1)),
                pltpu.SemaphoreType.DMA((2, 1)),
                pltpu.SemaphoreType.DMA((world, 3)),
                pltpu.SemaphoreType.DMA((world, 3))],
            compiler_params=comm_params(collective_id=7, world=world),
            interpret=interpret,
        )(qs, n, table, ks, vs)
        return out

    f = nestable_shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(None, axis), P(None, axis)),
        out_specs=P(), check_vma=False)
    return sync_interpret(f(q, kv_len, cache_k, cache_v), interpret)


@resilient("flash_decode_paged", env_keys=("TDT_PAGED_VARIANT",))
def gqa_fwd_batch_decode_paged(q: jax.Array, pool_k: jax.Array,
                               pool_v: jax.Array, block_table: jax.Array,
                               kv_len: jax.Array,
                               ctx: FlashDecodeContext | None = None,
                               impl: str = "pallas") -> jax.Array:
    """Paged-KV distributed decode (reference paged split-KV kernels,
    flash_decode.py:130-393 block_table/page_size :136,:203).

    Sharding contract: device r of the SP axis backs global positions
    [r*t_loc, (r+1)*t_loc) of every sequence, t_loc = n_pages*page_size.

    Args:
      q: (B, Hq, D) replicated.
      pool_k/pool_v: (w*P_loc, page_size, Hkv, D) physical page pools,
        dim 0 sharded over ``ctx.axis`` — each device owns P_loc slots.
      block_table: (w, B, n_pages) int32, dim 0 sharded — device r's
        table maps its logical page i of sequence b to a *local* slot id
        in [0, P_loc).
      kv_len: int32 global live length — scalar or per-sequence (B,).
    Returns:
      (B, Hq, D) replicated.
    """
    ctx = ctx or create_flash_decode_context()
    mesh, axis, world = ctx.mesh, ctx.axis, ctx.world_size
    b, hq, d = q.shape
    page_size, hkv = pool_k.shape[1], pool_k.shape[2]
    assert block_table.shape[0] == world and block_table.shape[1] == b
    n_pages = block_table.shape[2]
    groups = hq // hkv
    t_loc = n_pages * page_size
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))

    import os
    paged_variant = os.environ.get("TDT_PAGED_VARIANT",
                                   ctx.paged_variant)
    if paged_variant not in ("direct", "gathered"):
        # A typo here would silently run the direct path — the exact
        # compile-hang the override exists to dodge.
        raise ValueError(
            f"paged_variant {paged_variant!r} (field or "
            "TDT_PAGED_VARIANT) must be 'direct' or 'gathered'")
    if impl == "xla" or paged_variant == "gathered":
        # Reconstruct the contiguous (B, T, Hkv, D) view via table
        # gathers (position → slot is the allocator's map), then run
        # the contiguous decode. For impl="xla" this is the golden /
        # fast CPU-mesh path, like the other ops' xla impls; for
        # paged_variant="gathered" the dense TILED Pallas kernel
        # consumes the gathered view — the proven-on-chip path that
        # sidesteps the direct kernel's block-table indirection (see
        # FlashDecodeContext.paged_variant).
        from triton_dist_tpu.models.kv_cache import PagedKVCacheManager
        ck = PagedKVCacheManager.gathered_view(pool_k, block_table,
                                               world)  # (B, T, ...)
        cv = PagedKVCacheManager.gathered_view(pool_v, block_table,
                                               world)
        sh = jax.sharding.NamedSharding(mesh, P(None, axis))
        return gqa_fwd_batch_decode(
            q, jax.lax.with_sharding_constraint(ck, sh),
            jax.lax.with_sharding_constraint(cv, sh), kv_len, ctx,
            impl=impl)

    interpret = resolve_interpret(ctx.interpret)

    kernel = functools.partial(
        _tiled_decode_kernel, axis=axis, world=world, batch=b, hkv=hkv,
        groups=groups, d=d, t_loc=t_loc, t_blk=page_size, paged=True)

    def body(qs, n, table, ks, vs):
        out, *_ = pl.pallas_call(
            kernel,
            name="flash_decode_paged",
            out_shape=(jax.ShapeDtypeStruct((b, hq, d), q.dtype),)
            + _combine_shapes(world, b, hkv, groups, d),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pltpu.SMEM),
                      any_spec(),
                      any_spec()],
            out_specs=tuple([pl.BlockSpec(memory_space=pltpu.VMEM)] * 4),
            scratch_shapes=[
                pltpu.VMEM((2, b, page_size, hkv, d), pool_k.dtype),
                pltpu.VMEM((2, b, page_size, hkv, d), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2, b)),
                pltpu.SemaphoreType.DMA((2, b)),
                pltpu.SemaphoreType.DMA((world, 3)),
                pltpu.SemaphoreType.DMA((world, 3))],
            compiler_params=comm_params(collective_id=7, world=world),
            interpret=interpret,
        )(qs, n, table.reshape(b, n_pages), ks, vs)
        return out

    f = nestable_shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis), P(axis)),
        out_specs=P(), check_vma=False)
    return sync_interpret(
        f(q, kv_len, block_table, pool_k, pool_v), interpret)
