"""Tensor-parallel attention (reference ``TP_Attn``, layers/nvidia/tp_attn.py:79).

QKV projections column-parallel (sharded over heads), output projection
row-parallel. GQA with Qwen3-style per-head q/k RMSNorm and rotary
embeddings. The fused path shares one all-gather across the three QKV
GEMMs (``ag_gemm_multi``) and fuses the output projection with the
ReduceScatter / AllReduce (reference ``dist_triton_fwd`` tp_attn.py:215).

The attention core itself is a shard_map over the head axis — heads are
fully local under TP, so no collective appears between the QKV and O
projections (same property as the reference, which calls single-GPU flash
attention on the local heads).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from triton_dist_tpu.ops.common import nestable_shard_map

from triton_dist_tpu.layers.common import (
    RopeCache, apply_rope, col_parallel_matmul, rms_norm, shard_param)
from triton_dist_tpu.ops.allgather_gemm import create_ag_gemm_context
from triton_dist_tpu.ops.gemm_reduce_scatter import create_gemm_rs_context
# Differentiable wrappers (forward-identical; ops/autodiff.py).
from triton_dist_tpu.ops.autodiff import ag_gemm_multi, gemm_rs, gemm_ar


# The decode window: the attention of a call that says how many cache
# positions its live queries can see (``kv_need``) reads the cache in
# chunks of _WINDOW_CHUNK positions and stops at the chunk that covers
# them. A position read through the loop costs about 1.5x what it costs
# in the one fused whole-cache read (v5e, PERF.md PR 33), so past half
# of the cache the whole read is as cheap and is taken. A module
# constant, no knob.
_WINDOW_CHUNK = 512


def window_chunks(need, t: int):
    """How many leading chunks of a ``t``-position cache cover ``need``
    positions, or 0 where they pass half of it and the whole-cache read
    runs instead. ``need`` is a host int or a traced int32 scalar: the
    same arithmetic serves the step program and the session's host
    shadow of it. Always 0 for ``t < 1024``: such a cache has one
    program, the unbounded one."""
    n = (need + _WINDOW_CHUNK - 1) // _WINDOW_CHUNK
    return n * (2 * n * _WINDOW_CHUNK <= t)


def decode_window(need: int, t: int) -> int:
    """The cache positions a bounded read of ``need`` touches (host
    ints): a multiple of the chunk, or ``t``."""
    return int(window_chunks(need, t)) * _WINDOW_CHUNK or t


# The whole-bucket prefill. XLA keeps an attention's float32 scores in
# the chip's fast memory when they fit there beside the rest: on the
# v5e (128 MiB) a 64 MiB score tensor stays, a 128 MiB one is written to
# HBM, read back by the softmax's passes and read again as probabilities
# (one layer of 16 heads: 0.07 ms at S = T = 1024, 1.15 ms at 2048;
# PERF.md, PR 35). A call whose keys are exactly its own S positions is
# therefore read in query blocks whose scores stay under this budget,
# each against the keys its mask leaves it. The largest that was seen
# to fit: blocks of 16, 32 and 64 MiB read the same on the chip, and
# fewer blocks make a smaller program. A module constant, no knob.
_SCORE_BYTES = 64 << 20


def prefill_blocks(b: int, hq: int, s: int, window: int | None):
    """The static (first query, first key, end) triples a whole-bucket
    prefill of ``s`` positions is read in (host ints): query rows
    [first, end) against keys [first key, end), the keys a block's
    causal mask, and a window layer's band, leave to it. A block is the
    largest power-of-two share of ``s`` whose float32 scores (b x hq x
    rows x keys) fit ``_SCORE_BYTES``; one block, the whole square,
    where the bucket is short enough."""
    rows = s
    while rows > 8 and 4 * b * hq * rows * (
            s if window is None else min(s, rows + window + 127)
            ) > _SCORE_BYTES:
        rows //= 2
    blocks = []
    for first in range(0, s, rows):
        lo = 0 if window is None else max(first - window + 1, 0) // 128 * 128
        blocks.append((first, lo, min(first + rows, s)))
    return tuple(blocks)


def prefill_positions_scored(hq: int, s: int, window: int | None) -> int:
    """Query-key pairs one head of one layer scores in a whole-bucket
    admission of ``s`` positions (host ints; ``hq``: the query heads a
    device holds): its blocks' rows times their keys."""
    return sum((end - first) * (end - lo)
               for first, lo, end in prefill_blocks(1, hq, s, window))


class TPAttn:
    """GQA attention under TP. No QKV bias (Qwen3 dropped it)."""

    def __init__(self, hidden_size: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, mesh: Mesh | None = None, axis: str = "tp",
                 dtype=jnp.bfloat16, fwd_mode: str = "ag_rs",
                 impl: str = "pallas", qk_norm: bool = True,
                 rms_eps: float = 1e-6):
        if mesh is None:
            from triton_dist_tpu.runtime.dist import get_mesh
            mesh = get_mesh()
        self.mesh, self.axis = mesh, axis
        self.hidden_size = hidden_size
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim = head_dim
        self.dtype = dtype
        self.fwd_mode = fwd_mode
        self.impl = impl
        self.qk_norm = qk_norm
        self.rms_eps = rms_eps
        world = mesh.shape[axis]
        assert num_heads % world == 0, (num_heads, world)
        assert num_kv_heads % world == 0, (num_kv_heads, world)
        self.ag_ctx = create_ag_gemm_context(mesh, axis)
        self.rs_ctx = create_gemm_rs_context(mesh, axis)

    def set_fwd(self, mode: str):
        self.fwd_mode = mode

    # -- params ------------------------------------------------------------
    def init(self, key: jax.Array) -> dict:
        kq, kk, kv, ko = jax.random.split(key, 4)
        h, d = self.hidden_size, self.head_dim
        nq, nkv = self.num_heads * d, self.num_kv_heads * d
        scale = h ** -0.5
        params = {
            "w_q": jax.random.normal(kq, (h, nq), self.dtype) * scale,
            "w_k": jax.random.normal(kk, (h, nkv), self.dtype) * scale,
            "w_v": jax.random.normal(kv, (h, nkv), self.dtype) * scale,
            "w_o": jax.random.normal(ko, (nq, h), self.dtype) * (nq ** -0.5),
        }
        if self.qk_norm:
            params["q_norm"] = jnp.ones((d,), self.dtype)
            params["k_norm"] = jnp.ones((d,), self.dtype)
        return self.shard_params(params)

    def shard_params(self, params: dict) -> dict:
        m, ax = self.mesh, self.axis
        out = {
            "w_q": shard_param(params["w_q"], m, P(None, ax)),
            "w_k": shard_param(params["w_k"], m, P(None, ax)),
            "w_v": shard_param(params["w_v"], m, P(None, ax)),
            "w_o": shard_param(params["w_o"], m, P(ax, None)),
        }
        for name in ("q_norm", "k_norm"):
            if name in params:
                out[name] = shard_param(params[name], m, P())
        return out

    # -- forward -----------------------------------------------------------
    def __call__(self, params: dict, x: jax.Array, position_ids: jax.Array,
                 rope_cache: RopeCache,
                 kv_cache: tuple[jax.Array, jax.Array],
                 offset: jax.Array, mode: str | None = None,
                 kv_start: jax.Array | None = None,
                 kv_need: jax.Array | None = None,
                 window: int | None = None, rope: bool = True):
        """One attention block.

        Args:
          x: (M, H) activations, M = B*S. Row-sharded over tp for
            {xla, ag_rs}; replicated for {xla_ar, gemm_ar}.
          position_ids: (B, S) absolute positions.
          rope_cache: ``layers.precompute_rope_cache(...)``.
          kv_cache: (k, v) each (B, T, num_kv_heads, D), head-sharded.
          offset: int32 write position into the cache — scalar, or a
            (B,) per-row vector when S == 1 (continuous batching;
            see _attention_core).
          kv_need: optional traced int32 scalar, the number of cache
            positions any LIVE query of this call may see; bounds the
            attention's read of the cache (see _attention_core). Only
            the stream decode step passes it.
          window: this layer's sliding window (position i sees j with
            i - window < j <= i), or None for every earlier position.
            A per-row decode step (vector ``offset``, S == 1) then
            takes ``kv_cache`` as the row's RING of ``window``
            positions (see _attention_core).
          rope: whether this layer rotates q and k (a model may carry
            rotary embeddings in some of its layers only).
        Returns:
          (out, (k_cache, v_cache)): out has the same layout as x.
        """
        mode = mode or self.fwd_mode
        impl = "xla" if mode in ("xla", "xla_ar") else self.impl
        sharded = mode in ("xla", "ag_rs")
        b, s = position_ids.shape
        d = self.head_dim

        if sharded:
            q, k, v = ag_gemm_multi(
                x, [params["w_q"], params["w_k"], params["w_v"]],
                self.ag_ctx, impl=impl)
        else:
            q = col_parallel_matmul(x, params["w_q"], self.mesh, self.axis)
            k = col_parallel_matmul(x, params["w_k"], self.mesh, self.axis)
            v = col_parallel_matmul(x, params["w_v"], self.mesh, self.axis)

        q = q.reshape(b, s, self.num_heads, d)
        k = k.reshape(b, s, self.num_kv_heads, d)
        v = v.reshape(b, s, self.num_kv_heads, d)

        # Per-head RMSNorm before rope (Qwen3; reference tp_attn.py:196-200).
        if self.qk_norm:
            q = rms_norm(q, params["q_norm"], self.rms_eps)
            k = rms_norm(k, params["k_norm"], self.rms_eps)
        if rope:
            q = apply_rope(q, rope_cache, position_ids)
            k = apply_rope(k, rope_cache, position_ids)

        attn, new_cache = self._attention(q, k, v, kv_cache, offset,
                                          kv_start, kv_need, window)
        attn = attn.reshape(b * s, self.num_heads * d)

        if sharded:
            out = gemm_rs(attn, params["w_o"], self.rs_ctx, impl=impl)
        else:
            out = gemm_ar(attn, params["w_o"], self.rs_ctx, impl=impl)
        return out, new_cache

    def _attention(self, q, k, v, kv_cache, offset, kv_start=None,
                   kv_need=None, window=None):
        """Cached GQA attention, shard_mapped over the head axis.

        Equivalent role to the reference's flash-attn call on local heads
        (tp_attn.py:215 dist_triton_fwd); the Pallas flash/SP kernels
        (ops/flash_decode.py) plug in here for long-context paths."""
        axis = self.axis
        groups = self.num_heads // self.num_kv_heads
        core = functools.partial(_attention_core, groups=groups)
        if window is not None:     # only then: the partial is the jaxpr's
            core = functools.partial(core, window=window)
        spec = P(None, None, axis, None)
        if kv_start is None:
            core = functools.partial(core, left_pad=False)
            kv_start = jnp.zeros((q.shape[0],), jnp.int32)
        # kv_need rides along as one more replicated scalar, and only
        # when given: without it the traced program is unchanged.
        bound = () if kv_need is None else (jnp.asarray(kv_need, jnp.int32),)
        f = nestable_shard_map(
            core, mesh=self.mesh,
            in_specs=(spec, spec, spec, spec, spec, P(), P())
            + (P(),) * len(bound),
            out_specs=(spec, spec, spec), check_vma=False)
        out, ck, cv = f(q, k, v, kv_cache[0], kv_cache[1],
                        jnp.asarray(offset, jnp.int32),
                        jnp.asarray(kv_start, jnp.int32), *bound)
        return out, (ck, cv)


def _attention_core(q, k, v, cache_k, cache_v, offset, kv_start,
                    kv_need=None, *, groups: int, window: int | None = None,
                    left_pad: bool = True):
    """Single-device cached causal GQA (fp32 softmax).

    q: (B, S, hq, D); k/v: (B, S, hkv, D); cache: (B, T, hkv, D).
    Query i sits at absolute position offset+i and attends to cache
    positions kv_start[b] <= j <= offset+i — ``kv_start`` is the
    left-padding boundary for ragged batches (all-zeros = the plain
    causal mask). Fully-masked (pad) query rows get finite garbage (not
    NaN); their logits are never consumed.

    ``offset`` may be a PER-ROW (B,) vector (continuous batching: each
    row decodes at its own write position, Engine.serve_stream). Scalar
    offset keeps the contiguous dynamic_update_slice write; the vector
    path scatters per row — one position (S == 1, the stream decode
    step) or a burst of S positions offset[b]+[0, S) (the speculative-
    decoding verify window, Engine spec steps; out-of-range positions
    are dropped by the scatter, which only frozen rows near max_seq
    ever produce).

    ``kv_need`` (optional traced int32 scalar) BOUNDS THE READ: the
    number of cache positions any live query of this call may see, max
    over live rows of offset + S. The K/V write goes to the full cache
    as always. The read then runs chunk by chunk (``window_chunks``: a
    loop whose trip count is traced, so one program serves every
    length and nothing is decided on the host) over the leading chunks
    that cover ``kv_need``: scores per chunk, ONE mask and fp32 softmax
    over all of them, probs x V per chunk. The positions left out are
    positions the causal mask zeroes for every live row, so a live
    row's output is the unbounded call's up to the order of an fp32
    sum. Past half of the cache the unbounded read itself runs (a
    ``lax.cond``). A FROZEN row whose stale offset lies beyond the
    window attends to whatever the window holds of its lane and gets
    finite garbage, like a fully-masked pad row; its caller (the
    stream step's ``where(done, token, nxt)``) already discards it.
    ``None`` — every caller but the stream decode step — and any
    ``T < 1024`` trace today's program over the whole cache.

    ``window`` (static) makes this a SLIDING-WINDOW layer: query i sees
    position j only if also ``j > offset + i - window``. With a scalar
    offset (an admission's prefill, whole or in chunks, into a scratch
    cache that holds every position) that is one more term of the mask.
    With per-row offsets and S == 1 (the stream decode step) the cache
    IS the row's ring of ``window`` slots, position p at slot
    ``p % window``: the step overwrites slot ``offset % window`` and
    reads the ring whole (:func:`_attend_ring`); ``kv_need`` has nothing
    to bound there. A per-row burst (S > 1) on a ring is refused.

    THE WHOLE-BUCKET PREFILL. A call with a scalar offset whose cache
    is exactly its S positions (S == T, so the offset is 0: an
    admission into its bucket-sized scratch cache, a training forward)
    and whose caller says there is no left padding (``left_pad=False``,
    static: ``TPAttn._attention`` was given no ``kv_start``) reads its
    own k and v — the values it has just written — in static QUERY
    BLOCKS (:func:`prefill_blocks`, :func:`_attend_blocks`): each block
    of rows against keys [lo, end) only, ``end`` its own last row and
    ``lo`` the first key a window layer's band leaves it, so what lies
    above the diagonal of later rows, or before the band, is never
    scored, and a block's float32 scores are small enough
    (``_SCORE_BYTES``) to stay in the chip's fast memory instead of
    going through HBM four times. Same operands, precision and mask as
    :func:`_attend`, which each block is; the bucket's pad queries get
    finite values nobody reads. A bucket whose whole square fits the
    budget is one block and traces the program it always did. Chosen
    from the static shapes, the offset's rank, ``left_pad`` and
    ``kv_need`` alone; every other call takes the paths above."""
    b, s = q.shape[:2]
    t = cache_k.shape[1]
    if window is not None and offset.ndim:
        if s != 1:
            raise NotImplementedError(
                "a per-row burst (the speculative verify window) cannot "
                "run on a sliding-window layer's ring cache yet")
        assert t == window, (t, window)
        rows, slot = jnp.arange(b), offset % window
        cache_k = cache_k.at[rows, slot].set(k[:, 0])
        cache_v = cache_v.at[rows, slot].set(v[:, 0])
        return (_attend_ring(q, cache_k, cache_v, offset, groups),
                cache_k, cache_v)
    if offset.ndim == 0:
        cache_k = lax.dynamic_update_slice(cache_k, k, (0, offset, 0, 0))
        cache_v = lax.dynamic_update_slice(cache_v, v, (0, offset, 0, 0))
        off_b = jnp.broadcast_to(offset, (b,))
    else:
        rows = jnp.arange(b)
        if s == 1:
            cache_k = cache_k.at[rows, offset].set(k[:, 0])
            cache_v = cache_v.at[rows, offset].set(v[:, 0])
        else:
            # Burst write: row b's window lands at offset[b]+[0, S).
            # Positions past T (frozen rows at stale offsets) drop out
            # of the scatter; in-lane overshoot is overwritten before
            # any causal mask exposes it (the stream-admission pad-slot
            # safety argument, docs/serving.md "Speculative decoding").
            pos = offset[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
            cache_k = cache_k.at[rows[:, None], pos].set(k)
            cache_v = cache_v.at[rows[:, None], pos].set(v)
        off_b = offset

    max_chunks = t // (2 * _WINDOW_CHUNK)
    blocks = ()
    if not left_pad and offset.ndim == 0 and kv_need is None and s == t:
        blocks = prefill_blocks(b, q.shape[2], s, window)
    if len(blocks) > 1:
        out = _attend_blocks(q, cache_k, cache_v, groups, window, blocks)
    elif window is not None or kv_need is None or max_chunks == 0:
        out = _attend(q, cache_k, cache_v, off_b, kv_start, groups, window)
    else:
        n = window_chunks(kv_need, t)
        out = lax.cond(
            n > 0,
            lambda *read: _attend_chunks(*read, n, groups, max_chunks),
            lambda *read: _attend(*read, groups),
            q, cache_k, cache_v, off_b, kv_start)
    return out, cache_k, cache_v


def _scores_dtype(q, cache_k):
    # Contractions run in the cache dtype when q matches it (MXU-native
    # bf16 is up to 3x an f32 matmul; f32 accumulation keeps scores
    # bit-identical to an upcast-first dot — r4, same treatment as
    # ops/flash_decode). Mismatched precision keeps the exact f32 path.
    return cache_k.dtype if q.dtype == cache_k.dtype else jnp.float32


def _masked_softmax(scores, off_b, kv_start, window=None):
    """Causal + left-pad mask and fp32 softmax over the last axis of
    ``scores`` (B, hkv, G, S, T'): position j is visible to query i of
    row b iff kv_start[b] <= j <= off_b[b] + i (and, on a sliding-window
    layer, j > off_b[b] + i - window)."""
    s, t = scores.shape[-2:]
    q_pos = off_b[:, None, None] + jnp.arange(s)[None, :, None]  # (B,S,1)
    causal = jnp.arange(t)[None, None, :] <= q_pos  # (B, S, T)
    if window is not None:
        causal &= jnp.arange(t)[None, None, :] > q_pos - window
    live = jnp.arange(t)[None, :] >= kv_start[:, None]  # (B, T)
    mask = causal & live[:, None]  # (B, S, T)
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    return jax.nn.softmax(scores, axis=-1)


def _attend(q, cache_k, cache_v, off_b, kv_start, groups: int,
            window: int | None = None):
    """The read half of :func:`_attention_core` over the whole cache:
    scores, mask, fp32 softmax, probs x V. ``off_b``: (B,) position of
    query 0."""
    b, s, hq, d = q.shape
    hkv = cache_k.shape[2]
    dt = _scores_dtype(q, cache_k)
    qg = q.reshape(b, s, hkv, groups, d).astype(dt)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, cache_k.astype(dt),
                        preferred_element_type=jnp.float32) * (d ** -0.5)
    probs = _masked_softmax(scores, off_b, kv_start, window)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(dt),
                     cache_v.astype(dt),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, hq, d).astype(q.dtype)


# Jitted on its own so that the layers of one program, whose shapes and
# blocks are the same, trace and lower the blocks once and call them.
@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _attend_blocks(q, k, v, groups: int, window: int | None, blocks):
    """:func:`_attend` for a whole-bucket prefill (q, k, v:
    (B, S, heads, D), the keys being the queries' own positions), one
    block of :func:`prefill_blocks` at a time: what lies above the
    diagonal of a later block's rows, or before a window layer's band,
    is not scored, and no block's scores leave the fast memory."""
    zero = jnp.zeros((q.shape[0],), jnp.int32)
    return jnp.concatenate([
        _attend(q[:, first:end], k[:, lo:end], v[:, lo:end],
                zero + (first - lo), zero, groups, window)
        for first, lo, end in blocks], axis=1)


def ring_positions(last, window: int):
    """The position each slot of a ``window``-slot ring holds once
    position ``last`` (..., a traced int array) has been written, slot
    ``p % window`` holding ``p``: the newest position of each residue,
    negative where the sequence is shorter than the ring (slot never
    written). Shape ``last.shape + (window,)``."""
    slots = jnp.arange(window, dtype=jnp.int32)
    return last[..., None] - (last[..., None] - slots) % window


def _attend_ring(q, ring_k, ring_v, offset, groups: int):
    """One decode query per row over its ring (B, W, hkv, D), the row's
    own position ``offset[b]`` already written: every slot is inside the
    window by construction; slots the row has not reached yet (their
    position is negative) hold another occupant's or a pad's K/V and
    are masked. Keys were rotated at their absolute positions before
    they were cached, so the slots' order does not matter."""
    b, s, hq, d = q.shape
    w, hkv = ring_k.shape[1], ring_k.shape[2]
    dt = _scores_dtype(q, ring_k)
    qg = q.reshape(b, s, hkv, groups, d).astype(dt)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, ring_k.astype(dt),
                        preferred_element_type=jnp.float32) * (d ** -0.5)
    written = ring_positions(offset, w) >= 0                   # (B, W)
    scores = jnp.where(written[:, None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(dt),
                     ring_v.astype(dt), preferred_element_type=jnp.float32)
    return out.reshape(b, s, hq, d).astype(q.dtype)


def _attend_chunks(q, cache_k, cache_v, off_b, kv_start, n, groups: int,
                   max_chunks: int):
    """:func:`_attend` over the first ``n`` (traced, 1..max_chunks)
    chunks of the cache. Scores of chunk i land in columns
    [i*C, (i+1)*C) of one (.., max_chunks*C) buffer; the columns of
    chunks not read stay 0 and lie beyond every live row's causal
    mask, so the softmax is the whole-cache one."""
    b, s, hq, d = q.shape
    hkv, c = cache_k.shape[2], _WINDOW_CHUNK
    dt = _scores_dtype(q, cache_k)
    qg = q.reshape(b, s, hkv, groups, d).astype(dt)

    def chunk(cache, i):
        return lax.dynamic_slice_in_dim(cache, i * c, c, 1).astype(dt)

    def score(i, scores):
        sc = jnp.einsum("bskgd,btkd->bkgst", qg, chunk(cache_k, i),
                        preferred_element_type=jnp.float32) * (d ** -0.5)
        return lax.dynamic_update_slice_in_dim(scores, sc, i * c, 4)

    scores = lax.fori_loop(
        0, n, score,
        jnp.zeros((b, hkv, groups, s, max_chunks * c), jnp.float32))
    probs = _masked_softmax(scores, off_b, kv_start).astype(dt)

    def weigh(i, acc):
        return acc + jnp.einsum(
            "bkgst,btkd->bskgd", lax.dynamic_slice_in_dim(probs, i * c, c, 4),
            chunk(cache_v, i), preferred_element_type=jnp.float32)

    out = lax.fori_loop(0, n, weigh,
                        jnp.zeros((b, s, hkv, groups, d), jnp.float32))
    return out.reshape(b, s, hq, d).astype(q.dtype)
