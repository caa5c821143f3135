"""Multi-head latent attention (MLA, DeepSeek-V2 style) without a query
low-rank, with a head-wise output gate.

    q            = RMS_head(W_q x)                 heads x (nope + rope)
    [c, k_pe]    = W_kva x;  c <- RMS(c)           latent (rank) + rope
    k_nope, v    = c W_kvb                          heads x nope, heads x v
    q_pe, k_pe   rotated (interleaved pairs) at their positions;
                 k_pe is one vector shared by every head
    o            = softmax(q k^T / sqrt(nope + rope)) v
    out          = W_o (sigmoid(W_gate x) * o)      one gate per head

A row's cache is its LATENT lane (``models/kv_cache.LatentLane``): per
position ``c || k_pe``, ``rank + rope`` values, written where K and V
would be, then zeros up to the stored width (:attr:`MLAttn.latent_width`,
a whole number of the TPU's 128-wide lane tiles: 576 -> 640). A width
the tiles do not divide gets the positions as the minor dimension of
the cache's default device layout, and the decode step's one-position
write and its reads then copy the whole lane into another layout and
back, two or three times a step. Two reads of it:

* EXPANDED (a prompt: scalar ``offset``): ``k_nope`` and ``v`` of every
  cache position from the latents, then softmax attention; a call whose
  keys are exactly its own positions (an admission's bucket) is read in
  query blocks (``layers/tp_attn.prefill_blocks``), each against the
  keys up to its own end, so no block's scores leave the fast memory;
* ABSORBED (the decode step: per-row ``offset``, one position):
  ``W_kvb``'s key half folded into the query (``q_nope W_k^T``: one
  query of ``rank + rope`` per head against the latents themselves), the
  probabilities summed over the latents, then ``W_kvb``'s value half.
  ``kv_need`` bounds the read as it bounds a K/V layer's
  (``layers/tp_attn.window_chunks``): the leading 512-position chunks
  that cover the longest live row, or the whole lane past half of it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu.layers.common import rms_norm, shard_param
from triton_dist_tpu.layers.tp_attn import (
    _WINDOW_CHUNK, prefill_blocks, window_chunks)

# The TPU's lane tile: the minor dimension of an array is laid out in
# 128-wide tiles. The latent lane is stored a whole number of them wide,
# so that its default layout keeps the positions major (module
# docstring); the tile pads a narrower lane to the same bytes anyway.
_LANE_TILE = 128


def rope_interleaved(x, position_ids, theta: float):
    """Rotary embedding on interleaved pairs ``(x[2i], x[2i+1])``.
    x: (B, S, H, R); position_ids (B, S)."""
    r = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = position_ids.astype(jnp.float32)[..., None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (r // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    return jnp.stack([a * c - b * s, b * c + a * s],
                     axis=-1).reshape(x.shape).astype(x.dtype)


class MLAttn:
    """One MLA layer; weights replicated over ``axis`` (the
    replicated-activation modes)."""

    def __init__(self, hidden_size: int, num_heads: int, nope_dim: int,
                 rope_dim: int, v_dim: int, rank: int, rope_theta: float,
                 mesh: Mesh | None = None, axis: str = "tp",
                 dtype=jnp.bfloat16, rms_eps: float = 1e-6):
        if mesh is None:
            from triton_dist_tpu.runtime.dist import get_mesh
            mesh = get_mesh()
        self.mesh, self.axis = mesh, axis
        self.hidden_size, self.num_heads = hidden_size, num_heads
        self.nope_dim, self.rope_dim, self.v_dim = nope_dim, rope_dim, v_dim
        self.rank, self.rope_theta = rank, rope_theta
        self.dtype, self.rms_eps = dtype, rms_eps

    @property
    def latent_width(self) -> int:
        """The lane's stored width: ``rank + rope`` rounded up to whole
        lane tiles; the columns past ``rank + rope`` hold zeros."""
        return -(-(self.rank + self.rope_dim) // _LANE_TILE) * _LANE_TILE

    def init(self, key: jax.Array) -> dict:
        h, n = self.hidden_size, self.num_heads
        qd = self.nope_dim + self.rope_dim
        ks = jax.random.split(key, 5)
        normal = lambda k, shape, fan: (
            jax.random.normal(k, shape, jnp.float32) * fan ** -0.5
            ).astype(self.dtype)
        return self.shard_params({
            "w_q": normal(ks[0], (h, n * qd), h),
            "w_kva": normal(ks[1], (h, self.rank + self.rope_dim), h),
            "w_kvb": normal(ks[2], (self.rank, n * (self.nope_dim
                                                    + self.v_dim)),
                            self.rank),
            "w_gate": normal(ks[3], (h, n), h),
            "w_o": normal(ks[4], (n * self.v_dim, h), n * self.v_dim),
            "q_norm": jnp.ones((qd,), self.dtype),
            "kv_norm": jnp.ones((self.rank,), self.dtype)})

    def shard_params(self, params: dict) -> dict:
        return {k: shard_param(a, self.mesh, P()) for k, a in params.items()}

    def __call__(self, params: dict, x: jax.Array, position_ids: jax.Array,
                 cache: tuple, offset: jax.Array, kv_need=None):
        """x: (B, S, hidden) replicated; cache ``(lane,)`` (B, T,
        :attr:`latent_width`); ``offset`` scalar (a prompt at
        ``offset``) or (B,) with S == 1 (the decode step). Returns (out
        (B*S, hidden), new cache)."""
        b, s, _ = x.shape
        n, nope, rd = self.num_heads, self.nope_dim, self.rope_dim
        mm = lambda w: jnp.dot(x, w, preferred_element_type=jnp.float32)
        q = mm(params["w_q"]).reshape(b, s, n, nope + rd)
        q = rms_norm(q, params["q_norm"], self.rms_eps)
        q_pe = rope_interleaved(q[..., nope:], position_ids, self.rope_theta)
        kva = mm(params["w_kva"])
        c = rms_norm(kva[..., :self.rank], params["kv_norm"], self.rms_eps)
        k_pe = rope_interleaved(kva[..., None, self.rank:], position_ids,
                                self.rope_theta)[:, :, 0]
        (lane,) = cache
        new = _widen(jnp.concatenate([c, k_pe], axis=-1).astype(lane.dtype),
                     lane.shape[-1])
        wkvb = params["w_kvb"].reshape(self.rank, n, nope + self.v_dim)
        if offset.ndim:
            assert s == 1, "a per-row call is the one-position decode step"
            lane = lane.at[jnp.arange(b), offset].set(new[:, 0])
            qq = jnp.concatenate([
                jnp.einsum("bhn,chn->bhc", q[:, 0, :, :nope].astype(x.dtype),
                           wkvb[..., :nope],
                           preferred_element_type=jnp.float32),
                q_pe[:, 0]], axis=-1)
            o = self._absorbed(qq, lane, offset, kv_need)
            o = jnp.einsum("bhc,chv->bhv", o.astype(x.dtype),
                           wkvb[..., nope:],
                           preferred_element_type=jnp.float32)[:, None]
        else:
            lane = lax.dynamic_update_slice(lane, new, (0, offset, 0))
            o = self._expanded(jnp.concatenate([q[..., :nope], q_pe], -1),
                               lane, wkvb, offset)
        o = o * jax.nn.sigmoid(mm(params["w_gate"]))[..., None]
        out = jnp.dot(o.reshape(b * s, n * self.v_dim).astype(x.dtype),
                      params["w_o"], preferred_element_type=jnp.float32)
        return out.astype(x.dtype), (lane,)

    # -- the expanded read (a prompt) -------------------------------------
    def _expanded(self, q, lane, wkvb, offset):
        """q (B, S, H, nope + rope) f32 against every position of
        ``lane`` up to each query's own: (B, S, H, v) f32."""
        b, s, n, _ = q.shape
        t, nope = lane.shape[1], self.nope_dim
        kv = jnp.einsum("btc,chd->bthd", lane[..., :self.rank], wkvb,
                        preferred_element_type=jnp.float32).astype(lane.dtype)
        k_pe = lane[:, :, None, self.rank:self.rank + self.rope_dim]
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_pe, (b, t, n, self.rope_dim))], -1)
        v = kv[..., nope:]
        q = q.astype(lane.dtype)
        if s == t:          # keys are the call's own positions: offset 0
            return _blocks(q, k, v, prefill_blocks(b, n, s, None))
        return _attend(q, k, v, offset)

    # -- the absorbed read (the decode step) ------------------------------
    def _absorbed(self, qq, lane, offset, kv_need):
        """qq (B, H, rank + rope) f32 against row b's positions
        [0, offset[b]] of ``lane``: (B, H, rank) f32, the probabilities'
        sum of latents. ``qq`` is widened with zeros to the lane's
        stored width, so the pad columns add nothing to a score."""
        t = lane.shape[1]
        scale = (self.nope_dim + self.rope_dim) ** -0.5
        qq = _widen(qq.astype(lane.dtype), lane.shape[-1])
        max_chunks = t // (2 * _WINDOW_CHUNK)
        whole = functools.partial(_absorbed_read, rank=self.rank,
                                  scale=scale)
        if kv_need is None or max_chunks == 0:
            return whole(qq, lane, offset)
        n = window_chunks(kv_need, t)
        return lax.cond(
            n > 0,
            lambda *a: _absorbed_chunks(*a, n, self.rank, scale, max_chunks),
            whole, qq, lane, offset)


def _widen(x, width: int):
    """``x`` with zeros appended to its last axis up to ``width``."""
    pad = [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])]
    return jnp.pad(x, pad)


def _attend(q, k, v, offset):
    """Causal softmax attention of queries at ``offset + i`` over keys
    [0, T). q, k: (B, S|T, H, e); v (B, T, H, dv). Returns f32."""
    s, t = q.shape[1], k.shape[1]
    scores = jnp.einsum("bshe,bthe->bhst", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * q.shape[-1] ** -0.5
    seen = jnp.arange(t)[None, :] <= offset + jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
    return jnp.einsum("bhst,bthv->bshv", probs.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnums=(3,))
def _blocks(q, k, v, blocks):
    """:func:`_attend` of a whole-bucket prefill in query blocks, each
    block of rows against the keys up to its own end."""
    return jnp.concatenate([
        _attend(q[:, first:end], k[:, :end], v[:, :end], first)
        for first, _, end in blocks], axis=1)


def _absorbed_read(qq, lane, offset, *, rank: int, scale: float):
    """The whole lane: scores, mask, float32 softmax, sum of latents."""
    scores = jnp.einsum("bhc,btc->bht", qq, lane,
                        preferred_element_type=jnp.float32) * scale
    seen = jnp.arange(lane.shape[1])[None, :] <= offset[:, None]
    probs = jax.nn.softmax(jnp.where(seen[:, None], scores, -1e30), axis=-1)
    return jnp.einsum("bht,btc->bhc", probs.astype(lane.dtype),
                      lane[..., :rank], preferred_element_type=jnp.float32)


def _absorbed_chunks(qq, lane, offset, n, rank: int, scale: float,
                     max_chunks: int):
    """:func:`_absorbed_read` over the first ``n`` (traced) chunks of
    the lane: the columns of chunks not read stay 0 and lie beyond every
    live row's mask, so the softmax is the whole lane's."""
    b, h, _ = qq.shape
    c = _WINDOW_CHUNK

    def chunk(i):
        return lax.dynamic_slice_in_dim(lane, i * c, c, 1)

    def score(i, scores):
        sc = jnp.einsum("bhc,btc->bht", qq, chunk(i),
                        preferred_element_type=jnp.float32) * scale
        return lax.dynamic_update_slice_in_dim(scores, sc, i * c, 2)

    scores = lax.fori_loop(0, n, score,
                           jnp.zeros((b, h, max_chunks * c), jnp.float32))
    seen = jnp.arange(max_chunks * c)[None, :] <= offset[:, None]
    probs = jax.nn.softmax(jnp.where(seen[:, None], scores, -1e30),
                           axis=-1).astype(lane.dtype)

    def weigh(i, acc):
        return acc + jnp.einsum(
            "bht,btc->bhc", lax.dynamic_slice_in_dim(probs, i * c, c, 2),
            chunk(i)[..., :rank], preferred_element_type=jnp.float32)

    return lax.fori_loop(0, n, weigh, jnp.zeros((b, h, rank), jnp.float32))
