"""MoE routing / token-alignment utilities.

TPU-native redesign of the reference's MoE host utilities
(python/triton_dist/kernels/nvidia/moe_utils.py, csrc/lib/moe_utils.cu:61
``moe_ag_scatter_align_block_size_kernel``, :195 topk-reduce kernel, and the
EP preprocess path ep_a2a_layer.py:119-139: bincount of expert indices →
splits → recv offsets).

The reference aligns token→expert assignments to GEMM block boundaries so a
grouped GEMM can consume them; the TPU equivalent is sorting tokens by
expert and handing ``group_sizes`` to ``jax.lax.ragged_dot`` — XLA's native
grouped-GEMM primitive that tiles directly onto the MXU. Dynamic token
counts become static-shape tensors via fixed per-peer capacity plus masks
(SURVEY.md §7 "Dynamic shapes in EP": the reference also uses MAX_M
buffers, so parity holds).

Everything here is pure jnp — traced under jit, no host sync (the
reference needs a CUDA kernel + cpu pinned-memory roundtrip for the same
job, ep_a2a.py:244-310).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def topk_routing(router_logits: jax.Array, topk: int,
                 norm_topk_prob: bool = True):
    """Softmax→top-k gating (the Qwen3-MoE recipe, models/qwen_moe.py:50-80).

    Args:
      router_logits: (T, E) float logits.
      topk: experts per token.
      norm_topk_prob: renormalize the selected probabilities to sum to 1.

    Returns:
      (weights (T, topk) float32, indices (T, topk) int32)
    """
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    weights, indices = lax.top_k(probs, topk)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, indices.astype(jnp.int32)


def sigmoid_topk_routing(router_logits: jax.Array, bias: jax.Array,
                         topk: int, norm_topk_prob: bool = True,
                         scale: float = 1.0):
    """Sigmoid scores, selection by ``score + bias``, weights from the
    scores alone (the aux-loss-free recipe of DeepSeek-V3 / K-EXAONE,
    one group: no group limit).

    Args:
      router_logits: (T, E) float logits over ALL routed experts.
      bias: (E,) the per-expert selection bias: it moves which experts
        are chosen, never their weights.
      scale: ``routed_scaling_factor``.

    Returns:
      (weights (T, topk) float32, indices (T, topk) int32)
    """
    scores = jax.nn.sigmoid(router_logits.astype(jnp.float32))
    _, indices = lax.top_k(scores + bias.astype(jnp.float32), topk)
    weights = jnp.take_along_axis(scores, indices, axis=-1)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights * scale, indices.astype(jnp.int32)


def live_slot_mask(counts: jax.Array, world: int,
                   capacity: int) -> jax.Array:
    """(world, capacity) bool: slot s of slab p is live iff
    ``s < counts[p]``.

    One definition of "live" for the a2a slab layout, shared by the
    dispatch unpack (layers/ep_a2a.py) and the a2a VJP's cotangent
    masking (ops/autodiff.py) — the Pallas exchange leaves dead slots
    stale, and both sides must zero the same set of rows.
    """
    slot = lax.broadcasted_iota(jnp.int32, (world, capacity), 1)
    return slot < counts[:, None]


def bincount(indices: jax.Array, length: int) -> jax.Array:
    """Static-length bincount (reference device ``bincount`` ep_a2a.py:310,
    used for per-expert splits)."""
    one = jnp.zeros((length,), jnp.int32)
    return one.at[indices.reshape(-1)].add(1, mode="drop")


def dispatch_layout(exp_indices: jax.Array, num_experts: int, world: int,
                    capacity: int):
    """Compute the rank-major dispatch layout for EP all-to-all.

    The analog of the reference's send-request generation + recv-offset
    computation (ep_a2a_layer.py:119-139, ep_a2a.py:244) — but fully traced
    and static-shape: each (token, k) pair is assigned a slot
    ``(dest_rank, position)`` where ``position`` is the pair's ordinal among
    all pairs routed to ``dest_rank`` (stable, token-major). Pairs beyond
    ``capacity`` are dropped (marked invalid), like capacity-factor MoE.

    Args:
      exp_indices: (T, K) int32 global expert ids.
      num_experts: total experts E; experts_per_rank = E // world.
      world: EP world size.
      capacity: max pairs a rank may send to one peer.

    Returns dict of:
      dest        (T, K) int32 destination rank per pair
      pos         (T, K) int32 slot within the destination slab
      valid       (T, K) bool  pair kept (not capacity-dropped)
      send_counts (world,) int32 pairs actually sent per destination
      local_expert(T, K) int32 expert id local to the destination rank
    """
    epr = num_experts // world
    t, k = exp_indices.shape
    flat = exp_indices.reshape(-1)
    dest = flat // epr
    # position of pair i within its destination slab = number of earlier
    # pairs with the same destination (stable token-major order, matching
    # the reference's start/end-indices send requests).
    onehot = jax.nn.one_hot(dest, world, dtype=jnp.int32)      # (TK, world)
    pos = (jnp.cumsum(onehot, axis=0) - onehot)                # exclusive
    pos = jnp.take_along_axis(pos, dest[:, None], axis=1)[:, 0]
    valid = pos < capacity
    send_counts = jnp.sum(onehot * valid[:, None].astype(jnp.int32), axis=0)
    return {
        "dest": dest.reshape(t, k),
        "pos": pos.reshape(t, k),
        "valid": valid.reshape(t, k),
        "send_counts": send_counts.astype(jnp.int32),
        "local_expert": (flat % epr).reshape(t, k).astype(jnp.int32),
    }


def scatter_to_slabs(x: jax.Array, meta: dict, world: int, capacity: int,
                     extra: dict | None = None):
    """Scatter per-token payloads into the (world, capacity, ...) send
    buffer described by ``meta`` (from :func:`dispatch_layout`).

    ``x``: (T, H) token payloads, expanded to one row per (token, k) pair.
    ``extra``: name → (T, K) int32 side-band values scattered alongside
    (local expert id, source slot id ... the reference packs these into the
    same nvshmem send_buf rows, ep_a2a.py:37-150).

    Returns (send_buf (world, capacity, H), extras {name: (world, capacity)}).
    Invalid / unused slots are zero.
    """
    t, k = meta["dest"].shape
    h = x.shape[-1]
    dest = meta["dest"].reshape(-1)
    pos = meta["pos"].reshape(-1)
    valid = meta["valid"].reshape(-1)
    # Route dropped pairs to an out-of-range slot; mode="drop" discards them.
    slot = jnp.where(valid, dest * capacity + pos, world * capacity)
    rows = jnp.repeat(x, k, axis=0)                             # (TK, H)
    buf = jnp.zeros((world * capacity, h), x.dtype)
    buf = buf.at[slot].set(rows, mode="drop")
    extras_out = {}
    for name, val in (extra or {}).items():
        e = jnp.zeros((world * capacity,), val.dtype)
        extras_out[name] = e.at[slot].set(val.reshape(-1), mode="drop"
                                          ).reshape(world, capacity)
    return buf.reshape(world, capacity, h), extras_out


def sort_by_group(values: jax.Array, group_ids: jax.Array, num_groups: int):
    """Stable-sort rows by group id → (sorted values, group_sizes, unsort).

    The TPU-native ``moe_ag_scatter_align_block_size`` (csrc moe_utils.cu:61):
    instead of padding token blocks to GEMM tiles, sorting + ``group_sizes``
    feeds ``lax.ragged_dot`` which handles expert-boundary tiling natively.

    ``group_ids`` may contain ``num_groups`` (sentinel for invalid rows);
    those sort to the end and are excluded from ``group_sizes``.
    """
    order = jnp.argsort(group_ids, stable=True)
    sizes = bincount(jnp.minimum(group_ids, num_groups), num_groups)
    unsort = jnp.argsort(order, stable=True)
    return values[order], sizes, unsort


def moe_align_block_size(expert_ids, num_experts: int, block_size: int):
    """Host-side grouped-GEMM tile plan (reference
    ``moe_ag_scatter_align_block_size`` csrc/lib/moe_utils.cu:61 + CPU
    swizzle threadblock_swizzle_ag_moe.cc): stable expert-sorted order,
    per-expert counts, tile-padded offsets, and the block→expert map an
    explicit tiled grouped-GEMM kernel iterates. Native C++ via ctypes
    (csrc/moe/moe_align.cc) with a numpy fallback.

    Returns dict(sorted_order, expert_counts, padded_offsets,
    block_expert) — numpy arrays (host planning, like the reference).
    """
    import numpy as np
    ids = np.ascontiguousarray(np.asarray(expert_ids).reshape(-1), np.int32)
    if ids.size and (ids.min() < 0 or ids.max() > num_experts):
        raise ValueError(
            f"expert ids must lie in [0, {num_experts}] "
            f"(== {num_experts} is the invalid sentinel); got "
            f"[{ids.min()}, {ids.max()}]")
    n = ids.shape[0]
    lib = _moe_native()
    if lib is not None:
        import ctypes
        cap = n + num_experts
        order = np.empty(n, np.int32)
        counts = np.empty(num_experts, np.int32)
        offsets = np.empty(num_experts + 1, np.int32)
        blocks = np.empty(cap, np.int32)
        p = lambda a: a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
        nb = lib.tdt_moe_align_block_size(
            n, p(ids), num_experts, block_size, p(order), p(counts),
            p(offsets), p(blocks), cap)
        assert nb >= 0, f"tdt_moe_align_block_size failed (rc={nb})"
        return {"sorted_order": order, "expert_counts": counts,
                "padded_offsets": offsets, "block_expert": blocks[:nb]}
    # numpy fallback (bit-identical; tests assert so)
    order = np.argsort(ids, kind="stable").astype(np.int32)
    counts = np.bincount(ids[ids < num_experts],
                         minlength=num_experts).astype(np.int32)
    nblk = -(-counts // block_size)
    offsets = np.zeros(num_experts + 1, np.int32)
    offsets[1:] = np.cumsum(nblk * block_size)
    block_expert = np.repeat(np.arange(num_experts, dtype=np.int32), nblk)
    return {"sorted_order": order, "expert_counts": counts,
            "padded_offsets": offsets, "block_expert": block_expert}


_MOE_LIB = None
_MOE_TRIED = False


def _moe_native():
    global _MOE_LIB, _MOE_TRIED
    if _MOE_TRIED:
        return _MOE_LIB
    _MOE_TRIED = True
    import ctypes
    import os
    import subprocess
    src = os.path.abspath(os.path.join(
        os.path.dirname(__file__), "..", "csrc", "moe", "moe_align.cc"))
    so = os.path.join(os.path.dirname(src), "libtdtmoe.so")
    try:
        if (not os.path.exists(so)
                or os.path.getmtime(so) < os.path.getmtime(src)):
            subprocess.run(["g++", "-shared", "-fPIC", "-O2", "-o", so, src],
                           check=True, capture_output=True)
        lib = ctypes.CDLL(so)
        lib.tdt_moe_align_block_size.restype = ctypes.c_int32
        _MOE_LIB = lib
    except (OSError, subprocess.CalledProcessError):
        _MOE_LIB = None
    return _MOE_LIB


def topk_reduce(per_pair_out: jax.Array, weights: jax.Array) -> jax.Array:
    """Weighted sum over the top-k expert outputs per token (reference
    topk-reduce kernel, csrc/lib/moe_utils.cu:195).

    per_pair_out: (T, K, H); weights: (T, K) → (T, H).
    """
    w = weights.astype(jnp.float32)[..., None]
    return jnp.sum(per_pair_out.astype(jnp.float32) * w, axis=1
                   ).astype(per_pair_out.dtype)
