"""Shared layer math: RMSNorm, rotary embeddings, sharded matmul helpers.

Reference analogs: ``layer_norm`` (layers/nvidia/tp_attn.py:60, flashinfer
rmsnorm), ``_set_cos_sin_cache`` (tp_attn.py:69), ``shard_local``
(tp_mlp.py:38). On TPU the norms and rope stay as jnp ops — XLA fuses them
into neighbouring kernels; hand-writing them in Pallas would only block
fusion.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from triton_dist_tpu.ops.common import nestable_shard_map


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm in fp32 accumulation (reference layer_norm, tp_attn.py:60)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * lax.rsqrt(var + eps)
    return (normed * w.astype(jnp.float32)).astype(x.dtype)


class RopeCache(NamedTuple):
    """Rotary-embedding frequencies (head_dim//2,), fp32. The angles are
    computed from the positions at use, not read from a
    (max_len, head_dim//2) table: a table a jitted step closes over is
    embedded in every compiled program as a constant — two 10 MB arrays
    at Qwen3's 40960 positions, in each of a server's ~20 programs,
    which is most of what made one program a 20-57 MB compile-cache
    entry. The values are the table's up to fp32 rounding: the same
    product ``position * inv_freq`` through the same ``cos``/``sin``,
    now fused into their consumer."""
    inv_freq: jax.Array


def precompute_rope_cache(head_dim: int, max_len: int,
                          theta: float = 1e6) -> RopeCache:
    """The rope state of a ``head_dim`` head, valid at every position
    (``max_len`` is the model's range; nothing is sized by it)
    (reference ``_set_cos_sin_cache`` tp_attn.py:69-75)."""
    del max_len
    return RopeCache(1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                                 dtype=jnp.float32)
                                      / head_dim)))


def apply_rope(x: jax.Array, rope: RopeCache,
               position_ids: jax.Array) -> jax.Array:
    """Neox-style (rotate-half) rotary embedding.

    x: (B, S, H, D); position_ids: (B, S). Matches HF Qwen3 /
    flashinfer.apply_rope_with_cos_sin_cache (reference tp_attn.py:166)."""
    freqs = position_ids.astype(jnp.float32)[..., None] * rope.inv_freq
    c = jnp.cos(freqs)[:, :, None, :]  # (B, S, 1, D/2)
    s = jnp.sin(freqs)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def shard_param(x: jax.Array, mesh: Mesh, spec: P) -> jax.Array:
    """Place a (host) array with a named sharding — the analog of the
    reference's ``shard_local`` (tp_mlp.py:38), except JAX slices the
    global array per device instead of each rank slicing by hand."""
    return jax.device_put(x, NamedSharding(mesh, spec))


def col_parallel_matmul(x: jax.Array, w: jax.Array, mesh: Mesh,
                        axis: str = "tp") -> jax.Array:
    """x replicated (M, K) @ w column-sharded (K, N) -> (M, N) col-sharded.

    The local GEMM of the reference's replicated-activation modes
    (tp_attn.py torch_fwd / gemm-ar path)."""
    f = nestable_shard_map(
        lambda xs, ws: jnp.dot(xs, ws, preferred_element_type=jnp.float32
                               ).astype(xs.dtype),
        mesh=mesh, in_specs=(P(), P(None, axis)),
        out_specs=P(None, axis), check_vma=False)
    return f(x, w)


def row_parallel_matmul_ar(x: jax.Array, w: jax.Array, mesh: Mesh,
                           axis: str = "tp") -> jax.Array:
    """x col-sharded (M, K) @ w row-sharded (K, N) + psum -> replicated.

    XLA golden for the fused ``gemm_ar`` path."""
    def body(xs, ws):
        part = jnp.dot(xs, ws, preferred_element_type=jnp.float32
                       ).astype(xs.dtype)
        return lax.psum(part, axis)
    f = nestable_shard_map(body, mesh=mesh, in_specs=(P(None, axis), P(axis)),
                      out_specs=P(), check_vma=False)
    return f(x, w)
