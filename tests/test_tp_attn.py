"""TP_Attn layer vs single-device golden (reference test/nvidia/test_tp_attn.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.layers import TPAttn, precompute_rope_cache
from triton_dist_tpu.layers.tp_attn import _attention_core

H = 64
NQ, NKV, D = 16, 8, 8
B, S, T = 2, 4, 8


def np_rms(x, w, eps=1e-6):
    var = np.mean(x.astype(np.float64) ** 2, -1, keepdims=True)
    return (x / np.sqrt(var + eps)) * w


def np_rope(x, cos, sin, pos):
    c = cos[pos][:, :, None, :]
    s = sin[pos][:, :, None, :]
    x1, x2 = np.split(x, 2, -1)
    return np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def golden(params, x, pos, rope, offset):
    """Full-array (no TP) cached GQA attention in numpy."""
    wq = np.asarray(params["w_q"], np.float64)
    wk = np.asarray(params["w_k"], np.float64)
    wv = np.asarray(params["w_v"], np.float64)
    wo = np.asarray(params["w_o"], np.float64)
    xf = np.asarray(x, np.float64)
    b, s = pos.shape
    q = (xf @ wq).reshape(b, s, NQ, D)
    k = (xf @ wk).reshape(b, s, NKV, D)
    v = (xf @ wv).reshape(b, s, NKV, D)
    q = np_rms(q, np.asarray(params["q_norm"], np.float64))
    k = np_rms(k, np.asarray(params["k_norm"], np.float64))
    # The reference's own (cos, sin) tables, from the rope formula.
    freqs = np.outer(np.arange(T), np.asarray(rope.inv_freq, np.float64))
    cos, sin = np.cos(freqs), np.sin(freqs)
    q, k = np_rope(q, cos, sin, pos), np_rope(k, cos, sin, pos)
    # causal over the fresh segment only (offset=0 prefill)
    assert offset == 0
    scores = np.einsum("bsKgd,btKd->bKgst",
                       q.reshape(b, s, NKV, NQ // NKV, D), k) * D ** -0.5
    mask = np.tril(np.ones((s, s), bool))
    scores = np.where(mask[None, None, None], scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    out = np.einsum("bKgst,btKd->bsKgd", p, v).reshape(b, s, NQ * D)
    return out.reshape(b * s, -1) @ wo


@pytest.fixture()
def attn(mesh8):
    return TPAttn(H, NQ, NKV, D, mesh=mesh8, dtype=jnp.float32)


@pytest.fixture()
def setup(attn, key):
    params = attn.init(key)
    x = jax.random.normal(jax.random.PRNGKey(3), (B * S, H), jnp.float32)
    pos = jnp.tile(jnp.arange(S), (B, 1))
    rope = precompute_rope_cache(D, T)
    cache = (jnp.zeros((B, T, NKV, D), jnp.float32),
             jnp.zeros((B, T, NKV, D), jnp.float32))
    ref = golden(params, x, np.asarray(pos), rope, 0)
    return params, x, pos, rope, cache, ref


@pytest.mark.parametrize("mode", ["xla", "ag_rs", "xla_ar", "gemm_ar"])
def test_tp_attn_prefill(attn, setup, mode):
    params, x, pos, rope, cache, ref = setup
    out, (ck, cv) = attn(params, x, pos, rope, cache, 0, mode=mode)
    np.testing.assert_allclose(np.asarray(out, np.float64), ref,
                               rtol=2e-4, atol=2e-4)
    # cache got written at [0, S)
    assert not np.allclose(np.asarray(ck)[:, :S], 0)
    assert np.allclose(np.asarray(ck)[:, S:], 0)


def test_tp_attn_decode_matches_prefill(attn, setup):
    """Decode step at offset=S must equal prefilling S+1 tokens."""
    params, x, pos, rope, cache, _ = setup
    xs1 = jax.random.normal(jax.random.PRNGKey(9), (B, H), jnp.float32)

    # path A: prefill S then decode 1 (gemm_ar replicated decode layout)
    _, cache1 = attn(params, x, pos, rope, cache, 0, mode="xla")
    pos_d = jnp.full((B, 1), S)
    out_d, _ = attn(params, xs1, pos_d, rope, cache1, S, mode="gemm_ar")

    # path B: prefill S+1 at once
    x_all = jnp.concatenate([x.reshape(B, S, H),
                             xs1.reshape(B, 1, H)], axis=1).reshape(-1, H)
    pos_all = jnp.tile(jnp.arange(S + 1), (B, 1))
    cache0 = (jnp.zeros((B, T, NKV, D), jnp.float32),
              jnp.zeros((B, T, NKV, D), jnp.float32))
    # M = B*(S+1) = 10 doesn't divide the tp=8 axis -> replicated layout
    out_all, _ = attn(params, x_all, pos_all, rope, cache0, 0, mode="xla_ar")
    last = np.asarray(out_all).reshape(B, S + 1, H)[:, -1]
    np.testing.assert_allclose(np.asarray(out_d), last, rtol=2e-4, atol=2e-4)


def test_attention_core_gqa_grouping():
    """GQA must use the co-located KV head for each query group."""
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 2, 4, D), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 2, D), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 2, D), jnp.float32)
    ck = jnp.zeros((1, 4, 2, D), jnp.float32)
    z = jnp.zeros((1,), jnp.int32)
    out, _, _ = _attention_core(q, k, v, ck, ck, jnp.int32(0), z, groups=2)
    # head 0,1 share kv head 0; heads 2,3 share kv head 1.
    out2, _, _ = _attention_core(
        q[:, :, [2, 3, 0, 1]], k[:, :, [1, 0]], v[:, :, [1, 0]],
        ck, ck, jnp.int32(0), z, groups=2)
    np.testing.assert_allclose(np.asarray(out)[:, :, [2, 3, 0, 1]],
                               np.asarray(out2), rtol=1e-5, atol=1e-5)
