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


# -- the whole-bucket prefill, read in query blocks --------------------------
# A call whose cache is exactly its own S positions, with a scalar offset
# and no left padding, is read in static query blocks whose float32 scores
# fit the chip's fast memory (tp_attn.prefill_blocks), each against the
# keys its mask leaves it; a bucket whose square fits is one block and
# traces _attend as before. The tests shrink the budget to get blocks at
# small shapes.

@pytest.fixture()
def small_budget(monkeypatch):
    from triton_dist_tpu.layers import tp_attn
    monkeypatch.setattr(tp_attn, "_SCORE_BYTES", 256 << 10)
    return tp_attn


def _whole_inputs(s, t, hkv, groups, prompt, dtype=jnp.bfloat16, b=1, d=32):
    """q, k, v of a ``prompt``-token request right-padded to its bucket
    (the pad rows repeat one row: any finite values), and zero caches of
    ``t`` positions."""
    rng = np.random.RandomState(s + groups)

    def arr(*shape):
        a = rng.randn(*shape)
        a[:, prompt:] = a[:, prompt:prompt + 1]
        return jnp.asarray(a, dtype)
    cache = jnp.zeros((b, t, hkv, d), dtype)
    return (arr(b, s, hkv * groups, d), arr(b, s, hkv, d),
            arr(b, s, hkv, d), cache, cache)


def _spied(monkeypatch, tp_attn):
    """Count the calls of the blocked read while tracing."""
    calls = []
    blocks = tp_attn._attend_blocks
    monkeypatch.setattr(tp_attn, "_attend_blocks",
                        lambda *a: calls.append(a[3:]) or blocks(*a))
    return calls


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("s,prompt", [(256, 150), (1024, 700)])
@pytest.mark.parametrize("groups", [2, 8])
@pytest.mark.parametrize("window", [None, 128], ids=["causal", "window128"])
def test_whole_bucket_prefill_equals_attend(small_budget, monkeypatch, window,
                                            groups, s, prompt, dtype):
    tp_attn = small_budget
    q, k, v, ck, cv = _whole_inputs(s, s, 1, groups, prompt, dtype)
    zero = jnp.zeros((1,), jnp.int32)
    want, wk, wv = _attention_core(q, k, v, ck, cv, jnp.int32(0), zero,
                                   groups=groups, window=window)
    calls = _spied(monkeypatch, tp_attn)
    got, gk, gv = jax.jit(lambda *a: _attention_core(
        *a, groups=groups, window=window, left_pad=False))(
            q, k, v, ck, cv, jnp.int32(0), zero)
    blocks = tp_attn.prefill_blocks(1, groups, s, window)
    assert calls == [(groups, window, blocks)] and len(blocks) > 1
    np.testing.assert_array_equal(np.asarray(gk, np.float32),
                                  np.asarray(wk, np.float32))
    np.testing.assert_array_equal(np.asarray(gv, np.float32),
                                  np.asarray(wv, np.float32))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    # The blocks tile the queries, fit the budget, and hold every pair
    # the mask lets through; the admission counts their rows x keys.
    assert [b[0] for b in blocks] + [s] == [0] + [b[2] for b in blocks]
    for first, lo, end in blocks:
        assert 4 * groups * (end - first) * (end - lo) \
            <= tp_attn._SCORE_BYTES
        assert lo <= max(first - (window or s) + 1, 0)
    pairs = s * (s + 1) // 2 if window is None else sum(
        min(i + 1, window) for i in range(s))
    assert pairs <= tp_attn.prefill_positions_scored(groups, s, window) \
        < s * s


@pytest.mark.parametrize("b,hq,s,window,blocks", [
    # Qwen3-0.6B (16 heads): 1024 fits and is one block (the program it
    # always was), 2048 is four.
    (1, 16, 1024, None, [(0, 0, 1024)]),
    (1, 16, 2048, None, [(0, 0, 512), (512, 0, 1024), (1024, 0, 1536),
                         (1536, 0, 2048)]),
    # K-EXAONE (64 heads): a full layer at 1024 in blocks of 256 rows, a
    # window-128 layer's blocks against their band only; 512 fits.
    (1, 64, 1024, None, [(0, 0, 256), (256, 0, 512), (512, 0, 768),
                         (768, 0, 1024)]),
    (1, 64, 1024, 128, [(0, 0, 256), (256, 128, 512), (512, 384, 768),
                        (768, 640, 1024)]),
    (1, 64, 2048, None, [(i, 0, i + 128) for i in range(0, 2048, 128)]),
    (1, 64, 512, None, [(0, 0, 512)]),
    # A training batch counts: two sequences halve the rows.
    (2, 16, 1024, None, [(0, 0, 512), (512, 0, 1024)])])
def test_prefill_blocks_at_the_served_shapes(b, hq, s, window, blocks):
    from triton_dist_tpu.layers import tp_attn
    assert tp_attn.prefill_blocks(b, hq, s, window) == tuple(blocks)


WHOLE = dict(s=256, t=256, offset=0, left_pad=False, need=None)
NOT_WHOLE = {
    "s_below_t": dict(t=512),
    "vector_offset": dict(offset=[0]),
    "kv_start_given": dict(left_pad=True),
    "fits_the_budget": dict(s=64, t=64),
    "bounded_read": dict(s=1024, t=1024, need=1024),
}


@pytest.mark.parametrize("case", [None] + sorted(NOT_WHOLE))
def test_only_the_whole_bucket_prefill_is_read_in_blocks(
        small_budget, monkeypatch, case):
    """The path is chosen from the static shapes, the offset's rank,
    ``left_pad`` and ``kv_need`` alone; every other call traces the
    program it always did."""
    c = dict(WHOLE, **NOT_WHOLE.get(case, {}))
    s, t = c["s"], c["t"]
    q = jnp.ones((1, s, 4, 32), jnp.bfloat16)
    k = jnp.ones((1, s, 2, 32), jnp.bfloat16)
    cache = jnp.zeros((1, t, 2, 32), jnp.bfloat16)
    args = (q, k, k, cache, cache, jnp.asarray(c["offset"], jnp.int32),
            jnp.zeros((1,), jnp.int32))
    if c["need"] is not None:
        args += (jnp.int32(c["need"]),)
    calls = _spied(monkeypatch, small_budget)
    jax.make_jaxpr(lambda *a: _attention_core(
        *a, groups=2, left_pad=c["left_pad"]))(*args)
    assert bool(calls) == (case is None)


def test_tp_attn_passes_left_pad_only_with_kv_start(
        small_budget, monkeypatch, mesh8):
    """``TPAttn._attention`` tells the core there is no left padding
    exactly when it was given no ``kv_start`` (before it replaces it by
    zeros): Engine.serve_ragged's prefill stays on the masked read."""
    attn = TPAttn(256, 16, 8, 32, mesh=mesh8, dtype=jnp.bfloat16)
    q = jnp.ones((1, 512, 16, 32), jnp.bfloat16)
    k = jnp.ones((1, 512, 8, 32), jnp.bfloat16)
    cache = (jnp.zeros_like(k), jnp.zeros_like(k))
    calls = _spied(monkeypatch, small_budget)
    jax.make_jaxpr(lambda: attn._attention(q, k, k, cache, 0))()
    assert len(calls) == 1
    jax.make_jaxpr(lambda: attn._attention(
        q, k, k, cache, 0, kv_start=jnp.zeros((1,), jnp.int32)))()
    assert len(calls) == 1


@pytest.mark.parametrize("window", [None, 128], ids=["causal", "window128"])
def test_whole_bucket_prefill_differentiates(small_budget, window):
    """models/train.py differentiates through ``forward``, whose caches
    are exactly (B, S): the blocks are plain XLA and carry their own
    gradients, equal to the one-block read's."""
    q, k, v, ck, cv = _whole_inputs(512, 512, 1, 2, 512, jnp.float32, b=2)
    zero = jnp.zeros((2,), jnp.int32)

    def grad(left_pad):
        def f(q, k, v):
            out, nk, nv = _attention_core(
                q, k, v, ck, cv, jnp.int32(0), zero, groups=2,
                window=window, left_pad=left_pad)
            return jnp.sum(out ** 2) + jnp.sum(nk * nv)
        return jax.jit(jax.grad(f, argnums=(0, 1, 2)))

    got, want = grad(False)(q, k, v), grad(True)(q, k, v)
    for g, w in zip(got, want):
        assert float(jnp.abs(w).max()) > 0
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)
