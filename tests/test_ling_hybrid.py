"""Ling hybrid (``bailing_hybrid``) on the serving path, against its
plain reference (``benchmark/reference/ling_hybrid.py``) at a small
preset: KDA linear attention and latent attention 2:1 (two periods), a
dense first layer, 32 sigmoid-routed experts in 8 groups, top-4 among
the best 4 groups, of which this share holds 8, a shared expert.

Weights are the reference's (the benchmark builder's ``make_params``),
cast to float32 so that program and reference differ by summation order
only: a wrong decay, chunk boundary, conv tail, latent or router reads as
a gap of 0.1 and more, rounding as 1e-5.
"""

import dataclasses
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmark.harness.builders import ling as builder
from benchmark.reference import exaone_moe as exaone_ref
from benchmark.reference import ling_hybrid as ref
from triton_dist_tpu import obs
from triton_dist_tpu.layers import kda as kda_mod
from triton_dist_tpu.layers.kda import KDAttn, chunked_delta_rule
from triton_dist_tpu.layers.mla import MLAttn
from triton_dist_tpu.models import AutoLLM, Engine, LingHybrid, ModelConfig
from triton_dist_tpu.models.kv_cache import (
    KVCacheManager, LatentLane, StateLanes)

SEED = 13
LAYERS = 6          # dense KDA, then KDA MLA | KDA KDA MLA
HF = dict(
    hidden_size=64, intermediate_size=128, num_hidden_layers=LAYERS,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    vocab_size=256, max_position_embeddings=4096, rms_norm_eps=1e-6,
    rope_theta=6e6, tie_word_embeddings=False, model_type="bailing_hybrid",
    layer_group_size=3, first_k_dense_replace=1, num_experts=32,
    num_experts_per_tok=4, moe_intermediate_size=32, num_shared_experts=1,
    scoring_func="sigmoid", norm_topk_prob=True, routed_scaling_factor=2.5,
    n_group=8, topk_group=4, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, short_conv_kernel_size=4,
    kda_lower_bound=-5, num_nextn_predict_layers=0,
    expert_swiglu_limit_list=[0] * LAYERS,
    share_expert_swiglu_limit_list=[0] * LAYERS,
    expert_parallel={"world": 4, "rank": 0})


def ref_model(world=4, rank=0) -> dict:
    return dict(HF, expert_parallel=(world, rank), balance_shape=(4, 64))


@pytest.fixture(scope="module")
def mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("tp",))


def build(mesh, **over):
    cfg = dataclasses.replace(ModelConfig.from_hf_config(dict(HF, **over)),
                              dtype=jnp.float32)
    return AutoLLM.build(cfg, mesh=mesh, axis="tp", fwd_mode="xla_ar",
                         impl="xla")


@pytest.fixture(scope="module")
def served(mesh1):
    """(decoder, params, reference model dict): float32 throughout."""
    llm = build(mesh1)
    assert isinstance(llm, LingHybrid)
    model = ref_model()
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          builder.make_params(model, mesh1, SEED))
    return llm, llm.shard_params(params), model


def engine(llm, batch=4, max_seq=160, **kw) -> Engine:
    return Engine(llm, batch=batch, max_seq=max_seq, prefill_mode="xla_ar",
                  decode_mode="gemm_ar", **kw)


REF_LEN = 112


def ref_logits(model, seq):
    """The reference's logits at every position of ``seq``, in one padded
    shape (causal attention hides the pad): one compile per test run."""
    ids = np.zeros((1, REF_LEN), np.int32)
    ids[0, :len(seq)] = seq
    lg = ref.read_logits(model, SEED, ids, np.arange(REF_LEN)[None])
    return np.asarray(lg)[0, :len(seq)]


def gaps(model, prompt, served_tokens):
    full = list(prompt) + list(served_tokens)
    lg = ref_logits(model, full[:-1])[len(prompt) - 1:]
    at = lg[np.arange(len(served_tokens)), np.asarray(served_tokens)]
    return (lg.max(-1) - at) / lg.std(-1)


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, HF["vocab_size"], n).tolist() for n in lengths]


# -- configuration and cache ----------------------------------------------

def test_from_hf_config_reads_the_hybrid_keys(mesh1):
    c = ModelConfig.from_hf_config(HF)
    assert c.sparse_layers == (False,) + (True,) * 5
    assert (c.n_group, c.topk_group, c.layer_group_size) == (8, 4, 3)
    assert (c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim,
            c.v_head_dim) == (32, 16, 8, 16)
    assert (c.conv_size, c.kda_lower_bound) == (4, -5)
    assert c.experts_held == (0, 8) and c.swiglu_limits == ()
    llm = build(mesh1)
    assert llm.kinds == ("kda", "kda", "mla", "kda", "kda", "mla")
    assert llm.kda_layers == 4 and llm.attn_windows == (None, None)


def test_init_makes_the_tree_the_builder_makes(served):
    """The program's own random weights and the benchmark's (the
    reference's leaves) are one parameter tree, leaf for leaf."""
    llm, params, _ = served
    mine = jax.eval_shape(llm.init, jax.random.PRNGKey(0))
    shapes = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t)
    assert jax.tree.structure(mine) == jax.tree.structure(params)
    assert shapes(mine) == shapes(params)


def test_cache_kinds_hold_state_and_latent_lanes(mesh1):
    llm = build(mesh1)
    kv = engine(llm, batch=3, max_seq=64).kv
    caches = kv.init()
    assert kv.recurrent
    for kind, layer in zip(llm.cache_kinds, caches):
        if isinstance(kind, StateLanes):
            assert [c.shape for c in layer] == [(3, 4, 16, 16), (3, 3, 192)]
            assert layer[0].dtype == jnp.float32
        else:
            assert isinstance(kind, LatentLane)
            # rank 32 + rope 8, stored a whole 128-wide lane tile wide.
            assert [c.shape for c in layer] == [(3, 64, 128)]
    small = kv.scratch(caches, 16)
    assert [tuple(c.shape for c in layer) for layer in small][:3] == [
        ((1, 4, 16, 16), (1, 3, 192)), ((1, 4, 16, 16), (1, 3, 192)),
        ((1, 16, 128),)]
    plain = KVCacheManager(2, 4, 64, 2, 16, mesh=mesh1)
    assert not plain.recurrent and not plain.kinds


# -- the layers against their plain forms ---------------------------------

def sequential_delta_rule(q, k, v, g, beta, state):
    """The recurrence one position at a time, as the reference writes it."""
    outs = []
    for t in range(q.shape[1]):
        state = jnp.exp(g[:, t])[..., None] * state
        kst = jnp.einsum("bhi,bhij->bhj", k[:, t], state)
        state = state + beta[:, t, :, None, None] * k[:, t, ..., None] * (
            v[:, t] - kst)[..., None, :]
        outs.append(jnp.einsum("bhi,bhij->bhj", q[:, t], state))
    return jnp.stack(outs, axis=1), state


def delta_inputs(s, seed=0, h=2, d=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    norm = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = norm(jax.random.normal(ks[0], (1, s, h, d))) * d ** -0.5
    k = norm(jax.random.normal(ks[1], (1, s, h, d)))
    v = jax.random.normal(ks[2], (1, s, h, d))
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (1, s, h, d)) - 2.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, s, h)))
    state = 0.3 * jax.random.normal(ks[5], (1, h, d, d))
    return q, k, v, g, beta, state


@pytest.mark.parametrize("s", [5, 16, 40, 150])
def test_chunked_scan_matches_the_sequential_recurrence(s):
    """One chunk shorter than a sub-chunk, exactly one sub-chunk, one
    chunk of three sub-chunks, three chunks (the last partial): outputs
    and the state handed on, from a nonzero state."""
    q, k, v, g, beta, state = delta_inputs(s)
    want_o, want_s = sequential_delta_rule(q, k, v, g, beta, state)
    with jax.default_matmul_precision("highest"):
        got_o, got_s = jax.jit(chunked_delta_rule)(q, k, v, g, beta, state,
                                                   jnp.int32(s))
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=1e-4, atol=1e-5)


def test_strong_decay_across_a_whole_chunk_stays_finite():
    """Every channel at the lower bound for 150 positions: the pairwise
    decays reach exp(-750), which only the sub-chunk split keeps inside
    float32."""
    q, k, v, g, beta, state = delta_inputs(150, seed=1)
    g = jnp.full_like(g, -4.99)
    want_o, want_s = sequential_delta_rule(q, k, v, g, beta, state)
    with jax.default_matmul_precision("highest"):
        got_o, got_s = jax.jit(chunked_delta_rule)(q, k, v, g, beta, state,
                                                   jnp.int32(150))
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=1e-4, atol=1e-5)


def kda_layer(mesh, seed=3):
    layer = KDAttn(32, 2, 8, 4, -5.0, mesh=mesh, dtype=jnp.float32)
    params = layer.init(jax.random.PRNGKey(seed))
    params["b_f"] = params["b_f"] - 3.0
    return layer, params


@pytest.mark.parametrize("n,bucket", [(1, 8), (3, 16), (70, 128)])
def test_bucket_padding_leaves_the_state_and_conv_tail_of_the_true_length(
        mesh1, n, bucket):
    layer, params = kda_layer(mesh1)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, bucket, 32))
    start = (0.1 * jax.random.normal(jax.random.PRNGKey(5), (1, 2, 8, 8)),
             jax.random.normal(jax.random.PRNGKey(6), (1, 3, 48)))
    live = jnp.arange(bucket)[None] < n
    with jax.default_matmul_precision("highest"):
        out_pad, (st_pad, tail_pad) = layer(params, x, start, live)
        out, (st, tail) = layer(params, x[:, :n], start,
                                jnp.ones((1, n), bool))
    np.testing.assert_allclose(np.asarray(out_pad)[:n], np.asarray(out),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st_pad), np.asarray(st),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(tail_pad), np.asarray(tail),
                               rtol=1e-5, atol=1e-6)
    # A frozen row's decode step leaves both as they were.
    _, (st2, tail2) = layer(params, x[:, :1], (st, tail),
                            jnp.zeros((1, 1), bool))
    np.testing.assert_array_equal(np.asarray(st2), np.asarray(st))
    np.testing.assert_array_equal(np.asarray(tail2), np.asarray(tail))


@pytest.mark.parametrize("t,need", [(16, None), (2048, 700)])
def test_mla_absorbed_decode_matches_expanded(mesh1, t, need):
    """Rows at their own offsets: the decode step's absorbed read of the
    latent lane (whole, or the leading chunks ``kv_need`` leaves it)
    against the expanded prefill's output at the same positions."""
    layer = MLAttn(32, 2, 8, 4, 8, 16, 6e6, mesh=mesh1, dtype=jnp.float32)
    params = layer.init(jax.random.PRNGKey(7))
    s, w = 12, layer.latent_width
    x = jax.random.normal(jax.random.PRNGKey(8), (2, s, 32))
    pos = jnp.tile(jnp.arange(s)[None], (2, 1))
    with jax.default_matmul_precision("highest"):
        want, (lane,) = layer(params, x, pos, (jnp.zeros((2, s, w)),),
                              jnp.int32(0))
    want = np.asarray(want).reshape(2, s, 32)
    offsets = jnp.asarray([9, 4], jnp.int32)
    big = jnp.full((2, t, w), 7.0).at[:, :s].set(lane)    # stale past s
    kv_need = None if need is None else jnp.int32(need)
    with jax.default_matmul_precision("highest"):
        got, (after,) = jax.jit(lambda big, xs: layer(
            params, xs, offsets[:, None], (big,), offsets, kv_need=kv_need))(
                big, x[jnp.arange(2), offsets][:, None])
    got = np.asarray(got).reshape(2, 32)
    np.testing.assert_allclose(got[0], want[0, 9], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1, 4], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(after)[0, 9], np.asarray(lane)[0, 9],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("rank,rope,stored", [
    (512, 64, 640), (32, 8, 128), (16, 4, 128), (448, 64, 512)])
def test_latent_lane_is_stored_in_whole_lane_tiles(mesh1, rank, rope,
                                                   stored):
    """The stored width is ``rank + rope`` rounded up to 128-wide lane
    tiles (Ling's 576 -> 640); the projection still writes ``rank +
    rope``."""
    layer = MLAttn(64, 2, 8, rope, 8, rank, 6e6, mesh=mesh1)
    assert layer.latent_width == stored
    assert stored % 128 == 0 and 0 <= stored - (rank + rope) < 128
    shapes = jax.eval_shape(layer.init, jax.random.PRNGKey(0))
    assert shapes["w_kva"].shape == (64, rank + rope)


def test_stored_width_changes_no_output(mesh1):
    """A prompt's expanded read and a decode step's absorbed read (whole
    and chunked) give the same outputs from the stored lane as from one
    exactly ``rank + rope`` wide, and the columns past ``rank + rope``
    are written as zeros."""
    layer = MLAttn(32, 2, 8, 4, 8, 16, 6e6, mesh=mesh1, dtype=jnp.float32)
    params = layer.init(jax.random.PRNGKey(9))
    written, t, s = 20, 2048, 12
    x = jax.random.normal(jax.random.PRNGKey(10), (2, s, 32))
    pos = jnp.tile(jnp.arange(s)[None], (2, 1))
    offsets = jnp.asarray([s, 5], jnp.int32)
    step_x = jax.random.normal(jax.random.PRNGKey(11), (2, 1, 32))

    def run(width, kv_need):
        with jax.default_matmul_precision("highest"):
            out, (lane,) = layer(params, x, pos,
                                 (jnp.zeros((2, s, width)),), jnp.int32(0))
            big = jnp.zeros((2, t, width)).at[:, :s].set(lane)
            got, (after,) = jax.jit(lambda big, xs: layer(
                params, xs, offsets[:, None], (big,), offsets,
                kv_need=kv_need))(big, step_x)
        return [np.asarray(a) for a in (out, got, after)]

    for kv_need in (None, jnp.int32(700)):
        narrow = run(written, kv_need)
        stored = run(layer.latent_width, kv_need)
        for a, b in zip(narrow[:2], stored[:2]):
            np.testing.assert_allclose(b, a, rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(stored[2][..., :written], narrow[2])
        assert not stored[2][..., written:].any()


# -- the router and the share ---------------------------------------------

def test_group_limited_routing_matches_the_written_rule():
    from triton_dist_tpu.ops.moe_utils import sigmoid_topk_routing
    rng = np.random.default_rng(3)
    t, e = 64, 32
    logits = jnp.asarray(rng.normal(size=(t, e)), jnp.float32)
    bias = jnp.asarray(0.05 * rng.normal(size=e), jnp.float32)
    model = ref_model()
    dense, idx = ref.route(logits, jnp.eye(e), bias, model)
    w, got = sigmoid_topk_routing(logits, bias, 4, True, 2.5, 8, 4)
    np.testing.assert_array_equal(np.sort(np.asarray(got), -1),
                                  np.sort(np.asarray(idx), -1))
    np.testing.assert_allclose(
        np.asarray(w), np.take_along_axis(np.asarray(dense), np.asarray(got),
                                          -1), rtol=1e-6)
    # At most topk_group groups are ever used, and the limit binds.
    groups = np.asarray(got) // 4
    assert max(len(set(r)) for r in groups.tolist()) <= 4
    free = np.asarray(jax.lax.top_k(jax.nn.sigmoid(logits) + bias, 4)[1])
    assert (np.sort(free, -1) != np.sort(np.asarray(got), -1)).any()


def test_one_group_routing_is_the_one_group_rule():
    """``n_group == 1``: K-EXAONE's selection, weights and ids."""
    from triton_dist_tpu.ops.moe_utils import sigmoid_topk_routing
    rng = np.random.default_rng(5)
    logits = jnp.asarray(rng.normal(size=(48, 16)), jnp.float32)
    bias = jnp.asarray(0.05 * rng.normal(size=16), jnp.float32)
    model = {"num_experts_per_tok": 2, "norm_topk_prob": True,
             "routed_scaling_factor": 2.5}
    dense, idx = exaone_ref.route(logits, jnp.eye(16), bias, model)
    for kw in ({}, {"n_group": 1, "topk_group": 1}):
        w, got = sigmoid_topk_routing(logits, bias, 2, True, 2.5, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(idx))
        np.testing.assert_allclose(
            np.asarray(w), np.take_along_axis(np.asarray(dense),
                                              np.asarray(idx), -1),
            rtol=1e-6)


def test_eight_shares_add_up_to_the_uncut_layer(mesh1):
    """The routed parts of all 8 shares (one routing group each) plus the
    shared expert ONCE equal the uncut reference's sparse layer."""
    from triton_dist_tpu.layers.ep_moe import EPShareMoE
    key, layer = ref.seed_key(SEED), 1
    whole = dict(ref.model_items(ref_model(world=1)))
    x = jax.random.normal(jax.random.PRNGKey(5), (48, 64), jnp.float32)
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(6), (32,))
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    w_whole = f32(ref.layer_leaves(key, layer, whole, "kda", True))["moe"]
    want = np.asarray(ref.moe(x, w_whole, bias, whole))
    shared = np.asarray(exaone_ref._swiglu(x, w_whole["shared"], "f32"))
    total, pairs = shared.copy(), 0
    for rank in range(8):
        share = dict(ref.model_items(ref_model(world=8, rank=rank)))
        w = f32(ref.layer_leaves(key, layer, share, "kda", True))["moe"]
        np.testing.assert_array_equal(              # a cut of ONE model
            np.asarray(w["w_gate"]),
            np.asarray(w_whole["w_gate"][4 * rank:4 * rank + 4]))
        moe = EPShareMoE(64, 32, 32, 4, 4 * rank, 4, 32, mesh=mesh1,
                         dtype=jnp.float32, impl="xla", scale=2.5,
                         n_group=8, topk_group=4)
        out, counts = moe(moe.shard_params(dict(w, e_bias=bias)), x)
        total += np.asarray(out) - shared
        pairs += int(counts["held_pairs"])
    assert pairs == 48 * 4                       # every pair held once
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)


# -- the decoder against the reference ------------------------------------

def test_prefill_logits_match_reference(served):
    llm, params, model = served
    ids = np.asarray(prompts_of([70], seed=1), np.int32)
    small = engine(llm).kv.scratch(engine(llm).kv.init(), 70)
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(lambda ids, small: llm.forward(
            params, ids, small, 0, mode="xla_ar"))(jnp.asarray(ids), small)
    np.testing.assert_allclose(np.asarray(logits)[0],
                               ref_logits(model, ids[0]), rtol=2e-3,
                               atol=2e-4)


def test_admission_then_decode_through_the_caches_matches_reference(served):
    """Prompts in three buckets (one of two scan chunks), each row at its
    own position: the logits of every decode step, from the session's
    own state and latent lanes, against the reference's full forward
    pass."""
    llm, params, model = served
    eng = engine(llm)
    sess = eng.stream_session(params)
    prompts = prompts_of([70, 5, 21])
    seqs = [list(p) for p in prompts]
    for row, p in enumerate(prompts):
        seqs[row].append(sess.prefill_into_row(row, p))
    fwd = jax.jit(lambda caches, token, offsets: llm.forward(
        params, token[:, None], caches, offsets, mode="gemm_ar")[0])
    step_logits = []
    for _ in range(12):
        step_logits.append(np.asarray(fwd(
            sess.caches, sess.token[:4], sess.offsets))[:, 0])
        toks = sess.decode_step()
        for row in range(3):
            seqs[row].append(int(toks[row]))
    for row, (p, s) in enumerate(zip(prompts, seqs)):
        want = ref_logits(model, s[:-1])[len(p):]
        got = np.stack([lg[row] for lg in step_logits])
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4)
        assert gaps(model, p, s[len(p):]).max() < 1e-3


def test_lane_pad_columns_stay_zero_through_the_session(served):
    """Admissions in two buckets and a few decode steps: every MLA
    layer's lane holds zeros past ``rank + rope`` in every row, and the
    latents the rows wrote are there."""
    llm, params, _ = served
    sess = engine(llm).stream_session(params)
    for row, p in enumerate(prompts_of([33, 6], seed=4)):
        sess.prefill_into_row(row, p)
    for _ in range(5):
        sess.decode_step()
    written = llm.mla.rank + llm.mla.rope_dim
    lanes = [np.asarray(layer[0]) for kind, layer in zip(
        llm.cache_kinds, sess.caches) if isinstance(kind, LatentLane)]
    assert len(lanes) == 2
    for lane in lanes:
        assert lane.shape[-1] == llm.mla.latent_width > written
        assert not lane[..., written:].any()
        assert np.abs(lane[0, :33 + 5, :written]).max(-1).all()
        assert np.abs(lane[1, :6 + 5, :written]).max(-1).all()


def test_a_readmitted_row_starts_from_a_zero_state(served):
    """Row 1 serves one request, is retired, and takes another: its
    logits are those of a fresh session's row 1 given only the second."""
    llm, params, _ = served
    first, second = prompts_of([33, 19], seed=6)
    fwd = jax.jit(lambda caches, token, offsets: llm.forward(
        params, token[:, None], caches, offsets, mode="gemm_ar")[0])

    def serve(prompts):
        sess = engine(llm).stream_session(params)
        for i, p in enumerate(prompts):
            if i:
                sess.retire_row(1)
            sess.prefill_into_row(1, p)
            out = []
            for _ in range(6):
                out.append(np.asarray(fwd(sess.caches, sess.token[:4],
                                          sess.offsets))[1, 0])
                sess.decode_step()
        return np.stack(out)

    np.testing.assert_allclose(serve([first, second]), serve([second]),
                               rtol=1e-6, atol=1e-6)


def test_counts_are_made_in_the_programs_and_at_admission(served):
    llm, params, _ = served
    eng = engine(llm)
    names = eng.count_names
    assert names[-2:] == ("kda.rows_stepped", "attn.positions_read.latent")
    was = obs.enabled()
    obs.enable()
    try:
        c0 = dict(obs.snapshot().get("counters", {}))
        sess = eng.stream_session(params)
        for row, p in enumerate(prompts_of([70, 3])):
            sess.prefill_into_row(row, p)
        steps = 5
        for _ in range(steps):
            sess.decode_step()
        c1 = obs.snapshot()["counters"]
    finally:
        if not was:
            obs.disable()
    d = lambda k: c1.get(k, 0) - c0.get(k, 0)
    assert d("kda.rows_stepped") == 2 * 4 * steps
    lens = [n + j + 1 for n in (70, 3) for j in range(steps)]
    assert d("attn.positions_read.latent") == 2 * sum(lens)
    # 70 in the 128 bucket: 2 of its 2 chunks; 3 in the 8 bucket: 1 of 1.
    assert d("kda.scan_chunks_run") == 4 * (2 + 1)
    assert d("kda.scan_chunks_bucket") == 4 * (2 + 1)
    # Query-key pairs of the MLA layers only (2 of the 6).
    assert d("attn.prefill_positions_square") == 2 * (128 * 128 + 8 * 8)
    assert d("moe.routed_tokens") == 5 * (70 + 3 + 2 * steps)


def test_scan_chunk_counts_follow_the_live_prefix(served, monkeypatch):
    """A short prompt in a long bucket runs fewer chunks than it has."""
    monkeypatch.setattr(kda_mod, "CHUNK", 16)
    assert kda_mod.scan_chunks(20, 128) == 2
    assert kda_mod.scan_chunks(128, 128) == 8
    llm, params, _ = served
    was = obs.enabled()
    obs.enable()
    try:
        c0 = dict(obs.snapshot().get("counters", {}))
        engine(llm).stream_session(params).prefill_into_row(0, prompts_of(
            [70])[0])
        c1 = obs.snapshot()["counters"]
    finally:
        if not was:
            obs.disable()
    d = lambda k: c1.get(k, 0) - c0.get(k, 0)
    assert d("kda.scan_chunks_run") == 4 * 5
    assert d("kda.scan_chunks_bucket") == 4 * 8


def test_model_server_serves_concurrent_requests(served):
    from triton_dist_tpu.serving import ChatClient, ModelServer
    llm, params, model = served
    srv = ModelServer(engine(llm, batch=2), params, port=0).start()
    prompts = prompts_of([19, 4, 40, 12], seed=2)
    results = {}
    try:
        def worker(i):
            c = ChatClient(srv.host, srv.port)
            results[i] = c.generate_ids([prompts[i]], gen_len=5)
            c.close()

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
    finally:
        srv.stop()
    for i, p in enumerate(prompts):
        assert "tokens" in results[i], results[i]
        toks = results[i]["tokens"][0]
        assert len(toks) == 5
        assert gaps(model, p, toks).max() < 1e-3


# -- what cannot serve latent and recurrent layers yet says so ------------

@pytest.mark.parametrize("kw,what", [
    (dict(paged=True, prefill_mode="sp", decode_mode="sp"), "prefix cache"),
    (dict(prefill_mode="sp", decode_mode="sp"), "forward_sp"),
    (dict(decode_path="mega"), "mega decode step"),
    (dict(spec="k2"), "speculative verify step"),
])
def test_engine_refuses_paths_without_recurrent_layers(served, kw, what):
    llm = served[0]
    if "spec" in kw:
        from triton_dist_tpu.models import SpecConfig
        kw = dict(spec=SpecConfig(k=2))
    args = dict(prefill_mode="xla_ar", decode_mode="gemm_ar")
    args.update(kw)
    with pytest.raises(NotImplementedError,
                       match=r"LingHybrid has latent-cache and "
                             r"recurrent-state.*" + re.escape(what)):
        Engine(llm, batch=2, max_seq=64, **args)


def test_batch_serve_and_chunked_admissions_are_refused(served):
    from triton_dist_tpu.serving.scheduler import Scheduler
    llm, params, _ = served
    eng = engine(llm)
    with pytest.raises(NotImplementedError, match="stream session"):
        eng.serve(params, jnp.ones((2, 4), jnp.int32), 2)
    sess = eng.stream_session(params)
    with pytest.raises(NotImplementedError,
                       match="LingHybrid has recurrent-state.*chunked"):
        sess.prefill_into_row(0, prompts_of([20])[0], chunk=8)
    assert sess.free_rows() == [0, 1, 2, 3]
    with pytest.raises(NotImplementedError, match="LingHybrid.*chunked"):
        Scheduler(eng, params, prefill_chunk=8)


@pytest.mark.parametrize("kw,what", [
    (dict(mode="sp"), "forward_sp"),
    (dict(mode="ag_rs"), "replicated-activation"),
    (dict(block_table=jnp.zeros((1, 2, 2), jnp.int32)), "paged pools"),
    (dict(kv_start=jnp.zeros((2,), jnp.int32)), "ragged"),
])
def test_forward_refuses_what_it_cannot_compute(served, kw, what):
    llm, params, _ = served
    caches = engine(llm, batch=2).kv.init()
    with pytest.raises(NotImplementedError, match=what):
        llm.forward(params, jnp.ones((2, 1), jnp.int32), caches,
                    jnp.zeros((2,), jnp.int32), **{"mode": "gemm_ar", **kw})


def test_per_row_burst_is_refused(served):
    llm, params, _ = served
    caches = engine(llm, batch=2).kv.init()
    with pytest.raises(NotImplementedError, match="speculative verify"):
        llm.forward(params, jnp.ones((2, 3), jnp.int32), caches,
                    jnp.zeros((2,), jnp.int32), mode="gemm_ar")


@pytest.mark.parametrize("over,what", [
    (dict(expert_swiglu_limit_list=[0, 4, 0, 0, 0, 0]), "swiglu_limit"),
    (dict(share_expert_swiglu_limit_list=[0, 0, 5, 0, 0, 0]),
     "swiglu_limit"),
    (dict(num_nextn_predict_layers=1), "multi-token-prediction"),
])
def test_unbuilt_parts_of_the_configuration_are_refused(mesh1, over, what):
    with pytest.raises(NotImplementedError, match=what):
        build(mesh1, **over)
