"""K-EXAONE (``exaone_moe``) on the serving path, against its plain
reference (``benchmark/reference/exaone_moe.py``) at a small preset: two
periods of sliding/full attention (window 8), a dense first layer, 16
sigmoid-routed experts top-2 of which this share holds 4, a shared expert.

Weights are the reference's (the benchmark builder's ``make_params``),
cast to float32 so that program and reference differ by summation order
only: a wrong mask, ring slot, router or share reads as a gap of 0.1 and
more, rounding as 1e-5.
"""

import dataclasses
import hashlib
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmark.harness.builders import exaone as builder
from benchmark.reference import exaone_moe as ref
from triton_dist_tpu import obs
from triton_dist_tpu.models import (AutoLLM, DenseLLM, Engine, ExaoneMoE,
                                    ModelConfig)
from triton_dist_tpu.models.kv_cache import KVCacheManager, ring_lane

SEED = 11
WINDOW = 8
LAYERS = 8          # dense, then S S F | S S S F: both kinds, twice
HF = dict(
    hidden_size=64, intermediate_size=128, num_hidden_layers=LAYERS,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    vocab_size=256, max_position_embeddings=4096, rms_norm_eps=1e-5,
    rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
    sliding_window=WINDOW, model_type="exaone_moe",
    layer_types=["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    mlp_layer_types=["dense"] + ["sparse"] * 7, first_k_dense_replace=1,
    num_experts=16, num_experts_per_tok=2, moe_intermediate_size=32,
    num_shared_experts=1, scoring_func="sigmoid", norm_topk_prob=True,
    routed_scaling_factor=2.5, expert_parallel={"world": 4, "rank": 0})


def ref_model(world=4, rank=0) -> dict:
    return dict(HF, expert_parallel=(world, rank), rope_theta=1e6,
                balance_shape=(4, 64))


@pytest.fixture(scope="module")
def mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("tp",))


@pytest.fixture(scope="module")
def served(mesh1):
    """(decoder, params, reference model dict): float32 throughout."""
    cfg = dataclasses.replace(ModelConfig.from_hf_config(HF),
                              dtype=jnp.float32)
    llm = AutoLLM.build(cfg, mesh=mesh1, axis="tp", impl="xla")
    assert isinstance(llm, ExaoneMoE)
    model = ref_model()
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          builder.make_params(model, mesh1, SEED))
    return llm, llm.shard_params(params), model


def engine(llm, batch=4, max_seq=64, **kw) -> Engine:
    return Engine(llm, batch=batch, max_seq=max_seq, prefill_mode="xla_ar",
                  decode_mode="gemm_ar", **kw)


def ref_logits(model, seq):
    """The reference's logits at every position of ``seq``, in one padded
    shape (causal attention hides the pad): one compile per test run."""
    ids = np.zeros((1, 48), np.int32)
    ids[0, :len(seq)] = seq
    lg = ref.read_logits(model, SEED, ids, np.arange(48)[None])
    return np.asarray(lg)[0, :len(seq)]


def gaps(model, prompt, served_tokens):
    """Per served token: the reference's best logit minus its logit of
    the served token, over the position's logit spread."""
    full = list(prompt) + list(served_tokens)
    lg = ref_logits(model, full[:-1])[len(prompt) - 1:]
    at = lg[np.arange(len(served_tokens)), np.asarray(served_tokens)]
    return (lg.max(-1) - at) / lg.std(-1)


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, HF["vocab_size"], n).tolist() for n in lengths]


# -- configuration --------------------------------------------------------

def test_from_hf_config_reads_exaone_keys():
    c = ModelConfig.from_hf_config(HF)
    assert c.layer_windows == (8, 8, 8, None, 8, 8, 8, None)
    assert c.sparse_layers == (False,) + (True,) * 7
    assert (c.num_shared_experts, c.scoring_func) == (1, "sigmoid")
    assert c.routed_scaling_factor == 2.5 and c.rope_theta == 1e6
    assert c.experts_held == (0, 4) and c.qk_norm
    later = ModelConfig.from_hf_config(
        dict(HF, expert_parallel={"world": 4, "rank": 3}))
    assert later.experts_held == (12, 4)
    by_count = {k: v for k, v in HF.items() if k != "mlp_layer_types"}
    assert ModelConfig.from_hf_config(by_count).sparse_layers \
        == c.sparse_layers


@pytest.mark.parametrize("how", ["from_hf_config", "build"])
def test_unknown_model_type_is_refused(mesh1, how):
    with pytest.raises(ValueError, match="unknown model_type 'qwen9'"):
        if how == "from_hf_config":
            ModelConfig.from_hf_config(dict(HF, model_type="qwen9"))
        else:
            AutoLLM.build(dataclasses.replace(
                ModelConfig.from_hf_config(HF), model_type="qwen9"),
                mesh=mesh1)


def test_llama_type_still_builds_dense_without_qk_norm(mesh1):
    c = ModelConfig.from_hf_config(dict(
        hidden_size=64, num_hidden_layers=1, num_attention_heads=4,
        vocab_size=100, intermediate_size=128, model_type="llama"))
    assert not c.qk_norm
    assert isinstance(AutoLLM.build(c, mesh=mesh1, impl="xla"), DenseLLM)


# -- the cache ------------------------------------------------------------

def test_window_layers_cache_does_not_grow_with_max_seq(mesh1):
    windows = ModelConfig.from_hf_config(HF).layer_windows
    for max_seq in (64, 4096):
        kv = KVCacheManager(LAYERS, 4, max_seq, 2, 16, mesh=mesh1,
                            dtype=jnp.float32, windows=windows)
        shapes = [k.shape[1] for k, _ in kv.init()]
        assert shapes == [w or max_seq for w in windows]
    plain = KVCacheManager(2, 4, 64, 2, 16, mesh=mesh1)
    assert [k.shape[1] for k, _ in plain.init()] == [64, 64]


@pytest.mark.parametrize("length", [1, 5, 8, 9, 21, 32])
def test_ring_lane_keeps_the_last_window_positions(length):
    s = 32
    prefix = jnp.arange(s, dtype=jnp.float32).reshape(1, s, 1, 1)
    ring = np.asarray(ring_lane(prefix, jnp.int32(length), WINDOW))[0, :, 0,
                                                                    0]
    for p in range(max(length - WINDOW, 0), length):
        assert ring[p % WINDOW] == p


# -- the router and the share ---------------------------------------------

def test_router_matches_reference_on_near_ties():
    """Scores a few ulps apart, the bias deciding between them: the
    program's routing picks the reference's experts with its weights."""
    from triton_dist_tpu.ops.moe_utils import sigmoid_topk_routing
    rng = np.random.default_rng(3)
    t, e, k = 64, 16, 2
    logits = rng.normal(size=(t, e)).astype(np.float32)
    logits[:, 1::2] = logits[:, 0::2] + rng.choice(
        [-2e-7, 0.0, 2e-7], size=(t, e // 2)).astype(np.float32)
    bias = (rng.choice([-1e-7, 0.0, 1e-7], size=e)).astype(np.float32)
    model = dict(ref_model(), num_experts=e, num_experts_per_tok=k)
    # The reference scores x @ W_r: make x the logits and W_r the identity.
    dense, idx = ref.route(jnp.asarray(logits), jnp.eye(e), jnp.asarray(bias),
                           model)
    w, got = sigmoid_topk_routing(jnp.asarray(logits), jnp.asarray(bias), k,
                                  True, 2.5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(idx))
    np.testing.assert_allclose(
        np.asarray(w), np.take_along_axis(np.asarray(dense), np.asarray(idx),
                                          -1), rtol=1e-6)
    assert len({tuple(r) for r in np.asarray(idx).tolist()}) > 8


def test_shares_add_up_to_the_uncut_layer(mesh1):
    """The routed parts of all four shares plus the shared expert ONCE
    equal the uncut reference's sparse layer."""
    from triton_dist_tpu.layers.ep_moe import EPShareMoE
    key, layer = ref.seed_key(SEED), 2
    whole = dict(ref.model_items(ref_model(world=1)))
    x = jax.random.normal(jax.random.PRNGKey(5), (48, 64), jnp.float32)
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(6), (16,))
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    w_whole = f32(ref.layer_leaves(key, layer, whole, True))["moe"]
    want = np.asarray(ref.moe(x, w_whole, bias, whole))
    shared = np.asarray(ref._swiglu(x, w_whole["shared"], "f32"))
    total, pairs = shared.copy(), 0
    for rank in range(4):
        share = dict(ref.model_items(ref_model(world=4, rank=rank)))
        w = f32(ref.layer_leaves(key, layer, share, True))["moe"]
        np.testing.assert_array_equal(              # a cut of ONE model
            np.asarray(w["w_gate"]),
            np.asarray(w_whole["w_gate"][4 * rank:4 * rank + 4]))
        moe = EPShareMoE(64, 32, 16, 2, 4 * rank, 4, 32, mesh=mesh1,
                         dtype=jnp.float32, impl="xla", scale=2.5)
        out, counts = moe(moe.shard_params(dict(w, e_bias=bias)), x)
        total += np.asarray(out) - shared
        pairs += int(counts["held_pairs"])
    assert pairs == 48 * 2                       # every pair held once
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)


def test_held_expert_ffn_is_dropless_when_overloaded():
    """Every token picks held experts only: more pairs than the small
    grouped matmul takes, so the full one runs and loses none."""
    from triton_dist_tpu.ops.group_gemm import held_expert_ffn
    rng = np.random.default_rng(0)
    t, k, n, h, i = 32, 2, 4, 16, 8
    x = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(n, h, i)), jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(n, i, h)), jnp.float32)
    w = jnp.asarray(rng.uniform(size=(t, k)), jnp.float32)

    def dense(ids):
        out = np.zeros((t, h), np.float32)
        for tok in range(t):
            for j in range(k):
                e = int(ids[tok, j])
                if e < n:
                    a = jax.nn.silu(x[tok] @ wg[e]) * (x[tok] @ wu[e])
                    out[tok] += float(w[tok, j]) * np.asarray(a @ wd[e])
        return out

    crowded = rng.integers(0, n, (t, k)).astype(np.int32)
    out, sizes, rows = jax.jit(held_expert_ffn, static_argnums=6)(
        x, wg, wu, wd, crowded, w, 1 / 8)
    assert int(rows) == t * k and int(sizes.sum()) == t * k
    np.testing.assert_allclose(np.asarray(out), dense(crowded), rtol=1e-4,
                               atol=1e-4)
    sparse = np.where(rng.uniform(size=(t, k)) < 1 / 8, crowded, n
                      ).astype(np.int32)
    out, sizes, rows = jax.jit(held_expert_ffn, static_argnums=6)(
        x, wg, wu, wd, sparse, w, 1 / 8)
    assert int(rows) == 16 and int(sizes.sum()) == int((sparse < n).sum())
    np.testing.assert_allclose(np.asarray(out), dense(sparse), rtol=1e-4,
                               atol=1e-4)


# -- the decoder against the reference ------------------------------------

def test_prefill_logits_match_reference(served):
    llm, params, model = served
    ids = np.asarray(prompts_of([24], seed=1), np.int32)
    small = [(jnp.zeros((1, 24, 2, 16)), jnp.zeros((1, 24, 2, 16)))
             for _ in range(LAYERS)]
    logits, _ = jax.jit(lambda ids, small: llm.forward(
        params, ids, small, 0, mode="xla_ar"))(jnp.asarray(ids), small)
    np.testing.assert_allclose(np.asarray(logits)[0],
                               ref_logits(model, ids[0]), rtol=2e-3,
                               atol=2e-4)


def test_admission_then_decode_through_the_rings_matches_reference(served):
    """Prompts shorter and longer than the window, decoded past the
    ring's wrap, each row at its own position: logits of the per-row
    step against the reference's full forward pass."""
    llm, params, model = served
    eng = engine(llm)
    sess = eng.stream_session(params)
    prompts = prompts_of([21, 5, 13])
    seqs = [list(p) for p in prompts]
    for row, p in enumerate(prompts):
        seqs[row].append(sess.prefill_into_row(row, p))
    fwd = jax.jit(lambda caches, token, offsets: llm.forward(
        params, token[:, None], caches, offsets, mode="gemm_ar")[0])
    step_logits = []
    for _ in range(2 * WINDOW + 3):
        # The logits of this step, from the session's own rings and
        # rows (its step donates them: ours runs first).
        step_logits.append(np.asarray(fwd(
            sess.caches, sess.token[:4], sess.offsets))[:, 0])
        toks = sess.decode_step()
        for row in range(3):
            seqs[row].append(int(toks[row]))
    for row, (p, s) in enumerate(zip(prompts, seqs)):
        assert len(s) - len(p) == 2 * WINDOW + 4
        want = ref_logits(model, s[:-1])[len(p):]
        got = np.stack([lg[row] for lg in step_logits])
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4)
        assert gaps(model, p, s[len(p):]).max() < 1e-3


def test_chunked_admission_serves_the_same_tokens(served):
    llm, params, model = served
    prompt = prompts_of([27], seed=4)[0]
    out = []
    for chunk in (None, 8):
        sess = engine(llm).stream_session(params)
        first = sess.prefill_into_row(0, prompt, chunk=chunk)
        while first is None:
            first = sess.prefill_step(0)
        toks = [first] + [int(sess.decode_step()[0]) for _ in range(10)]
        out.append(toks)
    assert out[0] == out[1]
    assert gaps(model, prompt, out[0]).max() < 1e-3


def test_counts_are_made_in_the_programs_and_ride_with_the_tokens(served):
    llm, params, _ = served
    eng = engine(llm)
    names = eng.count_names
    assert names[:4] == ("moe.routed_tokens", "moe.held_pairs",
                         "moe.pair_rows_computed", "moe.experts_touched")
    assert names[-2:] == ("attn.positions_read.window",
                          "attn.positions_read.full")
    was = obs.enabled()
    obs.enable()
    try:
        c0 = dict(obs.snapshot().get("counters", {}))
        sess = eng.stream_session(params)
        assert sess.token.shape == (4 + len(names),)    # one vector home
        prompts = prompts_of([11, 3])
        for row, p in enumerate(prompts):
            sess.prefill_into_row(row, p)
        steps = 6
        for _ in range(steps):
            assert sess.decode_step().shape == (4,)
        c1 = obs.snapshot()["counters"]
    finally:
        if not was:
            obs.disable()
    d = {k: c1.get(k, 0) - c0.get(k, 0) for k in names}
    sparse = sum(ModelConfig.from_hf_config(HF).sparse_layers)
    tokens = 11 + 3 + 2 * steps          # pads and frozen rows left out
    assert d["moe.routed_tokens"] == tokens * sparse
    held = sum(d[f"moe.expert_pairs.{e}"] for e in range(4))
    assert held == d["moe.held_pairs"] > 0
    assert d["moe.pair_rows_computed"] >= held
    assert 0 < d["moe.experts_touched"] <= 4 * sparse * steps
    lens = [[n + j + 1 for j in range(steps)] for n in (11, 3)]
    assert d["attn.positions_read.full"] == 2 * sum(map(sum, lens))
    assert d["attn.positions_read.window"] == 6 * sum(
        min(n, WINDOW) for row in lens for n in row)


def test_model_server_serves_concurrent_requests(served):
    from triton_dist_tpu.serving import ChatClient, ModelServer
    llm, params, model = served
    srv = ModelServer(engine(llm, batch=2), params, port=0).start()
    prompts = prompts_of([19, 4, 9, 12, 3], seed=2)
    results = {}
    try:
        def worker(i):
            c = ChatClient(srv.host, srv.port)
            results[i] = c.generate_ids([prompts[i]], gen_len=6)
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
        assert len(toks) == 6
        assert gaps(model, p, toks).max() < 1e-3


# -- what cannot serve window layers yet says so --------------------------

@pytest.mark.parametrize("kw,what", [
    (dict(paged=True, prefill_mode="sp", decode_mode="sp"), "paged KV pools"),
    (dict(prefill_mode="sp", decode_mode="sp"), "forward_sp"),
    (dict(decode_path="mega"), "mega decode step"),
    (dict(decode_path="auto"), "mega decode step"),
    (dict(spec="k2"), "speculative verify step"),
])
def test_engine_refuses_paths_without_window_layers(served, kw, what):
    llm = served[0]
    if "spec" in kw:
        from triton_dist_tpu.models import SpecConfig
        kw = dict(spec=SpecConfig(k=2))
    args = dict(prefill_mode="xla_ar", decode_mode="gemm_ar")
    args.update(kw)
    with pytest.raises(NotImplementedError, match=re.escape(what)):
        Engine(llm, batch=2, max_seq=64, **args)


def test_batch_serve_is_refused(served):
    llm, params, _ = served
    with pytest.raises(NotImplementedError, match="stream session"):
        engine(llm).serve(params, jnp.ones((2, 4), jnp.int32), 2)


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


def test_per_row_burst_on_a_ring_is_refused():
    from triton_dist_tpu.layers.tp_attn import _attention_core
    q = jnp.zeros((2, 3, 4, 16))
    kv = jnp.zeros((2, 3, 2, 16))
    ring = jnp.zeros((2, WINDOW, 2, 16))
    with pytest.raises(NotImplementedError, match="speculative verify"):
        _attention_core(q, kv, kv, ring, ring, jnp.zeros((2,), jnp.int32),
                        jnp.zeros((2,), jnp.int32), groups=2, window=WINDOW)


def test_admission_in_query_blocks_seats_the_same_rings_and_lanes(
        served, monkeypatch):
    """A 40-token prompt in the 64 bucket with the score budget shrunk:
    window and full layers alike are read in query blocks
    (layers/tp_attn._attention_core), a window layer's blocks against
    their band only. Same first token, same ring of every window layer
    and lane of every full layer as the one-block read, fewer query-key
    pairs counted."""
    from triton_dist_tpu.layers import tp_attn
    llm, params, _ = served
    prompt = prompts_of([40], seed=2)[0]

    def admit():
        sess = engine(llm).stream_session(params)
        c0 = dict(obs.snapshot().get("counters", {}))
        first = sess.prefill_into_row(1, prompt)
        c1 = obs.snapshot()["counters"]
        lanes = [np.asarray(leaf)[1, :40] for pair in sess.caches
                 for leaf in pair]
        return (first, lanes, [c1[k] - c0.get(k, 0) for k in (
            "attn.prefill_positions_scored",
            "attn.prefill_positions_square")])

    was = obs.enabled()
    obs.enable()
    try:
        first0, lanes0, (scored0, square0) = admit()
        monkeypatch.setattr(tp_attn, "_SCORE_BYTES", 16 << 10)
        first, lanes, (scored, square) = admit()
    finally:
        if not was:
            obs.disable()
    full = tp_attn.prefill_blocks(1, 4, 64, None)
    band = tp_attn.prefill_blocks(1, 4, 64, WINDOW)
    assert full == band == ((0, 0, 16), (16, 0, 32), (32, 0, 48),
                            (48, 0, 64))
    assert first == first0
    assert [lane.shape[0] for lane in lanes[::2]] == [
        WINDOW if w else 40 for w in llm.windows]
    for got, want in zip(lanes, lanes0):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    assert square == square0 == scored0 == LAYERS * 64 * 64
    assert scored == LAYERS * 16 * (16 + 32 + 48 + 64)


# -- the programs of the configuration the benchmark already has ----------

# sha256 of the jaxpr text (addresses blanked) of the stream step and the
# admission of a small qwen3 engine: the step as PR 34's parent traced it
# (nothing since has touched it: PR 38's ``logits_at=None`` is that
# program letter for letter), the admission as PR 38 left it (the head
# multiplies the one row that is read; 0e19a6ba... before).
PARENT_JAXPRS = {
    "step": "1fa7b1ae50ec1c1bc33fc1808ff1f81e153688085df61e2b795d4ef2714900ed",
    "admit": "f08c3aa8ea70d40574e039001160dd113d69d9f0e0ef66e466b0d16927033981",
}


@pytest.mark.parametrize("program", sorted(PARENT_JAXPRS))
def test_qwen_stream_programs_trace_as_the_parent_did(mesh1, program):
    """Window layers, rings and counts are Python-level branches that a
    model without them never takes: the dense decoder's programs are
    the parent's, eqn for eqn."""
    cfg = ModelConfig(hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=16, vocab_size=128,
                      max_position_embeddings=2048)
    llm = AutoLLM.build(cfg, mesh=mesh1, axis="tp", impl="xla")
    eng = Engine(llm, batch=2, max_seq=2048)
    params = llm.init(jax.random.PRNGKey(0))
    sess = eng.stream_session(params)
    zeros = np.zeros((2,), np.int32)
    if program == "step":
        jaxpr = jax.make_jaxpr(eng._stream_step)(
            params, sess.caches, zeros, zeros, jax.random.PRNGKey(0),
            np.zeros((2,), bool), None)
    else:
        jaxpr = jax.make_jaxpr(eng._admit)(
            params, sess.caches, np.zeros((1, 16), np.int32), np.int32(5),
            np.int32(1), zeros, zeros, eng.key)
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PARENT_JAXPRS[program]


# The same for this file's K-EXAONE share, as PR 38's parent traced them:
# the model already computed one logit row, so giving every model
# ``logits_at`` (ISSUE 38) left its admission and its step eqn for eqn
# what they were: the benchmark's control cell by construction.
EXAONE_PARENT_JAXPRS = {
    "step": "26045bbc793ec9052d8f804cd5217c89401e152de53a191a419927773321a16d",
    "admit": "037fc8d9a7729e73d52c1ecd58cd8976b259d0b08f7b243246f487cee72d46c0",
}


@pytest.mark.parametrize("program", sorted(EXAONE_PARENT_JAXPRS))
def test_exaone_stream_programs_trace_as_the_parent_did(mesh1, program):
    cfg = dataclasses.replace(ModelConfig.from_hf_config(HF),
                              dtype=jnp.float32)
    eng = engine(AutoLLM.build(cfg, mesh=mesh1, axis="tp", impl="xla"))
    params = jax.eval_shape(eng.model.init, jax.random.PRNGKey(0))
    caches = jax.eval_shape(eng.kv.init)
    zeros = np.zeros((4,), np.int32)
    token = np.zeros((4 + len(eng.count_names),), np.int32)
    if program == "step":
        jaxpr = jax.make_jaxpr(eng._build_stream_step())(
            params, caches, token, zeros, jax.random.PRNGKey(0),
            np.zeros((4,), bool), None)
    else:
        jaxpr = jax.make_jaxpr(eng._build_admit())(
            params, caches, np.zeros((1, 16), np.int32), np.int32(5),
            np.int32(1), token, zeros, eng.key)
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    assert hashlib.sha256(text.encode()).hexdigest() \
        == EXAONE_PARENT_JAXPRS[program]


@pytest.mark.parametrize("rows,bound", [(4, 64), (64, 256)])
def test_default_queue_bound_grows_with_the_rows(served, rows, bound):
    """A burst of twice the rows (the benchmark's warm-up round) has to
    fit the waiting queue whatever the engine's size."""
    from triton_dist_tpu.serving.scheduler import Scheduler
    llm, params, _ = served
    assert Scheduler(engine(llm, batch=rows), params).max_waiting == bound
