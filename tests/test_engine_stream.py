"""Continuous batching (Engine.serve_stream): streamed greedy results
must equal serving each prompt alone — admission into freed rows cannot
perturb the other rows' generations (beyond-reference; vLLM-style)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.models import DenseLLM, Engine, ModelConfig


@pytest.fixture()
def small_model(mesh8, key):
    cfg = ModelConfig(hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=8,
                      num_key_value_heads=8, head_dim=4, vocab_size=64,
                      max_position_embeddings=64, dtype=jnp.float32)
    model = DenseLLM(cfg, mesh=mesh8, axis="tp", impl="xla")
    return model, model.init(key)


def solo(model, params, mesh8, prompt, gen_len, stop=()):
    eng = Engine(model, batch=1, max_seq=32, prefill_mode="xla_ar",
                 decode_mode="gemm_ar")
    out = np.asarray(eng.serve(params, jnp.asarray([prompt], jnp.int32),
                               gen_len, stop_tokens=stop))[0]
    row = out.tolist()
    if stop:
        # serve() pads stopped rows with the stop token; trim to match
        # serve_stream's exact-retire contract.
        gen = row[len(prompt):]
        for i, t in enumerate(gen):
            if t in set(stop):
                gen = gen[:i + 1]
                break
        row = row[:len(prompt)] + gen
    return row


@pytest.mark.slow(reason="51-63 s")
def test_stream_more_requests_than_rows(small_model, mesh8):
    model, params = small_model
    prompts = [[1, 2, 3], [9, 8], [4, 5, 6, 7], [11], [23, 29], [31]]
    gen_len = 5
    eng = Engine(model, batch=2, max_seq=32, prefill_mode="xla_ar",
                 decode_mode="gemm_ar")
    got = eng.serve_stream(params, prompts, gen_len)
    assert len(got) == len(prompts)
    for prompt, row in zip(prompts, got):
        want = solo(model, params, mesh8, prompt, gen_len)
        assert row == want, (prompt, row, want)


@pytest.mark.slow(reason="54-69 s")
def test_stream_stop_tokens_free_rows_early(small_model, mesh8):
    model, params = small_model
    # pick a stop token that actually occurs early for some prompt by
    # probing the solo generations
    prompts = [[1, 2], [3, 4], [5, 6], [7, 8]]
    gen_len = 6
    probe = [solo(model, params, mesh8, p, gen_len) for p in prompts]
    stop = (probe[0][len(prompts[0]) + 1],)  # 2nd generated tok of req 0
    eng = Engine(model, batch=2, max_seq=32, prefill_mode="xla_ar",
                 decode_mode="gemm_ar")
    got = eng.serve_stream(params, prompts, gen_len, stop_tokens=stop)
    for prompt, row in zip(prompts, got):
        want = solo(model, params, mesh8, prompt, gen_len, stop=stop)
        assert row == want, (prompt, row, want)


def test_stream_single_row_window(small_model, mesh8):
    """batch=1 degenerates to sequential serving."""
    model, params = small_model
    prompts = [[2, 3, 5], [7]]
    eng = Engine(model, batch=1, max_seq=32, prefill_mode="xla_ar",
                 decode_mode="gemm_ar")
    got = eng.serve_stream(params, prompts, 4)
    for prompt, row in zip(prompts, got):
        assert row == solo(model, params, mesh8, prompt, 4)


def test_stream_gen_len_zero_noop(small_model):
    model, params = small_model
    eng = Engine(model, batch=2, max_seq=32, prefill_mode="xla_ar",
                 decode_mode="gemm_ar")
    assert eng.serve_stream(params, [[1, 2], [3]], 0) == [[1, 2], [3]]


@pytest.fixture()
def sp_model(mesh8, key):
    from jax.sharding import Mesh
    import numpy as _np
    devs = [d for d in mesh8.devices.flat]
    mesh = Mesh(_np.array(devs).reshape(1, 8), ("tp", "sp"))
    cfg = ModelConfig(hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=16, vocab_size=64,
                      max_position_embeddings=64, dtype=jnp.float32)
    model = DenseLLM(cfg, mesh=mesh, axis="tp", sp_axis="sp",
                     impl="pallas", fwd_mode="sp")
    return model, model.init(key)


_SP_GOLDEN_CACHE: dict = {}


def _solo_sp(model, params, prompt, gen_len):
    # Golden: the plain tp engine on the same params — sp serving is
    # token-equal to it (test_sp_model.py::test_sp_paged_serving_matches)
    # and, unlike a solo sp engine, it accepts prompt lengths that
    # don't divide the sp world (the very case stream bucketing adds).
    # Cached across the paged parametrizations (paged-independent).
    key = (id(model), tuple(prompt), gen_len)
    if key not in _SP_GOLDEN_CACHE:
        eng = Engine(model, batch=1, max_seq=64, prefill_mode="xla",
                     decode_mode="xla_ar")
        _SP_GOLDEN_CACHE[key] = np.asarray(eng.serve(
            params, jnp.asarray([prompt], jnp.int32),
            gen_len))[0].tolist()
    return _SP_GOLDEN_CACHE[key]


@pytest.mark.slow(reason="76-92 s each")
@pytest.mark.parametrize("paged", [False, True])
def test_stream_sp_and_paged(sp_model, paged):
    """Continuous batching over the long-context engine families: the
    seq-sharded cache (per-row scatter through forward_sp) and the
    vLLM-style paged pools (block-granular admission prefills straight
    into the admitted row's pages; retired rows release eagerly)."""
    model, params = sp_model
    prompts = [[1, 2, 3], [9, 8], [4, 5, 6, 7], [11], [23, 29]]
    gen_len = 5
    eng = Engine(model, batch=2, max_seq=64, prefill_mode="sp",
                 decode_mode="sp", paged=paged, page_size=4)
    got = eng.serve_stream(params, prompts, gen_len)
    assert len(got) == len(prompts)
    for prompt, row in zip(prompts, got):
        want = _solo_sp(model, params, prompt, gen_len)
        assert row == want, (paged, prompt, row, want)


@pytest.mark.slow(reason="27-29 s")
def test_stream_paged_fewer_requests_than_rows(sp_model):
    """n_req < batch (advisor r3, medium): lanes that are NEVER admitted
    still run the per-row KV write each decode step through their
    block-table lane. Under block-granular admission (ISSUE 6) those
    lanes point at the per-device SENTINEL block, so frozen writes are
    structurally harmless; the lone request must decode exactly as
    when served alone."""
    model, params = sp_model
    prompt = [4, 5, 6, 7]
    gen_len = 6
    eng = Engine(model, batch=3, max_seq=64, prefill_mode="sp",
                 decode_mode="sp", paged=True, page_size=4)
    got = eng.serve_stream(params, [prompt], gen_len)
    assert got[0] == _solo_sp(model, params, prompt, gen_len)


def test_stream_sampled_deterministic_per_seed(small_model):
    """Stochastic streaming is reproducible: same seed → same tokens
    (the engine key advances identically through admissions + steps)."""
    model, params = small_model
    prompts = [[1, 2], [3, 4, 5], [6]]
    outs = []
    for _ in range(2):
        eng = Engine(model, batch=2, max_seq=32, prefill_mode="xla_ar",
                     decode_mode="gemm_ar", temperature=0.8, top_k=8,
                     top_p=0.9, seed=13)
        outs.append(eng.serve_stream(params, prompts, 4))
    assert outs[0] == outs[1]


@pytest.mark.slow(reason="105-116 s")
def test_stream_randomized_admission_fuzz(small_model, mesh8):
    """Seeded fuzz over the admission scheduler: random prompt lengths,
    a random stop token, 12 requests through 3 rows — every streamed
    row must equal its solo generation (reference stress_test_ag_gemm
    style: randomized loops catching sync bugs)."""
    model, params = small_model
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 64, size=int(n)).tolist()
               for n in rng.integers(1, 7, size=12)]
    stop = (int(rng.integers(1, 64)),)
    gen_len = int(rng.integers(2, 7))
    eng = Engine(model, batch=3, max_seq=32, prefill_mode="xla_ar",
                 decode_mode="gemm_ar")
    got = eng.serve_stream(params, prompts, gen_len, stop_tokens=stop)
    for prompt, row in zip(prompts, got):
        want = solo(model, params, mesh8, prompt, gen_len, stop=stop)
        assert row == want, (prompt, row, want)


@pytest.mark.slow(reason="49-50 s")
def test_stream_2d_tp_x_sp(mesh8, key):
    """Streaming over the 2-D tp×sp grid: heads tensor-parallel inside
    the sequence ring, per-row offsets through forward_sp."""
    from jax.sharding import Mesh
    import numpy as _np
    devs = [d for d in mesh8.devices.flat]
    mesh = Mesh(_np.array(devs).reshape(2, 4), ("tp", "sp"))
    cfg = ModelConfig(hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=16, vocab_size=64,
                      max_position_embeddings=64, dtype=jnp.float32)
    model = DenseLLM(cfg, mesh=mesh, axis="tp", sp_axis="sp",
                     impl="pallas", fwd_mode="sp")
    params = model.init(key)
    prompts = [[1, 2, 3], [9, 8], [4, 5, 6, 7]]
    eng = Engine(model, batch=2, max_seq=64, prefill_mode="sp",
                 decode_mode="sp")
    got = eng.serve_stream(params, prompts, 4)
    golden = Engine(model, batch=1, max_seq=64, prefill_mode="xla_ar",
                    decode_mode="xla_ar")
    for prompt, row in zip(prompts, got):
        want = np.asarray(golden.serve(
            params, jnp.asarray([prompt], jnp.int32), 4))[0].tolist()
        assert row == want, (prompt, row, want)


@pytest.mark.slow(reason="ep 50-54 s, tp 32-35 s")
@pytest.mark.parametrize("moe_parallel", ["tp", "ep"])
def test_stream_moe_model(mesh8, key, moe_parallel):
    """Per-row offsets thread through Qwen3MoE.forward — in BOTH MoE
    parallelizations (the EP dispatch/combine is token-level, so the
    per-row decode positions only touch the attention/cache path)."""
    from triton_dist_tpu.models import ModelConfig, Qwen3MoE
    cfg = ModelConfig(hidden_size=32, moe_intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=8,
                      num_key_value_heads=8, head_dim=4, vocab_size=64,
                      max_position_embeddings=64, dtype=jnp.float32,
                      num_experts=8, num_experts_per_tok=2,
                      intermediate_size=0)
    model = Qwen3MoE(cfg, mesh=mesh8, axis="tp", impl="xla",
                     moe_parallel=moe_parallel)
    params = model.init(key)
    prompts = [[1, 2, 3], [9, 8], [4, 5]]
    eng = Engine(model, batch=2, max_seq=32, prefill_mode="xla_ar",
                 decode_mode="gemm_ar")
    got = eng.serve_stream(params, prompts, 3)
    for prompt, row in zip(prompts, got):
        solo_eng = Engine(model, batch=1, max_seq=32,
                          prefill_mode="xla_ar", decode_mode="gemm_ar")
        want = np.asarray(solo_eng.serve(
            params, jnp.asarray([prompt], jnp.int32), 3))[0].tolist()
        assert row == want, (prompt, row, want)


def test_admission_read_in_query_blocks_seats_the_same_row(
        key, monkeypatch):
    """A 300-token prompt lands in the 512 bucket; with the score budget
    shrunk the admission's attention is read in query blocks
    (layers/tp_attn._attention_core: each block against the keys its
    mask leaves it), at the default budget the same admission is one
    block, the masked read. Same first token, same lane of every
    layer's cache; fewer query-key pairs counted."""
    from jax.sharding import Mesh
    from triton_dist_tpu import obs
    from triton_dist_tpu.layers import tp_attn
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    cfg = ModelConfig(hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=16, vocab_size=64,
                      max_position_embeddings=1024, dtype=jnp.float32)
    model = DenseLLM(cfg, mesh=mesh, axis="tp", impl="xla")
    params = model.init(key)
    prompt = np.random.default_rng(0).integers(1, 64, 300).tolist()

    def admit():
        eng = Engine(model, batch=2, max_seq=1024, prefill_mode="xla_ar",
                     decode_mode="gemm_ar")
        sess = eng.stream_session(params)
        c0 = dict(obs.snapshot().get("counters", {}))
        first = sess.prefill_into_row(1, prompt)
        c1 = obs.snapshot()["counters"]
        lanes = [np.asarray(leaf)[1, :300] for pair in sess.caches
                 for leaf in pair]
        return (first, lanes, [c1[k] - c0.get(k, 0) for k in (
            "attn.prefill_positions_scored",
            "attn.prefill_positions_square")])

    was = obs.enabled()
    obs.enable()
    try:
        first0, lanes0, (scored0, square0) = admit()
        monkeypatch.setattr(tp_attn, "_SCORE_BYTES", 1 << 20)
        blocks = tp_attn.prefill_blocks(1, 4, 512, None)
        first, lanes, (scored, square) = admit()
    finally:
        if not was:
            obs.disable()
    assert blocks == ((0, 0, 128), (128, 0, 256), (256, 0, 384),
                      (384, 0, 512))
    assert first == first0
    for got, want in zip(lanes, lanes0):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    assert square == square0 == scored0 == 2 * 512 * 512
    assert scored == 2 * 128 * (128 + 256 + 384 + 512)


# -- an admission computes the one logit row it reads (ISSUE 38) -----------

_VOCAB = 96     # no other dimension of these engines' programs


def _one_row_engine(paged: bool, key):
    """A dense engine on a small mesh, XLA paths: the scratch-prefill
    family (whole and chunked admissions) or the paged sp family (cold
    and prefix-hit admissions)."""
    from jax.sharding import Mesh
    devs = jax.devices()[:2]
    mesh = (Mesh(np.array(devs).reshape(1, 2), ("tp", "sp")) if paged
            else Mesh(np.array(devs[:1]), ("tp",)))
    cfg = ModelConfig(hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=16, vocab_size=_VOCAB,
                      max_position_embeddings=64, dtype=jnp.float32)
    if paged:
        model = DenseLLM(cfg, mesh=mesh, axis="tp", sp_axis="sp",
                         impl="xla", fwd_mode="sp")
        eng = Engine(model, batch=3, max_seq=64, prefill_mode="sp",
                     decode_mode="sp", paged=True, page_size=4)
    else:
        model = DenseLLM(cfg, mesh=mesh, axis="tp", impl="xla")
        eng = Engine(model, batch=3, max_seq=64, prefill_mode="xla_ar",
                     decode_mode="gemm_ar")
    return eng, model.init(key)


def _all_row_reference(eng, params, prompt, gen_len):
    """What the all-position contract gives: every row of the prompt's
    logits from a plain forward (no ``logits_at``), its row ``len - 1``
    read on the host, the K/V it wrote; and ``Engine.serve``'s greedy
    continuation of the same prompt."""
    model = eng.model
    mode = "xla" if eng.paged else "xla_ar"
    solo_eng = Engine(model, batch=1, max_seq=64, prefill_mode=mode,
                      decode_mode="xla_ar")
    logits, kv = jax.jit(lambda p, i, k: model.forward(
        p, i, k, 0, mode=mode))(params, jnp.asarray([prompt], jnp.int32),
                                solo_eng.kv.init())
    assert logits.shape == (1, len(prompt), _VOCAB)
    first = int(np.argmax(np.asarray(logits)[0, -1]))
    served = np.asarray(solo_eng.serve(
        params, jnp.asarray([prompt], jnp.int32), gen_len))[0].tolist()
    assert served[len(prompt)] == first
    return first, kv, served[len(prompt):]


@pytest.mark.parametrize("length", [11, 16], ids=["short", "bucket"])
@pytest.mark.parametrize("case",
                         ["whole", "chunked", "paged", "paged_prefix"])
def test_admission_reads_one_row_and_seats_what_all_rows_gave(
        key, case, length):
    """Every admission path tells the model which row it reads; first
    token, seated lanes, ``token`` / ``offsets`` / key and the decode
    that follows equal the all-position contract's, for a prompt
    shorter than its bucket and one exactly a bucket long, and
    ``engine.admit_head_rows`` counts one row per admission program."""
    from triton_dist_tpu import obs
    paged = case.startswith("paged")
    eng, params = _one_row_engine(paged, key)
    prompt = np.random.default_rng(length).integers(
        1, _VOCAB, length).tolist()
    gen_len = 4
    first_ref, kv_ref, gen_ref = _all_row_reference(eng, params, prompt,
                                                    gen_len)
    was = obs.enabled()
    obs.enable()
    try:
        sess = eng.stream_session(params)
        if case == "paged_prefix":
            # Leave the prompt's first two pages in the prefix cache.
            sess.prefill_into_row(0, prompt[:8] + [7, 7, 7], gen_budget=4)
            sess.retire_row(0)
        key0 = np.asarray(eng.key).copy()
        c0 = dict(obs.snapshot().get("counters", {}))
        row = 1
        first = sess.prefill_into_row(
            row, prompt, gen_budget=gen_len,
            chunk=4 if case == "chunked" else None)
        while first is None:
            first = sess.prefill_step(row)
        c1 = obs.snapshot()["counters"]
    finally:
        if not was:
            obs.disable()
    delta = {k: c1[k] - c0.get(k, 0) for k in (
        "engine.admit_head_rows", "engine.admit_bucket_tokens")}
    cached = 8 if case == "paged_prefix" else 0
    assert sess.admit_info["cached"] == cached
    programs = -(-length // 4) if case == "chunked" else 1
    assert delta["engine.admit_head_rows"] == programs
    assert delta["engine.admit_bucket_tokens"] == (
        programs * 4 if case == "chunked" else 16 if not cached else 8)
    assert first == first_ref
    assert np.asarray(sess.token)[row] == first
    assert np.asarray(sess.offsets)[row] == length
    np.testing.assert_array_equal(np.asarray(eng.key), key0)   # greedy
    if not paged:
        for (ck, cv), (rk, rv) in zip(sess.caches, kv_ref):
            for got, want in ((ck, rk), (cv, rv)):
                np.testing.assert_allclose(
                    np.asarray(got)[row, :length],
                    np.asarray(want)[0, :length], rtol=1e-5, atol=1e-5)
    got = [first]
    while len(got) < gen_len:
        got += sess.decode_burst()[row]
    assert got == gen_ref
    sess.close()


@pytest.mark.parametrize("program", ["admit", "chunk", "paged"])
def test_admission_program_holds_no_all_position_logits(key, program):
    """The lowered admission of a dense engine multiplies ONE row by the
    head: no float32 value of bucket x vocabulary anywhere in it."""
    eng, params = _one_row_engine(program == "paged", key)
    sess = eng.stream_session(params)
    lb = 16
    ids = sess._padded_ids([3] * 11, lb)
    state = (sess.token, sess.offsets, eng.key)
    if program == "admit":
        text = eng._admit.lower(params, sess.caches, ids, np.int32(11),
                                np.int32(1), *state).as_text()
    elif program == "paged":
        text = eng._admit.lower(params, sess.caches, ids, np.int32(11),
                                np.int32(1), sess.cur_table,
                                *state).as_text()
    else:
        small = [(jnp.zeros((1, lb) + ck.shape[2:], ck.dtype),
                  jnp.zeros((1, lb) + cv.shape[2:], cv.dtype))
                 for ck, cv in sess.caches]
        text = eng._build_admit_chunk().lower(
            params, small, ids, np.int32(0), np.int32(11), None).as_text()
    sess.close()
    assert f"x{_VOCAB}xf32>" in text
    for rows in (f"{lb}x{_VOCAB}xf32", f"1x{lb}x{_VOCAB}xf32"):
        assert rows not in text, rows
    assert f"tensor<1x1x{_VOCAB}xf32>" in text
