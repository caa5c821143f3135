"""Engine sampling / generation-contract tests (reference
test_e2e_inference.py sampling paths + Engine.serve loop invariants,
engine.py:113-190)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.models import DenseLLM, Engine, ModelConfig
from triton_dist_tpu.models.engine import sample_token


def _cfg():
    return ModelConfig(hidden_size=32, intermediate_size=64,
                       num_hidden_layers=1, num_attention_heads=8,
                       num_key_value_heads=8, head_dim=8, vocab_size=64,
                       max_position_embeddings=32, dtype=jnp.float32)


@pytest.fixture()
def model(mesh8):
    return DenseLLM(_cfg(), mesh=mesh8, axis="tp", impl="xla")


def test_greedy_sampling_is_argmax(key):
    logits = jax.random.normal(key, (3, 64), jnp.float32)
    tok = sample_token(logits, key, temperature=0.0, top_k=0)
    np.testing.assert_array_equal(np.asarray(tok),
                                  np.argmax(np.asarray(logits), axis=-1))


def test_topk_sampling_stays_in_topk(key):
    logits = jax.random.normal(key, (4, 64), jnp.float32)
    k = 5
    topk_sets = np.argsort(-np.asarray(logits), axis=-1)[:, :k]
    for i in range(20):
        tok = np.asarray(sample_token(logits, jax.random.PRNGKey(i),
                                      temperature=1.0, top_k=k))
        for b in range(4):
            assert tok[b] in topk_sets[b], (b, tok[b])


def test_top_p_nucleus_membership(key):
    """top_p samples stay inside the smallest prefix of the sorted
    distribution whose mass reaches p; p→0 degenerates to argmax."""
    logits = jax.random.normal(key, (4, 64), jnp.float32) * 3
    p = 0.6
    probs = np.asarray(jax.nn.softmax(logits, axis=-1))
    order = np.argsort(-probs, axis=-1)
    nucleus = []
    for b in range(4):
        cum, keep = 0.0, set()
        for idx in order[b]:
            if cum >= p:
                break
            keep.add(int(idx))
            cum += probs[b, idx]
        nucleus.append(keep)
    for i in range(20):
        tok = np.asarray(sample_token(logits, jax.random.PRNGKey(i),
                                      temperature=1.0, top_p=p))
        for b in range(4):
            assert int(tok[b]) in nucleus[b], (b, int(tok[b]), nucleus[b])
    # p small enough (including exactly 0) keeps only the argmax
    for p0 in (1e-6, 0.0):
        tok = np.asarray(sample_token(logits, jax.random.PRNGKey(99),
                                      temperature=1.0, top_p=p0))
        np.testing.assert_array_equal(tok,
                                      np.argmax(np.asarray(logits), -1))
    # combined top_k + top_p stays inside BOTH filters
    for i in range(10):
        tok = np.asarray(sample_token(logits, jax.random.PRNGKey(i),
                                      temperature=1.0, top_k=5, top_p=p))
        for b in range(4):
            topk_set = set(np.argsort(-np.asarray(logits)[b])[:5])
            assert int(tok[b]) in (nucleus[b] & topk_set) or \
                int(tok[b]) in topk_set, (b, int(tok[b]))


def test_sampling_seeded_determinism(key):
    logits = jax.random.normal(key, (2, 64), jnp.float32)
    a = sample_token(logits, jax.random.PRNGKey(7), 0.8, 10)
    b = sample_token(logits, jax.random.PRNGKey(7), 0.8, 10)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_seeded_generation_deterministic(model, key):
    params = model.init(key)
    ids = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    e1 = Engine(model, batch=2, max_seq=16, temperature=0.7, top_k=8,
                seed=11)
    e2 = Engine(model, batch=2, max_seq=16, temperature=0.7, top_k=8,
                seed=11)
    np.testing.assert_array_equal(np.asarray(e1.serve(params, ids, 5)),
                                  np.asarray(e2.serve(params, ids, 5)))


def test_engine_serve_shapes_and_prefix(model, key):
    """Output prepends the prompt unchanged; gen_len<=0 echoes it."""
    params = model.init(key)
    ids = jnp.asarray([[9, 8, 7]], jnp.int32)
    eng = Engine(model, batch=1, max_seq=16)
    out = eng.serve(params, ids, 4)
    assert out.shape == (1, 7)
    np.testing.assert_array_equal(np.asarray(out)[:, :3], np.asarray(ids))
    np.testing.assert_array_equal(np.asarray(eng.serve(params, ids, 0)),
                                  np.asarray(ids))


def test_engine_stop_tokens(model, key):
    """Rows that emit a stop token keep emitting it; output stays a
    (B, S+gen_len) rectangle; early-exit must not change the result."""
    params = model.init(key)
    ids = jnp.asarray([[1, 2, 3]], jnp.int32)
    eng = Engine(model, batch=1, max_seq=64)
    free = np.asarray(eng.serve(params, ids, 40))
    # pick the first generated token as the stop token: generation must
    # then be that token repeated for the whole gen window
    stop_tok = int(free[0, 3])
    eng2 = Engine(model, batch=1, max_seq=64)
    out = np.asarray(eng2.serve(params, ids, 40, stop_tokens=(stop_tok,)))
    assert out.shape == (1, 43)
    np.testing.assert_array_equal(out[0, 3:], np.full(40, stop_tok))


def test_engine_stop_token_rows_independent(model, key):
    """One row stopping must not stop the other row's generation."""
    params = model.init(key)
    ids = jnp.asarray([[1, 2, 3], [7, 8, 9]], jnp.int32)
    free = np.asarray(Engine(model, batch=2, max_seq=64)
                      .serve(params, ids, 6))
    stop_tok = int(free[0, 3])  # row 0's first token
    if stop_tok in free[1, 3:]:
        pytest.skip("stop token occurs in both rows for this seed")
    out = np.asarray(Engine(model, batch=2, max_seq=64)
                     .serve(params, ids, 6, stop_tokens=(stop_tok,)))
    np.testing.assert_array_equal(out[0, 3:], np.full(6, stop_tok))
    np.testing.assert_array_equal(out[1], free[1])


def test_engine_eos_from_config(mesh8, key):
    """With config.eos_token_id set, serve() stops on it by default."""
    import dataclasses
    cfg = dataclasses.replace(_cfg(), eos_token_id=5)
    m = DenseLLM(cfg, mesh=mesh8, axis="tp", impl="xla")
    params = m.init(key)
    ids = jnp.asarray([[9, 8, 7]], jnp.int32)
    free = np.asarray(Engine(m, batch=1, max_seq=64)
                      .serve(params, ids, 12, stop_tokens=()))
    out = np.asarray(Engine(m, batch=1, max_seq=64)
                     .serve(params, ids, 12))
    if 5 not in free[0, 3:]:
        np.testing.assert_array_equal(out, free)
    else:
        first = 3 + int(np.argmax(free[0, 3:] == 5))
        np.testing.assert_array_equal(out[0, :first + 1],
                                      free[0, :first + 1])
        np.testing.assert_array_equal(out[0, first:],
                                      np.full(out.shape[1] - first, 5))


def test_engine_serve_ragged_matches_solo(model, key):
    """Ragged batches (left-pad + kv_start mask + shifted rope) must
    generate exactly what each prompt generates served alone."""
    params = model.init(key)
    prompts = [[5, 9, 2, 7, 1], [3, 8]]
    outs = Engine(model, batch=2, max_seq=32).serve_ragged(
        params, prompts, gen_len=6)
    for i, p in enumerate(prompts):
        solo = np.asarray(Engine(model, batch=1, max_seq=32).serve(
            params, jnp.asarray([p], jnp.int32), 6))[0]
        np.testing.assert_array_equal(np.asarray(outs[i]), solo,
                                      err_msg=f"row {i}")


def test_engine_serve_ragged_equal_lengths_degenerates(model, key):
    """Equal-length prompts through serve_ragged == plain serve."""
    params = model.init(key)
    prompts = [[1, 2, 3], [4, 5, 6]]
    outs = Engine(model, batch=2, max_seq=32).serve_ragged(
        params, prompts, gen_len=4)
    plain = np.asarray(Engine(model, batch=2, max_seq=32).serve(
        params, jnp.asarray(prompts, jnp.int32), 4))
    np.testing.assert_array_equal(np.stack(outs), plain)


def test_engine_decode_profile_hook(model, key, tmp_path):
    """The decode profile window (reference engine.py:153-179) traces the
    first N steps and leaves generation unchanged."""
    params = model.init(key)
    ids = jnp.asarray([[9, 8, 7]], jnp.int32)
    # temperature > 0 locks the RNG-stream contract: profiling must not
    # consume extra PRNG splits vs an unprofiled serve.
    plain = np.asarray(Engine(model, batch=1, max_seq=16, temperature=0.7,
                              top_k=8, seed=3).serve(params, ids, 5))
    eng = Engine(model, batch=1, max_seq=16, temperature=0.7, top_k=8,
                 seed=3, profile_dir=str(tmp_path), profile_steps=2)
    prof = np.asarray(eng.serve(params, ids, 5))
    np.testing.assert_array_equal(plain, prof)
    from triton_dist_tpu.tools.profiler import trace_files
    assert trace_files("engine_decode", str(tmp_path)), "no trace written"


def test_engine_reuse_resets_cache(model, key):
    """Two serves from the same Engine must be independent (the KV cache
    resets between calls) — a stale cache would change the second run."""
    params = model.init(key)
    ids = jnp.asarray([[1, 2, 3]], jnp.int32)
    eng = Engine(model, batch=1, max_seq=16)
    first = np.asarray(eng.serve(params, ids, 4))
    second = np.asarray(eng.serve(params, ids, 4))
    np.testing.assert_array_equal(first, second)


def test_engine_batch_row_independence(model, key):
    """Greedy generation for a row must not depend on what else is in
    the batch (attention/cache leakage across rows)."""
    params = model.init(key)
    a = jnp.asarray([[1, 2, 3], [40, 50, 60]], jnp.int32)
    b = jnp.asarray([[1, 2, 3], [7, 8, 9]], jnp.int32)
    eng = Engine(model, batch=2, max_seq=16)
    out_a = np.asarray(eng.serve(params, a, 4))
    out_b = np.asarray(eng.serve(params, b, 4))
    np.testing.assert_array_equal(out_a[0], out_b[0])


def test_engine_ragged_stop_profile_combo(model, key, tmp_path):
    """All three serve features together keep the output contract."""
    params = model.init(key)
    prompts = [[5, 9, 2], [3]]
    eng = Engine(model, batch=2, max_seq=32,
                 profile_dir=str(tmp_path), profile_steps=2)
    free = eng.serve_ragged(params, prompts, gen_len=6)
    stop_tok = int(free[0][3])
    eng2 = Engine(model, batch=2, max_seq=32,
                  profile_dir=str(tmp_path), profile_steps=2)
    outs = eng2.serve_ragged(params, prompts, gen_len=6,
                             stop_tokens=(stop_tok,))
    assert len(outs) == 2
    assert len(outs[0]) == 3 + 6 and len(outs[1]) == 1 + 6
    # row 0 froze on its stop token
    gen0 = np.asarray(outs[0][3:])
    first = int(np.argmax(gen0 == stop_tok))
    assert (gen0[first:] == stop_tok).all()
