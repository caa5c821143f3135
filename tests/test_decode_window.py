"""The stream decode step's attention reads the cache only as far as its
longest LIVE row reaches (ISSUE 33).

``_attention_core(kv_need=)`` loops over the leading 512-position chunks
that cover ``kv_need`` (the whole-cache read past half of the cache);
the stream step passes ``max over live rows of offset + 1``. Guards:

- the bounded read equals the unbounded one on every live row, on both
  sides of a chunk boundary, for one position and for a burst, with a
  frozen row whose stale offset lies beyond the window;
- a session whose row crosses position 512 mid-answer emits the tokens
  of the solo ``Engine.serve`` reference (which passes no ``kv_need``);
- the host's and the program's window arithmetic agree on every need;
- ``engine.decode_window_positions`` advances by the window read.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu import obs
from triton_dist_tpu.layers.tp_attn import (
    _WINDOW_CHUNK, _attention_core, decode_window, window_chunks)
from triton_dist_tpu.models import DenseLLM, Engine, ModelConfig

HKV, G, D = 2, 2, 8


def _core_inputs(t, s, offsets, dtype, seed=0):
    rng = np.random.RandomState(seed)
    b = len(offsets)

    def arr(*shape):
        return jnp.asarray(rng.randn(*shape), dtype)

    # The whole cache holds values (stale lanes, positions past every
    # offset): anything the mask should hide would show if it leaked.
    return (arr(b, s, HKV * G, D), arr(b, s, HKV, D), arr(b, s, HKV, D),
            arr(b, t, HKV, D), arr(b, t, HKV, D),
            jnp.asarray(offsets, jnp.int32), jnp.zeros((b,), jnp.int32))


# (T, S, offsets, live rows): the last row of each case is FROZEN at a
# stale offset beyond the window the live rows pick.
CORE_CASES = {
    "inside_one_chunk": (1024, 1, [100, 400, 900], [0, 1]),
    "ends_on_the_chunk": (1024, 1, [0, 511, 900], [0, 1]),
    "one_past_the_chunk": (1024, 1, [0, 512, 900], [0, 1]),
    "past_half_reads_all": (1024, 1, [100, 700, 1000], [0, 1]),
    "burst_inside": (1024, 3, [100, 400, 900], [0, 1]),
    "burst_ends_on_the_chunk": (1024, 3, [7, 509, 900], [0, 1]),
    "burst_crosses_the_chunk": (1024, 3, [7, 510, 900], [0, 1]),
    "two_chunks": (2048, 1, [30, 700, 1800], [0, 1]),
    "two_chunks_burst": (2048, 3, [1021, 5, 2000], [0, 1]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(CORE_CASES))
def test_bounded_read_equals_the_unbounded_on_live_rows(case, dtype):
    t, s, offsets, live = CORE_CASES[case]
    args = _core_inputs(t, s, offsets, dtype)
    need = max(offsets[r] for r in live) + s
    want, wk, wv = _attention_core(*args, groups=G)
    got, gk, gv = jax.jit(
        lambda need: _attention_core(*args, need, groups=G))(
            jnp.int32(need))
    # The write is the unbounded call's, frozen row included.
    np.testing.assert_array_equal(np.asarray(gk, np.float32),
                                  np.asarray(wk, np.float32))
    np.testing.assert_array_equal(np.asarray(gv, np.float32),
                                  np.asarray(wv, np.float32))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32)[live],
                               np.asarray(want, np.float32)[live],
                               rtol=tol, atol=tol)
    # The frozen row reads finite garbage; its caller discards it.
    assert np.isfinite(np.asarray(got, np.float32)).all()
    bounded = 0 < 2 * decode_window(need, t) <= t
    assert bounded == (case not in ("one_past_the_chunk",
                                    "past_half_reads_all",
                                    "burst_crosses_the_chunk"))


def test_a_left_padded_row_keeps_its_mask_inside_the_window():
    args = list(_core_inputs(1024, 1, [300, 200], jnp.float32))
    args[6] = jnp.asarray([40, 0], jnp.int32)           # kv_start
    want, _, _ = _attention_core(*args, groups=G)
    got, _, _ = _attention_core(*args, jnp.int32(301), groups=G)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("t", [64, 512, 1000, 1024, 1536, 4096])
def test_host_and_traced_window_agree_on_every_need(t):
    needs = np.arange(1, t + 1)
    traced = np.asarray(jax.jit(jax.vmap(
        lambda n: window_chunks(n, t)))(jnp.asarray(needs, jnp.int32)))
    host = np.asarray([window_chunks(int(n), t) for n in needs])
    np.testing.assert_array_equal(traced, host)
    windows = np.asarray([decode_window(int(n), t) for n in needs])
    # A window covers its need, is a whole number of chunks up to half
    # of the cache and the cache itself beyond; never between.
    assert (windows >= needs).all()
    assert (windows[host > 0] == host[host > 0] * _WINDOW_CHUNK).all()
    assert (windows[host > 0] * 2 <= t).all()
    assert (windows[host == 0] == t).all()
    assert (windows - needs < _WINDOW_CHUNK)[host > 0].all()
    if t < 2 * _WINDOW_CHUNK:
        assert not host.any()      # one program: the unbounded one


def test_a_short_cache_traces_the_unbounded_program():
    """``max_seq < 1024``: ``kv_need`` changes nothing in the trace."""
    args = _core_inputs(512, 1, [3, 100], jnp.float32)
    plain = jax.make_jaxpr(
        lambda need: _attention_core(*args, groups=G))(jnp.int32(101))
    bounded = jax.make_jaxpr(
        lambda need: _attention_core(*args, need, groups=G))(jnp.int32(101))
    assert "while" not in str(bounded) and "cond" not in str(bounded)
    assert str(bounded) == str(plain)
    # ... and a long one holds the loop and the whole read, once each.
    args = _core_inputs(1024, 1, [3, 100], jnp.float32)
    text = str(jax.make_jaxpr(
        lambda need: _attention_core(*args, need, groups=G))(jnp.int32(101)))
    assert text.count("while[") == 2 and text.count("cond[") == 1


# -- the session ------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    """One layer, one device, ``max_seq=1024``: two windows (512, and
    the whole cache past half)."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    cfg = ModelConfig(hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=8, vocab_size=64,
                      max_position_embeddings=1024, dtype=jnp.float32)
    model = DenseLLM(cfg, mesh=mesh, axis="tp", impl="xla")
    return model, model.init(jax.random.PRNGKey(0))


def _engine(model, batch):
    return Engine(model, batch=batch, max_seq=1024, prefill_mode="xla_ar",
                  decode_mode="gemm_ar")


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(1, 64, size=n).tolist()


def test_a_row_crossing_the_chunk_emits_the_solo_tokens(tiny):
    """Row 0 starts at 505 and crosses 512 mid-answer (bounded read,
    then the whole one); row 1 stays short beside it; a retired long
    row's stale offset does not pick the window of the rows left."""
    model, params = tiny
    prompts = [_prompt(505, 1), _prompt(9, 2), _prompt(700, 3)]
    gens = [14, 20, 3]
    want = []
    for p, g in zip(prompts, gens):
        out = _engine(model, 1).serve(params, jnp.asarray([p], jnp.int32), g)
        want.append(np.asarray(out)[0, len(p):].tolist())

    sess = _engine(model, 3).stream_session(params)
    got = [[sess.prefill_into_row(r, p, gen_budget=g)]
           for r, (p, g) in enumerate(zip(prompts, gens))]
    left = [g - 1 for g in gens]
    while any(left):
        tokens = sess.decode_step()
        for r in range(3):
            if left[r]:
                got[r].append(int(tokens[r]))
                left[r] -= 1
                if not left[r]:
                    sess.retire_row(r)
    sess.close()
    assert got == want


def test_the_window_counter_advances_by_what_the_step_reads(tiny):
    model, params = tiny
    obs.enable()
    try:
        sess = _engine(model, 2).stream_session(params)

        def step():
            before = obs.snapshot()["counters"].get(
                "engine.decode_window_positions", 0)
            sess.decode_step()
            return obs.snapshot()["counters"][
                "engine.decode_window_positions"] - before

        sess.prefill_into_row(0, _prompt(510, 4), gen_budget=8)
        # offsets 510 and 511 need 511 and 512 positions: one chunk.
        assert [step(), step()] == [512, 512]
        # offset 512 needs 513: past half of 1024, the whole cache.
        assert step() == 1024
        sess.prefill_into_row(1, _prompt(5, 5), gen_budget=8)
        assert step() == 1024          # the longest live row decides
        sess.retire_row(0)
        assert step() == 512           # ... and a dead one does not
        sess.retire_row(1)
        sess.close()
    finally:
        obs.disable()
