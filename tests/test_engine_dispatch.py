"""One device program per admission (ISSUE 31).

A stream session's small state (sampling key, last token and write
offset per row) lives on the device. The admission programs take it,
seat their row in-graph and hand it back; what the driving thread adds
is NumPy values that upload with the call. Three guards:

- an AST lint: the session methods on an admission's path call nothing
  through ``jnp``, ``jax.random`` or ``.at[...]``, so an eager op (a
  device program of its own, 0.3-1 ms of dispatch each on the chip)
  cannot come back unnoticed; the decode step keeps exactly one, its
  key split, for the reason written where it stands;
- behaviour: after an admission of every kind the device's token and
  offset of the row, and the host's shadow, agree with the first token
  and the prompt length, and no other row moved;
- sampling: the key is split in the same order as before, so a seeded
  sampled run reproduces, token for token, goldens recorded from the
  parent commit (dcaf085); a greedy engine traces no split into an
  admission and gets the key back as it went in.
"""

import ast
import dataclasses
import inspect
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import DenseLLM, Engine, ModelConfig
from triton_dist_tpu.models.engine import StreamSession
from triton_dist_tpu.serving import Scheduler

# -- (a) no eager op on the pump's path ------------------------------------

#: The session methods around the one device program of an admission.
PUMP_PATH = ("launch_into_row", "prefill_into_row", "take_first_tokens",
             "_admit_whole", "_admit_paged", "_launch_admission",
             "_collect_first", "_collect_deferred", "_run_admission",
             "_padded_ids", "_prefill_slice", "_mark_admitted")


def _eager_calls(nodes) -> list:
    """``jnp.<...>(...)``, ``jax.random.<...>(...)`` and ``x.at[...]``
    anywhere under ``nodes``, as source text."""
    found = []
    for node in (n for top in nodes for n in ast.walk(top)):
        if isinstance(node, ast.Subscript) \
                and isinstance(node.value, ast.Attribute) \
                and node.value.attr == "at":
            found.append(ast.unparse(node))
        if isinstance(node, ast.Call):
            name = ast.unparse(node.func)
            if name.startswith(("jnp.", "jax.random.", "jax.numpy.")):
                found.append(name)
    return found


def _body(method: str) -> list:
    src = textwrap.dedent(inspect.getsource(getattr(StreamSession, method)))
    return ast.parse(src).body[0].body


@pytest.mark.parametrize("method", PUMP_PATH)
def test_no_eager_jax_op_on_the_pumps_path(method):
    assert _eager_calls(_body(method)) == []


def test_the_decode_step_keeps_one_eager_op_and_only_that():
    """``_base_step`` still splits its key on the host (the benchmark's
    clock check needs the launch that long; the comment there says
    why). Its ``done`` vector is NumPy and nothing else is eager."""
    assert _eager_calls(_body("_base_step")) == ["jax.random.split"]


def test_adopt_rows_tail_seats_the_row_without_an_eager_scatter():
    """``_adopt_row`` uploads the shipped blocks inside its rollback
    window (eager writes, its own business); what follows the window
    seats the row like an admission program does, in-graph."""
    body = _body("_adopt_row")
    window = next(i for i, n in enumerate(body) if isinstance(n, ast.Try))
    tail = body[window + 1:]
    assert tail and _eager_calls(tail) == []
    assert any("_seat" in ast.unparse(n) for n in tail)


def test_the_lint_sees_what_it_guards_against():
    planted = ast.parse(textwrap.dedent("""
        def f(self):
            self.key, sub = jax.random.split(self.key)
            ids = jnp.asarray([1, 2], jnp.int32)
            self.token = self.token.at[0].set(1)
    """)).body[0].body
    assert _eager_calls(planted) == [
        "jax.random.split", "jnp.asarray", "self.token.at[0]"]


# -- (b) an admission seats its row, and only its row ----------------------

def _model(mesh, key, heads, kv_heads, head_dim, **kw):
    cfg = ModelConfig(hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=heads,
                      num_key_value_heads=kv_heads, head_dim=head_dim,
                      vocab_size=64, max_position_embeddings=64,
                      dtype=jnp.float32)
    model = DenseLLM(cfg, mesh=mesh, axis="tp", impl="xla", **kw)
    return model, model.init(key)


def _dense_engine(mesh8, key, **kw):
    model, params = _model(mesh8, key, 8, 8, 4)
    return Engine(model, batch=3, max_seq=64, prefill_mode="xla_ar",
                  decode_mode="gemm_ar", **kw), params


def _paged_engine(mesh8, key):
    mesh = Mesh(np.array(list(mesh8.devices.flat)).reshape(1, 8),
                ("tp", "sp"))
    model, params = _model(mesh, key, 4, 2, 16, sp_axis="sp",
                           fwd_mode="sp")
    return Engine(model, batch=3, max_seq=64, prefill_mode="sp",
                  decode_mode="sp", paged=True, page_size=4), params


def _state(sess):
    return (np.asarray(sess.token).copy(), np.asarray(sess.offsets).copy(),
            list(sess._host_off))


PROMPT = [5, 6, 5, 6, 5, 6, 5, 6, 5, 6, 5, 6, 5, 6]


@pytest.mark.parametrize("case",
                         ["whole", "paged", "paged_prefix", "chunked"])
def test_admission_seats_its_row_and_no_other(mesh8, key, case):
    paged = case.startswith("paged")
    eng, params = (_paged_engine if paged else _dense_engine)(mesh8, key)
    sess = eng.stream_session(params)
    # A neighbour that decodes one step, so "untouched" is not "zero".
    sess.prefill_into_row(1, PROMPT[:5], gen_budget=8)
    sess.decode_burst()
    prompt, row = PROMPT, 0
    if case == "paged_prefix":
        sess.prefill_into_row(0, PROMPT, gen_budget=8)
        sess.retire_row(0)
        prompt, row = PROMPT[:12] + [9, 3], 2   # three cached pages
    tok0, off0, host0 = _state(sess)
    assert off0[1] == host0[1] == 6
    first = sess.prefill_into_row(
        row, prompt, gen_budget=8, chunk=4 if case == "chunked" else None)
    if case == "chunked":
        assert first is None
        while first is None:
            # Mid-admission nothing of the session's state has moved.
            for was, now in zip((tok0, off0, host0), _state(sess)):
                np.testing.assert_array_equal(was, now)
            first = sess.prefill_step(row)
    if case == "paged_prefix":
        assert sess.admit_info["cached"] == 12 \
            and eng._admit_prefix is not None
    tok, off, host = _state(sess)
    assert tok[row] == first
    assert off[row] == host[row] == len(prompt)
    others = [r for r in range(sess.batch) if r != row]
    np.testing.assert_array_equal(tok[others], tok0[others])
    np.testing.assert_array_equal(off[others], off0[others])
    assert [host[r] for r in others] == [host0[r] for r in others]
    # And the rows decode on from there: one step, one position each.
    burst = sess.decode_burst()
    tok2, off2, host2 = _state(sess)
    for r in (1, row):
        assert burst[r] == [tok2[r]] and off2[r] == host2[r] == off[r] + 1
    sess.close()


# -- (c) the sampling key ---------------------------------------------------

PROMPTS = [[1, 2, 3, 4, 5], [9, 8, 7], [11, 12, 13, 14, 15, 16, 17, 18, 19]]
#: (model, driver, temperature) -> 6 tokens for each of PROMPTS, with
#: ``Engine(batch=2, temperature=..., top_k=20, seed=7)``. The sampled
#: dense rows of "scheduler" and "serve_stream" were recorded from
#: dcaf085 (key split eagerly on the host before every admission and
#: every step), all of them again from aac321c, the parent of ISSUE 40
#: (an admission's first token read before the step is launched): the
#: programs, their order and so the key's draws are the same when the
#: step is launched behind the admissions and the first tokens are read
#: after it. "scheduler": one request at a time. "scheduler_batch": the
#: three enqueued at once, so the first turn launches two admissions
#: and the step before it reads anything, and the third is admitted
#: mid-decode. "exaone": the counting model (its counts ride home
#: behind the first token), window layers and held experts.
GOLDEN = {
    ("dense", "scheduler", 0.8):
        [[15, 41, 50, 4, 63, 56],
         [6, 51, 13, 15, 45, 6],
         [37, 38, 45, 0, 5, 45]],
    ("dense", "scheduler_batch", 0.8):
        [[15, 16, 4, 63, 56, 6],
         [62, 61, 44, 10, 59, 63],
         [26, 13, 33, 59, 26, 33]],
    ("dense", "serve_stream", 0.8):
        [[15, 16, 4, 63, 56, 6],
         [62, 61, 44, 10, 59, 63],
         [26, 13, 33, 59, 26, 33]],
    ("dense", "scheduler", 0.0):
        [[23, 50, 21, 17, 63, 42],
         [63, 20, 17, 63, 56, 14],
         [12, 10, 40, 3, 12, 53]],
    ("dense", "scheduler_batch", 0.0):
        [[23, 50, 21, 17, 63, 42],
         [63, 20, 17, 63, 56, 14],
         [12, 10, 40, 3, 12, 53]],
    ("dense", "serve_stream", 0.0):
        [[23, 50, 21, 17, 63, 42],
         [63, 20, 17, 63, 56, 14],
         [12, 10, 40, 3, 12, 53]],
    ("exaone", "scheduler", 0.8):
        [[21, 34, 25, 4, 21, 14],
         [59, 61, 30, 19, 59, 40],
         [27, 50, 7, 1, 29, 20]],
    ("exaone", "scheduler_batch", 0.8):
        [[21, 50, 15, 21, 14, 6],
         [5, 61, 12, 46, 59, 3],
         [22, 57, 35, 22, 15, 37]],
    ("exaone", "serve_stream", 0.8):
        [[21, 50, 15, 21, 14, 6],
         [5, 61, 12, 46, 59, 3],
         [22, 57, 35, 22, 15, 37]],
    ("exaone", "scheduler", 0.0):
        [[37, 13, 34, 19, 62, 43],
         [61, 24, 19, 16, 5, 59],
         [0, 1, 59, 48, 48, 60]],
    ("exaone", "scheduler_batch", 0.0):
        [[37, 13, 34, 19, 62, 43],
         [61, 24, 19, 16, 5, 59],
         [0, 1, 59, 48, 48, 60]],
    ("exaone", "serve_stream", 0.0):
        [[37, 13, 34, 19, 62, 43],
         [61, 24, 19, 16, 5, 59],
         [0, 1, 59, 48, 48, 60]],
}


def _exaone():
    """A small K-EXAONE share (tests/test_exaone_moe.py has the full
    preset): dense, then S S F, 16 experts top-2 of which 4 are held."""
    from benchmark.harness.builders import exaone as builder
    from triton_dist_tpu.models import AutoLLM
    hf = dict(
        hidden_size=64, intermediate_size=128, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        vocab_size=64, max_position_embeddings=4096, rms_norm_eps=1e-5,
        rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
        sliding_window=8, model_type="exaone_moe",
        layer_types=["sliding_attention"] * 3 + ["full_attention"],
        mlp_layer_types=["dense"] + ["sparse"] * 3, first_k_dense_replace=1,
        num_experts=16, num_experts_per_tok=2, moe_intermediate_size=32,
        num_shared_experts=1, scoring_func="sigmoid", norm_topk_prob=True,
        routed_scaling_factor=2.5, expert_parallel={"world": 4, "rank": 0})
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    cfg = dataclasses.replace(ModelConfig.from_hf_config(hf),
                              dtype=jnp.float32)
    llm = AutoLLM.build(cfg, mesh=mesh, axis="tp", impl="xla")
    ref = dict(hf, expert_parallel=(4, 0), rope_theta=1e6,
               balance_shape=(4, 64))
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          builder.make_params(ref, mesh, 11))
    return llm, llm.shard_params(params)


@pytest.mark.parametrize(
    "model,driver,temperature", sorted(GOLDEN),
    ids=["-".join(map(str, k)) for k in sorted(GOLDEN)])
def test_sampled_run_reproduces_the_parents_tokens(mesh8, key, model,
                                                   driver, temperature):
    llm, params = (_exaone() if model == "exaone"
                   else _model(mesh8, key, 8, 8, 4))
    eng = Engine(llm, batch=2, max_seq=64, prefill_mode="xla_ar",
                 decode_mode="gemm_ar", temperature=temperature, top_k=20,
                 seed=7)
    if driver == "serve_stream":
        # Three prompts through two rows: an admission mid-decode.
        out = [o[len(p):] for o, p in zip(
            eng.serve_stream(params, PROMPTS, 6, stop_tokens=()), PROMPTS)]
    else:
        sched = Scheduler(eng, params).start()
        try:
            if driver == "scheduler":
                # One request at a time: the order of admissions and
                # steps, and so of the key's splits, is then the same
                # in every run.
                out = [sched.submit(p, 6).result(timeout=300)
                       for p in PROMPTS]
            else:
                # One atomic enqueue: the same order in every run too.
                out = [r.result(timeout=300)
                       for r in sched.submit_many(PROMPTS, 6)]
        finally:
            sched.stop()
    assert [[int(t) for t in o] for o in out] == GOLDEN[
        model, driver, temperature]


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_only_a_sampling_engine_splits_the_key_in_an_admission(
        mesh8, key, temperature):
    eng, params = _dense_engine(mesh8, key, temperature=temperature, seed=3)
    sess = eng.stream_session(params)
    key0 = np.asarray(eng.key).copy()
    sess.prefill_into_row(0, PROMPT, gen_budget=4)
    key1 = np.asarray(eng.key).copy()
    assert isinstance(eng.key, jax.Array) and eng.key.shape == (2,)
    # The eager sequence it replaces: key, sub = split(key).
    split = np.asarray(jax.random.split(jnp.asarray(key0))[0])
    np.testing.assert_array_equal(
        key1, key0 if temperature == 0.0 else split)
    admit = eng._admit.lower(
        params, sess.caches, sess._padded_ids(PROMPT, 16), np.int32(14),
        np.int32(1), sess.token, sess.offsets, eng.key).as_text()
    assert ("threefry" in admit) == (temperature > 0.0)
    # The step draws from the same sequence (on the host, for now).
    sess.decode_burst()
    np.testing.assert_array_equal(
        np.asarray(eng.key),
        np.asarray(jax.random.split(jnp.asarray(key1))[0]))
    sess.close()


# -- (d) one decode program, whatever window its attention reads -----------

def test_one_step_program_serves_every_window(key):
    """The stream step picks its attention window in the graph (ISSUE
    33): steps whose live rows need one chunk and steps that need the
    whole cache are the same compiled program, one launch each."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    cfg = ModelConfig(hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=8, vocab_size=64,
                      max_position_embeddings=1024, dtype=jnp.float32)
    model = DenseLLM(cfg, mesh=mesh, axis="tp", impl="xla")
    eng = Engine(model, batch=2, max_seq=1024, prefill_mode="xla_ar",
                 decode_mode="gemm_ar")
    sess = eng.stream_session(model.init(key))
    sess.prefill_into_row(0, list(range(1, 63)) * 8 + [5] * 14,
                          gen_budget=8)          # 510 tokens
    step, launches = eng._stream_step, []

    def counting(*args):
        launches.append(int(np.asarray(args[3]).max()))   # offsets
        return step(*args)

    eng._stream_step = counting
    try:
        for _ in range(4):
            sess.decode_step()
    finally:
        eng._stream_step = step
    sess.close()
    # 510, 511 read one chunk; 512, 513 the whole cache: four launches
    # of one executable.
    assert launches == [510, 511, 512, 513]
    assert step._cache_size() == 1
