"""A turn launches before it reads (ISSUE 40).

An admission is launched (``StreamSession._launch_admission``) and
collected (``_collect_first``) in two moments. The scheduler's turn
launches every admission it was given, then the shared step behind
them, and only then reads the admissions' first tokens, each stamped as
it reaches the host, and the step's tokens after them. Which admissions
are deferred the session decides from what it is: a whole-bucket
admission of a non-paged session without a drafter whose request may
generate more than one token. Everything else reads at once, as before.

Checked here, on the CPU and without timing anything: the ORDER of
dispatches and host reads (counted on wrapped programs), what
``Request.t_first`` is, a first token that ends its request, which
sessions defer (``engine.admit_deferred``), and the two kinds of
admission failure. That the tokens are the parent's is
``tests/test_engine_dispatch.py::test_run_reproduces_the_parents_tokens``.
"""

import time

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu import obs
from triton_dist_tpu.models import DenseLLM, Engine, ModelConfig
from triton_dist_tpu.models.engine import DEFERRED, StreamSession
from triton_dist_tpu.models.kv_cache import KVCacheLost
from triton_dist_tpu.models.spec import SpecConfig
from triton_dist_tpu.serving import Scheduler

PROMPTS = [[1, 2, 3, 4, 5], [9, 8, 7], [11, 12, 13, 14, 15, 16, 17, 18, 19]]
#: Greedy tokens of ``tiny`` for PROMPTS, recorded from the parent commit
#: (aac321c), as in tests/test_engine_dispatch.py.
GREEDY = [[23, 50, 21, 17, 63, 42], [63, 20, 17, 63, 56, 14],
          [12, 10, 40, 3, 12, 53]]


@pytest.fixture(autouse=True)
def _telemetry_off():
    obs.disable()
    yield
    obs.disable()


@pytest.fixture()
def tiny(mesh8, key):
    cfg = ModelConfig(hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=8,
                      num_key_value_heads=8, head_dim=4, vocab_size=64,
                      max_position_embeddings=64, dtype=jnp.float32)
    model = DenseLLM(cfg, mesh=mesh8, axis="tp", impl="xla")
    return model, model.init(key)


@pytest.fixture()
def paged_tiny(mesh8, key):
    mesh = Mesh(np.array(list(mesh8.devices.flat)).reshape(1, 8),
                ("tp", "sp"))
    cfg = ModelConfig(hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=16, vocab_size=64,
                      max_position_embeddings=64, dtype=jnp.float32)
    model = DenseLLM(cfg, mesh=mesh, axis="tp", sp_axis="sp", impl="xla",
                     fwd_mode="sp")
    return model, model.init(key)


def _engine(model, batch=2, **kw):
    return Engine(model, batch=batch, max_seq=64, prefill_mode="xla_ar",
                  decode_mode="gemm_ar", **kw)


def _paged(model, batch=2):
    return Engine(model, batch=batch, max_seq=64, prefill_mode="sp",
                  decode_mode="sp", paged=True, page_size=4)


class _Unread:
    """A first token that says when the host reads it (``np.asarray``
    is the one read ``_collect_first`` makes)."""

    def __init__(self, value, on_read):
        self.value, self.on_read = value, on_read

    def __array__(self, dtype=None, copy=None):
        self.on_read()
        return np.asarray(self.value)


def _logging(eng, log, on_read=None):
    """Wrap the engine's admission and step programs: every dispatch
    and every read of a first token goes into ``log``."""
    admit, step = eng._admit, eng._stream_step

    def logged_admit(*args):
        log.append("admit")
        first, *state = admit(*args)
        return (_Unread(first, on_read or (lambda: log.append("read"))),
                *state)

    def logged_step(*args):
        log.append("step")
        return step(*args)

    eng._admit, eng._stream_step = logged_admit, logged_step


def _counters(reg):
    return reg.snapshot()["counters"]


# -- the order of a turn ---------------------------------------------------

def test_both_admissions_and_the_step_are_dispatched_before_any_read(tiny):
    model, params = tiny
    eng = _engine(model)
    eng.stream_session(params)          # builds the programs to wrap
    log = []
    _logging(eng, log)
    reg = obs.enable(obs.Registry())
    sched = Scheduler(eng, params).start()
    try:
        # One atomic enqueue: one turn is given both admissions.
        got = [r.result(timeout=180)
               for r in sched.submit_many(PROMPTS[:2], 6)]
    finally:
        sched.stop()
    assert log[:5] == ["admit", "admit", "step", "read", "read"]
    assert log[5:] == ["step"] * 4      # five steps make tokens 2..6
    assert got == GREEDY[:2]
    c = _counters(reg)
    assert c["engine.admit_deferred"] == c["engine.stream_admissions"] == 2


def test_t_first_is_the_reads_instant_inside_the_step(tiny, monkeypatch):
    """``req.t_first`` is the session's stamp (taken while the step
    runs), not the instant the pump gets round to recording it: it lies
    after the step's dispatch and before the step's tokens are back."""
    model, params = tiny
    eng = _engine(model)
    eng.stream_session(params)
    marks = {}
    _logging(eng, [], on_read=lambda: marks.setdefault(
        "read", time.perf_counter()))
    burst, taken = StreamSession.decode_burst, StreamSession.take_first_tokens

    def timed_burst(self):
        marks.setdefault("burst_called", time.perf_counter())
        out = burst(self)
        marks.setdefault("burst_returned", time.perf_counter())
        return out

    def kept_take(self):
        out = taken(self)
        marks.setdefault("stamps", out)
        return out

    monkeypatch.setattr(StreamSession, "decode_burst", timed_burst)
    monkeypatch.setattr(StreamSession, "take_first_tokens", kept_take)
    sched = Scheduler(eng, params).start()
    try:
        req = sched.submit(PROMPTS[0], 3)
        assert req.result(timeout=180) == GREEDY[0][:3]
    finally:
        sched.stop()
    (row, tok, stamp), = marks["stamps"]
    assert (row, tok) == (0, GREEDY[0][0]) and req.t_first == stamp
    assert req.t_admit < marks["burst_called"] < marks["read"] \
        <= req.t_first < marks["burst_returned"]
    assert req.timing["segments"]["prefill_ms"] == pytest.approx(
        (req.t_first - req.t_admit) * 1e3, abs=1e-2)


def test_a_first_token_that_stops_retires_the_row_and_drops_the_steps_token(
        tiny):
    model, params = tiny
    reg = obs.enable(obs.Registry())
    sched = Scheduler(_engine(model), params).start()
    try:
        first = GREEDY[0][0]
        stopped = sched.submit(PROMPTS[0], 6, stop_tokens=[first])
        assert stopped.result(timeout=180) == [first]
        c = _counters(reg)
        # The step was in flight behind the admission when the token
        # was read: it ran (one live row) and its token went nowhere.
        assert c["engine.admit_deferred"] == 1
        assert c["engine.decode_path.plain"] == 1
        assert c["serving.retired"] == 1
        # The lane is free, and the next occupant decodes from its own
        # seat, not from what that step left behind.
        assert sched.submit(PROMPTS[1], 6).result(timeout=180) == GREEDY[1]
    finally:
        sched.stop()


# -- which admissions are deferred -----------------------------------------

@pytest.mark.parametrize("case", [
    "whole", "one_token", "chunked", "speculative", "paged"])
def test_only_a_whole_unpaged_undrafted_longer_request_is_deferred(
        tiny, paged_tiny, case):
    model, params = paged_tiny if case == "paged" else tiny
    kw, gen = {}, 4
    if case == "paged":
        eng = _paged(model)
    else:
        eng = _engine(model, **(
            {"spec": SpecConfig(k=2)} if case == "speculative" else {}))
    if case == "chunked":
        kw["prefill_chunk"] = 4
    if case == "one_token":
        gen = 1
    reg = obs.enable(obs.Registry())
    sched = Scheduler(eng, params, **kw).start()
    try:
        # (both longer than the chunk: a prompt that fits one slice is
        # admitted whole)
        for p in PROMPTS[::2]:
            assert len(sched.submit(p, gen, stop_tokens=[])
                       .result(timeout=180)) == gen
    finally:
        sched.stop()
    c = _counters(reg)
    assert c["engine.stream_admissions"] == 2
    assert c.get("engine.admit_deferred", 0) == (2 if case == "whole" else 0)


def test_the_sessions_verbs(tiny):
    """``launch_into_row`` hands out ``DEFERRED`` and the next burst
    reads the token; ``prefill_into_row`` is the same launch read at
    once, counts no deferral, and serves the same token."""
    model, params = tiny
    reg = obs.enable(obs.Registry())
    sess = _engine(model).stream_session(params)
    assert sess.launch_into_row(0, PROMPTS[0], gen_budget=6) is DEFERRED
    assert sess.live[0] and sess.take_first_tokens() == []
    assert sess.launch_into_row(1, PROMPTS[1], gen_budget=1) == GREEDY[1][0]
    t0 = time.perf_counter()
    burst = sess.decode_burst()
    (row, tok, stamp), = sess.take_first_tokens()
    assert (row, tok) == (0, GREEDY[0][0]) and t0 < stamp < time.perf_counter()
    assert burst[0] == [GREEDY[0][1]] and sess.take_first_tokens() == []
    assert _counters(reg)["engine.admit_deferred"] == 1
    sess.retire_row(1)
    assert sess.prefill_into_row(1, PROMPTS[2], gen_budget=6) == GREEDY[2][0]
    assert sess.take_first_tokens() == [] and not sess._deferred
    assert _counters(reg)["engine.admit_deferred"] == 1
    assert _counters(reg)["engine.stream_admissions"] == 3
    sess.close()


# -- the two kinds of failure ----------------------------------------------

def _failing(eng, on_call: int, where: str):
    """The ``on_call``-th admission program raises at its call, or
    hands back a first token that raises when read."""
    admit, calls = eng._admit, {"n": 0}

    def boom():
        raise RuntimeError("injected")

    def flaky(*args):
        calls["n"] += 1
        if calls["n"] != on_call:
            return admit(*args)
        if where == "call":
            boom()
        first, *state = admit(*args)
        return (_Unread(first, boom), *state)

    eng._admit = flaky


def test_a_program_that_raises_at_its_call_fails_one_request(tiny):
    model, params = tiny
    eng = _engine(model, batch=3)
    eng.stream_session(params)
    _failing(eng, on_call=2, where="call")
    reg = obs.enable(obs.Registry())
    sched = Scheduler(eng, params).start()
    try:
        # One turn: the first admission is launched and unread when the
        # second one's call raises; it and the third are served.
        reqs = sched.submit_many(PROMPTS, 6)
        with pytest.raises(RuntimeError, match="^injected$"):
            reqs[1].result(timeout=180)
        assert reqs[0].result(timeout=180) == GREEDY[0]
        assert reqs[2].result(timeout=180) == GREEDY[2]
    finally:
        sched.stop()
    c = _counters(reg)
    assert c["serving.admit_errors"] == 1
    assert c.get("serving.pump_errors", 0) == 0
    assert c["engine.admit_deferred"] == 2


def test_a_program_that_raises_at_the_deferred_read_restarts_the_session(
        tiny):
    model, params = tiny
    eng = _engine(model, batch=3)
    eng.stream_session(params)
    _failing(eng, on_call=3, where="read")
    reg = obs.enable(obs.Registry())
    sched = Scheduler(eng, params).start()
    try:
        assert sched.submit(PROMPTS[0], 6).result(timeout=180) == GREEDY[0]
        # One turn admits both; the second one's program dies on the
        # device: its first token says so behind the step, and the
        # just-admitted neighbour goes with the caches.
        reqs = sched.submit_many(PROMPTS[1:], 6)
        for r in reqs:
            with pytest.raises(KVCacheLost,
                               match="KV cache was lost.*injected"):
                r.result(timeout=180)
        c = _counters(reg)
        assert c["serving.pump_errors"] == 1
        assert c.get("serving.admit_errors", 0) == 0
        # A fresh session serves on.
        assert sched.submit(PROMPTS[1], 6).result(timeout=180) == GREEDY[1]
    finally:
        sched.stop()


@pytest.mark.parametrize("verb", ["prefill_into_row", "launch_into_row"])
def test_a_failed_read_is_a_lost_cache_whichever_verb_reads_it(tiny, verb):
    model, params = tiny
    eng = _engine(model)
    sess = eng.stream_session(params)
    _failing(eng, on_call=1, where="read")
    with pytest.raises(KVCacheLost, match="injected") as err:
        if verb == "prefill_into_row":
            sess.prefill_into_row(0, PROMPTS[0], gen_budget=6)
        else:
            assert sess.launch_into_row(
                0, PROMPTS[0], gen_budget=6) is DEFERRED
            sess.decode_burst()
    assert isinstance(err.value.__cause__, RuntimeError)
    # A call that raises consumed nothing: the session lives on.
    sess = eng.stream_session(params)
    _failing(eng, on_call=1, where="call")
    with pytest.raises(RuntimeError, match="^injected$"):
        getattr(sess, verb)(0, PROMPTS[0], gen_budget=6)
    assert not sess.live[0] and not sess._deferred
    assert sess.prefill_into_row(
        0, PROMPTS[0], gen_budget=6) == GREEDY[0][0]
    sess.close()
