"""Spans and counters on the pump's path (ISSUE 27).

The scheduler's pump thread opens ``serving.pump_iteration`` around
each turn of engine work and ``serving.pump_wait`` around its idle
wait; the stream session opens ``engine.stream_admission`` around each
admission call and counts the work where it happens
(``engine.admit_prompt_tokens`` / ``engine.admit_bucket_tokens`` /
``engine.decode_live_rows``). Checked here on the flight recorder's
ring (every ``obs.span`` leaves a begin/end pair there) and on the
registry, for known prompts on the CPU.
"""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu import obs
from triton_dist_tpu.models import DenseLLM, Engine, ModelConfig
from triton_dist_tpu.obs import registry as obs_registry
from triton_dist_tpu.obs import trace
from triton_dist_tpu.serving import Scheduler

TURN, WAIT = "serving.pump_iteration", "serving.pump_wait"
ADMISSION, STEP = "engine.stream_admission", "engine.stream_step"
OURS = (TURN, WAIT, ADMISSION, STEP)


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
    from jax.sharding import Mesh
    devs = [d for d in mesh8.devices.flat]
    mesh = Mesh(np.array(devs).reshape(1, 8), ("tp", "sp"))
    cfg = ModelConfig(hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=16, vocab_size=64,
                      max_position_embeddings=64, dtype=jnp.float32)
    model = DenseLLM(cfg, mesh=mesh, axis="tp", sp_axis="sp",
                     impl="xla", fwd_mode="sp")
    return model, model.init(key)


def _engine(model, **kw):
    return Engine(model, batch=2, max_seq=64, prefill_mode="xla_ar",
                  decode_mode="gemm_ar", **kw)


def _serve(engine, params, prompts, gen_lens, trace_ids=None, **sched_kw):
    """Serve the prompts through one scheduler with telemetry and the
    ring on, from an idle pump to an idle pump; returns (counters,
    histograms, the pump thread's events)."""
    obs.enable(obs.Registry())
    trace.enable()
    sched = Scheduler(engine, params, **sched_kw).start()
    try:
        _until_idle(after_work=False)
        reqs = [sched.submit(p, g, stop_tokens=[],
                             trace_id=(trace_ids or {}).get(i))
                for i, (p, g) in enumerate(zip(prompts, gen_lens))]
        for r, g in zip(reqs, gen_lens):
            assert len(r.result(timeout=180)) == g
        # the pump goes back to its wait once the last row retired
        _until_idle(after_work=True)
    finally:
        sched.stop()
    snap = obs.snapshot()
    return snap["counters"], snap["histograms"], _pump_events()


def _pump_events():
    tracks = trace.collect()["tracks"]
    mine = [evs for name, evs in tracks.items()
            if name.startswith("tdt-scheduler")]
    assert len(mine) <= 1, sorted(tracks)
    # (ph, ts_us, dur_us, name, cat, trace_id, args)
    return [e for e in (mine[0] if mine else []) if e[3] in OURS]


def _until_idle(after_work: bool) -> None:
    """Block until the pump's newest event is the begin of a wait."""
    t0 = time.monotonic()
    while True:
        events = _pump_events()
        if events and (events[-1][0], events[-1][3]) == ("B", WAIT) \
                and (not after_work or any(e[3] == TURN for e in events)):
            return
        assert time.monotonic() - t0 < 60, "the pump never idled"
        time.sleep(0.01)


def _regions(events):
    """B/E pairs as ``(name, begin event, depth, parent name, children
    names)`` in begin order; asserts the pairs nest properly."""
    out, stack = [], []
    for e in events:
        if e[0] == "B":
            rec = {"name": e[3], "ev": e, "children": [],
                   "parent": stack[-1]["name"] if stack else None}
            if stack:
                stack[-1]["children"].append(e[3])
            stack.append(rec)
            out.append(rec)
        elif e[0] == "E":
            assert stack and stack[-1]["name"] == e[3], \
                f"end of {e[3]} inside {stack[-1]['name'] if stack else 0}"
            stack.pop()["closed"] = e
    return out


def test_turn_encloses_one_step_and_each_admission(tiny):
    model, params = tiny
    _, _, events = _serve(_engine(model), params,
                          [[1, 2, 3], [9, 8], [4, 5, 6, 7]], [4, 3, 5])
    regions = _regions(events)
    turns = [r for r in regions if r["name"] == TURN]
    assert turns
    for r in regions:
        if r["name"] in (ADMISSION, STEP):
            assert r["parent"] == TURN, r
        else:
            assert r["parent"] is None, r       # turns and waits: top
    for t in turns:
        assert t["children"].count(STEP) <= 1, t["children"]
        # admissions come first in a turn, then the shared step
        if STEP in t["children"]:
            assert t["children"][-1] == STEP, t["children"]
    assert sum(t["children"].count(ADMISSION) for t in turns) == 3
    # 3 + 2 + 4 decode tokens after each first; two rows share steps
    assert sum(t["children"].count(STEP) for t in turns) >= 4


def test_admission_events_carry_the_requests_trace_id(tiny):
    model, params = tiny
    _, _, events = _serve(_engine(model), params, [[1, 2, 3], [9] * 9],
                          [2, 2], trace_ids={0: "req-a", 1: "req-b"})
    adm = [r for r in _regions(events) if r["name"] == ADMISSION]
    assert [(r["ev"][5], r["closed"][5]) for r in adm] == \
        [("req-a", "req-a"), ("req-b", "req-b")]
    assert [r["ev"][6] for r in adm] == [
        {"row": 0, "prompt_len": 3, "bucket": 8},
        {"row": 1, "prompt_len": 9, "bucket": 16}]
    assert {r["ev"][4] for r in adm} == {"engine"}
    # the shared step and the turn serve many requests: unbound
    for r in _regions(events):
        if r["name"] in (TURN, STEP, WAIT):
            assert r["ev"][5] is None, r


def test_pump_wait_only_while_nothing_is_queued(tiny):
    model, params = tiny
    _, hists, events = _serve(_engine(model), params,
                              [[1, 2], [3, 4, 5], [6]], [6, 2, 4])
    names = [(e[0], e[3]) for e in events if e[3] in (TURN, WAIT)]
    first = names.index(("B", TURN))
    last = len(names) - 1 - names[::-1].index(("E", TURN))
    # idle before the first request and after the last retirement...
    assert ("B", WAIT) in names[:first] and ("E", WAIT) in names[:first]
    # (the closing stop() woke that last wait)
    assert names[last + 1:] == [("B", WAIT), ("E", WAIT)]
    # ...and never while a row is live or a request is queued
    assert all(n == TURN for _, n in names[first:last + 1])
    assert hists[WAIT + "_ms"]["count"] == names.count(("B", WAIT))


def test_pump_iteration_histogram_is_fed_by_the_span_alone(tiny):
    model, params = tiny
    _, hists, events = _serve(_engine(model), params, [[1, 2, 3]], [5])
    turns = [r for r in _regions(events) if r["name"] == TURN]
    # the admission's turn also runs the first shared step
    assert hists[TURN + "_ms"]["count"] == len(turns) == 4
    assert hists[ADMISSION + "_ms"]["count"] == 1
    assert hists[STEP + "_ms"]["count"] == 4


@pytest.mark.parametrize("prompts,gen_lens,bucket_tokens", [
    ([[1, 2, 3], [5] * 9, [7] * 5], [4, 3, 6], 8 + 16 + 8),
    ([[2] * 8, [3] * 17], [1, 2], 8 + 32),
])
def test_counters_read_exactly(tiny, prompts, gen_lens, bucket_tokens):
    model, params = tiny
    counters, _, _ = _serve(_engine(model), params, prompts, gen_lens)
    assert counters["engine.admit_prompt_tokens"] == \
        sum(len(p) for p in prompts)
    assert counters["engine.admit_bucket_tokens"] == bucket_tokens
    # a request is live for every token after its first
    assert counters["engine.decode_live_rows"] == \
        sum(g - 1 for g in gen_lens)
    assert counters["engine.stream_admissions"] == len(prompts)
    steps = counters.get("engine.decode_path.plain", 0)
    assert max(g - 1 for g in gen_lens) <= steps \
        <= sum(g - 1 for g in gen_lens)


def test_chunked_admission_one_span_per_call_counted_once(tiny):
    """20 tokens in slices of 8: ``prefill_into_row`` runs the first
    slice inside its own span, the scheduler's two ``prefill_step``
    calls open one each; the counters move once, at the last slice."""
    model, params = tiny
    counters, _, events = _serve(_engine(model), params,
                                 [list(range(1, 21))], [3],
                                 prefill_chunk=8)
    adm = [r for r in _regions(events) if r["name"] == ADMISSION]
    assert [r["ev"][6] for r in adm] == \
        [{"row": 0, "prompt_len": 20, "bucket": 24}] * 3
    assert all(r["parent"] == TURN and not r["children"] for r in adm)
    assert counters["engine.admit_prompt_tokens"] == 20
    assert counters["engine.admit_bucket_tokens"] == 24
    assert counters["engine.decode_live_rows"] == 2


def test_paged_prefix_hit_counts_the_suffix(paged_tiny):
    """The second prompt shares two cached pages (8 tokens): only its
    suffix runs, in the suffix's bucket, and the span says so."""
    model, params = paged_tiny
    eng = Engine(model, batch=2, max_seq=64, prefill_mode="sp",
                 decode_mode="sp", paged=True, page_size=4,
                 prefix_cache=True)
    pre = list(range(1, 9))
    obs.enable(obs.Registry())
    trace.enable()
    sched = Scheduler(eng, params).start()
    try:
        assert len(sched.submit(pre + [20], 2, stop_tokens=[])
                   .result(timeout=180)) == 2
        assert len(sched.submit(pre + [30, 31], 2, stop_tokens=[])
                   .result(timeout=180)) == 2
    finally:
        sched.stop()
    counters = obs.snapshot()["counters"]
    adm = [r for r in _regions(_pump_events()) if r["name"] == ADMISSION]
    assert [r["ev"][6] for r in adm] == [
        {"row": 0, "prompt_len": 9, "bucket": 16},
        {"row": 0, "prompt_len": 10, "bucket": 8}]
    assert counters["serving.prefill_tokens_saved"] == 8
    assert counters["engine.admit_prompt_tokens"] == 9 + 2
    assert counters["engine.admit_bucket_tokens"] == 16 + 8
    assert counters["engine.decode_live_rows"] == 2


def test_spec_verify_steps_count_their_live_rows(tiny):
    """A speculative verify step is a decode step too: it counts one
    ``engine.decode_path.spec`` and its live rows, so the ratio stays
    rows per step whatever the burst emitted."""
    from triton_dist_tpu.models.spec import SpecConfig
    model, params = tiny
    counters, _, _ = _serve(_engine(model, spec=SpecConfig(k=4)), params,
                            [[5, 6, 5, 6, 5, 6, 5]], [9])
    steps = sum(v for k, v in counters.items()
                if k.startswith("engine.decode_path."))
    assert counters.get("engine.decode_path.spec", 0) \
        == counters["serving.spec_steps"] > 0
    assert counters["engine.decode_live_rows"] == steps   # one row


def test_disabled_sites_return_the_shared_noop_span(tiny, monkeypatch):
    """Telemetry and tracing off: every new site gets the one no-op
    span (no clock read, no annotation, no ring event) and counts
    nothing."""
    model, params = tiny
    seen = {}
    real = obs.span

    def spy(name, *a, **kw):
        s = real(name, *a, **kw)
        seen.setdefault(name, []).append(s)
        return s

    monkeypatch.setattr(obs, "span", spy)
    assert not obs.enabled() and not trace.enabled()
    sched = Scheduler(_engine(model), params, prefill_chunk=8).start()
    try:
        for p, g in (([1, 2, 3], 3), (list(range(1, 21)), 2)):
            assert len(sched.submit(p, g, stop_tokens=[])
                       .result(timeout=180)) == g
    finally:
        sched.stop()
    assert set(OURS) <= set(seen)
    for name in OURS:
        assert all(s is obs_registry._NULL_SPAN for s in seen[name]), name
    assert obs_registry._NULL_SPAN.elapsed_ms is None
    assert obs.snapshot()["counters"] == {}
    assert trace.collect()["events_total"] == 0
