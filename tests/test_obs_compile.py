"""The compile log (``obs/compile.py``): one record per JAX trace,
lowering and back-end event, each second booked once, in the log, the
``compile.*`` counters and the ``compile`` track of the timeline."""
import sys
import threading
import time

import jax
import jax.monitoring as mon
import numpy as np
import pytest

from triton_dist_tpu import obs
from triton_dist_tpu.obs import compile as clog
from triton_dist_tpu.obs import trace

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
X = np.ones(4, np.float32)


@pytest.fixture(autouse=True)
def _fresh_log():
    obs.enable(obs.Registry())
    clog.reset()
    yield
    obs.disable()
    clog.reset()


def _of(name, log=None):
    """The records whose ``fun`` names ``name`` (``f`` when traced,
    ``jit(f)`` when lowered and compiled), by phase."""
    out = {}
    for r in (log or obs.compile_log())["records"]:
        if r["fun"] in (name, f"jit({name})", f"jit_{name}"):
            out.setdefault(r["phase"], []).append(r)
    return out


def _counters():
    return obs.snapshot()["counters"]


def test_a_fresh_jit_is_one_record_a_phase_and_a_second_call_none():
    def fresh_fn(x):
        time.sleep(2 * clog.MIN_TRACE_S)     # a trace worth a record
        return x + 1
    f = jax.jit(fresh_fn)
    f(X)
    got = _of("fresh_fn")
    back = got.get("compile", []) + got.get("cache_load", [])
    assert [len(got["trace"]), len(got["lower"]), len(back)] == [1, 1, 1]
    t, l, b = got["trace"][0], got["lower"][0], back[0]
    assert t["t0"] <= t["t1"] <= l["t1"] <= b["t1"] <= time.monotonic()
    assert all(r["s"] == pytest.approx(r["t1"] - r["t0"]) for r in (l, b))
    n = len(obs.compile_log()["records"])
    c = _counters()
    assert c["compile.programs"] == obs.compile_log()["programs"] >= 1
    for phase, total in obs.compile_log()["totals"].items():
        assert c.get(f"compile.{phase}_s", 0.0) == pytest.approx(total)
    f(X)            # the same shape again: nothing is built
    assert len(obs.compile_log()["records"]) == n
    assert _counters()["compile.programs"] == c["compile.programs"]


def test_a_jit_inside_a_jit_counts_its_trace_seconds_once():
    """Booked to the outermost: the inner trace (and the ``jnp``
    wrappers' under it) has no record, the outer's holds its seconds."""
    @jax.jit
    def nested_inner(x):
        time.sleep(0.05)
        return x * 2

    def nested_outer(x):
        time.sleep(0.02)
        return nested_inner(x) + 1
    jax.jit(nested_outer)(X)
    log = obs.compile_log()
    outer, = [r for r in log["records"] if r["phase"] == "trace"]
    assert outer["fun"] == "nested_outer"
    wall = outer["t1"] - outer["t0"]
    assert 0.07 <= outer["s"] == pytest.approx(wall)
    assert log["totals"]["trace"] <= wall + 1e-9
    assert _counters()["compile.trace_s"] == pytest.approx(wall)
    # the inner function is part of the outer's program: no lowering
    # nor back-end event of its own either
    assert "lower" in _of("nested_outer", log)
    assert _of("nested_inner", log) == {}


@pytest.mark.parametrize("hit,phase", [(True, "cache_load"),
                                       (False, "compile")])
def test_a_backend_event_is_a_load_only_with_a_cache_hit_inside(hit, phase):
    """The listeners driven as JAX drives them: a scalar as the region
    opens, the hit (if any) inside, the duration as it closes."""
    mon.record_event(HIT)              # a stale hit, before the region
    mon.record_scalar(BACKEND, time.time(), fun_name="jit_f")
    if hit:
        mon.record_event(HIT)
    else:
        mon.record_event(MISS)
    mon.record_event_duration_secs(BACKEND, 0.25, fun_name="jit_f")
    log = obs.compile_log()
    rec, = log["records"]
    assert (rec["phase"], rec["fun"], rec["s"]) == (phase, "jit_f", 0.25)
    assert rec["t1"] - rec["t0"] == pytest.approx(0.25)
    assert log["totals"] == {**dict.fromkeys(clog.PHASES, 0.0), phase: 0.25}
    assert log["programs"] == 1
    c = _counters()
    assert c == {f"compile.{phase}_s": 0.25, "compile.programs": 1}
    # the hit is used up: the next back-end event compiled
    mon.record_event_duration_secs(BACKEND, 0.5, fun_name="jit_g")
    assert obs.compile_log()["records"][-1]["phase"] == "compile"


def test_a_compile_inside_a_trace_is_taken_off_the_trace():
    """An eager op built while a trace evaluates a constant: its trace
    is folded into the outer one, its lowering and its back-end event
    keep their records and the outer trace loses their seconds."""
    mon.record_scalar(TRACE, time.time(), fun_name="admit")
    for ev, s in ((TRACE, 0.1), (LOWER, 0.2), (BACKEND, 0.3)):
        mon.record_scalar(ev, time.time(), fun_name="iota")
        if ev == LOWER:     # a trace inside a lowering is no trace's child
            mon.record_scalar(TRACE, time.time(), fun_name="rule")
            mon.record_event_duration_secs(TRACE, 0.05, fun_name="rule")
        mon.record_event_duration_secs(ev, s, fun_name="iota")
    mon.record_event_duration_secs(TRACE, 1.0, fun_name="admit")
    log = obs.compile_log()
    assert [(r["fun"], r["phase"], round(r["s"], 6))
            for r in log["records"]] == [
        ("rule", "trace", 0.05), ("iota", "lower", 0.15),
        ("iota", "compile", 0.3), ("admit", "trace", 0.5)]
    assert sum(log["totals"].values()) == pytest.approx(1.0)
    assert log["totals"]["trace"] == pytest.approx(0.55)
    # a duration whose start was never seen has nothing inside it
    mon.record_event_duration_secs(LOWER, 2.0, fun_name="admit")
    assert obs.compile_log()["records"][-1]["s"] == 2.0


def test_a_call_that_finds_its_jaxpr_cached_is_no_trace():
    """JAX reports a trace event on every call that misses its C++ fast
    path, microseconds long when the jaxpr is cached: neither a record
    nor seconds, at top level or inside another event."""
    mon.record_scalar(TRACE, time.time(), fun_name="step")
    mon.record_event_duration_secs(TRACE, 1e-4, fun_name="step")
    mon.record_scalar(LOWER, time.time(), fun_name="jit(f)")
    mon.record_scalar(TRACE, time.time(), fun_name="add")
    mon.record_event_duration_secs(TRACE, 2e-5, fun_name="add")
    mon.record_event_duration_secs(LOWER, 0.5, fun_name="jit(f)")
    rec, = obs.compile_log()["records"]
    assert (rec["fun"], rec["s"]) == ("jit(f)", 0.5)
    assert _counters() == {"compile.lower_s": 0.5}


def test_until_cuts_at_a_records_end():
    for s in (0.1, 0.2):
        mon.record_event_duration_secs(LOWER, s, fun_name="f")
        time.sleep(0.01)
    first, second = obs.compile_log()["records"]
    assert first["t1"] < second["t1"]
    for until, n in ((first["t1"] - 1e-6, 0), (first["t1"], 1),
                     (second["t1"] - 1e-6, 1), (second["t1"], 2),
                     (None, 2)):
        log = obs.compile_log(until=until)
        assert len(log["records"]) == n
        assert log["totals"]["lower"] == pytest.approx((0, 0.1, 0.3)[n])
    mon.record_event_duration_secs(BACKEND, 1.0, fun_name="late")
    assert obs.compile_log(until=second["t1"])["programs"] == 0
    assert obs.compile_log()["programs"] == 1


def test_the_list_is_bounded_and_the_counter_says_what_fell_off(monkeypatch):
    monkeypatch.setattr(clog, "MAX_RECORDS", 4)
    for i in range(7):
        mon.record_event_duration_secs(LOWER, 1.0, fun_name=f"f{i}")
    log = obs.compile_log()
    assert [r["fun"] for r in log["records"]] == ["f3", "f4", "f5", "f6"]
    c = _counters()
    assert c["compile.records_dropped"] == 3
    assert c["compile.lower_s"] == 7.0      # the counter keeps them all
    # the log says that its sums lack the oldest, whatever the cut
    assert (log["totals"]["lower"], log["dropped"]) == (4.0, 3)
    assert obs.compile_log(until=0.0)["dropped"] == 3
    clog.reset()
    assert obs.compile_log()["dropped"] == 0


def test_disabled_records_nothing_and_enabling_twice_installs_once():
    obs.enable()        # the fixture enabled once already
    mon.record_event(MISS)
    mon.record_event_duration_secs(LOWER, 1.0, fun_name="f")
    assert len(obs.compile_log()["records"]) == 1
    assert _counters() == {"compile.lower_s": 1.0}
    obs.disable()
    clog.reset()
    mon.record_scalar(LOWER, time.time(), fun_name="f")
    mon.record_event(HIT)
    mon.record_event_duration_secs(LOWER, 1.0, fun_name="f")
    jax.jit(lambda x: x - 3)(X)
    assert obs.compile_log() == {
        "records": [], "totals": dict.fromkeys(clog.PHASES, 0.0),
        "programs": 0, "dropped": 0}
    obs.enable()
    assert _counters() == {}
    # a region opened while obs was off is not waited for
    mon.record_event_duration_secs(BACKEND, 1.0, fun_name="f")
    assert obs.compile_log()["records"][0]["phase"] == "compile"


@pytest.mark.parametrize("reenabled_before_the_end", [False, True])
def test_an_event_left_open_across_a_disable_folds_no_later_trace(
        reenabled_before_the_end):
    """``obs`` switched off while a trace is open: whether its end comes
    while off (no record) or after (a record), the thread's next
    top-level trace is no child of it."""
    mon.record_scalar(TRACE, time.time(), fun_name="open")
    obs.disable()
    if reenabled_before_the_end:
        obs.enable()
    mon.record_event_duration_secs(TRACE, 1.0, fun_name="open")
    obs.enable()
    mon.record_scalar(TRACE, time.time(), fun_name="later")
    mon.record_event_duration_secs(TRACE, 0.5, fun_name="later")
    assert [(r["fun"], r["s"]) for r in obs.compile_log()["records"]] == (
        [("open", 1.0)] * reenabled_before_the_end + [("later", 0.5)])
    # nor is one whose start alone was seen while on
    clog.reset()
    mon.record_scalar(TRACE, time.time(), fun_name="open")
    obs.disable()
    mon.record_scalar(LOWER, time.time(), fun_name="unseen")
    obs.enable()
    mon.record_scalar(TRACE, time.time(), fun_name="later")
    mon.record_event_duration_secs(TRACE, 0.5, fun_name="later")
    assert [r["fun"] for r in obs.compile_log()["records"]] == ["later"]


def test_the_timeline_holds_the_events_on_the_compile_track():
    def traced_fn(x):
        time.sleep(2 * clog.MIN_TRACE_S)
        return x * 3
    trace.enable()
    jax.jit(traced_fn)(X)
    evs = trace.collect()["tracks"][clog.TRACK]
    mine = [e for e in evs if "traced_fn" in e[6]["fun"]]
    names = [e[3] for e in mine]
    assert names[:2] == ["compile.trace", "compile.lower"]
    assert names[2] in ("compile.compile", "compile.cache_load")
    assert all(e[0] == "X" and e[4] == "engine" and e[2] > 0 for e in mine)
    # on the tracer's clock: it ended just now
    end_us = mine[-1][1] + mine[-1][2]
    assert 0 <= trace.now_us() - end_us < 60e6
    # and the exporter takes the track as it is
    from triton_dist_tpu.tools import trace_export
    chrome = trace_export.to_chrome(trace.collect())
    assert any(e.get("name") == "compile.lower"
               and e.get("args", {}).get("fun", "").endswith("traced_fn)")
               for e in chrome["traceEvents"])


def test_a_record_made_under_a_bound_request_carries_its_trace_id():
    trace.enable()
    with trace.bind("req-7"):
        mon.record_event_duration_secs(BACKEND, 0.5, fun_name="jit_admit")
    mon.record_event_duration_secs(BACKEND, 0.5, fun_name="jit_step")
    a, b = obs.compile_log()["records"]
    assert (a["trace_id"], b["trace_id"]) == ("req-7", None)
    evs = trace.collect()["tracks"][clog.TRACK]
    assert [e[5] for e in evs] == ["req-7", None]


def test_threads_building_at_once_lose_no_record():
    """More threads than cores, a short switch interval: every event is
    in the log and in the counters, and each thread folds its own."""
    n_threads, n_each = 16, 50
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)

    def work(k):
        for i in range(n_each):
            mon.record_scalar(TRACE, 0.0, fun_name=f"outer{k}")
            mon.record_scalar(TRACE, 0.0, fun_name=f"inner{k}")
            mon.record_event_duration_secs(TRACE, 1.0, fun_name=f"inner{k}")
            mon.record_event_duration_secs(TRACE, 3.0, fun_name=f"outer{k}")
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    log = obs.compile_log()
    assert len(log["records"]) == n_threads * n_each     # inner: folded
    assert log["totals"]["trace"] == pytest.approx(3.0 * n_threads * n_each)
    assert _counters()["compile.trace_s"] == pytest.approx(
        3.0 * n_threads * n_each)
    assert {r["s"] for r in log["records"]} == {3.0}
