"""History plane: sampled series, trend math, early-warning detectors
(obs/history.py, ISSUE 16).

Quick tier — everything here is either pure math over point lists,
a thread-free sampler driven with injected timestamps, or a short
live-scheduler scenario on the xla-impl tiny model:

- trend math (slope / ema / window_stats / eta_to) against numpy
  goldens, including the no-crossing, negative-slope, and len<2
  degenerate cases ISSUE 17's autoscaler will lean on;
- ring-buffer semantics (wraparound, trailing-window trim,
  stride-downsample keeping the newest point) and sparkline units;
- the detector grammar (``metric>thr[@window]``), the fire-once
  latch, and the step detector's both-halves-populated guard;
- the sampler contract: gauges stored as values, counters as
  per-second rates (first sample skipped), a firing detector emits
  the ``history.warning`` counters + trace instant and a flight dump
  that EMBEDS the trailing series (the injectable provider satellite);
- ``{"cmd": "history"}`` through a live ModelServer + ChatClient, and
  the Perfetto counter-track export (library + CLI ``--history``);
- the acceptance scenario: under ramped load the step detector fires
  and produces a validated flight dump with attached series STRICTLY
  BEFORE the SLO breach dump;
- dashboards: ``top.py`` / ``fleet_top.py`` sparkline panels (pure
  render + live ``--once``), the fleet_top cached-merge contract
  (off-tick refreshes issue ZERO extra history scrapes), poll-fed
  FleetView health history, and ``report.py``'s history section;
- ``bench_ops.check_history_wellformed`` shape gate.
"""

import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.models import DenseLLM, Engine, ModelConfig
from triton_dist_tpu.obs import flight, trace
from triton_dist_tpu.obs.history import (DetectorSpec, HistorySampler,
                                         Series, SeriesStore,
                                         StepChange, SustainedSlope,
                                         downsample, ema, eta_to,
                                         make_detector, parse_detectors,
                                         slope, sparkline, window_stats)
from triton_dist_tpu.obs.registry import Registry
from triton_dist_tpu.serving import ChatClient, ModelServer, fanout

# ---------------------------------------------------------------------------
# Trend math vs numpy goldens.
# ---------------------------------------------------------------------------

_RAGGED = [(0.0, 1.0), (0.5, 2.2), (1.1, 2.9), (1.7, 4.5), (2.3, 4.9)]


def _np_slope(points):
    t = np.array([p[0] for p in points])
    v = np.array([p[1] for p in points])
    return float(np.polyfit(t, v, 1)[0])


def test_slope_matches_numpy_polyfit():
    assert slope(_RAGGED) == pytest.approx(_np_slope(_RAGGED))
    falling = [(t, 10.0 - 3.0 * t) for t in (0.0, 0.7, 1.3, 2.0)]
    s = slope(falling)
    assert s == pytest.approx(_np_slope(falling))
    assert s < 0


def test_slope_degenerate_cases():
    assert slope([]) is None
    assert slope([(1.0, 5.0)]) is None                # len < 2: no data
    assert slope([(1.0, 5.0), (1.0, 9.0)]) is None    # zero time variance


def test_ema_golden_and_alpha_validation():
    pts = [(0.0, 1.0), (1.0, 2.0), (2.0, 3.0)]
    # s = .5*2 + .5*1 = 1.5 ; s = .5*3 + .5*1.5 = 2.25
    assert ema(pts, alpha=0.5) == pytest.approx(2.25)
    assert ema([], alpha=0.5) is None
    for bad in (0.0, -0.1, 1.5):
        with pytest.raises(ValueError):
            ema(pts, alpha=bad)


def test_window_stats():
    assert window_stats([]) == {"n": 0}
    st = window_stats(_RAGGED)
    vals = [v for _, v in _RAGGED]
    assert st["n"] == len(vals)
    assert st["min"] == min(vals) and st["max"] == max(vals)
    assert st["avg"] == pytest.approx(sum(vals) / len(vals))
    assert st["last"] == vals[-1]
    assert st["span_s"] == pytest.approx(2.3)


def test_eta_to_forecasts_vs_numpy():
    rising = [(0.0, 0.0), (1.0, 1.0), (2.0, 2.0)]
    # Crossing ahead: (thr - last) / fitted slope.
    want = (5.0 - 2.0) / _np_slope(rising)
    assert eta_to(rising, 5.0) == pytest.approx(want)
    # Moving AWAY from the threshold (it is behind us): no crossing.
    assert eta_to(rising, -1.0) is None
    # Negative slope falling toward a lower threshold.
    falling = [(0.0, 10.0), (1.0, 8.0), (2.0, 6.0)]
    want = (2.0 - 6.0) / _np_slope(falling)
    assert eta_to(falling, 2.0) == pytest.approx(want)
    # Negative slope, threshold above: moving away, no crossing.
    assert eta_to(falling, 20.0) is None
    # Already sitting ON the threshold.
    assert eta_to(rising, 2.0) == 0.0
    # Flat never crosses; len<2 is no-data.
    assert eta_to([(0.0, 3.0), (1.0, 3.0)], 9.0) is None
    assert eta_to([(0.0, 3.0)], 9.0) is None


# ---------------------------------------------------------------------------
# Ring buffers, downsampling, sparklines.
# ---------------------------------------------------------------------------

def test_series_ring_wraparound():
    s = Series("q", maxlen=4)
    for i in range(6):
        s.append(float(i), float(i * 10))
    assert len(s) == 4
    assert s.total == 6
    assert s.last() == (5.0, 50.0)
    # Oldest-first, only the newest maxlen survive the wrap.
    assert s.points() == [(2.0, 20.0), (3.0, 30.0), (4.0, 40.0),
                          (5.0, 50.0)]
    # Trailing-window trim anchored at an explicit now.
    assert s.points(last_s=1.5, now=5.0) == [(4.0, 40.0), (5.0, 50.0)]
    assert s.values(last_s=1.5, now=5.0) == [40.0, 50.0]
    with pytest.raises(ValueError):
        Series("bad", maxlen=1)


def test_downsample_keeps_newest():
    pts = [(float(i), float(i)) for i in range(10)]
    out = downsample(pts, 3)
    assert len(out) <= 3
    assert out[-1] == pts[-1]                 # right edge always kept
    assert out == sorted(out)                 # still oldest-first
    assert downsample(pts, None) == pts
    assert downsample(pts, 100) == pts
    assert downsample(pts, 0) == []


def test_sparkline_units():
    assert sparkline([]) == ""
    assert sparkline([None, None]) == ""      # None-filtered → no data
    assert sparkline([3.0, 3.0, 3.0]) == "▄▄▄"   # flat ≠ empty
    assert sparkline(range(8)) == "▁▂▃▄▅▆▇█"
    assert len(sparkline(range(100), width=12)) == 12
    # Bucket-averaged width reduction keeps the ramp monotone.
    w = sparkline(range(64), width=8)
    assert list(w) == sorted(w)


def test_store_snapshot_filter_window_downsample():
    store = SeriesStore(maxlen=16)
    for i in range(10):
        store.record("a", float(i), float(i))
        store.record("b", float(i), 1.0)
    store.add_warning({"detector": "slope", "metric": "a"})
    store.add_warning({"detector": "step", "metric": "b"})
    snap = store.snapshot(series=["a"], max_points=4)
    assert set(snap["series"]) == {"a"}
    assert len(snap["series"]["a"]["points"]) <= 4
    assert snap["series"]["a"]["points"][-1] == [9.0, 9.0]
    assert snap["series"]["a"]["n"] == 10
    assert snap["maxlen"] == 16 and "epoch" in snap
    # Warnings are newest-first.
    assert [w["detector"] for w in snap["warnings"]] == ["step",
                                                         "slope"]
    # last_s trims relative to each series' newest point.
    snap2 = store.snapshot(last_s=2.0)
    assert len(snap2["series"]["a"]["points"]) == 3


# ---------------------------------------------------------------------------
# Detector grammar + the fire-once latch.
# ---------------------------------------------------------------------------

def test_detector_spec_validation():
    with pytest.raises(ValueError):
        DetectorSpec("nope", "m", ">", 1.0)
    with pytest.raises(ValueError):
        DetectorSpec("slope", "m", ">=", 1.0)
    with pytest.raises(ValueError):
        DetectorSpec("slope", "m", ">", 1.0, window_s=0.0)


def test_parse_detectors_grammar():
    specs = parse_detectors(
        "serving.queue_depth>0.5@30; kv.blocks_free<2", "slope")
    assert [s.metric for s in specs] == ["serving.queue_depth",
                                         "kv.blocks_free"]
    assert specs[0].op == ">" and specs[0].threshold == 0.5
    assert specs[0].window_s == 30.0
    assert specs[1].op == "<" and specs[1].window_s == 30.0  # default
    assert parse_detectors("", "slope") == []
    assert parse_detectors("  ;  ", "step") == []
    for bad in ("queue_depth", ">1.0", "m>abc", "m>1@xx"):
        with pytest.raises(ValueError):
            parse_detectors(bad, "slope")
    assert isinstance(make_detector(specs[0]), SustainedSlope)
    assert isinstance(
        make_detector(DetectorSpec("step", "m", ">", 1.0)), StepChange)


def test_sustained_slope_fires_once_then_rearms():
    det = make_detector(DetectorSpec("slope", "q", ">", 0.5,
                                     window_s=2.0))
    ramp = [(t * 0.5, t * 0.5 * 2.0) for t in range(5)]  # slope 2.0
    d = det.check(ramp, now=2.0)
    assert d is not None
    assert d["detector"] == "slope" and d["metric"] == "q"
    assert d["slope_per_s"] == pytest.approx(2.0)
    # Still over threshold: latched, no second fire.
    assert det.check(ramp, now=2.0) is None
    # Condition clears (flat window) → re-arms...
    flat = [(t * 0.5, 7.0) for t in range(5)]
    assert det.check(flat, now=2.0) is None
    # ... and a new sustained excursion fires again.
    assert det.check(ramp, now=2.0) is not None
    # Too few points / half-covered window: never fires.
    assert det.check(ramp[:2], now=2.0) is None
    fresh = make_detector(DetectorSpec("slope", "q", ">", 0.5,
                                       window_s=10.0))
    assert fresh.check(ramp, now=2.0) is None   # span 2 < 0.5*10


def test_step_change_needs_both_halves():
    det = make_detector(DetectorSpec("step", "q", ">", 2.0,
                                     window_s=1.0))
    # A series that APPEARS mid-window (late half only) cannot
    # instant-fire on its first samples.
    late_only = [(0.6, 5.0), (0.7, 5.0), (0.8, 5.0), (0.9, 5.0)]
    assert det.check(late_only, now=1.0) is None
    # Both halves populated and the level shift exceeds the threshold.
    pts = [(0.1, 0.0), (0.3, 0.0), (0.7, 5.0), (0.9, 5.0)]
    d = det.check(pts, now=1.0)
    assert d is not None and d["delta"] == pytest.approx(5.0)
    assert det.check(pts, now=1.0) is None     # latched
    # Shift below threshold clears the latch.
    small = [(0.1, 0.0), (0.3, 0.0), (0.7, 1.0), (0.9, 1.0)]
    assert det.check(small, now=1.0) is None
    assert det.check(pts, now=1.0) is not None  # re-armed, fires again


# ---------------------------------------------------------------------------
# The sampler: values vs rates, detector wiring, flight provider.
# ---------------------------------------------------------------------------

def _sampler(reg, **kw):
    kw.setdefault("thread", False)
    kw.setdefault("install_flight_provider", False)
    kw.setdefault("tick_s", 0.05)
    return HistorySampler(registry=reg, **kw)


def test_sampler_gauges_as_values_counters_as_rates():
    reg = Registry()
    reg.gauge("serving.queue_depth").set(5.0)
    reg.counter("serving.admitted").inc(10.0)
    smp = _sampler(reg, maxlen=32)
    smp.sample_once(now=100.0)
    # Gauge recorded as a value; the FIRST counter sample is skipped
    # (no previous tick to rate against).
    q = smp.store.get("serving.queue_depth")
    assert q is not None and q.last() == (100.0, 5.0)
    assert smp.store.get("serving.admitted") is None
    reg.counter("serving.admitted").inc(20.0)
    reg.gauge("serving.queue_depth").set(7.0)
    smp.sample_once(now=102.0)
    adm = smp.store.get("serving.admitted")
    assert adm.last() == (102.0, pytest.approx(10.0))   # 20 / 2 s
    assert smp.store.get("serving.queue_depth").last() == (102.0, 7.0)
    # Bookkeeping: tick counter + series-count gauge in the SAME
    # registry the sampler peeks.
    assert reg.counter("history.ticks").value == 2
    assert reg.gauge("history.series").value == len(smp.store)
    assert smp.snapshot()["tick_s"] == 0.05


def test_sampler_detector_fire_emits_warning_and_embedding_dump(
        monkeypatch, tmp_path):
    """A firing detector bumps the history.warning counters, records
    the excerpt, and the flight dump it triggers EMBEDS the trailing
    series (the injectable-provider satellite) as metadata AND as
    Perfetto counter tracks — and the artifact validates."""
    trace.enable()
    reg = Registry()
    det = make_detector(DetectorSpec("step", "g", ">", 2.0,
                                     window_s=1.0))
    smp = _sampler(reg, detectors=[det], install_flight_provider=True)
    try:
        for i, (now, v) in enumerate([(0.0, 0.0), (0.2, 0.0),
                                      (0.4, 0.0), (0.6, 5.0),
                                      (0.8, 5.0), (1.0, 5.0)]):
            reg.gauge("g").set(v)
            smp.sample_once(now=now)
        assert reg.counter("history.warnings").value == 1
        assert reg.counter("history.warning.step").value == 1
        (w,) = smp.store.warnings()
        assert w["detector"] == "step" and w["metric"] == "g"
        rec = flight.last_record()
        assert rec is not None and rec["reason"] == "history_step_g"
        with open(rec["path"]) as f:
            chrome = json.load(f)
        hist = chrome["metadata"]["history"]
        assert "g" in hist["series"] and hist["series"]["g"]["points"]
        cs = [e for e in chrome["traceEvents"] if e.get("ph") == "C"]
        assert cs and any(e["name"] == "g" for e in cs)
        from triton_dist_tpu.tools import trace_export
        errors, _ = trace_export.validate(chrome)
        assert errors == [], errors
    finally:
        smp.close()
    assert flight.history_provider() is None   # close uninstalls


def test_flight_provider_last_installer_wins():
    reg = Registry()
    a = _sampler(reg, install_flight_provider=True)
    assert flight.history_provider() == a.dump_payload
    b = _sampler(reg, install_flight_provider=True)
    assert flight.history_provider() == b.dump_payload
    a.close()                                  # not ours anymore: kept
    assert flight.history_provider() == b.dump_payload
    b.close()
    assert flight.history_provider() is None


def test_from_env_contract(monkeypatch):
    assert HistorySampler.from_env(registry=Registry()) is None
    monkeypatch.setenv("TDT_HISTORY", "1")
    monkeypatch.setenv("TDT_HISTORY_TICK_S", "0.05")
    monkeypatch.setenv("TDT_HISTORY_SLOPE", "serving.queue_depth>0.5@5")
    monkeypatch.setenv("TDT_HISTORY_STEP", "g>2@1")
    smp = HistorySampler.from_env(registry=Registry())
    try:
        assert smp is not None and smp.tick_s == 0.05
        assert [(d.kind, d.spec.metric) for d in smp.detectors] == \
            [("slope", "serving.queue_depth"), ("step", "g")]
    finally:
        smp.close()


def test_scheduler_ctor_injection_paths(tiny, monkeypatch):
    from triton_dist_tpu.models import Engine
    from triton_dist_tpu.serving import Scheduler
    model, params = tiny

    def _eng():
        return Engine(model, batch=2, max_seq=64,
                      prefill_mode="xla_ar", decode_mode="gemm_ar")

    # Default env-off: no sampler, no thread (zero-overhead contract).
    assert Scheduler(_eng(), params).history is None
    # Explicit opt-out even with the env set.
    monkeypatch.setenv("TDT_HISTORY", "1")
    assert Scheduler(_eng(), params, history_sampler=False) \
        .history is None
    # Injected instance is used verbatim.
    mine = _sampler(Registry())
    assert Scheduler(_eng(), params, history_sampler=mine) \
        .history is mine
    mine.close()
    # Env-on default path builds one.
    sched = Scheduler(_eng(), params)
    assert sched.history is not None
    sched.history.close()


# ---------------------------------------------------------------------------
# Perfetto counter-track export (library + CLI).
# ---------------------------------------------------------------------------

def _hist_snap():
    return {"epoch": 1000.0, "maxlen": 8,
            "series": {"q": {"points": [[1.0, 2.0], [2.0, 3.0]],
                             "n": 2},
                       "a": {"points": [[1.5, 7.0]], "n": 1}},
            "warnings": []}


def test_history_counter_events_and_validate():
    from triton_dist_tpu.tools import trace_export
    evs = trace_export.history_counter_events(_hist_snap(), pid=3)
    # Series-sorted; wall-anchored micros: (t + epoch) * 1e6.
    assert [e["name"] for e in evs] == ["a", "q", "q"]
    assert all(e["ph"] == "C" and e["pid"] == 3 and
               e["cat"] == "history" for e in evs)
    assert evs[0]["ts"] == pytest.approx(1001.5e6)
    assert evs[0]["args"] == {"value": 7.0}
    # Interleaved C events are exempt from the per-track monotonic
    # check (several series share a tid by design)...
    chrome = {"traceEvents": evs}
    errors, _ = trace_export.validate(chrome)
    assert errors == []
    # ... but non-numeric / empty args are schema errors.
    for bad_args in ({}, {"value": "x"}, {"value": True}, None):
        bad = {"traceEvents": [{"ph": "C", "ts": 1.0, "name": "q",
                                "args": bad_args}]}
        errors, _ = trace_export.validate(bad)
        assert errors, bad_args


def test_trace_export_cli_history_overlay(tmp_path, capsys):
    from triton_dist_tpu.tools import trace_export
    src = tmp_path / "in.trace.json"
    src.write_text(json.dumps({"traceEvents": [
        {"ph": "X", "ts": 1.0, "dur": 2.0, "pid": 0, "tid": 1,
         "name": "step", "cat": "serving"}]}))
    hist = tmp_path / "hist.json"
    # A saved {"cmd": "history"} reply — the wrapper is unwrapped.
    hist.write_text(json.dumps({"history": _hist_snap()}))
    out = tmp_path / "out.trace.json"
    rc = trace_export.main([str(src), "--out", str(out),
                            "--history", str(hist)])
    assert rc == 0
    merged = json.loads(out.read_text())
    assert merged["metadata"]["history_series"] == 2
    cs = [e for e in merged["traceEvents"] if e.get("ph") == "C"]
    assert len(cs) == 3
    # --history without --out, and a snapshot with no series: errors.
    with pytest.raises(SystemExit):
        trace_export.main([str(src), "--history", str(hist)])
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"history": {"series": {}}}))
    with pytest.raises(SystemExit):
        trace_export.main([str(src), "--out", str(out),
                           "--history", str(empty)])


# ---------------------------------------------------------------------------
# Live server: the {"cmd": "history"} verb + the acceptance scenario.
# ---------------------------------------------------------------------------

@pytest.fixture()
def tiny(mesh8, key):
    cfg = ModelConfig(hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=8,
                      num_key_value_heads=8, head_dim=4, vocab_size=64,
                      max_position_embeddings=64, dtype=jnp.float32)
    model = DenseLLM(cfg, mesh=mesh8, axis="tp", impl="xla")
    return model, model.init(key)


def _engine(model, batch=2, max_seq=64):
    return Engine(model, batch=batch, max_seq=max_seq,
                  prefill_mode="xla_ar", decode_mode="gemm_ar")


def _wait_until(pred, timeout=60.0, what="condition"):
    t0 = time.monotonic()
    while not pred():
        assert time.monotonic() - t0 < timeout, f"timed out on {what}"
        time.sleep(0.02)


def test_history_verb_null_without_sampler(tiny):
    model, params = tiny
    srv = ModelServer(_engine(model), params, port=0,
                      registry="private").start()
    try:
        c = ChatClient(srv.host, srv.port, timeout=180)
        assert c.request({"cmd": "history"}) == {"history": None}
        c.close()
    finally:
        srv.stop()


def test_history_verb_live_roundtrip(tiny, monkeypatch):
    """TDT_HISTORY=1 at construction: the sampler rides the pump's
    registry and the verb round-trips a downsampled snapshot."""
    monkeypatch.setenv("TDT_HISTORY", "1")
    monkeypatch.setenv("TDT_HISTORY_TICK_S", "0.05")
    model, params = tiny
    srv = ModelServer(_engine(model), params, port=0,
                      registry="private").start()
    try:
        c = ChatClient(srv.host, srv.port, timeout=180)
        c.generate_ids([[1, 2, 3]], gen_len=3)

        def _series():
            return c.request({"cmd": "history"})["history"]["series"]

        _wait_until(lambda: "serving.queue_depth" in _series(),
                    what="sampled queue_depth series")
        h = c.request({"cmd": "history", "max_points": 2,
                       "series": ["serving.queue_depth"]})["history"]
        assert h["tick_s"] == 0.05
        assert set(h["series"]) == {"serving.queue_depth"}
        assert 1 <= len(h["series"]["serving.queue_depth"]["points"]) \
            <= 2
        c.close()
    finally:
        srv.stop()


def test_early_warning_precedes_breach_live(tiny, monkeypatch):
    """Acceptance: under ramped load the step detector fires
    ``history.warning`` and dumps a flight record with the attached
    series STRICTLY BEFORE the SLO breach — the warning lands while
    ``serving.slo_breaches`` is still untouched, because the breach's
    slow window hasn't met its sample floor yet. The warning dump then
    validates as a Perfetto artifact with embedded counter tracks."""
    monkeypatch.setenv("TDT_SLO_TTFT_P99_MS", "0.001")
    monkeypatch.setenv("TDT_HISTORY", "1")
    monkeypatch.setenv("TDT_HISTORY_TICK_S", "0.05")
    monkeypatch.setenv("TDT_HISTORY_STEP",
                       "serving.queue_depth>1.5@1")
    model, params = tiny
    srv = ModelServer(_engine(model), params, port=0).start()
    try:
        assert trace.enabled()
        c = ChatClient(srv.host, srv.port, timeout=180)
        m0 = c.request({"cmd": "metrics",
                        "evaluate": False})["metrics"]["counters"]
        b0 = m0.get("serving.slo_breaches", 0)
        w0 = m0.get("history.warnings", 0)
        # Phase 1 — calm baseline: two serial requests, then idle long
        # enough for the sampler to record queue_depth == 0 into what
        # will become the detector window's EARLY half.
        for i in range(2):
            c.generate_ids([[1 + i, 2, 3]], gen_len=2)
        time.sleep(0.6)
        # Phase 2 — the ramp: 7 concurrent long generations through a
        # 2-row batch. Queue depth steps 0 → ~5; the step detector
        # fires mid-flood. TOTAL slow-window samples stay at 9 — below
        # the breach floor (TDT_SLO_MIN_SAMPLES = 10) — so the SLO
        # breach CANNOT fire yet: the warning is strictly earlier by
        # construction, not by a race.
        outs = fanout(srv.host, srv.port,
                      [{"prompt_ids": [[1 + i, 2, 3]], "gen_len": 48}
                       for i in range(7)], timeout=180)
        assert all("tokens" in o for o in outs), outs
        m1 = c.request({"cmd": "metrics",
                        "evaluate": False})["metrics"]["counters"]
        assert m1.get("history.warnings", 0) >= w0 + 1
        assert m1.get("serving.slo_breaches", 0) == b0   # not yet
        warn_rec = flight.last_record()
        assert warn_rec is not None
        assert warn_rec["reason"] == "history_step_serving.queue_depth"
        # Phase 3 — three more violating requests clear the sample
        # floor; the metrics scrape (evaluate defaults True) forces
        # the breach and its own dump.
        for i in range(3):
            c.generate_ids([[9 + i, 2]], gen_len=2)
        m2 = c.request({"cmd": "metrics"})["metrics"]
        c.close()
        assert m2["counters"]["serving.slo_breaches"] == b0 + 1
        breach_rec = flight.last_record()
        assert breach_rec["reason"] == "slo_ttft_p99"
        assert breach_rec["count"] > warn_rec["count"]   # strict order
        # The EARLY dump carries the lead-up series and validates.
        from triton_dist_tpu.tools import trace_export
        for rec in (warn_rec, breach_rec):
            with open(rec["path"]) as f:
                chrome = json.load(f)
            hist = chrome["metadata"].get("history")
            assert hist and hist["series"], rec["reason"]
            assert "serving.queue_depth" in hist["series"]
            assert any(e.get("ph") == "C"
                       for e in chrome["traceEvents"])
            errors, _ = trace_export.validate(chrome)
            assert errors == [], (rec["reason"], errors)
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Fleet: poll-fed health history + the cached-merge scrape contract.
# ---------------------------------------------------------------------------

class _FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


def _mk_health(rid, q=0.0, occ=0.0, p99=None):
    h = {"replica_id": rid, "seq": 1, "uptime_s": 1.0,
         "queue_depth": q, "batch_occupancy": occ}
    if p99 is not None:
        h["rolling"] = {"ttft_p99_ms": p99}
    return {"health": h}


def test_fleet_view_poll_feeds_history_and_staleness_gaps():
    from triton_dist_tpu.obs.fleet import FleetView
    clock = _FakeClock()
    state = {"b_alive": True}

    def scrape(endpoints, req):
        out = []
        for ep in endpoints:
            if ep[1] == 2 and not state["b_alive"]:
                out.append({"error": "timed out",
                            "type": "TimeoutError"})
            else:
                out.append(_mk_health(f"r{ep[1]}", q=2.0, occ=0.5,
                                      p99=8.0))
        return out

    view = FleetView(["127.0.0.1:1", "127.0.0.1:2"], stale_s_=5.0,
                     down_s_=20.0, clock=clock, scrape=scrape)
    assert view.history()["fleet"]["series"] == {}   # empty until poll
    view.poll()
    clock.t += 1.0
    view.poll()
    h = view.history()
    fl = h["fleet"]["series"]
    # Fleet rollup: additive sums over reporting replicas per poll.
    assert [v for _, v in fl["queue_depth"]["points"]] == [4.0, 4.0]
    assert [v for _, v in fl["replicas_reporting"]["points"]] == \
        [2.0, 2.0]
    assert set(h["replicas"]) == {"r1", "r2"}
    r1 = h["replicas"]["r1"]["series"]
    assert len(r1["queue_depth"]["points"]) == 2
    assert r1["ttft_p99_ms"]["points"][-1][1] == 8.0
    # A replica that fails the poll gets NO new point (a sparkline gap
    # is a staleness signal, not a zero) while the healthy one keeps
    # advancing; the fleet rollup drops to one reporter.
    state["b_alive"] = False
    clock.t += 1.0
    view.poll()
    h = view.history()
    assert len(h["replicas"]["r2"]["series"]["queue_depth"]
               ["points"]) == 2               # stopped advancing
    assert len(h["replicas"]["r1"]["series"]["queue_depth"]
               ["points"]) == 3
    # Stale (not yet down): the last-good health still counts toward
    # the rollup — only a DOWN replica drops out of it.
    assert h["fleet"]["series"]["replicas_reporting"]["points"][-1][1] \
        == 2.0
    clock.t += 25.0                           # past down_s
    view.poll()
    h = view.history()
    assert h["fleet"]["series"]["replicas_reporting"]["points"][-1][1] \
        == 1.0
    assert len(h["replicas"]["r2"]["series"]["queue_depth"]
               ["points"]) == 2               # still frozen


def test_fleet_top_off_tick_issues_zero_history_scrapes():
    """The cached-merge contract (METRICS_EVERY): an off-tick refresh
    polls health but issues NO {"cmd": "history"} (or metrics)
    scrapes — it renders the cached copies."""
    from triton_dist_tpu.obs.fleet import FleetView
    from triton_dist_tpu.tools import fleet_top
    clock = _FakeClock()
    counts: dict = {}

    def scrape(endpoints, req):
        counts[req["cmd"]] = counts.get(req["cmd"], 0) + 1
        if req["cmd"] == "health":
            return [_mk_health(f"r{ep[1]}", q=1.0) for ep in endpoints]
        if req["cmd"] == "metrics":
            return [{"metrics": {"replica_id": f"r{ep[1]}",
                                 "counters": {}, "gauges": {},
                                 "histograms": {}}}
                    for ep in endpoints]
        assert req["cmd"] == "history"
        assert req["max_points"] == 32       # downsampled server-side
        return [{"history": {
            "epoch": 0.0, "maxlen": 8, "tick_s": 0.05,
            "series": {"serving.queue_depth":
                       {"points": [[1.0, 2.0]], "n": 1}},
            "warnings": [{"detector": "step",
                          "metric": "serving.queue_depth"}]}}
            for ep in endpoints]

    view = FleetView(["127.0.0.1:1", "127.0.0.1:2"], clock=clock,
                     scrape=scrape)
    state = fleet_top.fetch(view, with_metrics=True)
    assert counts == {"health": 1, "metrics": 1, "history": 1}
    assert set(state["remote_history"]) == {"r1", "r2"}
    # Off-tick: health only — merged and remote history come from the
    # cache, zero extra scrape rounds.
    state = fleet_top.fetch(view, with_metrics=False)
    assert counts == {"health": 2, "metrics": 1, "history": 1}
    assert set(state["remote_history"]) == {"r1", "r2"}
    screen = fleet_top.render(state)
    assert "history: queue" in screen        # poll-fed fleet sparkline
    assert "r1: q" in screen
    assert "! r1: history.warning step serving.queue_depth" in screen


# ---------------------------------------------------------------------------
# Dashboards + report rendering.
# ---------------------------------------------------------------------------

def test_top_render_history_panel():
    from triton_dist_tpu.tools import top
    snap = {"counters": {}, "gauges": {}, "histograms": {},
            "health": None, "requests": [],
            "history": {"epoch": 0.0, "maxlen": 8, "tick_s": 0.05,
                        "series": {"serving.queue_depth":
                                   {"points": [[float(i), float(i)]
                                               for i in range(8)],
                                    "n": 8}},
                        "warnings": [{"detector": "slope",
                                      "metric": "serving.queue_depth",
                                      "op": ">", "threshold": 0.5,
                                      "window_s": 30.0}]}}
    screen = top.render(snap)
    assert "history (sampled)" in screen
    assert "serving.queue_depth" in screen
    assert any(ch in screen for ch in "▁▂▃▄▅▆▇█")
    assert "! slope" in screen
    # Additive: a history-less snapshot renders no panel and no crash.
    snap["history"] = None
    assert "history (sampled)" not in top.render(snap)


def test_dashboards_once_live_with_history(tiny, monkeypatch, capsys):
    """End-to-end ``--once``: both dashboards against a live sampling
    server render the sparkline panels."""
    from triton_dist_tpu.tools import fleet_top, top
    monkeypatch.setenv("TDT_HISTORY", "1")
    monkeypatch.setenv("TDT_HISTORY_TICK_S", "0.05")
    model, params = tiny
    srv = ModelServer(_engine(model), params, port=0,
                      registry="private", replica_id="h-a").start()
    try:
        c = ChatClient(srv.host, srv.port, timeout=180)
        c.generate_ids([[1, 2, 3]], gen_len=3)
        _wait_until(
            lambda: (c.request({"cmd": "history"})["history"]
                     or {}).get("series"),
            what="sampled series")
        c.close()
        assert top.main(["--host", srv.host, "--port", str(srv.port),
                         "--once"]) == 0
        out = capsys.readouterr().out
        assert "history (sampled)" in out
        assert any(ch in out for ch in "▁▂▃▄▅▆▇█")
        assert fleet_top.main(
            ["--endpoints", f"{srv.host}:{srv.port}", "--once"]) == 0
        out = capsys.readouterr().out
        assert "h-a" in out
        assert "history: queue" in out       # poll-fed fleet rollup
    finally:
        srv.stop()


def test_report_history_section():
    from triton_dist_tpu.tools.report import (render_history,
                                              render_telemetry)
    assert render_history(None) == ""
    assert render_history({"series": {}}) == ""
    hist = {"epoch": 0.0, "maxlen": 8,
            "series": {"serving.queue_depth":
                       {"points": [[float(i), float(i * 2)]
                                   for i in range(6)], "n": 6}},
            "warnings": [{"detector": "step",
                          "metric": "serving.queue_depth", "op": ">",
                          "threshold": 1.5, "window_s": 1.0}]}
    md = render_history(hist)
    assert "#### history" in md
    assert "| serving.queue_depth | 6 |" in md
    assert any(ch in md for ch in "▁▂▃▄▅▆▇█")
    assert "⚠ history.warning: step detector on " \
           "`serving.queue_depth`" in md
    # Rides render_telemetry like the fleet/router sections.
    tel = render_telemetry({"counters": {}, "gauges": {},
                            "histograms": {}, "history": hist})
    assert "#### history" in tel
