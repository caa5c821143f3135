"""Sliding-window SLO engine (obs/slo.py, ISSUE 8).

Quick tier, pure Python: every clock is injected, so window rotation,
subwindow expiry, empty-window reads, burn-rate arithmetic, and the
fast/slow multi-window agreement rules are tested without sleeping.
The flight-recorder arming test drives a fault-injected latency spike
through a real tracker with tracing on and checks the dump is a valid
Perfetto artifact written exactly once per breach episode.

The live-scheduler integration (a real request breaching a tiny
threshold through ``{"cmd": "metrics"}``) lives in
tests/test_scheduler.py next to the other server scenarios.
"""

import json

import pytest

from triton_dist_tpu import obs
from triton_dist_tpu.obs import flight, slo, trace
from triton_dist_tpu.obs.exposition import histogram_quantile


class Clock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _wh(ck, window=60.0, subs=12, retain=10):
    return slo.WindowedHistogram(window_s_=window, subwindows_=subs,
                                 retain_windows=retain, clock=ck)


# ---------------------------------------------------------------------------
# WindowedHistogram: rotation, expiry, empty reads.
# ---------------------------------------------------------------------------

def test_window_rotation_keeps_trailing_window():
    ck = Clock()
    w = _wh(ck)
    for _ in range(10):
        w.observe(4.0)
    ck.advance(30.0)                      # still inside the 60 s window
    assert w.snapshot()["count"] == 10
    ck.advance(40.0)                      # 70 s: out of the fast window
    assert w.snapshot()["count"] == 0
    # ... but still inside the retained slow span.
    assert w.snapshot(over_s=600.0)["count"] == 10


def test_subwindow_expiry_prunes_the_ring():
    ck = Clock()
    w = _wh(ck)
    w.observe(1.0)
    ck.advance(60.0 * 10 + 5.0)           # past the full retained span
    assert w.snapshot(over_s=600.0)["count"] == 0
    w.observe(2.0)                        # triggers expiry of the old slot
    assert len(w._slots) == 1


def test_empty_window_reads():
    ck = Clock()
    w = _wh(ck)
    assert w.snapshot()["count"] == 0
    assert w.quantile(0.99) is None
    assert slo.violating_fraction(w.snapshot(), 5.0) == 0.0


def test_rolling_quantile_tracks_recent_samples_only():
    ck = Clock()
    w = _wh(ck)
    for _ in range(100):
        w.observe(2.0)                    # old regime
    ck.advance(120.0)                     # old regime leaves the window
    for _ in range(10):
        w.observe(400.0)                  # new regime
    p50 = w.quantile(0.50)
    assert 250.0 < p50 <= 500.0, p50      # sees only the regression
    # The cumulative view would have said ~2 ms: that is the bug this
    # module exists to fix.


# ---------------------------------------------------------------------------
# Burn-rate arithmetic.
# ---------------------------------------------------------------------------

def test_violating_fraction_interpolates():
    h = {"buckets": [10.0, 20.0], "counts": [5, 5, 0], "count": 10}
    assert slo.violating_fraction(h, 15.0) == pytest.approx(0.25)
    assert slo.violating_fraction(h, 10.0) == pytest.approx(0.5)
    assert slo.violating_fraction(h, 0.0) == pytest.approx(1.0)


def test_violating_fraction_overflow_needs_proof():
    # Overflow samples are provably above the top finite edge — they
    # count against thresholds at/below it, never above it (no
    # manufactured false positives).
    h = {"buckets": [10.0, 20.0], "counts": [0, 0, 4], "count": 4}
    assert slo.violating_fraction(h, 20.0) == pytest.approx(1.0)
    assert slo.violating_fraction(h, 50.0) == 0.0


def test_burn_rate_fast_slow_agreement_breaches():
    ck = Clock(1000.0)
    t = slo.SLOTracker(targets=[slo.SLOTarget("ttft", 0.9, 10.0)],
                       clock=ck)
    for _ in range(50):
        t.observe("ttft", 100.0)          # fresh spike, no history
    r = t.evaluate(force=True)
    b = r["burn"]["ttft_p90"]
    assert b["fast"] == pytest.approx(10.0)
    assert b["slow"] == pytest.approx(10.0)
    assert b["breached"]
    assert r["new_breaches"] == ["ttft_p90"]


def test_burn_rate_slow_window_vetoes_fresh_blip():
    """Fast window screaming + slow window diluted by a long good
    history = no breach (the single-blip veto)."""
    ck = Clock()
    t = slo.SLOTracker(targets=[slo.SLOTarget("ttft", 0.9, 10.0)],
                       clock=ck)
    for i in range(500):                  # 500 good samples over ~8 min
        t.observe("ttft", 1.0)
        ck.advance(1.0)
    for _ in range(10):                   # small fresh spike
        t.observe("ttft", 100.0)
    r = t.evaluate(force=True)
    b = r["burn"]["ttft_p90"]
    assert b["fast"] > 1.0                # fast window sees the spike
    assert b["slow"] < 1.0                # diluted over the history
    assert not b["breached"]


def test_burn_rate_fast_window_vetoes_stale_spike():
    """An old spike that has left the fast window cannot breach, no
    matter how bad the slow window still looks."""
    ck = Clock()
    t = slo.SLOTracker(targets=[slo.SLOTarget("ttft", 0.99, 10.0)],
                       clock=ck)
    for _ in range(20):
        t.observe("ttft", 100.0)          # spike at t=0
    ck.advance(300.0)                     # 5 min later...
    for _ in range(50):
        t.observe("ttft", 1.0)            # ...recent traffic is clean
    r = t.evaluate(force=True)
    b = r["burn"]["ttft_p99"]
    assert b["fast"] == pytest.approx(0.0)
    assert b["slow"] > 1.0
    assert not b["breached"]


def test_sparse_traffic_single_blip_cannot_breach(monkeypatch):
    """Review hardening: with only the blip itself in BOTH windows,
    fast and slow agree trivially and the multiwindow veto is void —
    the slow-window sample floor (TDT_SLO_MIN_SAMPLES) restores
    'a single slow request cannot page anyone'."""
    ck = Clock(1000.0)
    t = slo.SLOTracker(targets=[slo.SLOTarget("ttft", 0.99, 10.0)],
                       clock=ck)
    t.observe("ttft", 600.0)              # one slow request, no traffic
    b = t.evaluate(force=True)["burn"]["ttft_p99"]
    assert b["fast"] > 1.0 and b["slow"] > 1.0
    assert not b["breached"]              # sample floor vetoes
    # The floor is a knob: a deployment that wants single-sample
    # sensitivity can have it.
    monkeypatch.setenv("TDT_SLO_MIN_SAMPLES", "1")
    assert t.evaluate(force=True)["burn"]["ttft_p99"]["breached"]


def test_reset_windows_starts_fresh_epoch():
    """A warmup/timed split: reset_windows drops every
    retained subwindow so the next scrape prices only post-reset
    traffic."""
    ck = Clock()
    t = slo.SLOTracker(targets=[], clock=ck)
    for _ in range(5):
        t.observe("ttft", 100.0)
    assert t.quantile("ttft", 0.5) is not None
    t.reset_windows()
    assert t.quantile("ttft", 0.5) is None
    t.observe("ttft", 2.0)
    assert t.quantile("ttft", 0.5) < 100.0


def test_evaluate_rate_limit_and_force():
    ck = Clock()
    t = slo.SLOTracker(targets=[], clock=ck)
    assert t.evaluate() is not None
    assert t.evaluate() is None           # < EVAL_INTERVAL_S later
    assert t.evaluate(force=True) is not None
    ck.advance(2.0)
    assert t.evaluate() is not None


# ---------------------------------------------------------------------------
# Breach → flight recorder, exactly once per episode.
# ---------------------------------------------------------------------------

def test_breach_arms_flight_recorder_once_and_dump_validates(tmp_path,
                                                             monkeypatch):
    monkeypatch.setenv("TDT_TRACE_DIR", str(tmp_path))
    trace.enable()
    reg = obs.Registry()
    obs.enable(reg)
    try:
        trace.instant("serving.fake_event", "serving")
        ck = Clock(1000.0)
        t = slo.SLOTracker(
            targets=[slo.SLOTarget("ttft", 0.9, 10.0)], clock=ck)
        for _ in range(50):
            t.observe("ttft", 500.0)      # the injected latency spike
        r1 = t.evaluate(force=True)
        assert r1["burn"]["ttft_p90"]["breached"]
        rec = flight.last_record()
        assert rec is not None and rec["count"] == 1
        assert rec["reason"] == "slo_ttft_p90"
        # Sustained breach: later evaluations do NOT dump again.
        ck.advance(5.0)
        t.observe("ttft", 500.0)
        r2 = t.evaluate(force=True)
        assert r2["burn"]["ttft_p90"]["breached"]
        assert not r2["new_breaches"]
        assert flight.last_record()["count"] == 1
        assert reg.snapshot()["counters"]["serving.slo_breaches"] == 1
        # The dump is a valid Perfetto artifact.
        with open(rec["path"]) as f:
            chrome = json.load(f)
        from triton_dist_tpu.tools import trace_export
        errors, _ = trace_export.validate(chrome)
        assert errors == [], errors
        names = [ev.get("name") for ev in chrome["traceEvents"]]
        assert "serving.slo_breach.ttft_p90" in names
    finally:
        obs.disable()


def test_recovery_rearms_the_breach_transition(tmp_path, monkeypatch):
    monkeypatch.setenv("TDT_TRACE_DIR", str(tmp_path))
    reg = obs.Registry()
    obs.enable(reg)
    try:
        ck = Clock()
        t = slo.SLOTracker(
            targets=[slo.SLOTarget("ttft", 0.9, 10.0,
                                   burn_threshold=1.0)], clock=ck)
        for _ in range(50):
            t.observe("ttft", 500.0)
        assert t.evaluate(force=True)["new_breaches"]
        # Full recovery: the spike ages out of BOTH windows.
        ck.advance(601.0)
        for _ in range(50):
            t.observe("ttft", 1.0)
        assert not t.evaluate(force=True)["burn"]["ttft_p90"]["breached"]
        # A second regression is a NEW transition.
        for _ in range(50):
            t.observe("ttft", 500.0)
        assert t.evaluate(force=True)["new_breaches"] == ["ttft_p90"]
        assert reg.snapshot()["counters"]["serving.slo_breaches"] == 2
    finally:
        obs.disable()


# ---------------------------------------------------------------------------
# Targets, defaults, gauges.
# ---------------------------------------------------------------------------

def test_default_targets_env_overrides(monkeypatch):
    monkeypatch.setenv("TDT_SLO_TTFT_P99_MS", "123")
    monkeypatch.setenv("TDT_SLO_TPOT_P99_MS", "0")   # disables it
    targets = {t.metric: t for t in slo.default_targets()}
    assert targets["ttft"].threshold_ms == 123.0
    assert "tpot" not in targets
    assert "queue_wait" in targets


def test_slo_target_validation():
    with pytest.raises(ValueError):
        slo.SLOTarget("nope", 0.99, 10.0)
    with pytest.raises(ValueError):
        slo.SLOTarget("ttft", 1.5, 10.0)
    with pytest.raises(ValueError):
        slo.SLOTarget("ttft", 0.99, 0.0)
    assert slo.SLOTarget("ttft", 0.999, 5.0).name == "ttft_p99_9"


def test_evaluate_sets_rolling_and_burn_gauges():
    reg = obs.Registry()
    obs.enable(reg)
    try:
        ck = Clock()
        t = slo.SLOTracker(targets=[slo.SLOTarget("ttft", 0.99, 60000.0)],
                           clock=ck)
        for m in slo.METRICS:
            for _ in range(8):
                t.observe(m, 5.0)
        t.evaluate(force=True)
        g = reg.snapshot()["gauges"]
        for name in slo.gauge_catalog([slo.SLOTarget("ttft", 0.99,
                                                     60000.0)]):
            assert name in g, name
        assert g["serving.slo_burn.ttft_p99"] == 0.0
        assert g["serving.rolling.ttft_n"] == 8
        assert 2.5 < g["serving.rolling.ttft_p50_ms"] <= 5.0
    finally:
        obs.disable()


def test_quantile_clips_to_top_edge_in_overflow():
    """The rolling windows never track min/max — the +Inf tail must
    still yield a usable (flagged) number (obs.histogram_quantile
    overflow handling, ISSUE 8 satellite)."""
    ck = Clock()
    w = _wh(ck)
    top = slo.SLO_MS_BUCKETS[-1]
    w.observe(top * 10)
    v, clipped = histogram_quantile(w.snapshot(), 0.5, detail=True)
    assert v == top and clipped
