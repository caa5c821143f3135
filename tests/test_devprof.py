"""Device-time truth layer (obs/devprof.py, ISSUE 10).

Quick tier, CPU only. Covered here:

- the parser on GOLDEN inputs: a checked-in synthetic trace-event
  fixture with exact interval geometry yields the exact measured
  overlap (mirroring tests/test_trace.py's ``--overlap`` tests), and
  the same geometry hand-encoded as an XPlane proto yields the
  identical summary (pinning the protobuf wire decoder);
- a LIVE ``jax.profiler`` capture round-trip on CPU: capture an eager
  ``@resilient``-routed op → parse → nonzero ``device.<op>.*``, and a
  scheduler pump window (``TDT_DEVPROF_EVERY``) → nonzero
  ``device.step.*`` — no TPU required;
- the drift gauge against the dispatch-time model gauge;
- the breach-armed postmortem: an injected SLO breach through a live
  server leaves BOTH the host Perfetto flight dump and a parsed
  device-profile summary;
- ``group_profile``'s structured result + obs counters and the
  ``trace_files`` glob (tools/profiler.py satellite);
- the ``profile_export`` CLI (validate rc contract, summary, chrome
  conversion) and ``trace_export --merge-profile`` overlay;
- the ``annotation-coverage`` tdt-check pass incl. the strip-a-span
  mutation (``devprof.unlabeled``).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from triton_dist_tpu import obs
from triton_dist_tpu.obs import devprof, flight, trace
from triton_dist_tpu.tools.profiler import (annotate, group_profile,
                                            trace_files)

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "devprof_golden.trace.json")


# ---------------------------------------------------------------------------
# Golden geometry: exact measured overlap from a checked-in fixture.
# ---------------------------------------------------------------------------

def test_golden_fixture_exact_overlap():
    s = devprof.summarize(devprof.load_capture(GOLDEN))
    m = s["ops"]["ag_gemm"]
    assert m["total_ms"] == 1.0
    assert m["compute_ms"] == 0.6
    assert m["comm_ms"] == 0.8
    assert m["exposed_comm_ms"] == 0.4
    assert m["overlap_pct"] == 50.0            # 100·(1 − 400/800)
    assert s["unlabeled_ms"] == 0.5            # fusion.2 outside window
    # The host-side python event is not execution and counts nowhere.
    assert s["n_events"] == 3


def _enc_varint(x: int) -> bytes:
    out = b""
    while True:
        b7 = x & 0x7F
        x >>= 7
        if x:
            out += bytes([b7 | 0x80])
        else:
            return out + bytes([b7])


def _field(fn: int, payload) -> bytes:
    if isinstance(payload, int):
        return _enc_varint(fn << 3 | 0) + _enc_varint(payload)
    if isinstance(payload, str):
        payload = payload.encode()
    return _enc_varint(fn << 3 | 2) + _enc_varint(len(payload)) + payload


def _xevent(mid, off_ps, dur_ps):
    return _field(1, mid) + _field(2, off_ps) + _field(3, dur_ps)


def test_xplane_wire_decoder_matches_golden_geometry():
    """The same interval geometry hand-encoded as an XSpace proto
    (XSpace→XPlane→XLine→XEvent with event_metadata names) parses to
    the identical summary — the wire decoder is pinned to the schema,
    not to whatever this jax build happens to emit."""
    def meta_entry(mid, name):
        return _field(4, _field(1, mid) + _field(2, _field(2, name)))
    host_plane = (_field(2, "/host:CPU")
                  + meta_entry(1, "device.ag_gemm.fused")
                  + _field(3, _field(3, 0)          # line ts_ns = 0
                           + _field(4, _xevent(1, 1_000_000_000,
                                               1_000_000_000))))
    dev_plane = (_field(2, "/device:TPU:0")
                 + meta_entry(1, "fusion.1")
                 + meta_entry(2, "all-gather-start.7")
                 + meta_entry(3, "fusion.2")
                 + _field(3, _field(3, 0)
                          + _field(4, _xevent(1, 1_000_000_000,
                                              600_000_000))
                          + _field(4, _xevent(3, 3_000_000_000,
                                              500_000_000)))
                 + _field(3, _field(3, 0)
                          + _field(4, _xevent(2, 1_200_000_000,
                                              800_000_000))))
    space = _field(1, host_plane) + _field(1, dev_plane)
    s = devprof.summarize(devprof.parse_xplane(space))
    assert s["ops"]["ag_gemm"] == devprof.summarize(
        devprof.load_capture(GOLDEN))["ops"]["ag_gemm"]
    assert s["unlabeled_ms"] == 0.5


def test_host_exec_spans_do_not_mask_device_comm():
    """Review regression: on a capture WITH a device plane, host-side
    Execute spans bracket dispatch, not device work — one covering a
    device comm interval must not count as compute and inflate the
    measured overlap (the exact fiction this tier exists to retire).
    Without a device plane (CPU backend) they remain the execution
    stand-in."""
    comm = {"name": "all-gather-start.1", "ts_us": 0.0, "dur_us": 1000.0,
            "pid": 2, "tid": 1, "device": True}
    host_exec = {"name": "TfrtCpuExecutable::Execute", "ts_us": 0.0,
                 "dur_us": 1000.0, "pid": 1, "tid": 1, "device": False}
    label = {"name": "device.ag_gemm.fused", "ts_us": 0.0,
             "dur_us": 1000.0, "pid": 1, "tid": 1, "device": False}
    m = devprof.summarize([label, comm, host_exec])["ops"]["ag_gemm"]
    assert m["compute_ms"] == 0.0          # host span ignored
    assert m["overlap_pct"] == 0.0         # comm fully exposed
    # CPU-shaped capture (no device plane): the host span IS the work.
    host_only = dict(host_exec)
    m2 = devprof.summarize([label, host_only])["ops"]["ag_gemm"]
    assert m2["compute_ms"] == 1.0


def test_unparseable_inputs_raise():
    with pytest.raises(ValueError):
        devprof.parse_xplane(b"")
    with pytest.raises(ValueError):
        devprof.load_capture("/nonexistent/path")


# ---------------------------------------------------------------------------
# Live CPU capture round-trip (eager op → device.<op>.* gauges).
# ---------------------------------------------------------------------------

def _capture_eager_op(tmp_path, mesh8):
    """One eager ag_gemm (@resilient-routed, so the router plants the
    device.ag_gemm.fused annotation) under a live jax.profiler
    capture. world=1 keeps it cheap — the label/attribution path under
    test is identical."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from triton_dist_tpu.ops.allgather_gemm import (ag_gemm,
                                                    create_ag_gemm_context)
    mesh = Mesh(np.array([d for d in mesh8.devices.flat][:1]), ("tp",))
    ctx = create_ag_gemm_context(mesh, "tp")
    a = jax.device_put(jnp.ones((64, 128), jnp.bfloat16),
                       NamedSharding(mesh, P("tp")))
    b = jax.device_put(jnp.ones((128, 128), jnp.bfloat16),
                       NamedSharding(mesh, P(None, "tp")))
    with group_profile("live_op", str(tmp_path)) as cap:
        jax.block_until_ready(ag_gemm(a, b, ctx, impl="pallas"))
    return cap


def test_live_capture_roundtrip_eager_op(tmp_path, mesh8):
    reg = obs.enable(obs.Registry())
    try:
        cap = _capture_eager_op(tmp_path / "prof", mesh8)
        assert cap.path == str(cap) and cap.name == "live_op"
        summary = devprof.parse_capture(cap)
        m = summary["ops"].get("ag_gemm")
        assert m is not None, summary["ops"]
        assert m["total_ms"] > 0
        assert m["compute_ms"] > 0      # TfrtCpuExecutable::Execute
        # world=1 on CPU: no real comm events → the honest marker
        # contract (overlap None), not a fictional 100%.
        assert m["overlap_pct"] is None or 0 <= m["overlap_pct"] <= 100
        devprof.publish(summary)
        g = reg.snapshot()["gauges"]
        assert g["device.ag_gemm.total_ms"] > 0
        assert g["device.ag_gemm.compute_ms"] > 0
        c = reg.snapshot()["counters"]
        assert c["profile.captures"] == 1
        assert c["profile.parsed"] == 1
    finally:
        obs.disable()


def test_live_capture_xplane_artifact_also_parses(tmp_path, mesh8):
    """The pb artifact of a REAL capture goes through the wire decoder
    (not just the JSON path) and attributes the same op."""
    import glob as _glob
    cap = _capture_eager_op(tmp_path / "prof", mesh8)
    pbs = _glob.glob(os.path.join(cap.path, "plugins/profile/*",
                                  "*.xplane.pb"))
    assert pbs, "jax wrote no xplane.pb artifact"
    with open(pbs[0], "rb") as f:
        events = devprof.parse_xplane(f.read())
    s = devprof.summarize(events)
    assert "ag_gemm" in s["ops"] and s["ops"]["ag_gemm"]["total_ms"] > 0


def test_group_profile_meta_and_trace_files(tmp_path):
    reg = obs.enable(obs.Registry())
    try:
        with group_profile("t2", str(tmp_path)) as cap:
            jnp.dot(jnp.ones((32, 32)),
                    jnp.ones((32, 32))).block_until_ready()
        meta = devprof.capture_meta(cap.path)
        assert meta["name"] == "t2" and meta["host"] == 0
        assert meta["t0_unix"] > 0
        files = trace_files("t2", str(tmp_path))
        assert files == sorted(files) and files
        # The glob walks the nested plugins/profile/<run>/ tree.
        assert any("plugins" in f for f in files)
        assert any(f.endswith("tdt_capture.json") for f in files)
        h = reg.snapshot()["histograms"]["profile.capture_ms"]
        assert h["count"] == 1 and h["sum"] > 0
    finally:
        obs.disable()


def test_group_profile_disabled_yields_none():
    with group_profile("off", "/nonexistent", enabled=False) as cap:
        assert cap is None


def test_drift_gauge_measured_minus_modeled():
    reg = obs.enable(obs.Registry())
    try:
        reg.gauge("comms.ag_gemm.overlap_pct").set(90.0)   # the model
        devprof.publish(devprof.summarize(devprof.load_capture(GOLDEN)))
        g = reg.snapshot()["gauges"]
        assert g["comms.ag_gemm.overlap_pct_measured"] == 50.0
        assert g["comms.ag_gemm.exposed_comm_ms_measured"] == 0.4
        assert g["comms.ag_gemm.overlap_drift_pct"] == -40.0
        assert g["device.unlabeled_ms"] == 0.5
    finally:
        obs.disable()


# ---------------------------------------------------------------------------
# Serving: continuous sampler + breach-armed postmortem.
# ---------------------------------------------------------------------------

def _tiny_engine(mesh8, key):
    from triton_dist_tpu.models import DenseLLM, Engine, ModelConfig
    cfg = ModelConfig(hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=8,
                      num_key_value_heads=8, head_dim=4, vocab_size=64,
                      max_position_embeddings=64, dtype=jnp.float32)
    model = DenseLLM(cfg, mesh=mesh8, axis="tp", impl="xla")
    params = model.init(key)
    return Engine(model, batch=2, max_seq=64, prefill_mode="xla_ar",
                  decode_mode="gemm_ar"), params


def test_pump_sampler_feeds_device_step_gauges(mesh8, key):
    """TDT_DEVPROF_EVERY acceptance: a jax.profiler capture of a
    scheduler pump window parses into nonzero device.step.* gauges —
    on CPU, no TPU required."""
    from triton_dist_tpu.serving import Scheduler
    engine, params = _tiny_engine(mesh8, key)
    reg = obs.enable(obs.Registry())
    try:
        sampler = devprof.PumpSampler(every=3, sync=True)
        sched = Scheduler(engine, params,
                          devprof_sampler=sampler).start()
        try:
            toks = sched.generate([1, 2, 3], 8)
            assert len(toks) >= 1
        finally:
            sched.stop()
        last = devprof.last_profile()
        assert last is not None and last["reason"] == "sampler"
        step = last["summary"]["ops"].get("step")
        assert step is not None, last["summary"]
        assert step["total_ms"] > 0
        # Nested inside the whole-iteration window, the scheduler
        # brackets the shared decode step alone with the per-path
        # label — decode-only device time, no admission contamination
        # (the split Engine(decode_path="auto") arbitrates on).
        sub = last["summary"]["ops"].get("step.plain")
        assert sub is not None, last["summary"]
        assert 0 < sub["total_ms"] <= step["total_ms"]
        g = reg.snapshot()["gauges"]
        assert g["device.step.total_ms"] > 0
        assert g["device.step.plain.total_ms"] > 0
        assert g["device.step.plain.windows"] >= 1
        assert g.get("device.step.compute_ms", 0) >= 0
        assert reg.snapshot()["counters"]["profile.parsed"] >= 1
    finally:
        obs.disable()


def test_pump_sampler_attributes_mega_iterations_separately(mesh8, key):
    """ISSUE 11 satellite: a mega-engine scheduler's profiled pump
    iterations land in device.step.MEGA gauges, not blended into the
    plain window — the auto policy's measured inputs."""
    from triton_dist_tpu.serving import Scheduler
    from triton_dist_tpu.models import DenseLLM, Engine, ModelConfig
    cfg = ModelConfig(hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=8,
                      num_key_value_heads=8, head_dim=4, vocab_size=64,
                      max_position_embeddings=64, dtype=jnp.float32)
    model = DenseLLM(cfg, mesh=mesh8, axis="tp", impl="xla")
    params = model.init(key)
    engine = Engine(model, batch=2, max_seq=64, prefill_mode="xla_ar",
                    decode_mode="gemm_ar", use_mega=True)
    reg = obs.enable(obs.Registry())
    try:
        sampler = devprof.PumpSampler(every=3, sync=True)
        sched = Scheduler(engine, params,
                          devprof_sampler=sampler).start()
        try:
            toks = sched.generate([1, 2, 3], 8)
            assert len(toks) >= 1
        finally:
            sched.stop()
        last = devprof.last_profile()
        assert last is not None
        ops = last["summary"]["ops"]
        assert "step.mega" in ops and ops["step.mega"]["total_ms"] > 0
        assert "step.plain" not in ops, ops
        # ... and the decode-only sub-window stays inside the
        # whole-iteration window.
        assert ops["step.mega"]["total_ms"] <= ops["step"]["total_ms"]
        g = reg.snapshot()["gauges"]
        assert g["device.step.mega.total_ms"] > 0
    finally:
        obs.disable()


def test_pump_sampler_off_by_default(mesh8, key):
    from triton_dist_tpu.serving import Scheduler
    engine, params = _tiny_engine(mesh8, key)
    sched = Scheduler(engine, params)
    assert sched.devprof is None       # both knobs unset (conftest)
    assert devprof.PumpSampler.from_env() is None


def test_breach_postmortem_has_dump_and_device_profile(mesh8, key,
                                                       monkeypatch):
    """Acceptance: an injected SLO breach produces a postmortem
    containing BOTH the host Perfetto flight dump AND a parsed
    device-profile summary (TDT_DEVPROF_ON_BREACH)."""
    from triton_dist_tpu.serving import Scheduler
    from triton_dist_tpu.obs import slo
    monkeypatch.setenv("TDT_SLO_MIN_SAMPLES", "1")
    engine, params = _tiny_engine(mesh8, key)
    reg = obs.enable(obs.Registry())
    trace.enable()
    try:
        trace.instant("serving.fake_event", "serving")
        sampler = devprof.PumpSampler(on_breach=2, sync=True)
        target = slo.SLOTarget("ttft", 0.99, 0.001)  # impossible: all violate
        sched = Scheduler(engine, params, slo_tracker=[target],
                          devprof_sampler=sampler).start()
        try:
            sched.generate([1, 2, 3], 4)
            # Force the burn evaluation now (the pump's own calls are
            # rate-limited): the breach transition dumps the flight
            # record AND arms the devprof capture.
            r = sched.slo.evaluate(force=True)
            assert r["burn"]["ttft_p99"]["breached"], r
            rec = flight.last_record()
            assert rec is not None and rec["reason"] == "slo_ttft_p99"
            # The next pump iterations run under the armed capture.
            sched.generate([4, 5, 6], 4)
        finally:
            sched.stop()
        last = devprof.last_profile()
        assert last is not None, "no device profile parsed post-breach"
        assert last["reason"] == "breach_slo_ttft_p99"
        assert last["summary"]["ops"]["step"]["total_ms"] > 0
        # BOTH artifacts: the Perfetto dump validates, the profile
        # summary rides the metrics payload's devprof key.
        with open(rec["path"]) as f:
            chrome = json.load(f)
        from triton_dist_tpu.tools import trace_export
        errors, _ = trace_export.validate(chrome)
        assert errors == [], errors
        st = devprof.stats()
        assert st["last_profile"] == last["path"]
        assert "step" in st["ops"]
    finally:
        trace.reset()
        obs.disable()


def test_arm_is_rate_limited():
    # Arming is consumer-gated: without a breach-configured sampler
    # alive, arm() is a no-op (a sampler-less process must not
    # advertise an armed capture forever).
    devprof.arm("ignored")
    assert devprof.armed_reason() is None
    sampler = devprof.PumpSampler(on_breach=1, sync=True)  # consumer
    devprof.arm("one")
    assert devprof._consume_arm() == "one"
    devprof.arm("two")                 # inside ARM_MIN_INTERVAL_S
    assert devprof._consume_arm() is None
    assert devprof.armed_reason() is None      # dropped, not queued
    del sampler


# ---------------------------------------------------------------------------
# profile_export CLI + trace_export --merge-profile.
# ---------------------------------------------------------------------------

def test_profile_export_validate_rc_contract(tmp_path, mesh8):
    from triton_dist_tpu.tools import profile_export
    cap = _capture_eager_op(tmp_path / "prof", mesh8)
    # Valid capture → rc 0 (dir form, like hw_watch points it at
    # TDT_DEVPROF_DIR).
    assert profile_export.main([str(tmp_path / "prof"),
                                "--validate"]) == 0
    # Unparseable capture → rc != 0.
    bad = tmp_path / "bad" / "plugins" / "profile" / "run1"
    bad.mkdir(parents=True)
    (bad / "host.trace.json").write_text("{not json")
    assert profile_export.main([str(tmp_path / "bad"),
                                "--validate"]) == 1
    # Empty dir: warning by default, failure under --require.
    empty = tmp_path / "empty"
    empty.mkdir()
    assert profile_export.main([str(empty), "--validate"]) == 0
    assert profile_export.main([str(empty), "--validate",
                                "--require"]) == 1
    # --summary emits machine-readable attribution.
    s, err = profile_export.validate_capture(str(cap))
    assert err is None and "ag_gemm" in s["ops"]
    # --chrome conversion is wall-clock anchored.
    out = tmp_path / "dev.json"
    assert profile_export.main([str(cap), "--chrome", str(out)]) == 0
    dev = json.loads(out.read_text())
    anchor_us = devprof.capture_meta(cap)["t0_unix"] * 1e6
    xs = [e["ts"] for e in dev["traceEvents"] if e.get("ph") == "X"]
    assert xs and all(t >= anchor_us for t in xs)


def test_merge_profile_overlays_on_one_clock(tmp_path, mesh8):
    from triton_dist_tpu.tools import profile_export, trace_export
    trace.enable()
    try:
        with trace.span("engine.decode_step", "engine"):
            pass
        host = trace_export.to_chrome(trace.collect())
    finally:
        trace.reset()
    cap = _capture_eager_op(tmp_path / "prof", mesh8)
    merged = trace_export.merge_profile(host, str(cap))
    pids = {e.get("pid") for e in merged["traceEvents"]}
    assert any(p is not None and p >= profile_export.DEVICE_PID_BASE
               for p in pids)
    names = {e.get("name") for e in merged["traceEvents"]}
    assert "device.ag_gemm.fused" in names       # the overlay rows
    assert "engine.decode_step" in names         # host events intact
    errors, _ = trace_export.validate(merged)
    assert errors == [], errors
    assert merged["metadata"]["merged_profiles"] == 1
    # Device timestamps sit on the tracer's wall-anchored clock: the
    # label window must land within the capture's wall-time span.
    lbl = [e for e in merged["traceEvents"]
           if e.get("name") == "device.ag_gemm.fused"
           and e.get("ph") == "X"]
    t0 = devprof.capture_meta(cap)["t0_unix"] * 1e6
    assert all(t0 <= e["ts"] <= t0 + 600e6 for e in lbl)


def test_merge_profile_cli(tmp_path, mesh8):
    from triton_dist_tpu.tools import trace_export
    trace.enable()
    try:
        trace.instant("serving.ping", "serving")
        host_path = tmp_path / "host.trace.json"
        trace_export.write_trace(
            trace_export.to_chrome(trace.collect()), str(host_path))
    finally:
        trace.reset()
    cap = _capture_eager_op(tmp_path / "prof", mesh8)
    out = tmp_path / "overlaid.json"
    rc = trace_export.main([str(host_path), "--merge-profile",
                            str(cap), "--out", str(out)])
    assert rc == 0
    merged = json.loads(out.read_text())
    assert any(str(e.get("name", "")).startswith("device.")
               for e in merged["traceEvents"])


# ---------------------------------------------------------------------------
# annotation-coverage pass (+ the strip-a-span mutation).
# ---------------------------------------------------------------------------

def test_annotation_coverage_repo_clean():
    from triton_dist_tpu.analysis import run_passes
    findings = run_passes(names=["annotation-coverage"])
    assert findings == [], [f.render() for f in findings]


def test_mutant_stripped_annotation_is_unlabeled(tmp_path):
    """Mutation test: strip the router's per-invocation annotation →
    the pass reports devprof.unlabeled with a file anchor."""
    from triton_dist_tpu.analysis import lint_annotations
    from triton_dist_tpu.resilience import router
    src = open(router.__file__.rstrip("c")).read()
    mut = src.replace("_op_annotation(op, impl, fallback_impl)",
                      "contextlib.nullcontext()")
    assert mut != src, "mutation site moved — update this test"
    p = tmp_path / "router.py"
    p.write_text(mut)
    findings = lint_annotations.check_router(p)
    assert [f.code for f in findings] == ["devprof.unlabeled"]
    assert findings[0].file == str(p) and findings[0].line
    # The clean source passes.
    p2 = tmp_path / "router_ok.py"
    p2.write_text(src)
    assert lint_annotations.check_router(p2) == []


def test_mutant_helper_without_device_prefix_is_unlabeled(tmp_path):
    """Renaming the label out of the device.* namespace is the same
    silent-misattribution bug as stripping the with — caught too."""
    from triton_dist_tpu.analysis import lint_annotations
    from triton_dist_tpu.resilience import router
    src = open(router.__file__.rstrip("c")).read()
    mut = src.replace('f"device.{op}.{branch}"', 'f"op.{op}.{branch}"')
    assert mut != src
    p = tmp_path / "router.py"
    p.write_text(mut)
    assert [f.code for f in lint_annotations.check_router(p)] \
        == ["devprof.unlabeled"]


def test_mutant_sampler_without_step_label(tmp_path):
    from triton_dist_tpu.analysis import lint_annotations
    dev_src = open(devprof.__file__.rstrip("c")).read()
    mut = dev_src.replace('STEP_LABEL = "device.step"',
                          'STEP_LABEL = "step"')
    assert mut != dev_src
    p = tmp_path / "devprof.py"
    p.write_text(mut)
    import triton_dist_tpu.serving.scheduler as sched_mod
    findings = lint_annotations.check_sampler(p, sched_mod.__file__)
    # The de-namespaced label ALSO breaks the per-path attribution
    # (step_label("mega") no longer yields device.step.mega), so both
    # finding classes fire.
    codes = [f.code for f in findings]
    assert "devprof.step_unlabeled" in codes, codes


def test_summarize_keeps_step_paths_separate():
    """The parser attributes device.step.mega / device.step.plain
    windows to SEPARATE ops (router device.<op>.<branch> labels still
    blend branches into one op) — the split the auto decode-path
    policy reads."""
    events = [
        {"name": "device.step.mega", "ts_us": 0.0, "dur_us": 100.0,
         "pid": 1, "tid": 1, "device": False},
        {"name": "fusion.a", "ts_us": 10.0, "dur_us": 40.0,
         "pid": 2, "tid": 1, "device": True},
        {"name": "device.step.plain", "ts_us": 200.0, "dur_us": 100.0,
         "pid": 1, "tid": 1, "device": False},
        {"name": "fusion.b", "ts_us": 210.0, "dur_us": 80.0,
         "pid": 2, "tid": 1, "device": True},
        {"name": "device.ag_gemm.fused", "ts_us": 400.0,
         "dur_us": 50.0, "pid": 1, "tid": 1, "device": False},
        {"name": "device.ag_gemm.xla", "ts_us": 500.0, "dur_us": 50.0,
         "pid": 1, "tid": 1, "device": False},
    ]
    ops = devprof.summarize(events)["ops"]
    assert set(ops) == {"step.mega", "step.plain", "ag_gemm"}
    assert ops["step.mega"]["compute_ms"] == pytest.approx(0.04)
    assert ops["step.plain"]["compute_ms"] == pytest.approx(0.08)
    assert devprof.step_label() == "device.step"
    assert devprof.step_label("mega") == "device.step.mega"


def test_mutant_step_label_blends(tmp_path):
    """Mutation test (ISSUE 11): collapse step_label(kind) back to the
    bare STEP_LABEL → the annotation-coverage pass reports
    devprof.step_path_blended (the auto policy would arbitrate on a
    blended device.step gauge)."""
    from triton_dist_tpu.analysis import lint_annotations
    dev_src = open(devprof.__file__.rstrip("c")).read()
    mut = dev_src.replace(
        'return f"{STEP_LABEL}.{kind}" if kind else STEP_LABEL',
        'return STEP_LABEL')
    assert mut != dev_src, "mutation site moved — update this test"
    p = tmp_path / "devprof.py"
    p.write_text(mut)
    import triton_dist_tpu.serving.scheduler as sched_mod
    findings = lint_annotations.check_sampler(p, sched_mod.__file__)
    assert [f.code for f in findings] == ["devprof.step_path_blended"]


def test_mutant_summarize_blends_step_paths(tmp_path):
    """Mutation test: a parser that regexes clean but BLENDS the step
    windows (two-segment rule stripped from _label_op) is caught by
    the behavioral check, not just pattern matching."""
    from triton_dist_tpu.analysis import lint_annotations
    dev_src = open(devprof.__file__.rstrip("c")).read()
    mut = dev_src.replace(
        'if parts[0] == "step" and len(parts) > 1 and parts[1]:',
        'if False:')
    assert mut != dev_src, "mutation site moved — update this test"
    p = tmp_path / "devprof.py"
    p.write_text(mut)
    import triton_dist_tpu.serving.scheduler as sched_mod
    findings = lint_annotations.check_sampler(p, sched_mod.__file__)
    assert [f.code for f in findings] == ["devprof.step_path_blended"]


def test_mutant_scheduler_without_kind(tmp_path):
    """Mutation test: a scheduler that stops bracketing the shared
    decode step with the per-path step_label annotation blends mega
    and plain decode time into the whole-iteration window."""
    from triton_dist_tpu.analysis import lint_annotations
    import triton_dist_tpu.serving.scheduler as sched_mod
    sched_src = open(sched_mod.__file__.rstrip("c")).read()
    mut = sched_src.replace("annotate(devprof.step_label(kind))",
                            "contextlib.nullcontext()")
    assert mut != sched_src, "mutation site moved — update this test"
    p = tmp_path / "scheduler.py"
    p.write_text(mut)
    findings = lint_annotations.check_sampler(
        devprof.__file__.rstrip("c"), p)
    assert [f.code for f in findings] == ["devprof.step_path_blended"]


# ---------------------------------------------------------------------------
# CLI module entry (subprocess, no jax import needed in profile_export).
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_profile_export_module_entry(tmp_path):
    empty = tmp_path / "none"
    empty.mkdir()
    r = subprocess.run(
        [sys.executable, "-m", "triton_dist_tpu.tools.profile_export",
         str(empty), "--validate"],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0 and "no profile captures" in r.stdout
