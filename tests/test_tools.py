"""Tools tests: autotuner, perf models, profiler, AOT export (reference
L9 coverage; the reference has no dedicated tool tests — we add them,
SURVEY.md §4 notes CI gaps)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.tools import (
    aot_compile_spaces, aot_export, aot_load, autotune,
    estimate_all_gather_time_ms, estimate_all_reduce_time_ms,
    estimate_gemm_sol_time_ms, get_chip_spec, group_profile,
    load_artifact, overlap_efficiency, save_artifacts, trace_files)
from triton_dist_tpu.tools import autotuner


def test_autotune_picks_fastest():
    import time

    def make_fn(sleep_ms):
        def fn():
            time.sleep(sleep_ms / 1e3)
            return None
        return fn

    res = autotune(make_fn, [{"sleep_ms": 5}, {"sleep_ms": 0.1},
                             {"sleep_ms": 3}], iters=3, warmup_iters=1)
    assert res.config == {"sleep_ms": 0.1}
    assert len(res.all_ms) == 3


def test_autotune_cache():
    autotuner.clear_cache()
    calls = []

    def make_fn(v):
        calls.append(v)
        return lambda: None

    r1 = autotune(make_fn, [{"v": 1}, {"v": 2}], key="k", iters=1,
                  warmup_iters=1)
    n = len(calls)
    r2 = autotune(make_fn, [{"v": 1}, {"v": 2}], key="k", iters=1,
                  warmup_iters=1)
    assert len(calls) == n and r1 == r2


def test_autotune_disk_cache(tmp_path, monkeypatch):
    """A sweep persisted to disk is served without re-running configs in
    a fresh process (simulated by clearing the in-memory cache)."""
    monkeypatch.setenv("TDT_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    autotuner.clear_cache()
    calls = []

    def make_fn(v):
        calls.append(v)
        return lambda: None

    cfgs = [{"v": 1}, {"v": 2}]
    r1 = autotune(make_fn, cfgs, key="dk", iters=1, warmup_iters=1)
    n = len(calls)
    autotuner.clear_cache()  # "new process"
    r2 = autotune(make_fn, cfgs, key="dk", iters=1, warmup_iters=1)
    assert len(calls) == n, "disk hit must not re-run configs"
    assert r1.config == r2.config
    # corrupt file degrades to a re-sweep, not an error
    (tmp_path / "tune.json").write_text("{not json")
    autotuner.clear_cache()
    r3 = autotune(make_fn, cfgs, key="dk", iters=1, warmup_iters=1)
    assert len(calls) > n and r3.config in cfgs


def test_autotune_disk_cache_stale_config_resweeps(tmp_path, monkeypatch):
    """A persisted winner absent from the current candidate list (config
    table changed, e.g. a tightened VMEM filter) must NOT be served."""
    monkeypatch.setenv("TDT_AUTOTUNE_CACHE", str(tmp_path / "t.json"))
    autotuner.clear_cache()
    calls = []

    def make_fn(v):
        calls.append(v)
        return lambda: None

    autotune(make_fn, [{"v": 1}, {"v": 2}], key="sk", iters=1,
             warmup_iters=1)
    n = len(calls)
    autotuner.clear_cache()
    r = autotune(make_fn, [{"v": 3}, {"v": 4}], key="sk", iters=1,
                 warmup_iters=1)
    assert len(calls) > n and r.config in ({"v": 3}, {"v": 4})


def test_autotune_disk_cache_failed_config_roundtrip(tmp_path, monkeypatch):
    """inf scores (failed configs) survive the JSON round trip as
    losers."""
    monkeypatch.setenv("TDT_AUTOTUNE_CACHE", str(tmp_path / "t.json"))
    autotuner.clear_cache()

    def make_fn(v):
        if v == 1:
            raise RuntimeError("boom")
        return lambda: None

    r1 = autotune(make_fn, [{"v": 1}, {"v": 2}], key="fk", iters=1,
                  warmup_iters=1)
    autotuner.clear_cache()
    r2 = autotune(make_fn, [{"v": 1}, {"v": 2}], key="fk", iters=1,
                  warmup_iters=1)
    assert r2.config == r1.config == {"v": 2}
    assert r2.all_ms[0] == float("inf")


def test_chip_spec_unknown_accelerator_is_an_error():
    """Only a CPU device gets the simulator spec; an accelerator the
    table does not know raises, naming its device_kind — a roofline
    against made-up peaks is worse than none."""
    import types

    import pytest
    from triton_dist_tpu.tools import perf_model as pm

    def dev(platform, kind):
        return types.SimpleNamespace(platform=platform, device_kind=kind)

    assert pm.get_chip_spec(dev("cpu", "cpu")) is pm.CPU_SIM_SPEC
    assert pm.get_chip_spec(dev("tpu", "TPU v5 lite")).name == "v5e"
    assert pm.get_chip_spec(dev("tpu", "TPU v6 lite")).name == "v6e"
    with pytest.raises(ValueError, match="TPU v9 hyper"):
        pm.get_chip_spec(dev("tpu", "TPU v9 hyper"))
    with pytest.raises(ValueError, match="NVIDIA H100"):
        pm.get_chip_spec(dev("gpu", "NVIDIA H100"))


def test_perf_model_monotonic():
    spec = get_chip_spec()
    t1 = estimate_gemm_sol_time_ms(1024, 1024, 1024, spec)
    t2 = estimate_gemm_sol_time_ms(2048, 2048, 2048, spec)
    assert 0 < t1 < t2
    a1 = estimate_all_gather_time_ms(1 << 20, 8, spec)
    a2 = estimate_all_gather_time_ms(1 << 22, 8, spec)
    assert 0 < a1 < a2
    assert estimate_all_reduce_time_ms(1 << 20, 8, spec) > 0
    assert overlap_efficiency(1.0, 1.0) == 2.0
    assert overlap_efficiency(2.0, 0.0) == 1.0


def test_cost_model_ranks_measured_winner():
    """The roofline cost model's ranking must be consistent with the
    round-5 measured hw_bench_headline.out winner: at the bench shape
    (2048, 4096, 4096) bf16 world=1 on TPU v5 lite, the hbm_kt
    (128, 256) config — the measured tuned winner — must survive
    pruning and rank first among the hbm_kt candidates; big-tile hbm
    configs (the measured best variant class) must rank above it."""
    from triton_dist_tpu.ops.allgather_gemm import ag_gemm_configs
    from triton_dist_tpu.ops.common import TUNED_VMEM_BUDGET
    from triton_dist_tpu.tools import perf_model as pm

    from triton_dist_tpu.ops.common import DEFAULT_VMEM_BUDGET
    spec = pm.CHIP_SPECS["v5 lite"]
    m = rows = 2048
    k = n_loc = 4096
    kt_target = {"variant": "hbm_kt", "block_m": 128, "block_k": 256}

    def cost(c):
        return pm.estimate_ag_gemm_cost(
            c, m=m, rows=rows, k=k, n_loc=n_loc, itemsize=2, world=1,
            spec=spec).total_ms

    # (a) Under the r5 sweep conditions (default-budget table — exactly
    # what produced the measured winner) the kt config is top of its
    # tier and stays reachable for the default-path clamps.
    dflt = ag_gemm_configs(m, rows, k, n_loc, 2, DEFAULT_VMEM_BUDGET)
    kts = [c for c in dflt if c["variant"] == "hbm_kt"]
    assert kt_target in kts
    assert min(kts, key=cost) == kt_target
    # (b) Absolute consistency with hw_bench_headline.out: the model's
    # prediction for the measured kt winner sits on its 0.892 ms, and
    # the hbm-NB class it measures as faster (0.515 ms) ranks faster.
    assert cost(kt_target) == pytest.approx(0.892, rel=0.25)
    full = ag_gemm_configs(m, rows, k, n_loc, 2, TUNED_VMEM_BUDGET,
                           tier_caps=False)
    best_hbm = min((c for c in full if c["variant"] == "hbm"), key=cost)
    assert cost(best_hbm) < cost(kt_target)

    # (c) The sweep's pruned table keeps an hbm_kt fallback and the
    # model's favorite, at >= 4x search-space reduction (acceptance).
    pruned, n_before = pm.prune_configs(
        full, cost, always_keep=lambda c: c["variant"] == "hbm_kt")
    assert any(c["variant"] == "hbm_kt" for c in pruned)
    assert best_hbm in pruned
    assert n_before >= 4 * len(pruned), (n_before, len(pruned))


def test_cost_model_prefers_big_tiles():
    """The measured round-5 hypothesis encoded: per-tile Mosaic
    overhead makes small tiles lose (docs/perf.md 'Why 135 TFLOPS')."""
    from triton_dist_tpu.tools import perf_model as pm
    spec = pm.CHIP_SPECS["v5 lite"]

    def cost(bm, bn):
        return pm.estimate_ag_gemm_cost(
            {"variant": "hbm", "block_m": bm, "block_n": bn},
            m=2048, rows=2048, k=4096, n_loc=4096, itemsize=2, world=1,
            spec=spec).total_ms

    assert cost(256, 1024) < cost(128, 512) < cost(128, 128)


def test_cost_model_overlap_pct():
    """Overlap accounting: no comm -> 100 (nothing exposed); a
    comm-dominated shape exposes most of its ring time; bidirectional
    halves the comm and can only improve the hidden fraction."""
    from triton_dist_tpu.tools import perf_model as pm
    spec = pm.CHIP_SPECS["v5 lite"]
    kw = dict(m=2048, rows=2048, k=4096, n_loc=4096, itemsize=2,
              spec=spec)
    c1 = pm.estimate_ag_gemm_cost({"variant": "vmem"}, world=1, **kw)
    assert c1.overlap_pct == 100.0 and c1.exposed_comm_ms == 0.0
    # world 8 of the same global shape: comm-heavier per-chip
    kw8 = dict(m=2048, rows=256, k=4096, n_loc=512, itemsize=2,
               spec=spec)
    uni = pm.estimate_ag_gemm_cost(
        {"variant": "hbm", "block_m": 256, "block_n": 512},
        world=8, ring_dirs=1, **kw8)
    bi = pm.estimate_ag_gemm_cost(
        {"variant": "hbm", "block_m": 256, "block_n": 512},
        world=8, ring_dirs=2, **kw8)
    assert 0.0 <= uni.overlap_pct <= 100.0
    assert bi.comm_ms < uni.comm_ms          # half the hops
    assert bi.total_ms <= uni.total_ms
    # breakdown is self-consistent
    assert bi.total_ms == pytest.approx(bi.compute_ms
                                        + bi.exposed_comm_ms)


def test_prune_configs_logs_counts():
    """record_prune lands the before/after pair in LAST_PRUNE and the
    obs gauges (the acceptance 'candidate count before/after logged')."""
    from triton_dist_tpu import obs
    from triton_dist_tpu.tools import autotuner
    obs.disable()
    obs.enable()
    try:
        autotuner.record_prune("ag_gemm", 16, 4)
        assert autotuner.LAST_PRUNE["ag_gemm"] == (16, 4)
        g = obs.snapshot()["gauges"]
        assert g["autotune.ag_gemm.candidates_before"] == 16.0
        assert g["autotune.ag_gemm.candidates_after"] == 4.0
    finally:
        obs.disable()


def test_group_profile_writes_trace(tmp_path):
    with group_profile("t1", str(tmp_path)):
        jnp.dot(jnp.ones((64, 64)), jnp.ones((64, 64))).block_until_ready()
    files = trace_files("t1", str(tmp_path))
    assert files, "no trace artifacts written"


def test_aot_export_roundtrip():
    def fn(x, y):
        return jnp.dot(x, y) + 1.0

    a = jnp.ones((8, 16), jnp.float32)
    b = jnp.ones((16, 4), jnp.float32)
    blob = aot_export(fn, (a, b))
    assert isinstance(blob, bytes) and len(blob) > 0
    loaded = aot_load(blob)
    np.testing.assert_allclose(np.asarray(loaded(a, b)),
                               np.asarray(fn(a, b)))


def test_aot_export_symbolic_dynamic_m():
    """One symbolic-M artifact serves multiple batch sizes (the
    reference's per-signature AOT spaces over M, compile_aot.py:61)."""
    from triton_dist_tpu.tools.aot import aot_export_symbolic
    w = jax.random.normal(jax.random.PRNGKey(0), (16, 8), jnp.float32)

    def fn(x):
        return x @ w

    blob = aot_export_symbolic(fn, [("m, 16", jnp.float32)])
    loaded = aot_load(blob)
    for m in (4, 32):
        x = jax.random.normal(jax.random.PRNGKey(m), (m, 16), jnp.float32)
        np.testing.assert_allclose(np.asarray(loaded(x)),
                                   np.asarray(x) @ np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


def test_aot_compile_spaces(tmp_path):
    a = jnp.ones((4, 4), jnp.float32)

    @aot_compile_spaces({"square": (a,)})
    def f(x):
        return x * x

    arts = f.aot_artifacts()
    assert set(arts) == {"square"}
    paths = save_artifacts(arts, str(tmp_path))
    assert os.path.exists(paths[0])
    g = load_artifact(paths[0])
    np.testing.assert_allclose(np.asarray(g(a)), np.asarray(a * a))


def test_perf_model_auto_crossovers():
    """AUTO method selection turns on perf-model crossovers, not
    hardcoded byte thresholds (VERDICT r2 next 9; reference
    comm_perf_model.py:94-116, allreduce.py:1101-1127)."""
    from triton_dist_tpu.tools.perf_model import (
        CHIP_SPECS, estimate_all_gather_time_ms,
        estimate_full_mesh_push_time_ms)
    from triton_dist_tpu.ops.allgather import (
        AllGatherMethod, get_auto_all_gather_method)
    from triton_dist_tpu.ops.allreduce import (
        AllReduceMethod, get_auto_allreduce_method)

    spec = CHIP_SPECS["v5e"]
    # Latency-bound: one launch beats per-step ring overhead.
    assert get_auto_all_gather_method(8, 4 * 1024, spec) \
        is AllGatherMethod.FULL_MESH_PUSH
    # Bandwidth-bound: through-traffic sinks full-mesh; ring wins.
    assert get_auto_all_gather_method(8, 64 * 1024 * 1024, spec) \
        is AllGatherMethod.RING_BIDIR
    # The crossover exists and is monotone: find it by bisection and
    # check the model actually flips there.
    lo, hi = 4 * 1024, 64 * 1024 * 1024
    while hi - lo > 1024:
        mid = (lo + hi) // 2
        if (estimate_full_mesh_push_time_ms(mid, 8, spec)
                <= estimate_all_gather_time_ms(mid, 8, spec)):
            lo = mid
        else:
            hi = mid
    assert 16 * 1024 < hi < 16 * 1024 * 1024  # physically plausible

    assert get_auto_allreduce_method(8, 16 * 1024, spec) \
        is AllReduceMethod.ONE_SHOT
    assert get_auto_allreduce_method(8, 64 * 1024 * 1024, spec) \
        is AllReduceMethod.TWO_SHOT
    # w<=2 degenerates to the single-hop method regardless of size.
    assert get_auto_all_gather_method(2, 64 * 1024 * 1024, spec) \
        is AllGatherMethod.FULL_MESH_PUSH


def test_reduce_scatter_auto_crossover():
    from triton_dist_tpu.ops.reduce_scatter import (
        ReduceScatterMethod, create_reduce_scatter_context)
    import numpy as np
    import jax
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:8]), ("tp",))
    ctx = create_reduce_scatter_context(mesh, "tp")
    assert ctx.resolve_method(8 * 1024) is ReduceScatterMethod.ONE_SHOT
    assert ctx.resolve_method(64 * 1024 * 1024) is ReduceScatterMethod.RING


def test_autotune_isolates_failing_config():
    """A config that fails to compile/run scores inf instead of killing
    the sweep (aggressive-tier configs rely on this)."""
    from triton_dist_tpu.tools.autotuner import autotune, clear_cache
    clear_cache()

    def make_fn(ok):
        if not ok:
            def boom():
                raise RuntimeError("synthetic compile failure")
            return boom

        def fine():
            return jnp.ones((8,)).sum()
        return fine

    res = autotune(make_fn, [{"ok": False}, {"ok": True}],
                   key="isolate-test", iters=2, warmup_iters=1)
    assert res.config == {"ok": True}
    assert res.all_ms[0] == float("inf")

    import pytest as _pytest
    with _pytest.raises(RuntimeError, match="every autotune config"):
        autotune(make_fn, [{"ok": False}], key="isolate-test-2",
                 iters=2, warmup_iters=1)


def test_disk_cache_device_kind_quarantine(tmp_path, monkeypatch):
    """A winner persisted under one device kind must NEVER be served
    under another (VERDICT r4 next-6): a CPU interpret-mode verdict
    (where ring beats fused by 100-300x of pure artifact) leaking onto
    TPU would silently pin the wrong impl on chip. The disk key is
    '{device_kind}::{key}'."""
    monkeypatch.setenv("TDT_AUTOTUNE_CACHE", str(tmp_path / "t.json"))
    autotuner.clear_cache()
    calls = []

    def make_fn(v):
        calls.append(v)
        return lambda: None

    cfgs = [{"v": 1}, {"v": 2}]
    # Plant a cpu-keyed winner by sweeping under the real (cpu) backend.
    r1 = autotune(make_fn, cfgs, key="quar", iters=1, warmup_iters=1)
    assert (tmp_path / "t.json").exists()
    import json
    keys = list(json.loads((tmp_path / "t.json").read_text()))
    assert all("::" in k for k in keys), keys

    # Same key looked up under a FAKE TPU platform: must miss.
    class _Dev:
        device_kind = "TPU v5 lite"

    class _FakeJax:
        @staticmethod
        def devices():
            return [_Dev()]
    real_jax = autotuner.jax
    monkeypatch.setattr(autotuner, "jax", _FakeJax)
    assert autotuner._disk_load("quar") is None
    # And back under the real platform it still hits.
    monkeypatch.setattr(autotuner, "jax", real_jax)
    hit = autotuner._disk_load("quar")
    assert hit is not None and hit.config == r1.config


def test_trace_fallback_multiprocess_refuses_disk(tmp_path, monkeypatch):
    """consult_disk_for_trace returns None on multi-process deployments
    even when a local cache hit exists (ADVICE r4-1: a per-host disk
    consult with no agreement step can bake MISMATCHED collective
    programs across ranks — a hang), and warns once."""
    import warnings

    monkeypatch.setenv("TDT_AUTOTUNE_CACHE", str(tmp_path / "t.json"))
    autotuner.clear_cache()
    autotuner._TRACE_FALLBACK_WARNED.clear()
    autotune(lambda v: (lambda: None), [{"v": 1}], key="mp", iters=1,
             warmup_iters=1)
    assert autotuner._disk_load("mp") is not None  # local hit exists

    class _FakeJax:
        @staticmethod
        def process_count():
            return 2

        @staticmethod
        def devices():
            return autotuner.jax.devices()
    real_jax = autotuner.jax
    monkeypatch.setattr(autotuner, "jax", _FakeJax)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert autotuner.consult_disk_for_trace("mp") is None
        assert autotuner.consult_disk_for_trace("mp") is None  # warn once
    assert len([x for x in w if "multi-process" in str(x.message)]) == 1
    monkeypatch.setattr(autotuner, "jax", real_jax)
    # Single-process: the same consult hits.
    autotuner._TRACE_FALLBACK_WARNED.clear()
    assert autotuner.consult_disk_for_trace("mp") is not None


def test_trace_fallback_miss_warns_once(tmp_path, monkeypatch):
    """A traced auto call with NO cached winner warns once that the
    program baked the default impl for its lifetime (ADVICE r4-4)."""
    import warnings

    monkeypatch.setenv("TDT_AUTOTUNE_CACHE", str(tmp_path / "t.json"))
    autotuner._TRACE_FALLBACK_WARNED.clear()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert autotuner.consult_disk_for_trace("missing_key") is None
        assert autotuner.consult_disk_for_trace("missing_key") is None
    msgs = [x for x in w if "baked" in str(x.message).lower()
            or "bakes" in str(x.message)]
    assert len(msgs) == 1


def test_top_render_dashboard_sections():
    """tools/top.py: the dashboard renders rolling SLOs, burn rates,
    occupancy/pool, and request waterfalls from a
    plain metrics snapshot (no server needed)."""
    from triton_dist_tpu.tools import top
    snap = {
        "gauges": {
            "serving.rolling.ttft_p50_ms": 12.5,
            "serving.rolling.ttft_p99_ms": 80.0,
            "serving.rolling.ttft_n": 42,
            "serving.slo_burn.ttft_p99": 0.2,
            "serving.slo_burn.ttft_p99_slow": 0.1,
            "serving.slo_breached.ttft_p99": 0,
            "serving.batch_occupancy": 3,
            "serving.queue_depth": 1,
            "kv.block_utilization": 0.75,
            "trace.dropped_total": 7,
        },
        "counters": {"serving.admitted": 10, "serving.retired": 9},
        "requests": [{"rid": 4, "total_ms": 20.0,
                      "segments": {"queue_wait_ms": 1.0,
                                   "prefill_ms": 9.0,
                                   "decode_ms": 10.0},
                      "tokens": 5, "cached_tokens": 2}],
    }
    out = top.render(snap)
    assert "rolling latency" in out and "p50 12.500" in out
    assert "slo burn rates" in out and "ttft_p99" in out
    assert "BREACH" not in out
    assert "block utilization" in out and "0.750" in out
    assert "rid 4" in out and "prefill 9" in out
    assert "TDT_TRACE_RING" in out
    snap["gauges"]["serving.slo_breached.ttft_p99"] = 1
    assert "BREACH" in top.render(snap)
    assert "(no serving metrics yet)" in top.render(
        {"gauges": {}, "counters": {}})


def test_top_and_report_render_device_time_section():
    """tools/top.py + tools/report.py: the device-time truth gauges
    (obs.devprof) render as their own section — measured per-op
    compute/comm, overlap + drift, unlabeled warning, last profile
    path (docs/observability.md "Device-time truth")."""
    from triton_dist_tpu.tools import report, top
    snap = {
        "gauges": {
            "device.ag_gemm.total_ms": 2.0,
            "device.ag_gemm.compute_ms": 1.2,
            "device.ag_gemm.comm_ms": 0.8,
            "device.step.total_ms": 5.0,
            "device.step.compute_ms": 4.0,
            "device.step.comm_ms": 0.0,
            "device.unlabeled_ms": 0.25,
            "comms.ag_gemm.overlap_pct_measured": 50.0,
            "comms.ag_gemm.exposed_comm_ms_measured": 0.4,
            "comms.ag_gemm.overlap_drift_pct": -40.0,
        },
        "counters": {"profile.captures": 3, "profile.parsed": 3},
        "devprof": {"last_profile": "/tmp/x/pump_1/host0",
                    "last_reason": "breach_slo_ttft_p99",
                    "ops": ["ag_gemm", "step"]},
    }
    out = top.render(snap)
    assert "device time (measured)" in out
    assert "ag_gemm" in out and "overlap 50" in out
    assert "drift -40" in out
    assert "step" in out
    assert "annotation-coverage" in out          # unlabeled warning
    assert "/tmp/x/pump_1/host0" in out
    md = report.render_devprof(snap, snap["devprof"])
    assert "#### device time (measured)" in md
    assert "comms.ag_gemm.overlap_drift_pct" in md and "-40" in md
    assert "profile.captures" in md
    assert "last_profile" in md and "breach_slo_ttft_p99" in md
    assert "annotation-coverage" in md           # unlabeled warning
    # The telemetry renderer routes device.*/profile.* rows into the
    # section instead of duplicating them in the scalar table.
    full = report.render_telemetry(snap)
    assert full.count("device.ag_gemm.total_ms") == 1
    # No devprof metrics at all → no section.
    assert report.render_devprof({"gauges": {}, "counters": {}}) == ""
