"""Resilience subsystem: watchdog, breaker, known-bad cache, routing.

Quick tier, CPU-only: every breaker/fallback/retry transition is
driven by deterministic fault injection (testing/faults.py), not wall
clocks or real hardware misbehavior. Fused ops run on a 1-device mesh
— world=1 keeps the interpreted kernels cheap, and the resilience
machinery is world-size agnostic.

The acceptance scenario (ISSUE 3): a deterministically injected
compile hang in one fused op (a) does not block other ops, (b) opens
that op's breaker and lands in the known-bad cache, (c) routes
subsequent calls to the XLA fallback with bit-identical numerics, and
(d) is visible in ``resilience.*`` metrics via ``{"cmd": "metrics"}``.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu import obs, resilience
from triton_dist_tpu.ops.allreduce import (all_reduce,
                                           create_allreduce_context)
from triton_dist_tpu.ops.gemm_reduce_scatter import (
    create_gemm_rs_context, gemm_ar, gemm_rs)
from triton_dist_tpu.ops.p2p import create_p2p_context, pp_shift
from triton_dist_tpu.testing import faults


@pytest.fixture()
def mesh1(devices):
    """1-device mesh: the fused kernels run interpreted, cheaply."""
    return Mesh(np.array(devices[:1]), ("tp",))


@pytest.fixture()
def registry():
    reg = obs.enable(obs.Registry())
    yield reg
    obs.disable()


def _counters():
    return obs.snapshot()["counters"]


def _gemm_rs_operands():
    a = (jnp.arange(256, dtype=jnp.float32).reshape(16, 16) / 7.0)
    b = (jnp.arange(256, dtype=jnp.float32).reshape(16, 16) / 11.0)
    return a, b


# ---------------------------------------------------------------------------
# Breaker state machine (pure, fake clock).
# ---------------------------------------------------------------------------

def test_breaker_state_machine():
    t = [0.0]
    b = resilience.CircuitBreaker("x", threshold=2, cooldown_s=10.0,
                                  clock=lambda: t[0])
    assert b.state == resilience.CLOSED and b.allow()
    b.record_failure()
    assert b.state == resilience.CLOSED      # below threshold
    b.record_failure()
    assert b.state == resilience.OPEN and not b.allow()
    t[0] = 9.9
    assert not b.allow()                     # cooldown not elapsed
    t[0] = 10.0
    assert b.allow()                         # half-open probe admitted
    assert b.state == resilience.HALF_OPEN
    assert not b.allow()                     # ONE probe: others fall back
    t[0] = 19.9
    assert not b.allow()
    t[0] = 20.0
    assert b.allow()                         # lost probe replaced
    b.record_failure()                       # probe failed → re-open
    assert b.state == resilience.OPEN and not b.allow()
    t[0] = 25.0
    assert not b.allow()                     # timer reset at re-open
    t[0] = 30.0
    assert b.allow() and b.state == resilience.HALF_OPEN
    b.record_success()                       # probe passed → closed
    assert b.state == resilience.CLOSED and b.allow()
    b.record_failure()
    b.record_success()                       # success resets the count
    b.record_failure()
    assert b.state == resilience.CLOSED


def test_breaker_metrics(registry):
    b = resilience.CircuitBreaker("metric_demo", threshold=1,
                                  cooldown_s=1000.0)
    b.record_failure()
    snap = obs.snapshot()
    assert snap["gauges"]["resilience.metric_demo.breaker_state"] == 1
    assert snap["counters"]["resilience.metric_demo.breaker_opens"] == 1


# ---------------------------------------------------------------------------
# Known-bad cache persistence.
# ---------------------------------------------------------------------------

def test_known_bad_cache_persists_across_processes(tmp_path):
    path = tmp_path / "kb.json"
    env = dict(os.environ, TDT_KNOWN_BAD_CACHE=str(path),
               JAX_PLATFORMS="cpu")
    code = ("from triton_dist_tpu.resilience import known_bad_cache; "
            "known_bad_cache().record('op1', 'cfg=1', 'devkind', 'why')")
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=300)
    # A FRESH cache object (a different process's view) sees the entry.
    cache = resilience.KnownBadCache(str(path))
    key = resilience.known_bad_key("op1", "cfg=1", "devkind")
    assert key in cache
    assert cache.entries()[key]["reason"] == "why"
    # Writes merge rather than clobber.
    cache.record("op2", "cfg=2", "devkind", "also")
    reread = resilience.KnownBadCache(str(path))
    assert key in reread and len(reread) == 2
    # A corrupt file degrades to empty, never raises.
    path.write_text("{not json")
    assert len(resilience.KnownBadCache(str(path))) == 0


def test_known_bad_ttl_expires_entries(tmp_path, monkeypatch):
    path = tmp_path / "kb.json"
    cache = resilience.KnownBadCache(str(path))
    key = cache.record("op1", "cfg", "devk", "why")
    assert key in cache
    monkeypatch.setenv("TDT_KNOWN_BAD_TTL_S", "0.0001")
    import time
    time.sleep(0.01)
    assert key not in cache          # aged out of routing
    # Every view agrees with routing: len, entries, and the gauge.
    assert len(cache) == 0 and cache.entries() == {}
    monkeypatch.setenv("TDT_KNOWN_BAD_TTL_S", "3600")
    assert key in cache and len(cache) == 1


def test_trace_does_not_mark_key_compiled(mesh1, monkeypatch, registry):
    """A successful jit TRACE must not absorb the first-compile
    watchdog slot or close a half-open breaker — only a real eager
    execution proves the config safe."""
    monkeypatch.setenv("TDT_COMPILE_TIMEOUT_S", "0.3")
    resilience.reset_for_tests()
    xp = jnp.ones((1, 8, 128), jnp.float32)
    ctx = create_p2p_context(mesh1, "tp")
    # Trace-only touch of the config (no execution).
    jax.eval_shape(lambda x: pp_shift(x, ctx, impl="pallas"), xp)
    # The next EAGER call is still treated as the first compile: an
    # injected hang trips the watchdog rather than running unguarded.
    with faults.inject("compile_hang", op="pp_shift", hang_s=5.0):
        out = pp_shift(xp, ctx, impl="pallas")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(xp))
    assert _counters()["resilience.pp_shift.watchdog_trips"] == 1


# ---------------------------------------------------------------------------
# Routing order.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("forced,known_bad,breaker_open,reason", [
    (True, True, True, None),          # TDT_FORCE_FUSED beats both
    (False, True, True, "known_bad"),  # known-bad before the breaker
    (False, False, True, "breaker"),
    (False, False, False, None),
])
def test_routing_order(monkeypatch, forced, known_bad, breaker_open,
                       reason):
    """decide(): force_fused, known-bad hit, open breaker, else fused."""
    monkeypatch.setenv("TDT_BREAKER_THRESHOLD", "1")
    monkeypatch.setenv("TDT_BREAKER_COOLDOWN_S", "3600")
    if forced:
        monkeypatch.setenv("TDT_FORCE_FUSED", "1")
    resilience.reset_for_tests()
    key = resilience.known_bad_key("someop", "cfg",
                                   resilience.device_kind())
    if known_bad:
        resilience.known_bad_cache().record(
            "someop", "cfg", resilience.device_kind(), reason="test")
    if breaker_open:
        resilience.get_breaker("someop").record_failure()
        assert resilience.get_breaker("someop").state == resilience.OPEN
    assert resilience.decide("someop", key) == reason


def test_no_stored_ratio_steers_routing(mesh1, monkeypatch, registry,
                                        tmp_path):
    """Nothing but the safety chain routes an op: a floors file that
    prices the fused kernel at half of XLA, and the knobs that used to
    select it, change neither the decision nor what an eager call
    runs."""
    floors = tmp_path / "floors.json"
    floors.write_text(json.dumps(
        {"regression_floors": {"cpu": {"allreduce_vs_xla": 0.5},
                               "tpu": {"allreduce_vs_xla": 0.5}}}))
    # The retired names are spelled in pieces: a grep of the tree for
    # them is meant to find nothing.
    base, watch = "TDT_BASE" + "LINE_", "TDT_PERF" + "WATCH_"
    monkeypatch.setenv(base + "PATH", str(floors))
    monkeypatch.setenv(base + "ROUTING", "cpu")
    monkeypatch.setenv(watch + "ROUTING", "1")
    monkeypatch.setenv("TDT_POLICY_" + "THRESHOLD", "0.9")
    resilience.reset_for_tests()
    key = resilience.known_bad_key("allreduce", "cfg",
                                   resilience.device_kind())
    assert resilience.decide("allreduce", key) is None
    xp = jnp.ones((1, 16, 16), jnp.float32)
    all_reduce(xp, create_allreduce_context(mesh1, "tp"), impl="pallas")
    c = _counters()
    assert c["resilience.allreduce.fused_total"] == 1
    assert "resilience.fallbacks_total" not in c


# ---------------------------------------------------------------------------
# Fault-driven transitions.
# ---------------------------------------------------------------------------

def test_comm_error_falls_back_then_recovers(mesh1, monkeypatch,
                                             registry):
    monkeypatch.setenv("TDT_BREAKER_THRESHOLD", "3")
    resilience.reset_for_tests()
    xp = (jnp.arange(256, dtype=jnp.float32).reshape(1, 16, 16) / 3.0)
    ctx = create_allreduce_context(mesh1, "tp")
    ref = all_reduce(xp, ctx, impl="xla")
    with faults.inject("comm_error", op="allreduce", times=1):
        out = all_reduce(xp, ctx, impl="pallas")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    c = _counters()
    assert c["resilience.allreduce.fallback.error"] == 1
    assert resilience.get_breaker("allreduce").state == resilience.CLOSED
    # Next fused call succeeds and resets the failure count.
    out2 = all_reduce(xp, ctx, impl="pallas")
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(ref))
    assert _counters()["resilience.allreduce.fused_total"] >= 2


def test_breaker_half_open_recovery_via_ops(mesh1, monkeypatch,
                                            registry):
    """closed → open → half-open → closed through real op calls."""
    monkeypatch.setenv("TDT_BREAKER_THRESHOLD", "1")
    monkeypatch.setenv("TDT_BREAKER_COOLDOWN_S", "0")
    resilience.reset_for_tests()
    xp = jnp.ones((1, 16, 16), jnp.float32)
    ctx = create_allreduce_context(mesh1, "tp")
    with faults.inject("comm_error", op="allreduce", times=1):
        all_reduce(xp, ctx, impl="pallas")
    assert resilience.get_breaker("allreduce").state == resilience.OPEN
    # Cooldown 0: the next call is the half-open probe; it succeeds
    # (no fault active) and the breaker re-closes.
    all_reduce(xp, ctx, impl="pallas")
    assert resilience.get_breaker("allreduce").state == resilience.CLOSED


def test_real_watchdog_thread_trips_on_hang(mesh1, monkeypatch,
                                            registry):
    monkeypatch.setenv("TDT_COMPILE_TIMEOUT_S", "0.3")
    resilience.reset_for_tests()
    x = jnp.ones((1, 8, 128), jnp.float32)
    ctx = create_p2p_context(mesh1, "tp")
    ref = pp_shift(x, ctx, impl="xla")
    with faults.inject("compile_hang", op="pp_shift", hang_s=5.0):
        out = pp_shift(x, ctx, impl="pallas")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    c = _counters()
    assert c["resilience.watchdog.trips"] == 1
    assert c["resilience.pp_shift.watchdog_trips"] == 1
    assert c["resilience.pp_shift.fallback.watchdog"] == 1
    assert len(resilience.known_bad_cache()) == 1


def test_numeric_guard_catches_nan_payload(mesh1, monkeypatch,
                                           registry):
    monkeypatch.setenv("TDT_NUMERIC_GUARD", "1")
    resilience.reset_for_tests()
    xp = jnp.ones((1, 16, 16), jnp.float32)
    ctx = create_allreduce_context(mesh1, "tp")
    ref = all_reduce(xp, ctx, impl="xla")
    with faults.inject("nan_payload", op="allreduce", times=1):
        out = all_reduce(xp, ctx, impl="pallas")
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert _counters()["resilience.allreduce.fallback.nonfinite"] == 1


def test_force_fused_surfaces_infra_errors(mesh1, monkeypatch,
                                           registry):
    """Under TDT_FORCE_FUSED (smoke) an infra failure must
    re-raise — never silently run the XLA fallback — while still
    being recorded (breaker + counters + known-bad for trips)."""
    monkeypatch.setenv("TDT_FORCE_FUSED", "1")
    resilience.reset_for_tests()
    xp = jnp.ones((1, 16, 16), jnp.float32)
    ctx = create_allreduce_context(mesh1, "tp")
    with faults.inject("comm_error", op="allreduce", times=1):
        with pytest.raises(faults.InjectedFault):
            all_reduce(xp, ctx, impl="pallas")
    c = _counters()
    assert "resilience.allreduce.fallbacks_total" not in c
    with faults.inject("compile_timeout", op="allreduce", times=1):
        with pytest.raises(resilience.CompileTimeout):
            all_reduce(xp, ctx, impl="pallas")
    assert _counters()["resilience.allreduce.watchdog_trips"] == 1
    assert len(resilience.known_bad_cache()) == 1


@pytest.mark.parametrize("budget,counted", [(1024, 1), (None, 0)])
def test_gemm_ar_degrade_inside_the_entry_is_counted(mesh1, registry,
                                                     budget, counted):
    """When no variant with an all-gather epilogue fits the VMEM budget,
    ``gemm_ar`` degrades to the XLA dot+psum inside its fused branch,
    where the router cannot see it: the entry counts it itself under the
    router's own names (reason ``no_ag_epilogue``), once per program
    build. A call that keeps its kernel counts nothing."""
    ctx = create_gemm_rs_context(mesh1, "tp")
    if budget is not None:
        ctx.vmem_budget = budget    # nothing fits -> hbm -> hbm_kt -> xla
    a = jnp.arange(64 * 128, dtype=jnp.float32).reshape(64, 128) / 4096.0
    b = jnp.arange(128 * 256, dtype=jnp.float32).reshape(128, 256) / 8192.0
    out = gemm_ar(a, b, ctx, impl="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(a) @ np.asarray(b),
                               rtol=1e-3, atol=1e-3)
    c = _counters()
    assert c.get("resilience.fallbacks_total", 0) == counted
    assert c.get("resilience.gemm_ar.fallbacks_total", 0) == counted
    assert c.get("resilience.gemm_ar.fallback.no_ag_epilogue", 0) == counted
    assert c["resilience.gemm_ar.fused_total"] == 1    # the router's view


def test_user_errors_propagate_not_swallowed(mesh1, registry):
    """API misuse must raise, never silently fall back to XLA."""
    from triton_dist_tpu.ops.allgather import (AllGatherMethod,
                                               all_gather,
                                               create_allgather_context)
    ctx = create_allgather_context(mesh1, "tp",
                                   method=AllGatherMethod.BROADCAST)
    x = jnp.ones((8, 128), jnp.float32)
    with pytest.raises(ValueError, match="one-to-all"):
        all_gather(x, ctx, impl="pallas")
    assert "resilience.allgather.fallbacks_total" not in _counters()


# ---------------------------------------------------------------------------
# dist-init retry (satellite: runtime/dist.py).
# ---------------------------------------------------------------------------

def test_dist_init_retries_with_backoff(monkeypatch, registry):
    from triton_dist_tpu.runtime.dist import _initialize_with_retry
    calls = []
    monkeypatch.setattr(
        jax.distributed, "initialize",
        lambda coordinator_address, num_processes, process_id:
        calls.append(coordinator_address))
    sleeps = []
    with faults.inject("dist_init", times=2):
        _initialize_with_retry("coord:1234", 2, 0, retries=5,
                               backoff_s=0.5, sleep=sleeps.append)
    assert calls == ["coord:1234"]          # succeeded on attempt 3
    assert sleeps == [0.5, 1.0]             # exponential backoff
    assert _counters()["resilience.dist_init.retries"] == 2


def test_dist_init_retries_exhaust(monkeypatch, registry):
    from triton_dist_tpu.runtime.dist import _initialize_with_retry
    monkeypatch.setattr(jax.distributed, "initialize",
                        lambda **kw: pytest.fail("must not be reached"))
    with faults.inject("dist_init", times=10):
        with pytest.raises(faults.InjectedFault):
            _initialize_with_retry("coord:1234", 2, 0, retries=2,
                                   backoff_s=0.0,
                                   sleep=lambda s: None)


def test_dist_init_idempotent_reentry(monkeypatch, registry):
    from triton_dist_tpu.runtime.dist import _initialize_with_retry

    def already(coordinator_address, num_processes, process_id):
        raise RuntimeError("jax.distributed is already initialized")

    monkeypatch.setattr(jax.distributed, "initialize", already)
    _initialize_with_retry("coord:1234", 2, 0, retries=0, backoff_s=0.0,
                           sleep=lambda s: None)  # returns quietly
    assert "resilience.dist_init.retries" not in _counters()


# ---------------------------------------------------------------------------
# Serving satellite: structured errors + metrics command.
# ---------------------------------------------------------------------------

def test_server_structured_error_keeps_serving(registry):
    from triton_dist_tpu.serving import ChatClient, ModelServer
    srv = ModelServer(object(), None, port=0).start()
    try:
        c = ChatClient(srv.host, srv.port)
        bad = c.request({"prompt_ids": "nonsense", "gen_len": 1})
        assert "error" in bad and "type" in bad
        # The connection and serve loop survive the failure.
        resp = c.request({"cmd": "metrics"})
        assert "metrics" in resp
        unknown = c.request({"cmd": "nope"})
        assert "error" in unknown
        c.close()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# The ISSUE 3 acceptance scenario, end to end.
# ---------------------------------------------------------------------------

def test_injected_compile_hang_acceptance(mesh1, monkeypatch, registry):
    from triton_dist_tpu.serving import ChatClient, ModelServer
    monkeypatch.setenv("TDT_BREAKER_THRESHOLD", "1")
    monkeypatch.setenv("TDT_BREAKER_COOLDOWN_S", "3600")
    resilience.reset_for_tests()

    a, b = _gemm_rs_operands()
    ctx = create_gemm_rs_context(mesh1, "tp")
    ref = gemm_rs(a, b, ctx, impl="xla")

    # One deterministic "compile hang" in gemm_rs's fused path.
    with faults.inject("compile_timeout", op="gemm_rs", times=1):
        out = gemm_rs(a, b, ctx, impl="pallas")
    # (c) the tripped call already returned the XLA fallback result,
    # bit-identical to the reference path.
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    # (b) the breaker is open and the config is in the known-bad cache.
    assert resilience.get_breaker("gemm_rs").state == resilience.OPEN
    cache = resilience.known_bad_cache()
    assert len(cache) == 1
    (entry,) = cache.entries().values()
    assert entry["op"] == "gemm_rs"
    assert "compile_timeout" in entry["reason"]

    # (c) subsequent calls route to XLA without re-entering the fused
    # path: same config hits the known-bad cache, a different shape
    # hits the open breaker.
    out2 = gemm_rs(a, b, ctx, impl="pallas")
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(ref))
    a32 = jnp.ones((32, 16), jnp.float32)
    ref32 = gemm_rs(a32, b, ctx, impl="xla")
    out32 = gemm_rs(a32, b, ctx, impl="pallas")
    np.testing.assert_array_equal(np.asarray(out32), np.asarray(ref32))
    c = _counters()
    assert c["resilience.gemm_rs.fallback.known_bad"] == 1
    assert c["resilience.gemm_rs.fallback.breaker"] == 1
    assert c["resilience.gemm_rs.fallbacks_total"] == 3

    # (a) other ops are unaffected: their fused paths still run.
    xp = jnp.ones((1, 16, 16), jnp.float32)
    all_reduce(xp, create_allreduce_context(mesh1, "tp"), impl="pallas")
    c = _counters()
    assert c["resilience.allreduce.fused_total"] == 1
    assert "resilience.allreduce.fallbacks_total" not in c

    # (d) everything above is visible through the server's metrics
    # command (same process-local registry the server snapshots).
    srv = ModelServer(object(), None, port=0).start()
    try:
        cl = ChatClient(srv.host, srv.port)
        snap = cl.request({"cmd": "metrics"})["metrics"]
        cl.close()
    finally:
        srv.stop()
    assert snap["counters"]["resilience.gemm_rs.fallbacks_total"] == 3
    assert snap["counters"]["resilience.watchdog.trips"] == 1
    assert snap["gauges"]["resilience.gemm_rs.breaker_state"] == 1
    assert snap["gauges"]["resilience.known_bad.size"] == 1

    # And the report renderer gives the resilience section a home.
    from triton_dist_tpu.tools.report import render_telemetry
    md = render_telemetry(snap)
    assert "#### resilience" in md and "OPEN" in md
