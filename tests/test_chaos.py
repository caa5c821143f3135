"""Chaos-harness injector verification (testing/chaos.py, ISSUE 15).

Quick tier. Each injector is pinned to EXACTLY the failure signature
and FleetView/breaker transition it claims, against a live fleet —
so the router tests (tests/test_router.py) and the ``serving_router``
bench can trust the faults they inject:

- ``kill_replica``: new connections refuse, in-flight clients see a
  DEAD SOCKET (never a polite error reply), FleetView degrades the
  victim live → stale → down on an injected clock while its sibling
  stays fresh;
- ``wedge_pump``: requests stall (client timeout) while the replica
  KEEPS answering the health verb — the failure class liveness
  checks cannot catch (the router's dispatch deadline/breaker does);
  releasing the wedge restores service;
- ``ChaosProxy`` blackhole / drop / delay: scrapes through the proxy
  fail (hang-to-timeout, instant close, reply past the deadline) →
  stale → down, and flipping back to ``forward`` recovers to live —
  without ever touching the replica behind it;
- ``ChaosProxy.sever``: a mid-request connection cut surfaces as a
  socket error on the client side.
"""

import socket
import threading
import time

import jax.numpy as jnp
import pytest

from triton_dist_tpu.obs.fleet import FleetView
from triton_dist_tpu.serving import ChatClient, ModelServer
from triton_dist_tpu.testing import chaos


class _FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


@pytest.fixture(scope="module")
def tiny(request):
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from triton_dist_tpu.models import DenseLLM, ModelConfig
    mesh = Mesh(np.array(jax.devices()), ("tp",))
    cfg = ModelConfig(hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=8,
                      num_key_value_heads=8, head_dim=4, vocab_size=64,
                      max_position_embeddings=64, dtype=jnp.float32)
    model = DenseLLM(cfg, mesh=mesh, axis="tp", impl="xla")
    return model, model.init(jax.random.PRNGKey(0))


def _server(tiny, rid, **kw):
    from triton_dist_tpu.models import Engine
    model, params = tiny
    eng = Engine(model, batch=2, max_seq=64, prefill_mode="xla_ar",
                 decode_mode="gemm_ar")
    return ModelServer(eng, params, port=0, registry="private",
                       replica_id=rid, **kw).start()


def _wait(pred, timeout=30.0, what="condition"):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {what}")


# ---------------------------------------------------------------------------
# kill_replica
# ---------------------------------------------------------------------------

def test_kill_refuses_new_connections_and_transitions_down(tiny):
    """The claimed FleetView transition: live → (kill) → stale →
    down by age, sibling fresh throughout; and the killed listener
    refuses new connections outright."""
    s0 = _server(tiny, "chaos-a")
    s1 = _server(tiny, "chaos-b")
    eps = [(s0.host, s0.port), (s1.host, s1.port)]
    try:
        clock = _FakeClock()
        view = FleetView(eps, stale_s_=5.0, down_s_=20.0, clock=clock)
        assert [r["status"] for r in view.poll()] == ["live", "live"]

        chaos.kill_replica(s1)
        with pytest.raises(OSError):
            socket.create_connection(eps[1], timeout=2.0)

        clock.t += 1.0
        rows = view.poll()
        assert rows[0]["status"] == "live"
        assert rows[1]["status"] == "stale"
        clock.t += 25.0
        rows = view.poll()
        assert rows[0]["status"] == "live"
        assert rows[1]["status"] == "down"
        # live traffic still lands on the survivor
        c = ChatClient(s0.host, s0.port, timeout=60)
        assert "tokens" in c.generate_ids([[1, 2]], gen_len=2)
        c.close()
    finally:
        s0.stop()
        s1.stop()


def test_kill_severs_inflight_connection_abruptly(tiny):
    """A client mid-generation on the victim sees a DEAD SOCKET
    (ConnectionError/OSError) — not a structured error reply: a
    killed process sends nothing. This is what lets the router treat
    the kill as a transport failure and re-dispatch."""
    srv = _server(tiny, "chaos-kill")
    try:
        got: dict = {}

        def bg():
            c = ChatClient(srv.host, srv.port, timeout=60)
            try:
                got["resp"] = c.generate_ids([[1, 2, 3]], gen_len=60)
            except OSError as e:
                got["err"] = e
            finally:
                c.close()

        th = threading.Thread(target=bg, daemon=True)
        th.start()
        _wait(lambda: srv.scheduler.inflight() >= 1,
              what="request in flight")
        chaos.kill_replica(srv)
        th.join(timeout=60)
        assert not th.is_alive()
        assert "err" in got, got     # dead socket, not an error reply
        assert isinstance(got["err"], OSError)
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# wedge_pump
# ---------------------------------------------------------------------------

def test_wedge_stalls_requests_health_stays_live(tiny):
    """The wedge's claimed signature: in-flight requests STALL
    (client timeout) while the health verb keeps answering — the
    replica looks alive to liveness checks while serving nothing.
    Release restores service."""
    srv = _server(tiny, "chaos-wedge")
    try:
        c = ChatClient(srv.host, srv.port, timeout=60)
        # Warm the compile OUTSIDE the wedge so the stall below is
        # the wedge, not a cold jit.
        assert "tokens" in c.generate_ids([[1, 2]], gen_len=2)
        with chaos.wedge_pump(srv.scheduler) as w:
            raw = ChatClient(srv.host, srv.port, retry_shed=False)
            with pytest.raises(TimeoutError):
                raw.generate_ids([[3, 4]], gen_len=2, timeout=1.0)
            raw.close()
            assert w.fired.is_set()      # provably wedged, not idle
            # Health still answers — from the handler threads.
            h = c.health()
            assert h["replica_id"] == "chaos-wedge"
            assert srv.scheduler.inflight() >= 1
        # Released: the stalled request finishes server-side; new
        # requests serve normally again.
        _wait(lambda: srv.scheduler.inflight() == 0,
              what="wedge drained")
        assert "tokens" in c.generate_ids([[5, 6]], gen_len=2)
        c.close()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# ChaosProxy: blackhole / drop / delay / sever
# ---------------------------------------------------------------------------

@pytest.fixture()
def proxied(tiny):
    srv = _server(tiny, "chaos-proxy")
    proxy = chaos.ChaosProxy((srv.host, srv.port))
    yield srv, proxy
    proxy.stop()
    srv.stop()


def test_proxy_forward_is_transparent(proxied):
    srv, proxy = proxied
    c = ChatClient(*proxy.endpoint, timeout=60)
    resp = c.generate_ids([[1, 2]], gen_len=2)
    assert "tokens" in resp
    assert c.health()["replica_id"] == "chaos-proxy"
    c.close()


def test_blackhole_scrape_times_out_stale_then_down_then_recovers(
        proxied):
    """Blackhole: the scrape hangs to its timeout (connection
    accepted, nothing answers) → stale → down by age; forward mode
    recovers to live. The replica itself is never touched."""
    srv, proxy = proxied
    clock = _FakeClock()
    view = FleetView([proxy.endpoint], timeout_s=0.3, stale_s_=5.0,
                     down_s_=20.0, clock=clock)
    (row,) = view.poll()
    assert row["status"] == "live"

    proxy.set_mode("blackhole")
    clock.t += 1.0
    (row,) = view.poll()
    assert row["status"] == "stale"
    assert row["health"] is not None     # last-good retained
    clock.t += 25.0
    (row,) = view.poll()
    assert row["status"] == "down"

    proxy.set_mode("forward")
    (row,) = view.poll()
    assert row["status"] == "live"       # recovered


def test_drop_mode_fails_connections_fast(proxied):
    srv, proxy = proxied
    proxy.set_mode("drop")
    clock = _FakeClock()
    view = FleetView([proxy.endpoint], timeout_s=1.0, stale_s_=5.0,
                     down_s_=20.0, clock=clock)
    t0 = time.monotonic()
    (row,) = view.poll()
    assert row["status"] == "stale"      # never-scraped, scrape died
    assert row["error"]
    assert time.monotonic() - t0 < 5.0   # fast failure, not a hang


def test_delay_pushes_health_past_the_scrape_deadline(proxied):
    """Delay: the reply arrives LATER than the scrape timeout — the
    injector that drives health responses past the stale/down
    thresholds without killing anything; dropping the delay below
    the deadline recovers."""
    srv, proxy = proxied
    clock = _FakeClock()
    view = FleetView([proxy.endpoint], timeout_s=0.3, stale_s_=5.0,
                     down_s_=20.0, clock=clock)
    assert view.poll()[0]["status"] == "live"

    proxy.set_mode("forward", delay_s=1.0)   # > scrape timeout
    clock.t += 1.0
    (row,) = view.poll()
    assert row["status"] == "stale"

    proxy.set_mode("forward", delay_s=0.0)
    (row,) = view.poll()
    assert row["status"] == "live"


def test_sever_cuts_live_connections_mid_request(proxied):
    """A severed proxied connection surfaces as a socket-level error
    on the client — the mid-request connection-kill injector."""
    srv, proxy = proxied
    c = ChatClient(*proxy.endpoint, timeout=60)
    assert "tokens" in c.generate_ids([[1, 2]], gen_len=2)

    got: dict = {}

    def bg():
        try:
            got["resp"] = c.generate_ids([[1, 2, 3]], gen_len=60)
        except OSError as e:
            got["err"] = e

    th = threading.Thread(target=bg, daemon=True)
    th.start()
    _wait(lambda: srv.scheduler.inflight() >= 1,
          what="request in flight")
    assert proxy.sever() >= 1
    # The client may retry once through the proxy before it gives up,
    # which can take its whole 60 s timeout: wait past that, not as
    # long as it (the join raced the client's own timeout and lost on
    # a loaded machine, PR 35).
    th.join(timeout=150)
    assert not th.is_alive()
    assert "err" in got, got
    c.close()


def test_proxy_rejects_unknown_mode(proxied):
    _, proxy = proxied
    with pytest.raises(ValueError, match="unknown chaos mode"):
        proxy.set_mode("explode")
