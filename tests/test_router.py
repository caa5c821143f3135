"""Fault-tolerant replica router (serving/router.py, ISSUE 15).

Quick tier. Covered here:

- basic routing: greedy tokens through the router are bit-identical
  to a direct replica's, responses carry ``replica`` + ``trace_id``
  and NO ``failovers`` key on the clean path; ``router_status`` /
  ``metrics`` verbs;
- the ACCEPTANCE scenario: three replicas, one killed mid-traffic-
  window → zero failed client requests, every in-flight request
  re-dispatched (``failovers >= 1`` observed), the victim marked
  ``down`` within the configured age, a validated flight dump, and
  ONE trace ID spanning both the dead and the answering replica;
- wedged-replica handling: requests fail over on the dispatch
  deadline while the victim's health verb stays live (the breaker —
  not liveness — catches it), the breaker opens, and the half-open
  probe re-closes it after recovery;
- fleet-level load shed: every replica draining/saturated → one
  structured ``queue_full`` with a ``retry_after_ms`` hint;
- graceful drain: the server ``drain`` verb + scheduler in-flight
  accounting, and live ``router_remove``/``router_add``;
- client fault-awareness (satellites): multi-endpoint ChatClient and
  ``fanout`` skip dead endpoints with a single retry on the next;
  ``retry_after_ms`` is honored with one sleep-and-retry;
- the regress gate (``check_router_wellformed``) and the dashboard
  surfaces (``fleet_top.render_router``, ``report.render_router``).
"""

import json
import socket
import socketserver
import threading
import time

import jax.numpy as jnp
import pytest

from triton_dist_tpu.serving import ChatClient, ModelServer, RouterServer
from triton_dist_tpu.serving.client import fanout
from triton_dist_tpu.testing import chaos


@pytest.fixture(scope="module")
def tiny():
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


def _router(eps, **kw):
    kw.setdefault("registry", "private")
    kw.setdefault("poll_s", 0.05)
    kw.setdefault("fleet_kwargs", {"stale_s_": 0.5, "down_s_": 1.5,
                                   "timeout_s": 2.0})
    return RouterServer(eps, **kw).start()


def _wait(pred, timeout=30.0, what="condition"):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        v = pred()
        if v:
            return v
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {what}")


def _dead_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------------------
# Basic routing.
# ---------------------------------------------------------------------------

def test_router_roundtrip_matches_direct_and_status(tiny):
    s0 = _server(tiny, "rt-a")
    s1 = _server(tiny, "rt-b")
    eps = [(s0.host, s0.port), (s1.host, s1.port)]
    r = _router(eps)
    try:
        direct = ChatClient(s0.host, s0.port, timeout=60)
        want = direct.generate_ids([[1, 2, 3]], gen_len=4)
        direct.close()
        c = ChatClient(r.host, r.port, timeout=60)
        got = c.generate_ids([[1, 2, 3]], gen_len=4)
        # Greedy replay-idempotence: any replica produces the same
        # tokens — the property failover's re-dispatch rests on.
        assert got["tokens"] == want["tokens"]
        assert got.get("trace_id")
        assert got.get("replica") in (f"{s0.host}:{s0.port}",
                                      f"{s1.host}:{s1.port}")
        assert "failovers" not in got       # clean path
        st = c.request({"cmd": "router_status"})["router"]
        assert len(st["replicas"]) == 2
        for row in st["replicas"]:
            assert row["status"] == "live"
            assert row["breaker"] == "closed"
            assert row["inflight"] == 0
            assert not row["draining"]
        assert sum(st["placements"].values()) >= 1
        m = c.request({"cmd": "metrics"})["metrics"]
        assert m["counters"]["router.requests"] >= 1
        assert m["router"]["replicas"]
        # generation without prompt_ids is a structured error
        bad = c.request({"x": 1})
        assert bad.get("type") == "ValueError"
        c.close()
    finally:
        r.stop()
        s0.stop()
        s1.stop()


# ---------------------------------------------------------------------------
# The acceptance scenario: kill one of three mid-window.
# ---------------------------------------------------------------------------

def test_kill_one_of_three_zero_client_failures(tiny):
    """ISSUE 15 acceptance: 3 replicas, one killed mid-window → zero
    failed client requests, in-flight requests re-dispatched
    (failovers >= 1), down within the configured age, a validated
    flight dump, and one trace ID spanning both replicas."""
    from triton_dist_tpu.obs import trace
    from triton_dist_tpu.tools import trace_export
    srvs = [_server(tiny, f"kill-{i}") for i in range(3)]
    eps = [(s.host, s.port) for s in srvs]
    down_s = 1.5
    r = _router(eps)
    rc = ChatClient(r.host, r.port, timeout=120)
    try:
        reqs = [{"prompt_ids": [[(i % 7) + 1, (i % 5) + 2]],
                 "gen_len": 60} for i in range(9)]
        # Warm all replicas' compiles before the timed window.
        fanout(endpoints=eps,
               requests=[dict(q, gen_len=2) for q in reqs])

        window: dict = {}
        base_admits = [s.registry.snapshot()["counters"]
                       .get("serving.admitted", 0) for s in srvs]

        def traffic():
            window["outs"] = fanout(r.host, r.port, requests=reqs)

        th = threading.Thread(target=traffic, daemon=True)
        th.start()

        def busy_victim():
            # A replica with an in-flight dispatch that its pump has
            # ADMITTED: killing pre-admission is legal (the router
            # still fails over) but leaves no victim-side admit
            # instant for the trace-stitching assertion below.
            rows = rc.request({"cmd": "router_status"}
                              )["router"]["replicas"]
            for i, row in enumerate(rows):
                admitted = (srvs[i].registry.snapshot()["counters"]
                            .get("serving.admitted", 0))
                if row["inflight"] > 0 and admitted > base_admits[i]:
                    return (i, row["endpoint"])
            return None

        victim_idx, victim_ep = _wait(busy_victim,
                                      what="in-flight on a replica")
        t_kill = time.monotonic()
        chaos.kill_replica(srvs[victim_idx])
        th.join(timeout=120)
        outs = window["outs"]

        # ZERO failed client requests — the acceptance bar.
        assert all("tokens" in o for o in outs), outs
        # At least one request actually failed over.
        hops = [o for o in outs if o.get("failovers")]
        assert hops, outs
        hop = hops[0]
        assert hop["failovers"] >= 1
        assert hop["replica"] != victim_ep   # answered elsewhere

        # Down within the configured age (+ poll slack).
        def victim_down():
            rows = rc.request({"cmd": "router_status"}
                              )["router"]["replicas"]
            st = {x["endpoint"]: x["status"] for x in rows}
            return st.get(victim_ep) == "down"
        _wait(victim_down, timeout=down_s + 10.0, what="victim down")
        assert time.monotonic() - t_kill < down_s + 10.0

        # The kill left an automatic flight dump (breaker open /
        # replica_down) — and it validates.
        stats = trace.stats()
        auto = stats.get("last_flight_record")
        assert auto, stats
        with open(auto) as f:
            errors, _warn = trace_export.validate(json.load(f))
        assert not errors, errors

        # One trace ID spans both replicas: the failover request's ID
        # tags the victim's admission, the router's failover instant,
        # and the survivor's admission/retire. (Fresh cmd dump = the
        # full current window; in-process replicas share the ring.)
        dump = rc.dump_trace()["dumped"]
        with open(dump) as f:
            evs = json.load(f)["traceEvents"]

        def story_of(h):
            return [e for e in evs
                    if (e.get("args") or {}).get("trace_id")
                    == h["trace_id"]]

        def admit_replicas(st):
            return {(e.get("args") or {}).get("replica")
                    for e in st if e["name"] == "serving.admit"}

        story = story_of(hop)
        assert any(e["name"] == "router.failover" for e in story)
        # A failed-over request whose VICTIM-side admission happened
        # (the kill can legally race ahead of the victim's pump, in
        # which case that hop has only the survivor's admit) — pick
        # any hop whose story spans both replicas; with several
        # requests in flight at the kill, at least one was admitted
        # on the victim before dying.
        spanning = [h for h in hops
                    if len(admit_replicas(story_of(h))) >= 2]
        assert spanning, [story_of(h) for h in hops]
        # The fleet kept serving afterwards.
        ok = rc.generate_ids([[9, 8]], gen_len=3)
        assert "tokens" in ok
        m = rc.request({"cmd": "metrics"})["metrics"]["counters"]
        assert m.get("router.failovers", 0) >= 1
        assert m.get("router.dispatch_errors", 0) >= 1
    finally:
        rc.close()
        r.stop()
        for s in srvs:
            s.stop()


# ---------------------------------------------------------------------------
# Wedged replica: dispatch deadline + breaker, not liveness.
# ---------------------------------------------------------------------------

def test_wedged_replica_fails_over_breaker_opens_then_recovers(tiny):
    s0 = _server(tiny, "wg-a")
    s1 = _server(tiny, "wg-b")
    eps = [(s0.host, s0.port), (s1.host, s1.port)]
    r = _router(eps, try_timeout_s=0.5, retries=3, backoff_ms=10,
                breaker_threshold=2, breaker_cooldown_s=0.3)
    c = ChatClient(r.host, r.port, timeout=120)
    try:
        # Warm BOTH replicas' compiled programs directly (not through
        # the router): each Engine jits its own step, and the first
        # generation's XLA compile can exceed the deliberately tight
        # 0.5 s dispatch deadline this test gives the router — which
        # would open both breakers before anything is wedged.
        for s in (s0, s1):
            w = ChatClient(s.host, s.port, timeout=120)
            try:
                assert "tokens" in w.generate_ids([[1, 2]], gen_len=2)
            finally:
                w.close()
        # Find where the router places, then wedge THAT replica.
        first = c.generate_ids([[1, 2]], gen_len=2)
        assert "tokens" in first
        by_label = {f"{s.host}:{s.port}": s for s in (s0, s1)}
        victim = by_label[first["replica"]]
        survivor = s1 if victim is s0 else s0

        def victim_row():
            rows = c.request({"cmd": "router_status"}
                             )["router"]["replicas"]
            return {x["endpoint"]: x for x in rows}[
                f"{victim.host}:{victim.port}"]

        with chaos.wedge_pump(victim.scheduler):
            # With the healthy sibling still attached, every request
            # SUCCEEDS — a wedged dispatch times out and fails over
            # (health-gated placement may also route around the
            # victim outright once its queue gauge rises; either way
            # the client never sees the wedge).
            for i in range(3):
                assert "tokens" in c.generate_ids(
                    [[i + 1, i + 2]], gen_len=2)
            # Isolate the victim (remove the survivor) so dispatches
            # MUST hit the wedge: the per-attempt deadline trips, the
            # breaker opens after `breaker_threshold` timeouts, and
            # the exhausted request degrades structurally — while the
            # victim's health verb keeps answering (status live: the
            # failure class liveness checks cannot catch).
            c.request({"cmd": "router_remove",
                       "endpoint": f"{survivor.host}:{survivor.port}"})
            # (The breaker may ALREADY be open here if the loop above
            # sent `breaker_threshold` dispatches into the wedge —
            # then this request sheds without a dispatch; either way
            # the reply is structured and the breaker ends open.)
            resp = c.generate_ids([[9, 9]], gen_len=2)
            assert resp.get("type") == "no_healthy_replicas", resp
            row = victim_row()
            assert row["breaker"] == "open", row
            assert row["status"] == "live", row
            # The wedge was exercised through the dispatch deadline:
            # the breaker needed `breaker_threshold` recorded
            # timeouts to open.
            m = c.request({"cmd": "metrics"})["metrics"]["counters"]
            assert m.get("router.dispatch_errors", 0) >= 2
        # Recovery: release the wedge; the half-open probe dispatch
        # must re-close the breaker.
        _wait(lambda: victim.scheduler.inflight() == 0,
              what="wedge drained")
        time.sleep(0.35)        # past breaker_cooldown_s
        resp = _wait(
            lambda: (lambda o: o if "tokens" in o else None)(
                c.generate_ids([[7, 7]], gen_len=2)),
            what="probe success via recovered replica")
        assert resp["replica"] == f"{victim.host}:{victim.port}"
        rows = c.request({"cmd": "router_status"}
                         )["router"]["replicas"]
        assert [x["breaker"] for x in rows] == ["closed"]
    finally:
        c.close()
        r.stop()
        s0.stop()
        s1.stop()


# ---------------------------------------------------------------------------
# Fleet-level shed + drain.
# ---------------------------------------------------------------------------

def test_all_replicas_draining_sheds_fleet_queue_full(tiny):
    srv = _server(tiny, "shed-a")
    r = _router([(srv.host, srv.port)])
    try:
        c = ChatClient(r.host, r.port, timeout=60, retry_shed=False)
        assert "tokens" in c.generate_ids([[1, 2]], gen_len=2)
        # Server-side drain: the replica answers {"type": "draining"}.
        drc = ChatClient(srv.host, srv.port, timeout=60)
        d = drc.request({"cmd": "drain"})
        assert d["draining"] is True
        drc.close()
        resp = c.generate_ids([[3, 4]], gen_len=2)
        assert resp.get("type") == "queue_full", resp
        assert resp.get("scope") == "fleet"
        assert isinstance(resp.get("retry_after_ms"), int)
        assert resp["retry_after_ms"] >= 25
        m = c.request({"cmd": "metrics"})["metrics"]["counters"]
        assert m.get("router.shed", 0) >= 1
        assert m.get("router.replica_sheds", 0) >= 1
        c.close()
    finally:
        r.stop()
        srv.stop()


def test_server_drain_verb_inflight_accounting_and_resume(tiny):
    srv = _server(tiny, "drain-a")
    try:
        c = ChatClient(srv.host, srv.port, timeout=60,
                       retry_shed=False)
        assert "tokens" in c.generate_ids([[1, 2]], gen_len=2)
        assert srv.scheduler.inflight() == 0

        got: dict = {}

        def bg():
            cc = ChatClient(srv.host, srv.port, timeout=60)
            got["resp"] = cc.generate_ids([[1, 2, 3]], gen_len=40)
            cc.close()

        th = threading.Thread(target=bg, daemon=True)
        th.start()
        _wait(lambda: srv.scheduler.inflight() >= 1,
              what="request in flight")
        d = c.request({"cmd": "drain"})
        assert d["draining"] is True and d["inflight"] >= 1
        # New work refuses with the draining type + hint...
        rej = c.generate_ids([[5, 6]], gen_len=2)
        assert rej.get("type") == "draining", rej
        assert isinstance(rej.get("retry_after_ms"), int)
        # ...while health advertises the drain (routers stop placing).
        assert c.health().get("draining") is True
        # In-flight work finishes; wait_s polls it to zero.
        d2 = c.request({"cmd": "drain", "wait_s": 60})
        assert d2["drained"] is True and d2["inflight"] == 0
        th.join(timeout=60)
        assert "tokens" in got["resp"]
        # Resume: admissions work again.
        d3 = c.request({"cmd": "drain", "resume": True})
        assert d3["draining"] is False
        assert "tokens" in c.generate_ids([[7, 8]], gen_len=2)
        assert c.health().get("draining") is None
        c.close()
    finally:
        srv.stop()


def test_router_remove_waits_for_inflight_then_add_restores(tiny):
    srv = _server(tiny, "rm-a")
    r = _router([(srv.host, srv.port)])
    c = ChatClient(r.host, r.port, timeout=120, retry_shed=False)
    try:
        assert "tokens" in c.generate_ids([[1, 2]], gen_len=2)
        got: dict = {}

        def bg():
            cc = ChatClient(r.host, r.port, timeout=120)
            got["resp"] = cc.generate_ids([[1, 2, 3]], gen_len=40)
            cc.close()

        th = threading.Thread(target=bg, daemon=True)
        th.start()
        _wait(lambda: any(
            x["inflight"] > 0 for x in c.request(
                {"cmd": "router_status"})["router"]["replicas"]),
            what="in-flight through the router")
        # Graceful remove: waits for the router's in-flight dispatch.
        rm = c.request({"cmd": "router_remove",
                        "endpoint": f"{srv.host}:{srv.port}",
                        "wait_s": 60})
        assert rm["removed"] == f"{srv.host}:{srv.port}"
        assert rm["drained"] is True and rm["inflight"] == 0
        th.join(timeout=60)
        assert "tokens" in got["resp"]     # the in-flight one finished
        # Empty fleet: structured no_healthy_replicas, not a hang.
        resp = c.generate_ids([[5, 5]], gen_len=2)
        assert resp.get("type") == "no_healthy_replicas", resp
        assert isinstance(resp.get("retry_after_ms"), int)
        # Live add restores service.
        add = c.request({"cmd": "router_add",
                         "endpoint": f"{srv.host}:{srv.port}"})
        assert add["replicas"] == 1
        assert "tokens" in c.generate_ids([[6, 6]], gen_len=2)
        m = c.request({"cmd": "metrics"})["metrics"]["counters"]
        assert m.get("router.replicas_removed") == 1
        assert m.get("router.replicas_added") == 1
    finally:
        c.close()
        r.stop()
        srv.stop()


# ---------------------------------------------------------------------------
# Client fault-awareness (satellites).
# ---------------------------------------------------------------------------

def test_multi_endpoint_client_skips_dead_and_retries_next(tiny):
    srv = _server(tiny, "skip-a")
    dead = ("127.0.0.1", _dead_port())
    c = ChatClient(endpoints=[dead, (srv.host, srv.port)], timeout=60)
    try:
        # Round-robin starts on the dead endpoint: the failure is
        # retried once on the next — the caller never sees it.
        for _ in range(4):
            assert "tokens" in c.generate_ids([[1, 2]], gen_len=2)
        # ... and the dead endpoint is skipped (marked bad), so ALL
        # requests landed on the live replica.
        h = c.health(endpoint=(srv.host, srv.port))
        assert h["counters"]["server.requests"] >= 4
    finally:
        c.close()
        srv.stop()


def test_fanout_retries_slot_on_next_endpoint(tiny):
    srv = _server(tiny, "fan-a")
    dead = ("127.0.0.1", _dead_port())
    outs = fanout(endpoints=[dead, (srv.host, srv.port)],
                  requests=[{"prompt_ids": [[i + 1, 2]], "gen_len": 2}
                            for i in range(4)], timeout=60)
    try:
        assert all("tokens" in o for o in outs), outs
        # Pinned mode (the FleetView scrape contract) keeps the old
        # exact slot→endpoint behavior: dead slots error.
        outs_pinned = fanout(
            endpoints=[dead, (srv.host, srv.port)],
            requests=[{"cmd": "health"}, {"cmd": "health"}],
            timeout=5, retry_next=False)
        assert "error" in outs_pinned[0]
        assert "health" in outs_pinned[1]
    finally:
        srv.stop()


def _stub_server(reply_fn):
    """Tiny protocol stub: one JSON line in → ``reply_fn(req, server)``
    out (return a dict, the bytes b"" to close the connection mid-
    reply-less, or a raw bytes payload for torn-reply injection)."""
    class _H(socketserver.StreamRequestHandler):
        def handle(self):
            for line in self.rfile:
                if not line.strip():
                    continue
                self.server.hits += 1
                out = reply_fn(json.loads(line), self.server)
                if isinstance(out, dict):
                    out = (json.dumps(out) + "\n").encode()
                if out:
                    self.wfile.write(out)
                    self.wfile.flush()
                if getattr(self.server, "close_after", False):
                    return          # sever the connection
    srv = socketserver.ThreadingTCPServer(("127.0.0.1", 0), _H)
    srv.daemon_threads = True
    srv.hits = 0
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


def test_client_fails_over_on_torn_reply(tiny):
    """Review regression: a replica severed mid-write leaves a torn
    JSON line — a ValueError, not an OSError — and the multi-endpoint
    client must treat it like any other endpoint death: mark bad,
    retry once on the next endpoint."""
    def torn(req, server):
        server.close_after = True
        return b'{"tokens": [[1, 2'          # cut mid-reply
    broken = _stub_server(torn)
    srv = _server(tiny, "torn-b")
    try:
        c = ChatClient(endpoints=[broken.server_address,
                                  (srv.host, srv.port)], timeout=60)
        resp = c.generate_ids([[1, 2]], gen_len=2)
        assert "tokens" in resp, resp        # failed over, no raise
        # ... and the torn endpoint is now skipped.
        assert "tokens" in c.generate_ids([[3, 4]], gen_len=2)
        assert broken.hits == 1
        c.close()
    finally:
        broken.shutdown()
        broken.server_close()
        srv.stop()


def test_shed_retry_fails_over_when_endpoint_dies_in_the_sleep(tiny):
    """Review regression: the retry_after_ms sleep-and-retry round
    trip carries the same dead-endpoint failover contract as the
    first attempt — a replica dying during the backpressure sleep
    costs the one retry, not a raw socket error."""
    shedder = _stub_server(
        lambda req, s: {"error": "full", "type": "queue_full",
                        "retry_after_ms": 30})
    dead = ("127.0.0.1", _dead_port())
    srv = _server(tiny, "shed-die-b")
    try:
        # Round-robin: attempt 1 → shedder (queue_full + hint), sleep,
        # retry → the DEAD endpoint → must fail over to the live one
        # inside the retry round trip, not raise.
        c = ChatClient(endpoints=[shedder.server_address, dead,
                                  (srv.host, srv.port)], timeout=60)
        resp = c.generate_ids([[1, 2]], gen_len=2)
        assert "tokens" in resp, resp
        c.close()
    finally:
        shedder.shutdown()
        shedder.server_close()
        srv.stop()


def test_router_fails_over_replica_fault_reply_passes_client_fault():
    """Review regression: an error reply that is a REPLICA fault
    (engine failure — anything outside the ValueError client-mistake
    class) must fail over and count against the breaker; the
    request's own ValueError passes through unchanged."""
    broken = _stub_server(
        lambda req, s: {"error": "device lost", "type": "RuntimeError"}
        if "prompt_ids" in req else {"health": {"replica_id": "bx"}})
    healthy = _stub_server(
        lambda req, s: {"tokens": [[9]], "gen_len": 1}
        if "prompt_ids" in req else {"health": {"replica_id": "hx"}})
    r = _router([broken.server_address, healthy.server_address],
                retries=2, backoff_ms=5)
    try:
        c = ChatClient(r.host, r.port, timeout=60, retry_shed=False)
        resp = c.generate_ids([[1, 2]], gen_len=2)
        assert resp.get("tokens") == [[9]], resp
        assert resp.get("failovers") == 1        # RuntimeError hopped
        rows = c.request({"cmd": "router_status"})["router"]["replicas"]
        by_ep = {x["endpoint"]: x for x in rows}
        b_ep = "%s:%s" % broken.server_address
        assert by_ep[b_ep]["breaker"] != "closed" \
            or c.request({"cmd": "metrics"})["metrics"]["counters"][
                "router.dispatch_errors"] >= 1
        # A ValueError reply (the request's own fault) passes through
        # from whichever replica produced it — no failover.
        vbad = _stub_server(
            lambda req, s: {"error": "bad prompt", "type": "ValueError"}
            if "prompt_ids" in req else {"health": {"replica_id": "v"}})
        r2 = _router([vbad.server_address])
        c2 = ChatClient(r2.host, r2.port, timeout=60, retry_shed=False)
        resp2 = c2.generate_ids([[1]], gen_len=1)
        assert resp2.get("type") == "ValueError", resp2
        assert "failovers" not in resp2
        assert vbad.hits >= 1
        c2.close()
        r2.stop()
        vbad.shutdown()
        vbad.server_close()
        c.close()
    finally:
        r.stop()
        for s in (broken, healthy):
            s.shutdown()
            s.server_close()


class _ShedOnce(socketserver.StreamRequestHandler):
    def handle(self):
        for line in self.rfile:
            if not line.strip():
                continue
            self.server.hits += 1
            if self.server.hits == 1:
                resp = {"error": "full", "type": "queue_full",
                        "retry_after_ms": 40}
            else:
                resp = {"tokens": [[5]], "gen_len": 1}
            self.wfile.write((json.dumps(resp) + "\n").encode())
            self.wfile.flush()


def _shed_server():
    srv = socketserver.ThreadingTCPServer(("127.0.0.1", 0), _ShedOnce)
    srv.daemon_threads = True
    srv.hits = 0
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


def test_client_honors_retry_after_ms_once():
    srv = _shed_server()
    try:
        c = ChatClient(*srv.server_address, timeout=60)
        t0 = time.monotonic()
        resp = c.generate_ids([[1]], gen_len=1)
        took = time.monotonic() - t0
        assert resp.get("tokens") == [[5]]       # retried through
        assert took >= 0.04                      # honored the hint
        assert srv.hits == 2                     # exactly one retry
        c.close()
    finally:
        srv.shutdown()
        srv.server_close()


def test_client_skips_retry_when_budget_too_small():
    srv = _shed_server()
    try:
        # hint (40ms) >= timeout budget (0.02s): no sleep-retry; the
        # raw shed reply comes back.
        c = ChatClient(*srv.server_address, timeout=0.02)
        resp = c.generate_ids([[1]], gen_len=1)
        assert resp.get("type") == "queue_full"
        assert srv.hits == 1
        c.close()
    finally:
        srv.shutdown()
        srv.server_close()


# ---------------------------------------------------------------------------
# Dashboards.
# ---------------------------------------------------------------------------

def test_fleet_top_render_router_pure():
    from triton_dist_tpu.tools.fleet_top import render_router
    status = {
        "uptime_s": 12.5,
        "replicas": [
            {"endpoint": "127.0.0.1:1", "replica_id": "r0",
             "status": "live", "age_s": 0.1, "score": 0.9,
             "breaker": "closed", "inflight": 2, "draining": False},
            {"endpoint": "127.0.0.1:2", "replica_id": "r1",
             "status": "down", "age_s": 40.0, "score": None,
             "breaker": "open", "inflight": 0, "draining": True},
        ],
        "placements": {"127.0.0.1:1": 10, "127.0.0.1:2": 3},
        "counters": {"router.requests": 13, "router.failovers": 2,
                     "router.shed": 1},
    }
    screen = render_router(status)
    assert "r0" in screen and "r1" in screen
    assert "open" in screen and "closed" in screen
    assert "failovers 2" in screen
    assert "shed 1" in screen
    # degraded fetch renders too
    assert "no replicas" in render_router({"replicas": []})


def test_fleet_top_router_live_and_report_section(tiny, capsys):
    from triton_dist_tpu.tools import fleet_top, report
    srv = _server(tiny, "dash-a")
    r = _router([(srv.host, srv.port)])
    try:
        c = ChatClient(r.host, r.port, timeout=60)
        assert "tokens" in c.generate_ids([[1, 2]], gen_len=2)
        status = fleet_top.fetch_router(f"{r.host}:{r.port}")
        assert status["replicas"][0]["status"] == "live"
        rc = fleet_top.main(["--router", f"{r.host}:{r.port}",
                             "--once"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "tdt router" in out and "dash-a" in out

        # report.py renders the same payload as the "router" section.
        status["failover_sample"] = {"trace_id": "t-1", "failovers": 1,
                                     "replica": "x:1", "timing": None}
        md = report.render_router(status)
        assert "#### router" in md and "dash-a" in md
        assert "trace_id=t-1" in md
        assert report.render_router(None) == ""
        full = report.render_telemetry({"counters": {}, "gauges": {},
                                        "histograms": {},
                                        "router": status})
        assert "#### router" in full
        c.close()
    finally:
        r.stop()
        srv.stop()
