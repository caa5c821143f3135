"""Chat client for the ModelServer (reference chat.py,
mega_triton_kernel/test/models/chat.py). Token-id protocol; plugs a HF
tokenizer in when available for text chat.

``timeout=`` (constructor or per call) bounds every protocol round
trip — a wedged server raises ``TimeoutError`` instead of blocking the
client forever. :func:`fanout` is the small concurrent-client helper
the serving bench and the scheduler load tests drive their traffic
through: one connection + thread per request, responses in request
order.

Multi-endpoint mode (ISSUE 14): ``ChatClient(endpoints=[...])`` holds
one lazy connection per replica and round-robins generation/control
requests across them (per-endpoint timeouts — the same ``timeout=``
machinery, applied per connection); ``health()`` speaks the server's
cheap ``{"cmd": "health"}`` verb; ``fanout(endpoints=[...])``
round-robins a request list across replicas — the client-side fanout
behind ``obs.fleet.FleetView``'s concurrent scrapes.

Fault awareness (ISSUE 15): multi-endpoint round-robin skips
endpoints whose last round trip died at the socket level and retries
the failed request once on the next endpoint (``fanout`` does the
same per slot, sharing one dead-set per call), so a replica death
costs a failover, not a client-visible error; and a ``queue_full`` /
``draining`` reply's ``retry_after_ms`` hint earns one
sleep-and-retry when the timeout budget allows
(``retry_shed=False`` opts out). For health-gated placement and
deadline-budgeted re-dispatch, front the fleet with
``serving.router.RouterServer`` instead — these client-side paths
are the router-less fallback.
"""

from __future__ import annotations

import json
import socket
import threading
import time

#: Sentinel distinguishing "no per-call timeout given" from an explicit
#: ``timeout=None`` (= block forever).
_UNSET = object()


def _parse_endpoint(ep) -> tuple:
    """``(host, port)`` from ``"host:port"`` / ``(host, port)`` —
    one parser for every multi-endpoint surface (the fleet view's
    ``obs.fleet.parse_endpoint``; obs never imports serving, so no
    cycle)."""
    from triton_dist_tpu.obs.fleet import parse_endpoint
    return parse_endpoint(ep)


class ChatClient:
    def __init__(self, host: str = "127.0.0.1", port: int = 8777,
                 tokenizer=None, timeout: float | None = None,
                 endpoints=None, retry_shed: bool = True):
        """``timeout``: seconds each protocol round trip may take
        (connect included) before ``TimeoutError``; ``None`` blocks
        indefinitely (the historical behavior). ``endpoints``: a list
        of ``"host:port"`` / ``(host, port)`` replicas — requests
        round-robin across them over one lazy persistent connection
        each (``host``/``port`` are ignored then); the single-endpoint
        form keeps its eager connect, so a refused connection still
        fails at construction. Endpoints whose last round trip died at
        the socket level are SKIPPED by the round-robin (and the
        failed request retried ONCE on the next endpoint) until a
        later success clears them — a dead replica degrades to one
        client-side retry, never a per-request error (ISSUE 15).
        ``retry_shed``: honor a ``queue_full`` / ``draining`` reply's
        ``retry_after_ms`` hint on generation requests — sleep that
        long and retry once when the timeout budget allows
        (``False`` returns the raw shed reply)."""
        self.tokenizer = tokenizer
        self.timeout = timeout
        self.retry_shed = retry_shed
        if endpoints:
            self.endpoints = [_parse_endpoint(e) for e in endpoints]
        else:
            self.endpoints = [(host, int(port))]
        self.addr = self.endpoints[0]
        self._conns: dict = {}          # endpoint -> (sock, file)
        self._rr = 0
        self._lock = threading.Lock()   # rr index + conn/lock creation
        self._ep_locks: dict = {}       # endpoint -> round-trip lock
        self._bad: set = set()          # endpoints whose last try died
        if not endpoints:
            self._conn(self.endpoints[0])   # eager: historical contract

    def _conn(self, ep, connect_timeout=_UNSET):
        """The endpoint's persistent connection, created lazily. The
        blocking connect runs OUTSIDE the client-wide lock (the lock
        only publishes the result) — a wedged replica must not stall
        requests to the healthy ones — and honors the caller's
        per-call timeout: in multi-endpoint mode first contact with a
        replica happens inside request(), so the override has to
        cover the connect, not just the round trip."""
        with self._lock:
            c = self._conns.get(ep)
        if c is not None:
            return c
        to = self.timeout if connect_timeout is _UNSET else connect_timeout
        s = socket.create_connection(ep, timeout=to)
        s.settimeout(self.timeout)
        with self._lock:
            raced = self._conns.get(ep)
            if raced is not None:
                c = raced            # another thread won; drop ours
            else:
                c = self._conns[ep] = (s, s.makefile("rwb"))
        if c[0] is not s:
            try:
                s.close()
            except OSError:
                pass
        return c

    def _ep_lock(self, ep):
        with self._lock:
            lk = self._ep_locks.get(ep)
            if lk is None:
                lk = self._ep_locks[ep] = threading.Lock()
        return lk

    def _next_endpoint(self) -> tuple:
        """Round-robin, skipping endpoints whose last round trip died
        at the socket level (all-bad falls back to plain round-robin —
        somebody has to probe them back to life)."""
        with self._lock:
            n = len(self.endpoints)
            for _ in range(n):
                ep = self.endpoints[self._rr % n]
                self._rr += 1
                if ep not in self._bad:
                    return ep
            ep = self.endpoints[self._rr % n]
            self._rr += 1
        return ep

    def _mark_bad(self, ep) -> None:
        """Remember a socket-level failure and drop the endpoint's
        (now protocol-undefined) cached connection."""
        with self._lock:
            self._bad.add(ep)
            conn = self._conns.pop(ep, None)
        if conn is not None:
            for c in conn[::-1]:
                try:
                    c.close()
                except OSError:
                    pass

    def _roundtrip(self, ep, req: dict, timeout=_UNSET) -> dict:
        with self._ep_lock(ep):
            sock, file = self._conn(ep, connect_timeout=timeout)
            if timeout is not _UNSET:
                sock.settimeout(timeout)
            try:
                file.write((json.dumps(req) + "\n").encode())
                file.flush()
                line = file.readline()
            finally:
                if timeout is not _UNSET:
                    sock.settimeout(self.timeout)
        if not line:
            raise ConnectionError("server closed the connection")
        return json.loads(line)

    def request(self, req: dict, timeout=_UNSET, endpoint=None) -> dict:
        """One protocol round trip with an arbitrary request object
        (generation or control-plane, e.g. ``{"cmd": "metrics"}``).
        ``timeout`` overrides the client default for this call only
        (``socket.timeout`` is a ``TimeoutError``; that endpoint's
        connection is left in an undefined protocol state after one —
        reconnect). ``endpoint`` pins the replica; otherwise
        multi-endpoint clients round-robin, skip endpoints whose last
        round trip died, and retry a socket-level failure ONCE on the
        next endpoint (so a replica death surfaces as a failover, not
        a client error — ISSUE 15); a pinned or single-endpoint call
        keeps the historical raise. Thread-safe: each endpoint's
        write→read round trip runs under a per-endpoint lock, so
        concurrent callers sharing one client serialize per connection
        instead of interleaving protocol bytes (use :func:`fanout`
        for genuinely concurrent traffic — one fresh connection per
        request)."""
        pinned = endpoint is not None
        ep = _parse_endpoint(endpoint) if pinned else None
        resp = self._request_failover(ep, req, timeout, pinned)
        # Shed backpressure with a hint (docs/serving.md): a
        # queue_full / draining reply carrying retry_after_ms earns
        # ONE sleep-and-retry on a generation request — when the
        # timeout budget covers the sleep — instead of bouncing the
        # shed straight back to a caller who will immediately hammer.
        if (self.retry_shed and "prompt_ids" in req
                and isinstance(resp, dict)
                and resp.get("type") in ("queue_full", "draining")
                and resp.get("retry_after_ms")):
            delay_s = float(resp["retry_after_ms"]) / 1e3
            budget = self.timeout if timeout is _UNSET else timeout
            if budget is None or delay_s < float(budget):
                time.sleep(delay_s)
                # Same failover contract as the first attempt: an
                # endpoint dying DURING the backpressure sleep must
                # cost the one retry, not a raw socket error.
                resp = self._request_failover(ep, req, timeout,
                                              pinned)
        return resp

    def _request_failover(self, ep, req: dict, timeout,
                          pinned: bool) -> dict:
        """One round trip with the dead-endpoint contract: a failure
        at the socket OR framing level (``OSError``; ``ValueError``
        covers a torn/garbled reply line from a connection severed
        mid-write — the same classes the router's dispatch counts)
        marks the endpoint bad and retries ONCE on the next endpoint;
        pinned/single-endpoint calls keep the historical raise."""
        if ep is None:
            ep = self._next_endpoint()
        try:
            resp = self._roundtrip(ep, req, timeout)
        except (OSError, ValueError):
            self._mark_bad(ep)
            if pinned or len(self.endpoints) < 2:
                raise
            nxt = self._next_endpoint()
            if nxt == ep:
                raise
            resp = self._roundtrip(nxt, req, timeout)  # single retry
            ep = nxt
        with self._lock:
            self._bad.discard(ep)
        return resp

    def generate_ids(self, prompt_ids, gen_len: int = 16,
                     trace_id: str | None = None,
                     timeout=_UNSET) -> dict:
        """Generate; with tracing on server-side the response carries
        ``trace_id`` (yours if given) for cross-referencing a later
        flight record (docs/observability.md "Tracing"), and
        ``gen_len`` echoes the server's effective (possibly clamped)
        value."""
        req = {"prompt_ids": prompt_ids, "gen_len": gen_len}
        if trace_id is not None:
            req["trace_id"] = trace_id
        return self.request(req, timeout=timeout)

    def dump_trace(self, seconds: float | None = None) -> dict:
        """Ask the server to dump its flight record
        (``{"cmd": "dump_trace"}``); returns the dump path + stats."""
        req: dict = {"cmd": "dump_trace"}
        if seconds is not None:
            req["seconds"] = seconds
        return self.request(req)

    def request_stats(self, last: int | None = None) -> list:
        """The newest ``last`` finished requests' latency-attribution
        waterfalls (``{"cmd": "request_stats"}`` — queue_wait →
        prefill → decode segments, prefix savings, per-token share;
        docs/observability.md "Request attribution"), newest first."""
        req: dict = {"cmd": "request_stats"}
        if last is not None:
            req["last"] = last
        return self.request(req).get("requests", [])

    def health(self, endpoint=None, timeout=_UNSET) -> dict:
        """One replica's compact ``ReplicaHealth`` snapshot via the
        cheap ``{"cmd": "health"}`` verb — lock-free server-side reads,
        no SLO force-evaluation (docs/observability.md "Fleet view").
        Round-robins like any request; pin a replica with
        ``endpoint=``. Raises ``RuntimeError`` on an error reply (an
        old server without the verb)."""
        resp = self.request({"cmd": "health"}, timeout=timeout,
                            endpoint=endpoint)
        if "health" not in resp:
            raise RuntimeError(resp.get("error", f"bad reply {resp!r}"))
        return resp["health"]

    def chat(self, text: str, gen_len: int = 64) -> str:
        assert self.tokenizer is not None, "text chat needs a tokenizer"
        ids = self.tokenizer(text, return_tensors="np")["input_ids"]
        resp = self.generate_ids(ids.tolist(), gen_len)
        if "error" in resp:
            raise RuntimeError(resp["error"])
        return self.tokenizer.decode(resp["tokens"][0])

    def close(self):
        with self._lock:
            conns, self._conns = list(self._conns.values()), {}
        for sock, file in conns:
            try:
                file.close()
                sock.close()
            except OSError:
                pass


def request_once(endpoint, req: dict,
                 timeout: float | None = None) -> dict:
    """One fresh-connection protocol round trip — the raw JSON-lines
    framing primitive, shared with ``RouterServer``'s dispatch
    attempts (serving/router.py) so the wire contract has ONE home.
    Raises ``OSError`` on transport failure (connect/timeout/reset),
    ``ConnectionError`` when the server closes without a reply line,
    and ``ValueError`` on a torn/garbled reply — the failure classes
    breakers and failover count. No retries, no endpoint skipping:
    callers that want the fault-aware behavior use
    :class:`ChatClient` / :func:`fanout`."""
    ep = _parse_endpoint(endpoint)
    with socket.create_connection(ep, timeout=timeout) as s:
        s.settimeout(timeout)
        with s.makefile("rwb") as f:
            f.write((json.dumps(req) + "\n").encode())
            f.flush()
            line = f.readline()
    if not line:
        raise ConnectionError("server closed the connection")
    return json.loads(line)


def fanout(host: str | None = None, port: int | None = None,
           requests: list | None = None,
           timeout: float | None = None, endpoints=None,
           retry_next: bool = True) -> list:
    """Issue ``requests`` (protocol dicts) CONCURRENTLY — one fresh
    connection and thread per request — and return the responses in
    request order. A request that fails client-side (timeout, refused
    connection) yields an ``{"error", "type"}`` dict in its slot, so
    the caller can count failures without unwinding the others. This
    is the concurrent-client helper behind the scheduler load tests.

    ``endpoints=[...]`` replaces ``host``/``port`` with a replica
    list: request ``i`` goes to ``endpoints[i % len(endpoints)]`` —
    the client-side round-robin ``obs.fleet.FleetView`` rides
    (per-request timeout, so one wedged replica cannot stall the other
    slots). A slot whose endpoint
    fails client-side is retried ONCE on the next endpoint that no
    sibling slot has seen die (ISSUE 15): a replica death mid-fanout
    costs one retry, and cannot be mis-attributed as a client
    failure; only a retry that ALSO fails records the error dict.
    ``retry_next=False`` pins slot ``i`` to ``endpoints[i % n]``
    exactly — what a health/metrics scrape needs: replica A's probe
    answered by replica B would corrupt per-replica records
    (``obs.fleet.FleetView`` passes it)."""
    if endpoints:
        eps = [_parse_endpoint(e) for e in endpoints]
    else:
        if host is None or port is None:
            raise ValueError("fanout needs host+port or endpoints=")
        eps = [(host, int(port))]
    if requests is None:
        raise ValueError("fanout needs requests")
    results: list = [None] * len(requests)
    dead: set = set()       # endpoints some slot saw die (GIL-safe)

    def one_shot(ep, payload: dict) -> dict:
        c = ChatClient(ep[0], ep[1], timeout=timeout)
        try:
            return c.request(payload)
        finally:
            c.close()

    def pick(start: int):
        """The first not-known-dead endpoint from ``start``; falls
        back to the start slot when every endpoint is dead."""
        n = len(eps)
        for j in range(n):
            ep = eps[(start + j) % n]
            if ep not in dead:
                return ep
        return eps[start % n]

    def worker(i: int, payload: dict) -> None:
        ep = pick(i) if retry_next else eps[i % len(eps)]
        try:
            results[i] = one_shot(ep, payload)
            return
        except Exception as e:  # noqa: BLE001 — per-slot isolation
            dead.add(ep)
            err = e
        if retry_next and len(eps) > 1:
            nxt = pick(i + 1)
            if nxt != ep:
                try:
                    results[i] = one_shot(nxt, payload)
                    return
                except Exception as e:  # noqa: BLE001
                    dead.add(nxt)
                    err = e
        results[i] = {"error": str(err) or repr(err),
                      "type": type(err).__name__}

    threads = [threading.Thread(target=worker, args=(i, r), daemon=True)
               for i, r in enumerate(requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results


def main():  # pragma: no cover - manual demo
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8777)
    ap.add_argument("--tokenizer-dir", default=None)
    ap.add_argument("--timeout", type=float, default=None)
    args = ap.parse_args()
    tok = None
    if args.tokenizer_dir:
        from transformers import AutoTokenizer
        tok = AutoTokenizer.from_pretrained(args.tokenizer_dir)
    client = ChatClient(args.host, args.port, tok, timeout=args.timeout)
    try:
        while True:
            text = input("you> ")
            if tok:
                print("model>", client.chat(text))
            else:
                ids = [[int(t) for t in text.split()]]
                print("model>", client.generate_ids(ids))
    except (EOFError, KeyboardInterrupt):
        client.close()


if __name__ == "__main__":  # pragma: no cover
    main()
