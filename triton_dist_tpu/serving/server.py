"""Generation server with cross-request continuous batching.

TPU-native analog of the reference's demo server
(python/triton_dist/mega_triton_kernel/test/models/model_server.py: a
socket server feeding the megakernel model, with chat.py as the
client) — extended past it: generation routes through the
continuous-batching scheduler (serving/scheduler.py) by default, so
prompts from DIFFERENT connections coexist in one decode batch
instead of queueing whole generations behind a lock
(docs/serving.md "Scheduler").
Protocol: newline-delimited JSON over TCP —

    → {"prompt_ids": [[...]], "gen_len": 16, "stop_tokens": [151645]}
    ← {"tokens": [[...]], "gen_len": 16, "latency_ms": 12.3}

``stop_tokens`` is optional (default: the model config's eos). The
response's ``gen_len`` echoes the EFFECTIVE value — requests past the
protocol cap (4096) or the engine's room (max_seq − longest prompt)
are clamped, counted into ``server.gen_len_clamped``, never silent.
A full admission queue answers a structured backpressure reply
instead of stalling the connection, with a ``retry_after_ms`` hint
(rolling TPOT × queue depth, clamped — ISSUE 15) that ``ChatClient``
honors instead of immediately hammering again —

    ← {"error": ..., "type": "queue_full", "queue_depth": N,
       "max_waiting": M, "retry_after_ms": T}

``{"cmd": "drain"}`` starts a graceful drain (nothing new admitted —
generation requests answer ``{"type": "draining", ...}`` — while
everything in flight finishes; ``"wait_s"`` blocks until idle,
``"resume": true`` cancels): the verb a router's graceful replica
removal speaks (docs/serving.md "Drain").

Telemetry (docs/observability.md): a metrics request on the same
protocol returns the server's registry snapshot, stamped with this
replica's identity —

    → {"cmd": "metrics"}
    ← {"metrics": {"counters": ..., "gauges": ..., "histograms": ...,
                   "replica_id": "host:port"}}

with ``"format": "prometheus"`` adding a ``prometheus`` text-exposition
field for scrapers; a metrics scrape first forces a fresh SLO
evaluation, so the ``serving.rolling.*`` / ``serving.slo_burn.*``
gauges are current as of the reply (``"evaluate": false`` skips that —
the last-evaluated gauges are returned as-is, which is what a 1 Hz
dashboard over N replicas should ask for).
Constructing a ModelServer enables the telemetry registry
(``telemetry=False`` opts out).

The fleet control surface (ISSUE 14, docs/observability.md "Fleet
view"): every server carries a stable ``replica_id`` (ctor >
``TDT_REPLICA_ID`` > ``host:port``) stamped into its metrics
snapshot, its scheduler's trace instants, and its flight-dump
filenames, and answers the CHEAP health verb —

    → {"cmd": "health"}
    ← {"health": {"replica_id": ..., "seq": N, "uptime_s": ...,
                  "rolling": ..., "slo": ..., "queue_depth": ...,
                  "batch_occupancy": ..., "breakers": ..., ...}}

``health`` never force-evaluates SLOs and reads gauges lock-free
(``obs.fleet.replica_health``): monitoring N replicas at 1 Hz
perturbs no pump loop. ``seq`` is a monotonic per-server snapshot
number. ``registry="private"`` gives the server its own metrics
registry (``obs.scoped_registry`` routes its handler threads and
scheduler pump there), so several replicas in ONE process — the
``serving_fleet`` bench, the fleet tests — keep distinct,
correctly-fleet-summable metrics.

Per-request latency attribution (ISSUE 8): scheduler-served responses
carry a ``"timing"`` waterfall per prompt (queue_wait → prefill →
decode segments summing to the request's wall time, plus prefix-cache
savings and per-token share — ``obs.attrib``), and the last-K ring is
queryable —

    → {"cmd": "request_stats", "last": 8}
    ← {"requests": [waterfall, ...]}        # newest first

Tracing (docs/observability.md "Tracing"): the server also runs the
event tracer / flight recorder by default (``TDT_TRACE=0`` opts out).
Every generation request gets a trace ID — the client's own
``"trace_id"`` if it sent one, a fresh one otherwise — carried by its
``serving.request`` span (handler thread) and by its scheduler-side
``serving.admit`` / ``serving.retire`` instants and admission events
(pump thread, re-bound per admission), so the request's
queue → admit → retire story filters to one ID in an exported
timeline; the shared decode-step spans serve many requests at once
and stay unbound. The ID is echoed back in the response. The flight recorder dumps the last
``TDT_FLIGHT_SECONDS`` of events on demand —

    → {"cmd": "dump_trace"}
    ← {"dumped": "/tmp/tdt_trace/flight_cmd_....trace.json", ...}

— and automatically on unhandled per-request failures, watchdog
trips, breaker opens, and SIGTERM.

Text in/out (tokenizer round trip) is the client's job when a HF
tokenizer is available; the server moves token ids only, like the
reference's server.
"""

from __future__ import annotations

import itertools
import json
import os
import socket
import socketserver
import threading
import time

import jax.numpy as jnp
import numpy as np

from triton_dist_tpu import obs
from triton_dist_tpu.obs import fleet as _fleet
from triton_dist_tpu.obs import flight, trace


class _Handler(socketserver.StreamRequestHandler):
    def handle(self):
        # One scope per connection: this handler thread's emissions
        # (request counters, error accounting, everything the request
        # path records) land in the owning server's registry — the
        # per-replica isolation that keeps fleet counter sums correct
        # when several servers share a process (no-op when the server
        # uses the process-global registry). The connection registers
        # with the owner so a chaos-harness kill can SEVER live
        # connections (testing/chaos.py: a killed replica's clients
        # must see a dead socket, never a polite error reply).
        owner = self.server.model_server
        track = getattr(owner, "_track_connection", None)
        if track is not None:
            track(self.connection)
        try:
            with obs.scoped_registry(owner.registry):
                self._handle_scoped()
        finally:
            untrack = getattr(owner, "_untrack_connection", None)
            if untrack is not None:
                untrack(self.connection)

    def _handle_scoped(self):
        try:
            self._serve_lines()
        except OSError:
            # The peer vanished mid-read (reset/abort): routers
            # abandon dispatch connections at their per-attempt
            # deadline BY DESIGN (serving/router.py), and a chaos
            # sever does the same — connection-scoped, the server
            # keeps serving every other client.
            return

    def _serve_lines(self):
        for line in self.rfile:
            line = line.strip()
            if not line:
                continue
            # A per-request failure — malformed JSON, bad arguments, or
            # the engine/fused-kernel path blowing up — answers THIS
            # request with a structured error and keeps both the
            # connection and the serve loop alive: a bad request must
            # degrade a request, never the process (docs/resilience.md).
            try:
                req = json.loads(line)
            except Exception as e:
                obs.counter("server.errors").inc()
                resp = {"error": f"malformed request: {e}",
                        "type": type(e).__name__}
            else:
                # Length-prefixed binary framing (ISSUE 18): a request
                # carrying "nbytes" is followed by exactly that many
                # raw bytes (the kv_ship block payload) — read them
                # off the SAME buffered stream before the next JSON
                # line. A short read means the peer died mid-frame:
                # connection-scoped, like any other sever.
                nbytes = req.get("nbytes") if isinstance(req, dict) \
                    else None
                if nbytes is not None:
                    payload = self.rfile.read(int(nbytes))
                    if len(payload) != int(nbytes):
                        return
                    req["_payload"] = payload
                try:
                    resp = self.server.model_server._serve_request(req)
                except Exception as e:  # report, keep serving
                    obs.counter("server.errors").inc()
                    # The request died past parsing — an engine/kernel
                    # failure, not client garbage: leave a postmortem
                    # of what the process was doing (rate-limited,
                    # never raises; no-op when tracing is off).
                    flight.maybe_dump("serve_error")
                    resp = {"error": str(e) or repr(e),
                            "type": type(e).__name__}
            try:
                wire = json.dumps(resp)
            except (TypeError, ValueError) as e:
                obs.counter("server.errors").inc()
                wire = json.dumps({"error": f"unserializable response: "
                                            f"{e}",
                                   "type": type(e).__name__})
            try:
                self.wfile.write((wire + "\n").encode())
                self.wfile.flush()
            except OSError:
                # Client hung up mid-response: connection-scoped —
                # the ThreadingTCPServer keeps serving other clients.
                break


class _TCPServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True


class ModelServer:
    """Wraps an Engine behind a TCP JSON-lines protocol.

    By default generation runs through the continuous-batching
    :class:`~triton_dist_tpu.serving.scheduler.Scheduler`: every
    connection's prompts share ONE decode batch, so a short request
    admitted while a long generation is mid-decode completes without
    queueing behind it (docs/serving.md "Scheduler"). ``scheduler=False``
    restores the serialized-lock path (one generation at a time).
    Every decode path is schedulable — the mega one-program step takes
    per-row offsets and paged tables like the plain step (ISSUE 11) —
    so ``use_mega`` / ``decode_path`` engines stream through the
    shared batch like any other.
    """

    def __init__(self, engine, params, host: str = "127.0.0.1",
                 port: int = 0, telemetry: bool = True,
                 scheduler: bool | None = None,
                 max_waiting: int | None = None,
                 prefill_chunk: int | None = None,
                 replica_id: str | None = None, registry=None,
                 tier: str = "unified"):
        """``replica_id``: this server's stable fleet identity
        (explicit > ``TDT_REPLICA_ID`` > ``host:port`` after bind).
        ``registry``: ``"private"`` gives the server its own metrics
        registry (or pass a ``obs.Registry``) — REQUIRED for distinct
        per-replica metrics when several servers share one process;
        the default (None) keeps the historical process-global
        registry. ``tier`` (ISSUE 18): this replica's advertised role
        in a disaggregated fleet — ``"prefill"``, ``"decode"``, or the
        default ``"unified"``; it rides the health verb so a tiered
        router (``TDT_ROUTER_TIERS``) can pool replicas without extra
        config, and any scheduler-path paged server answers the
        ``kv_*``/``disagg_prefill`` verbs regardless of tier (the
        tier is placement policy, not capability)."""
        self.engine = engine
        self.params = params
        self.registry = None
        if registry == "private":
            self.registry = obs.Registry()
        elif registry is not None:
            self.registry = registry
        if telemetry:
            # A serving process wants its numbers scrapeable; direct
            # Engine users keep the zero-overhead no-op default.
            obs.enable()
            # ... and its flight recorder armed: the bounded ring
            # buffer is the whole cost, and a hang with no recorder is
            # the round-5 postmortem-less failure class. TDT_TRACE=0
            # opts out (docs/observability.md "Tracing").
            if trace.env_enabled(default=True):
                trace.enable()
                flight.install_signal_handlers()
        # Live connection registry (chaos harness: kill_replica severs
        # these; see _Handler.handle).
        self._conn_lock = threading.Lock()
        self._active_conns: set = set()
        # Bind FIRST so the default replica_id can be host:port — but
        # close the listening socket if the REST of construction
        # raises (e.g. a malformed TDT_MAX_WAITING inside the
        # Scheduler ctor): pre-ISSUE-14 the bind happened last, so a
        # ctor failure never left a bound fd behind.
        self._lock = threading.Lock()  # serialized path only
        self._srv = _TCPServer((host, port), _Handler)
        try:
            self._srv.model_server = self
            self.host, self.port = self._srv.server_address
            self.replica_id = str(
                replica_id
                or os.environ.get("TDT_REPLICA_ID", "").strip()
                or f"{self.host}:{self.port}")
            self._started_monotonic = time.monotonic()
            self._health_seq = itertools.count(1)  # thread-safe counter
            if telemetry:
                # Flight dumps (filename + metadata) carry the replica
                # identity so two same-host replicas' postmortems
                # cannot alias (in-process multi-server shares one
                # tracer — the last server's id wins there,
                # documented). Unconditional on tracing state: a
                # cheap global write now means dumps stay stamped
                # even when tracing is enabled AFTER server start.
                flight.set_replica_id(self.replica_id)
            if scheduler is None:
                # Auto: on for engines a stream session can actually
                # serve (test doubles without a kv keep the serialized
                # path). Oversubscribed paged pools stream via
                # block-granular admission (ISSUE 6), and mega engines
                # stream via the per-row mega step (ISSUE 11) —
                # neither is a special case anymore.
                # ``scheduler=False`` stays as the explicit
                # serialized-path override.
                scheduler = getattr(engine, "kv", None) is not None
            self.scheduler = None
            if scheduler:
                from triton_dist_tpu.serving.scheduler import Scheduler
                self.scheduler = Scheduler(
                    engine, params, max_waiting=max_waiting,
                    prefill_chunk=prefill_chunk,
                    replica_id=self.replica_id,
                    registry=self.registry).start()
            self.tier = str(tier)
            self.disagg = None
            if self.scheduler is not None \
                    and getattr(engine, "paged", False):
                # Disaggregated handoff endpoint (ISSUE 18,
                # serving/disagg.py): decode-only admission needs the
                # paged pools; non-paged or serialized servers simply
                # don't answer the kv verbs.
                from triton_dist_tpu.serving.disagg import \
                    DisaggEndpoint
                self.disagg = DisaggEndpoint(self)
        except BaseException:
            self._srv.server_close()
            raise
        self._thread: threading.Thread | None = None

    def _track_connection(self, conn) -> None:
        with self._conn_lock:
            self._active_conns.add(conn)

    def _untrack_connection(self, conn) -> None:
        with self._conn_lock:
            self._active_conns.discard(conn)

    def _serve_request(self, req: dict) -> dict:
        # Handler threads route their emissions into this replica's
        # registry (no-op scope when registry=None — the historical
        # process-global path).
        with obs.scoped_registry(self.registry):
            return self._serve_request_scoped(req)

    def _serve_request_scoped(self, req: dict) -> dict:
        if "cmd" in req:
            return self._serve_command(req)
        obs.counter("server.requests").inc()
        obs.gauge("server.inflight").inc()
        # One trace ID per request, bound to the handling thread: the
        # serving span below plus every engine/op/resilience event the
        # generation emits (same thread — generation runs under the
        # lock in this handler) carries it, and the client gets it
        # back for cross-referencing a later dump.
        trace_id = str(req.get("trace_id") or trace.new_trace_id())
        try:
            with trace.bind(trace_id), \
                    trace.span("serving.request", "serving",
                               args={"gen_len": req.get("gen_len"),
                                     "batch": len(req.get(
                                         "prompt_ids", []) or [])}):
                resp = self._serve_generate(req)
        finally:
            obs.gauge("server.inflight").dec()
        if trace.enabled():
            resp.setdefault("trace_id", trace_id)
        return resp

    def _serve_command(self, req: dict) -> dict:
        """Control-plane requests on the same JSON-lines protocol."""
        cmd = req["cmd"]
        if cmd == "health":
            # The CHEAP control verb (ISSUE 14): lock-free gauge/
            # counter peeks, NO SLO force-evaluation — the pump
            # refreshes the gauges every working iteration, and the
            # monotonic ``seq`` + ``uptime_s`` let the fleet view
            # judge freshness. Monitoring N replicas at 1 Hz through
            # this perturbs no pump loop (obs.fleet.replica_health).
            obs.counter("serving.replica_health_requests").inc()
            seq = next(self._health_seq)
            obs.gauge("serving.replica_health_seq").set(seq)
            health = _fleet.replica_health(
                self.replica_id, seq, self._started_monotonic,
                registry=self.registry or obs.get_registry(),
                engine=self.engine, scheduler=self.scheduler,
                tier=self.tier)
            obs.gauge("serving.replica_uptime_s").set(
                health["uptime_s"])
            return {"health": health}
        if cmd == "metrics":
            # Snapshot under the generation lock is NOT needed: the
            # registry is internally locked, and a scraper must not
            # queue behind a multi-second generation.
            if req.get("evaluate", True) \
                    and self.scheduler is not None \
                    and self.scheduler.slo is not None:
                # Rolling/burn gauges current as of THIS scrape (the
                # pump only evaluates while it is doing work).
                # ``"evaluate": false`` opts out — dashboards polling
                # N replicas read the last-evaluated gauges instead
                # of forcing N quantile merges per tick.
                self.scheduler.slo.evaluate(force=True)
            snap = obs.snapshot()
            snap["replica_id"] = self.replica_id
            if trace.enabled():
                # Tracing counts + last flight record ride inside the
                # snapshot (tools/report.py renders them as the
                # Tracing section; merge_snapshots ignores the key).
                snap["trace"] = trace.stats()
            from triton_dist_tpu.obs import devprof
            if devprof.last_profile() is not None \
                    or devprof.armed_reason() is not None:
                # Device-profile state (last parsed capture path,
                # armed reason) rides the same way — tools/report.py
                # and tools/top.py render it as the device-time
                # section.
                snap["devprof"] = devprof.stats()
            resp = {"metrics": snap}
            if req.get("format") == "prometheus":
                resp["prometheus"] = obs.render_prometheus(snap)
            return resp
        if cmd == "drain":
            # Graceful drain (ISSUE 15, docs/serving.md "Drain"): stop
            # admitting, finish what is in flight. ``"resume": true``
            # cancels; ``"wait_s": N`` blocks until idle (or the
            # deadline). The reply always carries the live in-flight
            # count so a router can poll the drain to completion.
            if self.scheduler is None:
                obs.counter("server.errors").inc()
                return {"error": "drain needs the scheduler path "
                                 "(scheduler=False serializes whole "
                                 "generations — stop the server "
                                 "instead)"}
            if req.get("resume"):
                self.scheduler.resume()
                return {"draining": False,
                        "inflight": self.scheduler.inflight()}
            self.scheduler.drain()
            drained = None
            if req.get("wait_s") is not None:
                drained = self.scheduler.wait_idle(
                    float(req["wait_s"]))
            resp = {"draining": True,
                    "inflight": self.scheduler.inflight()}
            if drained is not None:
                resp["drained"] = drained
            return resp
        if cmd == "dump_trace":
            if not trace.enabled():
                obs.counter("server.errors").inc()
                return {"error": "tracing is disabled (TDT_TRACE)"}
            path = flight.dump("cmd", last_s=req.get("seconds"))
            return {"dumped": path, "trace": trace.stats()}
        if cmd == "request_stats":
            # The attribution ring (obs.attrib): the newest `last`
            # finished requests' waterfalls, newest first.
            from triton_dist_tpu.obs import attrib
            return {"requests": attrib.last(req.get("last"))}
        if cmd == "history":
            # Sampled series (ISSUE 16, docs/serving.md "History"):
            # downsampled ring-buffer points from the scheduler's
            # opt-in sampler — ``{"history": null}`` when no sampler
            # runs (TDT_HISTORY unset), so dashboards degrade instead
            # of erroring. ``last_s`` trims the window, ``series``
            # filters names, ``max_points`` bounds the reply size
            # (sparkline scrapes need ~32 points, not the whole ring).
            sampler = getattr(self.scheduler, "history", None)
            if sampler is None:
                return {"history": None}
            series = req.get("series")
            return {"history": sampler.snapshot(
                last_s=req.get("last_s"),
                series=list(series) if series else None,
                max_points=req.get("max_points"))}
        if self.disagg is not None and cmd in self.disagg.VERBS:
            # Disaggregated handoff verbs (ISSUE 18): kv_offer /
            # kv_ship / kv_commit (decode side) and disagg_prefill
            # (prefill side). A verb failure answers THIS request with
            # the structured error the sender's fallback contract
            # expects (_serve_lines wraps it).
            return self.disagg.handle(cmd, req)
        obs.counter("server.errors").inc()
        return {"error": f"unknown cmd {cmd!r} (known: metrics, "
                         f"health, drain, dump_trace, request_stats, "
                         f"history, kv_offer, kv_ship, kv_commit, "
                         f"disagg_prefill)"}

    def _effective_gen_len(self, req: dict, prompts) -> int:
        """Clamp the requested gen_len to the protocol cap (4096) AND
        the engine's room (max_seq − longest prompt). The clamp is no
        longer silent: the response echoes the effective value under
        ``"gen_len"`` and every clamped request counts into
        ``server.gen_len_clamped``, so clients can tell they asked for
        more than they got."""
        requested = int(req.get("gen_len", 16))
        room = self.engine.kv.max_seq - max(
            (len(p) for p in prompts), default=0)
        gen_len = max(0, min(requested, 4096, room))
        if gen_len != requested:
            obs.counter("server.gen_len_clamped").inc()
        return gen_len

    def _serve_generate(self, req: dict) -> dict:
        t_req0 = time.perf_counter()
        prompts = req["prompt_ids"]
        gen_len = self._effective_gen_len(req, prompts)
        stop = req.get("stop_tokens")  # None → engine default (eos)
        if self.scheduler is not None:
            from triton_dist_tpu.serving.scheduler import (
                Draining, QueueFull)
            try:
                futures = self.scheduler.submit_many(
                    prompts, gen_len, stop_tokens=stop,
                    trace_id=trace.current_trace_id())
            except Draining:
                # Graceful drain in progress: structurally like
                # queue_full (retry elsewhere / later) but with its
                # own type so a router knows this replica is LEAVING,
                # not merely busy.
                obs.counter("server.backpressure_replies").inc()
                return {"error": "replica is draining — retry on "
                                 "another replica",
                        "type": "draining",
                        "inflight": self.scheduler.inflight(),
                        "retry_after_ms":
                            self.scheduler.retry_after_ms()}
            except QueueFull:
                # Structured backpressure, not an exception page: the
                # client sees WHY and can retry; the connection (and
                # every other request in flight) is untouched. The
                # retry_after_ms hint (rolling TPOT × queue depth,
                # clamped) tells it WHEN — ChatClient honors it
                # instead of hammering (docs/serving.md).
                obs.counter("server.backpressure_replies").inc()
                return {"error": "admission queue full — retry later",
                        "type": "queue_full",
                        "queue_depth": self.scheduler.queue_depth(),
                        "max_waiting": self.scheduler.max_waiting,
                        "retry_after_ms":
                            self.scheduler.retry_after_ms()}
            # Rows retire exactly at their first stop token, so the
            # uniform client contract (tokens end at and include the
            # first stop token) needs no trimming here.
            tokens = [f.result() for f in futures]
            ms = (time.perf_counter() - t_req0) * 1e3
            obs.histogram("server.request_ms").observe(ms)
            resp = {"tokens": tokens, "gen_len": gen_len,
                    "latency_ms": round(ms, 3)}
            # Per-prompt latency attribution (obs.attrib): where this
            # request's time went, segment sums matching latency_ms
            # up to handler↔pump handoff (docs/observability.md).
            timing = [f.timing for f in futures]
            if any(t is not None for t in timing):
                resp["timing"] = timing
            return resp
        return self._serve_generate_serialized(req, prompts, gen_len,
                                               stop, t_req0)

    def _serve_generate_serialized(self, req, prompts, gen_len, stop,
                                   t_req0) -> dict:
        # The pre-scheduler path (scheduler=False): a global lock
        # serializes whole generations. The request clock
        # starts BEFORE the lock: under load, queue wait is the
        # dominant latency component and server.request_ms must show
        # it (client-facing latency_ms keeps its original
        # generation-only meaning here).
        lens = [len(p) for p in prompts]
        ragged = len(set(lens)) > 1
        batch = self.engine.kv.batch
        # Uniform client contract across all three engine routes: each
        # row's tokens end at (and include) the first stop token.
        # serve()/serve_ragged() pad stopped rows to a rectangle with
        # the stop token; serve_stream() retires exactly — normalize to
        # the latter (the server branch taken is an internal engine
        # dimension the client cannot see).
        if stop is None:
            eos = getattr(self.engine.model.config, "eos_token_id", -1)
            stop_set = {eos} if eos >= 0 else set()
        else:
            stop_set = set(int(t) for t in stop)

        def trim(row):
            row = list(row)
            for i, t in enumerate(row):
                if t in stop_set:
                    return row[:i + 1]
            return row

        with self._lock:
            t0 = time.perf_counter()
            if len(prompts) > batch:
                # More requests than decode rows: continuous batching
                # pumps the stream through the fixed window
                # (Engine.serve_stream).
                rows = self.engine.serve_stream(self.params, prompts,
                                                gen_len, stop_tokens=stop)
                tokens = [r[ln:] for r, ln in zip(rows, lens)]
            elif ragged:
                rows = self.engine.serve_ragged(self.params, prompts,
                                                gen_len, stop_tokens=stop)
                tokens = [r[ln:].tolist() for r, ln in zip(rows, lens)]
            else:
                ids = np.asarray(prompts, np.int32)
                out = np.asarray(self.engine.serve(
                    self.params, jnp.asarray(ids), gen_len,
                    stop_tokens=stop))
                tokens = out[:, ids.shape[1]:].tolist()
            ms = (time.perf_counter() - t0) * 1e3
        obs.histogram("server.request_ms").observe(
            (time.perf_counter() - t_req0) * 1e3)
        return {"tokens": [trim(r) for r in tokens], "gen_len": gen_len,
                "latency_ms": round(ms, 3)}

    def start(self):
        self._thread = threading.Thread(target=self._srv.serve_forever,
                                        daemon=True)
        self._thread.start()
        if self.disagg is not None:
            # Same-process transport tier (ISSUE 18): a sibling
            # prefill replica in this process hands blocks over
            # directly instead of re-entering the TCP stack.
            from triton_dist_tpu.serving import disagg as _disagg
            _disagg.register_inproc(f"{self.host}:{self.port}",
                                    self.disagg)
        return self

    def stop(self):
        if self.disagg is not None:
            from triton_dist_tpu.serving import disagg as _disagg
            _disagg.unregister_inproc(f"{self.host}:{self.port}")
        self._srv.shutdown()
        self._srv.server_close()
        if self.scheduler is not None:
            self.scheduler.stop()


def main():  # pragma: no cover - manual demo
    import argparse
    import jax
    from jax.sharding import Mesh
    from triton_dist_tpu.models import AutoLLM, Engine, ModelConfig

    ap = argparse.ArgumentParser()
    ap.add_argument("--model-dir", default=None,
                    help="HF checkpoint dir (random tiny model if unset)")
    ap.add_argument("--port", type=int, default=8777)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--max-seq", type=int, default=1024)
    args = ap.parse_args()

    from triton_dist_tpu.runtime.compile_cache import (
        configure_compile_cache)
    from triton_dist_tpu.runtime.topology import topology_aware_grid
    configure_compile_cache()
    # Before the first program is built, not only at ModelServer's
    # construction: the compile log then holds the start-up's compiles.
    obs.enable()
    devices = np.array(jax.devices())
    mesh = Mesh(topology_aware_grid(devices, devices.shape), ("tp",))
    if args.model_dir:
        model, params = AutoLLM.from_pretrained(args.model_dir, mesh=mesh)
    else:
        cfg = ModelConfig(num_hidden_layers=2, hidden_size=256,
                          intermediate_size=512, num_attention_heads=8,
                          num_key_value_heads=8, head_dim=32,
                          vocab_size=1024)
        model = AutoLLM.build(cfg, mesh=mesh)
        params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, batch=args.batch, max_seq=args.max_seq)
    srv = ModelServer(eng, params, port=args.port).start()
    print(f"serving on {srv.host}:{srv.port}")
    threading.Event().wait()


if __name__ == "__main__":  # pragma: no cover
    main()
