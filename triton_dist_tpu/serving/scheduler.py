"""Cross-request continuous batching: one shared decode loop for every
connection.

The reference's model server — and our ``ModelServer`` before this
module — holds a global lock for an entire generation: concurrent
clients queue head-of-line behind whichever generation got there first,
even though the engine's continuous-batching machinery
(``Engine.serve_stream`` / ``StreamSession``) already knows how to
admit a new prompt into a freed decode row mid-flight. This module
closes that gap at the REQUEST level: a single scheduler thread owns
the engine's fixed decode batch and pumps one shared decode loop, while
handler threads enqueue requests into a bounded FIFO admission queue
and block on per-request futures. A 4-token request submitted while a
4096-token generation is mid-decode completes in milliseconds, not
minutes — T3's fine-grained-interleaving lesson (PAPERS.md) applied at
the request level: throughput under load is gated by the scheduler,
not the kernels.

Design:

- **One engine thread.** Only the pump thread touches the Engine's
  ``StreamSession``; handler threads interact through the queue and
  per-request done-events, so no generation lock exists at all.
- **A turn launches before it reads.** The admissions a turn was
  given are dispatched back to back (``StreamSession.launch_into_row``),
  the shared step behind them, and only then are the admissions' first
  tokens read, each stamped as it reaches the host (``Request.t_first``),
  and the step's tokens after them: the chip goes from program to
  program while the host catches up (docs/serving.md "The order of a
  turn").
- **Fair FIFO admission with backpressure.** :meth:`Scheduler.submit`
  appends to a bounded queue (``max_waiting`` / ``TDT_MAX_WAITING``,
  default 64, or four times the engine's rows if that is more); a full queue raises :class:`QueueFull`, which the
  server answers with a structured ``queue_full`` reply instead of
  stalling the connection. Admission order is strictly
  first-come-first-served.
- **Chunked prefill.** With ``prefill_chunk`` (``TDT_PREFILL_CHUNK``)
  set, long prompts prefill ``chunk`` tokens at a time — one slice per
  pump iteration, interleaved with the shared decode step — so
  admitting a long prompt cannot stall the token cadence of the rows
  already decoding (``StreamSession.prefill_step``).
- **Block-granular paged admission** (ISSUE 6). On paged engines the
  head of the queue additionally waits for enough free KV BLOCKS for
  its worst case (``StreamSession.can_admit``) — still strictly FIFO —
  and passes its ``gen_len`` budget through so the pool commits the
  decode tail. Oversubscribed pools therefore stream through the
  shared batch instead of falling back to the serialized path; a
  request that could never fit fails at ``submit()`` as ``ValueError``
  (docs/serving.md "Block-granular admission").
- **Decode-path agnostic** (ISSUE 11). The pump drives whatever decode
  step the session resolves — the plain jitted step or the mega
  one-program task-graph step (``Engine(use_mega=True)`` /
  ``decode_path="auto"``) — through the same
  :meth:`StreamSession.decode_burst` verb; greedy outputs are
  bit-identical either way (docs/serving.md "Decode-path selection").
- **Variable tokens per step** (ISSUE 13). A row emits 0..k+1 tokens
  per pump iteration: with ``Engine(spec=SpecConfig(...))`` each
  iteration drafts up to k tokens per row, verifies them in one
  widened step, and commits the accepted prefix atomically — a row
  whose burst contains its stop token retires MID-burst (the tail is
  discarded), and greedy outputs stay bit-identical to spec-off
  (docs/serving.md "Speculative decoding"). Fairness is unchanged:
  admission is still strictly FIFO per iteration, and a burst never
  exceeds the row's remaining ``gen_len`` budget.
- **Drain + in-flight accounting** (ISSUE 15). :meth:`Scheduler.drain`
  flips the scheduler to admit-nothing-new (``submit`` raises
  :class:`Draining`, the server answers a structured ``draining``
  reply, ``serving.draining`` advertises it through the health verb)
  while everything already in flight finishes; :meth:`inflight` counts
  the requests still owed an answer and :meth:`wait_idle` blocks until
  it reaches zero — the wait a graceful replica removal
  (``RouterServer.remove_replica``) rides. ``retry_after_ms_hint``
  turns rolling TPOT × queue depth into the backpressure hint both
  the single-server ``queue_full`` reply and the router's fleet-level
  shed carry.
- **Observability** (docs/observability.md): ``serving.queue_depth``
  and ``serving.batch_occupancy`` gauges, per-request
  ``serving.ttft_ms`` and ``serving.queue_wait_ms`` histograms,
  ``serving.admitted`` / ``serving.retired`` /
  ``serving.rejected_queue_full`` counters, and ``serving.admit`` /
  ``serving.retire`` instants on the trace timeline carrying each
  request's trace ID — a Perfetto dump of a loaded server shows rows
  churning through the batch.
- **SLO observatory** (ISSUE 8, docs/observability.md "SLOs and burn
  rates"). The pump feeds an :class:`obs.slo.SLOTracker`: every
  request's TTFT, queue wait, and per-output-token time (TPOT), plus
  each pump iteration's duration, land in rolling-window histograms
  (``serving.rolling.*`` gauges), and declarative SLO targets
  (``Engine(slo=...)`` / ``TDT_SLO_*`` env) are burn-rate-evaluated
  Google-SRE style each iteration — a breach arms the flight recorder
  so a latency regression leaves a Perfetto postmortem before
  anything crashes. Each retired request also gets a latency
  waterfall (``obs.attrib``: queue_wait → prefill → decode, prefix
  savings, per-token share) attached to its future (the server
  returns it under ``"timing"``) and pushed to the last-K ring behind
  ``{"cmd": "request_stats"}``.

Greedy results are bit-identical to per-request ``Engine.serve()``
(tests/test_scheduler.py): the scheduler drives the same
admission/decode programs ``serve_stream`` is proven on
(tests/test_engine_stream.py).
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time
import warnings

from triton_dist_tpu import obs
from triton_dist_tpu.models.engine import DEFERRED
from triton_dist_tpu.models.kv_cache import KVCacheLost
from triton_dist_tpu.obs import attrib, devprof, history, slo, trace

__all__ = ["DEFAULT_MAX_WAITING", "Draining", "QueueFull", "Request",
           "RETRY_AFTER_MAX_MS", "RETRY_AFTER_MIN_MS", "Scheduler",
           "retry_after_ms_hint"]

DEFAULT_MAX_WAITING = 64

#: Bounds on the ``retry_after_ms`` backpressure hint (ISSUE 15): the
#: floor keeps a quiet server from telling clients to hammer at 0 ms,
#: the cap keeps one deep queue from parking clients for minutes.
RETRY_AFTER_MIN_MS = 25
RETRY_AFTER_MAX_MS = 5000
#: The hint when no TPOT signal exists yet (cold server): one modest
#: beat, not zero.
RETRY_AFTER_DEFAULT_MS = 100


def retry_after_ms_hint(tpot_p50_ms, queue_depth) -> int:
    """Backpressure hint for ``queue_full`` / ``draining`` replies:
    how long a shed client should wait before retrying, derived from
    the rolling per-output-token time times the queue depth (a crude
    but honest estimate of when a queued slot frees up), clamped to
    ``[RETRY_AFTER_MIN_MS, RETRY_AFTER_MAX_MS]``. With no TPOT signal
    (cold server, SLO engine off) the hint is
    ``RETRY_AFTER_DEFAULT_MS`` — the one home for the formula shared
    by the single-server reply and the router's fleet-level shed
    (serving/router.py)."""
    try:
        tpot = float(tpot_p50_ms) if tpot_p50_ms is not None else 0.0
    except (TypeError, ValueError):
        tpot = 0.0
    if tpot <= 0.0:
        return RETRY_AFTER_DEFAULT_MS
    est = tpot * max(float(queue_depth or 0.0), 1.0)
    return int(min(max(est, RETRY_AFTER_MIN_MS), RETRY_AFTER_MAX_MS))


class QueueFull(RuntimeError):
    """Admission queue is at ``max_waiting`` — backpressure; the caller
    should retry later (the server turns this into a structured
    ``queue_full`` reply)."""


class Draining(QueueFull):
    """The scheduler is draining (ISSUE 15): it finishes what is in
    flight but admits nothing new — the server answers a structured
    ``draining`` reply so a router stops placing here and clients
    retry elsewhere."""


class Request:
    """One prompt's life through the shared batch: queued → admitted →
    decoding → done. Handler threads block on :meth:`result`; only the
    pump thread mutates the other fields."""

    __slots__ = ("prompt", "gen_len", "stop_set", "trace_id", "rid",
                 "t_submit", "t_admit", "t_first", "tokens", "error",
                 "done", "cached", "chunks", "timing", "draft_ms",
                 "verify_ms", "kv_export", "preloaded")

    def __init__(self, prompt, gen_len: int, stop_set, trace_id, rid):
        self.prompt = prompt
        self.gen_len = gen_len
        self.stop_set = stop_set
        self.trace_id = trace_id
        self.rid = rid
        self.t_submit = time.perf_counter()
        self.t_admit = None
        self.t_first = None
        self.tokens: list[int] = []     # generated tokens (no prompt)
        self.error: BaseException | None = None
        self.done = threading.Event()
        self.cached = 0            # prefix-cache-hit prompt tokens
        self.chunks = 0            # prefill slices dispatched
        self.timing: dict | None = None   # attribution waterfall
        self.draft_ms = 0.0        # spec draft time this request rode
        self.verify_ms = 0.0       # spec verify time this request rode
        # Disaggregated handoff hooks (ISSUE 18, serving/disagg.py):
        # ``kv_export`` is called by the pump as fn(session, row,
        # request) just BEFORE the row retires — while its KV blocks
        # are still mapped — so a prefill replica can extract the
        # finished chain for streaming; ``preloaded`` =
        # {"first": tok, "blocks": {j: payload}} admits the row
        # DECODE-ONLY through StreamSession.adopt_row instead of
        # running a prefill program.
        self.kv_export = None
        self.preloaded: dict | None = None

    def result(self, timeout: float | None = None) -> list[int]:
        """Block until the request finishes; returns the generated
        tokens (ending at, and including, the first stop token).
        Raises the scheduler-side failure if the request degraded, or
        ``TimeoutError`` if ``timeout`` elapses first."""
        if not self.done.wait(timeout):
            raise TimeoutError(
                f"request {self.rid} not done within {timeout}s")
        if self.error is not None:
            raise self.error
        return self.tokens


class Scheduler:
    """Continuous-batching serving scheduler over one Engine.

    ``submit()`` from any thread; a single pump thread drives the
    engine's :class:`~triton_dist_tpu.models.engine.StreamSession` so
    prompts from different connections coexist in one decode batch.
    """

    def __init__(self, engine, params, max_waiting: int | None = None,
                 prefill_chunk: int | None = None, slo_tracker=None,
                 devprof_sampler=None, history_sampler=None,
                 replica_id: str | None = None, registry=None):
        self.engine = engine
        self.params = params
        # Fleet identity (ISSUE 14): stamped into this scheduler's
        # admit/retire trace instants so two same-host replicas'
        # merged Perfetto streams cannot alias, and — via
        # ``registry`` + obs.scoped_registry on the pump thread —
        # into a per-replica metrics registry when the server runs
        # several replicas in one process.
        self.replica_id = replica_id
        self._registry = registry
        if max_waiting is None:
            # The default grows with the decode window: a queue shorter
            # than twice the rows refuses a burst the engine would seat
            # within two turns (64 rows: 256 waiting).
            rows = getattr(getattr(engine, "kv", None), "batch", 0)
            max_waiting = obs.env_int(
                "TDT_MAX_WAITING", max(DEFAULT_MAX_WAITING, 4 * rows))
        if max_waiting <= 0:
            raise ValueError(f"max_waiting must be positive: {max_waiting}")
        self.max_waiting = max_waiting
        if prefill_chunk is None:
            # minimum=1 keeps "0" an error (like any non-positive
            # chunk); the unset default never hits the minimum check.
            prefill_chunk = obs.env_int("TDT_PREFILL_CHUNK", 0,
                                        minimum=1) or None
        if prefill_chunk is not None and prefill_chunk <= 0:
            raise ValueError(
                f"prefill_chunk must be positive: {prefill_chunk}")
        self.prefill_chunk = prefill_chunk
        # The SLO observatory for this scheduler: rolling TTFT / TPOT /
        # queue-wait / pump-time windows + burn-rate targets. Targets
        # come from Engine(slo=...), falling back to the env-overridable
        # defaults; pass an SLOTracker (tests: injectable clock) or a
        # target list to override, False to disable (TDT_SLO=0 does too).
        self.slo: slo.SLOTracker | None = None
        if slo_tracker is not False and slo.enabled():
            if isinstance(slo_tracker, slo.SLOTracker):
                self.slo = slo_tracker
            else:
                targets = (slo_tracker if slo_tracker is not None
                           else getattr(engine, "slo", None))
                self.slo = slo.SLOTracker(targets=targets)
        # Device-profile sampling of pump iterations (obs.devprof,
        # docs/observability.md "Device-time truth"): continuous
        # (TDT_DEVPROF_EVERY) and/or breach-armed
        # (TDT_DEVPROF_ON_BREACH via the flight recorder). None when
        # both knobs are off — the pump then pays nothing. Pass a
        # PumpSampler to override (tests: sync parse), False to
        # disable regardless of env.
        if devprof_sampler is False:
            self.devprof = None
        elif devprof_sampler is not None:
            self.devprof = devprof_sampler
        else:
            self.devprof = devprof.PumpSampler.from_env()
        # Sampled signal history (obs.history, docs/observability.md
        # "History plane"): an opt-in background sampler recording
        # this replica's gauges (values) and counters (rates) into
        # ring-buffered series behind the {"cmd": "history"} verb,
        # plus the early-warning detector pass. None unless
        # TDT_HISTORY=1 — no sampler, no thread, no cost. Pass a
        # HistorySampler to override (tests: thread=False + explicit
        # sample_once timestamps), False to disable regardless of env.
        if history_sampler is False:
            self.history = None
        elif history_sampler is not None:
            self.history = history_sampler
        else:
            self.history = history.HistorySampler.from_env(
                registry=self._registry)
        self._cond = threading.Condition()
        self._queue: collections.deque[Request] = collections.deque()
        self._rid = 0
        self._running = False
        self._thread: threading.Thread | None = None
        self._session = None
        self._inflight = 0          # live requests queued or in rows
        self._draining = False
        #: Injectable per-iteration hook (testing.chaos.wedge_pump):
        #: called by the pump thread at the top of every work
        #: iteration, OUTSIDE the scheduler lock — a hook that blocks
        #: wedges the pump exactly the way a stuck device step would,
        #: while handler threads (health, metrics) keep answering.
        self.pump_hook = None

    # -- client side -------------------------------------------------------
    def queue_depth(self) -> int:
        return len(self._queue)

    def inflight(self) -> int:
        """Live requests the scheduler currently owes an answer —
        queued plus admitted (in a decode row or mid-prefill). The
        in-flight accounting a graceful drain waits on (ISSUE 15)."""
        with self._cond:
            return self._inflight

    @property
    def draining(self) -> bool:
        return self._draining

    def drain(self) -> None:
        """Stop admitting NEW requests (``submit`` raises
        :class:`Draining`); everything already queued or in flight
        finishes normally. Publishes ``serving.draining`` so the
        replica's health verb advertises it and a router stops placing
        here (docs/serving.md "Drain")."""
        with self._cond:
            self._draining = True
        with obs.scoped_registry(self._registry):
            obs.gauge("serving.draining").set(1)

    def resume(self) -> None:
        """Cancel a drain: the scheduler admits again."""
        with self._cond:
            self._draining = False
        with obs.scoped_registry(self._registry):
            obs.gauge("serving.draining").set(0)

    def wait_idle(self, timeout: float | None = None) -> bool:
        """Block until no request is in flight (the drain wait);
        True when idle, False if ``timeout`` elapsed first."""
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        with self._cond:
            while self._inflight > 0:
                left = (None if deadline is None
                        else deadline - time.monotonic())
                if left is not None and left <= 0:
                    return False
                self._cond.wait(0.05 if left is None
                                else min(left, 0.05))
            return True

    def retry_after_ms(self) -> int:
        """This scheduler's backpressure hint (rolling TPOT p50 ×
        queue depth, clamped — :func:`retry_after_ms_hint`), read
        lock-free from the replica's own registry like the health
        verb."""
        from triton_dist_tpu.obs import fleet as _fleet
        g = _fleet.peek_gauges(self._registry
                               or obs.get_registry())
        return retry_after_ms_hint(
            g.get("serving.rolling.tpot_p50_ms"),
            g.get("serving.queue_depth", len(self._queue)))

    def _make_request(self, prompt, gen_len, stop_tokens, trace_id):
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("prompts must be non-empty")
        gen_len = int(gen_len)
        if len(prompt) + max(gen_len, 0) > self.engine.kv.max_seq:
            raise ValueError(
                f"prompt ({len(prompt)}) + gen_len ({gen_len}) must fit "
                f"max_seq ({self.engine.kv.max_seq})")
        if gen_len > 0 and getattr(self.engine, "paged", False):
            # Never-fitting requests must fail HERE, not queue: the
            # pump admits strictly FIFO, so an unadmittable head would
            # deadlock everything behind it.
            kv = self.engine.kv
            if not kv.fits_pool(len(prompt), gen_len):
                raise ValueError(
                    f"prompt ({len(prompt)}) + gen_len ({gen_len}) can "
                    f"never fit the block pool "
                    f"({kv.slots_per_dev} slots/device, page "
                    f"{kv.page_size}) — shrink the request or size the "
                    f"pool up")
        if stop_tokens is None:
            eos = getattr(self.engine.model.config, "eos_token_id", -1)
            stop_set = {eos} if eos >= 0 else set()
        else:
            stop_set = {int(t) for t in stop_tokens}
        self._rid += 1
        return Request(prompt, gen_len, stop_set, trace_id, self._rid)

    def submit(self, prompt, gen_len: int, stop_tokens=None,
               trace_id: str | None = None, kv_export=None) -> Request:
        """Enqueue one prompt; returns its :class:`Request` future.
        Raises :class:`QueueFull` when ``max_waiting`` requests are
        already queued, ``ValueError`` on an unservable request.
        ``kv_export`` (ISSUE 18): per-request retirement hook — see
        :class:`Request`; attached atomically with the enqueue so the
        pump can never retire the row before the hook exists."""
        return self.submit_many([prompt], gen_len, stop_tokens=stop_tokens,
                                trace_id=trace_id, kv_export=kv_export)[0]

    def submit_many(self, prompts, gen_len: int, stop_tokens=None,
                    trace_id: str | None = None,
                    kv_export=None) -> list[Request]:
        """Atomically enqueue several prompts (one client request's
        batch): either every prompt is queued or none is — a
        half-admitted batch is worse than a clean ``queue_full``
        reply."""
        with self._cond:
            if not self._running:
                raise RuntimeError("scheduler is not running")
            if self._draining:
                raise Draining(
                    "scheduler is draining — this replica admits "
                    "nothing new; retry on another replica")
            reqs = [self._make_request(p, gen_len, stop_tokens, trace_id)
                    for p in prompts]
            if kv_export is not None:
                for r in reqs:
                    r.kv_export = kv_export
            live = [r for r in reqs if r.gen_len > 0]
            for r in reqs:
                if r.gen_len <= 0:      # nothing to generate
                    r.done.set()
            if len(live) > self.max_waiting:
                # NOT QueueFull: retrying can never help — the batch
                # exceeds queue capacity even when idle. The server
                # turns ValueError into a non-retryable structured
                # error instead of a "retry later" reply.
                raise ValueError(
                    f"request batches {len(live)} prompts but the "
                    f"admission queue holds max_waiting="
                    f"{self.max_waiting} — split the batch")
            if live:
                if len(self._queue) + len(live) > self.max_waiting:
                    obs.counter("serving.rejected_queue_full").inc(
                        len(live))
                    raise QueueFull(
                        f"admission queue full "
                        f"({len(self._queue)} waiting, "
                        f"max_waiting {self.max_waiting})")
                self._queue.extend(live)
                self._inflight += len(live)
                obs.gauge("serving.queue_depth").set(len(self._queue))
                self._cond.notify()
        return reqs

    def submit_preloaded(self, prompt, gen_len: int, first: int,
                         blocks: dict, stop_tokens=None,
                         trace_id: str | None = None) -> Request:
        """Enqueue one DECODE-ONLY request from a verified
        disaggregated handoff (ISSUE 18, serving/disagg.py): the KV
        chain for ``prompt`` was streamed in (``blocks``: block index
        → packed payload) and ``first`` is the prefill side's sampled
        token, so admission runs :meth:`StreamSession.adopt_row`
        instead of a prefill program. Same FIFO queue, backpressure,
        and drain semantics as :meth:`submit`; the request's tokens
        include ``first``."""
        with self._cond:
            if not self._running:
                raise RuntimeError("scheduler is not running")
            if self._draining:
                raise Draining(
                    "scheduler is draining — this replica admits "
                    "nothing new; retry on another replica")
            req = self._make_request(prompt, gen_len, stop_tokens,
                                     trace_id)
            req.preloaded = {"first": int(first), "blocks": blocks}
            if req.gen_len <= 0:
                req.done.set()
                return req
            if len(self._queue) + 1 > self.max_waiting:
                obs.counter("serving.rejected_queue_full").inc()
                raise QueueFull(
                    f"admission queue full ({len(self._queue)} "
                    f"waiting, max_waiting {self.max_waiting})")
            self._queue.append(req)
            self._inflight += 1
            obs.gauge("serving.queue_depth").set(len(self._queue))
            self._cond.notify()
        return req

    def generate(self, prompt, gen_len: int, stop_tokens=None,
                 trace_id: str | None = None,
                 timeout: float | None = None) -> list[int]:
        """submit() + result(): the generated tokens for one prompt."""
        return self.submit(prompt, gen_len, stop_tokens=stop_tokens,
                           trace_id=trace_id).result(timeout)

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "Scheduler":
        with self._cond:
            if self._running:
                return self
            self._running = True
        self._thread = threading.Thread(target=self._pump,
                                        name="tdt-scheduler", daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout: float = 30.0) -> None:
        """Stop the pump thread; queued and in-flight requests fail
        with a "scheduler stopped" error (their handlers unblock)."""
        with self._cond:
            self._running = False
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None

    # -- the pump ----------------------------------------------------------
    def _bind(self, req: Request):
        """Per-request trace binding around that request's OWN engine
        work (admission prefill): its stream_admission instant — and,
        on the first compile, the op instants the programs emit — land
        under the request's trace ID. The shared decode step serves
        many requests at once and stays unbound."""
        return (trace.bind(req.trace_id) if req.trace_id
                else contextlib.nullcontext())

    def _targs(self, args: dict) -> dict:
        """Stamp this scheduler's replica identity into a trace-event
        args dict (ISSUE 14): two same-host replicas' admit/retire
        streams stay distinguishable in a merged Perfetto view."""
        if self.replica_id:
            args["replica"] = self.replica_id
        return args

    def _fail(self, req: Request, exc: BaseException) -> None:
        req.error = exc
        self._finish(req)

    def _finish(self, req: Request) -> None:
        """Mark one live request done and release its in-flight slot
        (idempotent — the pump-death drain may revisit an already
        failed request). Wakes :meth:`wait_idle` when the count hits
        zero."""
        if req.done.is_set():
            return
        with self._cond:
            if self._inflight > 0:
                self._inflight -= 1
            if self._inflight == 0:
                self._cond.notify_all()
        req.done.set()

    def _pump(self) -> None:
        """Pump-thread entry: however the loop exits — clean stop, a
        session that cannot even be CONSTRUCTED (e.g. an oversubscribed
        paged pool, legal for plain serve()), or an unexpected crash —
        every queued and in-flight waiter is unblocked with an error
        and the scheduler stops accepting work. A dead pump with
        ``_running`` still True would otherwise hang every
        ``result()`` caller forever."""
        rows: dict[int, Request] = {}        # occupied rows (any state)
        # The pump's emissions (and everything the engine work it
        # drives emits on this thread — loop, failure accounting, and
        # shutdown drain alike) land in the replica's own registry
        # when one was given; scoped_registry(None) is a no-op (the
        # process-global registry keeps receiving).
        with obs.scoped_registry(self._registry):
            exc = self._pump_guarded(rows)
        if exc is not None:
            # The waiters already carry the exception; re-raising from
            # a daemon thread would only add unhandled-thread noise.
            warnings.warn(f"scheduler pump died: {exc!r}",
                          RuntimeWarning, stacklevel=2)

    def _pump_guarded(self, rows: dict) -> BaseException | None:
        exc: BaseException | None = None
        try:
            self._pump_loop(rows)
        except BaseException as e:  # noqa: BLE001 — drain, then surface
            exc = e
            obs.counter("serving.pump_errors").inc()
        finally:
            with self._cond:
                self._running = False
                leftovers = list(self._queue)
                self._queue.clear()
                obs.gauge("serving.queue_depth").set(0)
            err = RuntimeError("scheduler stopped" if exc is None
                               else f"scheduler died: {exc!r}")
            for req in leftovers + list(rows.values()):
                self._fail(req, err)
            obs.gauge("serving.batch_occupancy").set(0)
            sess, self._session = self._session, None
            if sess is not None:
                try:
                    # Release what in-flight rows still hold (paged
                    # block pools): a stop mid-generation must not
                    # strand their blocks.
                    sess.close()
                except Exception:  # noqa: BLE001 — shutdown best-effort
                    pass
            if self.devprof is not None:
                try:
                    # A stop mid-capture must still end the profiler
                    # session (and parse what it got).
                    self.devprof.close()
                except Exception:  # noqa: BLE001 — shutdown best-effort
                    pass
            if self.history is not None:
                try:
                    # Stop the sampler thread and release the flight
                    # recorder's history-provider slot.
                    self.history.close()
                except Exception:  # noqa: BLE001 — shutdown best-effort
                    pass
        return exc

    def _pump_loop(self, rows: dict) -> None:
        sess = self.engine.stream_session(self.params)
        self._session = sess
        budgets: dict[int, int] = {}
        prefilling: set[int] = set()         # rows mid-chunked-prefill
        occupancy = obs.gauge("serving.batch_occupancy")

        def restart(e: Exception, *admitting: Request) -> None:
            """The session died (the shared step failed, or an
            admission lost the caches it had donated): every occupant
            degrades, ``admitting`` (a request not yet in ``rows``)
            with them, and a fresh session opens; the scheduler keeps
            serving."""
            nonlocal sess
            obs.counter("serving.pump_errors").inc()
            for req in [*rows.values(), *admitting]:
                self._fail(req, e)
            rows.clear()
            budgets.clear()
            prefilling.clear()
            sess = self.engine.stream_session(self.params)
            self._session = sess
            occupancy.set(0)

        def record(row: int, req: Request, tok: int,
                   t: float | None = None) -> None:
            """Book one token; ``t``: when it reached the host, where
            that was before now (a deferred first token's stamp)."""
            req.tokens.append(tok)
            if req.t_first is None:
                req.t_first = time.perf_counter() if t is None else t
                ttft_ms = (req.t_first - req.t_submit) * 1e3
                obs.histogram("serving.ttft_ms").observe(ttft_ms)
                if self.slo is not None:
                    self.slo.observe("ttft", ttft_ms)
            budgets[row] -= 1
            if budgets[row] <= 0 or tok in req.stop_set:
                if req.kv_export is not None:
                    # Disaggregated handoff (ISSUE 18): extract the
                    # row's finished KV chain while its blocks are
                    # still mapped — retire_row releases them eagerly.
                    # Export failure degrades the HANDOFF (the caller
                    # falls back to a local re-prefill), never the
                    # request itself.
                    try:
                        req.kv_export(sess, row, req)
                    except Exception:  # noqa: BLE001 — handoff-scoped
                        obs.counter("disagg.export_errors").inc()
                sess.retire_row(row)
                rows.pop(row)
                budgets.pop(row)
                obs.counter("serving.retired").inc()
                t_done = time.perf_counter()
                # The request's latency waterfall (obs.attrib): same
                # clock readings as the trace instants, partitioned
                # queue_wait → prefill → decode so the segments sum to
                # the request's wall time by construction.
                req.timing = attrib.build(
                    rid=req.rid, trace_id=req.trace_id,
                    t_submit=req.t_submit, t_admit=req.t_admit,
                    t_first=req.t_first, t_done=t_done,
                    prompt_tokens=len(req.prompt),
                    tokens=len(req.tokens), cached_tokens=req.cached,
                    prefill_chunks=req.chunks,
                    draft_ms=req.draft_ms, verify_ms=req.verify_ms)
                attrib.push(req.timing)
                if req.timing["tpot_ms"] is not None:
                    # Cumulative TPOT histogram next to the rolling
                    # window: per-replica snapshots of it merge
                    # BUCKET-WISE into the fleet TPOT percentiles
                    # (obs.fleet.merge_fleet_snapshots — a fleet p99
                    # must come from summed buckets, never from
                    # averaging per-replica percentiles).
                    obs.histogram("serving.tpot_ms").observe(
                        req.timing["tpot_ms"])
                    if self.slo is not None:
                        self.slo.observe("tpot", req.timing["tpot_ms"])
                trace.emit("i", "serving.retire", "serving",
                           args=self._targs({"row": row, "rid": req.rid,
                                             "tokens": len(req.tokens)}),
                           trace_id=req.trace_id)
                self._finish(req)

        def admit(row: int, req: Request) -> None:
            req.t_admit = time.perf_counter()
            qw_ms = (req.t_admit - req.t_submit) * 1e3
            obs.histogram("serving.queue_wait_ms").observe(qw_ms)
            if self.slo is not None:
                self.slo.observe("queue_wait", qw_ms)
            obs.counter("serving.admitted").inc()
            trace.emit("i", "serving.admit", "serving",
                       args=self._targs({
                           "row": row, "rid": req.rid,
                           "prompt_len": len(req.prompt),
                           "queued_ms": round(
                               (req.t_admit - req.t_submit) * 1e3, 3)}),
                       trace_id=req.trace_id)
            try:
                with self._bind(req):
                    if req.preloaded is not None:
                        # Decode-only admission from a verified
                        # disaggregated handoff (ISSUE 18): the KV
                        # chain was streamed in, no prefill runs.
                        first = sess.adopt_row(
                            row, req.prompt,
                            req.preloaded["first"],
                            req.gen_len, req.preloaded["blocks"])
                    else:
                        # Launched, not waited for: where the session
                        # can, the first token is read behind this
                        # turn's step (DEFERRED).
                        first = sess.launch_into_row(
                            row, req.prompt, chunk=self.prefill_chunk,
                            gen_budget=req.gen_len)
            except KVCacheLost as e:
                # The admission failed AFTER dispatch and took every
                # row's K/V with it: not one request's failure.
                restart(e, req)
                return
            except Exception as e:  # noqa: BLE001 — degrade THIS request
                sess.cancel_prefill(row)
                obs.counter("serving.admit_errors").inc()
                self._fail(req, e)
                return
            req.chunks = 1          # one-shot, or the first slice
            rows[row] = req
            budgets[row] = req.gen_len
            if first is None:
                prefilling.add(row)
                return
            req.cached = (getattr(sess, "admit_info", None)
                          or {}).get("cached", 0)
            if first is not DEFERRED:
                record(row, req, first)

        while True:
            if self.devprof is not None and not rows and not self._queue:
                # Going idle with a multi-iteration capture open would
                # leave the jax.profiler session running until the
                # next request (maybe hours: a breach often precedes a
                # traffic drain). End it here — BEFORE the cond lock,
                # session teardown is file I/O — and parse what it
                # got: a short postmortem beats a never-closing one.
                self.devprof.close()
            admits = []
            with self._cond:
                while self._running and not self._queue and not rows:
                    # No live row and nothing queued: the idleness
                    # that is the traffic's, not the program's.
                    with obs.span("serving.pump_wait"):
                        self._cond.wait()
                if not self._running:
                    break
                free = sess.free_rows()
                # Block-granular admission (paged engines): the head
                # of the queue waits until enough blocks are free for
                # its worst case — strictly FIFO, no skip-ahead.
                # ``pending`` accumulates the demand of this batch's
                # earlier admits (they run outside the lock, so the
                # pool hasn't seen them yet).
                pending = None
                while self._queue and free:
                    head = self._queue[0]
                    if not sess.can_admit(len(head.prompt),
                                          head.gen_len, extra=pending):
                        break
                    need = sess.admission_need(len(head.prompt),
                                               head.gen_len)
                    if need is not None:
                        pending = need if pending is None \
                            else pending + need
                    admits.append((free.pop(0), self._queue.popleft()))
                obs.gauge("serving.queue_depth").set(len(self._queue))
            # Engine work happens OUTSIDE the lock: submitters only ever
            # wait on queue capacity, never on device time. The devprof
            # sampler wraps exactly this lock-free region — a capture
            # can span it but never a held scheduler lock.
            hook = self.pump_hook
            if hook is not None:
                # Chaos/test hook (testing.chaos.wedge_pump): runs in
                # the lock-free work region, so a blocking hook wedges
                # engine progress — in-flight rows stall, admissions
                # stop — while handler threads stay responsive (the
                # wedged-replica failure class the router's dispatch
                # deadline exists for).
                hook()
            # One turn of engine work (the cond wait above is idleness,
            # not work); the admissions and the shared step nest inside
            # it on this thread, so the turn's self time is the pump's
            # own: token recording, retirement, waking handlers.
            work = bool(admits or rows)
            turn = (obs.span("serving.pump_iteration") if work
                    else contextlib.nullcontext())
            prof = (self.devprof.iteration()
                    if self.devprof is not None and work
                    else contextlib.nullcontext())
            with turn, prof:
                for row, req in admits:
                    admit(row, req)
                for row in sorted(prefilling):  # one slice each, FIFO-ish
                    req = rows[row]
                    try:
                        with self._bind(req):
                            first = sess.prefill_step(row)
                    except KVCacheLost as e:
                        restart(e)
                        break
                    except Exception as e:  # noqa: BLE001
                        sess.cancel_prefill(row)
                        prefilling.discard(row)
                        rows.pop(row)
                        budgets.pop(row, None)
                        obs.counter("serving.admit_errors").inc()
                        self._fail(req, e)
                        continue
                    req.chunks += 1
                    if first is not None:
                        prefilling.discard(row)
                        req.cached = (getattr(sess, "admit_info", None)
                                      or {}).get("cached", 0)
                        record(row, req, first)
                occupancy.set(len(rows))
                live = [(r, rows[r]) for r in sorted(rows)
                        if r not in prefilling]
                if live:
                    # Resolve the decode path for THIS step, and — only
                    # while a device capture is open — bracket the
                    # shared step alone with the per-path label
                    # (devprof.step_label: device.step.mega vs .plain),
                    # nested inside the whole-iteration device.step
                    # window. Admission/prefill work stays OUTSIDE the
                    # per-path window, so the device.step.<kind>.*
                    # gauges hold pure decode-step time — what the auto
                    # policy (Engine(decode_path="auto")) arbitrates
                    # on; labeling the whole iteration would book
                    # prefill compiles as decode cost.
                    kind_fn = getattr(sess, "decode_kind", None)
                    kind = kind_fn() if kind_fn is not None else None
                    ann = contextlib.nullcontext()
                    if kind and self.devprof is not None \
                            and self.devprof.capturing:
                        from triton_dist_tpu.tools.profiler import \
                            annotate
                        ann = annotate(devprof.step_label(kind))
                    try:
                        with ann:
                            # Variable tokens per row per iteration
                            # (ISSUE 13): one token on the base paths,
                            # 1..k+1 from a speculative verify step.
                            bursts = sess.decode_burst()
                    except Exception as e:  # noqa: BLE001
                        # The SHARED step died, or an admission
                        # launched before it did and its first token
                        # said so: the cache state is suspect (and
                        # donated away).
                        restart(e)
                        continue
                    # First tokens that came home while the step ran,
                    # with the instant they did; one that ends its
                    # request retires the row, and the loop below
                    # drops what the step made for it.
                    for row, tok, t in sess.take_first_tokens():
                        record(row, rows[row], tok, t)
                    bt = sess.last_burst_timing
                    for row, req in live:
                        if rows.get(row) is not req:   # failed above
                            continue
                        if bt is not None:
                            # Draft/verify sub-attribution: shared step
                            # time booked to every rider, like the
                            # decode wall-clock itself (obs.attrib).
                            req.draft_ms += bt["draft_ms"]
                            req.verify_ms += bt["verify_ms"]
                        for tok in bursts.get(row, ()):
                            if rows.get(row) is not req:
                                break   # retired (stop/EOS/first token)
                            record(row, req, int(tok))
            occupancy.set(len(rows))
            if work and self.slo is not None:
                # The span's own reading (None when telemetry and
                # tracing are both off: nothing was timed). Evaluation
                # is rate-limited inside the tracker; a breach arms the
                # flight recorder (obs.slo).
                if turn.elapsed_ms is not None:
                    self.slo.observe("pump", turn.elapsed_ms)
                self.slo.evaluate()
