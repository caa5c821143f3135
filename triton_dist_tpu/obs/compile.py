"""Compile log: what JAX traced, lowered, compiled or fetched from the
persistent cache, by function, with start and end.

JAX reports each of these through ``jax.monitoring`` when, and only
when, a program is built: nothing here runs on a step or an admission
that is already compiled. :func:`install` (``obs.enable()`` calls it)
registers the listeners once per process; every callback returns at
once while ``obs`` is disabled. One record per event::

    {"t0", "t1", "s", "phase", "fun", "trace_id"}

``t0``/``t1`` are seconds on ``time.monotonic()``, ``fun`` the event's
``fun_name``, ``trace_id`` the thread's bound request
(``trace.current_trace_id()``), and ``phase`` one of

- ``trace``: Python to jaxpr (``jaxpr_trace_duration``);
- ``lower``: jaxpr to StableHLO, the Pallas-to-Mosaic lowering of every
  ``pallas_call`` in the program included
  (``jaxpr_to_mlir_module_duration``);
- ``compile``: a ``backend_compile_duration`` with no persistent-cache
  hit reported on its thread meanwhile: XLA compiled;
- ``cache_load``: one with a hit: the executable came from the cache.

**Each second is booked once.** Events nest on a thread. A trace
directly inside a trace (a ``jit`` called while another is traced,
every ``jnp`` wrapper among them: thousands for one program) gets no
record: its seconds stay in the outermost trace's. Anything else that
closes inside an open event (an eager op lowered and compiled while a
trace evaluates a constant) keeps its record, and the enclosing record
loses those seconds: ``s`` is a record's duration minus that of the
records directly inside it, so ``totals`` can be added up. Threads
that build programs at the same time are each counted in full. A trace
event under a millisecond is dropped: JAX reports one on EVERY call of
a compiled function that cannot take its C++ fast path (a program with
effects, as interpreted Pallas kernels have), when it finds the jaxpr
in its tracing cache; the smallest real trace takes about that long.

The same events land in ``obs``'s two stores: the ``compile.*``
counters (docs/observability.md) and, when the tracer is on, one
complete event ``compile.<phase>`` on the named track ``compile``.
"""

from __future__ import annotations

import collections
import threading
import time

from triton_dist_tpu.obs import registry as _registry
from triton_dist_tpu.obs import trace as _trace

__all__ = ["MAX_RECORDS", "MIN_TRACE_S", "PHASES", "TRACK", "compile_log",
           "install", "reset"]

_BACKEND = "/jax/core/compile/backend_compile_duration"
_SPANS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
          _BACKEND: "compile"}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

PHASES = ("trace", "lower", "compile", "cache_load")
#: The named tracer track (its own ring: pump events cannot evict it).
TRACK = "compile"
#: A trace event shorter than this is a tracing-cache hit, not a trace.
MIN_TRACE_S = 1e-3
#: Records kept; older ones fall off, ``compile.records_dropped`` and
#: the log's ``dropped`` count them.
MAX_RECORDS = 4096

_LOCK = threading.Lock()
_RECORDS: collections.deque = collections.deque()
_DROPPED = 0
_INSTALLED = False
_TLS = threading.local()


def _open_spans() -> list:
    """This thread's open events, outermost first: ``[event, seconds
    of the records that closed directly inside it]``."""
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    return stack


def _live_registry():
    """This thread's registry, or None while ``obs`` is disabled: what
    the thread had open then is forgotten with it (an event left open
    across a ``disable()`` would make every later trace on the thread
    look nested, and fold it)."""
    reg = _registry._current()
    if reg is not _registry._NULL_REGISTRY:
        return reg
    if getattr(_TLS, "stack", None):
        _TLS.stack = []
    return None


def _on_start(event, _value, **_kw):
    # JAX records a scalar (the start time) as each timed region opens.
    if event in _SPANS and _live_registry() is not None:
        _open_spans().append([event, 0.0])
        if event == _BACKEND:
            _TLS.hit = False


def _on_event(event, **_kw):
    if event == _CACHE_HIT and _live_registry() is not None:
        _TLS.hit = True


def _on_duration(event, duration, fun_name="", **_kw):
    global _DROPPED
    phase = _SPANS.get(event)
    reg = None if phase is None else _live_registry()
    if reg is None:
        return
    inside = 0.0
    stack = _open_spans()
    for i in range(len(stack) - 1, -1, -1):
        if stack[i][0] == event:
            inside = stack[i][1]
            # Own entry and, with it, whatever above it never closed.
            del stack[i:]
            break
    if phase == "trace" and (duration < MIN_TRACE_S
                             or (stack and stack[-1][0] == event)):
        if stack:
            stack[-1][1] += inside  # folded into what encloses it
        return
    if stack:
        stack[-1][1] += duration
    t1, p1 = time.monotonic(), time.perf_counter()
    if event == _BACKEND:
        reg.counter("compile.programs").inc()
        if getattr(_TLS, "hit", False):
            phase, _TLS.hit = "cache_load", False
    own = max(duration - inside, 0.0)
    reg.counter(f"compile.{phase}_s").inc(own)
    rec = {"t0": t1 - duration, "t1": t1, "s": own, "phase": phase,
           "fun": str(fun_name),
           "trace_id": _trace.current_trace_id()}
    with _LOCK:
        _RECORDS.append(rec)
        dropped = max(len(_RECORDS) - MAX_RECORDS, 0)
        for _ in range(dropped):
            _RECORDS.popleft()
        _DROPPED += dropped
    if dropped > 0:
        reg.counter("compile.records_dropped").inc(dropped)
    _trace.complete(f"compile.{phase}", "engine",
                    _trace.perf_to_us(p1 - duration), duration * 1e6,
                    args={"fun": rec["fun"]}, track=TRACK)


def install() -> None:
    """Register the listeners with ``jax.monitoring``, once per process
    (a second set would book every event twice). Nothing where JAX
    cannot be imported: ``obs`` works without it."""
    global _INSTALLED
    with _LOCK:
        if _INSTALLED:
            return
        try:
            import jax.monitoring as mon
        except ImportError:
            return
        mon.register_scalar_listener(_on_start)
        mon.register_event_listener(_on_event)
        mon.register_event_duration_secs_listener(_on_duration)
        _INSTALLED = True


def compile_log(until: float | None = None) -> dict:
    """``{"records": [...], "totals": {phase: seconds}, "programs":
    n, "dropped": m}`` over the records that ENDED at or before
    ``until`` (``time.monotonic()`` seconds; None: all). ``totals``
    adds up the records' ``s``; ``programs`` counts back-end events,
    compiled or loaded. The cut is what lets a reader ask for "before
    the serving window opened" after later work has compiled more.
    ``dropped`` is how many records have fallen off the list, the
    OLDEST ones: while it is not 0, ``totals`` and ``programs`` lack
    them whatever ``until`` is (the benchmark's ``setup.*`` readers
    then report nothing rather than a sum that reads low)."""
    with _LOCK:
        records = [dict(r) for r in _RECORDS
                   if until is None or r["t1"] <= until]
        dropped = _DROPPED
    totals = dict.fromkeys(PHASES, 0.0)
    for r in records:
        totals[r["phase"]] += r["s"]
    return {"records": records, "totals": totals,
            "programs": sum(r["phase"] in ("compile", "cache_load")
                            for r in records),
            "dropped": dropped}


def reset() -> None:
    """Forget the records (tests); the listeners stay installed."""
    global _DROPPED
    with _LOCK:
        _RECORDS.clear()
        _DROPPED = 0
    _TLS.stack, _TLS.hit = [], False
