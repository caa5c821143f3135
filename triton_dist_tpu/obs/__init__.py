"""Telemetry subsystem: what each module records, and who reads it.

- ``registry``: process-local counters, gauges, fixed-bucket histograms
  and ``obs.span`` (a histogram, an xprof ``TraceAnnotation`` and a
  timeline begin/end in one). Read by ``{"cmd": "metrics"}``, the
  benchmark's counter and span readers (``benchmark/layer_metrics/``),
  ``tools/top.py`` and ``tools/report.py``; ``scoped_registry`` keeps
  several replicas in one process apart.
- ``exposition``: Prometheus text of a snapshot and the merge of
  per-host snapshots. Read by the server's ``metrics`` verb and
  ``tools/trace_export.py``.
- ``trace``: the event timeline, per-thread rings plus named tracks,
  with the request's trace id on every event. Read by ``obs.flight``
  and ``tools/trace_export.py`` (Chrome / Perfetto JSON).
- ``flight``: dumps the timeline's trailing window on a watchdog trip,
  an open breaker, a serve-loop failure, SIGTERM or
  ``{"cmd": "dump_trace"}``. Read by an operator, in Perfetto.
- ``compile``: one record per JAX trace, lowering, back-end compile or
  persistent-cache load, by function (``compile_log``), the
  ``compile.*`` counters and the ``compile`` track of the timeline.
  Read by the benchmark's ``setup.*`` metrics, the ``metrics`` verb and
  every flight dump.
- ``attrib``: per-request waterfalls (queue wait, prefill, decode).
  Read by the reply's ``timing`` block, ``{"cmd": "request_stats"}``
  and ``tools/top.py``.
- ``slo``: rolling-window percentiles and burn rates that arm the
  flight recorder on a latency breach. Read by the scheduler's pump
  and the ``health`` verb.
- ``fleet``: per-replica health snapshots and their merge across
  endpoints, with staleness. Read by ``serving/router.py``,
  ``{"cmd": "health"}`` and ``tools/fleet_top.py``.
- ``history``: an opt-in sampler (``TDT_HISTORY=1``) of every gauge and
  counter rate into ring-buffered series, with trend detectors. Read
  by ``{"cmd": "history"}``, ``tools/top.py`` and flight dumps.
- ``devprof``: bounded ``jax.profiler`` captures from the pump and
  their reduction to per-op device time. Read by ``tools/report.py``
  and ``tools/profile_export.py``.

Disabled by default at zero hot-path cost; ``obs.enable()`` switches
the registry and the compile log on (the ModelServer does this at
construction; ``TDT_TRACE=1`` makes that enable tracing too).

See docs/observability.md for the metric name catalog and event
schema.
"""

from triton_dist_tpu.obs.registry import (  # noqa: F401
    DEFAULT_MS_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    NullRegistry,
    Registry,
    counter,
    disable,
    enable,
    enabled,
    env_int,
    gauge,
    get_registry,
    histogram,
    record_comm,
    reset,
    scoped_registry,
    set_registry,
    snapshot,
    span,
)
from triton_dist_tpu.obs.exposition import (  # noqa: F401
    aggregate_across_hosts,
    histogram_quantile,
    merge_snapshots,
    render_prometheus,
)
from triton_dist_tpu.obs import (  # noqa: F401
    attrib, compile, devprof, fleet, flight, history, slo, trace)
from triton_dist_tpu.obs.compile import compile_log  # noqa: F401
from triton_dist_tpu.obs.slo import (  # noqa: F401
    SLOTarget,
    SLOTracker,
    WindowedHistogram,
)
from triton_dist_tpu.obs.trace import (  # noqa: F401
    enabled as trace_enabled,
)
