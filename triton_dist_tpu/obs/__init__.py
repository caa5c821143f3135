"""Telemetry subsystem: metrics, spans, event tracing, exposition.

Process-local counters / gauges / fixed-bucket histograms
(``obs.registry``), wall-clock spans that land in a histogram, the
xprof trace, AND the structured event timeline (``obs.span``),
per-host snapshot merge mirroring the reference's rank-0
``gather_object`` trace merge, and a Prometheus text exposition path
served over the ModelServer protocol (``obs.exposition``).

The timeline side (``obs.trace``) records begin/end + instant events
into per-thread ring buffers, exports Chrome trace-event / Perfetto
JSON through ``tools/trace_export.py``, and doubles as a flight
recorder (``obs.flight``): the most recent event window dumps to disk
on watchdog trips, breaker opens, serve-loop failures, SIGTERM, or an
explicit ``{"cmd": "dump_trace"}``.

The serving SLO observatory (ISSUE 8) sits on top: ``obs.slo`` keeps
rolling-window percentiles + multi-window burn rates that arm the
flight recorder on a latency-SLO breach, and ``obs.attrib`` keeps
per-request latency waterfalls (queue → prefill → decode) the server
returns inline and ``tools/top.py`` renders live.

The fleet plane (ISSUE 14, ``obs.fleet``) lifts all of it across N
replicas: per-replica ``ReplicaHealth`` snapshots behind the server's
cheap ``{"cmd": "health"}`` verb, a ``FleetView`` aggregator that
scrapes endpoints concurrently, tracks staleness (live → stale →
down), and merges snapshots correctly by metric kind, plus the
``placement_score`` the multi-replica router will consume
(docs/observability.md "Fleet view"). Several replicas in one process
keep distinct metrics via ``obs.scoped_registry``.

The history plane (ISSUE 16, ``obs.history``) retains what everything
above only reads point-in-time: an opt-in sampler (``TDT_HISTORY=1``)
records every gauge (value) and counter (rate) into ring-buffered
series behind the server's ``{"cmd": "history"}`` verb, pure trend
math (``slope`` / ``ema`` / ``eta_to``) forecasts crossings, and
early-warning detectors arm the flight recorder BEFORE the SLO breach
— with the trailing series embedded in every dump as Perfetto counter
tracks (docs/observability.md "History plane").

Disabled by default at zero hot-path cost; flip metrics on with
``obs.enable()`` (the ModelServer does this at construction;
``TDT_TRACE=1`` makes that enable tracing too).

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
    attrib, devprof, fleet, flight, history, slo, trace)
from triton_dist_tpu.obs.slo import (  # noqa: F401
    SLOTarget,
    SLOTracker,
    WindowedHistogram,
)
from triton_dist_tpu.obs.trace import (  # noqa: F401
    enabled as trace_enabled,
)
