"""Fleet observability plane: per-replica health, cross-replica merge,
placement signals (ISSUE 14).

Every signal the multi-replica tier's router needs — SLO burn rates,
rolling TTFT/TPOT percentiles, breaker states, queue/occupancy gauges —
is already computed *per replica*; until now each was trapped inside
its own process behind ``{"cmd": "metrics"}``. This module is the
host-side control plane over N replicas, shipped BEFORE the router
(ROADMAP item 2) so placement can rest on tested, aggregated,
staleness-aware numbers:

- :func:`replica_health` builds the compact ``ReplicaHealth`` dict the
  server's cheap ``{"cmd": "health"}`` verb returns — lock-free gauge/
  counter peeks, NO SLO force-evaluation, no generation lock;
- :class:`FleetView` scrapes N endpoints concurrently (per-replica
  timeouts), tracks per-replica staleness (``live`` → ``stale`` →
  ``down`` by last-good-snapshot age; a dead or wedged replica
  degrades, never raises, and its last-good health is retained with
  its age reported), and merges full metric snapshots by kind;
- :func:`merge_fleet_snapshots` extends
  ``obs.exposition.merge_snapshots``: counters sum into fleet totals,
  histograms merge bucket-wise (fleet p99 comes from SUMMED buckets
  through the existing ``histogram_quantile`` — never from averaging
  per-replica percentiles), and gauges keep BOTH a fleet rollup
  (additive gauges like queue depth sum; point-in-time ones keep the
  max) and the per-replica values under ``per_replica``;
- :func:`placement_score` is the explicit, unit-tested scoring
  function ISSUE 15's router will consume verbatim: occupancy
  headroom minus queue-depth, burn-rate, breach, and breaker
  penalties (higher = better placement target);
- :func:`render_prometheus_fleet` renders the merged view as
  Prometheus text exposition with a ``replica`` label per series
  (``replica="fleet"`` for the rollup).

Knobs (docs/observability.md "Fleet view"): ``TDT_FLEET_STALE_S`` /
``TDT_FLEET_DOWN_S`` — ages past which a replica's last good snapshot
degrades its status; ``TDT_FLEET_TIMEOUT_S`` — per-replica scrape
timeout; ``TDT_REPLICA_ID`` — the server-side replica identity
(docs/serving.md "Server").
"""

from __future__ import annotations

import os
import re
import threading
import time

from triton_dist_tpu.obs import history as _history
from triton_dist_tpu.obs import registry as _registry
from triton_dist_tpu.obs.exposition import (
    _fmt, _prom_name, histogram_quantile, merge_snapshots)

__all__ = [
    "DEFAULT_DOWN_S", "DEFAULT_STALE_S", "DEFAULT_TIMEOUT_S",
    "FleetView", "PERCENTILE_HISTOGRAMS", "STATUSES",
    "merge_fleet_snapshots", "merged_percentiles", "parse_endpoint",
    "peek_counters", "peek_gauges", "placement_score",
    "render_prometheus_fleet", "replica_health",
]

#: Replica status ladder (docs/observability.md "Fleet view"): a
#: successful scrape younger than the stale age is ``live``; past it
#: (or after a failed scrape) the replica is ``stale`` — its last-good
#: snapshot is retained but must be read with its reported age — and
#: past the down age it is ``down`` (excluded from placement).
STATUSES = ("live", "stale", "down")

DEFAULT_STALE_S = 10.0
DEFAULT_DOWN_S = 30.0
DEFAULT_TIMEOUT_S = 5.0

#: placement_score weights — explicit module constants so the ISSUE 15
#: router's behavior is auditable (and tunable) in one place.
QUEUE_WEIGHT = 0.1      # per queued request
BURN_WEIGHT = 0.25      # per unit of burn rate above sustainable (1.0)
BREACH_PENALTY = 2.0    # per target currently breached
BREAKER_PENALTY = 0.5   # per circuit breaker not fully closed
STALE_PENALTY = 1.0     # stale (but not down) replicas rank below live


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name, "").strip()
    if not v:
        return default
    try:
        return float(v)
    except ValueError:
        raise ValueError(f"{name} must be a number: {v!r}") from None


def stale_s() -> float:
    return _env_float("TDT_FLEET_STALE_S", DEFAULT_STALE_S)


def down_s() -> float:
    return _env_float("TDT_FLEET_DOWN_S", DEFAULT_DOWN_S)


def scrape_timeout_s() -> float:
    return _env_float("TDT_FLEET_TIMEOUT_S", DEFAULT_TIMEOUT_S)


def parse_endpoint(ep) -> tuple:
    """``(host, port)`` from ``"host:port"``, ``(host, port)``, or a
    bare port int (localhost)."""
    if isinstance(ep, (tuple, list)) and len(ep) == 2:
        return str(ep[0]), int(ep[1])
    if isinstance(ep, int):
        return "127.0.0.1", ep
    host, _, port = str(ep).rpartition(":")
    if not host or not port:
        raise ValueError(f"endpoint must be host:port, got {ep!r}")
    return host, int(port)


# ---------------------------------------------------------------------------
# Lock-free registry peeks + the ReplicaHealth builder.
# ---------------------------------------------------------------------------

def peek_gauges(registry=None) -> dict:
    """Every gauge's current value WITHOUT taking the registry lock:
    ``list(dict.items())`` is a single C-level pass under the GIL and
    each ``_value`` read is one attribute load. This is what keeps the
    ``health`` verb cheap — a 1 Hz scrape of N replicas must not
    contend with N pump loops (ISSUE 14 satellite: ``tools/top.py``
    used to force-evaluate SLOs on every render tick)."""
    reg = registry if registry is not None else _registry.get_registry()
    store = getattr(reg, "_gauges", None) or {}
    return {k: m._value for k, m in list(store.items())}


def peek_counters(registry=None) -> dict:
    """Lock-free counter peek (see :func:`peek_gauges`)."""
    reg = registry if registry is not None else _registry.get_registry()
    store = getattr(reg, "_counters", None) or {}
    return {k: m._value for k, m in list(store.items())}


def replica_health(replica_id: str, seq: int, started_monotonic: float,
                   registry=None, engine=None, scheduler=None,
                   clock=time.monotonic, tier: str | None = None) -> dict:
    """The compact ``ReplicaHealth`` dict ``{"cmd": "health"}``
    returns (docs/serving.md "Server"): everything the fleet view and
    the placement score consume, built from lock-free reads of the
    LAST-EVALUATED gauges — the verb never forces an SLO evaluation
    (the pump refreshes them every working iteration; ``seq`` +
    ``uptime_s`` let a scraper judge freshness itself).

    Fields: ``replica_id``, ``seq`` (monotonic per-server snapshot
    number), ``uptime_s``, ``rolling`` (TTFT/TPOT/queue-wait p50/p99 +
    sample counts), ``slo`` (per-target fast/slow burn + breached
    flag), ``queue_depth`` / ``max_waiting``, ``batch_occupancy`` /
    ``batch``, ``kv`` (block utilization/free, paged engines),
    ``breakers`` (open count + not-closed ops), ``spec_accept_rate``
    (speculative engines), ``decode_path``, and the headline serving
    counters."""
    g = peek_gauges(registry)
    c = peek_counters(registry)

    rolling: dict = {}
    for m in ("ttft", "tpot", "queue_wait"):
        for tag in ("p50_ms", "p99_ms", "n"):
            v = g.get(f"serving.rolling.{m}_{tag}")
            if v is not None:
                rolling[f"{m}_{tag}"] = v

    slo: dict = {}
    for k, v in g.items():
        if not k.startswith("serving.slo_burn.") or k.endswith("_slow"):
            continue
        name = k[len("serving.slo_burn."):]
        slo[name] = {
            "burn": v,
            "burn_slow": g.get(f"{k}_slow"),
            "breached": bool(g.get(f"serving.slo_breached.{name}")),
        }

    not_closed = {k[len("resilience."):-len(".breaker_state")]: int(v)
                  for k, v in g.items()
                  if k.startswith("resilience.")
                  and k.endswith(".breaker_state") and v}

    health: dict = {
        "replica_id": replica_id,
        "seq": int(seq),
        "uptime_s": round(max(clock() - started_monotonic, 0.0), 3),
        "rolling": rolling,
        "slo": slo,
        "queue_depth": g.get("serving.queue_depth", 0.0),
        "batch_occupancy": g.get("serving.batch_occupancy", 0.0),
        "breakers": {"open": g.get("resilience.breakers_open", 0.0),
                     "not_closed": not_closed},
        "counters": {k: c[k] for k in ("serving.admitted",
                                       "serving.retired",
                                       "serving.pump_errors",
                                       "serving.slo_breaches",
                                       "server.requests",
                                       "server.errors") if k in c},
    }
    if tier is not None:
        # Disaggregated-fleet role (ISSUE 18): "prefill" / "decode" /
        # "unified" — a tiered router pools replicas by this field, so
        # it rides the cheap health verb like draining does.
        health["tier"] = str(tier)
    if engine is not None:
        kv = getattr(engine, "kv", None)
        health["batch"] = getattr(kv, "batch", None)
        health["decode_path"] = getattr(engine, "decode_path", None)
    if scheduler is not None:
        health["max_waiting"] = getattr(scheduler, "max_waiting", None)
    if g.get("serving.draining"):
        # Graceful drain in progress (ISSUE 15): the replica finishes
        # its in-flight work but admits nothing new — routers must
        # stop placing here (serving/router.py skips draining
        # replicas outright; the flag rides health so remote routers
        # see it without a full metrics scrape).
        health["draining"] = True
    if "kv.block_utilization" in g:
        health["kv"] = {"block_utilization": g["kv.block_utilization"],
                        "blocks_free": g.get("kv.blocks_free")}
    if "serving.spec_accept_rate" in g:
        health["spec_accept_rate"] = g["serving.spec_accept_rate"]
    return health


# ---------------------------------------------------------------------------
# Placement scoring — the function ISSUE 15's router consumes verbatim.
# ---------------------------------------------------------------------------

def placement_score(health: dict | None) -> float:
    """Score one replica as a placement target — HIGHER is better.

    Inputs (all from :func:`replica_health`): occupancy headroom
    (free decode rows / batch; 0 when capacity is unknown), minus
    ``QUEUE_WEIGHT`` per queued request, minus ``BURN_WEIGHT`` per
    unit of fast-window burn rate above the sustainable 1.0, minus
    ``BREACH_PENALTY`` per currently-breached SLO target, minus
    ``BREAKER_PENALTY`` per circuit breaker not fully closed. A
    replica with no health at all scores ``-inf`` (never a target).
    Staleness is the CALLER's dimension — :meth:`FleetView.placement`
    subtracts :data:`STALE_PENALTY` for stale replicas and excludes
    down ones; the score itself prices load and health only."""
    if not health:
        return float("-inf")
    occ = float(health.get("batch_occupancy") or 0.0)
    batch = health.get("batch")
    headroom = ((float(batch) - occ) / float(batch)
                if batch else 0.0)
    queue = float(health.get("queue_depth") or 0.0)
    burn = breached = 0.0
    for t in (health.get("slo") or {}).values():
        burn += max(float(t.get("burn") or 0.0) - 1.0, 0.0)
        breached += 1.0 if t.get("breached") else 0.0
    breakers = float((health.get("breakers") or {}).get("open") or 0.0)
    return (headroom - QUEUE_WEIGHT * queue - BURN_WEIGHT * burn
            - BREACH_PENALTY * breached - BREAKER_PENALTY * breakers)


# ---------------------------------------------------------------------------
# Snapshot merge by metric kind.
# ---------------------------------------------------------------------------

#: Gauges whose fleet rollup is a SUM (they count concurrent things,
#: so the fleet answer is the total across replicas); every other
#: gauge keeps ``merge_snapshots``'s max semantics (point-in-time
#: readings — max answers the capacity questions gauges exist for).
ADDITIVE_GAUGES = (
    "serving.queue_depth", "serving.batch_occupancy", "server.inflight",
    "kv.blocks_free", "kv.blocks_active", "kv.blocks_cached",
)


def merge_fleet_snapshots(by_replica: dict) -> dict:
    """Merge per-replica metric snapshots (``{replica_id: snapshot}``)
    into one fleet view, correctly BY KIND:

    - **counters** sum — fleet totals under the original names;
    - **histograms** merge bucket-wise (``merge_snapshots``), so a
      fleet percentile interpolates the SUMMED bucket counts via
      ``histogram_quantile`` — the only arithmetic that is correct
      (per-replica p99s cannot be averaged into a fleet p99);
    - **gauges** keep a fleet rollup under the original names
      (:data:`ADDITIVE_GAUGES` sum, everything else keeps the max)
      AND the raw per-replica values under ``per_replica`` —
      ``{rid: {"gauges": ..., "counters": ...}}`` — so nothing is
      lost to the rollup.

    The result carries ``replicas`` (sorted ids) and merges cleanly
    into ``tools/report.py``'s fleet section and
    :func:`render_prometheus_fleet`.
    """
    ids = sorted(by_replica)
    merged = merge_snapshots([by_replica[r] for r in ids])
    for name in ADDITIVE_GAUGES:
        vals = [by_replica[r].get("gauges", {}).get(name) for r in ids]
        vals = [v for v in vals if v is not None]
        if vals:
            merged["gauges"][name] = sum(vals)
    merged["replicas"] = ids
    merged["per_replica"] = {
        r: {"gauges": dict(by_replica[r].get("gauges", {})),
            "counters": dict(by_replica[r].get("counters", {}))}
        for r in ids}
    return merged


#: The latency histograms every fleet-percentile surface reads
#: (tools/report.py, tools/fleet_top.py):
#: (snapshot histogram name, display label) pairs.
PERCENTILE_HISTOGRAMS = (("serving.ttft_ms", "ttft"),
                         ("serving.tpot_ms", "tpot"))


def merged_percentiles(histograms: dict | None,
                       names=PERCENTILE_HISTOGRAMS) -> dict:
    """``{label: {"p50": v, "p99": v, "n": count}}`` for each named
    bucket-merged histogram present and non-empty in ``histograms``
    (a merged snapshot's ``histograms`` dict, or any dict of
    registry-shaped histogram dicts) — the ONE home for the fleet
    percentile arithmetic the report/dashboard/bench surfaces share,
    always interpolating the summed buckets via
    ``histogram_quantile``."""
    out: dict = {}
    for name, label in names:
        h = (histograms or {}).get(name)
        if not h or not h.get("count"):
            continue
        out[label] = {"p50": histogram_quantile(h, 0.50),
                      "p99": histogram_quantile(h, 0.99),
                      "n": h["count"]}
    return out


_LABEL_SAFE = re.compile(r"[^A-Za-z0-9_.:\-]")


def _label(replica: str) -> str:
    return _LABEL_SAFE.sub("_", str(replica))


def render_prometheus_fleet(by_replica: dict, prefix: str = "tdt") -> str:
    """Prometheus text exposition of the fleet: every counter/gauge
    series is emitted once per replica with a ``replica="<id>"`` label
    plus the fleet rollup as ``replica="fleet"`` (samples of one
    metric grouped under one ``# TYPE`` line, per the format spec);
    histograms are emitted fleet-rollup-only (bucket-merged — the
    per-replica bucket explosion belongs in a real TSDB, not a text
    page). Same name sanitization/prefixing as
    ``obs.render_prometheus``."""
    merged = merge_fleet_snapshots(by_replica)
    per = merged["per_replica"]
    ids = merged["replicas"]
    lines: list = []

    def emit(kind, pn, fleet_v, per_kind, name):
        lines.append(f"# TYPE {pn} {kind}")
        lines.append(f'{pn}{{replica="fleet"}} {_fmt(fleet_v)}')
        for rid in ids:
            v = per[rid][per_kind].get(name)
            if v is not None:
                lines.append(
                    f'{pn}{{replica="{_label(rid)}"}} {_fmt(v)}')

    for name in sorted(merged["counters"]):
        emit("counter", _prom_name(name, prefix) + "_total",
             merged["counters"][name], "counters", name)
    for name in sorted(merged["gauges"]):
        emit("gauge", _prom_name(name, prefix), merged["gauges"][name],
             "gauges", name)
    for name in sorted(merged["histograms"]):
        h = merged["histograms"][name]
        pn = _prom_name(name, prefix)
        lines.append(f"# TYPE {pn} histogram")
        cum = 0
        for ub, cnt in zip(h["buckets"], h["counts"]):
            cum += cnt
            lines.append(
                f'{pn}_bucket{{replica="fleet",le="{_fmt(ub)}"}} {cum}')
        cum += h["counts"][len(h["buckets"])]
        lines.append(f'{pn}_bucket{{replica="fleet",le="+Inf"}} {cum}')
        lines.append(f'{pn}_sum{{replica="fleet"}} {_fmt(h["sum"])}')
        lines.append(f'{pn}_count{{replica="fleet"}} {h["count"]}')
    return "\n".join(lines) + ("\n" if lines else "")


# ---------------------------------------------------------------------------
# FleetView: concurrent scrapes + staleness tracking.
# ---------------------------------------------------------------------------

class _Rec:
    """Mutable per-replica scrape record (internal)."""

    __slots__ = ("endpoint", "replica_id", "health", "snapshot", "seq",
                 "t_ok", "t_created", "last_ok", "error", "hist",
                 "rhist")

    def __init__(self, endpoint, t_created):
        self.endpoint = endpoint
        self.replica_id = f"{endpoint[0]}:{endpoint[1]}"
        self.health = None          # last GOOD health, retained
        self.snapshot = None        # last GOOD metrics snapshot
        self.seq = None
        self.t_ok = None            # clock() of the last good scrape
        self.t_created = t_created
        self.last_ok = False        # did the latest attempt succeed?
        self.error = None
        self.hist = None            # SeriesStore fed from health polls
        self.rhist = None           # last remote {"cmd": "history"} reply


class FleetView:
    """Aggregator over N replica endpoints.

    :meth:`poll` runs one CONCURRENT ``{"cmd": "health"}`` scrape
    (per-replica timeout via the client ``fanout`` machinery — one
    wedged replica cannot stall the others) and returns the per-replica
    rows; :meth:`scrape_metrics` does the same with full
    ``{"cmd": "metrics"}`` snapshots and returns the fleet merge
    (:func:`merge_fleet_snapshots`). Scrape failures NEVER raise: the
    replica's last-good data is retained and its status degrades by
    the age of that data — ``live`` while younger than ``stale_s``
    (and the latest attempt succeeded), ``stale`` until ``down_s``,
    ``down`` past it; a later good scrape recovers it to ``live``.
    ``clock`` is injectable so the transitions are testable without
    sleeping (tests/test_fleet.py)."""

    def __init__(self, endpoints, timeout_s: float | None = None,
                 stale_s_: float | None = None,
                 down_s_: float | None = None, clock=time.monotonic,
                 scrape=None):
        if not endpoints:
            raise ValueError("FleetView needs at least one endpoint")
        self.endpoints = [parse_endpoint(e) for e in endpoints]
        if len(set(self.endpoints)) != len(self.endpoints):
            raise ValueError(
                f"duplicate endpoints: {self.endpoints}")
        self.timeout_s = (timeout_s if timeout_s is not None
                          else scrape_timeout_s())
        self.stale_s = stale_s_ if stale_s_ is not None else stale_s()
        self.down_s = down_s_ if down_s_ is not None else down_s()
        if not 0 < self.stale_s <= self.down_s:
            raise ValueError(
                f"need 0 < stale_s <= down_s, got "
                f"{self.stale_s}/{self.down_s}")
        self._clock = clock
        self._scrape = scrape       # injectable (tests): (eps, req) -> list
        now = clock()
        self._eps_lock = threading.Lock()
        self._recs = {ep: _Rec(ep, now) for ep in self.endpoints}
        self._merged = None
        # Health history (ISSUE 16): every poll() appends the headline
        # health numbers into bounded per-replica ring buffers plus a
        # fleet-level rollup store — no extra scrapes, the poll the
        # dashboard already runs IS the sampler. TDT_HISTORY_LEN bounds
        # every buffer.
        self._hist_len = _history.history_len()
        self._fleet_hist = _history.SeriesStore(maxlen=self._hist_len)

    # -- dynamic membership (ISSUE 15: live replica add/remove) ------------
    def add_endpoint(self, ep) -> tuple:
        """Start tracking a replica (it joins the next poll; its
        status starts ``stale`` until a good scrape). Returns the
        parsed ``(host, port)``; duplicate endpoints are a
        ``ValueError`` like at construction."""
        ep = parse_endpoint(ep)
        with self._eps_lock:
            if ep in self._recs:
                raise ValueError(f"endpoint already tracked: {ep}")
            self._recs[ep] = _Rec(ep, self._clock())
            self.endpoints.append(ep)
        return ep

    def remove_endpoint(self, ep) -> tuple:
        """Stop tracking a replica (its record — and its contribution
        to any future merge — is dropped; a concurrent poll that
        already snapshotted the endpoint list finishes harmlessly
        against the dropped record)."""
        ep = parse_endpoint(ep)
        with self._eps_lock:
            if ep not in self._recs:
                raise ValueError(f"endpoint not tracked: {ep}")
            self._recs.pop(ep)
            self.endpoints.remove(ep)
        return ep

    def _snapshot_eps(self) -> list:
        with self._eps_lock:
            return list(self.endpoints)

    # -- scraping ----------------------------------------------------------
    def _scrape_all(self, eps, req: dict) -> list:
        """One request to every endpoint concurrently; per-slot
        ``{"error", "type"}`` dicts on failure (client fanout
        contract)."""
        if self._scrape is not None:
            return self._scrape(eps, req)
        from triton_dist_tpu.serving.client import fanout
        # retry_next=False pins slot i to endpoint i: a probe of
        # replica A answered by replica B (the generation-path retry)
        # would corrupt A's staleness record.
        return fanout(requests=[dict(req) for _ in eps],
                      timeout=self.timeout_s, endpoints=eps,
                      retry_next=False)

    def _record(self, rec: _Rec, resp, key: str) -> None:
        now = self._clock()
        ok = isinstance(resp, dict) and key in resp
        rec.last_ok = ok
        if not ok:
            rec.error = ((resp or {}).get("error")
                         if isinstance(resp, dict) else str(resp))
            _registry.counter("fleet.scrape_errors").inc()
            return
        rec.error = None
        rec.t_ok = now
        _registry.counter("fleet.scrapes").inc()
        if key == "health":
            rec.health = resp["health"]
            rec.seq = rec.health.get("seq")
            rid = rec.health.get("replica_id")
        else:
            rec.snapshot = resp["metrics"]
            rid = rec.snapshot.get("replica_id")
        if rid:
            rec.replica_id = str(rid)

    def _status(self, rec: _Rec, now: float) -> tuple:
        """(status, age_s) from the last-good-scrape age."""
        anchor = rec.t_ok if rec.t_ok is not None else rec.t_created
        age = max(now - anchor, 0.0)
        if rec.t_ok is None:
            # Never successfully scraped: no data to be "live" on.
            return ("down" if age > self.down_s else "stale"), age
        if rec.last_ok and age <= self.stale_s:
            return "live", age
        if age <= self.down_s:
            return "stale", age
        return "down", age

    def _publish(self, rows: list) -> None:
        counts = {st: 0 for st in STATUSES}
        for r in rows:
            counts[r["status"]] += 1
        _registry.gauge("fleet.replicas").set(len(rows))
        _registry.gauge("fleet.replicas_live").set(counts["live"])
        _registry.gauge("fleet.replicas_stale").set(counts["stale"])
        _registry.gauge("fleet.replicas_down").set(counts["down"])

    def poll(self) -> list:
        """One concurrent health scrape; returns :meth:`replicas`.
        Each poll also appends the headline health numbers into the
        bounded per-replica / fleet history stores (:meth:`history`) —
        the scrape the dashboard already runs IS the history sampler,
        no extra requests (ISSUE 16)."""
        t0 = time.perf_counter()
        eps = self._snapshot_eps()
        outs = self._scrape_all(eps, {"cmd": "health"})
        _registry.histogram("fleet.scrape_ms").observe(
            (time.perf_counter() - t0) * 1e3)
        for ep, resp in zip(eps, outs):
            rec = self._recs.get(ep)   # may have been removed mid-poll
            if rec is not None:
                self._record(rec, resp, "health")
        rows = self.replicas()
        self._append_history(rows)
        self._publish(rows)
        return rows

    def _append_history(self, rows: list) -> None:
        """One history tick from the poll that just completed: per
        LIVE-answering replica the headline health numbers (queue
        depth, batch occupancy, rolling TTFT p99, per-target fast
        burn), and one fleet-level rollup (additive sums over every
        replica not ``down``, plus how many replicas reported).
        Staleness-aware by construction: a replica that failed this
        poll gets NO new point — its series simply stops advancing, so
        a sparkline gap is a staleness signal, not a zero."""
        now = self._clock()
        reporting = 0
        fleet_q = fleet_occ = 0.0
        by_ep = {r["endpoint"]: r for r in rows}
        for ep in self._snapshot_eps():
            rec = self._recs.get(ep)
            row = by_ep.get(f"{ep[0]}:{ep[1]}")
            if rec is None or row is None or rec.health is None:
                continue
            h = rec.health
            if row["status"] != "down":
                reporting += 1
                fleet_q += float(h.get("queue_depth") or 0.0)
                fleet_occ += float(h.get("batch_occupancy") or 0.0)
            if not rec.last_ok:
                continue
            if rec.hist is None:
                rec.hist = _history.SeriesStore(maxlen=self._hist_len)
            rec.hist.record("queue_depth",
                            now, float(h.get("queue_depth") or 0.0))
            rec.hist.record("batch_occupancy",
                            now, float(h.get("batch_occupancy") or 0.0))
            p99 = (h.get("rolling") or {}).get("ttft_p99_ms")
            if p99 is not None:
                rec.hist.record("ttft_p99_ms", now, float(p99))
            for name, t in (h.get("slo") or {}).items():
                burn = t.get("burn")
                if burn is not None:
                    rec.hist.record(f"slo_burn.{name}",
                                    now, float(burn))
        self._fleet_hist.record("queue_depth", now, fleet_q)
        self._fleet_hist.record("batch_occupancy", now, fleet_occ)
        self._fleet_hist.record("replicas_reporting",
                                now, float(reporting))

    def scrape_metrics(self, evaluate: bool = False) -> dict | None:
        """Concurrent full-snapshot scrape → the fleet merge (also
        liveness evidence — a good metrics scrape refreshes the same
        staleness clock as a health scrape). ``evaluate=True`` asks
        each replica to force a fresh SLO evaluation first (the bench
        does, a 1 Hz dashboard should not). Returns None when no
        replica answered; replicas that failed merge with their LAST
        GOOD snapshot only if still ``stale`` or better — a ``down``
        replica's numbers leave the merge."""
        t0 = time.perf_counter()
        eps = self._snapshot_eps()
        outs = self._scrape_all(eps, {"cmd": "metrics",
                                      "evaluate": bool(evaluate)})
        _registry.histogram("fleet.scrape_ms").observe(
            (time.perf_counter() - t0) * 1e3)
        now = self._clock()
        by_replica: dict = {}
        for ep, resp in zip(eps, outs):
            rec = self._recs.get(ep)   # may have been removed mid-poll
            if rec is None:
                continue
            self._record(rec, resp, "metrics")
            status, _ = self._status(rec, now)
            if rec.snapshot is not None and status != "down":
                rid = rec.replica_id
                if rid in by_replica:
                    # Two replicas claiming one id must not silently
                    # collapse in the merge (their counters would
                    # alias) — disambiguate by endpoint.
                    rid = f"{rid}@{ep[0]}:{ep[1]}"
                by_replica[rid] = rec.snapshot
        self._publish(self.replicas())
        if not by_replica:
            self._merged = None
            return None
        self._merged = merge_fleet_snapshots(by_replica)
        return self._merged

    # -- reads -------------------------------------------------------------
    def merged(self) -> dict | None:
        """The last :meth:`scrape_metrics` merge (None before one)."""
        return self._merged

    def replicas(self) -> list:
        """Per-replica rows, endpoint order: ``{"endpoint",
        "replica_id", "status", "age_s", "seq", "health", "error",
        "score"}``. ``health`` is the LAST GOOD snapshot whatever the
        status — with ``age_s`` saying exactly how old it is, a stale
        value is never presented as current."""
        now = self._clock()
        rows = []
        for ep in self._snapshot_eps():
            rec = self._recs.get(ep)
            if rec is None:
                continue
            status, age = self._status(rec, now)
            rows.append({
                "endpoint": f"{ep[0]}:{ep[1]}",
                "replica_id": rec.replica_id,
                "status": status,
                "age_s": round(age, 3),
                "seq": rec.seq,
                "health": rec.health,
                "error": rec.error,
                "score": (None if status == "down"
                          else round(placement_score(rec.health)
                                     - (STALE_PENALTY
                                        if status == "stale" else 0.0),
                                     4)),
            })
        return rows

    def placement(self) -> list:
        """``[(replica_id, score), ...]`` best-first over the replicas
        a router may target: ``down`` replicas are excluded, ``stale``
        ones carry :data:`STALE_PENALTY` (already folded into the row
        score). This ranking is exactly what ISSUE 15's router will
        consume."""
        ranked = [(r["replica_id"], r["score"])
                  for r in self.replicas() if r["score"] is not None]
        ranked.sort(key=lambda t: -t[1])
        return ranked

    def fleet_quantile(self, hist_name: str, q: float):
        """Fleet percentile of a merged histogram — interpolated from
        the SUMMED buckets (None before a metrics scrape or when the
        histogram is absent/empty)."""
        if self._merged is None:
            return None
        h = self._merged.get("histograms", {}).get(hist_name)
        return histogram_quantile(h, q) if h else None

    # -- health history (ISSUE 16) -----------------------------------------
    def history(self, last_s: float | None = None,
                max_points: int | None = None) -> dict:
        """The poll-fed health history: ``{"fleet": <snapshot>,
        "replicas": {replica_id: <snapshot>}}`` where each snapshot is
        ``obs.history.SeriesStore.snapshot`` shaped (per-replica
        ``queue_depth`` / ``batch_occupancy`` / ``ttft_p99_ms`` /
        ``slo_burn.<name>``; fleet-level additive sums over non-down
        replicas plus ``replicas_reporting``). Timestamps are this
        view's ``clock`` — comparable within one view, not across
        processes. Empty until the first :meth:`poll`."""
        out = {"fleet": self._fleet_hist.snapshot(
                   last_s=last_s, max_points=max_points),
               "replicas": {}}
        for ep in self._snapshot_eps():
            rec = self._recs.get(ep)
            if rec is not None and rec.hist is not None:
                out["replicas"][rec.replica_id] = rec.hist.snapshot(
                    last_s=last_s, max_points=max_points)
        return out

    def scrape_history(self, last_s: float | None = None,
                       max_points: int | None = 64) -> dict:
        """One concurrent ``{"cmd": "history"}`` scrape: each
        replica's OWN sampled series (its in-process
        ``HistorySampler``, far richer than the poll-fed health
        history) is fetched and cached per replica, then returned as
        :meth:`remote_history`. Replicas without a sampler answer
        ``{"history": None}`` and simply stay absent. Deliberately
        does NOT touch the staleness clock — history is a bulk read,
        not liveness evidence (``poll`` owns that)."""
        eps = self._snapshot_eps()
        req: dict = {"cmd": "history"}
        if last_s is not None:
            req["last_s"] = last_s
        if max_points is not None:
            req["max_points"] = max_points
        outs = self._scrape_all(eps, req)
        for ep, resp in zip(eps, outs):
            rec = self._recs.get(ep)
            if rec is None:
                continue
            if isinstance(resp, dict) and "history" in resp:
                _registry.counter("fleet.history_scrapes").inc()
                rec.rhist = resp["history"]
        return self.remote_history()

    def remote_history(self) -> dict:
        """``{replica_id: <history snapshot>}`` from the last
        :meth:`scrape_history` — cached, zero requests (the dashboard
        reads this between its sparse scrape ticks)."""
        out: dict = {}
        for ep in self._snapshot_eps():
            rec = self._recs.get(ep)
            if rec is not None and rec.rhist is not None:
                out[rec.replica_id] = rec.rhist
        return out
