"""Render sweep rows and metrics snapshots into markdown tables.

The command line takes ``tools/bench_ops.py`` JSONL (``--sweep``);
:func:`render_telemetry` renders an obs snapshot (the server's
``{"cmd": "metrics"}`` payload) section by section.

Usage:
    python -m triton_dist_tpu.tools.report --sweep sweep.jsonl
"""

from __future__ import annotations

import argparse
import json
import sys


_BREAKER_STATES = {0: "closed", 1: "OPEN", 2: "half-open"}


def render_resilience(snap: dict) -> str:
    """Summarize the ``resilience.*`` metrics (docs/resilience.md):
    breaker states decoded to words, fallback totals by op and reason,
    watchdog trips, known-bad cache size. Empty string when the
    snapshot carries no resilience metrics."""
    counters = {k: v for k, v in snap.get("counters", {}).items()
                if k.startswith("resilience.")}
    gauges = {k: v for k, v in snap.get("gauges", {}).items()
              if k.startswith("resilience.")}
    if not counters and not gauges:
        return ""
    lines = ["#### resilience", "| metric | value |", "|---|---|"]
    for k in sorted(gauges):
        v = gauges[k]
        if k.endswith(".breaker_state"):
            v = _BREAKER_STATES.get(int(v), v)
        else:
            v = int(v) if float(v) == int(v) else round(float(v), 4)
        lines.append(f"| {k} | {v} |")
    for k in sorted(counters):
        v = counters[k]
        lines.append(f"| {k} | {int(v) if float(v) == int(v) else v} |")
    return "\n".join(lines)


def render_serving(snap: dict) -> str:
    """Summarize the continuous-batching scheduler's ``serving.*``
    metrics (docs/serving.md "Scheduler"): queue depth / batch
    occupancy gauges, admitted / retired / backpressure counters, and
    TTFT + queue-wait percentiles interpolated from the snapshot
    histograms. Empty string when the snapshot carries no serving
    metrics (a scheduler-less process)."""
    counters = {k: v for k, v in snap.get("counters", {}).items()
                if k.startswith("serving.")}
    gauges = {k: v for k, v in snap.get("gauges", {}).items()
              if k.startswith("serving.")}
    hists = {k: h for k, h in snap.get("histograms", {}).items()
             if k.startswith("serving.")}
    if not counters and not gauges and not hists:
        return ""
    from triton_dist_tpu.obs import histogram_quantile
    lines = ["#### serving", "| metric | value |", "|---|---|"]
    for k in sorted(gauges):
        v = gauges[k]
        lines.append(f"| {k} | "
                     f"{int(v) if float(v) == int(v) else round(v, 4)} |")
    for k in sorted(counters):
        v = counters[k]
        lines.append(f"| {k} | "
                     f"{int(v) if float(v) == int(v) else v} |")
    for k in sorted(hists):
        h = hists[k]
        p50 = histogram_quantile(h, 0.50)
        p99 = histogram_quantile(h, 0.99)
        lines.append(
            f"| {k} | n={h.get('count', 0)} "
            f"p50={round(p50, 3) if p50 is not None else '-'} "
            f"p99={round(p99, 3) if p99 is not None else '-'} "
            f"max={h.get('max')} |")
    return "\n".join(lines)


def render_kv(snap: dict) -> str:
    """Summarize the paged block pool + prefix cache (``kv.*`` metrics,
    docs/observability.md "KV block pool"): occupancy gauges
    (free / cached / active / utilization) and the eviction counter.
    The prefix-cache hit metrics live under ``serving.*`` and render in
    that section. Empty string for processes without a paged pool."""
    counters = {k: v for k, v in snap.get("counters", {}).items()
                if k.startswith("kv.")}
    gauges = {k: v for k, v in snap.get("gauges", {}).items()
              if k.startswith("kv.")}
    if not counters and not gauges:
        return ""
    lines = ["#### kv block pool", "| metric | value |", "|---|---|"]
    for k in sorted(gauges):
        v = gauges[k]
        lines.append(f"| {k} | "
                     f"{int(v) if float(v) == int(v) else round(v, 4)} |")
    for k in sorted(counters):
        v = counters[k]
        lines.append(f"| {k} | "
                     f"{int(v) if float(v) == int(v) else v} |")
    return "\n".join(lines)


def render_disagg(snap: dict) -> str:
    """Summarize the disaggregated prefill/decode plane (``disagg.*``
    metrics, docs/serving.md "Disaggregated prefill/decode"): stream
    counters — handoffs, blocks shipped/deduped by transport tier,
    fallbacks, severed streams — plus handoff-latency percentiles
    interpolated from the ``disagg.handoff_ms`` histogram and the
    end-to-end dedup ratio. Empty string when the snapshot carries no
    disagg metrics (a unified fleet)."""
    counters = {k: v for k, v in snap.get("counters", {}).items()
                if k.startswith("disagg.")}
    hists = {k: h for k, h in snap.get("histograms", {}).items()
             if k.startswith("disagg.")}
    if not counters and not hists:
        return ""
    from triton_dist_tpu.obs import histogram_quantile
    lines = ["#### disagg", "| metric | value |", "|---|---|"]
    for k in sorted(counters):
        v = counters[k]
        lines.append(f"| {k} | "
                     f"{int(v) if float(v) == int(v) else v} |")
    for k in sorted(hists):
        h = hists[k]
        p50 = histogram_quantile(h, 0.50)
        p99 = histogram_quantile(h, 0.99)
        lines.append(
            f"| {k} | n={h.get('count', 0)} "
            f"p50={round(p50, 3) if p50 is not None else '-'} "
            f"p99={round(p99, 3) if p99 is not None else '-'} "
            f"max={h.get('max')} |")
    offered = counters.get("disagg.blocks_offered")
    if offered:
        lines.append(
            f"| dedup ratio | "
            f"{round(counters.get('disagg.blocks_deduped', 0) / offered, 4)} |")
    return "\n".join(lines)


def render_fleet(merged: dict | None) -> str:
    """Summarize a fleet-merged snapshot (``obs.fleet.
    merge_fleet_snapshots``, under the snapshot's ``fleet`` key;
    docs/observability.md "Fleet view"): the replica roster,
    per-replica queue/occupancy/rolling-p99 rows, and the fleet rollup
    with BUCKET-MERGED TTFT/TPOT percentiles. Empty string when no
    merged snapshot is present."""
    if not merged or not merged.get("replicas"):
        return ""
    per = merged.get("per_replica", {})
    lines = ["#### fleet",
             f"replicas: {', '.join(merged['replicas'])}", "",
             "| replica | queue | occupancy | rolling ttft p99 | "
             "rolling tpot p99 | admitted | retired |",
             "|---|---|---|---|---|---|---|"]
    for rid in merged["replicas"]:
        g = per.get(rid, {}).get("gauges", {})
        c = per.get(rid, {}).get("counters", {})

        def _v(x):
            return "-" if x is None else (
                int(x) if float(x) == int(x) else round(float(x), 3))

        lines.append(
            f"| {rid} | {_v(g.get('serving.queue_depth'))} | "
            f"{_v(g.get('serving.batch_occupancy'))} | "
            f"{_v(g.get('serving.rolling.ttft_p99_ms'))} | "
            f"{_v(g.get('serving.rolling.tpot_p99_ms'))} | "
            f"{_v(c.get('serving.admitted'))} | "
            f"{_v(c.get('serving.retired'))} |")
    from triton_dist_tpu.obs.fleet import merged_percentiles
    fleet_bits = []
    for label, p in merged_percentiles(merged.get("histograms")).items():
        p50, p99 = p["p50"], p["p99"]
        fleet_bits.append(
            f"{label} p50={round(p50, 3) if p50 is not None else '-'}"
            f" p99={round(p99, 3) if p99 is not None else '-'}"
            f" (n={p['n']}, bucket-merged)")
    c = merged.get("counters", {})
    if "serving.retired" in c:
        fleet_bits.append(f"retired={int(c['serving.retired'])}")
    if fleet_bits:
        lines += ["", "fleet rollup: " + "  ".join(fleet_bits)]
    return "\n".join(lines)


def render_router(status: dict | None) -> str:
    """Summarize a router-status payload (``RouterServer.status()`` —
    a router's ``{"cmd": "metrics"}`` snapshot carries it under
    ``router``): per-replica placement rows
    with the router's breaker / in-flight / draining dimension, plus
    the failover and shed counters a failover postmortem reads first
    (docs/serving.md "Router"). Empty string when absent."""
    if not status or not status.get("replicas"):
        return ""
    placements = status.get("placements") or {}
    lines = ["#### router",
             "| replica | status | breaker | inflight | draining | "
             "score | placed |", "|---|---|---|---|---|---|---|"]
    for r in status["replicas"]:
        rid = r.get("replica_id") or r.get("endpoint") or "?"
        placed = (placements.get(r.get("endpoint"))
                  or placements.get(rid) or 0)
        lines.append(
            f"| {rid} | {r.get('status')} | {r.get('breaker')} | "
            f"{r.get('inflight')} | "
            f"{'yes' if r.get('draining') else '-'} | "
            f"{r.get('score')} | {int(placed)} |")
    # EVERY router counter renders here: render_telemetry suppresses
    # router.* from the generic table when this section exists, so a
    # counter skipped here (retries_exhausted, poll_errors, ...)
    # would be invisible in the postmortem — the opposite of what the
    # section is for (review finding).
    c = status.get("counters") or {}
    bits = [f"{k.split('.', 1)[1]}={int(c[k])}" for k in sorted(c)]
    if bits:
        lines += ["", "router counters: " + "  ".join(bits)]
    hop = status.get("failover_sample")
    if hop:
        # One stitched failover: this trace ID spans the dead
        # replica's admit, the router's failover instant, and the
        # answering replica's retire in the flight record.
        lines += ["", f"failover sample: trace_id={hop.get('trace_id')}"
                      f"  failovers={hop.get('failovers')}"
                      f"  answered_by={hop.get('replica')}"]
    return "\n".join(lines)


def render_tracing(stats: dict | None) -> str:
    """Summarize the event-tracing / flight-recorder state
    (``obs.trace.stats()``, carried under the snapshot's ``trace`` key
    by the server metrics command — docs/observability.md
    "Tracing"): events captured, events dropped
    to ring overwrites, and the last flight-record path so a
    postmortem reader knows which file to open in Perfetto. Empty
    string when the payload carries no tracing stats."""
    if not stats:
        return ""
    lines = ["#### tracing", "| metric | value |", "|---|---|"]
    for k in ("events_total", "dropped_total", "tracks",
              "ring_capacity", "ring_high_water", "flight_dumps"):
        if k in stats:
            lines.append(f"| {k} | {stats[k]} |")
    if stats.get("last_flight_record"):
        lines.append(
            f"| last_flight_record | {stats['last_flight_record']} |")
    if stats.get("dropped_total"):
        # An undersized TDT_TRACE_RING silently truncates every flight
        # record's window; surface it where the numbers are read
        # instead of only inside a dump.
        lines.append(
            f"\n⚠ {int(stats['dropped_total'])} trace events were "
            f"overwritten before export — the flight-recorder window "
            f"is truncated; raise TDT_TRACE_RING "
            f"(capacity {stats.get('ring_capacity', '?')}, high water "
            f"{stats.get('ring_high_water', '?')}).")
    return "\n".join(lines)


def render_history(hist: dict | None) -> str:
    """Summarize a sampled-history snapshot (``obs.history``, under
    the snapshot's ``history`` key; docs/observability.md "History
    plane"): per-series stats with a unicode sparkline, plus every
    retained early-warning excerpt. Empty string when no series were
    sampled."""
    if not hist or not hist.get("series"):
        return ""
    from triton_dist_tpu.obs.history import sparkline, window_stats
    lines = ["#### history",
             "| series | n | last | min | max | avg | trend |",
             "|---|---|---|---|---|---|---|"]

    def _v(x):
        return "-" if x is None else (
            int(x) if float(x) == int(x) else round(float(x), 4))

    for name in sorted(hist["series"]):
        s = hist["series"][name] or {}
        pts = s.get("points") or []
        st = window_stats(pts)
        if not st.get("n"):
            continue
        lines.append(
            f"| {name} | {s.get('n', st['n'])} | {_v(st['last'])} | "
            f"{_v(st['min'])} | {_v(st['max'])} | {_v(st['avg'])} | "
            f"{sparkline([v for _, v in pts], width=20)} |")
    for w in hist.get("warnings") or []:
        lines.append(
            f"\n⚠ history.warning: {w.get('detector', '?')} detector "
            f"on `{w.get('metric', '?')}` "
            f"({w.get('op', '?')} {_v(w.get('threshold'))} over "
            f"{_v(w.get('window_s'))} s).")
    return "\n".join(lines)


def render_devprof(snap: dict, stats: dict | None = None) -> str:
    """Summarize the device-time truth layer (``obs.devprof``,
    docs/observability.md "Device-time truth"): measured per-op
    compute/comm attribution and overlap, drift vs the dispatch-time
    model gauge, unlabeled device time, capture counts, and the last
    parsed profile artifact path. Empty string when the snapshot holds
    no ``device.*`` gauges and no devprof stats."""
    gauges = snap.get("gauges", {})
    counters = snap.get("counters", {})
    dev = {k: v for k, v in gauges.items() if k.startswith("device.")}
    meas = {k: v for k, v in gauges.items()
            if k.startswith("comms.") and ("_measured" in k
                                           or k.endswith("_drift_pct"))}
    prof = {k: v for k, v in counters.items()
            if k.startswith("profile.")}
    if not dev and not meas and not prof and not stats:
        return ""
    lines = ["#### device time (measured)", "| metric | value |",
             "|---|---|"]
    for k in sorted(dev) + sorted(meas):
        v = gauges[k]
        lines.append(f"| {k} | "
                     f"{int(v) if float(v) == int(v) else round(v, 4)} |")
    for k in sorted(prof):
        v = counters[k]
        lines.append(f"| {k} | {int(v) if float(v) == int(v) else v} |")
    if stats:
        if stats.get("last_profile"):
            lines.append(f"| last_profile | {stats['last_profile']} "
                         f"({stats.get('last_reason', '?')}) |")
        if stats.get("armed"):
            lines.append(f"| armed | {stats['armed']} |")
    if dev.get("device.unlabeled_ms"):
        # Nonzero unlabeled time means execution ran outside every
        # device.<op> window — the annotation-coverage pass guards the
        # label plumbing; surface it where the numbers are read.
        lines.append(
            f"\n⚠ {round(float(dev['device.unlabeled_ms']), 3)} ms of "
            f"device/runtime execution was attributed to NO "
            f"device.<op> label (see tdt-check annotation-coverage).")
    return "\n".join(lines)


def render_telemetry(snap: dict) -> str:
    """Render an obs snapshot (the server's ``{"cmd": "metrics"}``
    payload — docs/observability.md) as
    markdown: one counters/gauges table, one histogram summary table,
    plus dedicated resilience and tracing sections when those exist."""
    lines = ["### telemetry"]
    resil = render_resilience(snap)
    serving = render_serving(snap)
    kv = render_kv(snap)
    disagg = render_disagg(snap)
    fleet = render_fleet(snap.get("fleet"))
    router = render_router(snap.get("router"))
    tracing = render_tracing(snap.get("trace"))
    devprof = render_devprof(snap, snap.get("devprof"))
    history = render_history(snap.get("history"))
    # trace.* gauges mirror what the tracing section already shows
    # (they exist for the Prometheus exposition path) — don't render
    # the same numbers twice when that section is present; ditto the
    # serving.* / kv.* metrics and their dedicated sections.
    skip = lambda k: (k.startswith("resilience.")  # noqa: E731
                      or (bool(serving) and k.startswith("serving."))
                      or (bool(kv) and k.startswith("kv."))
                      or (bool(disagg) and k.startswith("disagg."))
                      or (bool(tracing) and k.startswith("trace."))
                      or (bool(devprof)
                          and (k.startswith("device.")
                               or k.startswith("profile.")
                               or (k.startswith("comms.")
                                   and ("_measured" in k
                                        or k.endswith("_drift_pct"))))))
    # The router section renders every router.* COUNTER itself;
    # router gauges/histograms are not in its payload and stay in the
    # generic tables below.
    scalars = [("counter", k, v)
               for k, v in sorted(snap.get("counters", {}).items())
               if not skip(k)
               and not (bool(router) and k.startswith("router."))]
    scalars += [("gauge", k, v)
                for k, v in sorted(snap.get("gauges", {}).items())
                if not skip(k)]
    if resil:
        lines += [resil, ""]
    if serving:
        lines += [serving, ""]
    if kv:
        lines += [kv, ""]
    if disagg:
        lines += [disagg, ""]
    if fleet:
        lines += [fleet, ""]
    if router:
        lines += [router, ""]
    if tracing:
        lines += [tracing, ""]
    if devprof:
        lines += [devprof, ""]
    if history:
        lines += [history, ""]
    if scalars:
        lines += ["| metric | type | value |", "|---|---|---|"]
        for kind, k, v in scalars:
            vv = int(v) if float(v) == int(v) else round(float(v), 4)
            lines.append(f"| {k} | {kind} | {vv} |")
    hists = {k: h for k, h in snap.get("histograms", {}).items()
             if not skip(k)}
    if hists:
        lines += ["", "| histogram | count | mean | min | max |",
                  "|---|---|---|---|---|"]
        for k in sorted(hists):
            h = hists[k]
            n = h.get("count", 0)
            mean = round(h["sum"] / n, 4) if n else None
            lines.append(
                f"| {k} | {n} | {mean} | {h.get('min')} | "
                f"{h.get('max')} |")
    if len(lines) == 1:
        lines.append("(empty)")
    return "\n".join(lines)


def render_sweep(rows: list[dict]) -> str:
    if not rows:
        return "(no rows)"
    out = []
    by_op: dict[str, list] = {}
    for r in rows:
        by_op.setdefault(r.get("op", "?"), []).append(r)
    for op, rs in sorted(by_op.items()):
        cols = [c for c in rs[0] if c != "op"]
        out.append(f"### {op}")
        out.append("| " + " | ".join(cols) + " |")
        out.append("|" + "---|" * len(cols))
        for r in rs:
            out.append("| " + " | ".join(str(r.get(c, "")) for c in cols)
                       + " |")
        out.append("")
    return "\n".join(out)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep", required=True)
    args = ap.parse_args(argv)
    rows = []
    with open(args.sweep) as f:
        for line in f:
            line = line.strip()
            if line:
                rows.append(json.loads(line))
    print(render_sweep(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
