"""Live serving dashboard: `top` for the SLO observatory.

Polls a running ModelServer's ``{"cmd": "metrics", "evaluate":
false}`` (read-only — a render tick must not force SLO evaluations;
the pump keeps the gauges fresh while it works, and the ``health``
verb's seq/uptime header says how fresh) plus ``{"cmd":
"request_stats"}`` and renders one refresh-loop screen: rolling
p50/p99 latencies, per-target burn rates with breach flags, batch
occupancy / queue depth, KV block-pool utilization, and the freshest
request waterfalls (``obs.attrib``) — the terminal answer to "is serving
healthy right now and where is the latency going", no Perfetto dump
required (docs/observability.md "SLOs and burn rates").

Usage:
    python -m triton_dist_tpu.tools.top --port 8777 [--interval 2]
        [--once]

``render()`` is pure (snapshot dict → string) so the screen is
testable without a server (tests/test_tools.py).
"""

from __future__ import annotations

import argparse
import sys
import time


def fetch(host: str, port: int, timeout: float = 10.0) -> dict:
    """One scrape: the metrics snapshot plus the newest request
    waterfalls, as the dict :func:`render` consumes.

    The read path is CHEAP on purpose (ISSUE 14 bugfix): the metrics
    request passes ``"evaluate": false`` — rendering a dashboard must
    not force an SLO evaluation per tick, or monitoring N replicas at
    1 Hz perturbs N pump loops — and the replica header comes from the
    lock-free ``{"cmd": "health"}`` verb (its ``seq``/``uptime_s``
    tell the reader how fresh the last-evaluated gauges are; the pump
    re-evaluates every working iteration, so an ACTIVE server's
    gauges are at most ~1 s old anyway)."""
    from triton_dist_tpu.serving.client import ChatClient
    c = ChatClient(host, port, timeout=timeout)
    try:
        snap = c.request({"cmd": "metrics",
                          "evaluate": False})["metrics"]
        try:
            snap["health"] = c.health()
        except Exception:  # noqa: BLE001 — pre-ISSUE-14 servers
            snap["health"] = None
        try:
            # Sampled series (ISSUE 16): None unless the server runs
            # with TDT_HISTORY=1; downsampled server-side so a screen's
            # worth of sparklines costs one small reply.
            snap["history"] = c.request(
                {"cmd": "history", "max_points": 32}).get("history")
        except Exception:  # noqa: BLE001 — pre-ISSUE-16 servers
            snap["history"] = None
        snap["requests"] = c.request_stats(last=5)
    finally:
        c.close()
    return snap


def _fmt(v) -> str:
    if v is None:
        return "-"
    f = float(v)
    return str(int(f)) if f == int(f) else f"{f:.3f}"


def _rows(lines: list, title: str, rows: list) -> None:
    if not rows:
        return
    lines.append(title)
    width = max(len(r[0]) for r in rows)
    for name, val in rows:
        lines.append(f"  {name:<{width}}  {val}")
    lines.append("")


def render(snap: dict) -> str:
    """One dashboard screen from a metrics snapshot (plus an optional
    ``requests`` waterfall list)."""
    g = snap.get("gauges", {})
    c = snap.get("counters", {})
    lines = [f"tdt top — {time.strftime('%H:%M:%S')}", ""]

    h = snap.get("health")
    rid = snap.get("replica_id") or (h or {}).get("replica_id")
    if rid:
        parts = [f"replica {rid}"]
        if h:
            parts.append(f"up {_fmt(h.get('uptime_s'))}s")
            parts.append(f"seq {_fmt(h.get('seq'))}")
        lines[0] += "   [" + "   ".join(parts) + "]"

    slo_rows = []
    for m in ("ttft", "tpot", "queue_wait", "pump"):
        p50 = g.get(f"serving.rolling.{m}_p50_ms")
        p99 = g.get(f"serving.rolling.{m}_p99_ms")
        n = g.get(f"serving.rolling.{m}_n")
        if p50 is None and p99 is None and not n:
            continue
        slo_rows.append((m, f"p50 {_fmt(p50)} ms   p99 {_fmt(p99)} ms"
                            f"   n {_fmt(n)}"))
    if "serving.spec_accept_rate" in g:
        # Speculative decoding rides the same panel: accept rate and
        # emitted tokens per verify step are the knobs that move TPOT
        # (docs/serving.md "Speculative decoding").
        slo_rows.append(
            ("spec", f"accept {_fmt(g['serving.spec_accept_rate'])}   "
                     f"tok/step "
                     f"{_fmt(g.get('serving.spec_tokens_per_step'))}"))
    _rows(lines, "rolling latency (window)", slo_rows)

    burn_rows = []
    for k in sorted(g):
        if k.startswith("serving.slo_burn.") and not k.endswith("_slow"):
            name = k[len("serving.slo_burn."):]
            slow = g.get(f"{k}_slow")
            breached = g.get(f"serving.slo_breached.{name}")
            flag = "  ** BREACH **" if breached else ""
            burn_rows.append(
                (name, f"fast {_fmt(g[k])}   slow {_fmt(slow)}{flag}"))
    _rows(lines, "slo burn rates", burn_rows)

    batch_rows = []
    for label, key in (("batch occupancy", "serving.batch_occupancy"),
                       ("queue depth", "serving.queue_depth"),
                       ("block utilization", "kv.block_utilization"),
                       ("blocks free", "kv.blocks_free"),
                       ("prefix hit rate", "serving.prefix_hit_rate")):
        if key in g:
            batch_rows.append((label, _fmt(g[key])))
    for label, key in (("admitted", "serving.admitted"),
                       ("retired", "serving.retired"),
                       ("slo breaches", "serving.slo_breaches")):
        if key in c:
            batch_rows.append((label, _fmt(c[key])))
    if g.get("trace.dropped_total"):
        batch_rows.append(("trace drops",
                           f"{_fmt(g['trace.dropped_total'])} "
                           f"(raise TDT_TRACE_RING)"))
    _rows(lines, "batch / pool", batch_rows)

    # Device-time truth (obs.devprof): measured per-op attribution
    # from parsed jax.profiler captures, drift vs the modeled gauge,
    # and the last profile artifact a postmortem reader should open.
    dev_rows = []
    ops = sorted({k.split(".")[1] for k in g
                  if k.startswith("device.") and k.count(".") == 2})
    for op in ops:
        comp = g.get(f"device.{op}.compute_ms")
        comm = g.get(f"device.{op}.comm_ms")
        ov = g.get(f"comms.{op}.overlap_pct_measured")
        drift = g.get(f"comms.{op}.overlap_drift_pct")
        val = (f"compute {_fmt(comp)} ms   comm {_fmt(comm)} ms"
               + (f"   overlap {_fmt(ov)}%" if ov is not None else "")
               + (f"   drift {_fmt(drift)}%" if drift is not None
                  else ""))
        dev_rows.append((op, val))
    if g.get("device.unlabeled_ms"):
        dev_rows.append(("(unlabeled)",
                         f"{_fmt(g['device.unlabeled_ms'])} ms "
                         f"(see tdt-check annotation-coverage)"))
    dp = snap.get("devprof") or {}
    if dp.get("last_profile"):
        dev_rows.append(("last profile",
                         f"{dp['last_profile']} "
                         f"({dp.get('last_reason', '?')})"))
    _rows(lines, "device time (measured)", dev_rows)

    # Sampled history (ISSUE 16): one sparkline per recorded series —
    # the time dimension every panel above lacks — plus the newest
    # early-warning excerpts. Only present when the server samples
    # (TDT_HISTORY=1); rendering is additive so old snapshots are fine.
    hist = snap.get("history") or {}
    hist_rows = []
    if hist.get("series"):
        from triton_dist_tpu.obs.history import sparkline, window_stats
        for name in sorted(hist["series"]):
            s = hist["series"][name]
            pts = s.get("points") or []
            st = window_stats(pts)
            if not st.get("n"):
                continue
            hist_rows.append(
                (name, f"{sparkline([v for _, v in pts], width=24)} "
                       f"last {_fmt(st['last'])}   "
                       f"min {_fmt(st['min'])}   max {_fmt(st['max'])}"))
        for w in (hist.get("warnings") or [])[:3]:
            hist_rows.append(
                (f"! {w.get('detector', '?')}",
                 f"{w.get('metric', '?')} {w.get('op', '')} "
                 f"{_fmt(w.get('threshold'))} "
                 f"(window {_fmt(w.get('window_s'))}s)"))
    _rows(lines, "history (sampled)", hist_rows)

    req_rows = []
    for r in snap.get("requests", [])[:5]:
        seg = r.get("segments", {})
        req_rows.append(
            (f"rid {r.get('rid')}",
             f"total {_fmt(r.get('total_ms'))} ms = queue "
             f"{_fmt(seg.get('queue_wait_ms'))} + prefill "
             f"{_fmt(seg.get('prefill_ms'))} + decode "
             f"{_fmt(seg.get('decode_ms'))}   "
             f"[{r.get('tokens')} tok, {r.get('cached_tokens')} "
             f"cached]"))
    _rows(lines, "latest requests", req_rows)

    if len(lines) == 2:
        lines.append("(no serving metrics yet)")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8777)
    ap.add_argument("--interval", type=float, default=2.0)
    ap.add_argument("--iterations", type=int, default=None,
                    help="stop after N refreshes (default: forever)")
    ap.add_argument("--once", action="store_true",
                    help="print one screen and exit (no ANSI clear)")
    args = ap.parse_args(argv)
    n = 1 if args.once else args.iterations
    i = 0
    try:
        while n is None or i < n:
            screen = render(fetch(args.host, args.port))
            if not args.once:
                sys.stdout.write("\x1b[2J\x1b[H")
            print(screen)
            sys.stdout.flush()
            i += 1
            if n is not None and i >= n:
                break
            time.sleep(args.interval)
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
