"""Device-time truth: parse ``jax.profiler`` captures into per-op
timelines and MEASURED overlap metrics.

Every ``comms.<op>.overlap_pct`` number the repo publishes elsewhere is
model-derived (``tools/perf_model``) or dispatch-derived
(``obs.trace``'s host-side chunk events), and no chip run has measured
overlap yet. T3's thesis (PAPERS.md) is that
fine-grained overlap wins are only real when read off the DEVICE
timeline, and the reference's own evaluation is built on per-rank
merged chrome traces. ``tools/profiler.py`` has long owned the capture
side (``group_profile`` wraps ``jax.profiler``); this module is the
missing read-back side:

- **Parse** a capture — the ``*.trace.json(.gz)`` trace-event dump jax
  emits AND/OR the ``*.xplane.pb`` XPlane proto (decoded with a
  self-contained protobuf wire reader; no tensorflow import) — into a
  normalized event list (:func:`load_capture`).
- **Attribute** device/runtime execution intervals to ops via the
  ``device.<op>.<branch>`` ``TraceAnnotation`` labels the resilience
  router plants around every fused-op invocation (and the
  ``device.step`` label the serving pump sampler plants around a
  profiled pump iteration): :func:`summarize`. Execution events are
  classified compute vs comm by name (collectives / DMA / copy vs
  everything else), and interval arithmetic inside each op window
  yields the MEASURED tier of the overlap accounting
  (docs/perf.md "Overlap accounting"):
  ``device.<op>.{total,compute,comm}_ms``,
  ``comms.<op>.overlap_pct_measured``,
  ``comms.<op>.exposed_comm_ms_measured``. Execution time under no
  label lands in ``unlabeled_ms`` (``device.unlabeled_ms``) — the
  annotation-coverage pass (``tdt-check``) keeps that bucket honest.
- **Publish** the summary as gauges, plus a model-vs-measured drift
  gauge ``comms.<op>.overlap_drift_pct`` against the dispatch-time
  ``comms.<op>.overlap_pct`` the cost model set (:func:`publish`).
- **Sample serving continuously** (:class:`PumpSampler`):
  ``TDT_DEVPROF_EVERY=N`` profiles one pump iteration every N, parses
  ASYNC off the pump thread, and feeds the ``device.step.*``
  attribution gauges — plus the decode-step-only sub-windows the
  scheduler brackets per decode path (``device.step.mega.*`` /
  ``device.step.plain.*``, :func:`step_label`), so the auto
  decode-path policy (models/engine.py) arbitrates on unblended,
  admission-free numbers; ``TDT_DEVPROF_ON_BREACH=N`` arms a bounded
  capture of the next N pump iterations when the flight recorder
  dumps (SLO breach, watchdog trip, breaker open) — the postmortem
  then includes what the chip actually did, not just host events.
  Captures start at iteration boundaries in the pump thread, never
  while any scheduler lock is held, and arming is rate-limited like
  flight dumps.

Labels under jit: the router's annotation wraps the PYTHON invocation,
so for a jitted call it brackets trace time (like the ``comms.*``
counters). Measured per-op attribution therefore profiles EAGER
dispatches — exactly how ``tpu_smoke.py`` uses it — while
the pump sampler attributes whole iterations (``device.step``), which
is correct for jitted programs too because the label wraps the
blocking call. docs/perf.md "Overlap accounting" spells out the tiers.

See tools/profile_export.py for the CLI (validate / summary / chrome
conversion) and ``tools/trace_export.py --merge-profile`` for the
one-clock overlay into a host Perfetto dump.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import re
import tempfile
import threading
import time
import weakref

from triton_dist_tpu.obs import registry as _registry

__all__ = [
    "PumpSampler", "STEP_LABEL", "arm", "armed_reason",
    "devprof_dir", "find_captures", "last_profile", "load_capture",
    "op_label", "parse_capture", "parse_xplane", "publish", "reset",
    "sampler_active", "stats", "step_label", "summarize", "wait_idle",
]

#: Annotation label the serving pump sampler plants around a profiled
#: pump iteration (the shared decode step + that iteration's
#: admissions) — the whole-iteration ``device.step.*`` gauges. INSIDE
#: a profiled iteration the scheduler additionally brackets the shared
#: decode step alone with the per-path variant (:func:`step_label` —
#: ``device.step.mega`` / ``device.step.plain``), so the decode paths
#: attribute separately and WITHOUT admission/prefill contamination —
#: the gauges ``Engine(decode_path="auto")`` arbitrates on.
STEP_LABEL = "device.step"

#: Label prefix every op-attribution annotation shares. The resilience
#: router plants ``device.<op>.<branch>`` around each @resilient
#: invocation; anything under no such label is "unlabeled" device time.
LABEL_PREFIX = "device."

#: Minimum spacing between consumed breach-arms (like
#: ``obs.flight.MIN_INTERVAL_S`` — a flapping breaker must not chain
#: captures back to back).
ARM_MIN_INTERVAL_S = 30.0


def op_label(op: str, branch: str = "fused") -> str:
    """The annotation label for one op invocation. The parser keys on
    the ``device.<op>`` prefix; ``branch`` (``fused``/``xla``) rides in
    the third segment so a Perfetto reader can tell a fallback's
    window from a fused one."""
    return f"{LABEL_PREFIX}{op}.{branch}"


def step_label(kind: str | None = None) -> str:
    """The step annotation label: bare :data:`STEP_LABEL` for the
    whole-iteration window, or the per-path variant
    (``device.step.mega`` / ``device.step.plain``) the scheduler
    brackets the SHARED DECODE STEP alone with — decode-step device
    time only, no admission/prefill contamination. The per-path
    segment is load-bearing: the parser keeps it (:func:`_label_op`),
    so the two decode paths attribute into separate
    ``device.step.<kind>.*`` gauges and the auto decode-path policy
    never reads a blend (annotation-coverage pass,
    docs/analysis.md)."""
    return f"{STEP_LABEL}.{kind}" if kind else STEP_LABEL


def _label_op(tail: str) -> str:
    """Attribution key for one ``device.*`` label tail. Router labels
    are ``device.<op>.<branch>`` → the key is ``<op>`` (branches
    blend into one op window); STEP labels keep their decode-path
    segment (``step.mega`` vs ``step.plain`` must NOT blend — the
    auto decode-path policy arbitrates on exactly these gauges)."""
    parts = tail.split(".")
    if parts[0] == "step" and len(parts) > 1 and parts[1]:
        return parts[0] + "." + parts[1]
    return parts[0]


def devprof_dir() -> str:
    """Where device-profile captures land (``TDT_DEVPROF_DIR``)."""
    return (os.environ.get("TDT_DEVPROF_DIR", "").strip()
            or os.path.join(tempfile.gettempdir(), "tdt_devprof"))


# ---------------------------------------------------------------------------
# Capture discovery + loading.
# ---------------------------------------------------------------------------

#: jax.profiler writes <dir>/plugins/profile/<run>/<host>.{trace.json.gz,
#: xplane.pb}; group_profile nests that under <out>/<name>/host<i>/.
_TRACE_SUFFIXES = (".trace.json.gz", ".trace.json", ".json.gz", ".json")
_XPLANE_SUFFIX = ".xplane.pb"


def find_captures(root: str) -> list[str]:
    """Profile run directories under ``root`` (newest last). ``root``
    may be a ``group_profile`` artifact dir, its parent, or already a
    ``plugins/profile/<run>`` dir."""
    root = str(root)
    if not os.path.isdir(root):
        return []
    runs = set()
    for pat in ("", "*/", "*/*/", "*/*/*/"):
        for d in glob.glob(os.path.join(root, pat + "plugins/profile/*")):
            if os.path.isdir(d):
                runs.add(os.path.abspath(d))
    if not runs and _capture_files(root):
        runs.add(os.path.abspath(root))
    return sorted(runs, key=lambda d: (os.path.getmtime(d), d))


def _capture_files(run_dir: str) -> list[str]:
    out = []
    for f in sorted(os.listdir(run_dir)):
        p = os.path.join(run_dir, f)
        if os.path.isfile(p) and (f.endswith(_TRACE_SUFFIXES)
                                  or f.endswith(_XPLANE_SUFFIX)):
            out.append(p)
    return out


def capture_meta(path: str) -> dict:
    """The ``tdt_capture.json`` anchor ``tools/profiler.group_profile``
    writes next to a capture (wall-clock start, host, name) — the
    one-clock handle ``trace_export --merge-profile`` aligns on.
    Empty dict when absent (foreign captures overlay un-anchored)."""
    d = str(path)
    if os.path.isfile(d):
        d = os.path.dirname(d)
    for _ in range(4):   # run dir → .../plugins/profile → host dir
        meta = os.path.join(d, "tdt_capture.json")
        if os.path.isfile(meta):
            try:
                with open(meta) as f:
                    return json.load(f)
            except (OSError, ValueError):
                return {}
        parent = os.path.dirname(d)
        if parent == d:
            break
        d = parent
    return {}


def load_capture(path: str) -> list[dict]:
    """Normalized events from a capture path (a run dir, a
    ``group_profile`` artifact dir, or a single trace/xplane file).

    Each event is ``{"name", "ts_us", "dur_us", "pid", "tid",
    "device": bool}`` — ``device`` marks events from a ``/device:*``
    plane/process (TPU/GPU timelines). Raises ``ValueError`` when the
    path holds no parseable capture (the ``profile_export --validate``
    rc!=0 contract)."""
    path = str(path)
    files: list[str] = []
    if os.path.isfile(path):
        files = [path]
    else:
        runs = find_captures(path)
        if runs:
            files = _capture_files(runs[-1])   # newest run
    if not files:
        raise ValueError(f"no profile capture found under {path!r}")
    # Prefer the trace-event JSON (it carries host-side python events
    # the xplane groups differently); fall back to the xplane proto.
    ordered = ([f for f in files if not f.endswith(_XPLANE_SUFFIX)]
               + [f for f in files if f.endswith(_XPLANE_SUFFIX)])
    last_exc: Exception | None = None
    for f in ordered:
        try:
            if f.endswith(_XPLANE_SUFFIX):
                with open(f, "rb") as fh:
                    return parse_xplane(fh.read())
            return _load_trace_json(f)
        except Exception as e:  # noqa: BLE001 — try the next artifact
            last_exc = e
    raise ValueError(
        f"unparseable profile capture under {path!r}: {last_exc!r}")


def _load_trace_json(path: str) -> list[dict]:
    if path.endswith(".gz"):
        with gzip.open(path) as f:
            data = json.loads(f.read().decode("utf-8", "replace"))
    else:
        with open(path) as f:
            data = json.load(f)
    evs = data.get("traceEvents")
    if not isinstance(evs, list):
        raise ValueError(f"{path}: traceEvents missing")
    device_pids = set()
    for e in evs:
        if (e.get("ph") == "M" and e.get("name") == "process_name"
                and str((e.get("args") or {}).get("name", ""))
                .startswith("/device:")):
            device_pids.add(e.get("pid"))
    out = []
    for e in evs:
        if e.get("ph") != "X":
            continue
        ts, dur = e.get("ts"), e.get("dur", 0.0)
        if not isinstance(ts, (int, float)):
            continue
        out.append({"name": str(e.get("name", "")), "ts_us": float(ts),
                    "dur_us": float(dur or 0.0),
                    "pid": e.get("pid", 0), "tid": e.get("tid", 0),
                    "device": e.get("pid") in device_pids})
    if not out:
        raise ValueError(f"{path}: no complete events")
    return out


# ---------------------------------------------------------------------------
# XPlane proto wire parser (self-contained; schema:
# tensorflow/core/profiler/protobuf/xplane.proto).
# ---------------------------------------------------------------------------

def _varint(b: bytes, i: int) -> tuple[int, int]:
    x = s = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << s
        if not c & 0x80:
            return x, i
        s += 7


def _fields(b: bytes):
    """(field_number, wire_type, value) triples of one message."""
    i, end = 0, len(b)
    while i < end:
        tag, i = _varint(b, i)
        fn, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            ln, i = _varint(b, i)
            v = b[i:i + ln]
            i += ln
        elif wt == 5:
            v, i = b[i:i + 4], i + 4
        elif wt == 1:
            v, i = b[i:i + 8], i + 8
        else:
            raise ValueError(f"unsupported protobuf wire type {wt}")
        yield fn, wt, v


def parse_xplane(data: bytes) -> list[dict]:
    """Decode an XSpace proto into the same normalized event list as
    the trace-event JSON loader. Planes become pids (hash of name),
    lines become tids; ``ts_us`` = line ``timestamp_ns``/1e3 + event
    ``offset_ps``/1e6 — the same profile-session-relative clock the
    JSON dump uses, so both sources anchor identically."""
    out: list[dict] = []
    pid = 0
    for fn, _wt, v in _fields(data):
        if fn != 1:          # XSpace.planes
            continue
        pid += 1
        plane_name = ""
        lines = []
        event_names: dict[int, str] = {}
        for fn2, _wt2, v2 in _fields(v):
            if fn2 == 2:     # XPlane.name
                plane_name = v2.decode("utf-8", "replace")
            elif fn2 == 3:   # XPlane.lines
                lines.append(v2)
            elif fn2 == 4:   # XPlane.event_metadata (map<int64, XEventMetadata>)
                mid, meta = None, b""
                for fn3, _wt3, v3 in _fields(v2):
                    if fn3 == 1:
                        mid = v3
                    elif fn3 == 2:
                        meta = v3
                if mid is not None:
                    name = ""
                    for fn4, _wt4, v4 in _fields(meta):
                        if fn4 == 2:    # XEventMetadata.name
                            name = v4.decode("utf-8", "replace")
                    event_names[mid] = name
        device = plane_name.startswith("/device:")
        for tid, line in enumerate(lines, start=1):
            ts_ns = 0
            events = []
            for fn3, _wt3, v3 in _fields(line):
                if fn3 == 3:            # XLine.timestamp_ns
                    ts_ns = v3
                elif fn3 == 4:          # XLine.events
                    events.append(v3)
            base_us = ts_ns / 1e3
            for ev in events:
                mid = off_ps = dur_ps = 0
                for fn4, _wt4, v4 in _fields(ev):
                    if fn4 == 1:
                        mid = v4
                    elif fn4 == 2:      # offset_ps
                        off_ps = v4
                    elif fn4 == 3:      # duration_ps
                        dur_ps = v4
                out.append({"name": event_names.get(mid, f"#{mid}"),
                            "ts_us": base_us + off_ps / 1e6,
                            "dur_us": dur_ps / 1e6,
                            "pid": pid, "tid": tid, "device": device})
    if not out:
        raise ValueError("xplane capture holds no events")
    return out


# ---------------------------------------------------------------------------
# Attribution: label windows x classified execution intervals.
# ---------------------------------------------------------------------------

#: Execution events on the HOST timeline that represent program
#: execution (the CPU backend has no device plane; TfrtCpuClient
#: executes inline). Device-plane events count wholesale.
_EXEC_PAT = re.compile(
    r"TfrtCpuExecutable::Execute\b|ThunkExecutor::Execute"
    r"|ExecuteReplicated|PjRtStreamExecutor.*Execute")

#: Communication classification, by event name: XLA collective /
#: copy / DMA op families on a device plane. Everything else executed
#: on-device is compute.
_COMM_PAT = re.compile(
    r"all[-_]?gather|all[-_]?reduce|reduce[-_]?scatter"
    r"|collective[-_]?permute|all[-_]?to[-_]?all|copy[-_]?(start|done)"
    r"|\bsend\b|\brecv\b|dma|infeed|outfeed|cross[-_]?replica",
    re.IGNORECASE)


def _union(ivs: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(ivs):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _union_len(ivs) -> float:
    return sum(b - a for a, b in _union(ivs))


def _clip(ivs, windows) -> list[tuple[float, float]]:
    """Intervals ∩ union(windows)."""
    out = []
    windows = _union(windows)
    for a, b in _union(ivs):
        for c, d in windows:
            if d <= a:
                continue
            if c >= b:
                break
            out.append((max(a, c), min(b, d)))
    return out


def _intersect_len(xs, ys) -> float:
    return _union_len(_clip(xs, ys))


def summarize(events: list[dict]) -> dict:
    """Attribute execution intervals to op label windows.

    Returns ``{"ops": {op: {"total_ms", "compute_ms", "comm_ms",
    "exposed_comm_ms", "overlap_pct", "n_events"}}, "unlabeled_ms",
    "n_events", "window_ms"}``. ``overlap_pct`` is
    ``100·(1 − exposed/comm)`` over the MEASURED interval geometry —
    ``None`` when the window held no comm events (a world-1 / CPU run
    has nothing to overlap; callers publish an explicit
    ``overlap_requires_chip`` marker instead of a fiction)."""
    windows: dict[str, list[tuple[float, float]]] = {}
    exec_iv: list[tuple[float, float]] = []
    comm_iv: list[tuple[float, float]] = []
    n_exec = 0
    t_lo, t_hi = float("inf"), float("-inf")
    # Host-side Execute spans stand in for device work ONLY when the
    # capture holds no device plane (the CPU backend executes inline).
    # On a TPU capture they merely bracket dispatch: counting one as
    # compute would let it "cover" device comm intervals and inflate
    # the measured overlap — the exact fiction this tier exists to
    # retire.
    has_device_plane = any(e["device"] for e in events)
    for e in events:
        name, ts, dur = e["name"], e["ts_us"], e["dur_us"]
        t_lo, t_hi = min(t_lo, ts), max(t_hi, ts + dur)
        if name.startswith(LABEL_PREFIX):
            op = _label_op(name[len(LABEL_PREFIX):])
            if op:
                windows.setdefault(op, []).append((ts, ts + dur))
            continue
        is_exec = e["device"] or (not has_device_plane
                                  and _EXEC_PAT.search(name))
        if not is_exec:
            continue
        n_exec += 1
        iv = (ts, ts + dur)
        if _COMM_PAT.search(name):
            comm_iv.append(iv)
        else:
            exec_iv.append(iv)
    ops: dict[str, dict] = {}
    for op, wins in sorted(windows.items()):
        compute = _clip(exec_iv, wins)
        comm = _clip(comm_iv, wins)
        comm_us = _union_len(comm)
        covered_us = _intersect_len(comm, compute)
        exposed_us = max(comm_us - covered_us, 0.0)
        ops[op] = {
            "total_ms": round(_union_len(wins) / 1e3, 6),
            "compute_ms": round(_union_len(compute) / 1e3, 6),
            "comm_ms": round(comm_us / 1e3, 6),
            "exposed_comm_ms": round(exposed_us / 1e3, 6),
            "overlap_pct": (round(100.0 * (1 - exposed_us / comm_us), 2)
                            if comm_us > 0 else None),
            "n_events": len(compute) + len(comm),
            # Annotation windows in the capture: a multi-iteration
            # breach capture unions N step windows into total_ms, so
            # per-window consumers (the auto decode-path policy)
            # normalize by this count instead of comparing unions of
            # different spans.
            "n_windows": len(wins),
        }
    all_windows = [iv for wins in windows.values() for iv in wins]
    unlabeled_us = (_union_len(exec_iv + comm_iv)
                    - _intersect_len(exec_iv + comm_iv, all_windows)
                    if (exec_iv or comm_iv) else 0.0)
    return {"ops": ops,
            "unlabeled_ms": round(max(unlabeled_us, 0.0) / 1e3, 6),
            "n_events": n_exec,
            "window_ms": (round((t_hi - t_lo) / 1e3, 6)
                          if t_hi > t_lo else 0.0)}


def parse_capture(path: str) -> dict:
    """Load + summarize one capture; the summary additionally carries
    ``source`` (the path) and the capture's wall-clock ``meta``."""
    s = summarize(load_capture(path))
    s["source"] = str(path)
    s["meta"] = capture_meta(path)
    return s


# ---------------------------------------------------------------------------
# Publication: summary → gauges (+ model-vs-measured drift).
# ---------------------------------------------------------------------------

def publish(summary: dict) -> None:
    """Set the ``device.*`` / ``*_measured`` gauges from a parsed
    summary, and — where the dispatch-time model gauge exists — the
    ``comms.<op>.overlap_drift_pct`` drift (measured − modeled; a
    large negative drift means the cost model promises overlap the
    chip does not deliver)."""
    reg = _registry.get_registry()
    snap_gauges = reg.snapshot().get("gauges", {})
    for op, m in summary.get("ops", {}).items():
        reg.gauge(f"device.{op}.total_ms").set(m["total_ms"])
        reg.gauge(f"device.{op}.compute_ms").set(m["compute_ms"])
        reg.gauge(f"device.{op}.comm_ms").set(m["comm_ms"])
        reg.gauge(f"device.{op}.windows").set(m.get("n_windows", 1))
        if m["overlap_pct"] is not None:
            reg.gauge(f"comms.{op}.overlap_pct_measured").set(
                m["overlap_pct"])
            reg.gauge(f"comms.{op}.exposed_comm_ms_measured").set(
                m["exposed_comm_ms"])
            modeled = snap_gauges.get(f"comms.{op}.overlap_pct")
            if modeled is not None:
                reg.gauge(f"comms.{op}.overlap_drift_pct").set(
                    round(m["overlap_pct"] - modeled, 2))
    reg.gauge("device.unlabeled_ms").set(summary.get("unlabeled_ms", 0.0))
    reg.counter("profile.parsed").inc()


# ---------------------------------------------------------------------------
# Breach arming (consumed by the pump sampler).
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_ARMED: str | None = None
_LAST_ARM_CONSUMED = 0.0
_LAST_PROFILE: dict | None = None
_PARSE_THREADS: list[threading.Thread] = []

#: Live samplers configured to consume breach-arms. arm() is a no-op
#: with no consumer: otherwise a watchdog trip in a sampler-less
#: process would set an "armed" flag nothing ever clears, and every
#: later metrics scrape would advertise a capture that can never
#: happen.
_CONSUMERS = weakref.WeakSet()

#: ALL live samplers (any trigger config). The auto decode-path
#: policy's exploration probe gates on this — running the other
#: decode path "so a sampler can measure it" is pure waste in a
#: process where no sampler can ever capture (same consumer-gating
#: rationale as :func:`arm`).
_SAMPLERS = weakref.WeakSet()


def sampler_active() -> bool:
    """Is any :class:`PumpSampler` alive in this process (i.e. could a
    pump iteration ever be captured into ``device.step.*`` gauges)?"""
    return any(True for _ in _SAMPLERS)


def arm(reason: str) -> None:
    """Request a device-profile capture of the next pump iterations.
    Called by ``obs.flight`` after each flight dump (SLO breach,
    watchdog trip, breaker open, ...); consumed by a
    :class:`PumpSampler` with a breach window configured. Cheap and
    lock-light: arming happens on failure paths."""
    global _ARMED
    if not any(True for _ in _CONSUMERS):
        return
    with _LOCK:
        if _ARMED is None:
            _ARMED = reason


def armed_reason() -> str | None:
    with _LOCK:
        return _ARMED


def _consume_arm() -> str | None:
    """Take the armed reason if the rate limit allows (one capture per
    :data:`ARM_MIN_INTERVAL_S`, like flight dumps per reason)."""
    global _ARMED, _LAST_ARM_CONSUMED
    with _LOCK:
        if _ARMED is None:
            return None
        now = time.monotonic()
        if now - _LAST_ARM_CONSUMED < ARM_MIN_INTERVAL_S:
            _ARMED = None           # drop: inside the rate window
            return None
        reason, _ARMED = _ARMED, None
        _LAST_ARM_CONSUMED = now
        return reason


def last_profile() -> dict | None:
    """``{"path", "reason", "ts", "summary"}`` of the newest parsed
    capture, or None."""
    with _LOCK:
        return dict(_LAST_PROFILE) if _LAST_PROFILE else None


def _set_last_profile(rec: dict) -> None:
    global _LAST_PROFILE
    with _LOCK:
        _LAST_PROFILE = rec


def stats() -> dict:
    """Devprof state for the server metrics payload / tools/report.py
    (the ``devprof`` key next to ``trace``)."""
    out: dict = {"armed": armed_reason()}
    last = last_profile()
    if last is not None:
        out["last_profile"] = last["path"]
        out["last_reason"] = last["reason"]
        ops = (last.get("summary") or {}).get("ops", {})
        if ops:
            out["ops"] = sorted(ops)
    return out


def wait_idle(timeout: float = 10.0) -> bool:
    """Join outstanding async parse threads (tests / shutdown)."""
    deadline = time.monotonic() + timeout
    with _LOCK:
        threads = list(_PARSE_THREADS)
    for t in threads:
        t.join(max(deadline - time.monotonic(), 0.0))
    with _LOCK:
        _PARSE_THREADS[:] = [t for t in _PARSE_THREADS if t.is_alive()]
        return not _PARSE_THREADS


def reset() -> None:
    """Test isolation: drop armed/last-profile state (parse threads
    are joined best-effort first)."""
    global _ARMED, _LAST_PROFILE, _LAST_ARM_CONSUMED
    wait_idle(timeout=5.0)
    with _LOCK:
        _ARMED = None
        _LAST_PROFILE = None
        _LAST_ARM_CONSUMED = 0.0


# ---------------------------------------------------------------------------
# The serving pump sampler.
# ---------------------------------------------------------------------------

class _ActiveCapture:
    """One in-flight multi-iteration capture (sampler-internal)."""

    __slots__ = ("reason", "remaining", "stack", "path", "t0")

    def __init__(self, reason: str, remaining: int, stack, path, t0):
        self.reason = reason
        self.remaining = remaining
        self.stack = stack
        self.path = path
        self.t0 = t0


class PumpSampler:
    """Low-overhead device-profile sampling for the scheduler pump.

    The pump wraps each iteration's ENGINE WORK (admissions + prefill
    slices + the shared decode step — everything outside the condition
    lock) in :meth:`iteration`. While no capture is active that is a
    null context; when one starts, the iteration runs under the
    :data:`STEP_LABEL` annotation inside a ``group_profile`` window
    that spans ``n`` consecutive iterations, then parsing and gauge
    publication happen on a detached daemon thread (``sync=True`` in
    tests parses inline).

    Two trigger paths, both iteration-boundary only (never mid-lock):

    - **Continuous** (``TDT_DEVPROF_EVERY=N``): every Nth working
      iteration captures one iteration.
    - **Breach-armed** (``TDT_DEVPROF_ON_BREACH=N``): a flight dump
      arms the module (:func:`arm`); the next working iteration starts
      a capture of N iterations. Rate-limited
      (:data:`ARM_MIN_INTERVAL_S`).
    """

    def __init__(self, every: int = 0, on_breach: int = 0,
                 out_dir: str | None = None, sync: bool = False):
        if every < 0 or on_breach < 0:
            raise ValueError("sampler windows must be >= 0")
        self.every = every
        self.on_breach = on_breach
        self.out_dir = out_dir or devprof_dir()
        self.sync = sync
        self._iter = 0
        self._n_captures = 0
        self._cap: _ActiveCapture | None = None
        _SAMPLERS.add(self)
        if on_breach > 0:
            _CONSUMERS.add(self)

    @classmethod
    def from_env(cls) -> "PumpSampler | None":
        """Sampler per the env knobs, or None when both are off (the
        scheduler then pays nothing per iteration)."""
        every = _registry.env_int("TDT_DEVPROF_EVERY", 0, minimum=0)
        on_breach = _registry.env_int("TDT_DEVPROF_ON_BREACH", 0,
                                      minimum=0)
        if every <= 0 and on_breach <= 0:
            return None
        return cls(every=every, on_breach=on_breach)

    def _maybe_start(self) -> None:
        if self._cap is not None:       # a multi-iteration capture is open
            return
        reason: str | None = None
        n = 1
        if self.on_breach > 0:
            armed = _consume_arm()
            if armed is not None:
                reason, n = f"breach_{armed}", self.on_breach
        if reason is None and self.every > 0:
            self._iter += 1
            if self._iter % self.every == 0:
                reason, n = "sampler", 1
        if reason is None:
            return
        try:
            from triton_dist_tpu.tools.profiler import group_profile
            stack = contextlib.ExitStack()
            self._n_captures += 1
            cap_path = stack.enter_context(group_profile(
                f"pump_{self._n_captures}", self.out_dir))
            self._cap = _ActiveCapture(reason, n, stack, str(cap_path),
                                time.perf_counter())
        except Exception:  # noqa: BLE001 — sampling must never hurt serving
            self._cap = None

    def _finish(self) -> None:
        cap, self._cap = self._cap, None
        if cap is None:
            return
        try:
            cap.stack.close()       # stops the jax profiler session
        except Exception:  # noqa: BLE001
            _registry.counter("profile.parse_errors").inc()
            return
        if self.sync:
            _parse_and_publish(cap.path, cap.reason)
            return
        t = threading.Thread(target=_parse_and_publish,
                             args=(cap.path, cap.reason),
                             name="tdt-devprof-parse", daemon=True)
        with _LOCK:
            # Prune finished parse threads as we go: production never
            # calls wait_idle(), and a long-lived server sampling
            # every Nth iteration must not accumulate one dead Thread
            # object per capture forever.
            _PARSE_THREADS[:] = [x for x in _PARSE_THREADS
                                 if x.is_alive()]
            _PARSE_THREADS.append(t)
        t.start()

    @property
    def capturing(self) -> bool:
        """A capture is open right now — the scheduler consults this
        to bracket the shared decode step with the per-path
        :func:`step_label` only while it would land in a capture."""
        return self._cap is not None

    @contextlib.contextmanager
    def iteration(self):
        """Wrap one pump iteration's engine work. Starts/extends/ends
        captures at the boundaries; pump-thread only."""
        self._maybe_start()
        cap = self._cap
        if cap is None:
            yield
            return
        try:
            from triton_dist_tpu.tools.profiler import annotate
            with annotate(STEP_LABEL):
                yield
        finally:
            cap.remaining -= 1
            if cap.remaining <= 0:
                self._finish()

    def close(self) -> None:
        """End any open capture (scheduler stop mid-window)."""
        if self._cap is not None:
            self._cap.remaining = 0
            self._finish()


def _parse_and_publish(path: str, reason: str) -> None:
    """Off-pump parse: capture → summary → gauges → last-profile
    record. Never raises (counts ``profile.parse_errors``)."""
    try:
        summary = parse_capture(path)
        publish(summary)
        _set_last_profile({"path": path, "reason": reason,
                           "ts": time.time(), "summary": summary})
    except Exception:  # noqa: BLE001 — observation only
        _registry.counter("profile.parse_errors").inc()
