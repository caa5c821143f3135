"""The one traffic generator and the load-offering client.

Two halves, neither of which imports JAX:

* :func:`plan` turns a traffic file (``benchmark/traffic/<name>.json``)
  plus ``--seed`` and ``--seconds`` into the requests of a run. The sizes,
  the arrival gaps and their order are a fixed function of the traffic file
  (stratified quantiles of its distributions, on one cycle); the seed picks
  where on the cycle the run starts and draws the token ids, so every seed
  offers the same work and meets the same queueing episodes, in rotation.
* ``python loadgen.py`` (a child of ``run.py``) reads a spec on stdin,
  offers the load over the server's JSON-lines socket from its own process
  (the parent holds the chip, the server and the scheduler pump; a generator
  in the same interpreter would share its GIL), and prints one JSON line of
  per-request records. Times are ``time.monotonic()``, which on Linux is one
  clock for every process of the machine.
"""

from __future__ import annotations

import json
import math
import queue
import socket
import sys
import threading
import time
from statistics import NormalDist

import numpy as np

SHAPE_SEED = 20260930   # second word of the permutations' seed
PREFIX_INDEX = 1 << 41  # token-id streams of shared prefixes
WARM_INDEX = 1 << 40    # ... and of warm-up prompts: never a request's index


# ---------------------------------------------------------------------------
# Distributions: n stratified draws, the same multiset for every seed.
# ---------------------------------------------------------------------------

def stratified(dist: dict, n: int) -> list[int]:
    """``n`` whole numbers covering ``dist`` evenly: the (i + 0.5)/n
    quantiles, clipped to ``[min, max]``."""
    lo, hi = int(dist["min"]), int(dist["max"])
    us = [(i + 0.5) / n for i in range(n)]
    kind = dist["dist"]
    if kind == "lognormal":
        nd = NormalDist()
        xs = [dist["median"] * math.exp(dist["sigma"] * nd.inv_cdf(u))
              for u in us]
    elif kind == "uniform":
        xs = [lo + u * (hi - lo + 1) - 0.5 for u in us]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return [min(max(int(round(x)), lo), hi) for x in xs]


def arrival_gaps(arrival: dict, rate: float, n: int) -> list[float]:
    """``n`` inter-arrival gaps (seconds) whose sum is ``n / rate``.

    ``poisson``: stratified exponential quantiles. ``burst``: the same
    inside ON periods of ``on_s`` seconds at ``rate * (on_s + off_s) /
    on_s``, with one ``off_s`` gap added whenever an ON period fills."""
    us = [(i + 0.5) / n for i in range(n)]
    base = [-math.log(1.0 - u) for u in us]
    scale = n / sum(base)
    proc = arrival.get("process", "poisson")
    if proc == "poisson":
        return [g * scale / rate for g in base]
    if proc == "burst":
        on, off = float(arrival["on_s"]), float(arrival["off_s"])
        duty = on / (on + off)
        return [g * scale * duty / rate for g in base]
    raise ValueError(f"unknown arrival process {proc!r}")


def _arrival_times(arrival: dict, gaps: list[float]) -> list[float]:
    """Cumulative times; a ``burst`` process inserts its OFF periods."""
    t, out = 0.0, []
    if arrival.get("process", "poisson") != "burst":
        for g in gaps:
            t += g
            out.append(t)
        return out
    on, off = float(arrival["on_s"]), float(arrival["off_s"])
    used = 0.0
    for g in gaps:
        used += g
        while used > on:        # the ON period is full: an OFF period passes
            used -= on
            t += off
        t += g
        out.append(t)
    return out


def prompt_ids(seed: int, index: int, n: int, vocab: int) -> list[int]:
    """Token ids of request ``index``: uniform over ``[1, vocab)``."""
    rng = np.random.default_rng([int(seed), int(index)])
    return rng.integers(1, vocab, n).tolist()


# ---------------------------------------------------------------------------
# The plan of a run.
# ---------------------------------------------------------------------------

def _cycle(traffic: dict, n: int, rate: float) -> list[dict]:
    """The traffic file's one cyclic sequence of ``n`` requests: sizes and
    arrival gaps paired and ordered by a permutation that depends on the
    file alone (``SHAPE_SEED``), never on ``--seed``."""
    rng = np.random.default_rng([SHAPE_SEED, n])
    plens = stratified(traffic["prompt_len"], n)
    glens = stratified(traffic["output_len"], n)
    plens = [plens[i] for i in rng.permutation(n)]
    glens = [glens[i] for i in rng.permutation(n)]
    out = [{"prompt_len": p, "gen_len": g} for p, g in zip(plens, glens)]
    if rate:
        gaps = arrival_gaps(traffic.get("arrival", {}), rate, n)
        for r, i in zip(out, rng.permutation(n)):
            r["gap"] = gaps[i]
    return out


def _open_phases(traffic: dict, start: int, n_ramp: int, n_win: int,
                 ramp_s: float, seconds: float) -> tuple[list, list]:
    """Ramp and window of an open loop: ``n_ramp + n_win`` consecutive
    requests of the cycle, beginning ``n_ramp`` before position ``start``.
    Every seed walks the same cycle from another starting point, so the
    queueing episodes a run meets are the same ones, in rotation."""
    rate = n_win / seconds
    cyc = _cycle(traffic, n_win, rate)
    arrival = traffic.get("arrival", {})
    seq = [cyc[(start - n_ramp + i) % n_win] for i in range(n_ramp + n_win)]
    times = _arrival_times(arrival, [r["gap"] for r in seq])
    # Stretch so the window's arrivals span it (a burst process adds its
    # OFF periods): the last ramp arrival falls where the window opens,
    # the window's last half a mean gap before it closes.
    t_first_win = times[n_ramp - 1] if n_ramp else 0.0
    stretch = seconds * (1.0 - 0.5 / n_win) \
        / max(times[-1] - t_first_win, 1e-9)
    reqs = [{"index": i, "prompt_len": r["prompt_len"],
             "gen_len": r["gen_len"],
             "due": (times[i] - t_first_win) * stretch}
            for i, r in enumerate(seq)]
    ramp = [r for r in reqs[:n_ramp] if r["due"] >= -ramp_s]
    return ramp, reqs[n_ramp:]


def _with_sessions(reqs: list[dict], sessions: dict, perm_rng) -> None:
    """Group consecutive requests into sessions whose turns share a
    prefix: each request gains ``prefix_group``, ``prefix_len`` (tokens
    shared with every session of the group) and ``history`` (indices of
    the earlier turns of its own session, whose new tokens it repeats)."""
    turns = stratified({"dist": "uniform", "min": sessions["turns"][0],
                        "max": sessions["turns"][1]}, max(len(reqs), 1))
    turns = [turns[i] for i in perm_rng.permutation(len(turns))]
    pre = stratified(dict(sessions["shared_prefix"], dist="uniform"),
                     max(len(reqs), 1))
    pre = [pre[i] for i in perm_rng.permutation(len(pre))]
    groups = int(sessions.get("groups", 1))
    i = s = 0
    while i < len(reqs):
        mine = reqs[i:i + turns[s]]
        for j, r in enumerate(mine):
            r["session"] = s
            r["prefix_group"] = s % groups
            r["prefix_len"] = pre[s % groups]
            r["history"] = [m["index"] for m in mine[:j]]
        i += len(mine)
        s += 1


def plan(traffic: dict, seed: int, seconds: float) -> dict:
    """The requests of one run: ``{"loop", "clients", "ramp_s",
    "ramp": [...], "window": [...]}``. Open loop: every request has a
    ``due`` time relative to the window's opening (negative in the ramp).
    Closed loop: ``window`` is the ordered population the clients draw
    from, cycling; nothing is due, a client sends when its reply is in.

    The seed chooses where on the traffic file's cycle the run starts
    (and, through ``prompt_ids``, every token id); the sizes, the gaps
    and their order are the file's."""
    rng = np.random.default_rng([int(seed), SHAPE_SEED])
    ramp_s = float(traffic.get("ramp_s", 0.0))
    out = {"loop": traffic["loop"], "ramp_s": ramp_s,
           "drain_s": float(traffic.get("drain_s", 60.0))}
    if traffic["loop"] == "open":
        rate = float(traffic["rate_rps"])
        n_win = max(int(round(rate * seconds)), 1)
        n_ramp = min(int(round(rate * ramp_s)), n_win)
        start = int(rng.integers(n_win))
        out["ramp"], out["window"] = _open_phases(
            traffic, start, n_ramp, n_win, ramp_s, float(seconds))
    elif traffic["loop"] == "closed":
        n = int(traffic.get("population", 256))
        cyc = _cycle(traffic, n, 0.0)
        start = int(rng.integers(n))
        out["clients"] = int(traffic["clients"])
        out["ramp"] = []
        out["window"] = [dict(cyc[(start + i) % n], index=i)
                         for i in range(n)]
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    if traffic.get("sessions"):
        _with_sessions(out["ramp"] + out["window"], traffic["sessions"],
                       np.random.default_rng([SHAPE_SEED, 1]))
    return out


def request_tokens(req: dict, seed: int, vocab: int,
                   by_index: dict | None = None) -> list[int]:
    """The prompt of one planned request. Without sessions: its own
    ids. With them: the group's shared prefix, the new tokens of the
    session's earlier turns, then its own."""
    own = prompt_ids(seed, req["index"], req["prompt_len"], vocab)
    if "prefix_group" not in req:
        return own
    prefix = prompt_ids(seed, PREFIX_INDEX + req["prefix_group"],
                        req["prefix_len"], vocab)
    hist = []
    for idx in req.get("history", []):
        h = by_index[idx]
        hist += prompt_ids(seed, h["index"], h["prompt_len"], vocab)
    return prefix + hist + own


def length_bounds(traffic: dict) -> tuple[int, int]:
    """Shortest and longest prompt the traffic file can produce; the
    warm-up covers every admission bucket between them, not the lengths
    one seed happened to draw."""
    lo, hi = int(traffic["prompt_len"]["min"]), int(traffic["prompt_len"]["max"])
    s = traffic.get("sessions")
    if s:
        lo += int(s["shared_prefix"]["min"])
        hi = (int(s["shared_prefix"]["max"])
              + int(s["turns"][1]) * int(traffic["prompt_len"]["max"]))
    return lo, hi


# ---------------------------------------------------------------------------
# The wire client (JSON lines over TCP, the server's own protocol).
# ---------------------------------------------------------------------------

class Connection:
    def __init__(self, host: str, port: int, timeout: float):
        self.sock = socket.create_connection((host, port), timeout=timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.rfile = self.sock.makefile("rb")

    def request(self, obj: dict) -> dict:
        self.sock.sendall((json.dumps(obj) + "\n").encode())
        line = self.rfile.readline()
        if not line:
            raise ConnectionError("server closed the connection")
        return json.loads(line)

    def close(self):
        try:
            self.rfile.close()
            self.sock.close()
        except OSError:
            pass


def send_one(conn: Connection, req: dict, tokens: list[int]) -> dict:
    """One generation request; the record the harness keeps of it."""
    rec = {"index": req["index"], "prompt_len": len(tokens),
           "gen_len": req["gen_len"], "due": req.get("due_abs")}
    rec["sent"] = time.monotonic()
    if rec["due"] is None:
        rec["due"] = rec["sent"]
    try:
        resp = conn.request({"prompt_ids": [tokens],
                             "gen_len": req["gen_len"], "stop_tokens": []})
        rec["recv"] = time.monotonic()
        if "tokens" not in resp:
            rec["error"] = str(resp.get("type") or resp.get("error"))[:200]
        else:
            rec["tokens"] = resp["tokens"][0]
            rec["server_ms"] = resp.get("latency_ms")
            timing = (resp.get("timing") or [None])[0]
            if timing is not None:
                rec["timing"] = {"total_ms": timing["total_ms"],
                                 **timing["segments"]}
    except (OSError, ValueError) as e:
        rec["recv"] = time.monotonic()
        rec["error"] = f"{type(e).__name__}: {e}"[:200]
    return rec


# ---------------------------------------------------------------------------
# Offering the load.
# ---------------------------------------------------------------------------

def _run_open(spec: dict, the_plan: dict, t_open: float) -> list[dict]:
    host, port = spec["host"], spec["port"]
    timeout = spec["seconds"] + the_plan["ramp_s"] + the_plan["drain_s"]
    reqs = the_plan["ramp"] + the_plan["window"]
    by_index = {r["index"]: r for r in reqs}
    records, lock = [], threading.Lock()
    idle: queue.LifoQueue = queue.LifoQueue()

    def worker(q: queue.Queue):
        conn = Connection(host, port, timeout)
        while True:
            item = q.get()
            if item is None:
                break
            rec = send_one(conn, *item)
            with lock:
                records.append(rec)
            idle.put(q)
        conn.close()

    def spawn() -> queue.Queue:
        q: queue.Queue = queue.Queue()
        threading.Thread(target=worker, args=(q,), daemon=True).start()
        return q

    for _ in range(int(spec.get("pool", 16))):
        idle.put(spawn())
    for r in sorted(reqs, key=lambda r: r["due"]):
        toks = request_tokens(r, spec["seed"], spec["vocab"], by_index)
        r = dict(r, due_abs=t_open + r["due"])
        delay = r["due_abs"] - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        try:
            q = idle.get_nowait()
        except queue.Empty:
            q = spawn()
        q.put((r, toks))
    deadline = t_open + spec["seconds"] + the_plan["drain_s"]
    while time.monotonic() < deadline:
        with lock:
            if len(records) == len(reqs):
                break
        time.sleep(0.01)
    with lock:
        done = {rec["index"] for rec in records}
        out = list(records)
    for r in reqs:                       # never answered: undrained
        if r["index"] not in done:
            out.append({"index": r["index"], "prompt_len": r["prompt_len"],
                        "gen_len": r["gen_len"], "due": t_open + r["due"],
                        "error": "undrained"})
    return out


def _run_closed(spec: dict, the_plan: dict, t_open: float) -> list[dict]:
    host, port = spec["host"], spec["port"]
    t_start = t_open - the_plan["ramp_s"]
    t_close = t_open + spec["seconds"]
    timeout = spec["seconds"] + the_plan["ramp_s"] + the_plan["drain_s"]
    pop = the_plan["window"]
    by_index = {r["index"]: r for r in pop}
    records, lock = [], threading.Lock()
    counter = [0]

    def client():
        conn = Connection(host, port, timeout)
        delay = t_start - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        while time.monotonic() < t_close:
            with lock:
                k = counter[0]
                counter[0] += 1
            base = pop[k % len(pop)]
            r = dict(base, index=k)
            toks = request_tokens(dict(base, index=k), spec["seed"],
                                  spec["vocab"], by_index)
            rec = send_one(conn, r, toks)
            with lock:
                records.append(rec)
        conn.close()

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(the_plan["clients"])]
    for t in threads:
        t.start()
    deadline = t_close + the_plan["drain_s"]
    for t in threads:
        t.join(max(deadline - time.monotonic(), 0.0))
    with lock:
        out = list(records)
        started = counter[0]
    done = {rec["index"] for rec in out}
    for k in range(started):             # sent, never answered: undrained
        if k not in done:
            base = pop[k % len(pop)]
            out.append({"index": k, "prompt_len": base["prompt_len"],
                        "gen_len": base["gen_len"], "due": None,
                        "error": "undrained"})
    return out


def offer(spec: dict) -> dict:
    the_plan = plan(spec["traffic"], spec["seed"], spec["seconds"])
    t_open = float(spec["t_open"])
    run = _run_open if the_plan["loop"] == "open" else _run_closed
    records = run(spec, the_plan, t_open)
    return {"t_open": t_open, "t_close": t_open + spec["seconds"],
            "records": sorted(records, key=lambda r: r["index"])}


def main() -> None:
    spec = json.loads(sys.stdin.read())
    out = offer(spec)
    sys.stdout.write(json.dumps(out) + "\n")
    sys.stdout.flush()
    # Threads stuck on a dead server must not keep the child alive.
    import os
    os._exit(0)


if __name__ == "__main__":
    main()
