"""What a run, the knee sweep and the calibration of ``correct`` share:
the compile watch, the warm-up through the server socket, and offering
one window of load from the child process."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

from benchmark.harness import loadgen

HARNESS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HARNESS))


def log(**obj) -> None:
    print(json.dumps(obj, default=str), file=sys.stderr, flush=True)


def prepare_environment(chips: int, rehearse: bool) -> None:
    """What has to be in the environment BEFORE JAX is imported."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={chips}")
    # The compile cache: where the environment says, else at a fixed path
    # inside this checkout (the path is part of the cache key). The
    # program's own rule (runtime/compile_cache.py) honours the variable.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # Cache every program, the sub-second ones too: a serving start-up is
    # dozens of them, and each is compiled again by every run otherwise.
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")


def end_to_end(mine, records, window, n_failed: int, wait_ms: float) -> dict:
    """``mine``: the requests DUE inside the window (the tails are over
    all of them); ``records``: every request of the run (the rate counts
    every reply RECEIVED inside the window, the ramp's among them)."""
    from benchmark.harness import stats
    t_open, t_close = window
    ok = [r for r in mine if "tokens" in r]
    tokens = sum(len(r["tokens"]) for r in records
                 if "tokens" in r and t_open <= r["recv"] < t_close)
    ttft = [(r["recv"] - r["due"]) * 1e3 - r["timing"]["decode_ms"]
            for r in ok]
    tpot = [r["timing"]["decode_ms"] / (len(r["tokens"]) - 1)
            for r in ok if len(r["tokens"]) > 1]
    return {
        "tokens_per_s": tokens / (t_close - t_open),
        "ttft_p95_ms": stats.percentile(
            stats.with_failures(ttft, n_failed, wait_ms), 95),
        "tpot_p95_ms": stats.percentile(
            stats.with_failures(tpot, n_failed, wait_ms), 95),
    }


class CompileWatch:
    """Backend compiles and persistent-cache hits, via jax.monitoring."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = self.hits = 0
        self.compile_s = 0.0
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._ev)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _ev(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


# ---------------------------------------------------------------------------
# Warm-up: every program the window will drive, through the server socket.
# ---------------------------------------------------------------------------

def warm_lengths(bounds) -> list[int]:
    """Prompt lengths that between them reach every admission program
    the traffic can: its shortest and longest possible prompt and every
    power of two between (the engine pads a prompt to a power of two,
    rounded to its row split). The harness does not ask the program for
    its rule: a bucket this ladder misses compiles inside the window,
    and ``compiles_in_window`` (limit 0) fails the run."""
    lo, hi = bounds
    ladder = {lo, hi}
    p = 1
    while p < hi:
        if p > lo:
            ladder.add(p)
        p *= 2
    return sorted(ladder)


def warm_round(sut, lengths: list[int], vocab: int, seed: int,
               timeout: float) -> int:
    """One request per bucket plus enough short ones to pass twice through
    every decode row, all at once; returns how many failed."""
    from benchmark.harness import loadgen
    lens = list(lengths) + [lengths[0]] * max(2 * sut.batch - len(lengths), 0)
    failed = [0]

    def one(i, n):
        try:
            conn = loadgen.Connection(sut.host, sut.port, timeout)
            rec = loadgen.send_one(
                conn, {"index": loadgen.WARM_INDEX + i,
                       "gen_len": 3 + i % 3},
                loadgen.prompt_ids(seed, loadgen.WARM_INDEX + i, n, vocab))
            conn.close()
            if "tokens" not in rec:
                raise RuntimeError(rec.get("error"))
        except Exception as e:  # noqa: BLE001 — counted, reported below
            log(event="warm_failed", length=n, error=repr(e)[:300])
            failed[0] += 1

    threads = [threading.Thread(target=one, args=(i, n))
               for i, n in enumerate(lens)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return failed[0]


def warm_up(sut, traffic: dict, seed: int, watch: CompileWatch,
            timeout: float = 1100.0):
    """Rounds of warm-up requests until one compiles nothing. Returns
    the traffic's prompt bounds; raises when a request fails or the
    fourth round still compiles."""
    bounds = loadgen.length_bounds(traffic)
    lengths = warm_lengths(bounds)
    log(event="warm_plan", prompt_bounds=bounds, lengths=lengths)
    vocab = sut.model["vocab_size"]
    for rnd in range(4):
        before, t0 = watch.compiles + watch.hits, time.monotonic()
        failed = warm_round(sut, lengths, vocab, seed, timeout)
        log(event="warm_round", round=rnd, failed=failed,
            s=round(time.monotonic() - t0, 3),
            compiles=watch.compiles + watch.hits - before,
            compile_s=round(watch.compile_s, 3), cache_hits=watch.hits)
        if failed:
            raise RuntimeError("a warm-up request failed")
        if watch.compiles + watch.hits == before:
            return bounds
    raise RuntimeError("still compiling after four warm-up rounds")


def sleep_until(t: float) -> None:
    d = t - time.monotonic()
    if d > 0:
        time.sleep(d)


class Window:
    """One window of load against ``sut``: the child process, the
    instants it opens and closes, and the requests it produced."""

    def __init__(self, sut, traffic: dict, seed: int, seconds: float):
        self.plan = loadgen.plan(traffic, seed, seconds)
        self.seed, self.vocab = seed, sut.model["vocab_size"]
        # The child imports numpy and opens its connections in ~0.3 s.
        self.t_open = time.monotonic() + self.plan["ramp_s"] + 1.5
        self.t_close = self.t_open + seconds
        spec = {"host": sut.host, "port": sut.port, "traffic": traffic,
                "seed": seed, "seconds": seconds, "vocab": self.vocab,
                "t_open": self.t_open, "pool": traffic.get("pool", 16)}
        self.child = subprocess.Popen(
            [sys.executable, os.path.join(HARNESS, "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, cwd=ROOT)
        self.child.stdin.write(json.dumps(spec).encode())
        self.child.stdin.close()
        self.by_index = {r["index"]: r
                         for r in self.plan["ramp"] + self.plan["window"]}

    def collect(self) -> list[dict]:
        """Wait for the child (it drains what is in flight) and return
        every request's record."""
        raw = self.child.stdout.read()
        self.child.wait()
        if self.child.returncode != 0 or not raw.strip():
            raise RuntimeError("the load generator ended with code "
                               f"{self.child.returncode}")
        self.records = json.loads(raw)["records"]
        return self.records

    def mine(self) -> list[dict]:
        """The requests of the window: those DUE inside it (a closed
        loop's request is due when its client's previous reply came)."""
        return [r for r in self.records if r.get("due") is not None
                and self.t_open <= r["due"] < self.t_close]

    def prompt_of(self, rec: dict) -> list[int]:
        if self.plan["loop"] == "closed":
            pop = self.plan["window"]
            base = dict(pop[rec["index"] % len(pop)], index=rec["index"])
        else:
            base = self.by_index[rec["index"]]
        return loadgen.request_tokens(base, self.seed, self.vocab,
                                      self.by_index)
