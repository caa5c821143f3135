"""What the program built before the window opened, from its own
compile log.

The program keeps one record per JAX trace, lowering, back-end compile
and persistent-cache load, with the instant each ended on
``time.monotonic()``, the clock ``ctx["window"]`` is on. The readers
``layer_metrics/setup.{trace_s,lower_s,compile_s,cache_load_s,
programs}.py`` ask it for everything that ended by the window's opening:
the float32 reference of ``correct`` compiles on this device after the
window, and ``ctx["counters"]`` is a difference over the window, where
nothing compiles. Which function of the program to ask is data,
``benchmark/trace_names/setup.json``; what it answers is the read's
contract: ``{"totals": {"trace" | "lower" | "compile" | "cache_load":
seconds}, "programs": n, "dropped": m}``. A program without the log (the
parent of the PR that brought it) reads nothing, and so does a log that
has DROPPED records: its list is bounded and loses the oldest, the
start-up's, so a sum over what is left would read low and say nothing
of it.
"""

from __future__ import annotations

import importlib
import json
import os

NAMES_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "trace_names", "setup.json")


def log(ctx) -> dict | None:
    """The program's log up to ``ctx["window"][0]``, or None where it
    keeps none or no longer holds all of it."""
    with open(NAMES_FILE) as f:
        where = json.load(f)["read"]
    read = getattr(importlib.import_module(where["module"]),
                   where["function"], None)
    got = None if read is None else read(until=ctx["window"][0])
    return None if got is None or got["dropped"] else got


def seconds(ctx, phase: str) -> float | None:
    """Seconds of set-up in ``phase``; 0.0 where the log exists and the
    phase did not occur."""
    got = log(ctx)
    return None if got is None else float(got["totals"].get(phase, 0.0))


def programs(ctx) -> float | None:
    """Programs compiled or loaded before the window."""
    got = log(ctx)
    return None if got is None else float(got["programs"])
