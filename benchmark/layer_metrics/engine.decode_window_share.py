"""Engine: how much of the cache window a decode step's attention reads.
Over the window, the program's count of cache positions per row that its
steps read (``engine.decode_window_positions``, at the step itself: the
512-position chunks that cover the longest live row, or the whole cache)
over the positions they would read unbounded: its delta of decode steps
(name: ``benchmark/trace_names/pump.json``) times the configuration's
``engine.max_seq``. 100 % is the whole window at every step. A program
without the counter (the parent of the PR that brought it) reads
nothing."""
import re

from benchmark.harness import hostspans

COUNTER = "engine.decode_window_positions"


def read(ctx):
    if COUNTER not in ctx["counters"]:
        return None
    rx = re.compile(hostspans.names()["counters"]["decode_steps"])
    steps = sum(v for k, v in ctx["counters"].items() if rx.search(k))
    if steps <= 0:
        return None
    whole = steps * int(ctx["config"]["engine"]["max_seq"])
    return 100.0 * ctx["counters"][COUNTER] / whole
