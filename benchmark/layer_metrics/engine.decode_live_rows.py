"""Engine: live rows per shared decode step, counted at the step: the
window's delta of the program's live-row counter over its delta of
decode steps (names: ``benchmark/trace_names/pump.json``). The outside
estimate of the same quantity is ``sched.batch_occupancy``."""
import re

from benchmark.harness import hostspans


def read(ctx):
    c = hostspans.names()["counters"]
    if c["decode_live_rows"] not in ctx["counters"]:
        return None
    rx = re.compile(c["decode_steps"])
    steps = sum(v for k, v in ctx["counters"].items() if rx.search(k))
    if steps <= 0:
        return None
    return ctx["counters"][c["decode_live_rows"]] / steps
