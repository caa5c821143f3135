"""Scheduler: the share of the captured window the pump spent in its
idle wait (no live row, nothing queued): the part of ``device.idle``
that is the traffic's and not the program's. ``None`` when the capture
holds neither a turn nor a wait of the pump (a program without these
spans). Span names: ``benchmark/trace_names/pump.json``."""
from benchmark.harness import hostspans


def read(ctx):
    tr = ctx["trace"]
    waits = hostspans.spans(tr.trace, "pump_wait")
    if tr.window_s <= 0 or not (
            waits or hostspans.spans(tr.trace, "pump_iteration")):
        return None
    return 100.0 * sum(s.dur for s in waits) / tr.window_s
