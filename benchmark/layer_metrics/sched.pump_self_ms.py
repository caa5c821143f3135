"""Scheduler: what one pump turn costs the pump itself. Median over the
``pump_iteration`` spans that contain a decode step of the turn's self
time: its duration minus the admission and decode-step spans inside it.
What is left is token recording, retirement, building the replies'
timings and waking the handler threads. Span names:
``benchmark/trace_names/pump.json``."""
from benchmark.harness import hostspans, stats


def read(ctx):
    step = hostspans.names()["spans"]["decode_step"]
    turns = [s for s in hostspans.spans(ctx["trace"].trace,
                                        "pump_iteration") if s.has(step)]
    return stats.percentile([s.self_s * 1e3 for s in turns], 50)
