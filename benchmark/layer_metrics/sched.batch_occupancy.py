"""Scheduler: live rows per shared decode step. Decode-phase tokens
generated inside the window (every token after a request's first, placed
evenly over its decode segment) over the window's count of decode steps
(delta of ``engine.decode_path.*``)."""
from benchmark.harness import intervals


def read(ctx):
    steps = sum(v for k, v in ctx["counters"].items()
                if k.startswith("engine.decode_path."))
    if steps <= 0:
        return None
    t_open, t_close = ctx["window"]
    tokens = 0.0
    for r in ctx["all_records"]:
        if "timing" not in r or len(r["tokens"]) < 2:
            continue
        d0 = r["recv"] - r["timing"]["decode_ms"] * 1e-3
        share = intervals.overlap(d0, r["recv"], t_open, t_close) \
            / max(r["recv"] - d0, 1e-9)
        tokens += share * (len(r["tokens"]) - 1)
    return tokens / steps
