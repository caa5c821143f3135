"""Client/server: what the socket, JSON and the handler thread add, the
client's latency minus the scheduler's own ``timing.total_ms`` (median)."""
from benchmark.harness import stats


def read(ctx):
    over = [(r["recv"] - r["sent"]) * 1e3 - r["timing"]["total_ms"]
            for r in ctx["records"] if "timing" in r]
    return stats.percentile(over, 50)
