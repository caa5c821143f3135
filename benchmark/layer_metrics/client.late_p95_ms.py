"""Load generator: how late requests left, send time minus due time
(p95 over the window's requests). A starved generator must not be read
as a fast server."""
from benchmark.harness import stats


def read(ctx):
    late = [(r["sent"] - r["due"]) * 1e3 for r in ctx["records"]
            if "sent" in r]
    return stats.percentile(late, 95)
