"""Scheduler: submit to admission start, from each reply's waterfall."""
from benchmark.harness import stats


def read(ctx):
    qw = [r["timing"]["queue_wait_ms"] for r in ctx["records"]
          if "timing" in r]
    return stats.percentile(qw, 95)
