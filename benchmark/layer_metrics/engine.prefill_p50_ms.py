"""Engine: admission start to first token sampled (the admission
prefill, and whatever the pump did in between), median."""
from benchmark.harness import stats


def read(ctx):
    pf = [r["timing"]["prefill_ms"] for r in ctx["records"]
          if "timing" in r]
    return stats.percentile(pf, 50)
