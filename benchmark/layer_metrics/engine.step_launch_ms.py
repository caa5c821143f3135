"""Engine: how long the host takes to get one decode step onto the
chip. Median of (start of the decode program on the fullest chip minus
start of the ``decode_step`` span that contains it): key split, the
eager programs before it, the dispatch of the argument tree. The pairing
and its clock check: ``benchmark/harness/hostspans.paired_steps``."""
from benchmark.harness import hostspans, stats


def read(ctx):
    pairs = hostspans.paired_steps(ctx)
    if not pairs:
        return None
    return stats.percentile([(p[1] - s.t0) * 1e3 for s, p in pairs], 50)
