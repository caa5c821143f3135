"""Engine: how late the pump learns that the chip has finished a decode
step. Median of (end of the ``decode_step`` span minus end of its decode
program): the blocking read and the wait for the interpreter lock. The
pairing and its clock check:
``benchmark/harness/hostspans.paired_steps``."""
from benchmark.harness import hostspans, stats


def read(ctx):
    pairs = hostspans.paired_steps(ctx)
    if not pairs:
        return None
    return stats.percentile([(s.t1 - p[2]) * 1e3 for s, p in pairs], 50)
