"""Engine: what one admission costs the host. Median over the
``admission`` spans of the span's duration minus the device time of the
admission programs that start inside it: list to array, key split,
padding, the blocking read of the first token, the two row updates.
Span name and program pattern: ``benchmark/trace_names/pump.json``."""
from benchmark.harness import hostspans, stats


def read(ctx):
    tr = ctx["trace"]
    if not tr.chips:
        return None
    found = hostspans.inside_device_extent(
        tr, hostspans.spans(tr.trace, "admission"))
    progs = hostspans.admission_programs(tr)
    host = [s.dur - sum(b - a for _, a, b in
                        hostspans.programs_started_in(s, progs))
            for s in found]
    return stats.percentile([h * 1e3 for h in host], 50)
