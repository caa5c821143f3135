"""Engine: the share of admission work the buckets waste. Over the
window, 1 minus (tokens the admitted requests needed run) over (padded
tokens the admission programs ran), from the program's two counters at
the admission itself (names: ``benchmark/trace_names/pump.json``)."""
from benchmark.harness import hostspans


def read(ctx):
    c = hostspans.names()["counters"]
    ran = ctx["counters"].get(c["admit_bucket_tokens"], 0)
    if ran <= 0:
        return None
    need = ctx["counters"].get(c["admit_prompt_tokens"], 0)
    return 100.0 * (1.0 - need / ran)
