"""Runtime: seconds of `setup_s` spent fetching executables from the
persistent compile cache (the back-end events during which the cache
reported a hit), from the program's compile log up to the window's
opening."""
from benchmark.harness import setupphases


def read(ctx):
    return setupphases.seconds(ctx, "cache_load")
