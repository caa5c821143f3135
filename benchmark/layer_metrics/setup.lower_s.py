"""Runtime: seconds of `setup_s` spent lowering jaxprs to StableHLO, the
Pallas-to-Mosaic lowering of every kernel in a program included, from the
program's compile log up to the window's opening. A warm compile cache
saves none of it: the cache is keyed on the lowered module."""
from benchmark.harness import setupphases


def read(ctx):
    return setupphases.seconds(ctx, "lower")
