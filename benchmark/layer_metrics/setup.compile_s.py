"""Runtime: seconds of `setup_s` inside XLA's back end on programs the
persistent cache did not hold, from the program's compile log up to the
window's opening: about 0 on a machine that ran the cell before, most of
the set-up on its first run."""
from benchmark.harness import setupphases


def read(ctx):
    return setupphases.seconds(ctx, "compile")
