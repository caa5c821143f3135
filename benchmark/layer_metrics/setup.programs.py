"""Runtime: programs compiled or loaded from the cache before the window
opened (the back-end records of the program's compile log, counted): what
one more admission bucket or one more query block adds to, whatever each
costs."""
from benchmark.harness import setupphases


def read(ctx):
    return setupphases.programs(ctx)
