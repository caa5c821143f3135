"""Runtime: seconds of `setup_s` spent tracing Python to jaxprs, from the
program's compile log up to the window's opening. A trace nested in
another (a `jit` inside a `jit`) is counted once: the log books it to the
outermost. Which function is asked: ``benchmark/trace_names/setup.json``;
nothing from a program without the log, nor from a log that lost records."""
from benchmark.harness import setupphases


def read(ctx):
    return setupphases.seconds(ctx, "trace")
