"""The five readers of the program's compile log through the look-up
``run.py`` uses: each by hand on a stubbed log, nothing where the program
keeps no log (the parent commit), and, on the program's own log, what
ended after the window opened left out."""
import json
import os
import time

import pytest

import run as bench_run
from triton_dist_tpu import obs

HERE = os.path.dirname(os.path.abspath(__file__))
T_OPEN = 1000.0
LOG = {"records": [], "programs": 61, "dropped": 0,
       "totals": {"trace": 21.5, "lower": 17.25, "compile": 0.0,
                  "cache_load": 9.125}}
BY_HAND = [("setup.trace_s", 21.5), ("setup.lower_s", 17.25),
           ("setup.compile_s", 0.0), ("setup.cache_load_s", 9.125),
           ("setup.programs", 61.0)]
NAMES = [n for n, _ in BY_HAND]


def read(name, t_open=T_OPEN):
    return bench_run.load_by_name("layer_metrics", name).read(
        {"window": (t_open, t_open + 40.0), "counters": {}})


@pytest.mark.parametrize("name,want", BY_HAND, ids=NAMES)
def test_setup_readers_by_hand(monkeypatch, name, want):
    asked = []
    monkeypatch.setattr(obs, "compile_log",
                        lambda until=None: asked.append(until) or LOG,
                        raising=False)
    value = read(name)
    assert value == want and isinstance(value, float)
    assert asked == [T_OPEN]          # up to the window's opening


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_log_reads_nothing(monkeypatch, name):
    monkeypatch.delattr(obs, "compile_log", raising=False)
    assert read(name) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_log_that_dropped_records_reads_nothing(monkeypatch, name):
    """The list loses its OLDEST records, the start-up's: no sum."""
    monkeypatch.setattr(obs, "compile_log",
                        lambda until=None: {**LOG, "dropped": 3},
                        raising=False)
    assert read(name) is None


@pytest.mark.parametrize("name", NAMES)
def test_the_programs_own_log_is_cut_at_the_windows_opening(name):
    """One lowering and one cache load before the opening, a compile
    after it (the reference's): the phase that did not occur reads 0."""
    import jax.monitoring as mon
    obs.enable(obs.Registry())
    obs.compile.reset()
    try:
        mon.record_event_duration_secs(
            "/jax/core/compile/jaxpr_to_mlir_module_duration", 2.0,
            fun_name="jit(admit)")
        mon.record_event("/jax/compilation_cache/cache_hits")
        mon.record_event_duration_secs(
            "/jax/core/compile/backend_compile_duration", 0.5,
            fun_name="jit(admit)")
        t_open = time.monotonic()
        time.sleep(0.002)
        mon.record_event_duration_secs(
            "/jax/core/compile/backend_compile_duration", 30.0,
            fun_name="jit(reference)")
        want = {"setup.trace_s": 0.0, "setup.lower_s": 2.0,
                "setup.compile_s": 0.0, "setup.cache_load_s": 0.5,
                "setup.programs": 1.0}[name]
        assert read(name, t_open) == want
    finally:
        obs.disable()
        obs.compile.reset()


@pytest.mark.parametrize("name,unit,source", [
    ("setup.trace_s", "s", "program_span"),
    ("setup.lower_s", "s", "program_span"),
    ("setup.compile_s", "s", "program_span"),
    ("setup.cache_load_s", "s", "program_span"),
    ("setup.programs", "programs", "program_span")], ids=NAMES)
def test_the_entries_are_in_the_benchmark_file(name, unit, source):
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert {"name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "runtime", "moves": "setup_s"} in bench["per_layer"]
    assert os.path.exists(os.path.join(
        HERE, "..", "layer_metrics", name + ".py"))
