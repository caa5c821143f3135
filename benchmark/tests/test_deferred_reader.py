"""The reader of ``engine.admit_deferred_share`` through the look-up
``run.py`` uses: by hand where the counter is, nothing where it is not
(the parent commit's program), nothing without an admission."""
import json
import os

import pytest

import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "engine.admit_deferred_share"


def read(counters):
    return bench_run.load_by_name("layer_metrics", NAME).read(
        {"counters": counters})


@pytest.mark.parametrize("deferred,want", [
    (140, 100.0),                     # every admission read behind a step
    (105, 75.0),                      # a quarter read at once
    (0, 0.0)],                        # the counter is there and never moved
    ids=["all", "three_quarters", "none"])
def test_deferred_share_by_hand(deferred, want):
    assert read({"engine.stream_admissions": 140,
                 "engine.admit_deferred": deferred}) == pytest.approx(want)


@pytest.mark.parametrize("counters", [
    {"engine.stream_admissions": 140, "engine.admit_bucket_tokens": 4096},
    {"engine.stream_admissions": 0, "engine.admit_deferred": 0},
    {}], ids=["the_parent_has_no_counter", "no_admission", "nothing"])
def test_nothing_to_read_reads_nothing(counters):
    assert read(counters) is None


def test_the_entry_is_in_the_benchmark_file():
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert {"name": NAME, "unit": "%", "better": "higher",
            "source": "program_counter", "layer": "engine",
            "moves": "tokens_per_s"} in bench["per_layer"]
