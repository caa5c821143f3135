"""The reader of ``engine.admit_head_rows_share`` through the look-up
``run.py`` uses: by hand where the counter is, nothing where it is not
(the parent commit's program), nothing without an admission."""
import json
import os

import pytest

import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "engine.admit_head_rows_share"


def read(counters):
    return bench_run.load_by_name("layer_metrics", NAME).read(
        {"counters": counters})


@pytest.mark.parametrize("rows,want", [
    (3, 100.0 * 3 / 5120),            # one row per admission program
    (5120, 100.0)],                   # every position of every bucket
    ids=["one_row_each", "all_rows"])
def test_head_rows_share_by_hand(rows, want):
    # two 2048 admissions and one 1024
    assert read({"engine.admit_bucket_tokens": 5120,
                 "engine.admit_head_rows": rows}) == pytest.approx(want)


@pytest.mark.parametrize("counters", [
    {"engine.admit_bucket_tokens": 4096, "engine.admit_prompt_tokens": 3000},
    {"engine.admit_bucket_tokens": 0, "engine.admit_head_rows": 0},
    {}], ids=["the_parent_has_no_counter", "no_admission", "nothing"])
def test_nothing_to_read_reads_nothing(counters):
    assert read(counters) is None


def test_the_entry_is_in_the_benchmark_file():
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert {"name": NAME, "unit": "%", "better": "lower",
            "source": "program_counter", "layer": "model step",
            "moves": "ttft_p95_ms"} in bench["per_layer"]
