"""The reader of ``attn.prefill_scored_share`` through the look-up
``run.py`` uses: by hand where the counters are, nothing where they are
not (the parent commit's program), nothing without an admission."""
import json
import os

import pytest

import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))


def reader(name):
    return bench_run.load_by_name("layer_metrics", name).read


def test_scored_share_by_hand():
    # 28 layers, one 2048 admission at 62.5 % and one 1024 at 75 %.
    square = 28 * (2048 ** 2 + 1024 ** 2)
    scored = 28 * (2048 ** 2 * 5 // 8 + 1024 ** 2 * 3 // 4)
    counters = {"attn.prefill_positions_scored": scored,
                "attn.prefill_positions_square": square}
    assert reader("attn.prefill_scored_share")({"counters": counters}) \
        == pytest.approx(65.0)


@pytest.mark.parametrize("counters", [
    {"engine.admit_bucket_tokens": 4096, "engine.admit_prompt_tokens": 3000},
    {"attn.prefill_positions_scored": 0, "attn.prefill_positions_square": 0},
    {}], ids=["the_parent_has_no_counter", "no_admission", "nothing"])
def test_nothing_to_read_reads_nothing(counters):
    assert reader("attn.prefill_scored_share")({"counters": counters}) \
        is None


def test_the_entry_is_in_the_benchmark_file():
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["per_layer"][-1] == {
        "name": "attn.prefill_scored_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "attention",
        "moves": "ttft_p95_ms"}
