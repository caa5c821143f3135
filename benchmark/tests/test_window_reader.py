"""The reader of ``engine.decode_window_share`` through the look-up
``run.py`` uses: by hand where the counter is, nothing where it is not
(the parent commit's program), nothing without a step."""
import json
import os

import pytest

import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
CFG = {"engine": {"batch": 8, "max_seq": 4096}}


def reader(name):
    return bench_run.load_by_name("layer_metrics", name).read


def _ctx(counters):
    return {"counters": counters, "config": CFG}


def test_window_share_by_hand():
    # 6 steps of one 512-chunk, 3 of two, 1 of the whole window:
    # 10 240 positions of 10 x 4096.
    counters = {"engine.decode_window_positions": 6 * 512 + 3 * 1024 + 4096,
                "engine.decode_path.plain": 10,
                # decisions of the auto policy are not steps
                "engine.decode_path.auto_plain": 10}
    assert reader("engine.decode_window_share")(_ctx(counters)) \
        == pytest.approx(25.0)


@pytest.mark.parametrize("counters", [
    {"engine.decode_path.plain": 10, "engine.decode_live_rows": 30},
    {"engine.decode_window_positions": 0},
    {}], ids=["the_parent_has_no_counter", "no_step", "nothing"])
def test_nothing_to_read_reads_nothing(counters):
    assert reader("engine.decode_window_share")(_ctx(counters)) is None


def test_the_entry_is_in_the_benchmark_file():
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "engine.decode_window_share")
    assert entry == {"name": "engine.decode_window_share", "unit": "%",
                     "better": "lower", "source": "program_counter",
                     "layer": "engine", "moves": "tpot_p95_ms"}
