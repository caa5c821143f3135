import json
import os

import pytest

from benchmark.harness import loadgen

HERE = os.path.dirname(os.path.abspath(__file__))


def traffic(name):
    with open(os.path.join(HERE, "..", "traffic", name + ".json")) as f:
        return json.load(f)


def sizes(reqs):
    return sorted((r["prompt_len"], ) for r in reqs), \
        sorted(r["gen_len"] for r in reqs)


@pytest.mark.parametrize("name", ["chat-open", "longprompt-closed"])
def test_plan_is_a_pure_function_of_the_seed(name):
    t = traffic(name)
    a, b = loadgen.plan(t, 2147483999, 20), loadgen.plan(t, 2147483999, 20)
    assert a == b
    c = loadgen.plan(t, 7, 20)
    assert c["window"] != a["window"]                 # another order ...
    assert sizes(c["window"]) == sizes(a["window"])   # ... of the same work
    ids = loadgen.prompt_ids(2147483999, 3, 50, 151936)
    assert ids == loadgen.prompt_ids(2147483999, 3, 50, 151936)
    assert ids != loadgen.prompt_ids(7, 3, 50, 151936)
    assert all(1 <= x < 151936 for x in ids)


def test_open_loop_arrivals_fill_the_window_at_the_rate():
    t = traffic("chat-open")
    p = loadgen.plan(t, 5, 40)
    n = round(t["rate_rps"] * 40)
    assert len(p["window"]) == n
    assert 0 < len(p["ramp"]) <= round(t["rate_rps"] * t["ramp_s"])
    dues = [r["due"] for r in p["window"]]
    assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 40
    assert all(-t["ramp_s"] <= r["due"] <= 0 for r in p["ramp"])
    # every seed walks the same cycle of (size, gap) pairs from another
    # starting point: the same neighbours, in rotation
    q = loadgen.plan(t, 6, 40)
    pairs = lambda w: [(r["prompt_len"], r["gen_len"]) for r in w]
    a, b = pairs(p["window"]), pairs(q["window"])
    assert a != b
    k = next(k for k in range(n) if a[k:] + a[:k] == b)
    assert 0 < k < n


def bucket(n):          # the engine's rule: powers of two from 8
    b = 8
    while b < n:
        b *= 2
    return b


@pytest.mark.parametrize("name,buckets", [
    ("chat-open", {32, 64, 128, 256, 512}),
    ("longprompt-closed", {1024, 2048})])
def test_lengths_cover_exactly_the_buckets_the_clips_allow(name, buckets):
    t = traffic(name)
    lo, hi = loadgen.length_bounds(t)
    assert {bucket(n) for n in range(lo, hi + 1)} == buckets
    p = loadgen.plan(t, 11, 40)
    drawn = {bucket(r["prompt_len"]) for r in p["window"]}
    assert drawn == buckets
    assert all(lo <= r["prompt_len"] <= hi for r in p["window"])
    g = t["output_len"]
    assert all(g["min"] <= r["gen_len"] <= g["max"] for r in p["window"])
    # the warm-up's ladder reaches each of them without asking the program
    from benchmark.harness import runner
    assert {bucket(n) for n in runner.warm_lengths((lo, hi))} == buckets


def test_burst_arrivals_leave_the_off_periods_empty():
    t = dict(traffic("chat-open"),
             arrival={"process": "burst", "on_s": 2.0, "off_s": 2.0})
    p = loadgen.plan(t, 3, 40)
    assert len(p["window"]) == round(t["rate_rps"] * 40)
    dues = [r["due"] for r in p["window"]]
    widest = max(b - a for a, b in zip(dues, dues[1:]))
    assert widest > 1.5          # an OFF period shows as a long gap


def test_sessions_share_prefixes_and_repeat_history():
    t = dict(traffic("chat-open"),
             sessions={"turns": [3, 5], "groups": 2,
                       "shared_prefix": {"min": 512, "max": 1024}})
    p = loadgen.plan(t, 9, 20)
    reqs = p["ramp"] + p["window"]
    by_index = {r["index"]: r for r in reqs}
    first = [r for r in reqs if not r["history"]]
    later = [r for r in reqs if r["history"]]
    assert first and later
    a, b = [r for r in first if r["prefix_group"] == 0][:2]
    ta = loadgen.request_tokens(a, 9, 1000, by_index)
    tb = loadgen.request_tokens(b, 9, 1000, by_index)
    n = a["prefix_len"]
    assert ta[:n] == tb[:n] and ta[n:] != tb[n:]
    r = later[0]
    tr = loadgen.request_tokens(r, 9, 1000, by_index)
    prev = by_index[r["history"][-1]]
    assert len(tr) == (r["prefix_len"] + r["prompt_len"]
                       + sum(by_index[i]["prompt_len"]
                             for i in r["history"]))
    assert loadgen.prompt_ids(9, prev["index"], prev["prompt_len"],
                              1000) == tr[-r["prompt_len"]
                                          - prev["prompt_len"]:
                                          -r["prompt_len"]]
    lo, hi = loadgen.length_bounds(t)
    assert lo <= len(tr) <= hi
