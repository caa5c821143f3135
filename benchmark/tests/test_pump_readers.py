"""The readers of the pump's spans and counters on a small recorded
capture (``data/pump_trace.txt``): each value by hand, nothing where
there is nothing to read, and the raise when a decode-step span holds no
decode program."""
import importlib.util
import os

import pytest

from benchmark.harness import hostspans, tracered, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1e-6
CFG = {"trace_names": {"decode_program": "^jit_step$"}}


def _text():
    with open(os.path.join(HERE, "data", "pump_trace.txt")) as f:
        return "".join(l for l in f if not l.startswith("#"))


def _ctx(text=None, device=True, counters=None):
    trace = xplane.load_text(text or _text())
    if not device:
        trace.lines = {k: v for k, v in trace.lines.items()
                       if not k[0].startswith("/device:")}
    return {"trace": tracered.Reduced(trace, window_s=50 * US),
            "config": CFG, "counters": counters or {}}


def reader(name):
    path = os.path.join(HERE, "..", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("r", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_trees_nest_by_containment_on_one_line():
    trees = hostspans.trees(_ctx()["trace"].trace)
    # the handler's request span is no pump span: its line holds none
    assert list(trees) == [("/host:CPU", "tdt-scheduler")]
    wait, turn1, turn2 = trees[("/host:CPU", "tdt-scheduler")]
    assert (wait.name, wait.children) == ("serving.pump_wait", [])
    assert [c.name for c in turn1.children] == \
        ["engine.stream_admission", "engine.stream_step"]
    assert [c.name for c in turn2.children] == ["engine.stream_step"]
    assert turn1.self_s == pytest.approx((28 - 13 - 10) * US)
    assert turn2.self_s == pytest.approx((16 - 13) * US)
    assert all(not c.children for c in turn1.children)


@pytest.mark.parametrize("name,value", [
    # turns' self times 5 and 3 us: median 4
    ("sched.pump_self_ms", 4e-3),
    # one wait of 6 us in a window of 58.5 (the device's extent)
    ("sched.starved_share", 100 * 6 / 58.5),
    # the admission span is 13 us, its program ran 8 of them
    ("engine.admit_host_ms", 5e-3),
    # programs start 1 and 3 us into their spans
    ("engine.step_launch_ms", 2e-3),
    # spans end 3 and 4 us after their programs
    ("engine.step_return_ms", 3.5e-3),
])
def test_span_readers_by_hand(name, value):
    assert reader(name)(_ctx()) == pytest.approx(value)


def test_counter_readers_by_hand():
    counters = {"engine.admit_prompt_tokens": 300,
                "engine.admit_bucket_tokens": 400,
                "engine.decode_live_rows": 45,
                "engine.decode_path.plain": 6,
                "engine.decode_path.spec": 3,
                # decisions of the auto policy are not steps
                "engine.decode_path.auto_plain": 6,
                "engine.decode_path.auto_source.default": 6}
    ctx = _ctx(counters=counters)
    assert reader("engine.prefill_padding_share")(ctx) == pytest.approx(25.0)
    assert reader("engine.decode_live_rows")(ctx) == pytest.approx(5.0)


DEVICE_READERS = ("engine.admit_host_ms", "engine.step_launch_ms",
                  "engine.step_return_ms")
ALL_READERS = DEVICE_READERS + (
    "sched.pump_self_ms", "sched.starved_share",
    "engine.prefill_padding_share", "engine.decode_live_rows")


@pytest.mark.parametrize("name", DEVICE_READERS)
def test_device_readers_read_nothing_without_device_planes(name):
    """The CPU rehearsal: host planes only."""
    ctx = _ctx(device=False)
    assert reader(name)(ctx) is None
    # the span readers need no device
    assert reader("sched.pump_self_ms")(ctx) == pytest.approx(4e-3)
    assert reader("sched.starved_share")(ctx) == pytest.approx(12.0)


@pytest.mark.parametrize("name", ALL_READERS)
def test_a_program_without_the_spans_and_counters_reads_nothing(name):
    """The parent commit has ``engine.stream_step`` alone and none of
    the counters: nothing raises, the new metrics stay out (the two that
    need only that span and the device trace still read)."""
    text = _text()
    for gone in ("serving.pump_iteration", "serving.pump_wait",
                 "engine.stream_admission"):
        text = text.replace(f'name: "{gone}"', f'name: "x.{gone}"')
    value = reader(name)(_ctx(text, counters={
        "engine.decode_path.plain": 6}))
    if name in ("engine.step_launch_ms", "engine.step_return_ms"):
        assert value is not None
    else:
        assert value is None


@pytest.mark.parametrize("name", ["engine.step_launch_ms",
                                  "engine.step_return_ms"])
def test_a_step_span_without_its_program_fails_the_run(name):
    """The second decode program is gone from the device's lines: one of
    two spans is matched, under the 99 % the names file asks for."""
    text = _text().replace(
        "events { metadata_id: 3 offset_ps: 44000000 duration_ps: 6000000 }",
        "")
    with pytest.raises(LookupError, match="1 of 2 .*share a clock"):
        reader(name)(_ctx(text))
    # so does a span that holds two
    text = _text().replace(
        "events { metadata_id: 5 offset_ps: 10000000 duration_ps: 500000 }",
        "events { metadata_id: 3 offset_ps: 26000000 duration_ps: 500000 }")
    with pytest.raises(LookupError, match="1 of 2"):
        reader(name)(_ctx(text))


def test_spans_cut_by_the_captures_edge_are_left_out():
    """Without the trailing tiny program the last device event ends at
    50 us, before the second step span does (54): that span may have
    lost its program to the edge and is not judged."""
    text = _text().replace(
        "events { metadata_id: 5 offset_ps: 58000000 duration_ps: 500000 }",
        "")
    ctx = _ctx(text)
    assert reader("engine.step_launch_ms")(ctx) == pytest.approx(1e-3)
    assert reader("engine.step_return_ms")(ctx) == pytest.approx(3e-3)
