"""The xplane reader and the interval reduction on a small recorded
trace (``data/small_trace.txt``, an XSpace text proto)."""
import os

import pytest

from benchmark.harness import intervals, tracered, xplane

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def reduced():
    with open(os.path.join(HERE, "data", "small_trace.txt")) as f:
        text = "\n".join(l for l in f if not l.startswith("#"))
    return tracered.Reduced(xplane.load_text(text),
                            host_span_names=["engine.stream_step"])


def test_intervals():
    assert intervals.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert intervals.union_len([(0, 2), (1, 3), (5, 6)]) == 4
    assert intervals.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert intervals.clip([(0, 10)], 2, 3) == [(2, 3)]
    assert intervals.overlap(0, 4, 3, 9) == 1


def test_busy_and_window(reduced):
    us = 1e-6
    assert reduced.chips == ["/device:TPU:0"]
    # ops: [0,2] [2,3] [10,14] [20,22] [23,25] us -> busy 11 of 25
    assert reduced.busy_s == pytest.approx(11 * us)
    assert reduced.window_s == pytest.approx(25 * us)
    ops = dict(reduced.device_ops())
    assert ops["fusion"] == pytest.approx(8 * us)
    assert ops["gemm_ar_kernel"] == pytest.approx(3 * us)


def test_programs(reduced):
    steps = reduced.module_events("^jit_step$")
    assert len(steps) == 2
    assert sum(b - a for _, a, b in steps) == pytest.approx(8e-6)
    assert len(reduced.module_events("^jit_admit$")) == 1


def test_idle_gaps_go_to_what_was_going_on(reduced):
    gaps = dict(reduced.idle_gaps())
    # [3,10] us: no program on the chip, no named span open (the request
    # span of a handler thread is not one): the host before the admission
    assert gaps["before.jit_admit"] == pytest.approx(7e-6)
    # [14,20] us, middle 17: inside the program's decode span (16-25.5)
    assert gaps["engine.stream_step"] == pytest.approx(6e-6)
    # [22,23] us: a program is running, the chip waits on itself
    assert gaps["inside.jit_step"] == pytest.approx(1e-6)


def test_readers_on_the_small_trace(reduced):
    import importlib.util

    def reader(name):
        path = os.path.join(HERE, "..", "layer_metrics", name + ".py")
        spec = importlib.util.spec_from_file_location("r", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    cfg = {"trace_names": {"decode_program": "^jit_step$"}}
    model = {"hidden_size": 1024, "intermediate_size": 3072,
             "num_hidden_layers": 28, "num_attention_heads": 16,
             "num_key_value_heads": 8, "head_dim": 128,
             "vocab_size": 151936}
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}

    def load_kernel(n):
        path = os.path.join(HERE, "..", "kernels", n + ".py")
        spec = importlib.util.spec_from_file_location("k", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    # one reply of 3 tokens to a 10-token prompt, received in the window;
    # one received after it closed, which does not count
    records = [{"prompt_len": 10, "tokens": [1, 2, 3], "recv": 5.0},
               {"prompt_len": 10, "tokens": [1, 2, 3], "recv": 11.0},
               {"prompt_len": 10, "error": "undrained"}]
    ctx = {"trace": reduced, "config": cfg, "model": model, "peaks": peaks,
           "chips": 1, "load_kernel": load_kernel, "window": (0.0, 10.0),
           "all_records": records}
    assert reader("engine.decode_step_ms")(ctx) == pytest.approx(4e-3)
    assert reader("device.idle")(ctx) == pytest.approx(100 * 14 / 25)
    ms = load_kernel("model_step")
    need = (ms.prefill_flops(model, 10) + ms.decode_token_flops(model, 11)
            + ms.decode_token_flops(model, 12))
    assert reader("model_step.mfu")(ctx) == pytest.approx(
        100 * need / (197e12 * 10.0))
    # a capture whose decode program has another name fails the run
    renamed = dict(ctx, config={"trace_names": {"decode_program": "^nope$"}})
    with pytest.raises(LookupError, match="jit_step"):
        reader("engine.decode_step_ms")(renamed)
    # nothing to read is nothing, never a 0
    empty = dict(ctx, trace=tracered.Reduced(xplane.Trace()),
                 all_records=[])
    for name in ("engine.decode_step_ms", "device.idle", "model_step.mfu"):
        assert reader(name)(empty) is None
