"""``correct`` has to come out false when the timed path is broken
underneath, and for the control (the reference in the precision below the
configuration's, put in the program's place).

The harness's look for a chip is skipped (``--rehearse``: the rehearsal
sizes of the cell's own configuration, Pallas interpreted on the CPU) and
the rest of a run is driven in this process with a fault planted in the
program: a token altered where it is produced, an answer cut short where
it is produced (the faults a served one-chip cell can have). The limits
are those of the configuration file's rehearsal sizes.
"""
import json
import os

import numpy as np
import pytest

import run as bench_run


def rehearse(tmp_path, cell, seed=2147483999, seconds="3"):
    out = tmp_path / "r.json"
    with pytest.raises(bench_run.Refused) as e:
        bench_run.main(["--workload", cell, "--seed", str(seed),
                        "--seconds", seconds, "--trace", "0", "--rehearse",
                        "--rehearse-out", str(out)])
    assert e.value.code == 3
    return json.loads(out.read_text())


def test_sound_run_is_correct(tmp_path):
    got = rehearse(tmp_path, "qwen3-0.6b.chat-open")
    assert got["correct"] is True, got
    for name in ("gap_max", "gap_mean"):
        value, limit = got["check"][name]
        assert value < limit / 3, (name, got)


def test_altered_token_is_not_correct(tmp_path, monkeypatch):
    """Every fifth sampled token is replaced where it is produced."""
    import jax.numpy as jnp
    from triton_dist_tpu.models import engine
    real = engine.sample_token

    def altered(logits, *a, **kw):
        tok = real(logits, *a, **kw)
        second = jnp.argsort(logits, axis=-1)[..., -40]
        return jnp.where(tok % 5 == 0, second.astype(tok.dtype), tok)

    monkeypatch.setattr(engine, "sample_token", altered)
    got = rehearse(tmp_path, "qwen3-0.6b.chat-open", seed=77)
    assert got["correct"] is False, got
    value, limit = got["check"]["gap_max"]
    assert value > limit


def test_wrong_length_answer_is_not_correct(tmp_path, monkeypatch):
    """An answer cut short where it is produced (the server's reply)."""
    from triton_dist_tpu.serving import server
    real = server.ModelServer._serve_generate

    def short(self, req):
        resp = real(self, req)
        if "tokens" in resp and req.get("gen_len", 0) > 3:
            resp["tokens"] = [t[:-1] for t in resp["tokens"]]
        return resp

    monkeypatch.setattr(server.ModelServer, "_serve_generate", short)
    got = rehearse(tmp_path, "qwen3-0.6b.longprompt-closed", seed=78)
    assert got["correct"] is False
    assert got["check"]["bad_answers"][0] > 0


def test_control_in_lower_precision_is_not_correct():
    """The control at a size a test can hold, through ``correct.check``:
    the reference computed in fp8 and put in the program's place comes
    out ``correct: false`` on every seed, by ``gap_max`` and by
    ``gap_mean``; the float32 reference itself reads 0 on both. (At this
    size, 2 layers of 128, int8 is too close to bfloat16 to separate:
    0.001-0.004 against the program's 0-0.0006 in ``gap_mean``; at the
    cell's own size it fails ``gap_mean`` on the chip, ``PERF.md``.)"""
    from benchmark.harness import correct
    from benchmark.harness.builders import dense
    from benchmark.reference import dense_decoder as ref
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "configs", "qwen3-0.6b.json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearsal"])
    model = dense.model_dict(cfg)
    traffic, bounds = {"output_len": {"max": 12}}, (40, 40)
    for seed in (1, 2, 3):
        rng = np.random.default_rng(seed)
        sampled = []
        for _ in range(8):
            prompt = rng.integers(1, model["vocab_size"], 40).tolist()
            # the reference's own greedy continuation stands in for a
            # sound server's answer
            served = []
            for _ in range(12):
                ids = np.asarray([prompt + served], np.int32)
                pos = np.asarray([[ids.shape[1] - 1]], np.int32)
                served.append(int(np.asarray(
                    ref.read_logits(model, seed, ids, pos)).argmax()))
            sampled.append(({"tokens": served, "gen_len": 12}, prompt))
        sound = correct.check(cfg, model, traffic, bounds, seed, sampled)
        assert sound["ok"] is True
        assert sound["numbers"]["gap_max"]["value"] == 0.0
        assert sound["numbers"]["gap_mean"]["value"] == 0.0
        low = correct.check(cfg, model, traffic, bounds, seed, sampled,
                            control="fp8")
        assert low["ok"] is False, (seed, low)
        for name in ("gap_max", "gap_mean"):
            n = low["numbers"][name]
            assert n["value"] > n["limit"], (seed, name, n)
