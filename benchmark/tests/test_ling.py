"""What the Ling hybrid configuration added to the benchmark: the
configuration file against the catalog's cut, the count functions
against hand arithmetic at the published sizes, each new reader on the
small recorded trace and on counters (and reading nothing where the
program has no counters), the new cell's rehearsal, and ``correct``
coming out false for the controls and for a planted fault."""
import json
import os

import numpy as np
import pytest

import run as bench_run

from benchmark.harness import tracered, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "ling-3.0-flash.docgen-open"

with open(os.path.join(HERE, "..", "configs", "ling-3.0-flash.json")) as f:
    CFG = json.load(f)


def published_model() -> dict:
    from benchmark.harness.builders import ling
    return ling.model_dict(CFG)


def reader(name):
    return bench_run.load_by_name("layer_metrics", name).read


def kernel(name):
    return bench_run.load_by_name("kernels", name)


# -- the configuration file ------------------------------------------------

def test_configuration_is_the_catalog_entry_cut_as_it_says():
    assert CFG["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size", "num_nextn_predict_layers"]
    for key, value in CFG["published"].items():
        assert CFG[key] != value and key in CFG["reduced"]
    ep = CFG["expert_parallel"]
    assert CFG["num_experts"] * ep["world"] == CFG["published"]["num_experts"]
    assert CFG["num_experts"] * ep["world"] == 512
    assert CFG["vocab_size"] * ep["world"] == 157184
    # the published widths, none cut
    assert (CFG["hidden_size"], CFG["intermediate_size"],
            CFG["moe_intermediate_size"], CFG["head_dim"],
            CFG["num_attention_heads"], CFG["num_experts_per_tok"],
            CFG["kv_lora_rank"], CFG["qk_nope_head_dim"],
            CFG["qk_rope_head_dim"], CFG["v_head_dim"],
            CFG["short_conv_kernel_size"], CFG["kda_lower_bound"],
            CFG["n_group"], CFG["topk_group"],
            CFG["routed_scaling_factor"]) \
        == (2560, 6144, 768, 128, 32, 8, 512, 128, 64, 128, 4, -5, 8, 4,
            2.5)
    model = published_model()
    assert model["num_experts"] == 512
    # dense KDA KDA, then K K K M K K: one whole aligned period, 7:1
    assert kernel("ling_step").kinds(model) == [
        ("kda", False), ("kda", False), ("kda", True), ("kda", True),
        ("kda", True), ("mla", True), ("kda", True), ("kda", True)]
    # the clamps are off in every layer kept
    assert not any(CFG["expert_swiglu_limit_list"][:8])
    assert not any(CFG["share_expert_swiglu_limit_list"][:8])


def test_the_cell_and_configuration_are_in_the_benchmark_file():
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == CFG["name"])
    assert entry["file"] == "benchmark/configs/ling-3.0-flash.json"
    assert entry["reduced"] == CFG["reduced"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ling-3.0-flash", "docgen-open", 1)
    assert len(cell["why"]) <= 200


# -- the count functions by hand, at the published sizes -------------------

H, N, D, HD = 2560, 32, 128, 4096
KDA = 5 * H * HD + 2 * H * N                     # q k v f o; beta, gate
MLA = H * N * 192 + H * 576 + 512 * N * 256 + H * N + N * 128 * H
EXPERT = 3 * H * 768
DENSE = 3 * H * 6144
HEAD = H * 19648


def test_parameter_counts_by_hand():
    ks, m = kernel("ling_step"), published_model()
    assert ks.kda_params(m) == KDA == 52_592_640
    assert ks.mla_params(m) == MLA == 31_965_184
    tokens = 7 * KDA + MLA + 6 * (H * 512 + EXPERT * 2) + 2 * DENSE
    assert ks.token_params(m) == tokens == 573_128_704


def test_step_bytes_by_hand():
    ks, m = kernel("ling_step"), published_model()
    norms = 8 * 2 * H * 2
    kda = 7 * ((KDA + 3 * HD * 4 + D) * 2 + (N + HD) * 4)
    mla = (MLA + 192 + 512) * 2
    sparse = 6 * ((H + 1) * 512 * 4 + EXPERT * 2)
    dense = 2 * DENSE * 2
    fixed = (HEAD + H) * 2 + norms + kda + mla + sparse + dense
    assert ks.fixed_weight_bytes(m) == fixed == 1_192_711_168
    state = 2 * (N * D * D * 4 + 3 * 3 * HD * 2)       # read and written
    assert ks.state_bytes(m, 1) == state == 4_341_760
    # 64 rows: 448 row-layers of state, 240 experts touched, 2000
    # positions a row read by the one MLA layer
    assert ks.step_bytes(m, 240, 448, 64 * 2000) == pytest.approx(
        fixed + 240 * EXPERT * 2 + 448 * state + 64 * 2000 * 576 * 2)


def test_flops_by_hand():
    ks, m = kernel("ling_step"), published_model()
    tokens = 573_128_704
    kda_step = 7 * (N * 3 * D * D + 3 * HD * 4)
    assert ks.decode_token_flops(m, 1000) == pytest.approx(
        2 * (tokens + HEAD + kda_step + N * (2 * 512 + 64) * 1000))
    p = 1024
    kda_scan = 7 * p * (N * (4 * 64 * D + 4 * D * D) + 3 * HD * 4)
    mla = N * (192 + 128) * p * (p + 1) / 2
    assert ks.prefill_flops(m, p) == pytest.approx(
        2 * (tokens * p + HEAD + kda_scan + mla))
    assert ks.prefill_flops(m, p) == pytest.approx(1_230_417_887_232)


# -- the readers -----------------------------------------------------------

@pytest.fixture(scope="module")
def reduced():
    with open(os.path.join(HERE, "data", "small_trace.txt")) as f:
        text = "\n".join(l for l in f if not l.startswith("#"))
    return tracered.Reduced(xplane.load_text(text),
                            host_span_names=["engine.stream_step"])


COUNTERS = {
    "engine.decode_path.plain": 10, "engine.decode_path.auto_plain": 10,
    "moe.experts_touched": 2400, "kda.rows_stepped": 10 * 7 * 50,
    "attn.positions_read.latent": 10 * 50 * 1500,
    "kda.scan_chunks_run": 7 * 30, "kda.scan_chunks_bucket": 7 * 48}


def ctx_of(reduced, **over):
    ctx = {"trace": reduced, "counters": dict(COUNTERS),
           "config": {"trace_names": {"decode_program": "^jit_step$"}},
           "model": published_model(), "chips": 1,
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "load_kernel": kernel, "window": (0.0, 10.0),
           "all_records": [
               {"prompt_len": 1000, "tokens": [1, 2, 3], "recv": 5.0},
               {"prompt_len": 1000, "tokens": [1, 2, 3], "recv": 11.0},
               {"prompt_len": 1000, "error": "undrained"}]}
    ctx.update(over)
    return ctx


def test_counter_readers_by_hand(reduced):
    ctx = ctx_of(reduced)
    ks, m = kernel("ling_step"), ctx["model"]
    assert reader("kda.scan_chunk_share")(ctx) == pytest.approx(
        100 * 30 / 48)
    # a mean step: 240 experts touched, 50 live rows x 7 KDA layers,
    # 50 x 1500 latent positions
    step = ks.step_bytes(m, 240, 350, 75000)
    assert reader("kda.state_share")(ctx) == pytest.approx(
        100 * ks.state_bytes(m, 350) / step)


def test_step_readers_by_hand(reduced):
    ctx = ctx_of(reduced)
    ks, m = kernel("ling_step"), ctx["model"]
    need = (ks.prefill_flops(m, 1000) + ks.decode_token_flops(m, 1001)
            + ks.decode_token_flops(m, 1002))
    assert reader("ling_step.mfu")(ctx) == pytest.approx(
        100 * need / (197e12 * 10.0))
    # the small trace's two steps last 3 and 5 us
    step = ks.step_bytes(m, 240, 350, 75000)
    assert reader("ling_step.hbm_roofline")(ctx) == pytest.approx(
        100 * step / (4e-6 * 819e9))


NEW = ("ling_step.mfu", "ling_step.hbm_roofline", "kda.state_share",
       "kda.scan_chunk_share")


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_reads_nothing(reduced, name):
    """The parent's program has none of the counters: every new reader
    returns nothing and does not raise, whatever model it is told of."""
    parent = ctx_of(reduced, counters={
        "engine.decode_path.plain": 10, "engine.decode_live_rows": 30,
        "moe.experts_touched": 40})
    assert reader(name)(parent) is None
    dense = {"hidden_size": 1024, "intermediate_size": 3072,
             "num_hidden_layers": 28, "num_attention_heads": 16,
             "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 151936}
    assert reader(name)(ctx_of(reduced, model=dense, counters={})) is None
    empty = ctx_of(reduced, trace=tracered.Reduced(xplane.Trace()),
                   counters={}, all_records=[])
    assert reader(name)(empty) is None


@pytest.mark.parametrize("name", NEW)
def test_the_entries_are_in_the_benchmark_file(name):
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert sorted(entry) == ["better", "layer", "moves", "name", "source",
                             "unit", "workloads"]


# -- the cell's rehearsal, sound and with a fault planted ------------------

def rehearse(tmp_path, trace="0", seed=2147483999):
    out = tmp_path / "r.json"
    with pytest.raises(bench_run.Refused) as e:
        bench_run.main(["--workload", CELL, "--seed", str(seed),
                        "--seconds", "4", "--trace", trace, "--rehearse",
                        "--rehearse-out", str(out)])
    assert e.value.code == 3
    return json.loads(out.read_text())


def test_rehearsal_is_correct_and_reads_the_counters(tmp_path):
    got = rehearse(tmp_path, trace="1")
    assert got["correct"] is True, got
    assert got["attempted"] > 0 and got["failed"] == 0
    for name in ("gap_max", "gap_mean"):
        value, limit = got["check"][name]
        assert value <= limit, (name, got)
    names = set(got["metric_names"])
    assert {"kda.state_share", "kda.scan_chunk_share",
            "attn.prefill_scored_share",
            "engine.decode_window_share"} <= names
    assert "ling_step.hbm_roofline" not in names        # a device metric


def test_decay_left_out_is_not_correct(tmp_path, monkeypatch):
    """The decode step forgets nothing: the state never decays."""
    from triton_dist_tpu.layers import kda
    real = kda.delta_step
    monkeypatch.setattr(kda, "delta_step",
                        lambda q, k, v, g, beta, state:
                        real(q, k, v, g * 0, beta, state))
    got = rehearse(tmp_path, seed=93)
    assert got["correct"] is False, got


@pytest.mark.parametrize("control", ["int8", "fp8"])
def test_control_in_lower_precision_is_not_correct(control):
    """The controls at a size a test can hold, through ``correct.check``:
    the reference's own greedy continuation stands in for a sound
    server's answer and reads 0; the reference computed in the lower
    precision and put in the program's place comes out ``correct:
    false`` under the rehearsal's limits."""
    from benchmark.harness import correct
    from benchmark.harness.builders import ling
    from benchmark.reference import ling_hybrid as ref
    cfg = dict(CFG)
    cfg.update(cfg["rehearsal"])
    model = ling.model_dict(cfg)
    traffic, bounds = {"output_len": {"max": 8}}, (40, 40)
    seed = 3
    rng = np.random.default_rng(seed)
    sampled = []
    for _ in range(6):
        prompt = rng.integers(1, model["vocab_size"], 40).tolist()
        served = []
        for _ in range(8):
            ids = np.zeros((1, 48), np.int32)
            ids[0, :40 + len(served)] = prompt + served
            pos = np.asarray([[39 + len(served)]], np.int32)
            served.append(int(np.asarray(
                ref.read_logits(model, seed, ids, pos)).argmax()))
        sampled.append(({"tokens": served, "gen_len": 8}, prompt))
    sound = correct.check(cfg, model, traffic, bounds, seed, sampled)
    assert sound["ok"] is True
    assert sound["numbers"]["gap_max"]["value"] == 0.0
    low = correct.check(cfg, model, traffic, bounds, seed, sampled,
                        control=control)
    assert low["ok"] is False, low
