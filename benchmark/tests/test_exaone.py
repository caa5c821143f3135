"""What the K-EXAONE configuration added to the benchmark: the plain
reference against a hand-worked tiny case, the count functions against
hand-worked shapes, each new reader on the small recorded trace and on
counters, the new cell's rehearsal, and ``correct`` coming out false for
the controls and for each planted fault."""
import json
import os

import numpy as np
import pytest

import run as bench_run

from benchmark.harness import tracered, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "k-exaone-236b-a23b.mixed-open"

with open(os.path.join(HERE, "..", "configs", "k-exaone-236b-a23b.json")) as f:
    CFG = json.load(f)


def published_model() -> dict:
    from benchmark.harness.builders import exaone
    return exaone.model_dict(CFG)


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
    assert CFG["num_experts"] * ep["world"] == 128
    assert CFG["vocab_size"] * ep["world"] == 153600
    # the published widths, none cut
    assert (CFG["hidden_size"], CFG["intermediate_size"],
            CFG["moe_intermediate_size"], CFG["head_dim"],
            CFG["num_attention_heads"], CFG["num_key_value_heads"],
            CFG["num_experts_per_tok"], CFG["sliding_window"]) \
        == (6144, 18432, 2048, 128, 64, 8, 8, 128)
    model = published_model()
    assert model["num_experts"] == 128
    kinds = kernel("exaone_step").kinds(model)
    # dense first, then S S F S: a whole LLLG period among the four sparse
    assert kinds == [(128, False), (128, True), (128, True), (None, True),
                     (128, True)]
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [c["name"] for c in bench["configs"]] == ["qwen3-0.6b",
                                                     "k-exaone-236b-a23b"]
    assert all(w["chips"] == 1 for w in bench["workloads"])
    mfu = next(m for m in bench["per_layer"] if m["name"] == "model_step.mfu")
    assert CELL not in mfu["workloads"]


# -- the reference by hand -------------------------------------------------

TINY = dict(
    hidden_size=8, intermediate_size=12, num_hidden_layers=3,
    num_attention_heads=2, num_key_value_heads=1, head_dim=4, vocab_size=32,
    rope_theta=100.0, rms_norm_eps=1e-5, sliding_window=3, num_experts=4,
    num_experts_per_tok=2, moe_intermediate_size=6, num_shared_experts=1,
    routed_scaling_factor=2.5, norm_topk_prob=True,
    layer_types=["sliding_attention", "full_attention", "sliding_attention"],
    mlp_layer_types=["dense", "sparse", "sparse"], expert_parallel=(2, 1),
    balance_shape=(2, 16))


def by_hand(model, weights, top, bias, ids):
    """One sequence, position by position and expert by expert, float64:
    nothing shared with the reference but the equations."""
    f = lambda a: np.asarray(a, np.float64)
    eps, d = model["rms_norm_eps"], model["head_dim"]
    nh, nkv = model["num_attention_heads"], model["num_key_value_heads"]

    def rms(x, g):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * f(g)

    def rope(x, pos):
        half = d // 2
        inv = 1.0 / model["rope_theta"] ** (np.arange(0, d, 2) / d)
        c, s = np.cos(pos * inv), np.sin(pos * inv)
        return np.concatenate([x[:half] * c - x[half:] * s,
                               x[half:] * c + x[:half] * s])

    def swiglu(x, w, e=None):
        g, u, dn = (f(w[k]) if e is None else f(w[k][e])
                    for k in ("w_gate", "w_up", "w_down"))
        a = x @ g
        return (a / (1 + np.exp(-a)) * (x @ u)) @ dn

    x = f(top["embed"])[ids]                                   # (S, H)
    n = len(ids)
    for li, w in enumerate(weights):
        window = (model["sliding_window"]
                  if model["layer_types"][li] == "sliding_attention"
                  else None)
        a = w["attn"]
        q = (x @ f(a["w_q"])).reshape(n, nh, d)
        k = (x @ f(a["w_k"])).reshape(n, nkv, d)
        v = (x @ f(a["w_v"])).reshape(n, nkv, d)
        q, k = rms(q, a["q_norm"]), rms(k, a["k_norm"])
        if window is not None:
            q = np.stack([[rope(q[i, h], i) for h in range(nh)]
                          for i in range(n)])
            k = np.stack([[rope(k[i, h], i) for h in range(nkv)]
                          for i in range(n)])
        att = np.zeros((n, nh, d))
        for i in range(n):
            lo = 0 if window is None else max(i - window + 1, 0)
            for h in range(nh):
                kv = h // (nh // nkv)
                sc = np.array([q[i, h] @ k[j, kv] / np.sqrt(d)
                               for j in range(lo, i + 1)])
                p = np.exp(sc - sc.max())
                p /= p.sum()
                att[i, h] = sum(p[j - lo] * v[j, kv]
                                for j in range(lo, i + 1))
        x = x + rms(att.reshape(n, nh * d) @ f(a["w_o"]), w["ln_attn"])
        if "mlp" in w:
            y = np.stack([swiglu(x[i], w["mlp"]) for i in range(n)])
        else:
            m = w["moe"]
            lo_e, held = 2, 2                      # rank 1 of 2 holds 2, 3
            y = np.zeros_like(x)
            for i in range(n):
                s = 1 / (1 + np.exp(-(x[i] @ f(m["w_router"]))))
                pick = np.argsort(-(s + f(bias[li])), kind="stable")[:2]
                wts = s[pick] / s[pick].sum() * 2.5
                y[i] = swiglu(x[i], m["shared"])
                for e, wt in zip(pick, wts):
                    if lo_e <= e < lo_e + held:
                        y[i] += wt * swiglu(x[i], m, e - lo_e)
        x = x + rms(y, w["ln_mlp"])
    return rms(x, top["final_norm"]) @ f(top["lm_head"]).T


def test_reference_against_a_hand_worked_case():
    from benchmark.reference import exaone_moe as ref
    model = dict(ref.model_items(TINY))
    seed = 5
    key = ref.seed_key(seed)
    bias = np.asarray(ref.selection_bias(TINY, seed))
    assert bias.shape == (3, 4) and not bias[0].any() and bias[1].any()
    weights = [ref.layer_leaves(key, i, model, ref.is_sparse(model, i))
               for i in range(3)]
    assert weights[1]["moe"]["w_gate"].shape == (2, 8, 6)     # held only
    top = ref.top_leaves(key, model)
    ids = np.array([3, 17, 9, 30, 1, 22, 8])
    want = by_hand(model, weights, top, bias, ids)
    got = np.asarray(ref.read_logits(TINY, seed, ids[None],
                                     np.arange(7)[None]))[0]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # a pad suffix is invisible to the positions before it
    padded = np.asarray(ref.read_logits(
        TINY, seed, np.concatenate([ids, [0, 0, 0]])[None],
        np.arange(7)[None]))[0]
    np.testing.assert_allclose(padded, got, rtol=1e-5, atol=1e-6)


def test_selection_bias_evens_the_load_on_fresh_ids():
    """The balanced bias is a property of the weights: ids the balancing
    never saw load the experts evenly too (and zero bias does not)."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import exaone_moe as ref
    model = dict(ref.model_items(dict(
        TINY, hidden_size=32, num_experts=16, expert_parallel=(4, 0),
        balance_shape=(16, 64), num_hidden_layers=2,
        layer_types=TINY["layer_types"][:2],
        mlp_layer_types=TINY["mlp_layer_types"][:2])))
    seed = 9
    bias = ref.selection_bias(model, seed)[1]
    key = ref.seed_key(seed)
    ids = jax.random.randint(jax.random.PRNGKey(1234), (16, 64), 1, 32)
    x = ref.top_leaves(key, model)["embed"][ids].astype(jnp.float32)
    x = ref._layer_of_seed(key, jnp.int32(0), x, bias * 0,
                           items=ref.model_items(model), sparse=False,
                           window=3, precision="f32")
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     ref.layer_leaves(key, 1, model, True))
    mid = x + ref._rms_norm(ref.attention(x, w["attn"], model, None),
                            w["ln_attn"], model["rms_norm_eps"])

    def imbalance(b):
        _, idx = ref.route(mid.reshape(-1, 32), w["moe"]["w_router"], b,
                           model)
        load = np.bincount(np.asarray(idx).ravel(), minlength=16)
        return load.max() / load.mean()

    assert imbalance(bias) < 1.25 < imbalance(bias * 0)


# -- the counts by hand ----------------------------------------------------

def test_counts_against_hand_worked_shapes():
    ks, m = kernel("exaone_step"), published_model()
    attn = 6144 * (8192 + 2 * 1024) + 8192 * 6144
    expert = 3 * 6144 * 2048
    assert ks.attn_params(m) == attn == 113_246_208
    assert ks.expert_params(m) == expert == 37_748_736
    assert ks.held_pairs_expected(m) == 1.0             # 8 x 16 / 128
    token = 5 * attn + 3 * 6144 * 18432 + 4 * (6144 * 128 + 2 * expert)
    assert ks.token_params(m) == token
    head = 6144 * 19200
    # a token at context 1000: window layers attend 128, the full one 1000
    assert ks.decode_token_flops(m, 1000) == pytest.approx(
        2 * (token + head) + 4 * 64 * 128 * (4 * 128 + 1000))
    assert ks.prefill_flops(m, 1) == pytest.approx(
        ks.decode_token_flops(m, 1))
    # a prompt of 300: full layer 300*301/2 pairs, a window layer
    # 128*129/2 + 172*128
    assert ks.prefill_flops(m, 300) == pytest.approx(
        2 * token * 300 + 2 * head
        + 4 * 64 * 128 * (300 * 301 / 2 + 4 * (128 * 129 / 2 + 172 * 128)))
    fixed = (2 * (head + 6144)
             + 5 * 2 * (attn + 2 * 128 + 2 * 6144)
             + 2 * 3 * 6144 * 18432
             + 4 * (4 * (6144 + 1) * 128 + 2 * expert))
    assert ks.fixed_weight_bytes(m) == fixed
    # the issue's arithmetic: 7.19 GB of weights a step when all 64 held
    # experts of the four sparse layers get a token
    assert ks.step_bytes(m, 64, 0, 0) == fixed + 64 * expert * 2
    assert ks.step_bytes(m, 64, 0, 0) == pytest.approx(7.19e9, rel=0.01)
    kv = 2 * 8 * 128 * 2
    assert ks.step_bytes(m, 10, 1000, 3000) == fixed + 10 * expert * 2 \
        + 4000 * kv


# -- the readers -----------------------------------------------------------

@pytest.fixture(scope="module")
def reduced():
    with open(os.path.join(HERE, "data", "small_trace.txt")) as f:
        text = "\n".join(l for l in f if not l.startswith("#"))
    return tracered.Reduced(xplane.load_text(text),
                            host_span_names=["engine.stream_step"])


COUNTERS = {
    "engine.decode_path.plain": 10, "engine.decode_path.auto_plain": 10,
    "moe.routed_tokens": 4000, "moe.held_pairs": 4100,
    "moe.pair_rows_computed": 8200, "moe.experts_touched": 400,
    "attn.positions_read.window": 4 * 10 * 20 * 128,
    "attn.positions_read.full": 10 * 20 * 640,
    **{f"moe.expert_pairs.{e}": 250 for e in range(16)},
    "moe.expert_pairs.3": 350}


def ctx_of(reduced, **over):
    ctx = {"trace": reduced, "counters": dict(COUNTERS),
           "config": {"trace_names": {"decode_program": "^jit_step$"}},
           "model": published_model(), "chips": 1,
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "load_kernel": kernel, "window": (0.0, 10.0),
           "all_records": [
               {"prompt_len": 300, "tokens": [1, 2, 3], "recv": 5.0},
               {"prompt_len": 300, "tokens": [1, 2, 3], "recv": 11.0},
               {"prompt_len": 300, "error": "undrained"}]}
    ctx.update(over)
    return ctx


def test_counter_readers_by_hand(reduced):
    ctx = ctx_of(reduced)
    assert reader("moe.held_pairs_per_token")(ctx) == pytest.approx(1.025)
    assert reader("moe.load_imbalance")(ctx) == pytest.approx(
        350 / (4100 / 16))
    assert reader("moe.padding_share")(ctx) == pytest.approx(50.0)
    # per layer and step: 20 rows x 128 of a window layer, x 640 of the
    # full one
    assert reader("attn.window_read_share")(ctx) == pytest.approx(20.0)


def test_step_readers_by_hand(reduced):
    ctx = ctx_of(reduced)
    ks, m = kernel("exaone_step"), ctx["model"]
    need = (ks.prefill_flops(m, 300) + ks.decode_token_flops(m, 301)
            + ks.decode_token_flops(m, 302))
    assert reader("exaone_step.mfu")(ctx) == pytest.approx(
        100 * need / (197e12 * 10.0))
    # a mean step: 40 experts touched, 10 240 window and 12 800 full
    # positions; the small trace's two steps last 3 and 5 us
    per_step = ks.step_bytes(m, 40, 4 * 20 * 128, 20 * 640)
    assert reader("exaone_step.hbm_roofline")(ctx) == pytest.approx(
        100 * per_step / (4e-6 * 819e9))


NEW = ("exaone_step.mfu", "exaone_step.hbm_roofline",
       "moe.held_pairs_per_token", "moe.load_imbalance",
       "moe.padding_share", "attn.window_read_share")


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_reads_nothing(reduced, name):
    """The parent's program has none of the counters and its model none
    of the keys: every new reader returns nothing and does not raise."""
    dense = {"hidden_size": 1024, "intermediate_size": 3072,
             "num_hidden_layers": 28, "num_attention_heads": 16,
             "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 151936}
    parent = ctx_of(reduced, model=dense, counters={
        "engine.decode_path.plain": 10, "engine.decode_live_rows": 30})
    assert reader(name)(parent) is None
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
        assert value < limit / 2, (name, got)
    names = set(got["metric_names"])
    assert {"moe.held_pairs_per_token", "moe.load_imbalance",
            "moe.padding_share", "attn.window_read_share"} <= names
    assert "model_step.mfu" not in names
    assert "exaone_step.hbm_roofline" not in names       # a device metric


def test_dropped_window_mask_is_not_correct(tmp_path, monkeypatch):
    """The admission's prefill attends past the window."""
    from triton_dist_tpu.layers import tp_attn
    real = tp_attn._masked_softmax
    monkeypatch.setattr(tp_attn, "_masked_softmax",
                        lambda scores, off_b, kv_start, window=None:
                        real(scores, off_b, kv_start))
    got = rehearse(tmp_path, seed=91)
    assert got["correct"] is False, got


def test_ignored_selection_bias_is_not_correct(tmp_path, monkeypatch):
    """The router selects by its scores alone."""
    from triton_dist_tpu.ops import moe_utils
    real = moe_utils.sigmoid_topk_routing
    monkeypatch.setattr(moe_utils, "sigmoid_topk_routing",
                        lambda logits, bias, *a, **kw:
                        real(logits, bias * 0, *a, **kw))
    got = rehearse(tmp_path, seed=92)
    assert got["correct"] is False, got


@pytest.mark.parametrize("control", ["int8", "fp8"])
def test_control_in_lower_precision_is_not_correct(control):
    """The controls at a size a test can hold, through ``correct.check``:
    the reference's own greedy continuation stands in for a sound
    server's answer and reads 0; the reference computed in the lower
    precision and put in the program's place comes out ``correct:
    false`` under the rehearsal's limits."""
    from benchmark.harness import correct
    from benchmark.harness.builders import exaone
    from benchmark.reference import exaone_moe as ref
    cfg = dict(CFG)
    cfg.update(cfg["rehearsal"])
    model = exaone.model_dict(cfg)
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
