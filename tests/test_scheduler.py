"""Cross-request continuous batching (serving/scheduler.py, ISSUE 5).

Quick tier: the scheduler is pure Python orchestration over the proven
stream-session programs, and the xla-impl tiny model keeps every test
CPU-cheap. Covered here:

- equivalence: scheduler results are bit-identical (greedy) to
  per-request ``Engine.serve()`` for uniform, ragged, and over-batch
  workloads, including chunked prefill;
- fairness: a short request admitted while a long generation is
  mid-decode retires while the long one is still running, under ONE
  shared batch;
- backpressure: a full admission queue yields a structured
  ``queue_full`` reply and the server survives;
- observability: ``{"cmd": "metrics"}`` exposes queue_depth /
  batch_occupancy / ttft_ms / queue_wait_ms, and a trace dump from a
  loaded server shows admit/retire events interleaved;
- the ``gen_len`` clamp echo + counter, and the client ``timeout=``.

ISSUE 6 (paged-native scheduling + prefix caching) adds: greedy
bit-exactness with the prefix cache on vs off (shared / partial / no
overlap, uniform and ragged), oversubscribed pools running through the
shared-batch path, prefix + block-pool metrics through the metrics
command and tools/report.py, and an autouse leak audit asserting every
paged engine's block pool is fully returned after each scenario.

ISSUE 11 (mega decode in the shared batch) adds: greedy bit-identity
mega-vs-plain under ragged offsets, mid-decode admission/retirement,
oversubscribed paged pools, and prefix-cache warm hits; plus the
decode-path auto-selection policy unit tests (injected device.step.*
gauge values, both flip directions, the no-measurement default, and
the TDT_MEGA_AUTO opt-out).
"""

import json
import socket
import socketserver
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.models import DenseLLM, Engine, ModelConfig
from triton_dist_tpu.serving import (ChatClient, ModelServer, QueueFull,
                                     Scheduler, fanout)


@pytest.fixture()
def tiny(mesh8, key):
    cfg = ModelConfig(hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=8,
                      num_key_value_heads=8, head_dim=4, vocab_size=64,
                      max_position_embeddings=64, dtype=jnp.float32)
    model = DenseLLM(cfg, mesh=mesh8, axis="tp", impl="xla")
    return model, model.init(key)


@pytest.fixture()
def paged_tiny(mesh8, key):
    """xla-impl sp model on a (tp=1, sp=8) grid — the paged engine
    family, cheap enough for the quick tier."""
    from jax.sharding import Mesh
    devs = [d for d in mesh8.devices.flat]
    mesh = Mesh(np.array(devs).reshape(1, 8), ("tp", "sp"))
    cfg = ModelConfig(hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=16, vocab_size=64,
                      max_position_embeddings=64, dtype=jnp.float32)
    model = DenseLLM(cfg, mesh=mesh, axis="tp", sp_axis="sp",
                     impl="xla", fwd_mode="sp")
    return model, model.init(key)


#: Paged engines created this test session — the leak-audit fixture
#: below checks every one of them after each scenario.
_PAGED_ENGINES: list = []


@pytest.fixture(autouse=True)
def _block_pool_leak_audit():
    """ISSUE 6 satellite: after EVERY scenario in this file, each paged
    engine's block pool must be back to fully-returned state — zero
    active blocks, zero outstanding commitment, free + evictable
    covering the whole pool. A retired (or
    stop()-killed) request that strands blocks is a slow production
    OOM."""
    _PAGED_ENGINES.clear()
    yield
    for eng in _PAGED_ENGINES:
        a = eng.kv.block_audit()
        assert a["active"] == 0 and a["committed"] == 0, a
        assert a["free"] + a["evictable"] == a["total"], a
    _PAGED_ENGINES.clear()


def _engine(model, batch=2, max_seq=64):
    return Engine(model, batch=batch, max_seq=max_seq,
                  prefill_mode="xla_ar", decode_mode="gemm_ar")


def _paged_engine(model, batch=2, max_seq=64, page=4, slots=None,
                  prefix=True, decode_path=None):
    eng = Engine(model, batch=batch, max_seq=max_seq,
                 prefill_mode="sp", decode_mode="sp", paged=True,
                 page_size=page, prefix_cache=prefix,
                 kv_slots_per_dev=slots,
                 **({"decode_path": decode_path} if decode_path else {}))
    _PAGED_ENGINES.append(eng)
    return eng


def _solo_paged_golden(model, params, prompt, gen_len):
    """Golden for the sp-paged family: the plain tp engine on the same
    params (token-equal across families; accepts prompt lengths that
    don't divide the sp world)."""
    eng = Engine(model, batch=1, max_seq=64, prefill_mode="xla",
                 decode_mode="xla_ar")
    out = np.asarray(eng.serve(params, jnp.asarray([prompt], jnp.int32),
                               gen_len))[0].tolist()
    return out[len(prompt):]


def _solo(model, params, prompt, gen_len, stop=()):
    """Golden: the prompt served alone, trimmed to the exact-retire
    contract (generated tokens end at the first stop token)."""
    out = np.asarray(_engine(model, batch=1).serve(
        params, jnp.asarray([prompt], jnp.int32), gen_len,
        stop_tokens=stop))[0].tolist()
    gen = out[len(prompt):]
    for i, t in enumerate(gen):
        if t in set(stop):
            return gen[:i + 1]
    return gen


def _wait_until(pred, timeout=60.0, what="condition"):
    t0 = time.monotonic()
    while not pred():
        assert time.monotonic() - t0 < timeout, f"timed out on {what}"
        time.sleep(0.02)


# ---------------------------------------------------------------------------
# Equivalence: scheduler == per-request serve(), greedy.
# ---------------------------------------------------------------------------

def test_scheduler_matches_solo_serve(tiny):
    """Uniform, ragged, AND over-batch in one workload: 6 mixed-length
    prompts through a 2-row window, all submitted concurrently, each
    bit-identical to serving it alone."""
    model, params = tiny
    sched = Scheduler(_engine(model), params).start()
    try:
        prompts = [[1, 2, 3], [9, 8], [4, 5, 6, 7], [11], [23, 29],
                   [7, 7, 7]]
        reqs = [sched.submit(p, 5) for p in prompts]
        for p, r in zip(prompts, reqs):
            assert r.result(timeout=180) == _solo(model, params, p, 5)
    finally:
        sched.stop()


def test_admission_buckets_follow_the_prefill_row_split(tiny):
    """A row-sharded tp prefill (ag_rs) hands each rank bucket/world
    rows as its ring chunk, which the fused kernels slice only in whole
    8-row tiles (ops.common.ring_padded_rows): on 8 ranks the smallest
    bucket is 64, and every bucket splits into whole tiles. The
    replicated prefill (xla_ar) keeps the plain power-of-two buckets."""
    model, params = tiny
    sharded = Engine(model, batch=2, max_seq=256, prefill_mode="ag_rs",
                     decode_mode="gemm_ar").stream_session(params)
    assert [sharded._bucket(n) for n in (1, 5, 64, 65, 200)] == \
        [64, 64, 64, 128, 256]
    replicated = _engine(model).stream_session(params)
    assert [replicated._bucket(n) for n in (1, 5, 9, 40)] == [8, 8, 16, 64]


def test_scheduler_stop_tokens_exact_retire(tiny):
    """Per-request stop sets retire rows exactly at the stop token."""
    model, params = tiny
    probe = _solo(*tiny, [1, 2], 6)
    stop = (probe[1],)      # 2nd generated token of the first prompt
    sched = Scheduler(_engine(model), params).start()
    try:
        prompts = [[1, 2], [3, 4], [5, 6]]
        reqs = [sched.submit(p, 6, stop_tokens=stop) for p in prompts]
        for p, r in zip(prompts, reqs):
            want = _solo(model, params, p, 6, stop=stop)
            assert r.result(timeout=180) == want, (p, want)
    finally:
        sched.stop()


def test_scheduler_chunked_prefill_matches_solo(tiny):
    """Chunked admission (TDT_PREFILL_CHUNK path): a long prompt
    prefills in slices interleaved with decode steps and still decodes
    bit-identically; a second request rides along mid-prefill."""
    model, params = tiny
    eng = _engine(model)
    sched = Scheduler(eng, params, prefill_chunk=4).start()
    try:
        long_p = list(range(1, 15))          # 14 tokens → 4 chunks of 4
        short_p = [5, 9]
        r_long = sched.submit(long_p, 5)
        r_short = sched.submit(short_p, 5)
        assert r_long.result(timeout=180) == _solo(model, params,
                                                   long_p, 5)
        assert r_short.result(timeout=180) == _solo(model, params,
                                                    short_p, 5)
        assert eng._admit_chunk is not None  # the chunked path ran
    finally:
        sched.stop()


def test_server_scheduler_roundtrip_matches_solo(tiny):
    """The whole stack — socket protocol → scheduler → shared batch —
    returns per-request results equal to solo serving; the response
    echoes the effective gen_len."""
    model, params = tiny
    srv = ModelServer(_engine(model), params, port=0).start()
    try:
        prompts = [[1, 2, 3], [9, 8], [4, 5, 6, 7]]
        outs = fanout(srv.host, srv.port,
                      [{"prompt_ids": [p], "gen_len": 4}
                       for p in prompts], timeout=180)
        for p, o in zip(prompts, outs):
            assert o.get("gen_len") == 4, o
            assert o["tokens"][0] == _solo(model, params, p, 4)
        # multi-prompt request: one connection, rows still per-prompt
        c = ChatClient(srv.host, srv.port, timeout=180)
        r = c.generate_ids(prompts, gen_len=3)
        for p, row in zip(prompts, r["tokens"]):
            assert row == _solo(model, params, p, 3)
        c.close()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Fairness: no head-of-line blocking under one shared batch.
# ---------------------------------------------------------------------------

def test_short_request_retires_while_long_decodes(tiny):
    """ISSUE 5 acceptance: a short request admitted while a long
    generation is mid-decode completes while the long one is STILL
    decoding — the serialized-lock server could never do this."""
    model, params = tiny
    sched = Scheduler(_engine(model, batch=2), params).start()
    try:
        r_long = sched.submit([1, 2, 3], 55)
        # wait until the long generation is genuinely mid-decode
        _wait_until(lambda: len(r_long.tokens) >= 3, what="long decode")
        r_short = sched.submit([9, 8], 2)
        short_out = r_short.result(timeout=180)
        assert not r_long.done.is_set(), \
            "short request should retire while the long one decodes"
        assert short_out == _solo(model, params, [9, 8], 2)
        r_long.result(timeout=180)      # and the long one finishes too
    finally:
        sched.stop()


# ---------------------------------------------------------------------------
# Backpressure.
# ---------------------------------------------------------------------------

def test_scheduler_queue_full_raises(tiny):
    model, params = tiny
    sched = Scheduler(_engine(model, batch=1), params,
                      max_waiting=2).start()
    try:
        r_a = sched.submit([1, 2, 3], 50)
        # A must leave the queue (admitted into the one row) first so
        # the fill below is deterministic.
        _wait_until(lambda: sched.queue_depth() == 0, what="A admitted")
        r_b = sched.submit([4, 5], 4)           # queue slot 1
        r_c = sched.submit([6, 7], 4)           # queue slot 2 → full
        with pytest.raises(QueueFull):
            sched.submit([6], 2)
        # submit_many is atomic: a 2-prompt batch (which FITS capacity,
        # so it is retryable) into a full queue rejects BOTH — no
        # half-admitted client batch.
        with pytest.raises(QueueFull):
            sched.submit_many([[7], [8]], 2)
        # ... while a batch LARGER than capacity can never be admitted
        # and refuses as non-retryable ValueError instead.
        with pytest.raises(ValueError, match="split the batch"):
            sched.submit_many([[7], [8], [9]], 2)
        assert r_a.result(timeout=180) and r_b.result(timeout=180)
        assert r_c.result(timeout=180)
    finally:
        sched.stop()


def test_server_backpressure_structured_reply(tiny):
    """The protocol-level contract: a full queue answers a structured
    queue_full reply and the server keeps serving afterwards."""
    model, params = tiny
    srv = ModelServer(_engine(model, batch=1), params, port=0,
                      max_waiting=1).start()
    try:
        c = ChatClient(srv.host, srv.port, timeout=180)
        done: dict = {}

        def bg(name, prompt, gen):
            cc = ChatClient(srv.host, srv.port, timeout=180)
            done[name] = cc.generate_ids([prompt], gen_len=gen)
            cc.close()

        ta = threading.Thread(target=bg, args=("a", [1, 2, 3], 55),
                              daemon=True)
        ta.start()
        # wait until A occupies the row (metrics don't take any lock)
        _wait_until(lambda: c.request({"cmd": "metrics"})["metrics"]
                    ["gauges"].get("serving.batch_occupancy", 0) >= 1,
                    what="A occupying the batch")
        tb = threading.Thread(target=bg, args=("b", [4, 5], 40),
                              daemon=True)
        tb.start()
        _wait_until(lambda: c.request({"cmd": "metrics"})["metrics"]
                    ["gauges"].get("serving.queue_depth", 0) >= 1,
                    what="B queued")
        # The raw protocol reply is under test: opt out of the
        # client's sleep-and-retry-on-retry_after_ms (ISSUE 15).
        raw = ChatClient(srv.host, srv.port, timeout=180,
                         retry_shed=False)
        rej = raw.generate_ids([[6]], gen_len=2)
        raw.close()
        assert rej.get("type") == "queue_full", rej
        assert "max_waiting" in rej and "queue_depth" in rej
        # The backpressure hint rides the reply (rolling TPOT x queue
        # depth, clamped — docs/serving.md).
        assert isinstance(rej.get("retry_after_ms"), int)
        assert rej["retry_after_ms"] >= 25
        ta.join(timeout=180)
        tb.join(timeout=180)
        assert "tokens" in done["a"] and "tokens" in done["b"]
        ok = c.generate_ids([[5]], gen_len=2)   # server survives
        assert "tokens" in ok
        m = c.request({"cmd": "metrics"})["metrics"]
        assert m["counters"].get("server.backpressure_replies", 0) >= 1
        c.close()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Observability: metrics + trace acceptance.
# ---------------------------------------------------------------------------

def test_metrics_and_trace_show_batch_churn(tiny):
    """ISSUE 5 acceptance: metrics expose queue_depth /
    batch_occupancy / ttft_ms / queue_wait_ms, and a trace dump from a
    loaded server shows admit/retire instants interleaved — some
    request admitted between another's admit and retire."""
    model, params = tiny
    srv = ModelServer(_engine(model, batch=2), params, port=0).start()
    try:
        outs = fanout(srv.host, srv.port,
                      [{"prompt_ids": [[1 + i, 2 + i]], "gen_len": 6}
                       for i in range(5)], timeout=180)
        assert all("tokens" in o for o in outs), outs
        c = ChatClient(srv.host, srv.port, timeout=180)
        m = c.request({"cmd": "metrics"})["metrics"]
        assert "serving.queue_depth" in m["gauges"]
        assert "serving.batch_occupancy" in m["gauges"]
        assert m["histograms"]["serving.ttft_ms"]["count"] >= 5
        assert m["histograms"]["serving.queue_wait_ms"]["count"] >= 5
        assert m["counters"]["serving.admitted"] >= 5
        assert m["counters"]["serving.retired"] >= 5
        d = c.dump_trace(seconds=600)
        c.close()
        with open(d["dumped"]) as f:
            evs = json.load(f)["traceEvents"]
        admits = sorted((e["ts"], e["args"]["rid"]) for e in evs
                        if e["name"] == "serving.admit")
        retires = {e["args"]["rid"]: e["ts"] for e in evs
                   if e["name"] == "serving.retire"}
        assert len(admits) >= 5 and len(retires) >= 5
        # interleaving: some OTHER request was admitted inside another
        # request's admit→retire window (rows churn through the batch)
        assert any(ts_a < ts_b < retires[rid_a]
                   for ts_a, rid_a in admits
                   for ts_b, rid_b in admits
                   if rid_a != rid_b and rid_a in retires), \
            "no admission interleaved with a live request"
        # every admit instant carries the request's trace id
        tids = {e["args"].get("trace_id") for e in evs
                if e["name"] == "serving.admit"}
        assert all(tids) and len(tids) >= 5
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Satellites: gen_len clamp echo, client timeout, legacy path.
# ---------------------------------------------------------------------------

def test_gen_len_clamp_echo_and_counter(tiny):
    model, params = tiny
    srv = ModelServer(_engine(model, batch=1, max_seq=16), params,
                      port=0).start()
    try:
        c = ChatClient(srv.host, srv.port, timeout=180)
        r = c.generate_ids([[1, 2, 3]], gen_len=500)
        assert r["gen_len"] == 13            # max_seq 16 − prompt 3
        assert len(r["tokens"][0]) <= 13
        m = c.request({"cmd": "metrics"})["metrics"]
        assert m["counters"]["server.gen_len_clamped"] == 1
        r2 = c.generate_ids([[1, 2]], gen_len=4)   # unclamped echoes
        assert r2["gen_len"] == 4                  # the request as-is
        m = c.request({"cmd": "metrics"})["metrics"]
        assert m["counters"]["server.gen_len_clamped"] == 1
        c.close()
    finally:
        srv.stop()


def test_client_timeout_on_wedged_server():
    """A server that accepts but never answers must raise TimeoutError
    within the client timeout, not block forever (the satellite fix)."""
    class _Mute(socketserver.BaseRequestHandler):
        def handle(self):
            time.sleep(30)

    srv = socketserver.ThreadingTCPServer(("127.0.0.1", 0), _Mute)
    srv.daemon_threads = True
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        host, port = srv.server_address
        c = ChatClient(host, port, timeout=0.3)
        t0 = time.monotonic()
        with pytest.raises(TimeoutError):
            c.request({"prompt_ids": [[1]], "gen_len": 1})
        assert time.monotonic() - t0 < 5.0
        # per-call override on a fresh connection
        c2 = ChatClient(host, port)
        with pytest.raises(TimeoutError):
            c2.request({"cmd": "metrics"}, timeout=0.2)
    finally:
        srv.shutdown()
        srv.server_close()


def test_scheduler_stop_unblocks_waiters(tiny):
    model, params = tiny
    sched = Scheduler(_engine(model, batch=1), params).start()
    r = sched.submit([1, 2, 3], 60)
    _wait_until(lambda: len(r.tokens) >= 1, what="decode started")
    sched.stop()
    with pytest.raises(RuntimeError, match="scheduler stopped"):
        r.result(timeout=30)
    with pytest.raises(RuntimeError, match="not running"):
        sched.submit([1], 1)


def test_scheduler_invalid_requests_fail_fast(tiny):
    model, params = tiny
    sched = Scheduler(_engine(model, batch=1, max_seq=16), params).start()
    try:
        with pytest.raises(ValueError, match="non-empty"):
            sched.submit([], 4)
        with pytest.raises(ValueError, match="max_seq"):
            sched.submit(list(range(1, 15)), 10)
        r = sched.submit([1, 2], 0)          # gen_len 0: trivially done
        assert r.result(timeout=5) == []
        out = sched.generate([1, 2, 3], 3)   # scheduler still healthy
        assert out == _solo(model, params, [1, 2, 3], 3)
    finally:
        sched.stop()


def test_pump_death_unblocks_and_stops_accepting(tiny, monkeypatch):
    """A pump thread that dies (even during SESSION CONSTRUCTION — an
    oversubscribed paged pool is legal for plain serve() yet asserts
    in a stream session) must fail every waiter and flip the scheduler
    to not-running, not leave handlers blocked on result() forever
    (review finding)."""
    model, params = tiny
    eng = _engine(model, batch=1)
    monkeypatch.setattr(
        eng, "stream_session",
        lambda p: (_ for _ in ()).throw(RuntimeError("pool exhausted")))
    sched = Scheduler(eng, params).start()
    try:
        r = sched.submit([1, 2], 4)
    except RuntimeError:
        pass                    # pump already died — submit refused
    else:
        with pytest.raises(RuntimeError, match="scheduler"):
            r.result(timeout=30)
    _wait_until(lambda: not sched._running, what="pump marked dead")
    with pytest.raises(RuntimeError, match="not running"):
        sched.submit([1], 1)
    sched.stop()


def test_oversized_batch_is_not_retryable_queue_full(tiny):
    """A single request with more prompts than max_waiting can NEVER
    be admitted — it must fail as a non-retryable error, not a
    'retry later' queue_full reply (review finding)."""
    model, params = tiny
    srv = ModelServer(_engine(model, batch=1), params, port=0,
                      max_waiting=2).start()
    try:
        c = ChatClient(srv.host, srv.port, timeout=180)
        r = c.generate_ids([[1], [2], [3]], gen_len=2)
        assert "error" in r and r.get("type") != "queue_full", r
        assert "split the batch" in r["error"]
        ok = c.generate_ids([[1], [2]], gen_len=2)  # fits → served
        assert "tokens" in ok
        c.close()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Paged-native scheduling + cross-request prefix caching (ISSUE 6).
# ---------------------------------------------------------------------------

def test_paged_prefix_cache_bit_exact(paged_tiny):
    """Tentpole acceptance: greedy outputs are bit-identical with the
    prefix cache enabled vs disabled, across shared-, partial-, and
    no-overlap prompts of mixed (ragged) lengths — and both match the
    solo golden, so they can't be identically wrong."""
    model, params = paged_tiny
    pre = list(range(1, 9))                 # 8 tokens = 2 full pages
    prompts = [pre + [20],                  # full shared prefix
               pre + [30, 31],              # ... ragged length
               pre[:4] + [40, 41],          # partial overlap (1 page)
               [50, 51, 52],                # no overlap
               pre + [60]]                  # another full hit
    outs = {}
    for flag in (True, False):
        sched = Scheduler(_paged_engine(model, prefix=flag),
                          params).start()
        try:
            reqs = [sched.submit(p, 5) for p in prompts]
            outs[flag] = [r.result(timeout=180) for r in reqs]
        finally:
            sched.stop()
    assert outs[True] == outs[False]
    for p, row in zip(prompts, outs[True]):
        assert row == _solo_paged_golden(model, params, p, 5), p


def test_paged_prefix_cache_uniform_prompts_bit_exact(paged_tiny):
    """Same acceptance, uniform lengths: every prompt shares the full
    preamble and the warm admissions demonstrably skipped prefill."""
    model, params = paged_tiny
    pre = list(range(3, 11))
    prompts = [pre + [t] for t in (21, 22, 23, 24)]
    eng = _paged_engine(model, prefix=True)
    sched = Scheduler(eng, params).start()
    try:
        reqs = [sched.submit(p, 4) for p in prompts]
        got = [r.result(timeout=180) for r in reqs]
    finally:
        sched.stop()
    for p, row in zip(prompts, got):
        assert row == _solo_paged_golden(model, params, p, 4), p
    st = eng.kv.prefix.stats()
    assert st["hit_blocks"] >= 6, st     # requests 2..4 each hit 2 blocks


def test_oversubscribed_pool_runs_shared_batch(paged_tiny):
    """ISSUE 6 acceptance: a paged engine whose pool CANNOT hold every
    row (the engine the old auto-detect sent to the serialized lock)
    runs through the shared-batch scheduler path — more concurrent
    requests than whole-row capacity, correct results, no fallback."""
    model, params = paged_tiny
    # batch=3 rows x 2 blocks/dev whole-row = 6; the pool has 5 slots
    # (all usable — the sentinel page rides outside the pool) ->
    # whole-row streaming could hold at most 2 lanes and the OLD
    # session refused to start at all.
    eng = _paged_engine(model, batch=3, slots=5)
    srv = ModelServer(eng, params, port=0).start()
    try:
        assert srv.scheduler is not None   # auto-detect: no fallback
        prompts = [[2 * i + 1, 2 * i + 2] for i in range(5)]
        outs = fanout(srv.host, srv.port,
                      [{"prompt_ids": [p], "gen_len": 6}
                       for p in prompts], timeout=180)
        for p, o in zip(prompts, outs):
            assert o["tokens"][0] == _solo_paged_golden(
                model, params, p, 6), (p, o)
        c = ChatClient(srv.host, srv.port, timeout=180)
        m = c.request({"cmd": "metrics"})["metrics"]
        c.close()
        assert m["counters"]["serving.admitted"] >= 5
        # block-pool occupancy gauges ride the same snapshot
        assert "kv.blocks_free" in m["gauges"]
        assert "kv.blocks_active" in m["gauges"]
    finally:
        srv.stop()


def test_oversubscribed_requests_wait_not_die(paged_tiny):
    """Block-granular backpressure: when the pool is too tight for two
    concurrent generations, the second request WAITS for the first
    row's eager block release instead of failing — and a request that
    could never fit fails fast as a non-retryable error."""
    model, params = paged_tiny
    eng = _paged_engine(model, batch=2, slots=1)  # 1 block/dev
    sched = Scheduler(eng, params).start()
    try:
        # Each needs 1 block on device 0 -> strictly one at a time.
        reqs = [sched.submit([7 + i, 8], 2) for i in range(3)]
        for i, r in enumerate(reqs):
            got = r.result(timeout=180)
            assert got == _solo_paged_golden(model, params,
                                             [7 + i, 8], 2)
        with pytest.raises(ValueError, match="never fit"):
            sched.submit([1, 2, 3, 4, 5], 4)   # 2 blocks on device 0
    finally:
        sched.stop()


def test_admission_upload_failure_releases_blocks(paged_tiny,
                                                  monkeypatch):
    """A failure in the block-table device upload during paged
    admission must release the row's just-allocated blocks and leave
    the lane clean for the next admission (review regression: the
    upload sat OUTSIDE _admit_paged's rollback window, so it stranded
    the blocks and every later admission into that row tripped the
    already-holds-blocks assert)."""
    model, params = paged_tiny
    eng = _paged_engine(model, batch=2)
    sched = Scheduler(eng, params).start()
    try:
        # Warm: session construction + one clean admission/retire
        # cycle consume their block_table() calls before we arm.
        golden = _solo_paged_golden(model, params, [1, 2, 3], 2)
        assert sched.submit([1, 2, 3], 2).result(timeout=180) == golden
        orig, armed = eng.kv.block_table, {"left": 1}

        def flaky():
            if armed["left"]:
                armed["left"] -= 1
                raise RuntimeError("injected device upload failure")
            return orig()

        monkeypatch.setattr(eng.kv, "block_table", flaky)
        with pytest.raises(RuntimeError, match="injected"):
            sched.submit([1, 2, 3], 2).result(timeout=180)
        # The degraded row's blocks came back: same prompt admits into
        # the same lane and matches the golden (the autouse leak audit
        # re-checks the pool after teardown).
        assert sched.submit([1, 2, 3], 2).result(timeout=180) == golden
    finally:
        sched.stop()


def test_admission_failure_after_dispatch_restarts_session(paged_tiny,
                                                          monkeypatch):
    """The admission programs DONATE the session's caches, so one that
    fails after dispatch (device OOM, a runtime error surfacing at the
    first token) takes every row's K/V with it. That is the death of
    the session, not of one request: the admitted request and both
    occupants fail with an error that names the lost cache and its
    culprit, ``serving.pump_errors`` counts it, a fresh session serves
    the next request bit-identically, and no block stays stranded (the
    autouse leak audit). The silent version would degrade one request
    and let the next shared step die on "Array has been deleted"."""
    from triton_dist_tpu import obs
    model, params = paged_tiny
    eng = _paged_engine(model, batch=3)
    reg = obs.enable(obs.Registry())
    sched = Scheduler(eng, params).start()
    try:
        golden = _solo_paged_golden(model, params, [1, 2, 3], 2)
        assert sched.submit([1, 2, 3], 2).result(timeout=180) == golden
        real, calls = eng._admit, {"n": 0}

        def admit_then_fail(*args):
            out = real(*args)           # the program runs and donates
            calls["n"] += 1
            if calls["n"] == 3:
                raise RuntimeError("injected failure after dispatch")
            return out

        monkeypatch.setattr(eng, "_admit", admit_then_fail)
        # One pump turn admits all three: rows 0 and 1 are live when
        # the third admission fails.
        reqs = [sched.submit([7 + i, 8, 9], 48) for i in range(3)]
        for r in reqs:
            with pytest.raises(RuntimeError,
                               match="KV cache was lost.*injected"):
                r.result(timeout=180)
        counters = reg.snapshot()["counters"]
        assert counters["serving.pump_errors"] == 1
        assert counters.get("serving.admit_errors", 0) == 0
        assert sched.submit([1, 2, 3], 2).result(timeout=180) == golden
    finally:
        sched.stop()
        obs.disable()


def test_paged_prefix_metrics_and_report(paged_tiny):
    """ISSUE 6 acceptance: serving.prefix_hit_rate /
    serving.prefill_tokens_saved and the kv.* block gauges are visible
    through {"cmd": "metrics"} and render in tools/report.py."""
    model, params = paged_tiny
    eng = _paged_engine(model, batch=2)
    srv = ModelServer(eng, params, port=0).start()
    try:
        pre = list(range(1, 9))
        outs = fanout(srv.host, srv.port,
                      [{"prompt_ids": [pre + [30 + i]], "gen_len": 3}
                       for i in range(4)], timeout=180)
        assert all("tokens" in o for o in outs), outs
        c = ChatClient(srv.host, srv.port, timeout=180)
        m = c.request({"cmd": "metrics"})["metrics"]
        c.close()
        assert m["counters"]["serving.prefill_tokens_saved"] >= 24
        assert m["gauges"]["serving.prefix_hit_rate"] > 0
        assert m["gauges"]["kv.blocks_cached"] >= 2  # preamble resident
        from triton_dist_tpu.tools.report import render_telemetry
        md = render_telemetry(m)
        assert "kv block pool" in md and "kv.blocks_free" in md
        assert "serving.prefix_hit_rate" in md
    finally:
        srv.stop()


def test_server_serialized_path_still_works(tiny):
    """scheduler=False keeps the pre-scheduler serialized route (now
    an explicit override only — mega engines schedule) intact, clamp
    echo included."""
    model, params = tiny
    srv = ModelServer(_engine(model, batch=1, max_seq=16), params,
                      port=0, scheduler=False).start()
    try:
        assert srv.scheduler is None
        c = ChatClient(srv.host, srv.port, timeout=180)
        r = c.generate_ids([[1, 2, 3]], gen_len=4)
        assert r["tokens"][0] == _solo(model, params, [1, 2, 3], 4)
        assert r["gen_len"] == 4
        r2 = c.generate_ids([[1, 2, 3]], gen_len=500)
        assert r2["gen_len"] == 13
        c.close()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# ISSUE 8: the serving SLO observatory through a live scheduler.
# ---------------------------------------------------------------------------

def test_response_timing_waterfall_sums_to_wall_time(tiny):
    """Acceptance: the attribution waterfall's segments partition the
    request's measured wall time — segment sum == total exactly (one
    clock, by construction), total within 5 ms of the server-measured
    latency (handler↔pump handoff is the only slack)."""
    model, params = tiny
    srv = ModelServer(_engine(model), params, port=0).start()
    try:
        c = ChatClient(srv.host, srv.port, timeout=180)
        c.generate_ids([[1, 2, 3]], gen_len=3)       # warm compiles
        r = c.generate_ids([[4, 5, 6]], gen_len=5)
        c.close()
        (t,) = r["timing"]
        seg = t["segments"]
        assert set(seg) == {"queue_wait_ms", "prefill_ms", "decode_ms"}
        assert sum(seg.values()) == pytest.approx(t["total_ms"],
                                                  abs=0.01)
        assert abs(t["total_ms"] - r["latency_ms"]) < 5.0, (t, r)
        assert t["tokens"] == len(r["tokens"][0]) == 5
        assert t["prompt_tokens"] == 3
        assert t["tpot_ms"] == pytest.approx(
            seg["decode_ms"] / 4, abs=0.01)
        assert t["trace_id"] == r["trace_id"]
    finally:
        srv.stop()


def test_request_stats_ring_newest_first(tiny):
    model, params = tiny
    srv = ModelServer(_engine(model), params, port=0).start()
    try:
        c = ChatClient(srv.host, srv.port, timeout=180)
        for i in range(3):
            c.generate_ids([[1 + i, 2, 3]], gen_len=2)
        stats = c.request({"cmd": "request_stats", "last": 2})
        all_stats = c.request({"cmd": "request_stats"})
        c.close()
        assert len(stats["requests"]) == 2
        assert len(all_stats["requests"]) == 3
        rids = [r["rid"] for r in all_stats["requests"]]
        assert rids == sorted(rids, reverse=True)    # newest first
        for r in all_stats["requests"]:
            assert sum(r["segments"].values()) == pytest.approx(
                r["total_ms"], abs=0.01)
    finally:
        srv.stop()


def test_waterfall_reports_prefix_savings(paged_tiny):
    """A warm shared-prefix admission's waterfall shows the skipped
    tokens (cached_tokens > 0) — the prefix-cache savings leg of the
    attribution story."""
    model, params = paged_tiny
    eng = _paged_engine(model, batch=2)
    srv = ModelServer(eng, params, port=0).start()
    try:
        pre = list(range(1, 9))                      # two full pages
        c = ChatClient(srv.host, srv.port, timeout=180)
        c.generate_ids([pre + [30]], gen_len=2)      # indexes preamble
        r = c.generate_ids([pre + [31]], gen_len=2)  # warm hit
        c.close()
        (t,) = r["timing"]
        assert t["cached_tokens"] >= 8, t
        assert t["prompt_tokens"] == 9
    finally:
        srv.stop()


def test_latency_regression_breaches_and_arms_recorder(tiny,
                                                       monkeypatch):
    """Acceptance: a latency regression (every TTFT 'violates' a
    deliberately impossible threshold — the CPU-tier stand-in for a
    fault-injected spike) drives a fast+slow burn breach through the
    LIVE scheduler, arms the flight recorder exactly once, and the
    dump validates as a Perfetto artifact."""
    import json as _json
    monkeypatch.setenv("TDT_SLO_TTFT_P99_MS", "0.001")
    from triton_dist_tpu.obs import flight, trace
    model, params = tiny
    srv = ModelServer(_engine(model), params, port=0).start()
    try:
        assert trace.enabled()                       # server default
        c = ChatClient(srv.host, srv.port, timeout=180)
        before = c.request({"cmd": "metrics"})["metrics"]
        b0 = before["counters"].get("serving.slo_breaches", 0)
        # Enough violating requests to clear the slow-window sample
        # floor (TDT_SLO_MIN_SAMPLES): a sustained regression, not a
        # single-request blip (which must NOT page — see below).
        outs = fanout(srv.host, srv.port,
                      [{"prompt_ids": [[1 + i, 2, 3]], "gen_len": 3}
                       for i in range(12)], timeout=180)
        assert all("tokens" in o for o in outs), outs
        # The metrics scrape forces a fresh evaluation.
        m = c.request({"cmd": "metrics"})["metrics"]
        assert m["counters"]["serving.slo_breaches"] == b0 + 1
        assert m["gauges"]["serving.slo_breached.ttft_p99"] == 1
        assert m["gauges"]["serving.slo_burn.ttft_p99"] > 1
        rec = flight.last_record()
        assert rec is not None and rec["reason"] == "slo_ttft_p99"
        dumps0 = rec["count"]
        # Sustained breach: another request + scrape, no second dump
        # (transition-gated), no second breach count.
        c.generate_ids([[4, 5, 6]], gen_len=3)
        m2 = c.request({"cmd": "metrics"})["metrics"]
        c.close()
        assert m2["counters"]["serving.slo_breaches"] == b0 + 1
        assert flight.last_record()["count"] == dumps0
        with open(rec["path"]) as f:
            chrome = _json.load(f)
        from triton_dist_tpu.tools import trace_export
        errors, _ = trace_export.validate(chrome)
        assert errors == [], errors
    finally:
        srv.stop()


def test_slo_no_false_positive_under_default_targets(tiny):
    """Default (generous) targets must never breach on healthy
    quick-tier traffic — the false-positive half of the acceptance
    bar."""
    model, params = tiny
    srv = ModelServer(_engine(model), params, port=0).start()
    try:
        c = ChatClient(srv.host, srv.port, timeout=180)
        b0 = c.request({"cmd": "metrics"})["metrics"]["counters"].get(
            "serving.slo_breaches", 0)    # registry is process-global
        outs = fanout(srv.host, srv.port,
                      [{"prompt_ids": [[1 + i, 2]], "gen_len": 4}
                       for i in range(4)], timeout=180)
        assert all("tokens" in o for o in outs), outs
        m = c.request({"cmd": "metrics"})["metrics"]
        c.close()
        assert m["counters"].get("serving.slo_breaches", 0) == b0
        for k, v in m["gauges"].items():
            if k.startswith("serving.slo_breached."):
                assert v == 0, k
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# ISSUE 11: mega decode in the shared batch + decode-path auto-selection.
# ---------------------------------------------------------------------------

def test_scheduler_mega_matches_plain_ragged_overbatch(tiny):
    """Tentpole acceptance (dense family): the mega one-program step
    pumped by the scheduler is greedily bit-identical to the plain
    path under ragged per-row offsets AND mid-decode
    admission/retirement — 6 mixed-length prompts through a 2-row
    window, so rows retire and re-admit while others decode."""
    model, params = tiny
    prompts = [[1, 2, 3], [9, 8], [4, 5, 6, 7], [11], [23, 29],
               [7, 7, 7]]
    outs = {}
    for path in ("mega", "plain"):
        eng = Engine(model, batch=2, max_seq=64, prefill_mode="xla_ar",
                     decode_mode="gemm_ar", decode_path=path)
        sched = Scheduler(eng, params).start()
        try:
            reqs = [sched.submit(p, 5) for p in prompts]
            outs[path] = [r.result(timeout=180) for r in reqs]
        finally:
            sched.stop()
    assert outs["mega"] == outs["plain"]
    for p, row in zip(prompts, outs["mega"]):
        assert row == _solo(model, params, p, 5), p


def test_scheduler_mega_paged_prefix_matches_plain(paged_tiny):
    """Tentpole acceptance (paged family): Engine(use_mega=True,
    paged=True) serves through the scheduler — per-row offsets against
    the paged pool's table lanes, prefix-cache WARM hits included —
    bit-identical to the plain paged scheduler path and to the solo
    golden."""
    model, params = paged_tiny
    pre = list(range(1, 9))                 # 8 tokens = 2 full pages
    prompts = [pre + [20],                  # cold (indexes the preamble)
               pre + [30, 31],              # warm full-prefix hit, ragged
               pre[:4] + [40, 41],          # partial overlap
               [50, 51, 52],                # no overlap
               pre + [60]]                  # another warm hit
    outs = {}
    hits = {}
    for path in ("mega", "plain"):
        eng = _paged_engine(model, decode_path=path)
        sched = Scheduler(eng, params).start()
        try:
            reqs = [sched.submit(p, 5) for p in prompts]
            outs[path] = [r.result(timeout=180) for r in reqs]
        finally:
            sched.stop()
        hits[path] = eng.kv.prefix.stats()["hit_blocks"]
    assert outs["mega"] == outs["plain"]
    assert hits["mega"] >= 4, hits          # the warm hits really hit
    for p, row in zip(prompts, outs["mega"]):
        assert row == _solo_paged_golden(model, params, p, 5), p


def test_scheduler_mega_oversubscribed_pool(paged_tiny):
    """The mega step streams an OVERSUBSCRIBED pool like the plain one:
    more concurrent requests than whole-row capacity, block-granular
    admission waits, correct results (the leak audit re-checks the
    pool after teardown)."""
    model, params = paged_tiny
    eng = _paged_engine(model, batch=3, slots=5, decode_path="mega")
    sched = Scheduler(eng, params).start()
    try:
        prompts = [[2 * i + 1, 2 * i + 2] for i in range(5)]
        reqs = [sched.submit(p, 6) for p in prompts]
        for p, r in zip(prompts, reqs):
            assert r.result(timeout=180) == _solo_paged_golden(
                model, params, p, 6), p
    finally:
        sched.stop()


def test_decode_path_auto_policy_unit(monkeypatch):
    """Auto-selection consumes MEASURED device.step.* gauges: both
    flip directions, the no-measurement default, provenance counters,
    and the TDT_MEGA_AUTO opt-out."""
    from triton_dist_tpu import obs
    from triton_dist_tpu.models.engine import DecodePathPolicy
    reg = obs.enable(obs.Registry())
    try:
        pol = DecodePathPolicy()
        # No measurement → the chip-prior default (mega), counted as
        # provenance "default".
        assert pol.decide() == "mega"
        # One-sided measurement is NOT a comparison → still default.
        reg.gauge("device.step.mega.total_ms").set(5.0)
        assert pol.decide() == "mega"
        snap = reg.snapshot()["counters"]
        assert snap["engine.decode_path.auto_source.default"] == 2
        # Both measured: slower mega → plain ...
        reg.gauge("device.step.plain.total_ms").set(2.0)
        assert pol.decide() == "plain"
        assert reg.snapshot()["gauges"]["serving.mega_selected"] == 0.0
        # ... and the other flip direction.
        reg.gauge("device.step.plain.total_ms").set(9.0)
        assert pol.decide() == "mega"
        snap = reg.snapshot()
        assert snap["counters"]["engine.decode_path.auto_mega"] == 3
        assert snap["counters"]["engine.decode_path.auto_plain"] == 1
        assert snap["counters"][
            "engine.decode_path.auto_source.measured"] == 2
        assert snap["gauges"]["serving.mega_selected"] == 1.0
        # Per-WINDOW normalization: a 4-iteration breach capture's
        # unioned plain total (9 ms / 4 windows = 2.25/step) must beat
        # a single-window 5 ms mega step — comparing raw unions would
        # pick mega.
        reg.gauge("device.step.plain.windows").set(4.0)
        assert pol.decide() == "plain"
        reg.gauge("device.step.plain.windows").set(1.0)
        # Probe beat: every PROBE_EVERY-th SAMPLABLE decision runs the
        # OTHER path (provenance "probe") so a live sampler can
        # measure or refresh it — without it, only the winning path's
        # gauge ever updates and the policy could never correct
        # itself. Doubly measurability-gated: no probes without a live
        # devprof sampler, and none for non-samplable decisions
        # (serve() resolved outside the pump would run a whole
        # generation on the probed path with nothing able to capture
        # it).
        kinds = [pol.decide(samplable=True)
                 for _ in range(DecodePathPolicy.PROBE_EVERY)]
        assert "plain" not in kinds, "probe fired with no sampler"
        from triton_dist_tpu.obs import devprof
        sampler = devprof.PumpSampler(every=10 ** 9, sync=True)
        kinds = [pol.decide()         # non-samplable: still no probe
                 for _ in range(DecodePathPolicy.PROBE_EVERY)]
        assert "plain" not in kinds, "probe fired for serve()-style call"
        kinds = [pol.decide(samplable=True)
                 for _ in range(DecodePathPolicy.PROBE_EVERY)]
        assert "plain" in kinds, "no probe fired in a full period"
        assert reg.snapshot()["counters"][
            "engine.decode_path.auto_source.probe"] >= 1
        del sampler
        # Env opt-out: auto resolves to plain regardless of gauges.
        monkeypatch.setenv("TDT_MEGA_AUTO", "0")
        off = DecodePathPolicy()
        reg.gauge("device.step.plain.total_ms").set(999.0)
        assert off.decide() == "plain"
        assert reg.snapshot()["counters"][
            "engine.decode_path.auto_source.env_off"] == 1
    finally:
        obs.disable()


def test_scheduler_auto_decode_path_serves(tiny):
    """Engine(decode_path="auto") through the scheduler: decisions are
    taken per pump iteration (provenance counted) and results stay
    bit-identical to solo serving whatever the policy picks."""
    from triton_dist_tpu import obs
    model, params = tiny
    eng = Engine(model, batch=2, max_seq=64, prefill_mode="xla_ar",
                 decode_mode="gemm_ar", decode_path="auto")
    reg = obs.enable(obs.Registry())
    try:
        sched = Scheduler(eng, params).start()
        try:
            prompts = [[1, 2, 3], [9, 8], [4, 5, 6, 7]]
            reqs = [sched.submit(p, 4) for p in prompts]
            got = [r.result(timeout=180) for r in reqs]
        finally:
            sched.stop()
        for p, row in zip(prompts, got):
            assert row == _solo(model, params, p, 4), p
        snap = reg.snapshot()["counters"]
        decisions = (snap.get("engine.decode_path.auto_mega", 0)
                     + snap.get("engine.decode_path.auto_plain", 0))
        assert decisions >= 1
        sources = [k for k in snap
                   if k.startswith("engine.decode_path.auto_source.")]
        assert sources, snap
    finally:
        obs.disable()


# ---------------------------------------------------------------------------
# ISSUE 13: speculative decoding in the shared batch.
# ---------------------------------------------------------------------------

def _spec_cfg(k=4, **kw):
    from triton_dist_tpu.models.spec import SpecConfig
    return SpecConfig(k=k, **kw)


def test_scheduler_spec_matches_plain_ragged_overbatch(tiny):
    """Tentpole acceptance (dense family): spec-on greedy outputs are
    bit-identical to spec-off across ragged mixed-length prompts AND
    mid-decode admission/retirement — 7 prompts through a 2-row
    window, so rows retire and re-admit while others burst; the
    repetitive prompt exercises real multi-token accepts."""
    model, params = tiny
    prompts = [[1, 2, 3], [9, 8], [4, 5, 6, 7], [11], [23, 29],
               [7, 7, 7], [5, 6, 5, 6, 5, 6, 5]]
    outs = {}
    for tag, spec in (("on", _spec_cfg()), ("off", None)):
        eng = Engine(model, batch=2, max_seq=64, prefill_mode="xla_ar",
                     decode_mode="gemm_ar", spec=spec)
        sched = Scheduler(eng, params).start()
        try:
            reqs = [sched.submit(p, 9) for p in prompts]
            outs[tag] = [r.result(timeout=180) for r in reqs]
        finally:
            sched.stop()
    assert outs["on"] == outs["off"]
    for p, row in zip(prompts, outs["on"]):
        assert row == _solo(model, params, p, 9), p


def test_scheduler_spec_paged_prefix_matches_plain(paged_tiny):
    """Tentpole acceptance (paged family): spec bursts against the
    paged pool's table lanes — prefix-cache WARM hits included — are
    bit-identical to spec-off and to the solo golden; the autouse leak
    audit re-checks both pools after teardown (multi-token commits +
    rejected-tail rollbacks must strand nothing)."""
    model, params = paged_tiny
    pre = list(range(1, 9))                 # 8 tokens = 2 full pages
    prompts = [pre + [20],                  # cold (indexes the preamble)
               pre + [30, 31],              # warm full-prefix hit, ragged
               pre[:4] + [40, 41],          # partial overlap
               [50, 51, 52],                # no overlap
               pre + [60]]                  # another warm hit
    outs = {}
    hits = {}
    for tag, spec in (("on", _spec_cfg()), ("off", None)):
        eng = Engine(model, batch=2, max_seq=64, prefill_mode="sp",
                     decode_mode="sp", paged=True, page_size=4,
                     prefix_cache=True, spec=spec)
        _PAGED_ENGINES.append(eng)
        sched = Scheduler(eng, params).start()
        try:
            reqs = [sched.submit(p, 6) for p in prompts]
            outs[tag] = [r.result(timeout=180) for r in reqs]
        finally:
            sched.stop()
        hits[tag] = eng.kv.prefix.stats()["hit_blocks"]
    assert outs["on"] == outs["off"]
    assert hits["on"] >= 4, hits            # the warm hits really hit
    for p, row in zip(prompts, outs["on"]):
        assert row == _solo_paged_golden(model, params, p, 6), p


def test_scheduler_spec_oversubscribed_pool(paged_tiny):
    """Spec bursts stream an OVERSUBSCRIBED pool: multi-block commits
    and rejected-tail rollbacks against a pool too small for every
    row, block-granular admission waits, correct results (the leak
    audit re-checks the pool after teardown)."""
    model, params = paged_tiny
    eng = Engine(model, batch=3, max_seq=64, prefill_mode="sp",
                 decode_mode="sp", paged=True, page_size=4,
                 kv_slots_per_dev=5, spec=_spec_cfg())
    _PAGED_ENGINES.append(eng)
    sched = Scheduler(eng, params).start()
    try:
        prompts = [[2 * i + 1, 2 * i + 2] for i in range(5)]
        reqs = [sched.submit(p, 6) for p in prompts]
        for p, r in zip(prompts, reqs):
            assert r.result(timeout=180) == _solo_paged_golden(
                model, params, p, 6), p
    finally:
        sched.stop()


def test_scheduler_spec_stop_tokens_retire_mid_burst(tiny):
    """A stop token landing MID-burst retires the row at that token
    and discards the burst's tail — the per-request stop contract is
    unchanged by variable tokens-per-step."""
    model, params = tiny
    probe = _solo(*tiny, [5, 6, 5, 6, 5, 6, 5], 9)
    stop = (probe[3],)          # 4th generated token
    prompts = [[5, 6, 5, 6, 5, 6, 5], [1, 2, 3], [9, 8]]
    outs = {}
    for tag, spec in (("on", _spec_cfg()), ("off", None)):
        eng = Engine(model, batch=2, max_seq=64, prefill_mode="xla_ar",
                     decode_mode="gemm_ar", spec=spec)
        sched = Scheduler(eng, params).start()
        try:
            reqs = [sched.submit(p, 9, stop_tokens=stop)
                    for p in prompts]
            outs[tag] = [r.result(timeout=180) for r in reqs]
        finally:
            sched.stop()
    assert outs["on"] == outs["off"]
    for p, row in zip(prompts, outs["on"]):
        assert row == _solo(model, params, p, 9, stop=stop), p


def test_spec_metrics_and_waterfall_through_server(tiny):
    """ISSUE 13 acceptance: serving.spec_accept_rate /
    serving.spec_tokens_per_step are visible through
    {"cmd": "metrics"}, the request waterfalls carry draft/verify
    segments through "timing" and request_stats, top.py renders the
    accept-rate gauge, and report.py's serving section carries the
    spec rows."""
    model, params = tiny
    eng = Engine(model, batch=2, max_seq=64, prefill_mode="xla_ar",
                 decode_mode="gemm_ar", spec=_spec_cfg())
    srv = ModelServer(eng, params, port=0).start()
    try:
        c = ChatClient(srv.host, srv.port, timeout=180)
        c.generate_ids([[5, 6, 5, 6, 5, 6, 5]], gen_len=9)  # warm
        r = c.generate_ids([[5, 6, 5, 6, 5, 6, 5]], gen_len=9)
        m = c.request({"cmd": "metrics"})["metrics"]
        stats = c.request({"cmd": "request_stats", "last": 1})
        c.close()
        assert m["counters"]["serving.spec_steps"] >= 1
        assert 0.0 <= m["gauges"]["serving.spec_accept_rate"] <= 1.0
        assert m["gauges"]["serving.spec_tokens_per_step"] >= 1.0
        assert "engine.spec_verify_ms" in m["histograms"]
        (t,) = r["timing"]
        assert t["spec"]["verify_ms"] >= 0.0
        assert t["spec"]["draft_ms"] >= 0.0
        assert stats["requests"][0]["spec"]["verify_ms"] >= 0.0
        # segments still partition exactly (spec is sub-attribution)
        assert sum(t["segments"].values()) == pytest.approx(
            t["total_ms"], abs=0.01)
        from triton_dist_tpu.tools.report import render_telemetry
        from triton_dist_tpu.tools.top import render
        assert "serving.spec_accept_rate" in render_telemetry(m)
        assert "accept" in render(m)
    finally:
        srv.stop()


def test_metrics_catalog_wellformed(tiny):
    """CI satellite: every SLO metric in the documented catalog
    appears in a live {"cmd": "metrics"} snapshot after real
    traffic."""
    model, params = tiny
    srv = ModelServer(_engine(model), params, port=0).start()
    try:
        # Real traffic populates every rolling window (tpot needs a
        # multi-token request; pump/queue_wait/ttft come for free).
        outs = fanout(srv.host, srv.port,
                      [{"prompt_ids": [[1 + i, 2, 3]], "gen_len": 4}
                       for i in range(3)], timeout=180)
        assert all("tokens" in o for o in outs), outs
        from triton_dist_tpu.obs import slo
        c = ChatClient(srv.host, srv.port, timeout=180)
        m = c.request({"cmd": "metrics"})["metrics"]
        c.close()
        for name in slo.gauge_catalog():
            assert name in m["gauges"], name
        assert "serving.pump_iteration_ms" in m["histograms"]
    finally:
        srv.stop()
