"""Disaggregated prefill/decode over KV-block streaming (ISSUE 18).

Quick tier. Covered here:

- the ACCEPTANCE scenario: a prefill replica streams a multi-block
  prompt's KV to a decode replica, the decode-side admission is
  bit-identical to unified greedy serving, and a WARM handoff (decode
  prefix cache already holding the chain) ships strictly fewer blocks
  than a cold one;
- both transport tiers: in-process (symm-mem ship path) and the
  length-prefixed wire verbs (``KVStreamSender`` over a real socket);
- the sever acceptance: ``chaos.sever_stream`` kills the prefill
  replica mid-stream → the router re-places on the decode replica,
  ZERO client errors, and the decode side counts the severed stream
  when purging its stale staging entry;
- the kvstream protocol model: clean schedules verify for every
  (n_blocks, held) shape, and the three mutation classes fail with
  DISTINCT finding codes (deadlock / signal_wait_imbalance /
  coverage);
- two-tier routing: ``parse_tiers``, health-advertised tier pickup,
  live ``router_retier`` under drain (sticky across health polls);
- satellites: ``tdt-check --changed`` selects the disagg watches, the
  regress gate (``check_disagg_wellformed``), and the dashboard
  surfaces (fleet_top tier column, report disagg section).
"""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.models import DenseLLM, Engine, ModelConfig
from triton_dist_tpu.serving import ChatClient, ModelServer, RouterServer
from triton_dist_tpu.serving import disagg as disagg_mod
from triton_dist_tpu.serving import kv_stream
from triton_dist_tpu.testing import chaos

PAGE = 4


@pytest.fixture()
def paged_tiny(mesh8, key):
    """xla-impl sp model on a (tp=1, sp=8) grid — the paged engine
    family (same recipe as tests/test_scheduler.py)."""
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


def _paged_server(tiny, rid, **kw):
    model, params = tiny
    eng = Engine(model, batch=2, max_seq=64, prefill_mode="sp",
                 decode_mode="sp", paged=True, page_size=PAGE,
                 prefix_cache=True)
    return ModelServer(eng, params, port=0, registry="private",
                       replica_id=rid, **kw).start()


def _golden(tiny, prompt, gen_len):
    """Unified greedy golden: the plain tp engine on the same params
    (token-equal across engine families, pinned by test_scheduler)."""
    model, params = tiny
    eng = Engine(model, batch=1, max_seq=64, prefill_mode="xla",
                 decode_mode="xla_ar")
    out = np.asarray(eng.serve(params, jnp.asarray([prompt], jnp.int32),
                               gen_len))[0].tolist()
    return out[len(prompt):]


def _counter(server, name):
    return server.registry.snapshot()["counters"].get(name, 0)


def _wait(pred, timeout=30.0, what="condition"):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        v = pred()
        if v:
            return v
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {what}")


# ---------------------------------------------------------------------------
# Schedule helpers (the functions the model checker executes).
# ---------------------------------------------------------------------------

def test_schedule_helper_geometry():
    assert kv_stream.block_span(12, 4) == 3
    assert kv_stream.block_span(13, 4) == 4
    assert list(kv_stream.needed_blocks(3, 0)) == [0, 1, 2]
    assert list(kv_stream.needed_blocks(3, 2)) == [2]
    assert list(kv_stream.needed_blocks(3, 9)) == []
    assert kv_stream.ship_schedule(3, 0) == [(0, 0), (1, 1), (2, 2)]
    assert kv_stream.ship_schedule(3, 2) == [(2, 0)]
    assert kv_stream.ship_schedule(3, 3) == []


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    layers = [(rng.standard_normal((2, 4, 16), dtype=np.float32),
               rng.standard_normal((2, 4, 16), dtype=np.float32))]
    payload = kv_stream.pack_block(layers)
    back = kv_stream.unpack_block(payload, 1, (2, 4, 16))
    np.testing.assert_array_equal(back[0][0], layers[0][0])
    np.testing.assert_array_equal(back[0][1], layers[0][1])
    with pytest.raises(ValueError):
        kv_stream.unpack_block(payload[:-4], 1, (2, 4, 16))


# ---------------------------------------------------------------------------
# Acceptance: e2e handoff, bit-identical, warm dedup.
# ---------------------------------------------------------------------------

def test_disagg_e2e_bit_identical_and_warm_dedup(paged_tiny):
    """Cold handoff streams every block and the decode replica's
    decode-only admission reproduces unified greedy exactly; a warm
    handoff of the same prompt ships STRICTLY fewer blocks (the
    content-addressed dedup)."""
    prompt = list(range(1, 13))            # 3 full pages
    gen = 5
    want = _golden(paged_tiny, prompt, gen)
    p = _paged_server(paged_tiny, "dz-p", tier="prefill")
    d = _paged_server(paged_tiny, "dz-d", tier="decode")
    try:
        c = ChatClient(p.host, p.port, timeout=120)
        req = {"cmd": "disagg_prefill", "prompt_ids": prompt,
               "gen_len": gen,
               "decode_endpoint": f"{d.host}:{d.port}"}
        cold = c.request(dict(req))
        assert cold["tokens"][0] == want
        assert cold["disagg"]["decode"] == f"{d.host}:{d.port}"
        cold_shipped = _counter(p, "disagg.blocks_shipped")
        assert cold_shipped == 3           # every block streamed
        assert _counter(p, "disagg.handoffs") == 1
        assert _counter(p, "disagg.fallbacks") == 0
        assert _counter(d, "disagg.decode_admits") == 1
        assert _counter(d, "disagg.offers") == 1
        assert _counter(p, "disagg.ship_inproc") == 3

        warm = c.request(dict(req))
        assert warm["tokens"][0] == want
        warm_shipped = (_counter(p, "disagg.blocks_shipped")
                        - cold_shipped)
        # The decode replica's prefix cache now holds the chain: only
        # the always-ship tail block moves. Near-zero bytes, strictly
        # fewer than cold — the tentpole's dedup property.
        assert 0 < warm_shipped < cold_shipped
        assert warm_shipped == 1
        assert _counter(d, "disagg.blocks_deduped") == 2
        c.close()
    finally:
        p.stop()
        d.stop()


def test_disagg_wire_tier_bit_identical(paged_tiny):
    """With the in-process registration removed, the handoff takes the
    length-prefixed WIRE verbs over a real socket — and still matches
    unified greedy."""
    prompt = list(range(3, 11))            # 2 full pages
    gen = 4
    want = _golden(paged_tiny, prompt, gen)
    p = _paged_server(paged_tiny, "dw-p", tier="prefill")
    d = _paged_server(paged_tiny, "dw-d", tier="decode")
    try:
        disagg_mod.unregister_inproc(f"{d.host}:{d.port}")
        c = ChatClient(p.host, p.port, timeout=120)
        out = c.request({"cmd": "disagg_prefill", "prompt_ids": prompt,
                         "gen_len": gen,
                         "decode_endpoint": f"{d.host}:{d.port}"})
        assert out["tokens"][0] == want
        assert _counter(p, "disagg.ship_wire") == 2
        assert _counter(p, "disagg.ship_inproc") == 0
        assert _counter(d, "disagg.decode_admits") == 1
        assert _counter(d, "disagg.stream_bytes") > 0
        c.close()
    finally:
        p.stop()
        d.stop()


def test_disagg_short_prompt_no_handoff(paged_tiny):
    """gen_len == 1 (and stop-on-first) answers from the prefill
    replica — no stream, no decode involvement."""
    prompt = [1, 2, 3, 4]
    want = _golden(paged_tiny, prompt, 1)
    p = _paged_server(paged_tiny, "ds-p", tier="prefill")
    d = _paged_server(paged_tiny, "ds-d", tier="decode")
    try:
        c = ChatClient(p.host, p.port, timeout=120)
        out = c.request({"cmd": "disagg_prefill", "prompt_ids": prompt,
                         "gen_len": 1,
                         "decode_endpoint": f"{d.host}:{d.port}"})
        assert out["tokens"][0] == want
        assert _counter(p, "disagg.handoffs") == 0
        assert _counter(d, "disagg.offers") == 0
        c.close()
    finally:
        p.stop()
        d.stop()


def test_disagg_dead_decode_falls_back_locally(paged_tiny):
    """A dead decode endpoint NEVER surfaces to the client: the
    fallback contract re-serves the full request on the prefill
    replica (its prefix cache is still warm)."""
    prompt = list(range(1, 13))
    gen = 4
    want = _golden(paged_tiny, prompt, gen)
    p = _paged_server(paged_tiny, "df-p", tier="prefill")
    try:
        c = ChatClient(p.host, p.port, timeout=120)
        out = c.request({"cmd": "disagg_prefill", "prompt_ids": prompt,
                         "gen_len": gen,
                         "decode_endpoint": "127.0.0.1:9"})
        assert out["tokens"][0] == want
        assert out["disagg"] == {"fallback": True}
        assert _counter(p, "disagg.fallbacks") == 1
        assert _counter(p, "disagg.handoffs") == 0
        c.close()
    finally:
        p.stop()


# ---------------------------------------------------------------------------
# Two-tier routing.
# ---------------------------------------------------------------------------

def test_parse_tiers():
    from triton_dist_tpu.serving.router import parse_tiers
    assert parse_tiers("") == {}
    got = parse_tiers("prefill=127.0.0.1:81;decode=127.0.0.1:82")
    assert got == {("127.0.0.1", 81): "prefill",
                   ("127.0.0.1", 82): "decode"}
    with pytest.raises(ValueError):
        parse_tiers("turbo=127.0.0.1:81")
    with pytest.raises(ValueError):
        parse_tiers("prefill127.0.0.1:81")


def test_router_disagg_dispatch_and_retier(paged_tiny):
    """A tiered router sends single-prompt generates down the
    disagg_prefill path (prefill pool by TTFT burn, decode pool by
    TPOT burn), tokens bit-identical to unified greedy; a live
    ``router_retier`` survives subsequent health polls (the replica
    advertises its static tier, the override must not flap back)."""
    prompt = list(range(1, 13))
    gen = 4
    want = _golden(paged_tiny, prompt, gen)
    p = _paged_server(paged_tiny, "rt-p", tier="prefill")
    d = _paged_server(paged_tiny, "rt-d", tier="decode")
    eps = [(p.host, p.port), (d.host, d.port)]
    r = RouterServer(eps, registry="private", poll_s=0.05,
                     fleet_kwargs={"stale_s_": 0.5, "down_s_": 1.5,
                                   "timeout_s": 5.0}).start()
    try:
        # Tier pickup is health-advertised: wait for the poll.
        _wait(lambda: {row["tier"] for row in r.status()["replicas"]}
              == {"prefill", "decode"}, what="tier pickup")
        c = ChatClient(r.host, r.port, timeout=120)
        got = c.generate_ids([prompt], gen_len=gen)
        assert got["tokens"][0] == want
        assert got.get("disagg_route") or got.get("disagg")
        st = r.status()
        assert st["counters"].get("router.disagg_dispatches") == 1
        assert _counter(p, "disagg.handoffs") == 1
        assert _counter(d, "disagg.decode_admits") == 1

        # Live retier: decode → prefill under drain; sticky across
        # polls even though the replica still advertises "decode".
        resp = c.request({"cmd": "router_retier",
                          "endpoint": f"{d.host}:{d.port}",
                          "tier": "prefill"})
        assert resp["retiered"] == f"{d.host}:{d.port}"
        assert resp["tier"] == "prefill"
        time.sleep(0.2)                    # several poll cycles
        tiers = {row["replica_id"]: row["tier"]
                 for row in r.status()["replicas"]}
        assert tiers["rt-d"] == "prefill"
        assert st["counters"].get("router.retiers", 0) == 0  # pre-call
        assert r.status()["counters"]["router.retiers"] == 1

        # With no decode pool left, routing degrades to unified
        # placement — still correct tokens.
        got2 = c.generate_ids([prompt], gen_len=gen)
        assert got2["tokens"][0] == want
        c.close()
    finally:
        r.stop()
        p.stop()
        d.stop()


# ---------------------------------------------------------------------------
# Acceptance: sever mid-stream, zero client errors.
# ---------------------------------------------------------------------------

def test_sever_stream_zero_client_errors(paged_tiny, monkeypatch):
    """chaos.sever_stream kills the prefill replica after the first
    shipped block. The router's dispatch dies on the severed socket,
    tiered placement yields to the unified loop, and the DECODE
    replica serves the request in full — the client sees correct
    tokens, never an error. The decode side's half-received staging
    entry is purged as ``disagg.streams_severed`` on its next offer."""
    monkeypatch.setenv("TDT_KVSTREAM_STALE_S", "1")
    prompt = list(range(1, 13))
    gen = 4
    want = _golden(paged_tiny, prompt, gen)
    p = _paged_server(paged_tiny, "sv-p", tier="prefill")
    d = _paged_server(paged_tiny, "sv-d", tier="decode")
    eps = [(p.host, p.port), (d.host, d.port)]
    r = RouterServer(eps, registry="private", poll_s=0.05,
                     fleet_kwargs={"stale_s_": 0.5, "down_s_": 1.5,
                                   "timeout_s": 5.0}).start()
    try:
        _wait(lambda: {row["tier"] for row in r.status()["replicas"]}
              == {"prefill", "decode"}, what="tier pickup")
        with chaos.sever_stream(p, after_blocks=1) as cut:
            c = ChatClient(r.host, r.port, timeout=120)
            got = c.generate_ids([prompt], gen_len=gen)
            assert cut.fired.is_set()
            assert cut.blocks == 1
        # Zero client errors: the answer is the unified greedy tokens,
        # served by the surviving replica.
        assert got["tokens"][0] == want
        assert "error" not in got
        st = r.status()
        assert st["counters"].get("router.disagg_errors", 0) >= 1
        assert st["counters"].get("router.disagg_dispatches", 0) == 0
        # The decode side holds a half-received handoff; its next
        # offer purges the stale entry and counts the severed stream.
        assert len(d.disagg.staging) == 1
        time.sleep(1.1)                    # > TDT_KVSTREAM_STALE_S
        from triton_dist_tpu import obs
        with obs.scoped_registry(d.registry):
            d.disagg.handle("kv_offer",
                            {"handoff_id": "probe", "hashes": [],
                             "n_blocks": 1})
        assert _counter(d, "disagg.streams_severed") == 1
        c.close()
    finally:
        r.stop()
        p.stop()
        d.stop()


# ---------------------------------------------------------------------------
# Protocol model: clean verify + DISTINCT mutation codes.
# ---------------------------------------------------------------------------

def test_kvstream_model_clean():
    from triton_dist_tpu.analysis import kvstream_model
    assert kvstream_model.verify_kvstream() == []


def test_kvstream_mutations_distinct_codes():
    """Each mutation class fails with its OWN finding code — dropped
    signal deadlocks, double-ship leaves the semaphore unbalanced,
    dedup dropping a needed block breaks coverage. Pairwise-distinct
    signatures, so a regression names its failure class."""
    from triton_dist_tpu.analysis import kvstream_model as km
    from triton_dist_tpu.analysis.protocol_model import check_trace
    t = km.handoff_trace(4, 1)

    dropped = {v.code for v in check_trace(km.drop_signal(t))}
    doubled = {v.code for v in check_trace(km.double_ship(t))}
    deduped = {v.code for v in check_trace(km.dedup_drop_needed(4, 1))}

    assert "kvstream.deadlock" in dropped
    assert doubled == {"kvstream.signal_wait_imbalance"}
    assert deduped == {"kvstream.coverage"}
    # Signatures are pairwise distinct: coverage-only, imbalance-only,
    # and deadlock (absent from both others).
    assert "kvstream.deadlock" not in doubled | deduped
    assert "kvstream.coverage" not in dropped | doubled
    assert len({frozenset(dropped), frozenset(doubled),
                frozenset(deduped)}) == 3


def test_kvstream_claimed_and_changed_selection():
    """lint_protocol claims serving/kv_stream.py for kvstream-protocol
    (path-keyed CLAIM), and ``tdt-check --changed`` on any of the
    three disagg files selects the protocol pass plus the metric /
    annotation watches that pin them."""
    from triton_dist_tpu.analysis import select_passes_for
    from triton_dist_tpu.analysis.lint_protocol import CLAIMS, run
    assert CLAIMS["serving/kv_stream.py"] == "kvstream-protocol"
    assert run(None) == []                 # the claim verifies
    for f in ("triton_dist_tpu/serving/kv_stream.py",
              "triton_dist_tpu/serving/disagg.py",
              "triton_dist_tpu/analysis/kvstream_model.py"):
        sel = set(select_passes_for([f]))
        assert "kvstream-protocol" in sel, f
    sel = set(select_passes_for(["triton_dist_tpu/serving/disagg.py"]))
    assert {"metric-catalog", "annotation-coverage"} <= sel


# ---------------------------------------------------------------------------
# Satellites: dashboards.
# ---------------------------------------------------------------------------

def test_fleet_top_tier_column(paged_tiny):
    from triton_dist_tpu.obs.fleet import FleetView
    from triton_dist_tpu.tools import fleet_top
    p = _paged_server(paged_tiny, "ft-p", tier="prefill")
    try:
        view = FleetView([(p.host, p.port)])
        screen = fleet_top.render({"replicas": view.poll(),
                                   "merged": None})
        assert "tier" in screen.splitlines()[2]
        assert "prefill" in screen
    finally:
        p.stop()


def test_report_disagg_section():
    from triton_dist_tpu.tools.report import render_disagg
    snap = {"counters": {"disagg.handoffs": 2,
                         "disagg.blocks_offered": 6,
                         "disagg.blocks_deduped": 3},
            "histograms": {"disagg.handoff_ms": {
                "count": 2, "sum": 30.0, "min": 10.0, "max": 20.0,
                "buckets": [[16.0, 1], [32.0, 2]]}}}
    out = render_disagg(snap)
    assert "#### disagg" in out
    assert "disagg.handoff_ms" in out
    assert "dedup ratio | 0.5" in out
    assert render_disagg({"counters": {}}) == ""
