"""Per-op shape-sweep microbenchmarks.

The reference ends every op test with a perf loop over shapes
(test/nvidia/test_ag_gemm.py:72-197: correctness then `perf_func` +
`group_profile` per (M, N, K)); this is that harness as a standalone
tool. Each case checks correctness against the op's XLA golden first —
a wrong kernel's throughput is meaningless — then times both paths over
chained windows (``runtime.utils.perf_func_chained``). Every shape is
failure-isolated (a VMEM/compile failure emits an error row and the
sweep continues) and rows are written as they finish, so a crash late
in an expensive TPU session cannot discard earlier results.

Usage:
    python -m triton_dist_tpu.tools.bench_ops [--op ag_gemm]
        [--json out.jsonl]

On CPU hosts the sweep runs interpret-mode (tiny shapes, correctness
spot-check of the harness itself); real numbers need the TPU.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np


def _init_mesh():
    import jax
    from jax.sharding import Mesh
    devices = jax.devices()
    return Mesh(np.array(devices), ("tp",)), len(devices)


def _is_tpu():
    from triton_dist_tpu.runtime.platform import is_tpu
    return is_tpu()


def _time(step, x0):
    from triton_dist_tpu.runtime.utils import perf_func_chained
    # CPU interpret-mode exists only to prove the harness runs; keep the
    # chains short there (each step re-runs the Pallas interpreter).
    iters = (8, 24) if _is_tpu() else (1, 3)
    return perf_func_chained(step, x0, iters)


def _emit(row, out):
    out.write(json.dumps(row) + "\n")
    out.flush()


def _sweep_gemm_family(op_name, mesh, world, shapes, out):
    """Shared sweep for the collective-matmul ops: ag_gemm (row-sharded
    A, column-sharded B) and gemm_rs (col-sharded A, row-sharded B).
    The chain fold is CHEAP (scaled slice tiled back to the input
    shape) so the timed step is dominated by the op under test, and the
    (M/w, N) gemm_rs output is tiled back up so `x = step(x)` chains at
    any world size."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from triton_dist_tpu.runtime.utils import assert_allclose

    if op_name == "ag_gemm":
        from triton_dist_tpu.ops.allgather_gemm import (
            ag_gemm as op, create_ag_gemm_context as mk_ctx)
        a_spec, b_spec = P("tp"), P(None, "tp")
    else:
        from triton_dist_tpu.ops.gemm_reduce_scatter import (
            create_gemm_rs_context as mk_ctx, gemm_rs as op)
        a_spec, b_spec = P(None, "tp"), P("tp")

    for (m, k, n) in shapes:
        row = {"op": op_name, "m": m, "k": k, "n": n}
        try:
            ctx = mk_ctx(mesh, "tp")
            a0 = jax.device_put(
                jax.random.normal(jax.random.PRNGKey(0), (m, k),
                                  jnp.float32).astype(jnp.bfloat16),
                NamedSharding(mesh, a_spec))
            b = jax.device_put(
                (jax.random.normal(jax.random.PRNGKey(1), (k, n),
                                   jnp.float32) / 8).astype(jnp.bfloat16),
                NamedSharding(mesh, b_spec))
            assert_allclose(op(a0, b, ctx, impl="pallas"),
                            op(a0, b, ctx, impl="xla"),
                            rtol=3e-2, atol=3e-2)

            def mk(impl):
                @jax.jit
                def step(a):
                    c = op(a, b, ctx, impl=impl)
                    # cheap fold back to (m, k): scaled slice, tiled up
                    sl = (c[:, :k] if c.shape[1] >= k else
                          jnp.tile(c, (1, -(-k // c.shape[1])))[:, :k])
                    reps = -(-m // sl.shape[0])
                    return (jnp.tile(sl, (reps, 1))[:m]
                            * jnp.asarray(2 ** -4, jnp.bfloat16))
                return step

            ms_p, ms_x = _time(mk("pallas"), a0), _time(mk("xla"), a0)
            flops = 2 * m * k * n
            row.update({
                "pallas_ms": round(ms_p, 4), "xla_ms": round(ms_x, 4),
                "tflops_per_chip": round(
                    flops / world / (ms_p * 1e-3) / 1e12, 2),
                "vs_xla": round(ms_x / ms_p, 4)})
        except Exception as e:  # noqa: BLE001 — per-shape isolation
            row["error"] = repr(e)[:200]
        _emit(row, out)


def sweep_ag_gemm(mesh, world, shapes, out):
    _sweep_gemm_family("ag_gemm", mesh, world, shapes, out)


def sweep_gemm_rs(mesh, world, shapes, out):
    _sweep_gemm_family("gemm_rs", mesh, world, shapes, out)


def sweep_flash_decode(mesh, world, shapes, out):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from triton_dist_tpu.ops.flash_decode import (
        create_flash_decode_context, gqa_fwd_batch_decode)
    from triton_dist_tpu.runtime.utils import assert_allclose

    for (b, hq, hkv, d, t) in shapes:
        row = {"op": "flash_decode", "b": b, "hq": hq, "hkv": hkv,
               "d": d, "t": t}
        try:
            ctx = create_flash_decode_context(mesh, "tp", variant="tiled",
                                              t_blk=min(512, t // world))
            q0 = jax.random.normal(jax.random.PRNGKey(0), (b, hq, d),
                                   jnp.float32).astype(jnp.bfloat16)
            sh = NamedSharding(mesh, P(None, "tp"))
            kc = jax.device_put(jax.random.normal(
                jax.random.PRNGKey(1), (b, t, hkv, d), jnp.float32
            ).astype(jnp.bfloat16), sh)
            vc = jax.device_put(jax.random.normal(
                jax.random.PRNGKey(2), (b, t, hkv, d), jnp.float32
            ).astype(jnp.bfloat16), sh)
            n = jnp.int32(t - 1)
            assert_allclose(
                gqa_fwd_batch_decode(q0, kc, vc, n, ctx, impl="pallas"),
                gqa_fwd_batch_decode(q0, kc, vc, n, ctx, impl="xla"),
                rtol=3e-2, atol=3e-2)

            def mk(impl):
                @jax.jit
                def step(q):
                    o = gqa_fwd_batch_decode(q, kc, vc, n, ctx, impl=impl)
                    return (o.astype(jnp.float32) * 0.5 + 0.25
                            ).astype(q.dtype)
                return step

            ms_p, ms_x = _time(mk("pallas"), q0), _time(mk("xla"), q0)
            row.update({"pallas_ms": round(ms_p, 4),
                        "xla_ms": round(ms_x, 4),
                        "vs_xla": round(ms_x / ms_p, 4)})
        except Exception as e:  # noqa: BLE001 — per-shape isolation
            row["error"] = repr(e)[:200]
        _emit(row, out)


# ---------------------------------------------------------------------------
# Regression gate (--regress): compare *_vs_xla ratios against the
# checked-in floors in BASELINE.json and exit nonzero on a drop.
# ---------------------------------------------------------------------------

def _default_baseline_path() -> str:
    import os
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "BASELINE.json")


def load_floors(baseline_path: str, tier: str) -> dict:
    """Floor dict for ``tier`` ("tpu" | "cpu") from BASELINE.json's
    ``regression_floors``. The cpu tier is deliberately lax (near-zero
    floors): a CPU smoke asserts the harness runs end to end and the
    keys exist, not interpret-mode throughput."""
    with open(baseline_path) as f:
        floors = json.load(f).get("regression_floors", {})
    if tier not in floors:
        raise SystemExit(
            f"BASELINE.json regression_floors has no {tier!r} tier "
            f"(found {sorted(floors)})")
    return {k: v for k, v in floors[tier].items()
            if not k.startswith("_")}


def check_regression(extras: dict, floors: dict) -> list[str]:
    """Machine-check a bench run's ratios against the floors.

    Returns failure strings (empty = pass). A missing or non-numeric
    key fails — that is how the CPU smoke asserts the harness produced
    every metric end to end — and a non-null ``baseline_anomaly``
    fails outright: when the same-matmul XLA baselines disagree, every
    vs_xla ratio in the run is untrustworthy (docs/perf.md), so a
    "pass" against floors would be meaningless.
    """
    fails = []
    for key, floor in sorted(floors.items()):
        val = extras.get(key)
        if not isinstance(val, (int, float)):
            fails.append(f"{key}: missing (floor {floor})")
        elif float(val) < float(floor):
            fails.append(f"{key}: {val} < floor {floor}")
    anom = extras.get("baseline_anomaly")
    if anom:
        fails.append(f"baseline_anomaly is set - ratios untrustworthy: "
                     f"{anom}")
    return fails


#: Rolling-window serving percentiles a bench run's extras must carry
#: once it produced serving numbers (ISSUE 8): lifetime-histogram
#: percentiles hide a fresh regression under hours of good samples, so
#: the gate pins the extras to the WINDOWED gauges.
SERVING_ROLLING_KEYS = (
    "serving_rolling_ttft_p50_ms", "serving_rolling_ttft_p99_ms",
    "serving_rolling_tpot_p50_ms", "serving_rolling_tpot_p99_ms",
)


#: Fused-family bench parts that must publish MEASURED overlap
#: evidence (ISSUE 10): once a part ran (its `<part>_pallas_ms` /
#: fused-ms key exists), its extras must carry either a numeric
#: `<part>_overlap_pct_measured` (chip, world>1) or an explicit
#: marker — `<part>_overlap_requires_chip` (no comm events in the
#: profiled window) or `<part>_profile_error` / `_profile_unattributed`
#: (the capture path failed, recorded rather than silently absent).
#: (part, ran-sentinel-key) pairs.
OVERLAP_MEASURED_PARTS = (
    ("ag_gemm", "ag_gemm_pallas_ms"),
    ("gemm_rs", "gemm_rs_pallas_ms"),
    ("gemm_ar", "gemm_ar_pallas_ms"),
    ("tp_mlp", "tp_mlp_fused_ms"),
)


def check_overlap_measured_wellformed(extras: dict) -> list[str]:
    """Failure strings when a fused-family part ran without leaving
    measured-overlap evidence, or left a malformed value. The measured
    number is the device-timeline tier of the overlap accounting
    (docs/perf.md): a part publishing neither the number nor an
    explicit marker would let the next chip window report modeled
    numbers as if they were measured again."""
    fails = []
    for part, ran_key in OVERLAP_MEASURED_PARTS:
        if ran_key not in extras:
            continue          # part did not run this time
        val = extras.get(f"{part}_overlap_pct_measured")
        if val is not None:
            if not isinstance(val, (int, float)) \
                    or isinstance(val, bool) \
                    or not 0.0 <= float(val) <= 100.0:
                fails.append(f"{part}_overlap_pct_measured: malformed "
                             f"value {val!r} (want 0..100)")
            continue
        if not (extras.get(f"{part}_overlap_requires_chip")
                or extras.get(f"{part}_profile_error")
                or extras.get(f"{part}_profile_unattributed")):
            fails.append(
                f"{part}: ran but published neither "
                f"{part}_overlap_pct_measured nor an explicit "
                f"overlap_requires_chip / profile_error marker")
    return fails


def load_measured_overlap_floors(baseline_path: str, tier: str) -> dict:
    """Per-tier floors for `*_overlap_pct_measured` from BASELINE.json
    ``measured_overlap_floors`` (absent → empty). Deliberately
    generous: the hook exists so the NEXT chip window's measured
    numbers are machine-compared, not so today's 0% chip evidence
    fails retroactively."""
    with open(baseline_path) as f:
        floors = json.load(f).get("measured_overlap_floors", {})
    return {k: v for k, v in floors.get(tier, {}).items()
            if not k.startswith("_")}


def check_measured_overlap_floors(extras: dict, floors: dict) \
        -> list[str]:
    """Compare `*_overlap_pct_measured` values that EXIST against the
    tier floors (a CPU run's explicit requires-chip marker passes the
    wellformedness check instead; a present-but-below value fails)."""
    fails = []
    for key, floor in sorted(floors.items()):
        val = extras.get(key)
        if isinstance(val, (int, float)) and not isinstance(val, bool) \
                and float(val) < float(floor):
            fails.append(f"{key}: {val} < measured-overlap floor "
                         f"{floor}")
    return fails


def check_serving_wellformed(extras: dict) -> list[str]:
    """Failure strings when a run that measured serving throughput is
    missing its rolling-window TTFT/TPOT percentiles (empty when the
    serving part did not run — kernel-only sweeps pass untouched — or
    when the run recorded the explicit ``TDT_SLO=0`` opt-out)."""
    if "serving_tokens_per_s" not in extras:
        return []
    if extras.get("serving_rolling_disabled"):
        return []
    return [f"{k}: missing/non-numeric (serving extras must carry "
            f"rolling-window percentiles)"
            for k in SERVING_ROLLING_KEYS
            if not isinstance(extras.get(k), (int, float))
            or isinstance(extras.get(k), bool)]


def check_mega_serving_wellformed(extras: dict) -> list[str]:
    """Failure strings when the serving_mega part ran (its tokens/s
    key exists) without publishing a well-formed
    ``serving_mega_vs_plain`` ratio (ISSUE 11): the mega-in-scheduler
    number is the composition evidence ROADMAP item 1 asks for, and a
    run that silently dropped it would let the next chip window claim
    the two subsystems compose without a machine-readable ratio.
    Empty when the part did not run."""
    if "serving_mega_tokens_per_s" not in extras:
        return []
    v = extras.get("serving_mega_vs_plain")
    if not isinstance(v, (int, float)) or isinstance(v, bool) \
            or float(v) <= 0.0:
        return [f"serving_mega_vs_plain: missing/malformed ({v!r}) — "
                f"the serving_mega part ran but published no "
                f"mega-vs-plain scheduler ratio"]
    return []


def check_spec_serving_wellformed(extras: dict) -> list[str]:
    """Failure strings when the serving_spec part ran (its tokens/s
    key exists) without publishing a well-formed
    ``serving_spec_vs_plain`` ratio and accept-rate evidence
    (ISSUE 13): the spec-on-vs-off scheduler ratio is the acceptance
    bar, and the accept rate is what explains it — a run that
    silently dropped either would let a drafter regression hide
    behind a stale floor pass. Empty when the part did not run."""
    if "serving_spec_tokens_per_s" not in extras:
        return []
    fails = []
    v = extras.get("serving_spec_vs_plain")
    if not isinstance(v, (int, float)) or isinstance(v, bool) \
            or float(v) <= 0.0:
        fails.append(
            f"serving_spec_vs_plain: missing/malformed ({v!r}) — the "
            f"serving_spec part ran but published no spec-vs-plain "
            f"scheduler ratio")
    r = extras.get("serving_spec_accept_rate")
    if not isinstance(r, (int, float)) or isinstance(r, bool) \
            or not 0.0 <= float(r) <= 1.0:
        fails.append(
            f"serving_spec_accept_rate: missing/malformed ({r!r}) — "
            f"want a rate in [0, 1]")
    return fails


def check_fleet_wellformed(extras: dict) -> list[str]:
    """Failure strings when the serving_fleet part ran (its tokens/s
    key exists) without leaving well-formed fleet evidence
    (ISSUE 14): the two-replica-vs-one ratio must be present and
    positive, the per-replica rows must exist (at least two replica
    ids — a "fleet" of one would fake the scale-out number), no
    replica may have been ``down`` after the timed window, EVERY
    replica must have retired rows during the window (a replica whose
    pump died mid-window still answers health from its handler
    threads, so liveness alone cannot catch it — its retired-delta
    can), and no request in either timed leg may have errored (a
    fanout half-landing on a dead replica would otherwise publish a
    fleet tokens/s that is really a single-replica number). Empty
    when the part did not run."""
    if "serving_fleet_tokens_per_s" not in extras:
        return []
    fails = []
    v = extras.get("serving_fleet_vs_single")
    if not isinstance(v, (int, float)) or isinstance(v, bool) \
            or float(v) <= 0.0:
        fails.append(
            f"serving_fleet_vs_single: missing/malformed ({v!r}) — "
            f"the serving_fleet part ran but published no "
            f"fleet-vs-single ratio")
    ids = extras.get("serving_fleet_replica_ids")
    if not isinstance(ids, (list, tuple)) or len(ids) < 2 \
            or len(set(ids)) != len(ids):
        fails.append(
            f"serving_fleet_replica_ids: want >= 2 distinct replica "
            f"rows, got {ids!r}")
    down = extras.get("serving_fleet_down_replicas")
    if not isinstance(down, (int, float)) or isinstance(down, bool):
        fails.append(
            f"serving_fleet_down_replicas: missing/malformed "
            f"({down!r})")
    elif down:
        fails.append(
            f"serving_fleet_down_replicas: {down} replica(s) were not "
            f"live during the timed window — the fleet tokens/s is "
            f"not a 2-replica number")
    retired = extras.get("serving_fleet_replica_retired")
    if not isinstance(retired, (list, tuple)) or len(retired) < 2:
        fails.append(
            f"serving_fleet_replica_retired: want >= 2 per-replica "
            f"retired-deltas, got {retired!r}")
    elif not all(isinstance(r, (int, float))
                 and not isinstance(r, bool) and r > 0
                 for r in retired):
        fails.append(
            f"serving_fleet_replica_retired: every replica must have "
            f"retired rows in the timed window, got {retired!r} — a "
            f"dead-pump replica served nothing")
    for key in ("serving_fleet_error_count",
                "serving_fleet_single_error_count"):
        n = extras.get(key)
        if not isinstance(n, (int, float)) or isinstance(n, bool):
            fails.append(f"{key}: missing/malformed ({n!r})")
        elif n:
            fails.append(
                f"{key}: {n} request(s) errored in the timed window — "
                f"the tokens/s numbers are not comparable")
    return fails


#: Slack on the down-detection deadline: "down" is DEFINED as
#: last-good-scrape age exceeding the down threshold, so detection can
#: never land meaningfully under it — what the gate must catch is a
#: router that missed the death by a poll period or more, not the
#: sub-second scrape/poll lag inherent to the mechanism.
DOWN_DETECT_SLACK_S = 2.0


def check_router_wellformed(extras: dict) -> list[str]:
    """Failure strings when the serving_router part ran (its tokens/s
    key exists) without leaving well-formed fault-tolerance evidence
    (ISSUE 15). The kill window is the part's whole point, so when
    the part ran its kill keys are REQUIRED:

    - ``serving_router_vs_direct`` present and positive (router
      overhead vs client-side round-robin on the same fleet);
    - ``serving_router_kill_client_errors`` == 0 — killing one of
      three replicas mid-window must cost ZERO client-visible
      failures (the acceptance bar);
    - ``serving_router_failovers`` ≥ 1 — at least one request was
      actually re-dispatched (zero would mean the kill window missed
      every in-flight request and proved nothing);
    - ``serving_router_down_detect_s`` ≤ ``serving_router_down_s`` +
      :data:`DOWN_DETECT_SLACK_S` (the configured
      TDT_FLEET_DOWN_S-style age, plus the scrape/poll lag the
      mechanism cannot avoid) — the router noticed the death within
      its own threshold.

    Empty when the part did not run."""
    if "serving_router_tokens_per_s" not in extras:
        return []
    fails = []
    v = extras.get("serving_router_vs_direct")
    if not isinstance(v, (int, float)) or isinstance(v, bool) \
            or float(v) <= 0.0:
        fails.append(
            f"serving_router_vs_direct: missing/malformed ({v!r}) — "
            f"the serving_router part ran but published no "
            f"router-vs-direct ratio")
    errs = extras.get("serving_router_kill_client_errors")
    if not isinstance(errs, (int, float)) or isinstance(errs, bool):
        fails.append(f"serving_router_kill_client_errors: "
                     f"missing/malformed ({errs!r})")
    elif errs:
        fails.append(
            f"serving_router_kill_client_errors: {errs} client-"
            f"visible failure(s) during the kill window — the router "
            f"did not absorb the replica death")
    fo = extras.get("serving_router_failovers")
    if not isinstance(fo, (int, float)) or isinstance(fo, bool) \
            or fo < 1:
        fails.append(
            f"serving_router_failovers: want >= 1 recorded failover "
            f"in the kill window, got {fo!r} — zero means no request "
            f"was in flight on the victim and the window proved "
            f"nothing")
    det = extras.get("serving_router_down_detect_s")
    down_s = extras.get("serving_router_down_s")
    if not isinstance(det, (int, float)) or isinstance(det, bool) \
            or not isinstance(down_s, (int, float)) \
            or isinstance(down_s, bool):
        fails.append(
            f"serving_router_down_detect_s/serving_router_down_s: "
            f"missing/malformed ({det!r}/{down_s!r})")
    elif det > down_s + DOWN_DETECT_SLACK_S:
        fails.append(
            f"serving_router_down_detect_s: {det} > configured down "
            f"age {down_s} + {DOWN_DETECT_SLACK_S}s slack — the "
            f"router missed its detection deadline")
    return fails


def check_history_wellformed(extras: dict) -> list[str]:
    """Failure strings when the serving_history part ran (its
    tokens/s key exists) without leaving well-formed history-plane
    evidence (ISSUE 16):

    - ``serving_history_on_vs_off`` present and positive (the
      sampler-on vs sampler-off throughput ratio the BASELINE.json
      cpu floor gates — this check guards SHAPE, the floor guards
      magnitude);
    - ``serving_history_ticks`` ≥ 1 — the 20 Hz sampler must have
      actually ticked during the on-leg (zero would mean the ratio
      priced nothing);
    - ``serving_history_series`` ≥ 1 — at least one series was
      recorded and shipped back through ``{"cmd": "history"}`` (the
      pump publishes queue/occupancy gauges every working iteration,
      so an empty snapshot means the verb or the sampler is broken).

    Empty when the part did not run."""
    if "serving_history_tokens_per_s" not in extras:
        return []
    fails = []
    v = extras.get("serving_history_on_vs_off")
    if not isinstance(v, (int, float)) or isinstance(v, bool) \
            or float(v) <= 0.0:
        fails.append(
            f"serving_history_on_vs_off: missing/malformed ({v!r}) — "
            f"the serving_history part ran but published no "
            f"on-vs-off ratio")
    ticks = extras.get("serving_history_ticks")
    if not isinstance(ticks, (int, float)) or isinstance(ticks, bool) \
            or ticks < 1:
        fails.append(
            f"serving_history_ticks: want >= 1 sampler tick in the "
            f"on-leg, got {ticks!r} — the overhead ratio priced a "
            f"sampler that never ran")
    series = extras.get("serving_history_series")
    if not isinstance(series, (int, float)) \
            or isinstance(series, bool) or series < 1:
        fails.append(
            f"serving_history_series: want >= 1 recorded series in "
            f"the on-leg history snapshot, got {series!r}")
    return fails


def check_disagg_wellformed(extras: dict) -> list[str]:
    """Failure strings when the serving_disagg part ran (its tokens/s
    key exists) without leaving well-formed disaggregation evidence
    (ISSUE 18):

    - ``serving_disagg_vs_unified`` present and positive (the 1
      prefill + 2 decode fleet vs 3 unified replicas on the same
      workload — the BASELINE.json cpu floor gates magnitude, this
      check guards shape);
    - ``serving_disagg_handoffs`` ≥ 1 — at least one prefill→decode
      KV stream actually completed (zero would mean every request
      fell back and the ratio compared nothing);
    - ``serving_disagg_dedup_ratio`` in [0, 1] — blocks deduped over
      blocks offered: the content-addressed negotiation's yield is a
      RATIO by construction, anything outside the interval means the
      counters are wrong, not the workload.

    Empty when the part did not run."""
    if "serving_disagg_tokens_per_s" not in extras:
        return []
    fails = []
    v = extras.get("serving_disagg_vs_unified")
    if not isinstance(v, (int, float)) or isinstance(v, bool) \
            or float(v) <= 0.0:
        fails.append(
            f"serving_disagg_vs_unified: missing/malformed ({v!r}) — "
            f"the serving_disagg part ran but published no "
            f"disagg-vs-unified ratio")
    ho = extras.get("serving_disagg_handoffs")
    if not isinstance(ho, (int, float)) or isinstance(ho, bool) \
            or ho < 1:
        fails.append(
            f"serving_disagg_handoffs: want >= 1 completed KV "
            f"handoff, got {ho!r} — the disagg leg fell back to "
            f"unified serving throughout")
    dr = extras.get("serving_disagg_dedup_ratio")
    if not isinstance(dr, (int, float)) or isinstance(dr, bool) \
            or not 0.0 <= float(dr) <= 1.0:
        fails.append(
            f"serving_disagg_dedup_ratio: want a ratio in [0, 1], "
            f"got {dr!r} — blocks_deduped/blocks_offered accounting "
            f"is broken")
    return fails


def _extras_from_file(path: str) -> dict:
    """Extras dict from any bench artifact: a bench.py checkpoint
    ({"extras": ...}), a bench.py result line ({"metric", "extras"}),
    or a plain extras dict."""
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, dict) and isinstance(data.get("extras"), dict):
        return data["extras"]
    return data


def _extras_from_sweep(mesh, world, on_tpu) -> dict:
    """Run the standard sweeps and fold rows into bench-style extras:
    per op the WORST (min) vs_xla across shapes, so a single regressed
    shape cannot hide behind a good one."""
    import io
    buf = io.StringIO()
    for name, (fn, tpu_shapes, cpu_shapes) in sorted(SWEEPS.items()):
        fn(mesh, world, tpu_shapes if on_tpu else cpu_shapes, buf)
    extras: dict = {}
    for line in buf.getvalue().splitlines():
        row = json.loads(line)
        key = f"{row['op']}_vs_xla"
        if "vs_xla" in row:
            extras[key] = min(extras.get(key, float("inf")),
                              row["vs_xla"])
        elif "error" in row:
            extras.setdefault(f"{row['op']}_errors", []).append(
                row["error"])
    extras["baseline_anomaly"] = None   # sweep shares one timing path
    return extras


def run_regress(baseline_path: str, from_file: str | None,
                tier: str | None) -> int:
    skipped: list = []
    if from_file:
        extras = _extras_from_file(from_file)
        if tier is None:
            tier = ("tpu" if "tpu" in str(extras.get("device_kind", "")
                                          ).lower() else "cpu")
    else:
        mesh, world = _init_mesh()
        on_tpu = _is_tpu()
        if tier is None:
            tier = "tpu" if on_tpu else "cpu"
        extras = _extras_from_sweep(mesh, world, on_tpu)
    floors = load_floors(baseline_path, tier)
    if not from_file:
        # The live sweep covers the SWEEPS ops only; floors for
        # bench.py-only metrics (gemm_ar, tp_mlp, ...) apply to --from
        # checkpoints. Without this filter the missing-key-fails
        # contract would make the live TPU gate structurally unpassable.
        sweep_keys = {f"{op}_vs_xla" for op in SWEEPS}
        skipped = sorted(set(floors) - sweep_keys)
        floors = {k: v for k, v in floors.items() if k in sweep_keys}
    fails = check_regression(extras, floors)
    fails += check_serving_wellformed(extras)
    fails += check_mega_serving_wellformed(extras)
    fails += check_spec_serving_wellformed(extras)
    fails += check_fleet_wellformed(extras)
    fails += check_router_wellformed(extras)
    fails += check_history_wellformed(extras)
    fails += check_disagg_wellformed(extras)
    fails += check_overlap_measured_wellformed(extras)
    fails += check_measured_overlap_floors(
        extras, load_measured_overlap_floors(baseline_path, tier))
    report = {"tier": tier, "floors": floors, "failures": fails,
              "floors_skipped_not_swept": skipped,
              "checked": {k: extras.get(k) for k in sorted(floors)}}
    print(json.dumps(report, indent=1))
    if fails:
        print(f"REGRESSION: {len(fails)} metric(s) below floor",
              file=sys.stderr)
        return 1
    print("regression gate: PASS", file=sys.stderr)
    return 0


SWEEPS = {
    "ag_gemm": (sweep_ag_gemm,
                [(2048, 4096, 4096), (4096, 4096, 4096),
                 (1024, 8192, 4096)],
                [(64, 64, 64)]),
    "gemm_rs": (sweep_gemm_rs,
                [(2048, 4096, 4096), (4096, 4096, 4096)],
                [(64, 64, 64)]),
    "flash_decode": (sweep_flash_decode,
                     [(8, 32, 8, 128, 8192), (1, 32, 8, 128, 32768),
                      (32, 32, 8, 128, 2048)],
                     [(2, 8, 2, 32, 64)]),
}


def main(argv=None):
    # 1-core CPU hosts deadlock interpret-mode semaphore waits unless the
    # affinity shim re-execs us first (runtime/cpu_shim.py; same call
    # every user-style script makes).
    from triton_dist_tpu.runtime.cpu_shim import maybe_reexec_with_shim
    maybe_reexec_with_shim()
    ap = argparse.ArgumentParser()
    ap.add_argument("--op", choices=sorted(SWEEPS) + ["all"],
                    default="all")
    ap.add_argument("--json", default=None,
                    help="append JSON lines here (default stdout)")
    ap.add_argument("--regress", action="store_true",
                    help="compare *_vs_xla ratios against BASELINE.json "
                         "regression_floors; exit 1 on a drop")
    ap.add_argument("--baseline", default=None,
                    help="floor file (default: repo BASELINE.json)")
    ap.add_argument("--from", dest="from_file", default=None,
                    help="take ratios from a bench checkpoint/result "
                         "JSON instead of running the sweep")
    ap.add_argument("--tier", choices=["tpu", "cpu"], default=None,
                    help="floor tier (default: by device_kind/backend)")
    args = ap.parse_args(argv)

    if args.regress:
        return run_regress(args.baseline or _default_baseline_path(),
                           args.from_file, args.tier)

    mesh, world = _init_mesh()
    on_tpu = _is_tpu()
    out = open(args.json, "a") if args.json else sys.stdout
    try:
        for name, (fn, tpu_shapes, cpu_shapes) in sorted(SWEEPS.items()):
            if args.op not in ("all", name):
                continue
            fn(mesh, world, tpu_shapes if on_tpu else cpu_shapes, out)
    finally:
        if args.json:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
