"""Unit tests for bench.py's self-consistency machinery: the
arithmetic recheck, baseline cross-check, headline
selection, and the shared chain fold. These run the bench's CODE, not
its measurements — the orchestration end-to-end is validated by the
TDT_BENCH_CPU run (and the chip run by the driver)."""

import importlib.util
import json
import pathlib

import jax.numpy as jnp
import pytest

_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _load_bench():
    spec = importlib.util.spec_from_file_location("bench", _ROOT / "bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


bench = _load_bench()


def test_finalize_checks_consistent():
    ex = {"n_devices": 1, "timing_selfcheck": {"calib_ms": 1.0},
          "ag_gemm_flops": 2.0 * 2048 * 4096 * 4096,
          "ag_gemm_pallas_ms": 1.0, "ag_gemm_xla_ms": 1.1,
          "ag_gemm_tflops": round(2.0 * 2048 * 4096 * 4096
                                  / 1e-3 / 1e12, 2),
          "gemm_rs_xla_ms": 1.2}
    bench._finalize_checks(ex)
    assert ex["arith_ok"], ex["arith_bad"]
    assert ex["baseline_anomaly"] is None
    assert ex["baseline_xla_ratio"] == round(1.2 / 1.1, 3)


def test_finalize_checks_catches_2x_tflops():
    """The r3 notes' exact failure: ms and TFLOPS apart by 2x."""
    flops = 2.0 * 2048 * 4096 * 4096
    true_tflops = flops / (0.634e-3) / 1e12
    ex = {"n_devices": 1, "ag_gemm_flops": flops,
          "ag_gemm_pallas_ms": 0.634,
          "ag_gemm_tflops": round(true_tflops / 2, 2)}  # the 2x lie
    bench._finalize_checks(ex)
    assert not ex["arith_ok"]
    assert ex["arith_bad"][0]["key"] == "ag_gemm_tflops"


def test_finalize_checks_flags_baseline_split():
    """The r3 anomaly: same-shape XLA baselines 3.5x apart."""
    ex = {"n_devices": 1, "ag_gemm_xla_ms": 0.913,
          "gemm_rs_xla_ms": 3.226,
          "timing_selfcheck": {"calib_ms": 0.9}}
    bench._finalize_checks(ex)
    assert ex["baseline_anomaly"] is not None
    assert any("same matmul" in a for a in ex["baseline_anomaly"])
    assert any("gemm_rs_xla_ms" in a for a in ex["baseline_anomaly"])


def test_select_result_fallback_order():
    assert bench._select_result({})["value"] is None
    ex = {"tp_mlp_fused_ms": 2.0, "tp_mlp_vs_xla": 1.1}
    r = bench._select_result(ex)
    assert r["metric"] == "tp_mlp_fused_ms" and r["vs_baseline"] == 1.1
    ex["ag_gemm_tflops"] = 100.0
    assert bench._select_result(ex)["metric"] == "ag_gemm_tflops"


def test_chain_fold_shapes():
    m, k = 64, 32
    # slice path (output at least (m, k))
    big = jnp.ones((64, 48), jnp.float32)
    assert bench._chain_fold(big, m, k).shape == (m, k)
    # tile path (RS output: (m/w, n))
    small = jnp.ones((8, 48), jnp.float32)
    out = bench._chain_fold(small, m, k)
    assert out.shape == (m, k) and out.dtype == jnp.bfloat16


def test_no_tpu_is_a_nonzero_exit(tmp_path, monkeypatch, capsys):
    """A bench part that finds no TPU (this suite runs on the CPU
    backend) exits with the no-chip code and prints no result line —
    nothing is probed for, retried, or carried over from an earlier
    run's checkpoint."""
    prior = tmp_path / "progress.json"
    prior.write_text(json.dumps(
        {"last_done": "ag_gemm", "ts": 0,
         "extras": {"ag_gemm_tflops": 123.0}}))
    mod = _load_bench()
    monkeypatch.setenv("TDT_BENCH_PROGRESS", str(prior))
    monkeypatch.setenv("TDT_BENCH_ONLY", "ag_gemm")
    monkeypatch.delenv("TDT_BENCH_CPU", raising=False)
    with pytest.raises(SystemExit) as exc:
        mod.main()
    assert exc.value.code == mod._NO_CHIP_RC != 0
    out = capsys.readouterr()
    assert "123.0" not in out.out and '"metric"' not in out.out
    assert "no TPU" in out.err


# -- tools/bench_ops.py --regress (the quick-tier CI smoke) ----------------

def _floors_file(tmp_path):
    path = tmp_path / "BASELINE.json"
    path.write_text(json.dumps({"regression_floors": {
        "tpu": {"ag_gemm_vs_xla": 0.7, "gemm_rs_vs_xla": 0.78},
        "cpu": {"ag_gemm_vs_xla": 0.001}}}))
    return str(path)


def test_regress_passes_and_fails(tmp_path):
    from triton_dist_tpu.tools.bench_ops import (check_regression,
                                                 load_floors)
    floors = load_floors(_floors_file(tmp_path), "tpu")
    ok = {"ag_gemm_vs_xla": 1.5, "gemm_rs_vs_xla": 0.78,
          "baseline_anomaly": None}
    assert check_regression(ok, floors) == []
    bad = dict(ok, ag_gemm_vs_xla=0.5)
    fails = check_regression(bad, floors)
    assert any("ag_gemm_vs_xla" in f for f in fails)
    # a missing metric fails too — the end-to-end assertion
    missing = {"ag_gemm_vs_xla": 1.5}
    assert any("missing" in f for f in check_regression(missing, floors))


def test_regress_flags_baseline_anomaly(tmp_path):
    """baseline_anomaly is machine-checked: when the same-matmul XLA
    baselines disagree, every vs_xla ratio is untrustworthy and the
    gate must fail regardless of the ratios themselves."""
    from triton_dist_tpu.tools.bench_ops import (check_regression,
                                                 load_floors)
    floors = load_floors(_floors_file(tmp_path), "tpu")
    ex = {"ag_gemm_vs_xla": 1.5, "gemm_rs_vs_xla": 1.0,
          "baseline_anomaly": ["ag vs rs: 2.37x apart"]}
    fails = check_regression(ex, floors)
    assert any("anomaly" in f for f in fails)


def test_regress_cli_end_to_end(tmp_path, capsys):
    """The harness runs end to end from a bench checkpoint file — the
    CPU-only smoke wiring (relaxed cpu floors, exit code contract)."""
    from triton_dist_tpu.tools import bench_ops
    baseline = _floors_file(tmp_path)
    ckpt = tmp_path / "ckpt.json"
    ckpt.write_text(json.dumps(
        {"ts": 1, "extras": {"device_kind": "cpu",
                             "ag_gemm_vs_xla": 0.4,
                             "baseline_anomaly": None}}))
    rc = bench_ops.main(["--regress", "--from", str(ckpt),
                         "--baseline", baseline])
    report = json.loads(capsys.readouterr().out)
    assert rc == 0 and report["tier"] == "cpu"
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(
        {"extras": {"device_kind": "TPU v5 lite",
                    "ag_gemm_vs_xla": 0.2, "gemm_rs_vs_xla": 0.9,
                    "baseline_anomaly": None}}))
    rc = bench_ops.main(["--regress", "--from", str(bad),
                        "--baseline", baseline])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert report["tier"] == "tpu" and report["failures"]


def test_regress_live_sweep_filters_unswept_floors(tmp_path, monkeypatch,
                                                   capsys):
    """Live-sweep mode checks only the floors its sweeps can produce
    (bench.py-only metrics like tp_mlp_vs_xla apply to --from
    checkpoints) — otherwise the missing-key-fails contract would make
    the live TPU gate structurally unpassable (review finding)."""
    from triton_dist_tpu.tools import bench_ops
    baseline = tmp_path / "BASELINE.json"
    baseline.write_text(json.dumps({"regression_floors": {
        "tpu": {"ag_gemm_vs_xla": 0.7, "tp_mlp_vs_xla": 0.45}}}))
    monkeypatch.setattr(bench_ops, "_init_mesh", lambda: (None, 1))
    monkeypatch.setattr(bench_ops, "_is_tpu", lambda: True)
    monkeypatch.setattr(
        bench_ops, "_extras_from_sweep",
        lambda *a: {"ag_gemm_vs_xla": 1.5, "gemm_rs_vs_xla": 1.0,
                    "flash_decode_vs_xla": 1.0, "baseline_anomaly": None})
    rc = bench_ops.main(["--regress", "--baseline", str(baseline)])
    report = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert report["floors_skipped_not_swept"] == ["tp_mlp_vs_xla"]
    assert "tp_mlp_vs_xla" not in report["floors"]


def test_repo_baseline_floors_wellformed():
    """The checked-in BASELINE.json floor file parses and carries both
    tiers with the keys the bench actually emits."""
    from triton_dist_tpu.tools.bench_ops import load_floors
    path = str(_ROOT / "BASELINE.json")
    tpu = load_floors(path, "tpu")
    cpu = load_floors(path, "cpu")
    # The tpu tier exists (--regress needs it) but holds only floors
    # taken from a ledger row: the router routes on them, so a number
    # from any other run must not sit here.
    assert all(isinstance(v, (int, float)) for v in tpu.values())
    # cpu KERNEL floors are the end-to-end smoke: near-zero by design
    # (interpret-mode ratios price the interpreter, not the kernels)
    assert all(v <= 0.01 for k, v in cpu.items()
               if k.endswith("_vs_xla"))
    # ... but the scheduler ratio is kernel-independent (both paths run
    # the same xla model), so its floor is the ISSUE 5 acceptance bar:
    # 8 concurrent clients >= 2x the serialized-lock server.
    assert cpu.get("serving_sched_vs_serial", 0) >= 2.0


def test_regress_gates_serving_ratio(tmp_path):
    """serving_sched_vs_serial is machine-checked like the kernel
    ratios: below-floor (a scheduler regressed toward serialized
    behavior) or missing (the serving probe never ran) both fail."""
    from triton_dist_tpu.tools.bench_ops import (check_regression,
                                                 load_floors)
    path = tmp_path / "BASELINE.json"
    path.write_text(json.dumps({"regression_floors": {
        "cpu": {"ag_gemm_vs_xla": 0.001,
                "serving_sched_vs_serial": 2.0}}}))
    floors = load_floors(str(path), "cpu")
    ok = {"ag_gemm_vs_xla": 1.0, "serving_sched_vs_serial": 40.0,
          "baseline_anomaly": None}
    assert check_regression(ok, floors) == []
    bad = dict(ok, serving_sched_vs_serial=1.1)
    assert any("serving_sched_vs_serial" in f
               for f in check_regression(bad, floors))
    gone = {k: v for k, v in ok.items()
            if k != "serving_sched_vs_serial"}
    assert any("serving_sched_vs_serial" in f and "missing" in f
               for f in check_regression(gone, floors))


def test_mega_serving_wellformed_gate():
    """ISSUE 11 satellite: once the serving_mega part ran, its
    serving_mega_vs_plain ratio must exist and be a positive number —
    a run silently dropping the mega-in-scheduler evidence fails; a
    run that never measured serving_mega passes untouched."""
    from triton_dist_tpu.tools.bench_ops import (
        check_mega_serving_wellformed)
    assert check_mega_serving_wellformed({}) == []      # part didn't run
    ok = {"serving_mega_tokens_per_s": 100.0,
          "serving_mega_vs_plain": 0.97}
    assert check_mega_serving_wellformed(ok) == []
    for bad_val in (None, "fast", True, 0.0, -1.0):
        bad = {"serving_mega_tokens_per_s": 100.0,
               "serving_mega_vs_plain": bad_val}
        fails = check_mega_serving_wellformed(bad)
        assert fails and "serving_mega_vs_plain" in fails[0], bad_val
    gone = {"serving_mega_tokens_per_s": 100.0}
    assert check_mega_serving_wellformed(gone)


def test_spec_serving_wellformed_gate():
    """ISSUE 13 satellite: once the serving_spec part ran, its
    serving_spec_vs_plain ratio AND a [0, 1] accept rate must exist —
    a run silently dropping either would let a drafter regression
    hide behind a stale floor pass; a run that never measured
    serving_spec passes untouched."""
    from triton_dist_tpu.tools.bench_ops import (
        check_spec_serving_wellformed)
    assert check_spec_serving_wellformed({}) == []      # part didn't run
    ok = {"serving_spec_tokens_per_s": 100.0,
          "serving_spec_vs_plain": 1.62,
          "serving_spec_accept_rate": 0.44}
    assert check_spec_serving_wellformed(ok) == []
    for bad_val in (None, "fast", True, 0.0, -1.0):
        bad = dict(ok, serving_spec_vs_plain=bad_val)
        fails = check_spec_serving_wellformed(bad)
        assert fails and "serving_spec_vs_plain" in fails[0], bad_val
    for bad_rate in (None, "hi", True, -0.1, 1.5):
        bad = dict(ok, serving_spec_accept_rate=bad_rate)
        fails = check_spec_serving_wellformed(bad)
        assert fails and "serving_spec_accept_rate" in fails[0], \
            bad_rate
    gone = {"serving_spec_tokens_per_s": 100.0}
    assert len(check_spec_serving_wellformed(gone)) == 2


def test_fleet_wellformed_gate():
    """ISSUE 14 satellite: once the serving_fleet part ran, its
    fleet-vs-single ratio must exist and be positive, its per-replica
    rows must name >= 2 distinct replicas, no replica may have been
    down, every replica must have RETIRED rows in the timed window
    (a dead-pump replica still answers health from handler threads),
    and neither timed leg may have request errors — a fanout
    half-landing on a dead replica would publish a fleet tokens/s
    that is really a single-replica number. A run that never measured
    serving_fleet passes untouched."""
    from triton_dist_tpu.tools.bench_ops import check_fleet_wellformed
    assert check_fleet_wellformed({}) == []             # part didn't run
    ok = {"serving_fleet_tokens_per_s": 1200.0,
          "serving_fleet_vs_single": 0.84,
          "serving_fleet_replica_ids": ["r0", "r1"],
          "serving_fleet_down_replicas": 0,
          "serving_fleet_replica_retired": [8, 8],
          "serving_fleet_error_count": 0,
          "serving_fleet_single_error_count": 0}
    assert check_fleet_wellformed(ok) == []
    for bad_val in (None, "fast", True, 0.0, -1.0):
        fails = check_fleet_wellformed(
            dict(ok, serving_fleet_vs_single=bad_val))
        assert fails and "serving_fleet_vs_single" in fails[0], bad_val
    for bad_ids in (None, [], ["r0"], ["r0", "r0"], "r0,r1"):
        fails = check_fleet_wellformed(
            dict(ok, serving_fleet_replica_ids=bad_ids))
        assert fails and "replica_ids" in fails[0], bad_ids
    fails = check_fleet_wellformed(
        dict(ok, serving_fleet_down_replicas=1))
    assert fails and "down" in fails[0]
    fails = check_fleet_wellformed(
        dict(ok, serving_fleet_down_replicas=None))
    assert fails and "down_replicas" in fails[0]
    # The dead-pump case: replica r1 answered health (not down) but
    # retired nothing in the window — must fail.
    for bad_ret in (None, [8], [8, 0], [8, True], [8, "x"]):
        fails = check_fleet_wellformed(
            dict(ok, serving_fleet_replica_retired=bad_ret))
        assert fails and "replica_retired" in fails[0], bad_ret
    # Errored requests in either timed leg fail too.
    for key in ("serving_fleet_error_count",
                "serving_fleet_single_error_count"):
        fails = check_fleet_wellformed(dict(ok, **{key: 2}))
        assert fails and key in fails[0]
        fails = check_fleet_wellformed(dict(ok, **{key: None}))
        assert fails and key in fails[0]
    gone = {"serving_fleet_tokens_per_s": 1200.0}
    assert len(check_fleet_wellformed(gone)) == 6


def test_regress_gates_fleet(tmp_path):
    """serving_fleet rides the full --regress path: a well-formed run
    above the cpu floor passes; a down replica or a below-floor ratio
    fails."""
    import pathlib
    from triton_dist_tpu.tools.bench_ops import run_regress
    base = {"metric": "x", "extras": {
        "ag_gemm_vs_xla": 1.0, "gemm_rs_vs_xla": 1.0,
        "flash_decode_vs_xla": 1.0, "serving_sched_vs_serial": 50.0,
        "serving_prefix_ttft_vs_cold": 6.0,
        "serving_mega_vs_plain": 1.0, "serving_spec_vs_plain": 1.6,
        "serving_router_vs_direct": 0.9,
        "serving_history_on_vs_off": 0.97,
        "serving_disagg_vs_unified": 0.31,
        "serving_fleet_vs_single": 0.84,
        "serving_fleet_tokens_per_s": 1200.0,
        "serving_fleet_replica_ids": ["r0", "r1"],
        "serving_fleet_down_replicas": 0,
        "serving_fleet_replica_retired": [8, 8],
        "serving_fleet_error_count": 0,
        "serving_fleet_single_error_count": 0,
        "baseline_anomaly": None}}
    repo_baseline = str(pathlib.Path(__file__).resolve().parents[1]
                        / "BASELINE.json")
    p = tmp_path / "ok.json"
    p.write_text(json.dumps(base))
    assert run_regress(repo_baseline, str(p), "cpu") == 0
    bad = json.loads(json.dumps(base))
    bad["extras"]["serving_fleet_down_replicas"] = 1
    p2 = tmp_path / "down.json"
    p2.write_text(json.dumps(bad))
    assert run_regress(repo_baseline, str(p2), "cpu") == 1
    low = json.loads(json.dumps(base))
    low["extras"]["serving_fleet_vs_single"] = 0.1
    p3 = tmp_path / "low.json"
    p3.write_text(json.dumps(low))
    assert run_regress(repo_baseline, str(p3), "cpu") == 1


def test_bench_parts_typo_fails_before_checkpoint(tmp_path, monkeypatch):
    """A typo'd TDT_BENCH_PARTS must SystemExit before the checkpoint
    clear — prior evidence survives (review r5a-2)."""
    import pytest

    progress = tmp_path / "progress.json"
    progress.write_text(json.dumps(
        {"ts": 1.0, "extras": {"ag_gemm_tflops": 9.0}}))
    mod = _load_bench()
    monkeypatch.setenv("TDT_BENCH_PROGRESS", str(progress))
    monkeypatch.setenv("TDT_BENCH_PARTS", "ag_gemm,flash_deocde")
    with pytest.raises(SystemExit):
        mod.main()
    assert json.loads(progress.read_text())["extras"] == {
        "ag_gemm_tflops": 9.0}


def test_check_serving_wellformed_requires_rolling_keys():
    """ISSUE 8 satellite: --regress fails a serving bench run whose
    extras lack rolling-window TTFT/TPOT percentiles."""
    from triton_dist_tpu.tools import bench_ops
    # Kernel-only runs pass untouched.
    assert bench_ops.check_serving_wellformed({"ag_gemm_vs_xla": 1.0}) == []
    ex = {"serving_tokens_per_s": 100.0,
          "serving_rolling_ttft_p50_ms": 1.2,
          "serving_rolling_ttft_p99_ms": 3.4,
          "serving_rolling_tpot_p50_ms": 0.5,
          "serving_rolling_tpot_p99_ms": 0.9}
    assert bench_ops.check_serving_wellformed(ex) == []
    bad = dict(ex)
    bad["serving_rolling_tpot_p99_ms"] = None
    del bad["serving_rolling_ttft_p50_ms"]
    fails = bench_ops.check_serving_wellformed(bad)
    assert len(fails) == 2
    assert any("serving_rolling_ttft_p50_ms" in f for f in fails)
    assert any("serving_rolling_tpot_p99_ms" in f for f in fails)
    # The recorded TDT_SLO=0 opt-out is not a missing-metric failure.
    assert bench_ops.check_serving_wellformed(
        {"serving_tokens_per_s": 50.0,
         "serving_rolling_disabled": True}) == []


def test_regress_from_file_gates_serving_rolling(tmp_path):
    """run_regress picks the wellformedness check up end to end."""
    import json as _json
    from triton_dist_tpu.tools import bench_ops
    baseline = tmp_path / "BASELINE.json"
    baseline.write_text(_json.dumps(
        {"regression_floors": {"cpu": {}}}))
    art = tmp_path / "bench.json"
    art.write_text(_json.dumps(
        {"extras": {"serving_tokens_per_s": 50.0}}))
    rc = bench_ops.run_regress(str(baseline), str(art), "cpu")
    assert rc == 1
    ok = tmp_path / "bench_ok.json"
    ok.write_text(_json.dumps({"extras": {
        "serving_tokens_per_s": 50.0,
        "serving_rolling_ttft_p50_ms": 1.0,
        "serving_rolling_ttft_p99_ms": 2.0,
        "serving_rolling_tpot_p50_ms": 0.3,
        "serving_rolling_tpot_p99_ms": 0.6}}))
    assert bench_ops.run_regress(str(baseline), str(ok), "cpu") == 0
