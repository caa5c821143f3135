"""``run.py`` end to end at the rehearsal size on the CPU: it refuses
without a TPU, a rehearsal never prints a result line, and a run with the
timed path broken underneath comes out as not correct."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RUN = os.path.join(ROOT, "benchmark", "run.py")
CELL = "qwen3-0.6b.chat-open"


def run_cli(*extra, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="17")
    e.update(env or {})
    return subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "2147483999",
         "--seconds", "2", "--trace", "0", *extra],
        capture_output=True, text=True, cwd=ROOT, env=e, timeout=600)


def test_no_tpu_is_a_clean_refusal():
    p = run_cli()
    assert p.returncode == 2
    assert p.stdout.strip() == ""                  # no result line
    assert "no TPU" in p.stderr


def test_unknown_workload_is_refused():
    p = subprocess.run(
        [sys.executable, RUN, "--workload", "nope", "--seed", "1",
         "--seconds", "2", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 2 and p.stdout.strip() == ""


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_prints_no_result_and_no_device_metric(tmp_path, trace):
    out = tmp_path / "r.json"
    p = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "2147483999",
         "--seconds", "3", "--trace", trace, "--rehearse",
         "--rehearse-out", str(out)],
        capture_output=True, text=True, cwd=ROOT, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == ""                  # never a result line
    got = json.loads(out.read_text())
    assert got["correct"] is True, got
    assert got["attempted"] > 0 and got["failed"] == 0
    assert got["device"]["platform"] == "cpu"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    device_metrics = {m["name"] for m in bench["per_layer"]
                      if m["source"] == "device_trace"}
    assert not device_metrics & set(got["metric_names"])
    assert "busy_s" not in got["device"]
    # the last lines of stderr name each number compared beside its limit
    tail = p.stderr.strip().splitlines()[-8:]
    assert any(l.startswith("check gap_max:") and "limit" in l for l in tail)
