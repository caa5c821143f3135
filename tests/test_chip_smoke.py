"""chip_smoke.py without a chip: the rule "no TPU means failure" holds.

The script is the command that proves the serving path on the TPU; here,
on the CPU backend, every way of running it must exit non-zero and never
print ``"ok": true`` — while its rehearsal size still drives the whole
control flow (both engines through ModelServer and the real client; the
four-chip phase on four virtual devices), so a later PR that breaks the
script finds out before it spends chip time.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _run(args, tmp_path, n_devices=1):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    env["TDT_TRACE_DIR"] = str(tmp_path / "traces")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py"), *args],
                       capture_output=True, text=True, timeout=900, env=env,
                       cwd=tmp_path)
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    return r, lines


# (arguments, virtual devices, events the run must get through)
@pytest.mark.parametrize("args,n_devices,reached", [
    (["--size", "tiny"], 1, ("compare", "served", "phase", "done")),
    (["--size", "tiny", "--chips", "4"], 4,
     ("collectives", "compare", "served", "done")),
    ([], 1, ()),
], ids=["one-chip-rehearsal", "four-chip-rehearsal", "full-size"])
def test_no_tpu_never_ok(tmp_path, args, n_devices, reached):
    r, lines = _run(args, tmp_path, n_devices)
    tail = r.stdout[-2000:] + r.stderr[-2000:]
    assert r.returncode != 0, tail
    assert '"ok"' not in r.stdout, tail
    assert lines[0]["event"] == "start", tail
    assert lines[0]["device"]["platform"] == "cpu", tail
    assert lines[0]["device"]["count"] == n_devices, tail
    assert str(tmp_path) in lines[0]["compile_cache_dir"], tail
    events = [ln["event"] for ln in lines]
    for ev in reached:
        assert ev in events, (ev, tail)
    # A rehearsal is refused at its end; the real size before it starts.
    assert events[-1] == ("refused" if reached else "failed"), tail
    if "--chips" in args:
        # with the option, no one-chip phase runs
        phases = [ln["phase"] for ln in lines if ln["event"] == "phase"]
        assert phases == ["collectives", "tp4"], tail


def test_a_fallback_fails_the_run(tmp_path):
    """One injected runtime failure in an op of the path sends that call
    to its XLA fallback — which the script must report as a failure, not
    as a served request."""
    r, lines = _run(["--size", "tiny", "--fail-op", "gemm_ar"], tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert lines[-1]["event"] == "failed"
    assert "gemm_ar.fallback" in lines[-1]["reason"]
