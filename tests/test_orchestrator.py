"""Tests for bench.py's part orchestrator: one child per part, a child
that overruns its deadline is terminated and reaped before the next
part starts, and a child that finds no TPU ends the run non-zero."""

import importlib.util
import json
import pathlib

_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- bench orchestrator ------------------------------------------------------

def _run_children(monkeypatch, tmp_path, parts, deadlines, child_behavior):
    """Drive _run_parts_in_children with a stubbed child process;
    returns (extras, log of spawn/terminate/wait events)."""
    log = []
    # bench.py's import-time env defaults (compile cache dir, traceback
    # filtering) must not leak past this test (review r5j-3).
    for key in ("JAX_COMPILATION_CACHE_DIR", "JAX_TRACEBACK_FILTERING",
                "TDT_AUTOTUNE_CACHE"):
        monkeypatch.setenv(key, __import__("os").environ.get(key) or "")
    bench = _load("bench_t", _ROOT / "bench.py")
    monkeypatch.setenv("TDT_BENCH_PARTS", ",".join(parts))
    monkeypatch.setenv("TDT_BENCH_PROGRESS", str(tmp_path / "p.json"))
    monkeypatch.setattr(bench, "_PART_DEADLINE_S", deadlines)
    monkeypatch.setattr(bench, "_PART_DEADLINE_DEFAULT_S", 0.5)
    # Generous wall budget so only per-part deadlines matter.
    monkeypatch.setenv("TDT_BENCH_BUDGET_S", "600")
    bench._T0 = __import__("time").monotonic()

    class FakeChild:
        def __init__(self, name, tmp_progress):
            self.name = name
            self.behavior = child_behavior(name)
            self.returncode = None
            if self.behavior == "ok":
                # A real child checkpoints metrics; emulate that.
                with open(tmp_progress, "w") as f:
                    json.dump({"ts": 1.0, "extras":
                               {f"{name}_pallas_ms": 1.0}}, f)

        def poll(self):
            if self.behavior == "ok":
                self.returncode = 0
            elif self.behavior == "nochip":
                self.returncode = bench._NO_CHIP_RC
            return self.returncode  # None: hung until terminated

        def terminate(self):
            log.append(("terminate", self.name))

        def wait(self, timeout=None):
            log.append(("wait", self.name))
            self.returncode = -15
            return self.returncode

    import subprocess as sp

    def fake_popen(argv, env=None, **kw):
        name = env["TDT_BENCH_ONLY"]
        log.append(("spawn", name))
        return FakeChild(name, env["TDT_BENCH_PROGRESS"])
    # bench imports subprocess inside the function, so patching the
    # global module object covers it; monkeypatch undoes on teardown.
    monkeypatch.setattr(sp, "Popen", fake_popen)
    extras = {}
    bench._run_parts_in_children(extras)
    return extras, log


def test_orchestrator_reaps_overrun_before_next_part(monkeypatch,
                                                     tmp_path):
    """A part that blows its deadline is terminated AND waited for
    before the next part is spawned (a live child keeps the chip), the
    run goes on, and already-completed parts keep their metrics."""
    extras, log = _run_children(
        monkeypatch, tmp_path,
        parts=["ag_gemm", "gemm_rs", "gemm_ar"],
        deadlines={"gemm_rs": 0.5},
        child_behavior=lambda n: "hang" if n == "gemm_rs" else "ok")
    assert "ag_gemm_pallas_ms" in extras            # completed part kept
    assert extras["gemm_rs_timeout_s"] == 0         # round(0.5)
    assert "gemm_rs_rc" not in extras               # a timeout, not a crash
    assert "gemm_ar_pallas_ms" in extras            # the run went on
    assert log == [("spawn", "ag_gemm"), ("spawn", "gemm_rs"),
                   ("terminate", "gemm_rs"), ("wait", "gemm_rs"),
                   ("spawn", "gemm_ar")]


def test_orchestrator_completes_all_when_children_finish(monkeypatch,
                                                         tmp_path):
    extras, log = _run_children(
        monkeypatch, tmp_path,
        parts=["ag_gemm", "gemm_rs"],
        deadlines={},
        child_behavior=lambda n: "ok")
    assert "ag_gemm_pallas_ms" in extras and "gemm_rs_pallas_ms" in extras
    assert not any(ev == "terminate" for ev, _ in log)


def test_orchestrator_no_tpu_child_ends_run(monkeypatch, tmp_path):
    """The parent never touches the backend itself; a child reporting
    the no-chip exit code ends the whole run non-zero and no later
    part is spawned."""
    import pytest
    with pytest.raises(SystemExit) as exc:
        _run_children(
            monkeypatch, tmp_path, parts=["ag_gemm", "gemm_rs"],
            deadlines={}, child_behavior=lambda n: "nochip")
    assert "no TPU" in str(exc.value.code)
