"""Quick-tier CI gate for the static-analysis framework (ISSUE 9).

Three layers:

- the repo itself is clean under every registered pass (the
  acceptance gate — `python -m triton_dist_tpu.tools.tdt_check`
  exits 0);
- the ring-protocol model checker verifies every fused-family
  schedule for worlds 1..8 in both ring directions, and each of the
  five known-bad schedule mutants is caught with the RIGHT finding
  class and a nonzero driver exit code — a checker that passes
  everything is untested;
- one seeded drift per contract-lint class fires with a
  file:line-anchored finding.
"""

import json
import textwrap

import pytest

from triton_dist_tpu.analysis import (
    Finding, PASSES, exit_code, filter_suppressed, run_passes)
from triton_dist_tpu.analysis import ring_model as rm
from triton_dist_tpu.analysis import vmem as avmem
from triton_dist_tpu.analysis import (
    lint_env, lint_fallback, lint_metrics, lint_trace)
from triton_dist_tpu.tools import tdt_check


# ---------------------------------------------------------------------------
# The repo is clean (the CI gate)
# ---------------------------------------------------------------------------

def test_repo_is_clean_under_all_passes():
    findings = run_passes()
    assert findings == [], "\n".join(f.render() for f in findings)


def test_driver_main_exit_code_and_json(capsys):
    assert tdt_check.main([]) == 0
    assert tdt_check.main(["--json"]) == 0
    out = capsys.readouterr().out
    payload = json.loads(out[out.index("{"):])
    assert payload["errors"] == 0
    assert tdt_check.main(["--list"]) == 0
    listed = capsys.readouterr().out
    for name in PASSES:
        assert name in listed


def test_driver_rejects_unknown_pass():
    with pytest.raises(ValueError, match="unknown pass"):
        run_passes(names=["no-such-pass"])


def test_smoke_preflight_is_green():
    import tpu_smoke
    assert tpu_smoke.run_preflight() == 0


# ---------------------------------------------------------------------------
# Ring-protocol model checker: green on the real schedules...
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("world", range(1, 9))
@pytest.mark.parametrize("dirs", [1, 2])
def test_every_family_schedule_verifies(world, dirs):
    for trace in rm.family_traces(world, dirs):
        assert rm.check_trace(trace) == [], trace.name


# ...and each known-bad mutant is caught with the right class.

def _codes(trace):
    return {v.code for v in rm.check_trace(trace)}


@pytest.mark.parametrize("world,dirs", [(4, 2), (5, 2), (3, 1)])
def test_mutant_dropped_wait(world, dirs):
    t = rm.drop_first_wait(rm.ag_ring_trace(world, dirs))
    codes = _codes(t)
    assert "ring.race" in codes, codes           # read of in-flight chunk
    assert "ring.signal_wait_imbalance" in codes


@pytest.mark.parametrize("world,dirs", [(4, 2), (2, 1)])
def test_mutant_doubled_signal(world, dirs):
    codes = _codes(rm.double_signal(rm.ag_ring_trace(world, dirs)))
    assert codes == {"ring.signal_wait_imbalance"}, codes


@pytest.mark.parametrize("world,dirs", [(4, 2), (5, 1)])
def test_mutant_off_by_one_chunk(world, dirs):
    codes = _codes(rm.shift_consume(rm.ag_ring_trace(world, dirs)))
    assert "ring.coverage" in codes, codes


@pytest.mark.parametrize("world,dirs", [(4, 2), (3, 1), (8, 2)])
def test_mutant_swapped_direction(world, dirs):
    codes = _codes(rm.swap_direction(rm.ag_ring_trace(world, dirs)))
    assert "ring.deadlock" in codes, codes


def test_mutant_rs_off_by_one_reduction():
    codes = _codes(rm.gemm_rs_trace(5, 2, send_idx_shift=1))
    assert "ring.coverage" in codes, codes


def test_mutants_exit_nonzero_with_anchor():
    """Acceptance shape: every mutant → nonzero exit + file:line."""
    base = rm.ag_ring_trace(4, 2)
    mutants = [rm.drop_first_wait(base), rm.double_signal(base),
               rm.shift_consume(base), rm.swap_direction(base)]
    for t in mutants:
        findings = [Finding(code=v.code, message=v.detail,
                            file=t.anchor[0], line=t.anchor[1])
                    for v in rm.check_trace(t)]
        assert exit_code(findings) != 0, t.name
        assert findings[0].file and findings[0].file.endswith(".py")
        assert findings[0].line and findings[0].line > 0
        assert ":" in findings[0].anchor


def test_ring_pass_runs_real_schedule_code(monkeypatch):
    """The checker symbolically executes ring_chunk_schedule itself: a
    bug injected THERE (not in the mirror) must surface."""
    from triton_dist_tpu.ops import common as ops_common
    orig = ops_common.ring_chunk_schedule

    def broken(me, s, world, dirs):
        c, b, o = orig(me, s, world, dirs)
        return (c + 1) % world if world > 1 else c, b, o

    monkeypatch.setattr(ops_common, "ring_chunk_schedule", broken)
    rm._schedule_table.cache_clear()
    try:
        t = rm.ag_ring_trace(4, 2)
        assert rm.check_trace(t) != []
    finally:
        rm._schedule_table.cache_clear()


# ---------------------------------------------------------------------------
# VMEM-over-budget mutant: rejected statically, no compile invoked
# ---------------------------------------------------------------------------

def test_mutant_vmem_over_budget_rejected_statically():
    cfg = {"variant": "hbm", "block_m": 1024, "block_n": 2048}
    f = avmem.vet_candidate("ag_gemm", cfg, rows=8192, m=8192, k=8192,
                            n_loc=8192, itemsize=2, world=1)
    assert f is not None and f.code == "vmem.over_budget"
    assert f.file and f.line and exit_code([f]) != 0
    # and an in-budget config passes the same gate
    ok = avmem.vet_candidate("ag_gemm",
                             {"variant": "hbm", "block_m": 128,
                              "block_n": 128},
                             rows=1024, m=1024, k=1024, n_loc=1024,
                             itemsize=2, world=1)
    assert ok is None


def test_autotune_vet_skips_rejected_candidates_without_compiling():
    from triton_dist_tpu.tools import autotuner
    built = []

    def make_fn(**cfg):
        built.append(dict(cfg))
        return lambda: None

    res = autotuner.autotune(
        make_fn, [{"a": 1}, {"a": 2}, {"a": 3}], key=None, iters=1,
        warmup_iters=0,
        vet=lambda c: "too big" if c["a"] == 2 else None)
    assert {c["a"] for c in built} == {1, 3}   # a=2 never constructed
    assert res.config["a"] in (1, 3)
    with pytest.raises(ValueError, match="static vet"):
        autotuner.autotune(make_fn, [{"a": 2}], key=None, iters=1,
                           warmup_iters=0, vet=lambda c: "no")


def test_autotune_vet_blocks_stale_cached_winner(tmp_path, monkeypatch):
    """A persisted winner from a sweep that predates the vet (or a
    footprint-model fix) must be re-swept, not resurrected unvetted:
    the vet filters the candidate list BEFORE the cache consult, so
    the staleness membership check runs against the vetted list."""
    from triton_dist_tpu.tools import autotuner
    monkeypatch.setenv("TDT_AUTOTUNE_CACHE", str(tmp_path / "c.json"))
    built = []

    def make_fn(**cfg):
        built.append(dict(cfg))
        return lambda: None

    r1 = autotuner.autotune(make_fn, [{"a": 2}], key="stale-k",
                            iters=1, warmup_iters=0)
    assert r1.config == {"a": 2}
    autotuner.clear_cache()          # in-memory gone; disk hit remains
    built.clear()
    r2 = autotuner.autotune(
        make_fn, [{"a": 2}, {"a": 3}], key="stale-k", iters=1,
        warmup_iters=0,
        vet=lambda c: "over cap" if c["a"] == 2 else None)
    assert r2.config == {"a": 3}
    assert built == [{"a": 3}]       # the stale winner never compiled


def test_candidate_tables_fit_cap_all_worlds():
    assert avmem.sweep_candidate_tables() == []


def test_declared_footprint_agrees_with_config_generators():
    """The footprint model and the generators' feasibility filters are
    the same arithmetic: every candidate the generator emits (budget
    AND aggressive tiers) must score <= the hard cap the generator
    filters against."""
    from triton_dist_tpu.ops.allgather_gemm import ag_gemm_configs
    from triton_dist_tpu.ops.common import (DEFAULT_VMEM_BUDGET,
                                            HARD_FOOTPRINT_CAP)
    from triton_dist_tpu.tools.perf_model import declared_footprint
    m = k = n = 4096
    for world in (1, 2, 4, 8):
        rows, n_loc = m // world, n // world
        for cfg in ag_gemm_configs(m, rows, k, n_loc, 2,
                                   DEFAULT_VMEM_BUDGET):
            if cfg["variant"] == "hbm_kt":
                continue  # kt fallbacks are listed unconditionally
            fp = declared_footprint("ag_gemm", cfg, rows=rows, m=m,
                                    k=k, n_loc=n_loc, itemsize=2,
                                    world=world)
            assert fp <= HARD_FOOTPRINT_CAP, (world, cfg, fp)


# ---------------------------------------------------------------------------
# Seeded drift per lint class
# ---------------------------------------------------------------------------

def test_seeded_metric_drift(tmp_path):
    src = tmp_path / "mod.py"
    src.write_text(textwrap.dedent("""
        import obs
        def f(op):
            obs.counter("totally.new_metric").inc()
            obs.gauge(f"comms.{op}.known_gauge").set(1)
    """))
    cat = tmp_path / "observability.md"
    cat.write_text(textwrap.dedent("""
        ## Metric catalog

        | metric | type | meaning |
        |---|---|---|
        | `comms.<op>.known_gauge` | gauge | fine |
        | `never.emitted_anywhere` | counter | stale |
    """))
    findings = lint_metrics.run(files=[src], catalog=cat)
    codes = {(f.code, f.line is not None and f.file is not None)
             for f in findings}
    assert ("lint.metric_undocumented", True) in codes
    assert ("lint.metric_dead", True) in codes
    assert len(findings) == 2 and exit_code(findings) != 0


def test_catalog_suffix_alternates_expand():
    """`x.a` / `.b` and `p50` / `_p99` style rows match both forms."""
    import pathlib
    cat = pathlib.Path(__file__).parents[1] / "docs" / "observability.md"
    pats = [p for _, cands in lint_metrics.catalog_patterns(cat)
            for p in cands]
    assert any(p.endswith("engine.decode_path.plain") for p in pats)
    assert any(p.endswith("_p99_ms") and "rolling" in p for p in pats)


def test_seeded_env_drift(tmp_path, monkeypatch):
    src = tmp_path / "mod.py"
    src.write_text(textwrap.dedent("""
        import os
        def f():
            v = os.environ.get("TDT_TOTALLY_NEW_KNOB", "").strip()
            n = int(v) if v else 3
            direct = int(os.environ.get("TDT_MAX_WAITING", "64"))
            return n + direct
    """))
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "a.md").write_text("`TDT_MAX_WAITING` is documented.\n")
    findings = lint_env.run(files=[src], docs_dir=docs)
    by_code = {}
    for f in findings:
        by_code.setdefault(f.code, []).append(f)
    assert "lint.env_undocumented" in by_code
    assert "TDT_TOTALLY_NEW_KNOB" in by_code["lint.env_undocumented"][0].message
    # BOTH int-parse shapes fire: via tainted local AND direct
    knobs = {f.message.split()[4] for f in by_code["lint.env_int_parse"]}
    assert {"TDT_TOTALLY_NEW_KNOB", "TDT_MAX_WAITING"} <= knobs
    assert all(f.file and f.line for f in findings)
    assert exit_code(findings) != 0


def test_seeded_trace_imbalance(tmp_path):
    src = tmp_path / "mod.py"
    src.write_text(textwrap.dedent("""
        from triton_dist_tpu.obs import trace
        def leaky():
            trace.begin("op.thing", "op")
            return 1   # no end
        def fine():
            trace.begin("op.other", "op")
            trace.end("op.other", "op")
        class Paired:
            def __enter__(self):
                trace.begin("op.paired", "op")
            def __exit__(self, *exc):
                trace.end("op.paired", "op")
    """))
    findings = lint_trace.run(files=[src])
    assert [f.code for f in findings] == ["lint.trace_unbalanced"]
    assert "leaky" in findings[0].message
    assert findings[0].file == str(src) and findings[0].line
    assert exit_code(findings) != 0


def test_seeded_fallback_drift():
    """Removing a DELEGATES entry re-exposes the contract violation,
    anchored at the delegate's def line in ops/."""
    delegates = dict(lint_fallback.DELEGATES)
    removed = delegates.pop("allgather_gemm.ag_gemm")
    assert removed == "ag_gemm"
    findings = lint_fallback.collect_findings(delegates=delegates)
    assert len(findings) == 1
    f = findings[0]
    assert f.code == "lint.fallback_uncovered"
    assert "allgather_gemm.ag_gemm" in f.message
    assert f.file.endswith("allgather_gemm.py") and f.line > 0
    assert exit_code(findings) != 0


# ---------------------------------------------------------------------------
# Suppression pragmas
# ---------------------------------------------------------------------------

def test_pragma_suppression(tmp_path):
    src = tmp_path / "mod.py"
    src.write_text(
        "from triton_dist_tpu.obs import trace\n"
        "def hang_marker():\n"
        "    trace.begin('op.hang', 'op')"
        "  # tdt: ignore[lint.trace_unbalanced]\n")
    findings = filter_suppressed(lint_trace.run(files=[src]))
    assert findings == []
    # a pragma naming a DIFFERENT code does not suppress
    src.write_text(
        "from triton_dist_tpu.obs import trace\n"
        "def hang_marker():\n"
        "    trace.begin('op.hang', 'op')  # tdt: ignore[other.code]\n")
    assert len(filter_suppressed(lint_trace.run(files=[src]))) == 1
    # bare pragma suppresses anything
    src.write_text(
        "from triton_dist_tpu.obs import trace\n"
        "def hang_marker():\n"
        "    trace.begin('op.hang', 'op')  # tdt: ignore\n")
    assert filter_suppressed(lint_trace.run(files=[src])) == []


# ---------------------------------------------------------------------------
# Shim compatibility
# ---------------------------------------------------------------------------

def test_fallback_lint_shim_matches_pass():
    from triton_dist_tpu.tools import fallback_lint
    assert fallback_lint.missing_fallbacks() == [
        f.message for f in lint_fallback.collect_findings()]
    assert fallback_lint.DELEGATES is lint_fallback.DELEGATES
