"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` names the cell's configuration and traffic mix; their
files are found by those names (``benchmark/configs/<config>.json``,
``benchmark/traffic/<traffic>.json``), the per-layer readers by the
metrics' names (``benchmark/layer_metrics/<name>.py``). Nothing in this
file knows a cell, a configuration or a metric by name.

The run: build the system under test with weights from ``--seed``, warm
every admission bucket the traffic's clips allow plus the decode step
until a round compiles nothing (all of it set-up), offer the load from a
child process for ``--seconds``, read memory, free the program, then
decide ``correct`` against the plain reference. The last line of stdout
is the result object; everything else goes to stderr.

It fails (exit 2, no result) when JAX reports no TPU or fewer chips than
the cell asks for. ``--rehearse`` runs the same control flow at the
configuration's ``rehearsal`` sizes on the CPU; it exits 3 and never
prints a result line, so no CPU number can pass for a device metric.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse          # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark.harness import runner   # noqa: E402  (numpy only, no JAX)
from benchmark.harness.runner import log   # noqa: E402


class Refused(Exception):
    """The run ends without a result line; ``code`` is the exit code."""

    def __init__(self, code: int, why: str):
        super().__init__(why)
        self.code = code


def die(code: int, why: str):
    raise Refused(code, why)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload: str, rehearse: bool):
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        die(2, f"no workload {workload!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(ROOT, entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    if rehearse:
        over = dict(cfg.get("rehearsal", {}))
        cfg.update(over)
        traffic.update(traffic.get("rehearsal", {}))
    return bench, cell, cfg, traffic


def load_by_name(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module (names hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the configuration's rehearsal "
                         "sizes; exits 3 and prints no result line")
    ap.add_argument("--rehearse-out", default=None,
                    help="with --rehearse: write what a result line would "
                         "hold (minus device metrics) to this file")
    args = ap.parse_args(argv)

    bench, cell, cfg, traffic = load_cell(args.workload, args.rehearse)
    chips = int(cell["chips"])
    runner.prepare_environment(chips, args.rehearse)

    import jax
    if args.rehearse and __name__ == "__main__":
        from triton_dist_tpu.runtime.cpu_shim import maybe_reexec_with_shim
        maybe_reexec_with_shim()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": chips}
    log(event="start", workload=cell["name"], seed=args.seed,
        seconds=args.seconds, trace=args.trace, device=device,
        devices_found=len(devices),
        compile_cache=os.environ["JAX_COMPILATION_CACHE_DIR"])
    if not args.rehearse and device["platform"] != "tpu":
        die(2, f"no TPU: JAX reports platform {device['platform']!r}")
    if len(devices) < chips:
        die(2, f"the cell asks for {chips} chips, JAX reports "
               f"{len(devices)}")
    if not args.rehearse:
        from benchmark.harness.peaks import peaks_for
        peaks = peaks_for(device["kind"])
        from triton_dist_tpu.ops.common import resolve_interpret
        if resolve_interpret(None) is not False:
            die(2, "Pallas kernels would run interpreted")
    else:
        peaks = None

    from benchmark.harness import correct
    from triton_dist_tpu import obs
    obs.enable()
    watch = runner.CompileWatch()

    # -- set-up ------------------------------------------------------------
    builder = importlib.import_module(
        f"benchmark.harness.builders.{cfg.get('builder', 'dense')}")
    t0 = time.monotonic()
    sut = builder.build(cfg, devices[:chips], args.seed)
    model = sut.model
    log(event="built", s=round(time.monotonic() - t0, 3),
        compiles=watch.compiles, cache_hits=watch.hits)
    try:
        bounds = runner.warm_up(sut, traffic, args.seed, watch)
    except RuntimeError as e:
        die(1, str(e))

    # -- the window --------------------------------------------------------
    win = runner.Window(sut, traffic, args.seed, args.seconds)
    t_open, t_close = win.t_open, win.t_close
    runner.sleep_until(t_open)
    setup_s = time.monotonic() - T_PROCESS
    c_open = sut.counters()
    n_compiles_open = watch.compiles + watch.hits
    capture = None
    if args.trace:
        trace_s = min(float(traffic.get("trace_s", 4.0)), args.seconds * 0.8)
        # Early in the window: collecting the capture (seconds per chip)
        # should end before the window does.
        runner.sleep_until(t_open + (args.seconds - trace_s) / 4.0)
        capture = Capture()
        capture.start()
        runner.sleep_until(capture.t_a + trace_s)
        capture.stop()
    runner.sleep_until(t_close)
    c_close = sut.counters()
    n_compiles_close = watch.compiles + watch.hits
    try:
        records = win.collect()
    except RuntimeError as e:
        die(1, str(e))
    window = (t_open, t_close)
    c_end = sut.counters()

    # -- after the close: memory, free the program, then the reference -----
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in sut.devices)
    device["memory_peak_bytes"] = int(peak)
    counters = {k: c_close.get(k, 0) - c_open.get(k, 0) for k in c_close}
    mine = win.mine()
    n_failed = sum(1 for r in mine if "tokens" not in r)
    wait_ms = (args.seconds + win.plan["drain_s"]) * 1e3
    chk = cfg["check"]
    sampled = correct.sample_requests(
        mine, args.seed, int(chk.get("min_tokens", 300)),
        int(chk.get("max_requests", 16)))
    sampled = [(r, win.prompt_of(r)) for r in sampled]
    sut.close()
    del sut
    t0 = time.monotonic()
    verdict = correct.check(cfg, model, traffic, bounds, args.seed, sampled)
    numbers = verdict["numbers"]
    numbers["compiles_in_window"] = {
        "value": n_compiles_close - n_compiles_open, "limit": 0}
    bad = {k: v for k, v in c_end.items() if v and (
        "fallback" in k or "policy_source" in k or "watchdog" in k
        or k in ("serving.pump_errors", "serving.admit_errors"))}
    numbers["fallbacks_and_errors"] = {"value": sum(bad.values()),
                                       "limit": 0}
    fused = sum(v for k, v in c_end.items() if k.endswith(".fused_total"))
    ok = all(v["value"] <= v["limit"] for v in numbers.values())
    log(event="check", s=round(time.monotonic() - t0, 3), bad=bad,
        fused_kernels_counted=fused, **verdict["info"])

    # -- metrics -----------------------------------------------------------
    e2e = runner.end_to_end(mine, records, window, n_failed, wait_ms)
    e2e["setup_s"] = setup_s
    wanted = bench["per_layer"] if args.trace else bench["end_to_end"]
    metrics = {}
    if not args.trace:
        for m in wanted:
            if _applies(m, cell) and e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    breakdown = None
    if args.trace:
        from benchmark.harness import tracered, xplane
        t0 = time.monotonic()
        reduced = tracered.Reduced(
            xplane.load(capture.dir), capture.t_b - capture.t_a,
            cfg["trace_names"]["host_spans"])
        capture.discard()
        log(event="trace_parsed", s=round(time.monotonic() - t0, 3),
            planes=reduced.trace.planes(), chips=reduced.chips,
            window_s=reduced.window_s, busy=reduced.busy,
            holds=reduced.describe())
        if not reduced.chips and not args.rehearse:
            die(1, "the capture holds no device operation")
        ctx = {"records": mine, "all_records": records, "window": window,
               "counters": counters,
               "trace": reduced, "config": cfg, "model": model,
               "traffic": traffic, "peaks": peaks, "chips": chips,
               "load_kernel": lambda n: load_by_name("kernels", n)}
        for m in wanted:
            if not _applies(m, cell):
                continue
            value = load_by_name("layer_metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced.chips:
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s
            breakdown = {"device_ops": reduced.device_ops(),
                         "idle_gaps": reduced.idle_gaps()}

    result = {"correct": bool(ok), "attempted": len(mine),
              "failed": n_failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = {k: [v["value"], v["limit"]]
                       for k, v in numbers.items()}
    log(event="summary", e2e=e2e, attempted=len(mine), failed=n_failed,
        requests_total=len(records), compiles=watch.compiles,
        compile_s=round(watch.compile_s, 3), cache_hits=watch.hits,
        wall_s=round(time.monotonic() - T_PROCESS, 3))
    # The numbers compared, each beside its limit: the last lines of stderr.
    for k, v in numbers.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(f"correct: {ok}", file=sys.stderr, flush=True)
    if args.rehearse:
        if args.rehearse_out:
            with open(args.rehearse_out, "w") as f:
                json.dump({"correct": result["correct"],
                           "attempted": result["attempted"],
                           "failed": result["failed"],
                           "check": result["check"],
                           "metric_names": sorted(metrics),
                           "device": device}, f)
        die(3, "rehearsal on the CPU: only a TPU run gives a result")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def _applies(metric: dict, cell: dict) -> bool:
    return "workloads" not in metric or cell["name"] in metric["workloads"]


class Capture:
    """One profiler session into a directory under ``TMPDIR``."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.t_a = self.t_b = None

    def start(self):
        import jax
        jax.profiler.start_trace(self.dir)
        self.t_a = time.monotonic()

    def stop(self):
        import jax
        self.t_b = time.monotonic()
        jax.profiler.stop_trace()

    def discard(self):
        shutil.rmtree(self.dir, ignore_errors=True)


if __name__ == "__main__":
    try:
        code = main()
    except Refused as e:
        log(event="refused", reason=str(e))
        code = e.code
    except Exception as e:  # noqa: BLE001 — no result line on a failure
        import traceback
        traceback.print_exc()
        log(event="refused", reason=f"{type(e).__name__}: {e}"[:2000])
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # Pump, server and profiler threads are daemons or stopped; do not
    # let a device teardown outlive the result.
    os._exit(code)
