"""Readings for the limits of ``correct`` (and the knee of an open-loop
cell), taken on the chip in ONE set-up.

    python benchmark/calibrate.py --workload <name> --seeds 101,102,...
        [--seconds 8] [--controls int8,fp8] [--rates 3,4,5,6]

For each seed: new weights from the seed into the same engine (its
compiled programs do not depend on them), a short window at the cell's
own load through the server socket, then ``correct.check`` over the
sampled answers (the lower readings of ``gap_max`` and ``gap_mean``) and,
on the same prompts and served tokens, ``correct.check`` with each
control precision in the program's place (the upper readings, and
whether the control comes out ``correct: false`` under the configuration
file's limits). With
``--rates`` (open loop only) it instead offers each rate in turn with the
first seed and prints what the knee is read from.

Not part of a benchmark run: ``run.py`` never imports it. One JSON object
per line on stdout.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def emit(**obj) -> None:
    print(json.dumps(obj, default=str), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="101,102,103")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--controls", default="int8,fp8")
    ap.add_argument("--rates", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import run as bench_run
    bench, cell, cfg, traffic = bench_run.load_cell(args.workload,
                                                    args.rehearse)
    chips = int(cell["chips"])
    from benchmark.harness import correct, runner, stats
    runner.prepare_environment(chips, args.rehearse)
    import jax
    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        emit(event="refused", reason="no TPU")
        return 2
    from triton_dist_tpu import obs
    obs.enable()
    watch = runner.CompileWatch()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [c for c in args.controls.split(",") if c]
    builder = importlib.import_module(
        f"benchmark.harness.builders.{cfg.get('builder', 'dense')}")
    t0 = time.monotonic()
    sut = builder.build(cfg, devices[:chips], seeds[0])
    bounds = runner.warm_up(sut, traffic, seeds[0], watch)
    emit(event="ready", setup_s=round(time.monotonic() - t0, 3),
         device=devices[0].device_kind, chips=chips)
    chk = cfg["check"]

    def window(seed, tr):
        c0 = watch.compiles + watch.hits
        win = runner.Window(sut, tr, seed, args.seconds)
        runner.sleep_until(win.t_close)
        win.collect()
        mine = win.mine()
        ok = [r for r in mine if "tokens" in r]
        ttft = [(r["recv"] - r["due"]) * 1e3 - r["timing"]["decode_ms"]
                for r in ok]
        qw = [r["timing"]["queue_wait_ms"] for r in ok]
        late_done = [r["recv"] - win.t_close for r in ok]
        return win, mine, {
            "attempted": len(mine), "failed": len(mine) - len(ok),
            **runner.end_to_end(mine, win.records,
                                (win.t_open, win.t_close),
                                len(mine) - len(ok), 0.0),
            "ttft_p50_ms": stats.percentile(ttft, 50),
            "queue_wait_p95_ms": stats.percentile(qw, 95),
            "drain_s": max(late_done) if late_done else None,
            "compiles": watch.compiles + watch.hits - c0}

    if args.rates:
        for rate in [float(r) for r in args.rates.split(",")]:
            _, _, numbers = window(seeds[0], dict(traffic, rate_rps=rate))
            emit(event="rate", rate_rps=rate, **numbers)
        return 0

    for i, seed in enumerate(seeds):
        if i:
            sut.reseed(seed)
        win, mine, numbers = window(seed, traffic)
        sampled = correct.sample_requests(
            mine, seed, int(chk.get("min_tokens", 300)),
            int(chk.get("max_requests", 16)))
        sampled = [(r, win.prompt_of(r)) for r in sampled]
        sut.release()
        t1 = time.monotonic()
        verdict = correct.check(cfg, sut.model, traffic, bounds, seed,
                                sampled)
        t2 = time.monotonic()
        reading = {k: v["value"] for k, v in verdict["numbers"].items()}
        reading.update(correct=verdict["ok"], **verdict["info"],
                       reference_s=round(t2 - t1, 3))
        for c in controls:
            low = correct.check(cfg, sut.model, traffic, bounds, seed,
                                sampled, control=c)
            for k, v in low["numbers"].items():
                reading[f"control_{c}_{k}"] = v["value"]
            reading[f"control_{c}_match"] = low["info"]["match_share"]
            reading[f"control_{c}_correct"] = low["ok"]
        emit(event="seed", seed=seed, **numbers, **reading)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
