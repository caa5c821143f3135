"""Distributed-synchronized autotuner.

TPU-native redesign of the reference's ``ContextualAutoTuner``
(python/triton_dist/kernels/nvidia/autotuner.py:43-250: sweeps configs
with barriers interleaved so ALL ranks pick the same config — a rank
divergence would deadlock the fused kernels' signal protocols).

Same hazard here: shard_map programs with different tuning params on
different hosts would compile different collectives. The sweep is
SPMD-deterministic (every process times the same candidates in the same
order) and the winner is broadcast from process 0
(``multihost_utils.broadcast_one_to_all``) so divergent clocks can't
split the decision.
"""

from __future__ import annotations

import dataclasses
from collections.abc import Callable, Sequence

import jax
import numpy as np

from triton_dist_tpu.runtime.utils import perf_func

_CACHE: dict = {}


@dataclasses.dataclass(frozen=True)
class TuneResult:
    config: dict
    avg_ms: float
    all_ms: tuple


def clear_cache():
    _CACHE.clear()


def _disk_cache_path() -> str | None:
    """Persistent sweep cache (off unless ``TDT_AUTOTUNE_CACHE`` is set —
    a path, or ``1`` for the default location). Worth it on TPU, where
    each candidate costs a 20-40 s Mosaic compile; the reference's
    autotuner caches only per Autotuner instance."""
    import os
    val = os.environ.get("TDT_AUTOTUNE_CACHE")
    if not val:
        return None
    if val == "1":
        return os.path.expanduser("~/.cache/triton_dist_tpu/autotune.json")
    return os.path.expanduser(val)


#: Bump when the sweep's TIMING methodology changes materially: every
#: persisted winner under an older version must miss (a fresh sweep is
#: cheaper than serving a winner ranked by a measurement now known to
#: be wrong). v3: candidates are timed by plain enqueue +
#: block_until_ready windows (runtime/utils.perf_func).
_CACHE_VERSION = "v3"


def _disk_key(key: str) -> str:
    kind = getattr(jax.devices()[0], "device_kind", "cpu")
    return f"{_CACHE_VERSION}::{kind}::{key}"


def _disk_load(key: str) -> TuneResult | None:
    path = _disk_cache_path()
    if path is None:
        return None
    import json
    import os
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            data = json.load(f)
        ent = data.get(_disk_key(key))
        if ent is None:
            return None
        return TuneResult(
            config=dict(ent["config"]), avg_ms=float(ent["avg_ms"]),
            all_ms=tuple(float("inf") if t is None else float(t)
                         for t in ent["all_ms"]))
    except (OSError, ValueError, KeyError, TypeError):
        return None


def _disk_store(key: str, result: TuneResult) -> None:
    path = _disk_cache_path()
    if path is None or jax.process_index() != 0:
        return
    import json
    import os
    try:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        data = {}
        if os.path.exists(path):
            try:
                with open(path) as f:
                    data = json.load(f)
            except (OSError, ValueError):
                data = {}
        # Evict entries from older cache versions on rewrite: a version
        # bump means their timing methodology is known-wrong, and dead
        # winners would otherwise accumulate one generation per bump.
        data = {k: v for k, v in data.items()
                if k.startswith(_CACHE_VERSION + "::")}
        data[_disk_key(key)] = {
            "config": result.config, "avg_ms": result.avg_ms,
            "all_ms": [t if np.isfinite(t) else None
                       for t in result.all_ms]}
        tmp = path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(data, f, indent=1)
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
    except (OSError, TypeError, ValueError):
        # Persistence is best-effort and, on multi-host, runs on process
        # 0 only — raising here (e.g. a non-JSON config value) would
        # desync ranks after an otherwise successful sweep.
        pass


#: Last cost-model prune per op family: op -> (n_before, n_after).
#: Introspectable record of the search-space reduction (the acceptance
#: log for "prunes >= 4x"); the same pair lands in the obs gauges
#: ``autotune.<op>.candidates_before/after``.
LAST_PRUNE: dict[str, tuple[int, int]] = {}


def record_prune(op: str, n_before: int, n_after: int) -> None:
    """Log a cost-model candidate-table prune (perf_model.prune_configs):
    keeps the before/after counts visible in telemetry and in
    :data:`LAST_PRUNE` so sweeps can show their search-space reduction."""
    LAST_PRUNE[op] = (int(n_before), int(n_after))
    from triton_dist_tpu import obs
    if obs.enabled():
        obs.gauge(f"autotune.{op}.candidates_before").set(n_before)
        obs.gauge(f"autotune.{op}.candidates_after").set(n_after)
    import logging
    logging.getLogger("triton_dist_tpu.autotuner").info(
        "autotune %s: cost model pruned %d candidates -> %d",
        op, n_before, n_after)


_TRACE_FALLBACK_WARNED: set = set()


def consult_disk_for_trace(key: str) -> "TuneResult | None":
    """Disk-cache consult for an ``impl="auto"`` call first hit under
    jit TRACING (no eager sweep possible there).

    Two deliberate restrictions (ADVICE r4 items 1 and 4):

    - **Multi-process: always None.** The cache file may exist on only
      some hosts, and a winner applied on some ranks but not others
      would bake MISMATCHED collective programs across the deployment —
      a hang, not a slowdown. Eager ``autotune`` sweeps are rank-agreed
      (worst-rank scores + process-0 hit broadcast); this traced
      shortcut has no agreement step, so it is single-controller-only.
    - **One-time warning on a miss**, so users know the traced program
      baked the default impl for its lifetime and a later eager tune
      will not update it.
    """
    if jax.process_count() > 1:
        if key not in _TRACE_FALLBACK_WARNED:
            _TRACE_FALLBACK_WARNED.add(key)
            import warnings
            warnings.warn(
                f"impl='auto' for {key!r} hit under jit tracing in a "
                "multi-process deployment: using the default impl on "
                "every rank (the per-host disk cache is not consulted "
                "— divergent winners would hang collectives). Tune "
                "eagerly once before jit to pick a measured winner.",
                stacklevel=3)
        return None
    hit = _disk_load(key)
    if hit is None and key not in _TRACE_FALLBACK_WARNED:
        _TRACE_FALLBACK_WARNED.add(key)
        import warnings
        warnings.warn(
            f"impl='auto' for {key!r} was first reached under jit "
            "tracing with no cached winner: the traced program bakes "
            "the default impl for its LIFETIME (a later eager tune "
            "cannot update it). Run one eager call first to tune.",
            stacklevel=3)
    return hit


def autotune(make_fn: Callable[..., Callable], configs: Sequence[dict],
             key: str | None = None, iters: int = 20,
             warmup_iters: int = 5,
             vet: Callable[[dict], "str | None"] | None = None
             ) -> TuneResult:
    """Pick the fastest config.

    Args:
      make_fn: config-kwargs → zero-arg callable running the op (the
        analog of re-launching the Triton kernel per config).
      configs: candidate dicts (reference per-op config tables, e.g.
        ``matmul_get_configs`` allgather_gemm.py:396).
      key: cache key — one sweep per key per process (reference caches on
        the Autotuner instance).
      vet: optional static candidate gate (config → rejection reason or
        None), e.g. ``perf_model.vet_vmem`` bound to the sweep shape.
        Rejected candidates never reach ``make_fn`` — no compile is
        invoked for them (``autotune.candidates_rejected_static``;
        docs/analysis.md "vmem-budget"). Deterministic, so every rank
        rejects the same set and the sweep stays SPMD-agreed.
    Returns the winning TuneResult (same on every process).

    Failure isolation: a config that raises scores inf (skipped, like
    the reference's OutOfResources handling). On multi-host sweeps the
    per-config scores are agreed as the WORST rank's time, so a config
    failing anywhere loses everywhere; note that a non-SPMD-deterministic
    failure (raising on only some ranks mid-collective) can still desync
    the sweep itself — only configs whose failures are deterministic
    across ranks are fully safe to list.
    """
    from triton_dist_tpu import obs
    if vet is not None:
        # BEFORE any cache consult: a persisted winner from a sweep
        # that predates the vet (or a footprint-model fix) must fail
        # the staleness membership check below against the VETTED
        # list, not be resurrected unvetted. Deterministic, so every
        # rank rejects the same set and the sweep stays SPMD-agreed.
        kept = []
        for cfg in configs:
            reason = vet(dict(cfg))
            if reason is None:
                kept.append(cfg)
                continue
            import logging
            logging.getLogger("triton_dist_tpu.autotuner").warning(
                "autotune %s: candidate rejected statically: %s",
                key, reason)
            if obs.enabled():
                obs.counter("autotune.candidates_rejected_static").inc()
        if not kept:
            raise ValueError(
                f"autotune {key!r}: every candidate was rejected by "
                f"the static vet — the config table and the vet "
                f"disagree (docs/analysis.md)")
        configs = kept
    if key is not None and key in _CACHE:
        return _CACHE[key]
    if key is not None:
        hit = _disk_load(key)
        # A persisted winner that is no longer in the candidate list is
        # stale (the config table changed — e.g. a tightened VMEM-budget
        # filter excluded it): fall through to a fresh sweep rather than
        # resurrect a config the current filter rejects.
        if hit is not None and hit.config not in [dict(c) for c in configs]:
            hit = None
        if jax.process_count() > 1:
            # The hit/miss decision must be AGREED, not per-process: the
            # cache file may exist on only some hosts, and a partial hit
            # would leave the missing ranks blocking in the sweep's
            # process_allgather forever. Process 0 decides; the winner
            # index + time are broadcast (configs are identical and
            # identically ordered on every process by construction).
            from jax.experimental import multihost_utils
            idx = -1.0
            avg = float("nan")
            allms = [float("nan")] * len(configs)
            if hit is not None and jax.process_index() == 0:
                idx = float(next(i for i, c in enumerate(configs)
                                 if dict(c) == hit.config))
                avg = hit.avg_ms
                # keep the per-config scores (incl. inf losers) so the
                # TuneResult contract matches the single-host hit
                for i, t in enumerate(hit.all_ms[:len(configs)]):
                    allms[i] = t
            agreed = np.asarray(multihost_utils.broadcast_one_to_all(
                np.asarray([idx, avg] + allms, np.float64)))
            if agreed[0] >= 0:
                hit = TuneResult(config=dict(configs[int(agreed[0])]),
                                 avg_ms=float(agreed[1]),
                                 all_ms=tuple(float(t)
                                              for t in agreed[2:]))
            else:
                hit = None
        if hit is not None:
            _CACHE[key] = hit
            return hit

    times = []
    errors = []
    for cfg in configs:
        # A config that fails to compile/run (e.g. VMEM overflow on this
        # chip generation) scores inf instead of killing the sweep — the
        # reference's Triton autotuner likewise skips OutOfResources
        # configs. This keeps aggressive candidates safe to list.
        # Each candidate is a span: a sweep that wedges on one Mosaic
        # compile leaves that candidate's un-ended begin (with its
        # exact config) in the flight record.
        with obs.span("autotune.candidate", cat="op",
                      args={"key": key, **{k: v for k, v in cfg.items()
                                           if isinstance(v, (int, str,
                                                             bool))}}):
            try:
                fn = make_fn(**cfg)
                _, ms = perf_func(fn, iters=iters,
                                  warmup_iters=warmup_iters,
                                  return_output=False)
            except Exception as e:  # noqa: BLE001 — per-config isolation
                ms = float("inf")
                errors.append((cfg, repr(e)[:200]))
        times.append(ms)

    if jax.process_count() > 1:
        # Agree on scores BEFORE picking: a config that failed on ANY
        # rank must lose everywhere (worst-rank time), and the cached
        # avg_ms must be the agreed number, not this rank's local inf
        # (code-review r3d findings 1/4). Residual hazard documented
        # above: a config failing on only SOME ranks may already have
        # desynced the sweep itself — per-config isolation is fully safe
        # only where failures are SPMD-deterministic.
        from jax.experimental import multihost_utils
        allt = np.asarray(multihost_utils.process_allgather(
            np.asarray(times, np.float64)))
        times = list(allt.reshape(jax.process_count(), -1).max(axis=0))
    if not np.isfinite(times).any():
        raise RuntimeError(f"every autotune config failed: {errors}")
    best = int(np.argmin(times))
    result = TuneResult(config=dict(configs[best]), avg_ms=times[best],
                        all_ms=tuple(times))
    if key is not None:
        _CACHE[key] = result
        _disk_store(key, result)
    return result
