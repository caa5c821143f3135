"""Fallback router: every fused op keeps an always-available escape
hatch to its XLA reference path.

Triton-distributed itself treats the hand-written overlapped kernel as
one routing choice among several per shape/topology (arXiv:2504.19442
§5), and T3-style transparent overlap (arXiv:2401.16677) presumes a
safe non-fused path always exists. This module makes that stance
structural: the :func:`resilient` decorator wraps every public op
entry in ``ops/`` and, per call, chooses between the fused
implementation and the op's ``impl="xla"`` reference branch — the same
function, same arguments, different ``impl`` — so a fallback is
bit-identical to calling the reference path directly.

Routing order (first match wins), per (op, config, device_kind):

1. ``TDT_FORCE_FUSED=1``    → fused, always (smoke / manual
   revalidation; the watchdog still guards the compile).
2. known-bad cache hit      → XLA (``resilience.knownbad`` — a config
   that ever hung Mosaic is never re-entered, across processes).
3. open circuit breaker     → XLA until the cooldown's half-open probe
   (``resilience.breaker``).
4. otherwise                → fused, guarded: first-compile runs under
   the watchdog (``resilience.watchdog``), infra failures (Mosaic /
   XLA runtime errors, injected faults, watchdog trips, optional
   non-finite-output guard) record into the breaker + known-bad cache
   and the call retries on the XLA path. User errors (bad shapes,
   unsupported compositions: ``ValueError`` / ``AssertionError`` /
   ``NotImplementedError`` / ``TypeError``) propagate unchanged.

Everything here is Python-side and works at trace time too — under
``jax.jit`` the routing decision is baked into the traced program
(like the ``comms.*`` counters, it is per program build; a breaker
that opens later does not rewrite already-compiled programs).

Metric surface (docs/observability.md): ``resilience.fallbacks_total``,
``resilience.<op>.fallbacks_total`` / ``.fallback.<reason>`` /
``.fused_total``, ``resilience.watchdog.trips`` /
``resilience.<op>.watchdog_trips``, breaker + known-bad gauges.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import inspect
import os
import threading

from triton_dist_tpu import obs
from triton_dist_tpu.resilience import knownbad
from triton_dist_tpu.resilience.breaker import get_breaker
from triton_dist_tpu.resilience.watchdog import (CompileTimeout,
                                                 compile_timeout_s,
                                                 run_with_timeout)

__all__ = ["FallbackSpec", "NonFiniteOutput", "decide", "device_kind",
           "force_fused", "registered_fallbacks",
           "resilient", "reset_router"]


class NonFiniteOutput(RuntimeError):
    """The numeric guard (``TDT_NUMERIC_GUARD=1``) found NaN/inf in a
    fused op's eager output. Infra-class: the call is retried on the
    XLA reference path and the breaker records the failure."""

    def __init__(self, op: str):
        self.op = op
        super().__init__(
            f"fused op {op!r} produced non-finite outputs")


# ---------------------------------------------------------------------------
# Registry: which entries have an escape hatch (tools/fallback_lint.py
# cross-checks this against the public surface of ops/).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FallbackSpec:
    op: str
    entry: str                      # "module.qualname" of the entry fn
    fused_impls: tuple[str, ...]
    fallback_impl: str


_REGISTRY: dict[str, FallbackSpec] = {}


def registered_fallbacks() -> dict[str, FallbackSpec]:
    return dict(_REGISTRY)


# ---------------------------------------------------------------------------
# Environment / platform probes (read per call so tests can monkeypatch).
# ---------------------------------------------------------------------------

def force_fused() -> bool:
    """``TDT_FORCE_FUSED=1``: bypass all routing, always run fused
    (tpu_smoke.py sets this — a smoke run that silently exercised XLA
    would be worse than one that fails)."""
    return os.environ.get("TDT_FORCE_FUSED", "").strip() in (
        "1", "true", "yes")


def _numeric_guard_enabled() -> bool:
    return os.environ.get("TDT_NUMERIC_GUARD", "").strip() in (
        "1", "true", "yes")


_DEVICE_KIND: str | None = None


def device_kind() -> str:
    """``device_kind`` of device 0 (the known-bad cache's third key
    field — a config that hangs v5e Mosaic may be fine on v5p)."""
    global _DEVICE_KIND
    if _DEVICE_KIND is None:
        try:
            import jax
            d = jax.devices()[0]
            _DEVICE_KIND = str(getattr(d, "device_kind", d.platform))
        except Exception:  # noqa: BLE001 — no backend yet
            return "unknown"
    return _DEVICE_KIND


# ---------------------------------------------------------------------------
# The routing decision.
# ---------------------------------------------------------------------------

def decide(op: str, key: str) -> str | None:
    """None → run fused; otherwise the fallback reason string."""
    if force_fused():
        return None
    if key in knownbad.get_cache():
        return "known_bad"
    if not get_breaker(op).allow():
        return "breaker"
    return None


def count_fallback(op: str, reason: str) -> None:
    """One fallback of ``op`` to its XLA branch, by reason. Public for
    the op entries that degrade by themselves, inside the fused branch
    and so out of the router's sight (``gemm_ar`` without a feasible
    all-gather epilogue): they count here at trace time, once per
    program build, like ``obs.record_comm``."""
    obs.counter("resilience.fallbacks_total").inc()
    obs.counter(f"resilience.{op}.fallbacks_total").inc()
    obs.counter(f"resilience.{op}.fallback.{reason}").inc()
    obs.trace.instant(f"resilience.{op}.fallback", "resilience",
                      args={"op": op, "reason": reason})


def _record_failure(op: str, key: str, config: str, exc) -> None:
    get_breaker(op).record_failure()
    obs.trace.instant(f"resilience.{op}.failure", "resilience",
                      args={"op": op, "type": type(exc).__name__,
                            "config": config[:200]})
    if isinstance(exc, CompileTimeout):
        obs.counter("resilience.watchdog.trips").inc()
        obs.counter(f"resilience.{op}.watchdog_trips").inc()
        knownbad.get_cache().record(op, config, device_kind(),
                                    reason=f"compile_timeout: {exc}")
        # A hang postmortem: dump the trailing event window — what ran
        # in the seconds before this compile wedged — to disk
        # (docs/observability.md "Flight recorder"; rate-limited,
        # never raises, no-op when tracing is off).
        obs.flight.maybe_dump(f"watchdog_{op}")
    elif _is_compile_error(exc):
        # Deterministic compiler breaks (Mosaic rejection, Pallas
        # lowering failure) re-break on every process restart — record
        # them like hangs so no process re-enters the compile, instead
        # of each one burning breaker-threshold attempts rediscovering
        # it (runtime errors stay out: they may be transient).
        knownbad.get_cache().record(
            op, config, device_kind(),
            reason=f"compile_error: {type(exc).__name__}: "
                   f"{str(exc)[:200]}")


#: Exception type names treated as infra failures when raised from a
#: fused path. Matched by name: the concrete classes live in jaxlib /
#: Mosaic modules whose import paths move between jax versions.
_INFRA_EXC_NAMES = frozenset({
    "XlaRuntimeError", "JaxRuntimeError", "InternalError",
    "MosaicError", "LoweringError", "LoweringException",
    "VerificationError",
})

#: The deterministic-compiler-break subset of the infra classes: these
#: reproduce on every compile of the config, so they join watchdog
#: trips in the known-bad cache.
_COMPILE_EXC_NAMES = frozenset({
    "MosaicError", "LoweringError", "LoweringException",
    "VerificationError",
})


def _is_compile_error(e: BaseException) -> bool:
    t = type(e)
    return (t.__name__ in _COMPILE_EXC_NAMES
            or "mosaic" in (t.__module__ or "").lower())


def _is_infra_error(e: BaseException) -> bool:
    from triton_dist_tpu.testing.faults import InjectedFault
    if isinstance(e, (CompileTimeout, InjectedFault, NonFiniteOutput)):
        return True
    t = type(e)
    if t.__name__ in _INFRA_EXC_NAMES:
        return True
    mod = (t.__module__ or "").lower()
    return "mosaic" in mod


# ---------------------------------------------------------------------------
# The @resilient decorator.
# ---------------------------------------------------------------------------

_TLS = threading.local()

#: (op, config, device_kind) keys that have completed a fused run in
#: this process — later calls skip the watchdog thread (a key that
#: compiled once cannot hang on compile again).
_COMPILED: set[str] = set()


def _in_resilient() -> bool:
    return getattr(_TLS, "depth", 0) > 0


class _Reentrant:
    """Nested op entries (ag_gemm → ag_gemm_multi, paged → gathered
    decode, autotune sweeps) run under the outer guard only."""

    def __enter__(self):
        _TLS.depth = getattr(_TLS, "depth", 0) + 1
        return self

    def __exit__(self, *exc):
        _TLS.depth -= 1
        return False


#: Context fields worth distinguishing in a config key: the knobs that
#: select a kernel variant / tile schedule (the things a compile hang
#: depends on).
_CTX_KEY_FIELDS = ("variant", "paged_variant", "method", "block_m",
                   "block_n", "block_k", "t_blk", "ring_dirs",
                   "vmem_budget")


def _default_config(bound: inspect.BoundArguments,
                    env_keys: tuple[str, ...] = ()) -> str:
    parts = []
    for name, v in bound.arguments.items():
        if hasattr(v, "shape") and hasattr(v, "dtype"):
            parts.append(f"{name}={tuple(v.shape)}:{v.dtype}")
        elif (isinstance(v, (list, tuple)) and v
              and all(hasattr(e, "shape") and hasattr(e, "dtype")
                      for e in v)):
            # ag_gemm_multi-style operand lists.
            parts.append(name + "=[" + ";".join(
                f"{tuple(e.shape)}:{e.dtype}" for e in v) + "]")
        elif dataclasses.is_dataclass(v) and not isinstance(v, type):
            for fld in _CTX_KEY_FIELDS:
                if hasattr(v, fld):
                    fv = getattr(v, fld)
                    if isinstance(fv, (int, str, bool, type(None))):
                        parts.append(f"{fld}={fv}")
        elif isinstance(v, (int, str, bool)) or v is None:
            parts.append(f"{name}={v}")
    for k in env_keys:
        # Variant-selecting env overrides (TDT_PAGED_VARIANT,
        # TDT_RING_DIRS): when ctx is None the entry builds a default
        # context AFTER this key is computed, so the env override is
        # the only visible variant selector — without it a hang in one
        # variant would share a key with (and wrongly route) the other.
        ev = os.environ.get(k)
        if ev:
            parts.append(f"{k}={ev}")
    return ",".join(parts)


def _is_tracing(bound: inspect.BoundArguments) -> bool:
    """True when this call is being staged rather than executed: some
    argument is a tracer, or the thread is inside a trace (``jit``,
    ``export``, ``shard_map``) whose operands the op closed over. The
    ambient trace is thread-local, so a traced call must never hop to
    the watchdog thread — there it would run eagerly on the default
    backend instead of being staged."""
    import jax
    if not jax.core.trace_ctx.is_top_level():
        return True
    for v in bound.arguments.values():
        for leaf in jax.tree_util.tree_leaves(v):
            if isinstance(leaf, jax.core.Tracer):
                return True
    return False


def _op_annotation(op: str, impl, fallback_impl):
    """xprof ``TraceAnnotation`` labeling this invocation's branch —
    ``device.<op>.fused`` / ``device.<op>.xla`` — the label
    ``obs.devprof`` attributes measured device time by (an eager call
    brackets real execution; under jit it brackets trace time, like
    the ``comms.*`` counters). Must never break the call: degrades to
    a null context when the profiler side is unavailable. The
    annotation-coverage pass (``tdt-check``) statically verifies this
    wrapper stays on the invocation path — without it the parser
    silently books every op's device time as ``device.unlabeled_ms``."""
    try:
        from triton_dist_tpu.tools.profiler import annotate
        branch = "xla" if impl == fallback_impl else "fused"
        return annotate(f"device.{op}.{branch}")
    except Exception:  # noqa: BLE001 — labeling is observation only
        return contextlib.nullcontext()


def _all_finite(out) -> bool:
    from triton_dist_tpu.runtime.utils import tree_all_finite
    return tree_all_finite(out)


def _nan_fill(out):
    import jax
    import jax.numpy as jnp

    def fill(leaf):
        if isinstance(leaf, jax.Array) and jnp.issubdtype(
                leaf.dtype, jnp.floating):
            return jnp.full_like(leaf, jnp.nan)
        return leaf

    return jax.tree_util.tree_map(fill, out)


def resilient(op: str, *, fused_impls: tuple[str, ...] = ("pallas",),
              fallback_impl: str = "xla", config_fn=None,
              env_keys: tuple[str, ...] = ()):
    """Wrap an op entry with watchdog + breaker + fallback routing.

    The entry must take an ``impl`` parameter whose ``fallback_impl``
    value selects the jax.lax/XLA reference path. Calls whose ``impl``
    is not in ``fused_impls`` (already on the reference path, or on a
    collective-composition impl like sp_attention's ``ring``) pass
    through untouched. ``config_fn(bound_arguments) -> str`` overrides
    the default shape/dtype/ctx-field config key; ``env_keys`` folds
    the named env vars into the default key (variant selectors that
    bypass the ctx object)."""

    def deco(fn):
        sig = inspect.signature(fn)
        _REGISTRY[op] = FallbackSpec(
            op=op, entry=f"{fn.__module__}.{fn.__qualname__}",
            fused_impls=tuple(fused_impls), fallback_impl=fallback_impl)

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if _in_resilient():
                return fn(*args, **kwargs)
            try:
                bound = sig.bind(*args, **kwargs)
                bound.apply_defaults()
            except TypeError:
                # Let the entry raise its own signature error.
                return fn(*args, **kwargs)
            if bound.arguments.get("impl") not in fused_impls:
                return fn(*args, **kwargs)
            config = (config_fn(bound) if config_fn
                      else _default_config(bound, env_keys))
            key = knownbad.make_key(op, config, device_kind())

            def call(impl):
                # Fresh binding per invocation: an abandoned watchdog
                # worker still running the fused call must not share
                # mutable argument state with the main thread's
                # fallback re-invocation (a shared impl slot could
                # race the fallback back onto the fused path).
                b = sig.bind(*args, **kwargs)
                b.apply_defaults()
                b.arguments["impl"] = impl
                with _Reentrant(), \
                        _op_annotation(op, impl, fallback_impl):
                    return fn(*b.args, **b.kwargs)

            reason = decide(op, key)
            if reason is not None:
                count_fallback(op, reason)
                return call(fallback_impl)
            return _guarded(op, key, config, call,
                            bound, fallback_impl)

        wrapper.__tdt_resilient_op__ = op
        return wrapper

    return deco


def _guarded(op, key, config, call, bound, fallback_impl):
    """Run the fused path with watchdog + fault hooks; on an infra
    failure, record it and retry on the reference path."""
    from triton_dist_tpu.testing import faults

    fused_impl = bound.arguments["impl"]
    obs.counter(f"resilience.{op}.fused_total").inc()
    tracing = _is_tracing(bound)
    timeout = compile_timeout_s()
    try:
        f = faults.take("comm_error", op) if faults.active() else None
        if f is not None:
            raise faults.InjectedFault(f"{f.message} (op {op})")
        f = (faults.take("compile_timeout", op)
             if faults.active() else None)
        if f is not None:
            raise CompileTimeout(op, key, 0.0)
        if not tracing and timeout > 0 and key not in _COMPILED:

            def thunk():
                # Runs in the watchdog worker thread; call() re-enters
                # the reentrancy guard on that thread's own stack.
                hang = (faults.take("compile_hang", op)
                        if faults.active() else None)
                if hang is not None:
                    import time
                    time.sleep(hang.hang_s)
                return call(fused_impl)

            out = run_with_timeout(thunk, timeout, op=op, key=key)
        else:
            out = call(fused_impl)
        if not tracing:
            f = (faults.take("nan_payload", op)
                 if faults.active() else None)
            if f is not None:
                out = _nan_fill(out)
            if _numeric_guard_enabled() and not _all_finite(out):
                raise NonFiniteOutput(op)
    except Exception as e:  # noqa: BLE001 — classified below
        if not _is_infra_error(e):
            raise
        _record_failure(op, key, config, e)
        if force_fused():
            # Smoke runs set TDT_FORCE_FUSED precisely so a run can
            # never silently exercise the XLA fallback while claiming
            # to exercise the fused kernel — the failure is recorded
            # (breaker, known-bad, counters) and then SURFACES.
            raise
        reason = ("watchdog" if isinstance(e, CompileTimeout)
                  else "nonfinite" if isinstance(e, NonFiniteOutput)
                  else "error")
        count_fallback(op, reason)
        return call(fallback_impl)
    if not tracing:
        # Only a real execution proves anything: a successful TRACE
        # must neither mark the key compiled (the genuine first Mosaic
        # compile — the hang class — comes later and must stay under
        # the watchdog) nor close a half-open breaker.
        _COMPILED.add(key)
        get_breaker(op).record_success()
    return out


def reset_router() -> None:
    """Drop router process state (tests): compiled-key set, breakers,
    known-bad singleton. The fallback registry is code-derived and
    survives."""
    from triton_dist_tpu.resilience.breaker import reset_breakers
    _COMPILED.clear()
    reset_breakers()
    knownbad.reset_cache()
