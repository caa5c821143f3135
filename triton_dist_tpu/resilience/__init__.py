"""Resilience subsystem: compile watchdog, circuit-breaker fallback
routing, and the known-bad config cache.

Round 5 proved the stack can reach the chip but not survive it: one
Mosaic compile hang (the paged flash-decode ``direct`` kernel) wedged
the hardware queue for the rest of the round, and the fused ops that
measure slower than XLA had no automatic escape hatch. This package
makes a bad kernel config degrade a *request*, never the process:

- ``resilience.watchdog`` — bounded first-compile of every fused op
  (``TDT_COMPILE_TIMEOUT_S``); a trip lands the exact (op, config,
  device_kind) tuple in the on-disk known-bad cache.
- ``resilience.knownbad`` — cross-process cache of configs that ever
  hung or broke the compiler; the router never re-enters them.
- ``resilience.breaker``  — per-op circuit breakers
  (closed → open → half-open → closed).
- ``resilience.router``   — the ``@resilient`` decorator on every
  public op entry in ``ops/``: routes to each op's ``impl="xla"``
  reference path on known-bad hits or an open breaker, and converts
  fused infra failures into recorded fallbacks. ``TDT_FORCE_FUSED=1``
  bypasses routing (smoke).

Fault injection for all of the above lives in
``triton_dist_tpu.testing.faults``; policies and env knobs are
documented in docs/resilience.md, metrics in docs/observability.md.
"""

from triton_dist_tpu.resilience.breaker import (  # noqa: F401
    CLOSED,
    HALF_OPEN,
    OPEN,
    CircuitBreaker,
    all_breakers,
    get_breaker,
    reset_breakers,
)
from triton_dist_tpu.resilience.knownbad import (  # noqa: F401
    KnownBadCache,
    get_cache as known_bad_cache,
    make_key as known_bad_key,
)
from triton_dist_tpu.resilience.router import (  # noqa: F401
    FallbackSpec,
    NonFiniteOutput,
    count_fallback,
    decide,
    device_kind,
    force_fused,
    registered_fallbacks,
    resilient,
    reset_router,
)
from triton_dist_tpu.resilience.watchdog import (  # noqa: F401
    CompileTimeout,
    compile_timeout_s,
    run_with_timeout,
)


def reset_for_tests() -> None:
    """Reset every piece of process-local resilience state (breakers,
    compiled-key set, known-bad singleton)."""
    reset_router()
