"""Bench / verify / logging helpers.

TPU-native analogs of the reference's host utilities
(python/triton_dist/utils.py): ``perf_func`` (:274), ``dist_print`` (:289),
``assert_allclose`` (:870), ``init_seed`` (:77).
"""

from __future__ import annotations

import sys
import time
from collections.abc import Callable

import jax
import jax.numpy as jnp
import numpy as np


def init_seed(seed: int = 42) -> jax.Array:
    """Deterministic seeding (reference utils.py:77-96). Returns a JAX PRNG
    key; numpy is seeded for host-side golden generation."""
    np.random.seed(seed)
    return jax.random.PRNGKey(seed)


def tree_all_finite(tree) -> bool:
    """Every floating jax.Array leaf of ``tree`` is NaN/inf-free.

    The one shared finiteness walk (resilience numeric guard,
    tpu_smoke result scoring): blocks on the leaves, casts to f32 so
    bf16/f16 reduce without surprises."""
    import jax.numpy as jnp
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array) and jnp.issubdtype(
                leaf.dtype, jnp.floating):
            if not bool(jnp.isfinite(leaf.astype(jnp.float32)).all()):
                return False
    return True


def _block(tree) -> None:
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            leaf.block_until_ready()


def perf_func(
    func: Callable,
    iters: int = 50,
    warmup_iters: int = 10,
    return_output: bool = True,
):
    """Time a JAX function with proper device synchronization.

    Analog of reference ``perf_func`` (utils.py:274-288, CUDA-event based).
    Warm up, enqueue ``iters`` calls, ``block_until_ready`` the last
    output inside the timed region. Returns ``(output, avg_ms)``.
    """
    out = None
    for _ in range(max(warmup_iters, 1)):
        out = func()
    _block(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = func()
    _block(out)
    avg_ms = (time.perf_counter() - t0) / iters * 1e3
    if return_output:
        return out, avg_ms
    return None, avg_ms


def perf_func_chained(step: Callable, x0, iters: tuple[int, int] = (20, 60)):
    """Time ``x = step(x)`` per iteration over chained windows, each
    ended by ``block_until_ready``: the chain serializes the steps, so
    a window's wall time is the sum of its steps. Returns avg ms per
    step.

    Min of 5 windows, escalating the chain until one window carries
    >= ~20 ms of signal: a single sub-ms window on a loaded shared
    host spreads 3-4x run to run, and min() is the right estimator for
    "cost without preemption". ``iters[1]`` is the first window's
    chain length.
    """
    _block(step(x0))

    def run(n: int) -> float:
        x = x0
        t0 = time.perf_counter()
        for _ in range(n):
            x = step(x)
        _block(x)
        return time.perf_counter() - t0

    _, n2 = iters
    t = run(n2)
    while t < 0.02 and n2 < 2000:
        n2 = min(n2 * 4, 2000)
        t = run(n2)
    samples = [t / n2]
    # Re-target the chain to a ~40 ms window for the remaining samples:
    # a slow (interpret-mode) step's window can carry seconds, and four
    # more full-size windows would multiply the CPU bench wall ~5x for
    # no extra noise rejection.
    n2 = max(2, min(n2, int(round(0.04 / max(samples[0], 1e-9)))))
    for _ in range(4):
        samples.append(run(n2) / n2)
    return min(samples) * 1e3


def timing_selfcheck(iters: tuple[int, int] = (8, 24)) -> dict:
    """Calibrate :func:`perf_func_chained` against a known-FLOPs matmul.

    Runs a chained (2048x4096)@(4096x4096) bf16 dot and reports the
    implied TFLOPS; ``ok`` is False when the number exceeds the chip's
    physical bf16 peak — i.e. the timing path is broken and every other
    number from this process is suspect.
    """
    m = k = 4096
    n = 2048
    a = jax.random.normal(jax.random.PRNGKey(0), (n, m),
                          jnp.float32).astype(jnp.bfloat16)
    b = jax.random.normal(jax.random.PRNGKey(1), (m, k),
                          jnp.float32).astype(jnp.bfloat16)

    @jax.jit
    def step(x):
        y = jnp.dot(x, b, preferred_element_type=jnp.float32)
        return (y * jnp.asarray(2.0 ** -6, jnp.float32)).astype(x.dtype)

    ms = perf_func_chained(step, a, iters)
    tflops = 2.0 * n * m * k / (ms * 1e-3) / 1e12
    from triton_dist_tpu.tools.perf_model import get_chip_spec
    spec = get_chip_spec()   # raises on an accelerator it does not know
    peak = spec.bf16_tflops
    return {"calib_ms": round(ms, 4), "calib_tflops": round(tflops, 1),
            "peak_tflops": peak, "ok": bool(tflops <= 1.05 * peak)}


def dist_print(*args, prefix: bool = True, need_sync: bool = False,
               allowed_ranks="all", **kwargs) -> None:
    """Per-process-prefixed printing (reference ``dist_print`` utils.py:289).

    ``allowed_ranks`` filters by ``jax.process_index()`` (host granularity —
    per-device printing from inside jitted code uses ``jax.debug.print``).
    ``need_sync`` serializes output across processes: each rank prints in
    turn with a global barrier between turns (reference behavior).
    """
    rank = jax.process_index()
    world = jax.process_count()
    if allowed_ranks == "all":
        allowed = range(world)
    else:
        allowed = allowed_ranks

    def _emit():
        if rank in allowed:
            if prefix:
                print(f"[rank {rank}/{world}]", *args, **kwargs)
            else:
                print(*args, **kwargs)
            sys.stdout.flush()

    if need_sync and world > 1:
        from jax.experimental import multihost_utils
        for r in range(world):
            if rank == r:
                _emit()
            multihost_utils.sync_global_devices(f"dist_print_{r}")
    else:
        _emit()


def assert_allclose(x, y, rtol: float = 1e-2, atol: float = 1e-2,
                    verbose: bool = True) -> None:
    """Structured allclose with mismatch diagnostics (reference
    ``assert_allclose`` utils.py:870-886)."""
    x = np.asarray(jax.device_get(x), dtype=np.float64)
    y = np.asarray(jax.device_get(y), dtype=np.float64)
    if x.shape != y.shape:
        raise AssertionError(f"shape mismatch: {x.shape} vs {y.shape}")
    close = np.isclose(x, y, rtol=rtol, atol=atol)
    if not close.all():
        bad = np.argwhere(~close)
        n = bad.shape[0]
        msg = [f"allclose failed: {n}/{x.size} mismatched "
               f"(rtol={rtol}, atol={atol})"]
        if verbose:
            for idx in bad[:10]:
                i = tuple(idx)
                msg.append(f"  at {i}: {x[i]!r} vs {y[i]!r}")
            abs_err = np.abs(x - y)
            msg.append(f"  max abs err {abs_err.max():.3e}, "
                       f"mean abs err {abs_err.mean():.3e}")
        raise AssertionError("\n".join(msg))


def bitwise_equal(x, y) -> bool:
    """Bitwise comparison used to gate deterministic collectives
    (SURVEY.md §7 stage-2 gate)."""
    x = np.asarray(jax.device_get(x))
    y = np.asarray(jax.device_get(y))
    return x.shape == y.shape and bool(
        np.array_equal(x.view(np.uint8), y.view(np.uint8)))


def rand(key, shape, dtype=jnp.float32, scale: float = 1.0) -> jax.Array:
    """Test-data helper: normal data cast to ``dtype``."""
    return (jax.random.normal(key, shape) * scale).astype(dtype)
