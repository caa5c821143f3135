"""Compile watchdog: bound the first compile of every fused op.

A Mosaic compile hang is the one failure class that neither raises nor
returns (the paged-``direct`` flash-decode kernel and the fused SP
attention kernel at world 4 have both been seen to compile for minutes
without an answer). The watchdog runs a suspect thunk in a daemon
worker thread and gives it ``TDT_COMPILE_TIMEOUT_S`` to produce a
result; on expiry the caller gets :class:`CompileTimeout` and moves on.
A Python thread cannot be killed: the worker is left to finish (or
hang) in the background and its result is discarded. Only eager calls
take the hop — a call being traced stays on its own thread, where the
trace lives (``resilience.router``).

The router only routes first-time (op, config) keys through the
watchdog — a key that has compiled once cannot hang on compile again
in this process, so steady-state calls pay nothing. Timeouts default
ON on TPU (where the hang class lives) and OFF on CPU test meshes,
where interpret-mode kernels are slow-but-finite and a worker thread
per op would only add scheduling noise; ``TDT_COMPILE_TIMEOUT_S``
overrides either way (``0`` disables).
"""

from __future__ import annotations

import os
import threading

__all__ = ["CompileTimeout", "compile_timeout_s", "run_with_timeout"]

#: Default first-compile budget on TPU backends. A cold Mosaic compile
#: of a main-path kernel takes 1-7 s (tests/test_chip_compile.py) and a
#: whole 36-layer step under 90 s; 600 s is headroom over both —
#: anything past it is the hang class, not a slow compile.
DEFAULT_TPU_TIMEOUT_S = 600.0


class CompileTimeout(TimeoutError):
    """A guarded thunk exceeded its compile budget (or a
    ``compile_timeout`` fault was injected)."""

    def __init__(self, op: str, key: str = "", timeout_s: float = 0.0):
        self.op = op
        self.key = key
        self.timeout_s = timeout_s
        super().__init__(
            f"compile watchdog tripped for op {op!r} after "
            f"{timeout_s:g}s (config {key or '?'})")


def _on_tpu() -> bool:
    try:
        import jax
        return jax.default_backend() == "tpu"
    except Exception:  # noqa: BLE001 — no backend ⇒ no TPU hang class
        return False


def compile_timeout_s() -> float:
    """Effective watchdog budget in seconds; ``<= 0`` disables."""
    env = os.environ.get("TDT_COMPILE_TIMEOUT_S")
    if env is not None and env.strip():
        try:
            return float(env)
        except ValueError:
            raise ValueError(
                f"TDT_COMPILE_TIMEOUT_S must be a number: {env!r}"
            ) from None
    return DEFAULT_TPU_TIMEOUT_S if _on_tpu() else 0.0


def run_with_timeout(thunk, timeout_s: float, *, op: str = "?",
                     key: str = ""):
    """Run ``thunk()`` with a deadline; raise :class:`CompileTimeout`
    on expiry (the worker thread is abandoned, never killed).

    ``timeout_s <= 0`` calls the thunk inline. Exceptions from the
    thunk re-raise in the caller."""
    if timeout_s <= 0:
        return thunk()
    box: dict = {}
    done = threading.Event()

    def worker():
        try:
            box["out"] = thunk()
        except BaseException as e:  # noqa: BLE001 — relayed to caller
            box["exc"] = e
        finally:
            done.set()

    t = threading.Thread(target=worker, daemon=True,
                         name=f"tdt-watchdog-{op}")
    t.start()
    if not done.wait(timeout_s):
        raise CompileTimeout(op, key, timeout_s)
    if "exc" in box:
        raise box["exc"]
    return box.get("out")
