"""Platform probing and execution-mode defaults.

The reference selects a backend (NVIDIA/AMD) at compile time
(backends/nvidia/backend/compiler.py). On TPU there is one hardware target,
but we support two execution modes for every Pallas kernel:

- compiled (Mosaic) on real TPU devices;
- interpreted (``pltpu.InterpretParams``) on a forced-multi-device CPU mesh,
  which simulates remote DMAs and semaphores. This is the single-process
  multi-"rank" test spine that the reference lacks (SURVEY.md §4 TPU
  translation note).
"""

from __future__ import annotations

import functools

import jax


@functools.cache
def backend_platform() -> str:
    return jax.devices()[0].platform


def is_tpu() -> bool:
    return backend_platform() == "tpu"


def is_cpu() -> bool:
    return backend_platform() == "cpu"


def default_interpret() -> bool:
    """Interpret Pallas TPU kernels when not running on real TPU hardware.

    ``TDT_FORCE_COMPILED=1`` forces the compiled path regardless of the
    backend — used by the export-lint mode (tpu_smoke --export-lint),
    which lowers every kernel FOR the tpu platform on a CPU host to run
    the Pallas→Mosaic verifier without executing anything."""
    import os
    if os.environ.get("TDT_FORCE_COMPILED") == "1":
        return False
    return not is_tpu()
