"""Where the persistent XLA compilation cache lives — the one rule every
entry point (``chip_smoke.py``, ``tdt-serve``, ``tdt-finetune``,
``tpu_smoke.py``) applies before its first compile.

The path is part of the cache key, so it must never move between runs:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing is set
  in code, so whoever runs the program decides where the cache goes.
- not set: ``<checkout>/.jax_cache`` — fixed relative to the code, never
  a temporary name, a pid or a time.

``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` is left at JAX's default:
a serving cold start is dozens of 1-3 s kernel and admission-bucket
programs, and a higher threshold would cache none of them.
"""

from __future__ import annotations

import os

import jax

_ENV = "JAX_COMPILATION_CACHE_DIR"


def configure_compile_cache() -> str:
    """Apply the rule above; returns the cache directory in effect."""
    path = os.environ.get(_ENV)
    if path:
        return path
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

