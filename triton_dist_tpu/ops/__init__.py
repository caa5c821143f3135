"""Overlapping kernel library (reference L5: python/triton_dist/kernels/).

Every op follows the reference's API shape: a ``create_*_context`` builder
that allocates persistent workspaces/configs, plus a functional entry point
(e.g. ``ag_gemm``, ``gemm_rs``, ``all_reduce``, ``fast_all_to_all``).

Each op has (at least) two implementations:

- ``impl="xla"``  — shard_map + ``jax.lax`` collectives. Always correct;
  XLA's async collective scheduler provides coarse overlap. This is also
  the golden baseline, like the reference's torch/NCCL goldens.
- ``impl="pallas"`` — fused Pallas kernel with explicit remote DMA /
  semaphore overlap (compiled on TPU, interpreted on CPU meshes).

Resilience contract (docs/resilience.md): every public entry here
wears the ``@resilient`` decorator, registering its ``impl="xla"``
branch as the always-available escape hatch — the router diverts
known-bad configs and open-breaker ops to it, and retries fused infra
failures on it with bit-identical numerics.
``tools/fallback_lint.py`` (quick tier) rejects any new entry that
ships without one.
"""
