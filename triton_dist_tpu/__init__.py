"""triton_dist_tpu — a TPU-native framework for compute–communication
overlapping distributed kernels.

A from-scratch re-design (JAX / XLA / Pallas / shard_map over ICI/DCN meshes)
of the capabilities of Triton-distributed (reference: ByteDance-Seed
Triton-distributed, see SURVEY.md). The layering mirrors the reference:

- ``triton_dist_tpu.language``  — device-side one-sided communication and
  signal primitives usable inside Pallas kernels (reference L3:
  python/triton_dist/language/distributed_ops.py,
  language/extra/libshmem_device.py).
- ``triton_dist_tpu.runtime``   — host distributed runtime: mesh init,
  symmetric buffers, bench/verify helpers, topology (reference L4:
  python/triton_dist/utils.py).
- ``triton_dist_tpu.ops``       — the overlapping kernel library: AG-GEMM,
  GEMM-RS, AllReduce, EP AllToAll, MoE, distributed flash-decode,
  SP attention (reference L5: python/triton_dist/kernels/nvidia/).
- ``triton_dist_tpu.parallel``  — TP/EP/SP model layers (reference L6:
  python/triton_dist/layers/nvidia/).
- ``triton_dist_tpu.models``    — Qwen3-class dense + MoE models, KV cache,
  inference engine (reference L7: python/triton_dist/models/).
- ``triton_dist_tpu.mega``      — fused whole-decoder-step runtime
  (reference L8: python/triton_dist/mega_triton_kernel/).
- ``triton_dist_tpu.tools``     — AOT export, profiling (reference L9:
  python/triton_dist/tools/).

Unlike the reference (CUDA/NVSHMEM), the hot path is Pallas kernels with
async remote DMA over ICI plus XLA collectives, composed under
``jax.shard_map`` over a ``jax.sharding.Mesh``.
"""

__version__ = "0.1.0"

from triton_dist_tpu.runtime.dist import (  # noqa: F401
    initialize_distributed,
    finalize_distributed,
    get_context,
    get_mesh,
)
