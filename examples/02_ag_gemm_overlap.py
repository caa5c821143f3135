"""Tutorial 02: fused AllGather-GEMM and overlap measurement.

Analog of the reference's tutorials/07 (AG-GEMM) with an
overlap-efficiency reading: run the fused collective matmul, verify
against the XLA golden, and report the measured speedup next to the
perf-model upper bound.

Run:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/02_ag_gemm_overlap.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# 8-device CPU simulation by default (forced in-config, whatever the
# machine holds); set TDT_EXAMPLES_ON_TPU=1 to run on real devices instead.
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
import jax

if not os.environ.get("TDT_EXAMPLES_ON_TPU"):
    jax.config.update("jax_platforms", "cpu")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_dist_tpu.ops.allgather_gemm import (
    create_ag_gemm_context, ag_gemm)
from triton_dist_tpu.runtime.utils import assert_allclose, perf_func
from triton_dist_tpu.tools import (
    estimate_all_gather_time_ms, estimate_gemm_sol_time_ms,
    overlap_efficiency)


def main():
    devs = jax.devices()
    mesh = Mesh(np.array(devs), ("tp",))
    world = len(devs)
    m, k, n = 8 * world, 128, 32 * world

    key = jax.random.PRNGKey(0)
    a = jax.device_put(jax.random.normal(key, (m, k), jnp.float32),
                       NamedSharding(mesh, P("tp")))
    b = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32),
        NamedSharding(mesh, P(None, "tp")))

    ctx = create_ag_gemm_context(mesh, "tp")
    c_fused = ag_gemm(a, b, ctx, impl="pallas")
    c_gold = ag_gemm(a, b, ctx, impl="xla")
    assert_allclose(c_fused, c_gold, rtol=1e-4, atol=1e-4)

    _, t_fused = perf_func(lambda: ag_gemm(a, b, ctx, impl="pallas"),
                           iters=5, warmup_iters=2)
    _, t_gold = perf_func(lambda: ag_gemm(a, b, ctx, impl="xla"),
                          iters=5, warmup_iters=2)
    bound = overlap_efficiency(
        estimate_gemm_sol_time_ms(m, n // world, k),
        estimate_all_gather_time_ms(m // world * k * 4, world))
    print(f"fused {t_fused:.3f} ms vs golden {t_gold:.3f} ms "
          f"(speedup {t_gold / t_fused:.2f}x, overlap bound {bound:.2f}x)")
    print("OK")


if __name__ == "__main__":
    main()
