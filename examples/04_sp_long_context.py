"""Tutorial 04: sequence-parallel long-context attention.

Analog of the reference's SP tutorials (AG-KV prefill + distributed
flash-decode): prefill with ring attention (KV never materialized in
full) and decode over a sequence-sharded KV cache with the cross-rank
partial-softmax combine.

Run:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/04_sp_long_context.py
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

from triton_dist_tpu.ops.flash_decode import (
    create_flash_decode_context, gqa_fwd_batch_decode)
from triton_dist_tpu.ops.sp_attention import (
    create_sp_attention_context, sp_ag_attention)


def main():
    devs = jax.devices()
    world = len(devs)
    mesh = Mesh(np.array(devs), ("sp",))
    b, s, hq, hkv, d = 1, 16 * world, 2 * world, world, 16

    key = jax.random.PRNGKey(0)
    sh = NamedSharding(mesh, P(None, "sp"))
    q = jax.device_put(jax.random.normal(key, (b, s, hq, d), jnp.float32),
                       sh)
    k = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(1), (b, s, hkv, d),
                          jnp.float32), sh)
    v = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(2), (b, s, hkv, d),
                          jnp.float32), sh)

    # prefill: ring attention (causal) — each device holds s/world positions
    ctx = create_sp_attention_context(mesh, "sp", causal=True)
    out = sp_ag_attention(q, k, v, ctx, impl="ring")
    print("prefill out", out.shape, "finite:",
          bool(jnp.isfinite(out).all()))

    # decode: distributed flash-decode over the same sharded KV
    dctx = create_flash_decode_context(mesh, "sp")
    qd = jax.random.normal(jax.random.PRNGKey(3), (b, hq, d), jnp.float32)
    dec = gqa_fwd_batch_decode(qd, k, v, jnp.int32(s), dctx, impl="pallas")
    print("decode out", dec.shape, "finite:", bool(jnp.isfinite(dec).all()))

    # chunked prefill: a LATER chunk of queries attends the cache-like
    # full KV with live-length masking (q_offset/kv_len) — the
    # cache-aware path behind Engine(prefill_chunk=...).
    half = s // 2
    q2 = jax.device_put(q[:, half:], sh)
    chunk_out = sp_ag_attention(q2, k, v, ctx, impl="ring",
                                q_offset=half, kv_len=s)
    np.testing.assert_allclose(np.asarray(chunk_out),
                               np.asarray(out[:, half:]), rtol=2e-4,
                               atol=2e-4)
    print("chunked prefill (second half) == single-shot second half")
    print("OK")


if __name__ == "__main__":
    main()
