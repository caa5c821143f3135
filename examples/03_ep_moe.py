"""Tutorial 03: expert-parallel MoE with the LL all-to-all.

Analog of the reference's tutorials/04 (DeepSeek-style inference a2a):
route tokens to expert-owning ranks, run the grouped expert FFN locally,
and combine back with routing weights.

Run:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/03_ep_moe.py
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

from triton_dist_tpu.layers.ep_a2a import EPAll2AllLayer
from triton_dist_tpu.ops.group_gemm import grouped_expert_ffn
from triton_dist_tpu.ops.moe_utils import topk_routing


def main():
    devs = jax.devices()
    world = len(devs)
    mesh = Mesh(np.array(devs), ("ep",))
    rows, h, i, e, topk = 8, 32, 48, 2 * world, 2
    t = world * rows
    epr = e // world

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (t, h), jnp.float32)
    router = jax.random.normal(jax.random.PRNGKey(1), (h, e), jnp.float32)
    wg = jax.random.normal(jax.random.PRNGKey(2), (e, h, i), jnp.float32)
    wu = jax.random.normal(jax.random.PRNGKey(3), (e, h, i), jnp.float32)
    wd = jax.random.normal(jax.random.PRNGKey(4), (e, i, h), jnp.float32)

    weights, indices = topk_routing(x @ router, topk)

    layer = EPAll2AllLayer(max_tokens=rows, hidden=h, topk=topk,
                           num_experts=e, mesh=mesh, axis="ep",
                           dtype=jnp.float32, impl="pallas")
    sh = lambda a, s: jax.device_put(a, NamedSharding(mesh, s))

    tokens, local_expert, handle = layer.dispatch(sh(x, P("ep")),
                                                  sh(indices, P("ep")))

    def local_ffn(tok, le, g, u, d):
        return grouped_expert_ffn(tok, g, u, d, le, epr)

    out_tok = jax.shard_map(
        local_ffn, mesh=mesh, in_specs=(P("ep"),) * 5, out_specs=P("ep"),
        check_vma=False)(tokens, local_expert, sh(wg, P("ep")),
                         sh(wu, P("ep")), sh(wd, P("ep")))

    out = layer.combine(out_tok, sh(weights, P("ep")), handle)
    print("tokens routed:", int(np.asarray(handle.valid).sum()),
          "of", t * topk, "pairs; output", out.shape)
    assert bool(jnp.isfinite(out).all())

    # fp8 wire (the reference's headline LL-a2a config: tokens travel as
    # float8_e4m3fn + per-row scales — half the ICI bytes for bf16
    # models). Same layer API: wire_dtype="fp8".
    layer8 = EPAll2AllLayer(max_tokens=rows, hidden=h, topk=topk,
                            num_experts=e, mesh=mesh, axis="ep",
                            dtype=jnp.float32, impl="pallas",
                            wire_dtype="fp8")
    tok8, le8, h8 = layer8.dispatch(sh(x, P("ep")), sh(indices, P("ep")))
    out8_tok = jax.shard_map(
        local_ffn, mesh=mesh, in_specs=(P("ep"),) * 5, out_specs=P("ep"),
        check_vma=False)(tok8, le8, sh(wg, P("ep")),
                         sh(wu, P("ep")), sh(wd, P("ep")))
    out8 = layer8.combine(out8_tok, sh(weights, P("ep")), h8)
    rel = float(jnp.max(jnp.abs(out8 - out)) /
                (jnp.max(jnp.abs(out)) + 1e-9))
    print(f"fp8 wire vs full precision: rel err {rel:.4f}")
    assert rel < 0.1
    print("OK")


if __name__ == "__main__":
    main()
