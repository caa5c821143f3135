"""Tutorial 05: end-to-end inference with the Engine.

Analog of the reference's e2e demo (test_e2e_inference.py / Engine.serve):
build a Qwen3-style model, prefill, then run the jit-compiled decode loop
(the CUDA-graph analog) — plus the mega one-program decode step.

Run:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/05_engine.py
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
from jax.sharding import Mesh

from triton_dist_tpu.mega import MegaQwen3
from triton_dist_tpu.models import DenseLLM, Engine, ModelConfig
from triton_dist_tpu.models.kv_cache import KVCacheManager


def main():
    devs = jax.devices()
    mesh = Mesh(np.array(devs), ("tp",))
    world = len(devs)
    cfg = ModelConfig(hidden_size=8 * world, intermediate_size=16 * world,
                      num_hidden_layers=2, num_attention_heads=world,
                      num_key_value_heads=world, head_dim=8,
                      vocab_size=128, max_position_embeddings=32,
                      dtype=jnp.float32)
    model = DenseLLM(cfg, mesh=mesh, axis="tp", impl="xla")
    params = model.init(jax.random.PRNGKey(0))

    eng = Engine(model, batch=2, max_seq=32, prefill_mode="xla_ar",
                 decode_mode="gemm_ar")
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0,
                                cfg.vocab_size, jnp.int32)
    out = eng.serve(params, prompt, gen_len=5)
    print("generated:", np.asarray(out))

    # mega: the whole decode step as one compiled program
    mega = MegaQwen3(model, decode_mode="gemm_ar")
    kv = KVCacheManager(cfg.num_hidden_layers, 2, 32,
                        cfg.num_key_value_heads, cfg.head_dim, mesh=mesh,
                        axis="tp", dtype=cfg.dtype)
    logits, _ = mega.step(params, out[:, -1:], kv.init(), 0)
    print("mega step logits:", logits.shape)
    print(mega.graph.summary().splitlines()[0])
    print("OK")


if __name__ == "__main__":
    main()
