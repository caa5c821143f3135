"""Tutorial 10: continuous batching — a request stream through a fixed
decode window.

Beyond the reference (its Engine serves fixed batches): serve_stream
admits the next queued prompt into a batch row the moment its occupant
finishes, so short requests never wait for the longest generation in
their batch (vLLM-style scheduling). Every row runs at its own cache
position — admission resets just that row's lane.

Run:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/10_continuous_batching.py
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

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from triton_dist_tpu.models import DenseLLM, Engine, ModelConfig


def main():
    mesh = Mesh(np.array(jax.devices()), ("tp",))
    cfg = ModelConfig(hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=8,
                      num_key_value_heads=8, head_dim=8, vocab_size=256,
                      max_position_embeddings=64, dtype=jnp.float32)
    model = DenseLLM(cfg, mesh=mesh, axis="tp", impl="xla")
    params = model.init(jax.random.PRNGKey(0))

    # Ten requests, two decode rows: with static batching the two
    # longest generations would gate every batch; streamed, each row
    # picks up the next prompt the moment it frees.
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 256, size=n).tolist()
               for n in rng.integers(1, 9, size=10)]
    eng = Engine(model, batch=2, max_seq=32, prefill_mode="xla_ar",
                 decode_mode="gemm_ar")
    results = eng.serve_stream(params, prompts, gen_len=6)

    # Greedy streamed results must equal serving each prompt alone.
    solo = Engine(model, batch=1, max_seq=32, prefill_mode="xla_ar",
                  decode_mode="gemm_ar")
    for prompt, row in zip(prompts, results):
        want = np.asarray(solo.serve(
            params, jnp.asarray([prompt], jnp.int32), 6))[0].tolist()
        assert row == want, (prompt, row, want)
    print(f"{len(prompts)} requests through a 2-row window; "
          "all token-exact vs solo serving")

    # CROSS-REQUEST continuous batching (ISSUE 5): the serving
    # scheduler shares one decode batch across concurrent clients — no
    # shared prompt list needed up front. submit() from any thread; a
    # short request admitted mid-flight retires while longer ones are
    # still decoding (docs/serving.md "Scheduler").
    from triton_dist_tpu.serving import Scheduler
    eng2 = Engine(model, batch=2, max_seq=32, prefill_mode="xla_ar",
                  decode_mode="gemm_ar")
    sched = Scheduler(eng2, params).start()
    futures = [sched.submit(p, 6) for p in prompts]
    for prompt, fut in zip(prompts, futures):
        want = np.asarray(solo.serve(
            params, jnp.asarray([prompt], jnp.int32), 6))[0].tolist()
        assert fut.result(timeout=300) == want[len(prompt):]
    sched.stop()
    print(f"{len(prompts)} concurrent submissions through the "
          "scheduler; token-exact vs solo serving")

    # The same stream through the LONG-CONTEXT engine: sequence-parallel
    # model + vLLM-style paged KV pools. Admission allocates the row's
    # pages and prefills straight into them; retirement hands the pages
    # to the next request (atomic turnover at admission).
    from jax.sharding import Mesh as _Mesh
    mesh_sp = _Mesh(np.array(jax.devices()).reshape(1, len(jax.devices())),
                    ("tp", "sp"))
    sp_model = DenseLLM(cfg, mesh=mesh_sp, axis="tp", sp_axis="sp",
                        impl="pallas", fwd_mode="sp")
    sp_params = sp_model.init(jax.random.PRNGKey(0))
    eng_paged = Engine(sp_model, batch=2, max_seq=64, prefill_mode="sp",
                       decode_mode="sp", paged=True, page_size=4)
    paged_results = eng_paged.serve_stream(sp_params, prompts[:6],
                                           gen_len=6)
    golden = Engine(sp_model, batch=1, max_seq=64, prefill_mode="xla",
                    decode_mode="xla_ar")
    for prompt, row in zip(prompts[:6], paged_results):
        want = np.asarray(golden.serve(
            sp_params, jnp.asarray([prompt], jnp.int32), 6))[0].tolist()
        assert row == want, (prompt, row, want)
    print(f"{len(paged_results)} requests streamed through 2 paged rows "
          "(page turnover); token-exact vs the plain engine")
    print("OK")


if __name__ == "__main__":
    main()
