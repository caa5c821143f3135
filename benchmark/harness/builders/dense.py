"""Builder ``"dense"``: a configuration file -> the system under test.

``AutoLLM.build -> Engine -> ModelServer`` exactly as a user of
``tdt-serve`` gets them (the pattern of ``chip_smoke.py``), with the
weights made by the benchmark: every leaf of
``benchmark/reference/dense_decoder.py`` from ``--seed``, generated in
bfloat16 on the devices and in the sharding the program keeps them in
(one jitted call per decoder layer, one for embedding, norm and head),
then handed to the program's own ``shard_params``.

A configuration that needs other construction (paged cache, MoE, a
router over replicas) names another builder: a new file beside this one.
"""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark.reference import dense_decoder as ref

_COL = ("w_q", "w_k", "w_v", "w_gate", "w_up")      # (in, out/tp)
_ROW = ("w_o", "w_down")                            # (in/tp, out)


def model_dict(cfg: dict) -> dict:
    """The model's sizes as the configuration file states them (its
    top-level keys are the published ``config.json``'s)."""
    keys = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "max_position_embeddings", "rope_theta",
            "rms_norm_eps", "tie_word_embeddings", "model_type")
    return {k: cfg[k] for k in keys}


def make_params(model: dict, mesh: Mesh, axis: str, seed: int) -> dict:
    """The program's parameter tree, generated on the device(s)."""
    def spec(name):
        if name in _COL:
            return P(None, axis)
        if name in _ROW:
            return P(axis, None)
        return P()

    def sh(name):
        return NamedSharding(mesh, spec(name))

    n_layers = model["num_hidden_layers"]
    layer_sh = {"attn": {k: sh(k) for k in ("w_q", "w_k", "w_v", "w_o",
                                            "q_norm", "k_norm")},
                "mlp": {k: sh(k) for k in ("w_gate", "w_up", "w_down")},
                "ln_attn": sh("ln_attn"), "ln_mlp": sh("ln_mlp")}
    tied = bool(model.get("tie_word_embeddings"))
    top_sh = {"embed": sh("embed"), "final_norm": sh("final_norm")}
    if not tied:
        top_sh["lm_head"] = sh("lm_head")

    def gen_layer(key, i):
        w = ref.layer_leaves(key, i, model)
        return {"attn": {k: w[k] for k in ("w_q", "w_k", "w_v", "w_o",
                                           "q_norm", "k_norm")},
                "mlp": {k: w[k] for k in ("w_gate", "w_up", "w_down")},
                "ln_attn": w["ln_attn"], "ln_mlp": w["ln_mlp"]}

    def gen_top(key):
        top = ref.top_leaves(key, model)
        if tied:
            del top["lm_head"]
        return top

    # Two small programs (the layer index is traced), not one that holds
    # every layer unrolled: the unrolled one is a 28-59 MB executable,
    # a third of the 192 MiB the compile cache may hold on the chip's
    # machine, and takes a minute to compile cold.
    key = ref.seed_key(seed)
    layer = jax.jit(gen_layer, out_shardings=layer_sh)
    params = jax.jit(gen_top, out_shardings=top_sh)(key)
    params["layers"] = [layer(key, jnp.int32(i)) for i in range(n_layers)]
    if tied:
        params["lm_head"] = params["embed"]
    return params


class Sut:
    """The system under test of one run: model, engine, server."""

    def __init__(self, cfg: dict, devices, seed: int):
        from triton_dist_tpu.models import AutoLLM, Engine, ModelConfig
        from triton_dist_tpu.runtime.topology import topology_aware_grid

        self.model = model_dict(cfg)
        axes = cfg.get("mesh", {"tp": 1})
        n = int(np.prod(list(axes.values())))
        if len(devices) < n:
            raise RuntimeError(f"configuration {cfg['name']!r} needs {n} "
                               f"devices, JAX reports {len(devices)}")
        self.devices = list(devices[:n])
        grid = topology_aware_grid(np.array(self.devices),
                                   tuple(axes.values()))
        self.mesh = Mesh(grid, tuple(axes))
        mc = ModelConfig.from_hf_config(dict(self.model, eos_token_id=None))
        self.llm = AutoLLM.build(mc, mesh=self.mesh, axis="tp",
                                 impl=cfg.get("impl", "pallas"))
        self.engine = Engine(self.llm, **cfg["engine"])
        self.batch = int(cfg["engine"]["batch"])
        self.server = self.params = None
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        """Weights from ``seed`` behind a fresh server on the same engine
        (its compiled programs take the weights as arguments)."""
        from triton_dist_tpu.serving import ModelServer
        self.release()
        params = make_params(self.model, self.mesh, "tp", seed)
        self.params = jax.block_until_ready(self.llm.shard_params(params))
        del params
        self.server = ModelServer(self.engine, self.params, port=0).start()
        self.host, self.port = self.server.host, self.server.port

    def release(self) -> None:
        """Stop the server and drop weights and cache; the engine and its
        compiled programs stay."""
        if self.server is not None:
            self.server.stop()
        self.server = self.params = None
        gc.collect()

    def counters(self) -> dict:
        from triton_dist_tpu import obs
        return dict(obs.snapshot().get("counters", {}))

    def close(self) -> None:
        """Stop the server and drop every device buffer of the program."""
        self.release()
        self.engine = self.llm = None
        gc.collect()


def build(cfg: dict, devices, seed: int) -> Sut:
    return Sut(cfg, devices, seed)
