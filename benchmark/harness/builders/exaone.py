"""Builder ``"exaone"``: a K-EXAONE configuration file -> the system
under test, one chip's share of an expert-parallel deployment.

``AutoLLM.build -> Engine -> ModelServer`` as ``builders/dense.py`` builds
them, with the weights of ``benchmark/reference/exaone_moe.py``: every
leaf from ``--seed``, generated in its served type on the device (one
jitted call per layer kind, the layer index traced), the selection bias
of every sparse layer from the reference's own balancing
(``selection_bias``: the same call the reference makes after the window,
answered from its cache then), all handed to the program's own
``shard_params``.

The configuration file's ``num_experts`` and ``vocab_size`` are what this
chip HOLDS (both in ``reduced``); ``expert_parallel`` gives the
deployment's ``world`` and this chip's ``rank``, so the router's width is
``num_experts * world``, which has to be the published count
(``published.num_experts``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark.harness.builders import dense
from benchmark.reference import exaone_moe as ref

_COPIED = ("hidden_size", "intermediate_size", "num_hidden_layers",
           "num_attention_heads", "num_key_value_heads", "head_dim",
           "vocab_size", "max_position_embeddings", "rms_norm_eps",
           "tie_word_embeddings", "model_type", "sliding_window",
           "layer_types", "mlp_layer_types", "num_experts_per_tok",
           "moe_intermediate_size", "num_shared_experts", "scoring_func",
           "routed_scaling_factor", "norm_topk_prob", "rope_parameters")


def model_dict(cfg: dict) -> dict:
    """The model's sizes as the reference and ``from_hf_config`` read
    them: the file's published keys, the router's full width, the
    share."""
    ep = cfg["expert_parallel"]
    model = {k: cfg[k] for k in _COPIED}
    model["num_experts"] = cfg["num_experts"] * ep["world"]
    assert model["num_experts"] == cfg["published"]["num_experts"], \
        "experts held x chips sharing a layer != the published count"
    assert cfg["n_group"] == cfg["topk_group"] == 1, "no group limit built"
    model["expert_parallel"] = (ep["world"], ep["rank"])
    model["rope_theta"] = cfg["rope_parameters"]["rope_theta"]
    if "balance_shape" in cfg:
        model["balance_shape"] = tuple(cfg["balance_shape"])
    return model


def make_params(model: dict, mesh, seed: int) -> dict:
    """The program's parameter tree, generated on the device(s)."""
    sh = NamedSharding(mesh, P())
    key = ref.seed_key(seed)
    bias = ref.selection_bias(model, seed)
    items = dict(ref.model_items(model))
    gen = {sparse: jax.jit(
        lambda key, i, sparse=sparse: ref.layer_leaves(key, i, items, sparse),
        out_shardings=sh) for sparse in (False, True)}
    params = jax.jit(lambda key: ref.top_leaves(key, items),
                     out_shardings=sh)(key)
    params["layers"] = []
    for i in range(items["num_hidden_layers"]):
        layer = gen[ref.is_sparse(items, i)](key, jnp.int32(i))
        if "moe" in layer:
            layer["moe"]["e_bias"] = bias[i]
        params["layers"].append(layer)
    return params


class Sut(dense.Sut):
    """``dense.Sut`` (release, counters, close) around this model's
    sizes, decoder and weights."""

    def __init__(self, cfg: dict, devices, seed: int):
        import numpy as np
        from jax.sharding import Mesh
        from triton_dist_tpu.models import AutoLLM, Engine, ModelConfig
        try:
            from triton_dist_tpu.models.exaone_moe import ExaoneMoE
        except ImportError as e:
            # A program without this decoder (the parent of the PR that
            # brought it) leaves the cell at once, before any weight is
            # made: AutoLLM.build there would hand back another model.
            raise RuntimeError(
                f"this program has no decoder for {cfg['model_type']!r}: "
                f"{e}") from e
        self.model = model_dict(cfg)
        if len(devices) < 1:
            raise RuntimeError(f"configuration {cfg['name']!r} needs a device")
        self.devices = list(devices[:1])
        self.mesh = Mesh(np.array(self.devices), ("tp",))
        hf = dict(self.model, eos_token_id=None,
                  expert_parallel=cfg["expert_parallel"])
        self.llm = AutoLLM.build(ModelConfig.from_hf_config(hf),
                                 mesh=self.mesh, axis="tp",
                                 impl=cfg.get("impl", "pallas"))
        if not isinstance(self.llm, ExaoneMoE):
            raise RuntimeError(
                f"AutoLLM.build gave {type(self.llm).__name__} for "
                f"{cfg['model_type']!r}")
        self.engine = Engine(self.llm, **cfg["engine"])
        self.batch = int(cfg["engine"]["batch"])
        self.server = self.params = None
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        from triton_dist_tpu.serving import ModelServer
        self.release()
        params = make_params(self.model, self.mesh, seed)
        self.params = jax.block_until_ready(self.llm.shard_params(params))
        del params
        self.server = ModelServer(self.engine, self.params, port=0).start()
        self.host, self.port = self.server.host, self.server.port


def build(cfg: dict, devices, seed: int) -> Sut:
    return Sut(cfg, devices, seed)
