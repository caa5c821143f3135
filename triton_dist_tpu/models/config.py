"""Model configuration (reference ``ModelConfig``,
python/triton_dist/models/config.py — extended with the MoE fields the
reference keeps on the HF config object, models/qwen_moe.py:108-140)."""

from __future__ import annotations

import dataclasses
import json
import os

import jax.numpy as jnp


def _scalar_eos(v) -> int:
    """HF configs store eos_token_id as an int or a list; keep the first
    (generation stops on it; multi-eos callers pass stop_tokens to
    ``Engine.serve``)."""
    if v is None:  # "eos_token_id": null is valid HF JSON
        return -1
    if isinstance(v, (list, tuple)):
        return int(v[0]) if v else -1
    return int(v)


# The decoder families ``AutoLLM`` can build: model_type -> (decoder class
# by name, whether q and k heads are RMS-normalised). A ``model_type`` that
# is not listed is refused: a name's prefix says nothing about its layers.
MODEL_TYPES = {
    "qwen3": ("DenseLLM", True),
    "qwen3_moe": ("Qwen3MoE", True),
    "llama": ("DenseLLM", False),
    "seed_oss": ("DenseLLM", False),
    "exaone_moe": ("ExaoneMoE", True),
}


def known_model_type(model_type: str) -> tuple[str, bool]:
    try:
        return MODEL_TYPES[model_type]
    except KeyError:
        raise ValueError(
            f"unknown model_type {model_type!r}: this system builds "
            f"{sorted(MODEL_TYPES)}") from None


@dataclasses.dataclass
class ModelConfig:
    """Architecture hyperparameters of the decoders ``AutoLLM`` builds."""

    hidden_size: int = 1024
    intermediate_size: int = 3072
    num_hidden_layers: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 8
    head_dim: int = 64
    vocab_size: int = 32000
    max_position_embeddings: int = 4096
    rope_theta: float = 1_000_000.0
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    dtype: object = jnp.bfloat16
    # MoE (0 experts = dense; reference Qwen3MoE fields)
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    norm_topk_prob: bool = True
    model_type: str = "qwen3"
    # Qwen3 applies RMSNorm to q/k heads; Llama-3 / Seed-OSS-class dense
    # models (reference AutoLLM maps both to DenseLLM,
    # models/__init__.py:33-42) do not.
    qk_norm: bool = True
    eos_token_id: int = -1  # -1 = no stop token
    # exaone_moe (models/exaone_moe.py). Per layer: the attention's window
    # (None = every earlier position) and whether its feed-forward is the
    # sparse one; empty = every layer full attention / the model's one kind.
    layer_windows: tuple = ()
    sparse_layers: tuple = ()
    num_shared_experts: int = 0
    scoring_func: str = "softmax"
    routed_scaling_factor: float = 1.0
    # Expert parallelism's share: this rank of ``ep_world`` holds experts
    # [ep_rank * num_experts / ep_world, ...) of the ``num_experts`` the
    # router scores and selects over.
    ep_world: int = 1
    ep_rank: int = 0

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def experts_held(self) -> tuple[int, int]:
        """(first held expert, how many are held)."""
        n = self.num_experts // self.ep_world
        return self.ep_rank * n, n

    def window_of(self, layer: int):
        return self.layer_windows[layer] if self.layer_windows else None

    def param_split(self) -> tuple[int, int, int]:
        """(attn params/layer, mlp params/layer incl. all experts,
        embedding params) — the ONE accounting shared by
        ``models.presets.param_count`` and
        ``parallel.plan_parallelism`` (review r5f-1: two hand-rolled
        copies had already diverged on tied embeddings). Norm weights
        are omitted (<0.1%)."""
        h = self.hidden_size
        attn = h * self.head_dim * (2 * self.num_attention_heads
                                    + 2 * self.num_key_value_heads)
        if self.is_moe:
            mlp = 3 * h * self.moe_intermediate_size * self.num_experts
        else:
            mlp = 3 * h * self.intermediate_size
        embed = (1 if self.tie_word_embeddings else 2) * h * self.vocab_size
        return attn, mlp, embed

    @classmethod
    def from_hf_config(cls, path_or_dict) -> "ModelConfig":
        """Build from a HF ``config.json`` (file path, model dir, or dict) —
        the reference reads the same fields off AutoConfig
        (models/dense.py:117-150)."""
        if isinstance(path_or_dict, dict):
            cfg = path_or_dict
        else:
            p = path_or_dict
            if os.path.isdir(p):
                p = os.path.join(p, "config.json")
            with open(p) as f:
                cfg = json.load(f)
        model_type = cfg.get("model_type", "qwen3")
        _, qk_norm = known_model_type(model_type)
        n = cfg["num_hidden_layers"]
        window = cfg.get("sliding_window")
        kinds = cfg.get("layer_types")
        windows = tuple(
            int(window) if kind == "sliding_attention" else None
            for kind in kinds[:n]) if kinds and window else ()
        if "mlp_layer_types" in cfg:
            sparse = tuple(kind == "sparse"
                           for kind in cfg["mlp_layer_types"][:n])
        elif "first_k_dense_replace" in cfg:
            sparse = tuple(i >= cfg["first_k_dense_replace"]
                           for i in range(n))
        else:
            sparse = ()
        rope = cfg.get("rope_parameters") or {}
        ep = cfg.get("expert_parallel") or {}
        return cls(
            layer_windows=windows, sparse_layers=sparse,
            num_shared_experts=cfg.get("num_shared_experts", 0),
            scoring_func=cfg.get("scoring_func", "softmax"),
            routed_scaling_factor=cfg.get("routed_scaling_factor", 1.0),
            ep_world=ep.get("world", 1), ep_rank=ep.get("rank", 0),
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg.get("intermediate_size", 0),
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=cfg["num_attention_heads"],
            num_key_value_heads=cfg.get("num_key_value_heads",
                                        cfg["num_attention_heads"]),
            head_dim=cfg.get("head_dim",
                             cfg["hidden_size"] // cfg["num_attention_heads"]),
            vocab_size=cfg["vocab_size"],
            max_position_embeddings=cfg.get("max_position_embeddings", 4096),
            rope_theta=cfg.get("rope_theta", rope.get("rope_theta", 1e6)),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            num_experts=cfg.get("num_experts", 0),
            num_experts_per_tok=cfg.get("num_experts_per_tok", 0),
            moe_intermediate_size=cfg.get("moe_intermediate_size", 0),
            norm_topk_prob=cfg.get("norm_topk_prob", True),
            model_type=model_type,
            qk_norm=qk_norm,
            eos_token_id=_scalar_eos(cfg.get("eos_token_id", -1)),
        )
