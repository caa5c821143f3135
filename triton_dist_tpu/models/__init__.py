"""Models + engine (reference L7: python/triton_dist/models/).

``AutoLLM.from_pretrained`` (reference models/__init__.py:33) dispatches
on the HF config's ``model_type``/MoE fields to ``DenseLLM``,
``Qwen3MoE``, ``ExaoneMoE`` or ``LingHybrid`` and loads safetensors weights when present.
"""

from __future__ import annotations

import glob
import os

from triton_dist_tpu.models.config import ModelConfig, known_model_type
from triton_dist_tpu.models.dense import DenseLLM
from triton_dist_tpu.models.qwen_moe import Qwen3MoE
from triton_dist_tpu.models.exaone_moe import ExaoneMoE
from triton_dist_tpu.models.ling_hybrid import LingHybrid
from triton_dist_tpu.models.kv_cache import KVCacheManager
from triton_dist_tpu.models.engine import Engine, StreamSession, sample_token
from triton_dist_tpu.models.spec import SpecConfig
from triton_dist_tpu.models.train import make_train_step, cross_entropy_loss
from triton_dist_tpu.models import presets

__all__ = ["ModelConfig", "DenseLLM", "Qwen3MoE", "ExaoneMoE", "LingHybrid",
           "KVCacheManager",
           "Engine", "StreamSession", "sample_token", "AutoLLM", "make_train_step", "presets",
           "cross_entropy_loss", "SpecConfig"]


def _load_safetensors_state(model_dir: str) -> dict:
    """Read all ``*.safetensors`` shards into one name→array dict
    (the reference loads via HF from_pretrained; we read directly —
    no torch needed on the load path)."""
    from safetensors import safe_open  # ships with transformers

    state = {}
    files = sorted(glob.glob(os.path.join(model_dir, "*.safetensors")))
    if not files:
        raise FileNotFoundError(f"no safetensors under {model_dir}")
    for path in files:
        with safe_open(path, framework="np") as f:
            for name in f.keys():
                state[name] = f.get_tensor(name)
    return state


class AutoLLM:
    """Dispatching loader (reference ``AutoLLM.from_pretrained``,
    models/__init__.py:33-64)."""

    @staticmethod
    def build(config: ModelConfig, mesh=None, axis: str = "tp",
              fwd_mode: str = "ag_rs", impl: str = "pallas"):
        """The decoder of ``config.model_type`` (``config.MODEL_TYPES``;
        an unknown type raises). A ``qwen3`` config with experts is the
        MoE decoder: presets and tests build it without naming the type."""
        name, _ = known_model_type(config.model_type)
        if name == "DenseLLM" and config.is_moe:
            name = "Qwen3MoE"
        cls = {"DenseLLM": DenseLLM, "Qwen3MoE": Qwen3MoE,
               "ExaoneMoE": ExaoneMoE, "LingHybrid": LingHybrid}[name]
        return cls(config, mesh=mesh, axis=axis, fwd_mode=fwd_mode,
                   impl=impl)

    @staticmethod
    def from_pretrained(model_dir: str, mesh=None, axis: str = "tp",
                        fwd_mode: str = "ag_rs", impl: str = "pallas"):
        """Build the model from a local HF checkpoint dir and load + shard
        its weights. Returns (model, params)."""
        config = ModelConfig.from_hf_config(model_dir)
        model = AutoLLM.build(config, mesh=mesh, axis=axis,
                              fwd_mode=fwd_mode, impl=impl)
        state = _load_safetensors_state(model_dir)
        params = model.load_hf_state_dict(state)
        return model, params
