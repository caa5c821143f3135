"""Qwen3-MoE decoder under TP/EP.

TPU-native redesign of the reference's ``Qwen3MoELayer`` + ``Qwen3MoE``
(python/triton_dist/models/qwen_moe.py:50-206: dense TP attention + sparse
MoE FFN with softmax-topk routing, HF weight loading). FFN is
``layers.tp_moe.TPMoE`` (AG + grouped ragged-dot GEMMs + ring MoE-RS);
the EP dispatch/combine path (layers/ep_a2a.py) plugs into the same slot
for expert-parallel serving (reference test_ep_moe_inference.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu.layers.common import (
    precompute_rope_cache, rms_norm, shard_param)
from triton_dist_tpu.layers.tp_attn import TPAttn
from triton_dist_tpu.layers.tp_moe import TPMoE
from triton_dist_tpu.models.config import ModelConfig


class Qwen3MoE:
    """TP/EP Qwen3-MoE decoder (reference models/qwen_moe.py:108).

    ``moe_parallel="tp"``: every expert's intermediate dim is sharded
    (TPMoE — AG + grouped GEMM + MoE-RS). ``moe_parallel="ep"``: the
    expert set is sharded, tokens route via the LL all-to-all (EPMoE —
    the reference's EP inference deployment, test_ep_moe_inference.py).
    Attention is TP over the same axis in both."""

    def __init__(self, config: ModelConfig, mesh: Mesh | None = None,
                 axis: str = "tp", fwd_mode: str = "ag_rs",
                 impl: str = "pallas", moe_parallel: str = "tp",
                 sp_axis: str | None = None):
        if mesh is None:
            from triton_dist_tpu.runtime.dist import get_mesh
            mesh = get_mesh()
        assert config.is_moe, "use DenseLLM for dense configs"
        assert moe_parallel in ("tp", "ep")
        self.config = config
        self.mesh, self.axis = mesh, axis
        self.fwd_mode = fwd_mode
        self.moe_parallel = moe_parallel
        self.sp_axis = sp_axis
        if sp_axis is not None:
            # Model-level SP for the MoE decoder (long-context serving):
            # same attention/cache machinery as DenseLLM (forward_sp is
            # REUSED, see below); the FFN hook runs a row-local MoE —
            # every device routes + grouped-FFNs its own S/w tokens with
            # replicated expert weights (no collectives in the FFN).
            assert moe_parallel == "tp" and mesh.shape[axis] == 1, (
                "sp MoE v1: pure-sp grid (tp axis size 1, replicated "
                "expert weights); ep x sp is future work")
            from triton_dist_tpu.ops.flash_decode import (
                create_flash_decode_context)
            from triton_dist_tpu.ops.sp_attention import (
                create_sp_attention_context)
            self.sp_ctx = create_sp_attention_context(
                mesh, sp_axis, causal=True, head_axis=None)
            self.fd_ctx = create_flash_decode_context(mesh, sp_axis)
            self.sp_impl = "ring" if impl == "pallas" else "xla"
            self.fd_impl = impl
        c = config
        self.attn = TPAttn(c.hidden_size, c.num_attention_heads,
                           c.num_key_value_heads, c.head_dim, mesh=mesh,
                           axis=axis, dtype=c.dtype, fwd_mode=fwd_mode,
                           impl=impl, rms_eps=c.rms_norm_eps)
        if moe_parallel == "ep":
            from triton_dist_tpu.layers.ep_moe import EPMoE
            self.moe = EPMoE(c.hidden_size, c.moe_intermediate_size,
                             c.num_experts, c.num_experts_per_tok,
                             mesh=mesh, axis=axis, dtype=c.dtype,
                             impl=impl, norm_topk_prob=c.norm_topk_prob)
        else:
            self.moe = TPMoE(c.hidden_size, c.moe_intermediate_size,
                             c.num_experts, c.num_experts_per_tok,
                             mesh=mesh, axis=axis, dtype=c.dtype,
                             fwd_mode=fwd_mode, impl=impl,
                             norm_topk_prob=c.norm_topk_prob)
        self.rope_cache = precompute_rope_cache(
            c.head_dim, c.max_position_embeddings, c.rope_theta)

    def set_fwd(self, mode: str):
        self.fwd_mode = mode
        self.attn.set_fwd(mode)
        if self.moe_parallel == "tp":
            self.moe.set_fwd("xla" if mode in ("xla", "xla_ar") else "ag_rs")

    # -- params ------------------------------------------------------------
    def init(self, key: jax.Array) -> dict:
        c = self.config
        keys = jax.random.split(key, c.num_hidden_layers + 2)
        layers = []
        for i in range(c.num_hidden_layers):
            ka, km = jax.random.split(keys[i])
            layers.append({
                "attn": self.attn.init(ka),
                "moe": self.moe.init(km),
                "ln_attn": jnp.ones((c.hidden_size,), c.dtype),
                "ln_mlp": jnp.ones((c.hidden_size,), c.dtype),
            })
        embed = (jax.random.normal(keys[-2], (c.vocab_size, c.hidden_size),
                                   c.dtype) * 0.02)
        params = {
            "embed": embed,
            "layers": layers,
            "final_norm": jnp.ones((c.hidden_size,), c.dtype),
            "lm_head": (embed if c.tie_word_embeddings else
                        jax.random.normal(keys[-1],
                                          (c.vocab_size, c.hidden_size),
                                          c.dtype) * 0.02),
        }
        return self.shard_params(params)

    def shard_params(self, params: dict) -> dict:
        m = self.mesh
        out = {
            "embed": shard_param(params["embed"], m, P()),
            "final_norm": shard_param(params["final_norm"], m, P()),
            "lm_head": shard_param(params["lm_head"], m, P()),
            "layers": [],
        }
        for lp in params["layers"]:
            out["layers"].append({
                "attn": self.attn.shard_params(lp["attn"]),
                "moe": self.moe.shard_params(lp["moe"]),
                "ln_attn": shard_param(lp["ln_attn"], m, P()),
                "ln_mlp": shard_param(lp["ln_mlp"], m, P()),
            })
        return out

    # -- forward -----------------------------------------------------------
    def forward(self, params: dict, input_ids: jax.Array, kv_caches,
                offset, mode: str | None = None, kv_start=None,
                block_table=None, kv_need=None, logits_at=None):
        """Same contract as DenseLLM.forward; MoE FFN needs the
        row-sharded layout (modes xla / ag_rs). ``logits_at`` (traced
        int): compute the logits of that one position only, (B, 1, V)."""
        c = self.config
        mode = mode or self.fwd_mode
        if mode == "sp":
            assert kv_start is None, "mode='sp' has no ragged support yet"
            return self.forward_sp(params, input_ids, kv_caches, offset,
                                   block_table=block_table,
                                   logits_at=logits_at)
        assert block_table is None, "paged caches need mode='sp'"
        if self.moe_parallel == "ep":
            moe_mode = "ep"
            if mode == "ep":
                # Row-sharded attention needs divisible rows; decode-size
                # batches fall back to the replicated gemm_ar path (the
                # reference's EP serving uses the same small-batch mode,
                # test_ep_moe_inference.py).
                w = self.mesh.shape[self.axis]
                attn_mode = "ag_rs" if (input_ids.size % w == 0) else \
                    "gemm_ar"
            else:
                attn_mode = mode
        else:
            moe_mode = "xla" if mode in ("xla", "xla_ar") else "ag_rs"
            attn_mode = mode
        b, s = input_ids.shape
        offset = jnp.asarray(offset, jnp.int32)
        # (B,) per-row offsets supported for S == 1 decode (continuous
        # batching — same contract as DenseLLM.forward).
        off2d = offset[:, None] if offset.ndim else offset
        position_ids = off2d + jnp.tile(
            jnp.arange(s, dtype=jnp.int32)[None], (b, 1))
        if kv_start is not None:
            position_ids = jnp.maximum(
                position_ids - jnp.asarray(kv_start, jnp.int32)[:, None], 0)

        x = params["embed"][input_ids].reshape(b * s, c.hidden_size)
        new_caches = []
        for lp, cache in zip(params["layers"], kv_caches):
            h = rms_norm(x, lp["ln_attn"], c.rms_norm_eps)
            a, cache = self.attn(lp["attn"], h, position_ids,
                                 self.rope_cache, cache, offset,
                                 mode=attn_mode, kv_start=kv_start,
                                 kv_need=kv_need)
            x = x + a
            h = rms_norm(x, lp["ln_mlp"], c.rms_norm_eps)
            x = x + self.moe(lp["moe"], h, mode=moe_mode)
            new_caches.append(cache)

        x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
        if logits_at is not None:
            x = jax.lax.dynamic_slice_in_dim(
                x.reshape(b, s, c.hidden_size), logits_at, 1, axis=1)[:, 0]
        logits = jnp.dot(x.astype(jnp.float32),
                         params["lm_head"].T.astype(jnp.float32))
        return logits.reshape(b, -1, c.vocab_size), new_caches

    # -- sequence-parallel forward (REUSED from DenseLLM: the
    # attention/cache/chunk/paged machinery is model-agnostic; only the
    # FFN hook differs) ----------------------------------------------------
    from triton_dist_tpu.models.dense import DenseLLM as _D
    forward_sp = _D.forward_sp
    _paged_scatter = _D._paged_scatter
    del _D

    def _sp_ffn(self, lp, h, constrain, xsh):
        """Row-local MoE FFN on (B, S, H) S-sharded activations:
        route + grouped expert FFN per device on its own tokens,
        replicated expert weights — zero FFN collectives (tokens never
        leave their sequence shard)."""
        from triton_dist_tpu.ops.common import nestable_shard_map
        from triton_dist_tpu.ops.group_gemm import grouped_expert_ffn
        from triton_dist_tpu.ops.moe_utils import topk_reduce, topk_routing
        c = self.config
        k, n_exp = c.num_experts_per_tok, c.num_experts
        mp = lp["moe"]
        sp = self.sp_axis

        def local(hs, rt, wg, wu, wd):
            bb, ss, hh = hs.shape
            rows = hs.reshape(bb * ss, hh)
            logits = rows.astype(jnp.float32) @ rt
            w, idx = topk_routing(logits, k, c.norm_topk_prob)
            pairs = jnp.repeat(rows, k, axis=0)
            out = grouped_expert_ffn(pairs, wg, wu, wd,
                                     idx.reshape(-1), n_exp)
            red = topk_reduce(out.reshape(bb * ss, k, hh), w)
            return red.reshape(hs.shape).astype(hs.dtype)

        spec = P() if h.shape[1] == 1 else P(None, sp, None)
        f = nestable_shard_map(
            local, mesh=self.mesh,
            in_specs=(spec, P(), P(), P(), P()), out_specs=spec,
            check_vma=False)
        return f(h, mp["w_router"], mp["w_gate"], mp["w_up"],
                 mp["w_down"])

    # -- HF weights --------------------------------------------------------
    def load_hf_state_dict(self, state: dict) -> dict:
        """Map a HF Qwen3-MoE state dict to our pytree. Per-expert HF
        weights ``mlp.experts.{e}.{gate,up,down}_proj`` stack into
        (E, in, out) ragged-dot operands."""
        c = self.config

        def get(name):
            a = state[name]
            if hasattr(a, "detach"):
                a = a.detach().cpu().numpy()
            return jnp.asarray(np.asarray(a), c.dtype)

        def lin(name):
            return get(name).T

        layers = []
        for i in range(c.num_hidden_layers):
            p = f"model.layers.{i}."
            experts = {
                "w_gate": jnp.stack([
                    lin(p + f"mlp.experts.{e}.gate_proj.weight")
                    for e in range(c.num_experts)]),
                "w_up": jnp.stack([
                    lin(p + f"mlp.experts.{e}.up_proj.weight")
                    for e in range(c.num_experts)]),
                "w_down": jnp.stack([
                    lin(p + f"mlp.experts.{e}.down_proj.weight")
                    for e in range(c.num_experts)]),
            }
            layers.append({
                "attn": {
                    "w_q": lin(p + "self_attn.q_proj.weight"),
                    "w_k": lin(p + "self_attn.k_proj.weight"),
                    "w_v": lin(p + "self_attn.v_proj.weight"),
                    "w_o": lin(p + "self_attn.o_proj.weight"),
                    "q_norm": get(p + "self_attn.q_norm.weight"),
                    "k_norm": get(p + "self_attn.k_norm.weight"),
                },
                "moe": {
                    "w_router": lin(p + "mlp.gate.weight"
                                    ).astype(jnp.float32),
                    **experts,
                },
                "ln_attn": get(p + "input_layernorm.weight"),
                "ln_mlp": get(p + "post_attention_layernorm.weight"),
            })
        embed = get("model.embed_tokens.weight")
        params = {
            "embed": embed,
            "layers": layers,
            "final_norm": get("model.norm.weight"),
            "lm_head": (embed if c.tie_word_embeddings else
                        get("lm_head.weight")),
        }
        return self.shard_params(params)
