"""Ling hybrid (``bailing_hybrid``) decoder: KDA linear attention beside
latent attention, leading dense layers, then group-routed experts beside
a shared one.

    x <- x + attn(RMS(x))     every ``layer_group_size``-th layer MLA
                              (layers/mla.py), the others KDA
                              (layers/kda.py)
    x <- x + ffn(RMS(x))      SwiGLU in the first ``first_k_dense_replace``
                              layers, then EPShareMoE (sigmoid scores,
                              selection bias, group limit)

One chip holds a SHARE of an expert-parallel deployment, as
``models/exaone_moe.py`` does: its experts of every sparse layer, the
shared expert, the router over all experts, attention whole; the
exchange that sums the parts across chips is not built yet (ROADMAP R1).

A KDA layer's cache is recurrent state (``KVCacheManager`` kind
``StateLanes``: the (heads, d, d) float32 state and the conv's tail), an
MLA layer's a latent lane (``LatentLane``). The stream session's
admission and step programs serve both: an admission's prefill leaves
the state of the prompt's true length (the bucket's pad changes
nothing) and the row's state is replaced whole; a frozen row's state
stays as it was. The paths that cannot (paged pools and the prefix
cache, ``mode="sp"``, the mega step, the speculative verify window,
chunked admissions, left-padded ragged batches) refuse this model by
name.

The multi-token-prediction head and nonzero SwiGLU clamps are not built:
a configuration that has either in the layers it keeps is refused.

``forward(..., counted=True)`` also returns the counters of
:attr:`count_names` as one int32 vector, made inside the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu.layers.common import rms_norm, shard_param
from triton_dist_tpu.layers.ep_moe import EPShareMoE
from triton_dist_tpu.layers.kda import KDAttn
from triton_dist_tpu.layers.mla import MLAttn
from triton_dist_tpu.layers.tp_mlp import TPMLP
from triton_dist_tpu.models.config import ModelConfig
from triton_dist_tpu.models.kv_cache import LatentLane, StateLanes


class LingHybrid:
    def __init__(self, config: ModelConfig, mesh: Mesh | None = None,
                 axis: str = "tp", fwd_mode: str = "xla_ar",
                 impl: str = "pallas"):
        if mesh is None:
            from triton_dist_tpu.runtime.dist import get_mesh
            mesh = get_mesh()
        c = config
        n = c.num_hidden_layers
        assert len(c.sparse_layers) == n, "sparse_layers: one per layer"
        if c.scoring_func != "sigmoid":
            raise ValueError(f"bailing_hybrid routes by sigmoid scores, not "
                             f"{c.scoring_func!r}")
        if any(map(any, c.swiglu_limits)):
            raise NotImplementedError(
                "LingHybrid: a nonzero expert_swiglu_limit / "
                "share_expert_swiglu_limit in the layers kept; the clamp's "
                "form is not built")
        if c.num_nextn_predict_layers:
            raise NotImplementedError(
                "LingHybrid: the multi-token-prediction head is not built "
                "(ROADMAP R4); cut num_nextn_predict_layers to 0")
        assert c.layer_group_size > 0, "layer_group_size: MLA every n-th"
        self.config = c
        self.mesh, self.axis = mesh, axis
        self.fwd_mode = fwd_mode
        self.sp_axis = None
        #: Per layer: "kda" or "mla".
        self.kinds = tuple("mla" if (i + 1) % c.layer_group_size == 0
                           else "kda" for i in range(n))
        self.kda = KDAttn(c.hidden_size, c.num_attention_heads, c.head_dim,
                          c.conv_size, c.kda_lower_bound, mesh=mesh,
                          axis=axis, dtype=c.dtype, rms_eps=c.rms_norm_eps)
        self.mla = MLAttn(c.hidden_size, c.num_attention_heads,
                          c.qk_nope_head_dim, c.qk_rope_head_dim,
                          c.v_head_dim, c.kv_lora_rank, c.rope_theta,
                          mesh=mesh, axis=axis, dtype=c.dtype,
                          rms_eps=c.rms_norm_eps)
        # The softmax-attention layer and its windows, for the engine's
        # count of the query-key pairs an admission scores.
        self.attn = self.mla
        self.attn_windows = (None,) * self.kinds.count("mla")
        self.kda_layers = self.kinds.count("kda")
        self.windows = ()
        self.cache_kinds = tuple(
            StateLanes(self.kda.state_shapes()) if k == "kda"
            else LatentLane(self.mla.latent_width) for k in self.kinds)
        self.mlp = TPMLP(c.hidden_size, c.intermediate_size, mesh=mesh,
                         axis=axis, dtype=c.dtype, fwd_mode=fwd_mode,
                         impl=impl)
        first, held = c.experts_held
        self.moe = EPShareMoE(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            c.num_experts_per_tok, first, held,
            c.moe_intermediate_size * c.num_shared_experts, mesh=mesh,
            axis=axis, dtype=c.dtype, impl=impl,
            norm_topk_prob=c.norm_topk_prob, scale=c.routed_scaling_factor,
            n_group=c.n_group, topk_group=c.topk_group)
        #: The counters ``forward(counted=True)`` returns, in order.
        #: ``moe.experts_touched``, ``kda.rows_stepped`` (live rows x KDA
        #: layers) and ``attn.positions_read.latent`` (the positions the
        #: live rows' MLA reads cannot skip) count decode steps only; the
        #: other ``moe.*`` every token that is somebody's.
        self.count_names = (
            ["moe.routed_tokens", "moe.held_pairs",
             "moe.pair_rows_computed", "moe.experts_touched"]
            + [f"moe.expert_pairs.{e}" for e in range(held)]
            + ["kda.rows_stepped", "attn.positions_read.latent"])

    def set_fwd(self, mode: str):
        self.fwd_mode = mode
        self.mlp.set_fwd(mode)

    # -- params ------------------------------------------------------------
    def init(self, key: jax.Array) -> dict:
        c = self.config
        keys = jax.random.split(key, c.num_hidden_layers + 2)
        layers = []
        for i, (kind, sparse) in enumerate(zip(self.kinds, c.sparse_layers)):
            ka, km = jax.random.split(keys[i])
            attn = (self.kda if kind == "kda" else self.mla).init(ka)
            ffn = ({"moe": self.moe.init(km)} if sparse
                   else {"mlp": self.mlp.init(km)})
            layers.append({"attn": attn, **ffn,
                           "ln_attn": jnp.ones((c.hidden_size,), c.dtype),
                           "ln_mlp": jnp.ones((c.hidden_size,), c.dtype)})
        shape = (c.vocab_size, c.hidden_size)
        return self.shard_params({
            "embed": jax.random.normal(keys[-2], shape, c.dtype) * 0.02,
            "layers": layers,
            "final_norm": jnp.ones((c.hidden_size,), c.dtype),
            "lm_head": jax.random.normal(keys[-1], shape, c.dtype) * 0.02})

    def shard_params(self, params: dict) -> dict:
        m = self.mesh
        out = {k: shard_param(params[k], m, P())
               for k in ("embed", "final_norm", "lm_head")}
        out["layers"] = []
        for kind, lp in zip(self.kinds, params["layers"]):
            attn = self.kda if kind == "kda" else self.mla
            ffn = ({"moe": self.moe.shard_params(lp["moe"])} if "moe" in lp
                   else {"mlp": self.mlp.shard_params(lp["mlp"])})
            out["layers"].append({
                "attn": attn.shard_params(lp["attn"]), **ffn,
                "ln_attn": shard_param(lp["ln_attn"], m, P()),
                "ln_mlp": shard_param(lp["ln_mlp"], m, P())})
        return out

    # -- forward -----------------------------------------------------------
    def forward(self, params: dict, input_ids: jax.Array, kv_caches,
                offset, mode: str | None = None, kv_start=None,
                block_table=None, kv_need=None, live=None, logits_at=None,
                counted: bool = False):
        """input_ids (B, S); kv_caches: per layer a KDA layer's (state,
        conv tail) or an MLA layer's (latent lane,). A scalar ``offset``
        is a prompt (an admission's prefill into its scratch); a per-row
        ``offset`` with S == 1 the stream decode step. Returns (logits,
        new_caches), and the count vector too when ``counted``.

        ``live`` (B, S) bool: the tokens that are somebody's, a prefix
        of each row; a bucket's pad and a frozen row leave the recurrent
        state as it was, and are routed to no expert and counted
        nowhere (default: all). ``logits_at`` (traced int): compute the
        logits of that one position only, (B, 1, V)."""
        c = self.config
        mode = mode or self.fwd_mode
        if mode not in ("xla_ar", "gemm_ar"):
            raise NotImplementedError(
                f"LingHybrid serves the replicated-activation modes "
                f"(xla_ar, gemm_ar), not {mode!r}: forward_sp and the "
                f"row-sharded prefills know no latent or recurrent layers")
        if block_table is not None or kv_start is not None:
            raise NotImplementedError(
                "LingHybrid: paged pools and left-padded ragged batches "
                "cannot serve latent-cache and recurrent-state layers yet")
        b, s = input_ids.shape
        offset = jnp.asarray(offset, jnp.int32)
        if offset.ndim and s != 1:
            raise NotImplementedError(
                "LingHybrid: a per-row burst (the speculative verify "
                "window) cannot run on recurrent state yet")
        off2d = offset[:, None] if offset.ndim else offset
        position_ids = off2d + jnp.tile(
            jnp.arange(s, dtype=jnp.int32)[None], (b, 1))
        alive = jnp.ones((b, s), bool) if live is None else live
        flat_live = None if live is None else live.reshape(b * s)

        x = params["embed"][input_ids].reshape(b * s, c.hidden_size)
        new_caches, moe_counts = [], []
        for lp, cache, kind in zip(params["layers"], kv_caches, self.kinds):
            h = rms_norm(x, lp["ln_attn"], c.rms_norm_eps).reshape(b, s, -1)
            if kind == "kda":
                a, cache = self.kda(lp["attn"], h, cache, alive)
            else:
                a, cache = self.mla(lp["attn"], h, position_ids, cache,
                                    offset, kv_need=kv_need)
            x = x + a
            h = rms_norm(x, lp["ln_mlp"], c.rms_norm_eps)
            if "moe" in lp:
                f, counts = self.moe(lp["moe"], h, mode=mode, live=flat_live)
                moe_counts.append(counts)
            else:
                f = self.mlp(lp["mlp"], h, mode=mode)
            x = x + f
            new_caches.append(cache)

        x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
        x = x.reshape(b, s, c.hidden_size)
        if logits_at is not None:
            x = jax.lax.dynamic_slice_in_dim(x, logits_at, 1, axis=1)
        logits = jnp.dot(x.astype(jnp.float32),
                         params["lm_head"].T.astype(jnp.float32))
        if not counted:
            return logits, new_caches
        return logits, new_caches, self._counts(moe_counts, offset, alive)

    def _counts(self, moe_counts, offset, alive):
        """:attr:`count_names` as one int32 vector."""
        zero = jnp.zeros((), jnp.int32)
        step = offset.ndim == 1

        def total(name):
            return sum((m[name] for m in moe_counts), zero)

        pairs = sum((m["expert_pairs"] for m in moe_counts),
                    jnp.zeros((self.moe.num_held,), jnp.int32))
        rows = latent = zero
        if step:
            rows = jnp.sum(alive[:, 0].astype(jnp.int32)) * self.kda_layers
            latent = jnp.sum(jnp.where(alive[:, 0], offset + 1, 0)) \
                * len(self.attn_windows)
        head = jnp.stack([total("routed_tokens"), total("held_pairs"),
                          total("pair_rows_computed"),
                          total("experts_touched") if step else zero])
        return jnp.concatenate([head, pairs,
                                jnp.stack([rows, latent])]).astype(jnp.int32)
