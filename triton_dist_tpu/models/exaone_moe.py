"""K-EXAONE (``exaone_moe``) decoder: sliding-window and full attention
mixed per layer, a dense first layer, then sigmoid-routed experts beside
a shared one, each sublayer's OUTPUT RMS-normalised before it is added.

    x <- x + RMS(attn(x))        q, k RMS-normalised per head; rotary in
                                 the sliding layers only (full: none)
    x <- x + RMS(ffn(x))         SwiGLU, or EPShareMoE

One chip holds a SHARE of an expert-parallel deployment
(``ModelConfig.ep_world`` / ``ep_rank``): its experts of every sparse
layer, the shared expert, the router over all experts, attention whole.
The routed part it computes is the part its own experts give
(:class:`~triton_dist_tpu.layers.ep_moe.EPShareMoE`); the exchange that
sums the parts across chips is not built yet (ROADMAP R1).

A window layer's cache is a ring of ``window`` positions per row
(``KVCacheManager(windows=...)``); a full layer keeps the whole-row cache
and the decode step's bounded read (``kv_need``). The stream session's
admission and step programs serve both; the paths that cannot yet
(paged pools, ``mode="sp"``, the mega step, the speculative verify
window, left-padded ragged batches) refuse this model by name.

``forward(..., counted=True)`` also returns the counters of
:attr:`count_names` as one int32 vector, made inside the program: the
engine lets them ride home with the tokens it reads back anyway.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu.layers.common import (
    precompute_rope_cache, rms_norm, shard_param)
from triton_dist_tpu.layers.ep_moe import EPShareMoE
from triton_dist_tpu.layers.tp_attn import TPAttn
from triton_dist_tpu.layers.tp_mlp import TPMLP
from triton_dist_tpu.models.config import ModelConfig


class ExaoneMoE:
    def __init__(self, config: ModelConfig, mesh: Mesh | None = None,
                 axis: str = "tp", fwd_mode: str = "xla_ar",
                 impl: str = "pallas"):
        if mesh is None:
            from triton_dist_tpu.runtime.dist import get_mesh
            mesh = get_mesh()
        c = config
        n = c.num_hidden_layers
        assert len(c.sparse_layers) == n, "sparse_layers: one per layer"
        assert not c.layer_windows or len(c.layer_windows) == n
        if c.scoring_func != "sigmoid":
            raise ValueError(f"exaone_moe routes by sigmoid scores, not "
                             f"{c.scoring_func!r}")
        self.config = c
        self.mesh, self.axis = mesh, axis
        self.fwd_mode = fwd_mode
        self.sp_axis = None
        self.windows = tuple(c.window_of(i) for i in range(n))
        self.attn = TPAttn(c.hidden_size, c.num_attention_heads,
                           c.num_key_value_heads, c.head_dim, mesh=mesh,
                           axis=axis, dtype=c.dtype, fwd_mode=fwd_mode,
                           impl=impl, rms_eps=c.rms_norm_eps,
                           qk_norm=c.qk_norm)
        self.mlp = TPMLP(c.hidden_size, c.intermediate_size, mesh=mesh,
                         axis=axis, dtype=c.dtype, fwd_mode=fwd_mode,
                         impl=impl)
        first, held = c.experts_held
        self.moe = EPShareMoE(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            c.num_experts_per_tok, first, held,
            c.moe_intermediate_size * c.num_shared_experts, mesh=mesh,
            axis=axis, dtype=c.dtype, impl=impl,
            norm_topk_prob=c.norm_topk_prob, scale=c.routed_scaling_factor)
        self.rope_cache = precompute_rope_cache(
            c.head_dim, c.max_position_embeddings, c.rope_theta)
        #: The counters ``forward(counted=True)`` returns, in order.
        #: ``moe.experts_touched`` and the two ``attn.positions_read``
        #: count decode steps only (the weights and the cache positions
        #: a step cannot avoid reading); the others count every token
        #: that is somebody's, prompt or generated.
        self.count_names = (
            ["moe.routed_tokens", "moe.held_pairs",
             "moe.pair_rows_computed", "moe.experts_touched"]
            + [f"moe.expert_pairs.{e}" for e in range(held)]
            + ["attn.positions_read.window", "attn.positions_read.full"])

    def set_fwd(self, mode: str):
        self.fwd_mode = mode
        self.attn.set_fwd(mode)
        self.mlp.set_fwd(mode)

    # -- params ------------------------------------------------------------
    def init(self, key: jax.Array) -> dict:
        c = self.config
        keys = jax.random.split(key, c.num_hidden_layers + 2)
        layers = []
        for i, sparse in enumerate(c.sparse_layers):
            ka, km = jax.random.split(keys[i])
            ffn = ({"moe": self.moe.init(km)} if sparse
                   else {"mlp": self.mlp.init(km)})
            layers.append({"attn": self.attn.init(ka), **ffn,
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
        for lp in params["layers"]:
            ffn = ({"moe": self.moe.shard_params(lp["moe"])} if "moe" in lp
                   else {"mlp": self.mlp.shard_params(lp["mlp"])})
            out["layers"].append({
                "attn": self.attn.shard_params(lp["attn"]), **ffn,
                "ln_attn": shard_param(lp["ln_attn"], m, P()),
                "ln_mlp": shard_param(lp["ln_mlp"], m, P())})
        return out

    # -- forward -----------------------------------------------------------
    def forward(self, params: dict, input_ids: jax.Array, kv_caches,
                offset, mode: str | None = None, kv_start=None,
                block_table=None, kv_need=None, live=None, logits_at=None,
                counted: bool = False):
        """input_ids (B, S); kv_caches [(k, v)] * L, a window layer's
        either a scratch that holds every position (scalar ``offset``:
        an admission's prefill) or the rows' rings (per-row ``offset``,
        S == 1: the stream decode step). Returns (logits, new_caches),
        and the count vector too when ``counted``.

        ``live`` (B, S) bool: the tokens that are somebody's; a bucket's
        pad and a frozen row are routed to no expert and counted
        nowhere (default: all). ``logits_at`` (traced int): compute the
        logits of that one position only, (B, 1, V)."""
        c = self.config
        mode = mode or self.fwd_mode
        if mode not in ("xla_ar", "gemm_ar"):
            raise NotImplementedError(
                f"ExaoneMoE serves the replicated-activation modes "
                f"(xla_ar, gemm_ar), not {mode!r}: forward_sp and the "
                f"row-sharded prefills know no window layers and no "
                f"expert share yet")
        if block_table is not None or kv_start is not None:
            raise NotImplementedError(
                "ExaoneMoE: paged pools and left-padded ragged batches "
                "cannot serve sliding-window layers yet")
        b, s = input_ids.shape
        offset = jnp.asarray(offset, jnp.int32)
        step = offset.ndim == 1          # the per-row decode step
        off2d = offset[:, None] if step else offset
        position_ids = off2d + jnp.tile(
            jnp.arange(s, dtype=jnp.int32)[None], (b, 1))
        flat_live = None if live is None else live.reshape(b * s)

        x = params["embed"][input_ids].reshape(b * s, c.hidden_size)
        new_caches, moe_counts = [], []
        for lp, cache, window in zip(params["layers"], kv_caches,
                                     self.windows):
            a, cache = self.attn(lp["attn"], x, position_ids,
                                 self.rope_cache, cache, offset, mode=mode,
                                 kv_need=kv_need, window=window,
                                 rope=window is not None)
            x = x + rms_norm(a, lp["ln_attn"], c.rms_norm_eps)
            if "moe" in lp:
                f, counts = self.moe(lp["moe"], x, mode=mode, live=flat_live)
                moe_counts.append(counts)
            else:
                f = self.mlp(lp["mlp"], x, mode=mode)
            x = x + rms_norm(f, lp["ln_mlp"], c.rms_norm_eps)
            new_caches.append(cache)

        x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
        x = x.reshape(b, s, c.hidden_size)
        if logits_at is not None:
            x = jax.lax.dynamic_slice_in_dim(x, logits_at, 1, axis=1)
        logits = jnp.dot(x.astype(jnp.float32),
                         params["lm_head"].T.astype(jnp.float32))
        if not counted:
            return logits, new_caches
        return logits, new_caches, self._counts(moe_counts, offset, live)

    def _counts(self, moe_counts, offset, live):
        """:attr:`count_names` as one int32 vector."""
        zero = jnp.zeros((), jnp.int32)
        step = offset.ndim == 1

        def total(name):
            return sum((m[name] for m in moe_counts), zero)

        pairs = sum((m["expert_pairs"] for m in moe_counts),
                    jnp.zeros((self.moe.num_held,), jnp.int32))
        window = full = zero
        if step:
            # What the live rows' steps cannot avoid reading, per layer
            # kind: a window layer the row's last ``window`` positions,
            # a full layer all of them.
            seen = jnp.where(True if live is None else live[:, 0],
                             offset + 1, 0)
            for w in self.windows:
                if w:
                    window = window + jnp.sum(jnp.minimum(seen, w))
                else:
                    full = full + jnp.sum(seen)
        head = jnp.stack([total("routed_tokens"), total("held_pairs"),
                          total("pair_rows_computed"),
                          total("experts_touched") if step else zero])
        return jnp.concatenate([head, pairs,
                                jnp.stack([window, full])]).astype(jnp.int32)
