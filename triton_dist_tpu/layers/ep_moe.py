"""Expert-parallel MoE FFN layer.

TPU-native equivalent of the reference's EP inference path
(python/triton_dist/test/nvidia/test_ep_moe_inference.py, 504 LoC:
Qwen3-MoE served with experts sharded across ranks and token routing via
the LL all-to-all; models/qwen_moe.py:108): the router runs on local
rows, :class:`~triton_dist_tpu.layers.ep_a2a.EPAll2AllLayer` dispatches
each (token, expert) pair to the rank owning the expert, the rank runs
its experts at FULL intermediate size over the received rows
(``grouped_expert_ffn`` — sorted ``ragged_dot``), and combine returns +
top-k-reduces the pair rows.

Contrast with :class:`~triton_dist_tpu.layers.tp_moe.TPMoE`: TP shards
every expert's intermediate dim across ranks (all ranks touch all
experts); EP shards the expert set itself (each rank owns E/w whole
experts) — the reference offers both, selected per deployment.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from triton_dist_tpu.ops.common import nestable_shard_map

from triton_dist_tpu.layers.common import shard_param
from triton_dist_tpu.layers.ep_a2a import EPAll2AllLayer
from triton_dist_tpu.ops.group_gemm import (
    grouped_expert_ffn, held_expert_ffn)
from triton_dist_tpu.ops.moe_utils import sigmoid_topk_routing, topk_routing


class EPMoE:
    """Expert-parallel sparse FFN: dispatch → local experts → combine."""

    def __init__(self, hidden_size: int, intermediate_size: int,
                 num_experts: int, topk: int, mesh: Mesh | None = None,
                 axis: str = "ep", dtype=jnp.bfloat16,
                 impl: str = "pallas", norm_topk_prob: bool = True,
                 wire_dtype: str | None = None):
        if mesh is None:
            from triton_dist_tpu.runtime.dist import get_mesh
            mesh = get_mesh()
        self.mesh, self.axis = mesh, axis
        self.world = mesh.shape[axis]
        assert num_experts % self.world == 0
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_experts = num_experts
        self.experts_per_rank = num_experts // self.world
        self.topk = topk
        self.dtype = dtype
        self.impl = impl
        self.norm_topk_prob = norm_topk_prob
        self.wire_dtype = wire_dtype  # "fp8": quantized dispatch wire
        # One a2a layer per distinct per-rank token count (prefill vs
        # decode shapes); the reference similarly sizes its symmetric
        # buffers by max_M and reuses them (ep_a2a_layer.py:70-90).
        self._a2a: dict[int, EPAll2AllLayer] = {}

    def set_fwd(self, mode: str):  # parity with TPMoE's interface
        pass

    def _a2a_for(self, t_loc: int) -> EPAll2AllLayer:
        if t_loc not in self._a2a:
            self._a2a[t_loc] = EPAll2AllLayer(
                max_tokens=t_loc, hidden=self.hidden_size, topk=self.topk,
                num_experts=self.num_experts, mesh=self.mesh,
                axis=self.axis, dtype=self.dtype, impl=self.impl,
                wire_dtype=self.wire_dtype)
        return self._a2a[t_loc]

    # -- params (same pytree as TPMoE; EP sharding) -------------------------
    def init(self, key: jax.Array) -> dict:
        kr, kg, ku, kd = jax.random.split(key, 4)
        h, i, e = self.hidden_size, self.intermediate_size, self.num_experts
        params = {
            "w_router": jax.random.normal(kr, (h, e), jnp.float32) * h**-0.5,
            "w_gate": jax.random.normal(kg, (e, h, i), self.dtype) * h**-0.5,
            "w_up": jax.random.normal(ku, (e, h, i), self.dtype) * h**-0.5,
            "w_down": jax.random.normal(kd, (e, i, h), self.dtype) * i**-0.5,
        }
        return self.shard_params(params)

    def shard_params(self, params: dict) -> dict:
        m, ax = self.mesh, self.axis
        return {
            "w_router": shard_param(params["w_router"], m, P()),
            # Expert dim sharded: each rank owns E/w whole experts.
            "w_gate": shard_param(params["w_gate"], m, P(ax)),
            "w_up": shard_param(params["w_up"], m, P(ax)),
            "w_down": shard_param(params["w_down"], m, P(ax)),
        }

    # -- forward -----------------------------------------------------------
    def __call__(self, params: dict, x: jax.Array,
                 mode: str | None = None) -> jax.Array:
        """x: (T, H) row-sharded over ``axis``; returns the same layout.

        ``mode`` accepts "ep" (default, LL a2a dispatch) or "xla"
        (dispatch/combine ride the XLA all_to_all baseline).

        Rows are padded up to a multiple of the axis size (decode-size
        batches) — pad rows carry zero weights and are sliced off."""
        t, h = x.shape
        t_pad = -(-t // self.world) * self.world
        logits = x.astype(jnp.float32) @ params["w_router"]
        weights, indices = topk_routing(logits, self.topk,
                                        self.norm_topk_prob)
        if t_pad != t:
            pad = t_pad - t
            x = jnp.concatenate([x, jnp.zeros((pad, h), x.dtype)])
            weights = jnp.concatenate(
                [weights, jnp.zeros((pad,) + weights.shape[1:],
                                    weights.dtype)])
            indices = jnp.concatenate(
                [indices, jnp.zeros((pad,) + indices.shape[1:],
                                    indices.dtype)])

        t_loc = t_pad // self.world
        a2a = self._a2a_for(t_loc)
        e_loc = self.experts_per_rank

        tokens, local_expert, handle = a2a.dispatch(x, indices)

        def local_ffn(tok, exp, wg, wu, wd):
            return grouped_expert_ffn(tok, wg, wu, wd, exp, e_loc)

        ffn = nestable_shard_map(
            local_ffn, mesh=self.mesh,
            in_specs=(P(self.axis), P(self.axis), P(self.axis),
                      P(self.axis), P(self.axis)),
            out_specs=P(self.axis), check_vma=False)
        expert_out = ffn(tokens, local_expert, params["w_gate"],
                         params["w_up"], params["w_down"])

        out = a2a.combine(expert_out, weights, handle)
        return out[:t] if t_pad != t else out


class EPShareMoE:
    """One expert-parallel rank's share of a sigmoid-routed MoE layer
    with a shared expert (DeepSeek-V3 / K-EXAONE style), WITHOUT its
    exchange: the layer is told which experts it holds
    (``[first_held, first_held + num_held)`` of ``num_experts``), scores
    and selects over all of them, and computes its own experts' part of
    every token's result plus the shared expert. That partial result is
    the layer's output: on one chip of a deployment whose other ranks
    are absent nothing stands in for them or for their traffic (the
    exchange that sums the parts across ranks is ROADMAP R1).

    Activations are replicated over ``axis`` (modes ``xla_ar`` /
    ``gemm_ar``); the held experts' weights are replicated too, the
    shared expert is a :class:`TPMLP`."""

    def __init__(self, hidden_size: int, intermediate_size: int,
                 num_experts: int, topk: int, first_held: int,
                 num_held: int, shared_intermediate_size: int,
                 mesh: Mesh | None = None, axis: str = "tp",
                 dtype=jnp.bfloat16, impl: str = "pallas",
                 norm_topk_prob: bool = True, scale: float = 1.0,
                 n_group: int = 1, topk_group: int = 1):
        from triton_dist_tpu.layers.tp_mlp import TPMLP
        if mesh is None:
            from triton_dist_tpu.runtime.dist import get_mesh
            mesh = get_mesh()
        assert 0 <= first_held and first_held + num_held <= num_experts
        self.mesh, self.axis = mesh, axis
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_experts, self.topk = num_experts, topk
        self.first_held, self.num_held = first_held, num_held
        self.dtype = dtype
        self.norm_topk_prob, self.scale = norm_topk_prob, scale
        self.n_group, self.topk_group = n_group, topk_group
        self.shared = TPMLP(hidden_size, shared_intermediate_size,
                            mesh=mesh, axis=axis, dtype=dtype, impl=impl)

    def init(self, key: jax.Array) -> dict:
        kr, kg, ku, kd, ks = jax.random.split(key, 5)
        h, i, n = self.hidden_size, self.intermediate_size, self.num_held
        params = {
            "w_router": jax.random.normal(
                kr, (h, self.num_experts), jnp.float32) * h**-0.5,
            "e_bias": jnp.zeros((self.num_experts,), jnp.float32),
            "w_gate": jax.random.normal(kg, (n, h, i), self.dtype) * h**-0.5,
            "w_up": jax.random.normal(ku, (n, h, i), self.dtype) * h**-0.5,
            "w_down": jax.random.normal(kd, (n, i, h), self.dtype) * i**-0.5,
        }
        out = self.shard_params(params)
        out["shared"] = self.shared.init(ks)
        return out

    def shard_params(self, params: dict) -> dict:
        out = {k: shard_param(params[k], self.mesh, P())
               for k in ("w_router", "e_bias", "w_gate", "w_up", "w_down")}
        if "shared" in params:
            out["shared"] = self.shared.shard_params(params["shared"])
        return out

    def __call__(self, params: dict, x: jax.Array, mode: str = "xla_ar",
                 live: jax.Array | None = None):
        """x: (T, H) replicated. ``live`` (T,) bool: tokens that are
        somebody's (not a bucket's pad, not a frozen row); the others
        are routed nowhere and counted nowhere. Returns (out (T, H),
        counts): ``routed_tokens``, ``held_pairs``,
        ``pair_rows_computed``, ``experts_touched`` () int32 and
        ``expert_pairs`` (num_held,) int32."""
        assert mode in ("xla_ar", "gemm_ar"), (
            f"EPShareMoE takes replicated activations, not mode {mode!r}")
        logits = jnp.dot(x.astype(jnp.float32), params["w_router"],
                         precision=jax.lax.Precision.HIGHEST)
        weights, idx = sigmoid_topk_routing(
            logits, params["e_bias"], self.topk, self.norm_topk_prob,
            self.scale, self.n_group, self.topk_group)
        local = idx - self.first_held
        mine = (local >= 0) & (local < self.num_held)
        if live is not None:
            mine &= live[:, None]
        local = jnp.where(mine, local, self.num_held)
        routed, sizes, rows = held_expert_ffn(
            x, params["w_gate"], params["w_up"], params["w_down"], local,
            weights, self.num_held / self.num_experts)
        out = routed.astype(x.dtype) + self.shared(params["shared"], x,
                                                   mode=mode)
        n_live = (jnp.int32(x.shape[0]) if live is None
                  else jnp.sum(live.astype(jnp.int32)))
        counts = {"routed_tokens": n_live, "held_pairs": jnp.sum(sizes),
                  "pair_rows_computed": rows,
                  "experts_touched": jnp.sum((sizes > 0).astype(jnp.int32)),
                  "expert_pairs": sizes}
        return out, counts
