"""Qwen3-class dense decoder under tensor parallelism.

TPU-native redesign of the reference's ``DenseLLM``
(python/triton_dist/models/dense.py:117-241: HF-weight-loading TP model,
per-layer ``set_fwd(mode)``, ``init_triton_dist_ctx`` allocating the fused
op contexts). Model math follows HF Qwen3: pre-norm decoder blocks with
GQA attention (per-head q/k RMSNorm) + SwiGLU MLP, rotary embeddings,
tied/untied LM head.

Functional shape: the module owns config + layer objects (which own the
fused-op contexts); parameters are a pytree; ``forward`` threads the KV
cache through. ``jax.jit`` of ``forward`` is the CUDA-graph analog
(SURVEY.md §7 stage 7).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu.layers.common import (
    apply_rope, precompute_rope_cache, rms_norm, shard_param)
from triton_dist_tpu.layers.tp_attn import TPAttn
from triton_dist_tpu.layers.tp_mlp import TPMLP
from triton_dist_tpu.models.config import ModelConfig


class DenseLLM:
    """TP Qwen3 decoder (reference models/dense.py:117)."""

    def __init__(self, config: ModelConfig, mesh: Mesh | None = None,
                 axis: str = "tp", fwd_mode: str = "ag_rs",
                 impl: str = "pallas", sp_axis: str | None = None):
        if mesh is None:
            from triton_dist_tpu.runtime.dist import get_mesh
            mesh = get_mesh()
        self.config = config
        self.mesh, self.axis = mesh, axis
        self.fwd_mode = fwd_mode
        self.sp_axis = sp_axis
        if sp_axis is not None:
            # Sequence-parallel contexts (mode="sp"): ring attention for
            # prefill/training, distributed split-KV flash decode over
            # the sequence-sharded cache. With tp > 1 this is a 2-D
            # tp×sp model: heads shard over tp inside the ring
            # (head_axis), weight collectives come from XLA shardings;
            # decode keeps the cache head-replicated (flash decode runs
            # per sp-rank on full heads).
            tp_world = mesh.shape[axis]
            from triton_dist_tpu.ops.flash_decode import (
                create_flash_decode_context)
            from triton_dist_tpu.ops.sp_attention import (
                create_sp_attention_context)
            self.sp_ctx = create_sp_attention_context(
                mesh, sp_axis, causal=True,
                head_axis=axis if tp_world > 1 else None)
            self.fd_ctx = create_flash_decode_context(mesh, sp_axis)
            self.sp_impl = "ring" if impl == "pallas" else "xla"
            self.fd_impl = impl
        c = config
        # One module per role, reused across layers (all layers share
        # shapes; params differ per layer).
        self.attn = TPAttn(c.hidden_size, c.num_attention_heads,
                           c.num_key_value_heads, c.head_dim, mesh=mesh,
                           axis=axis, dtype=c.dtype, fwd_mode=fwd_mode,
                           impl=impl, rms_eps=c.rms_norm_eps,
                           qk_norm=c.qk_norm)
        self.mlp = TPMLP(c.hidden_size, c.intermediate_size, mesh=mesh,
                         axis=axis, dtype=c.dtype, fwd_mode=fwd_mode,
                         impl=impl)
        self.rope_cache = precompute_rope_cache(
            c.head_dim, c.max_position_embeddings, c.rope_theta)

    def set_fwd(self, mode: str):
        """Switch all layers' forward mode (reference per-layer set_fwd,
        models/dense.py:216)."""
        self.fwd_mode = mode
        self.attn.set_fwd(mode)
        self.mlp.set_fwd(mode)

    # -- params ------------------------------------------------------------
    def init(self, key: jax.Array) -> dict:
        c = self.config
        keys = jax.random.split(key, c.num_hidden_layers + 2)
        layers = []
        for i in range(c.num_hidden_layers):
            ka, km = jax.random.split(keys[i])
            layers.append({
                "attn": self.attn.init(ka),
                "mlp": self.mlp.init(km),
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
                "mlp": self.mlp.shard_params(lp["mlp"]),
                "ln_attn": shard_param(lp["ln_attn"], m, P()),
                "ln_mlp": shard_param(lp["ln_mlp"], m, P()),
            })
        return out

    # -- forward -----------------------------------------------------------
    def forward(self, params: dict, input_ids: jax.Array, kv_caches,
                offset, mode: str | None = None, kv_start=None,
                remat: bool = False, block_table=None, kv_need=None,
                logits_at=None):
        """input_ids: (B, S) int32; kv_caches: [(k, v)] * L; offset: scalar
        write position. Returns (logits (B, S, V), new_caches).

        The reference's ``inference`` (dense.py:200-241). Activation
        layout: row-sharded (M=B*S over tp) for {xla, ag_rs} — requires
        B*S % world == 0; replicated for {xla_ar, gemm_ar} (decode).

        ``kv_start``: optional (B,) left-pad boundaries for ragged
        batches — rope positions count from each row's first real token
        and attention never sees the pad prefix (Engine.serve_ragged).

        ``remat``: checkpoint each decoder layer — activations are
        recomputed in the backward pass instead of stored, trading
        FLOPs for HBM so long-sequence training fits (models/train.py).

        ``kv_need``: optional traced int32 scalar, how many cache
        positions the call's live queries can see; every layer's
        attention then reads only the window that covers them
        (layers/tp_attn._attention_core). The stream decode step passes
        it; ``mode="sp"`` has ``kv_len`` of its own and ignores it.

        ``logits_at`` (traced int, a position inside ``[0, S)``): compute
        the logits of that one position only, (B, 1, V). The caller that
        reads one row of a prompt's logits (an admission, ``serve``'s
        prefill) says which; the layers still run on all S positions,
        whose K/V are the product.
        """
        c = self.config
        mode = mode or self.fwd_mode
        if mode == "sp":
            assert kv_start is None, "mode='sp' has no ragged support yet"
            return self.forward_sp(params, input_ids, kv_caches, offset,
                                   remat=remat, block_table=block_table,
                                   logits_at=logits_at)
        assert block_table is None, "paged caches need mode='sp'"
        b, s = input_ids.shape
        offset = jnp.asarray(offset, jnp.int32)
        # offset may be a (B,) vector (per-row decode positions —
        # continuous batching, Engine.serve_stream — with S == 1, or
        # the S == k+1 speculative-decoding verify window: the
        # attention core scatters row b's K/V at offset[b]+[0, S) and
        # masks each query position causally at its own absolute
        # position).
        off2d = offset[:, None] if offset.ndim else offset
        position_ids = off2d + jnp.tile(
            jnp.arange(s, dtype=jnp.int32)[None], (b, 1))
        if kv_start is not None:
            position_ids = jnp.maximum(
                position_ids - jnp.asarray(kv_start, jnp.int32)[:, None], 0)

        def layer_body(x, lp, cache):
            h = rms_norm(x, lp["ln_attn"], c.rms_norm_eps)
            a, cache = self.attn(lp["attn"], h, position_ids,
                                 self.rope_cache, cache, offset, mode=mode,
                                 kv_start=kv_start, kv_need=kv_need)
            x = x + a
            h = rms_norm(x, lp["ln_mlp"], c.rms_norm_eps)
            x = x + self.mlp(lp["mlp"], h, mode=mode)
            return x, cache

        body = jax.checkpoint(layer_body) if remat else layer_body
        x = params["embed"][input_ids].reshape(b * s, c.hidden_size)
        new_caches = []
        for lp, cache in zip(params["layers"], kv_caches):
            x, cache = body(x, lp, cache)
            new_caches.append(cache)

        x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
        if logits_at is not None:
            x = jax.lax.dynamic_slice_in_dim(
                x.reshape(b, s, c.hidden_size), logits_at, 1, axis=1)[:, 0]
        logits = jnp.dot(x.astype(jnp.float32),
                         params["lm_head"].T.astype(jnp.float32))
        return logits.reshape(b, -1, c.vocab_size), new_caches

    # -- sequence-parallel forward (long-context path) ---------------------
    def forward_sp(self, params: dict, input_ids: jax.Array, kv_caches,
                   offset, remat: bool = False, block_table=None,
                   logits_at=None):
        """Sequence-parallel forward: the long-context path the reference
        serves with ``SpFlashDecodeLayer`` + AG-attention
        (sp_ag_attention_inter_node.py:504, sp_flash_decode_layer.py),
        lifted to the whole model.

        Activations stay (B, S, H) with S sharded over ``sp_axis`` —
        each device holds S/w positions, so max context scales with the
        mesh. With tp > 1 this is a 2-D tp×sp model: projections keep
        their column/row TP shardings (XLA inserts the psums) and the
        ring attention runs on the head-local slice
        (``SpAttentionContext.head_axis``). Prefill/training (S > 1,
        offset must be 0) runs ring SP attention on the
        freshly-projected K/V; decode (S == 1) runs the distributed
        split-KV flash decode over the sequence-sharded,
        head-replicated cache. The cache must be allocated with
        ``KVCacheManager(seq_shard=True, axis=sp_axis)``.

        Differentiable end-to-end in the prefill shape (ring attention
        carries native transpose rules), so ``make_train_step(
        mode="sp")`` trains long sequences with S/w activation memory
        per device on top of the remat option.

        ``block_table``: switches the caches to PAGED pools
        (``PagedKVCacheManager`` layout: per-layer (pool_k, pool_v) of
        (w·slots, page, Hkv, D) dim-0-sharded physical pages plus this
        (w, B, n_pages) table) — prefill scatters the projected K/V
        into the allocated pages, decode writes one position and runs
        the paged distributed flash decode. vLLM-style slot reuse at
        the whole-model level (Engine(paged=True)).

        ``logits_at`` (traced int, a position inside ``[0, S)``): compute
        the logits of that one position only, (B, 1, V), as in
        :meth:`forward`.
        """
        from jax.sharding import NamedSharding
        from triton_dist_tpu.ops.flash_decode import (
            gqa_fwd_batch_decode, gqa_fwd_batch_decode_paged)
        from triton_dist_tpu.ops.sp_attention import sp_ag_attention
        from triton_dist_tpu.ops.common import nestable_shard_map

        assert self.sp_axis is not None, (
            "build the model with sp_axis=... to use mode='sp' "
            "(DenseLLM and Qwen3MoE share this forward)")
        c = self.config
        b, s = input_ids.shape
        sp = self.sp_axis
        decode = s == 1
        # Chunked prefill (S > 1, offset > 0): the chunk's K/V are
        # written into the cache, then ring attention runs with the
        # CACHE as the rotating KV — q positions offset+[0, S), live KV
        # limited to offset+S (sp_ag_attention q_offset/kv_len). A
        # traced offset conservatively selects the chunked path.
        chunked = (s > 1 and getattr(offset, "ndim", 0) == 0
                   and (isinstance(offset, jax.core.Tracer)
                        or int(offset) != 0))
        offset = jnp.asarray(offset, jnp.int32)
        # (B,) per-row offsets supported for decode (continuous
        # batching, Engine.serve_stream — same contract as the dense tp
        # forward): per-row cache writes, masks, and rope positions.
        # With S > 1 a vector offset is the speculative-decoding verify
        # window (Engine spec steps): row b's S tokens sit at absolute
        # positions offset[b]+[0, S), each scoring against its own
        # causal prefix — a burst of S decode steps in one program.
        burst = offset.ndim == 1 and s > 1
        off2d = offset[:, None] if offset.ndim else offset
        pos = off2d + jnp.tile(jnp.arange(s, dtype=jnp.int32)[None],
                               (b, 1))
        tp = self.sp_ctx.head_axis  # single source of truth (ctor)
        # Burst windows are decode-shaped work (S = k+1 small): keep
        # activations replicated like the decode step, not S-sharded.
        xsh = P() if decode or burst else P(None, sp, None)
        hsh = P() if decode or burst else P(None, sp, tp, None)

        def constrain(t, spec):
            return jax.lax.with_sharding_constraint(
                t, NamedSharding(self.mesh, spec))

        ap = self.attn  # head geometry + qk-norm config live there
        hq, hkv, d = ap.num_heads, ap.num_kv_heads, ap.head_dim
        rope = self.rope_cache
        eps = c.rms_norm_eps

        def layer_body(x, lp, cache):
            a = lp["attn"]
            h = rms_norm(x, lp["ln_attn"], eps)
            q = constrain((h @ a["w_q"]).reshape(b, s, hq, d), hsh)
            k = constrain((h @ a["w_k"]).reshape(b, s, hkv, d), hsh)
            v = constrain((h @ a["w_v"]).reshape(b, s, hkv, d), hsh)
            if ap.qk_norm:
                q = rms_norm(q, a["q_norm"], eps)
                k = rms_norm(k, a["k_norm"], eps)
            q = apply_rope(q, rope, pos)
            k = apply_rope(k, rope, pos)
            ck, cv = cache
            # Align to the cache layout (seq-sharded, head-replicated)
            # BEFORE the write: updating with head-sharded operands
            # forces SPMD into an involuntary full rematerialization.
            # (Training discards new_caches, so XLA dead-code-eliminates
            # this whole write chain — prefill attention reads the
            # just-projected k/v, not the cache.)
            csh = P() if decode or burst else P(None, sp, None, None)
            kc = constrain(k, csh).astype(ck.dtype)
            vc = constrain(v, csh).astype(cv.dtype)
            if block_table is None:
                if burst:
                    # Per-row burst (spec verify window): row b's S
                    # tokens scatter at offset[b]+[0, S); out-of-range
                    # positions (frozen rows) drop out of the scatter.
                    rows = jnp.arange(b)
                    posb = offset[:, None] + jnp.arange(
                        s, dtype=jnp.int32)[None]
                    ck = ck.at[rows[:, None], posb].set(kc)
                    cv = cv.at[rows[:, None], posb].set(vc)
                elif offset.ndim:
                    # Per-row decode positions: scatter one position
                    # per row into its own lane.
                    rows = jnp.arange(b)
                    ck = ck.at[rows, offset].set(kc[:, 0])
                    cv = cv.at[rows, offset].set(vc[:, 0])
                else:
                    ck = jax.lax.dynamic_update_slice(ck, kc,
                                                      (0, offset, 0, 0))
                    cv = jax.lax.dynamic_update_slice(cv, vc,
                                                      (0, offset, 0, 0))
            elif decode or burst:
                # Single-position (or per-row burst) paged write — the
                # address math lives in ONE place
                # (PagedKVCacheManager.position_to_slot*).
                from triton_dist_tpu.models.kv_cache import (
                    PagedKVCacheManager)
                spd = ck.shape[0] // self.mesh.shape[sp]
                if burst:
                    # Spec verify window: position j of row b is
                    # offset[b]+j. Positions past max_seq (frozen rows
                    # at stale offsets, or a live row padded past its
                    # own clamp by a wider batchmate) reroute to the
                    # device-0 SENTINEL page instead of wrapping the
                    # address math into a live block.
                    t_total = ck.shape[1] * block_table.shape[2] \
                        * self.mesh.shape[sp]
                    for j in range(s):
                        posj = offset + j
                        ok = posj < t_total
                        g, ip = \
                            PagedKVCacheManager.position_to_slot_rows(
                                block_table,
                                jnp.minimum(posj, t_total - 1),
                                ck.shape[1], spd)
                        g = jnp.where(ok, g, spd - 1)
                        ck = ck.at[g, ip].set(kc[:, j])
                        cv = cv.at[g, ip].set(vc[:, j])
                elif offset.ndim:
                    g, ip = PagedKVCacheManager.position_to_slot_rows(
                        block_table, offset, ck.shape[1], spd)
                    ck = ck.at[g, ip].set(kc[:, 0])
                    cv = cv.at[g, ip].set(vc[:, 0])
                else:
                    g, ip = PagedKVCacheManager.position_to_slot(
                        block_table, offset, ck.shape[1], spd)
                    ck = ck.at[g, ip].set(kc[:, 0])
                    cv = cv.at[g, ip].set(vc[:, 0])
            elif chunked:
                # Paged chunked prefill (prefix-cache suffix admission,
                # ISSUE 6): scatter ONLY positions offset+[0, S) into
                # the row's private pages — a full-table scatter here
                # would zero the shared cached-prefix blocks out from
                # under every other request referencing them.
                from triton_dist_tpu.models.kv_cache import (
                    PagedKVCacheManager)
                spd = ck.shape[0] // self.mesh.shape[sp]
                posn = offset + jnp.arange(s, dtype=jnp.int32)
                g, ip = PagedKVCacheManager.position_to_slot(
                    block_table, posn, ck.shape[1], spd)   # (S, B), (S,)
                ck = ck.at[g, ip[:, None]].set(kc.swapaxes(0, 1))
                cv = cv.at[g, ip[:, None]].set(vc.swapaxes(0, 1))
            else:
                ck = self._paged_scatter(ck, kc, block_table,
                                         nestable_shard_map)
                cv = self._paged_scatter(cv, vc, block_table,
                                         nestable_shard_map)
            if decode:
                if block_table is None:
                    att = gqa_fwd_batch_decode(q[:, 0], ck, cv,
                                               offset + 1, self.fd_ctx,
                                               impl=self.fd_impl)
                else:
                    att = gqa_fwd_batch_decode_paged(
                        q[:, 0], ck, cv, block_table, offset + 1,
                        self.fd_ctx, impl=self.fd_impl)
                att = att[:, None]
            elif burst:
                # Spec verify window: query position j runs the SAME
                # per-row flash decode the sequential stream step runs
                # — kv_len = offset+j+1 masks every later window
                # position, so logits are bit-identical to S sequential
                # decode steps (the spec acceptance contract,
                # docs/serving.md "Speculative decoding"). S = k+1 is
                # small, so the unrolled loop stays one program.
                atts = []
                for j in range(s):
                    if block_table is None:
                        atts.append(gqa_fwd_batch_decode(
                            q[:, j], ck, cv, offset + j + 1,
                            self.fd_ctx, impl=self.fd_impl))
                    else:
                        atts.append(gqa_fwd_batch_decode_paged(
                            q[:, j], ck, cv, block_table,
                            offset + j + 1, self.fd_ctx,
                            impl=self.fd_impl))
                att = jnp.stack(atts, axis=1)
            elif chunked:
                # Cache-aware chunk: attend over the updated cache
                # (prefix [0, offset) + this chunk), ring or xla. With a
                # STATIC offset (the scheduler's common case) the
                # rotated KV is sliced to the world-aligned live prefix
                # — a 512-token chunk at the front of a 64k cache must
                # not ppermute 64k mostly-masked positions per layer.
                if block_table is not None:
                    # Paged: reconstruct the contiguous per-row view —
                    # shared prefix blocks and this chunk's fresh
                    # writes land in one (B, T, Hkv, D) tensor; the
                    # kv_len mask below hides positions past the live
                    # length (gathered_view's docstring has the cost
                    # story).
                    from triton_dist_tpu.models.kv_cache import (
                        PagedKVCacheManager)
                    w = self.mesh.shape[sp]
                    csh = P(None, sp, None, None)
                    ck_att = constrain(PagedKVCacheManager.gathered_view(
                        ck, block_table, w), csh)
                    cv_att = constrain(PagedKVCacheManager.gathered_view(
                        cv, block_table, w), csh)
                else:
                    ck_att, cv_att = ck, cv
                if (block_table is None
                        and not isinstance(offset, jax.core.Tracer)):
                    # Slice the cache to the live prefix, rounded up to
                    # a length sp_ag_attention accepts: a multiple of
                    # BOTH the cache shard size (so the slice lands on
                    # shard boundaries) and world (its t % world == 0
                    # contract — advisor r3: per alone breaks when
                    # t_cache//world is not itself a world multiple).
                    # The sliced tensor is re-partitioned over the sp
                    # axis by the shard_map in_specs (data movement
                    # proportional to t_live, still far cheaper than
                    # ring-attending the full mostly-masked cache).
                    import math
                    world_sp = self.mesh.shape[sp]
                    t_cache = ck.shape[1]
                    if t_cache % world_sp == 0:
                        per = t_cache // world_sp
                        step = math.lcm(per, world_sp)
                        t_live = -(-(int(offset) + s) // step) * step
                        if t_live < t_cache:
                            ck_att = ck[:, :t_live]
                            cv_att = cv[:, :t_live]
                att = sp_ag_attention(
                    q, ck_att, cv_att, self.sp_ctx,
                    impl=("xla" if self.sp_impl == "xla" else "ring"),
                    q_offset=offset, kv_len=offset + s)
            else:
                # Ring attention over the JUST-projected K/V (single-
                # shot prefill from offset 0 — the Engine's fast path).
                att = sp_ag_attention(q, k, v, self.sp_ctx,
                                      impl=self.sp_impl)
            att = att.reshape(b, s, hq * d)
            x = x + constrain((att @ a["w_o"]).astype(x.dtype), xsh)
            h = rms_norm(x, lp["ln_mlp"], eps)
            x = x + self._sp_ffn(lp, h, constrain, xsh)
            return x, (ck, cv)

        body = jax.checkpoint(layer_body) if remat else layer_body
        x = constrain(params["embed"][input_ids], xsh)
        new_caches = []
        for lp, cache in zip(params["layers"], kv_caches):
            x, cache = body(x, lp, cache)
            new_caches.append(cache)

        x = rms_norm(x, params["final_norm"], eps)
        if logits_at is not None:
            x = jax.lax.dynamic_slice_in_dim(x, logits_at, 1, axis=1)
        logits = jnp.einsum("bsh,vh->bsv", x.astype(jnp.float32),
                            params["lm_head"].astype(jnp.float32))
        return logits, new_caches

    def _sp_ffn(self, lp, h, constrain, xsh):
        """FFN block of the sp forward on (B, S, H) activations — the
        hook Qwen3MoE overrides with its row-local MoE (the rest of
        forward_sp is model-agnostic and shared)."""
        m = lp["mlp"]
        gate = h @ m["w_gate"]
        up = h @ m["w_up"]
        act = jax.nn.silu(gate.astype(jnp.float32)).astype(h.dtype) * up
        return constrain((act @ m["w_down"]).astype(h.dtype), xsh)

    def _paged_scatter(self, pool, kv, table, shard_map_fn):
        """Scatter a (B, S, Hkv, D) seq-sharded prefill K/V into the
        paged pool: stage into the cache's position space (zeros past
        S), then each device moves its t_loc positions into its
        allocated page slots — a purely local scatter (the allocator
        guarantees distinct (row, page) → distinct slots).

        Known cost: staging + scatter are O(max_seq) per layer, not
        O(S) — a short prompt in a large-capacity engine rewrites the
        zero tail of every allocated page. Acceptable while prefill is
        single-shot (one scatter per serve); a page-granular scatter
        bounded by ceil(S/page) needs per-device drop-masked indices
        (the position spaces of K (S/w blocks) and the cache (t_loc
        blocks) disagree when S < capacity) — optimization candidate.
        """
        sp = self.sp_axis
        world = self.mesh.shape[sp]
        b, s = kv.shape[0], kv.shape[1]
        page, hkv, d = pool.shape[1], pool.shape[2], pool.shape[3]
        n_pages = table.shape[2]
        t_total = page * n_pages * world
        assert s <= t_total, f"prefill {s} > paged capacity {t_total}"
        staged = jnp.zeros((b, t_total, hkv, d), pool.dtype)
        staged = jax.lax.with_sharding_constraint(
            staged, jax.sharding.NamedSharding(self.mesh,
                                               P(None, sp, None, None)))
        staged = jax.lax.dynamic_update_slice(staged, kv, (0, 0, 0, 0))

        def local(pool_l, st_l, tb_l):
            pages = st_l.reshape(b, n_pages, page, hkv, d)
            return pool_l.at[tb_l.reshape(-1)].set(
                pages.reshape(b * n_pages, page, hkv, d))

        return shard_map_fn(
            local, mesh=self.mesh,
            in_specs=(P(sp), P(None, sp), P(sp)),
            out_specs=P(sp), check_vma=False)(pool, staged, table)

    # -- HF weights --------------------------------------------------------
    def load_hf_state_dict(self, state: dict) -> dict:
        """Map a HF Qwen3 state dict (name → array) to our params pytree
        and shard (the reference shards at load, dense.py:150-168,
        tp_mlp.py:72-96). Accepts numpy/jnp arrays or anything
        np.asarray-able (torch tensors via ``.numpy()``)."""
        c = self.config

        def get(name):
            a = state[name]
            if hasattr(a, "detach"):
                a = a.detach().cpu().numpy()
            return jnp.asarray(np.asarray(a), c.dtype)

        def lin(name):
            # HF nn.Linear keeps (out, in); we use (in, out).
            return get(name).T

        layers = []
        for i in range(c.num_hidden_layers):
            p = f"model.layers.{i}."
            attn = {
                "w_q": lin(p + "self_attn.q_proj.weight"),
                "w_k": lin(p + "self_attn.k_proj.weight"),
                "w_v": lin(p + "self_attn.v_proj.weight"),
                "w_o": lin(p + "self_attn.o_proj.weight"),
            }
            if c.qk_norm:  # absent in Llama-3 / Seed-OSS checkpoints
                attn["q_norm"] = get(p + "self_attn.q_norm.weight")
                attn["k_norm"] = get(p + "self_attn.k_norm.weight")
            layers.append({
                "attn": attn,
                "mlp": {
                    "w_gate": lin(p + "mlp.gate_proj.weight"),
                    "w_up": lin(p + "mlp.up_proj.weight"),
                    "w_down": lin(p + "mlp.down_proj.weight"),
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
