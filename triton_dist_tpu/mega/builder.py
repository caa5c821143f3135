"""ModelBuilder: records decoder ops layer-by-layer into a TaskGraph.

TPU-native redesign of the reference's ``ModelBuilder``
(python/triton_dist/mega_triton_kernel/models/model_builder.py:408:
``make_linear / make_rms_norm / make_activation / make_flash_decode /
make_allreduce ...`` task builders, tasks/{linear,attn,norm,activation,
elementwise,allreduce}.py) — the recorded graph compiles to ONE jitted
program per step instead of one persistent interpreted kernel.

Ops carry the same roles as the reference task kinds: linear (TP
col/row), rmsnorm, activation (silu·mul), elementwise add, attention
(cached GQA decode), allreduce epilogue (fused gemm_ar). The barrier /
prefetch task kinds collapse: XLA inserts synchronization and HBM→VMEM
prefetch itself.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
from jax.sharding import Mesh

from triton_dist_tpu.layers.common import (
    col_parallel_matmul, rms_norm, row_parallel_matmul_ar)
from triton_dist_tpu.mega.task_graph import TaskGraph
from triton_dist_tpu.ops.gemm_reduce_scatter import (
    create_gemm_rs_context, gemm_ar)


class ModelBuilder:
    """Record ops into a TaskGraph with TP-aware linear tasks."""

    def __init__(self, mesh: Mesh | None = None, axis: str = "tp",
                 impl: str = "pallas", rms_eps: float = 1e-6):
        if mesh is None:
            from triton_dist_tpu.runtime.dist import get_mesh
            mesh = get_mesh()
        self.mesh, self.axis = mesh, axis
        self.impl = impl
        self.rms_eps = rms_eps
        self.graph = TaskGraph()
        self.rs_ctx = create_gemm_rs_context(mesh, axis)

    # -- task builders (reference tasks/*.py) ------------------------------
    def make_rms_norm(self, x: str, w: str, out: str, name=None) -> str:
        fn = functools.partial(rms_norm, eps=self.rms_eps)
        return self.graph.add("rmsnorm", fn, [x, w], [out], name=name)[0]

    def make_linear_col(self, x: str, w: str, out: str, name=None) -> str:
        """Column-parallel GEMM: replicated (M,K) @ col-sharded (K,N/w)."""
        fn = functools.partial(col_parallel_matmul, mesh=self.mesh,
                               axis=self.axis)
        return self.graph.add("linear", fn, [x, w], [out], name=name,
                              cost=4)[0]

    def make_linear_ar(self, x: str, w: str, out: str, name=None) -> str:
        """Row-parallel GEMM + AllReduce epilogue (reference allreduce
        task over symm ptrs ≙ fused gemm_ar kernel)."""
        if self.impl == "xla":
            fn = functools.partial(row_parallel_matmul_ar, mesh=self.mesh,
                                   axis=self.axis)
        else:
            def fn(xv, wv):
                return gemm_ar(xv, wv, self.rs_ctx, impl=self.impl)
        return self.graph.add("linear_ar", fn, [x, w], [out], name=name,
                              cost=6)[0]

    def make_silu_mul(self, gate: str, up: str, out: str, name=None) -> str:
        def fn(g, u):
            import jax
            return (jax.nn.silu(g.astype(jnp.float32)) *
                    u.astype(jnp.float32)).astype(g.dtype)
        return self.graph.add("activation", fn, [gate, up], [out],
                              name=name)[0]

    def make_add(self, a: str, b: str, out: str, name=None) -> str:
        return self.graph.add("elementwise", lambda x, y: x + y, [a, b],
                              [out], name=name)[0]

    def make_attention(self, attn_module, qkv_norm_x: str, attn_params: str,
                       position_ids: str, rope: str, cache_k: str,
                       cache_v: str, offset: str, kv_start: str, out: str,
                       new_k: str, new_v: str, name=None):
        """Cached GQA decode attention task (reference flash_attn paged
        decode task, tasks/attn.py) — wraps the TP attention module's
        projections + core in one task; returns out + updated cache.

        ``offset`` may be a scalar OR a (B,) per-row vector (continuous
        batching: every row decodes at its own cache position) and
        ``kv_start`` carries the (B,) left-pad boundaries of ragged
        batches — both thread straight into ``_attention_core``'s
        scatter/mask path, so the mega graph serves the same batch
        shapes the plain forward does (ISSUE 11)."""
        def fn(x, p, pos, rc, ck, cv, off, ks):
            o, (nk, nv) = attn_module(p, x, pos, rc, (ck, cv), off,
                                      mode=attn_module.fwd_mode,
                                      kv_start=ks)
            return o, nk, nv
        return self.graph.add(
            "attention", fn,
            [qkv_norm_x, attn_params, position_ids, rope, cache_k, cache_v,
             offset, kv_start], [out, new_k, new_v], name=name, cost=8)

    def make_attention_sp(self, model, qkv_norm_x: str, attn_params: str,
                          position_ids: str, rope: str, cache_k: str,
                          cache_v: str, offset: str, out: str, new_k: str,
                          new_v: str, table: str | None = None, name=None):
        """Sequence-parallel DECODE attention task: the seq-sharded
        contiguous cache (``table=None``) or the paged pools (``table``
        names the block-table buffer).

        Mirrors ``dense.forward_sp``'s decode layer attention op for op
        — same projections, per-head norms, rope, scalar/per-row KV
        scatter through ``PagedKVCacheManager``'s one address-math home
        (``position_to_slot`` / ``position_to_slot_rows``), and the
        distributed split-KV flash decode — so the mega graph's greedy
        outputs match the plain stream step bit for bit
        (tests/test_scheduler.py). Frozen rows keep the plain path's
        safety story untouched: their writes land on the sentinel page
        (paged) or a lane the next admission overwrites."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from triton_dist_tpu.layers.common import apply_rope
        from triton_dist_tpu.models.kv_cache import PagedKVCacheManager
        from triton_dist_tpu.ops.flash_decode import (
            gqa_fwd_batch_decode, gqa_fwd_batch_decode_paged)

        ap = model.attn
        hq, hkv, d = ap.num_heads, ap.num_kv_heads, ap.head_dim
        eps = model.config.rms_norm_eps
        mesh, sp = model.mesh, model.sp_axis
        world = mesh.shape[sp]
        fd_ctx, fd_impl = model.fd_ctx, model.fd_impl

        def constrain(t):
            # decode keeps everything replicated (forward_sp: hsh/csh/
            # xsh all collapse to P() at S == 1)
            return jax.lax.with_sharding_constraint(
                t, NamedSharding(mesh, P()))

        def fn(x, a, pos, rc, ck, cv, off, *rest):
            tb = rest[0] if rest else None
            b, s = pos.shape
            q = constrain((x @ a["w_q"]).reshape(b, s, hq, d))
            k = constrain((x @ a["w_k"]).reshape(b, s, hkv, d))
            v = constrain((x @ a["w_v"]).reshape(b, s, hkv, d))
            if ap.qk_norm:
                q = rms_norm(q, a["q_norm"], eps)
                k = rms_norm(k, a["k_norm"], eps)
            q = apply_rope(q, rc, pos)
            k = apply_rope(k, rc, pos)
            kc = constrain(k).astype(ck.dtype)
            vc = constrain(v).astype(cv.dtype)
            if tb is None:
                if off.ndim:
                    rows = jnp.arange(b)
                    ck = ck.at[rows, off].set(kc[:, 0])
                    cv = cv.at[rows, off].set(vc[:, 0])
                else:
                    import jax.lax as lax
                    ck = lax.dynamic_update_slice(ck, kc, (0, off, 0, 0))
                    cv = lax.dynamic_update_slice(cv, vc, (0, off, 0, 0))
                att = gqa_fwd_batch_decode(q[:, 0], ck, cv, off + 1,
                                           fd_ctx, impl=fd_impl)
            else:
                spd = ck.shape[0] // world
                if off.ndim:
                    g, ip = PagedKVCacheManager.position_to_slot_rows(
                        tb, off, ck.shape[1], spd)
                else:
                    g, ip = PagedKVCacheManager.position_to_slot(
                        tb, off, ck.shape[1], spd)
                ck = ck.at[g, ip].set(kc[:, 0])
                cv = cv.at[g, ip].set(vc[:, 0])
                att = gqa_fwd_batch_decode_paged(q[:, 0], ck, cv, tb,
                                                 off + 1, fd_ctx,
                                                 impl=fd_impl)
            att = att[:, None].reshape(b, s, hq * d)
            o = constrain((att @ a["w_o"]).astype(x.dtype))
            return o, ck, cv

        inputs = [qkv_norm_x, attn_params, position_ids, rope, cache_k,
                  cache_v, offset]
        if table is not None:
            inputs.append(table)
        return self.graph.add("attention", fn, inputs,
                              [out, new_k, new_v], name=name, cost=8)

    def make_embedding(self, table: str, ids: str, out: str, name=None):
        def fn(t, i):
            b, s = i.shape
            return t[i].reshape(b * s, t.shape[-1])
        return self.graph.add("embedding", fn, [table, ids], [out],
                              name=name)[0]

    # -- sp-family tasks (forward_sp decode parity, ISSUE 11) --------------
    # The sp/paged engines keep (B, S, H) activations and plain
    # XLA-sharded matmuls (the weight shardings drive the collectives),
    # so their mega graph records forward_sp's exact decode ops rather
    # than the TP fused-op tasks above — op-for-op parity is what makes
    # mega-in-scheduler greedy outputs bit-identical to the plain path.

    def _constrain_replicated(self, t):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.lax.with_sharding_constraint(
            t, NamedSharding(self.mesh, P()))

    def make_embedding_sp(self, table: str, ids: str, out: str, name=None):
        """(B, S, H) embedding lookup with forward_sp's decode
        activation constraint (xsh = P() at S == 1)."""
        def fn(t, i):
            return self._constrain_replicated(t[i])
        return self.graph.add("embedding", fn, [table, ids], [out],
                              name=name)[0]

    def make_linear_sp(self, x: str, w: str, out: str, name=None) -> str:
        """Plain XLA-sharded linear on (B, S, H) activations —
        forward_sp's gate/up projections."""
        return self.graph.add("linear", lambda xv, wv: xv @ wv, [x, w],
                              [out], name=name, cost=4)[0]

    def make_silu_mul_sp(self, gate: str, up: str, out: str,
                         name=None) -> str:
        """``_sp_ffn``'s activation: silu in f32 cast back BEFORE the
        multiply. (:meth:`make_silu_mul` multiplies in f32 — a
        different rounding under bf16; sp parity needs this exact op
        order.)"""
        def fn(g, u):
            import jax
            return jax.nn.silu(g.astype(jnp.float32)).astype(g.dtype) * u
        return self.graph.add("activation", fn, [gate, up], [out],
                              name=name)[0]

    def make_linear_down_sp(self, x: str, w: str, out: str,
                            name=None) -> str:
        """``_sp_ffn``'s down projection with its replicated-output
        constraint (decode xsh = P())."""
        def fn(xv, wv):
            return self._constrain_replicated((xv @ wv).astype(xv.dtype))
        return self.graph.add("linear", fn, [x, w], [out], name=name,
                              cost=6)[0]

    def make_lm_head_sp(self, x: str, w: str, out: str, name=None):
        """forward_sp's LM head: einsum over (B, S, H) in f32."""
        def fn(xv, wv):
            return jnp.einsum("bsh,vh->bsv", xv.astype(jnp.float32),
                              wv.astype(jnp.float32))
        return self.graph.add("linear", fn, [x, w], [out], name=name,
                              cost=4)[0]

    def make_lm_head(self, x: str, w: str, out: str, name=None):
        def fn(xv, wv):
            return jnp.dot(xv.astype(jnp.float32),
                           wv.T.astype(jnp.float32))
        return self.graph.add("linear", fn, [x, w], [out], name=name,
                              cost=4)[0]

    # -- finalize ----------------------------------------------------------
    def compile(self, input_names, output_names, jit: bool = True,
                order_policy: str = "topo"):
        """Resolve deps and emit the step executor (reference
        ``ModelBuilder.compile`` building queues + codegen'ing the
        persistent kernel, model_builder.py / code_generator.py:153).
        ``order_policy="heft"`` emits in critical-path priority order
        (TaskGraph.priority_order)."""
        import jax
        run = self.graph.make_executor(input_names, output_names,
                                       order_policy=order_policy)
        return jax.jit(run) if jit else run
