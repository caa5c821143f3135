"""Qwen3 decode step as a mega task graph.

TPU-native redesign of the reference's mega-kernel Qwen3 integration
(python/triton_dist/mega_triton_kernel/models/qwen3.py:201: records the
whole decoder step op-by-op through ModelBuilder, then launches the
persistent kernel each step). Here the recorded graph jits into one XLA
program replayed per decode step; numerics match the plain forward
exactly (test_mega.py, tests/test_scheduler.py).

Two graph families, selected by ``decode_mode`` (ISSUE 11):

* dense tp (``gemm_ar``/``xla_ar``/...): the TP fused-op tasks over
  contiguous (B, T, Hkv, D) caches, matching
  ``DenseLLM.forward(mode=decode_mode)``;
* ``"sp"`` (± ``paged``): forward_sp's decode ops over the seq-sharded
  cache or the paged pools, matching
  ``DenseLLM.forward_sp`` — the continuous-batching scheduler's
  native substrate.

Both take ``offset`` as a scalar OR a (B,) per-row vector (every row
decodes at its own cache position — the shared-batch stream step), the
dense family additionally takes ragged ``kv_start`` boundaries, and the
paged family takes the block table. That is what lets ``Engine``'s
scheduler pump the mega step like any other decode forward instead of
refusing paged/ragged configurations.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from triton_dist_tpu.mega.builder import ModelBuilder
from triton_dist_tpu.models.dense import DenseLLM


class MegaQwen3:
    """One-program decode step for a DenseLLM (reference bench target:
    mega_triton_kernel.md decode latencies, SURVEY.md §6)."""

    def __init__(self, model: DenseLLM, decode_mode: str = "gemm_ar",
                 order_policy: str = "topo", paged: bool = False):
        self.model = model
        self.decode_mode = decode_mode
        self.order_policy = order_policy
        self.sp = decode_mode == "sp"
        self.paged = bool(paged)
        if self.paged and not self.sp:
            raise ValueError("paged mega decode rides the sp cache "
                             "layout — pass decode_mode='sp'")
        c = model.config
        if self.sp:
            # ValueError, not assert: user-facing configuration
            # validation must survive ``python -O`` (same contract as
            # Engine's decode_path checks).
            if not getattr(model, "sp_axis", None):
                raise ValueError(
                    "mega sp decode needs a model built with sp_axis=...")
        else:
            model.attn.set_fwd(decode_mode)
        b = ModelBuilder(model.mesh, model.axis, impl=model.attn.impl,
                         rms_eps=c.rms_norm_eps)
        self.builder = b

        inputs = ["ids", "pos", "offset", "rope", "embed", "final_norm",
                  "lm_head"]
        if self.sp:
            if self.paged:
                inputs.append("table")
        else:
            inputs.append("kv_start")
        outputs = []
        if self.sp:
            b.make_embedding_sp("embed", "ids", "x0")
        else:
            b.make_embedding("embed", "ids", "x0")
        x = "x0"
        for i in range(c.num_hidden_layers):
            p = f"l{i}."
            inputs += [p + "attn", p + "ln_attn", p + "w_gate", p + "w_up",
                       p + "w_down", p + "ln_mlp", p + "ck", p + "cv"]
            b.make_rms_norm(x, p + "ln_attn", p + "h_attn")
            if self.sp:
                b.make_attention_sp(
                    model, p + "h_attn", p + "attn", "pos", "rope",
                    p + "ck", p + "cv", "offset", p + "a", p + "nk",
                    p + "nv", table="table" if self.paged else None,
                    name=f"attn{i}")
            else:
                b.make_attention(model.attn, p + "h_attn", p + "attn",
                                 "pos", "rope", p + "ck", p + "cv",
                                 "offset", "kv_start",
                                 p + "a", p + "nk", p + "nv",
                                 name=f"attn{i}")
            outputs += [p + "nk", p + "nv"]
            b.make_add(x, p + "a", p + "x_mid")
            b.make_rms_norm(p + "x_mid", p + "ln_mlp", p + "h_mlp")
            if self.sp:
                b.make_linear_sp(p + "h_mlp", p + "w_gate", p + "gate",
                                 name=f"gate{i}")
                b.make_linear_sp(p + "h_mlp", p + "w_up", p + "up",
                                 name=f"up{i}")
                b.make_silu_mul_sp(p + "gate", p + "up", p + "act")
                b.make_linear_down_sp(p + "act", p + "w_down", p + "down",
                                      name=f"down{i}")
            else:
                b.make_linear_col(p + "h_mlp", p + "w_gate", p + "gate",
                                  name=f"gate{i}")
                b.make_linear_col(p + "h_mlp", p + "w_up", p + "up",
                                  name=f"up{i}")
                b.make_silu_mul(p + "gate", p + "up", p + "act")
                b.make_linear_ar(p + "act", p + "w_down", p + "down",
                                 name=f"down{i}")
            b.make_add(p + "x_mid", p + "down", p + "x_out")
            x = p + "x_out"
        b.make_rms_norm(x, "final_norm", "x_final")
        if self.sp:
            b.make_lm_head_sp("x_final", "lm_head", "logits")
        else:
            b.make_lm_head("x_final", "lm_head", "logits")
        self._input_names = inputs
        self._output_names = ["logits"] + outputs
        self._step = b.compile(inputs, self._output_names,
                               order_policy=order_policy)

    @property
    def graph(self):
        return self.builder.graph

    def flat_args(self, params: dict, token: jax.Array, kv_caches,
                  offset, kv_start=None, table=None) -> list:
        """The executor's positional argument list (also what lowers
        the program for a memory analysis).

        ``offset``: scalar or (B,) per-row decode positions.
        ``kv_start`` (dense family): (B,) ragged left-pad boundaries;
        ``None`` means the uniform batch (zeros — bit-identical to the
        plain forward called without kv_start). ``table`` (paged
        family): the (w, B, n_pages) device block table."""
        bsz, s = token.shape
        offset = jnp.asarray(offset, jnp.int32)
        off2d = offset[:, None] if offset.ndim else offset
        pos = off2d + jnp.tile(jnp.arange(s, dtype=jnp.int32)[None],
                               (bsz, 1))
        args = {
            "ids": token, "offset": offset,
            "rope": self.model.rope_cache,
            "embed": params["embed"], "final_norm": params["final_norm"],
            "lm_head": params["lm_head"],
        }
        # ValueErrors, not asserts: these are caller-facing contract
        # checks (they fire at trace time) and must survive python -O.
        if self.sp:
            if kv_start is not None:
                raise ValueError("mode='sp' has no ragged support yet")
            if self.paged:
                if table is None:
                    raise ValueError(
                        "paged mega step needs the block table")
                args["table"] = table
            elif table is not None:
                raise ValueError(
                    "block tables need MegaQwen3(paged=True)")
        else:
            if table is not None:
                raise ValueError("paged tables ride the sp mega graph")
            ks = (jnp.zeros((bsz,), jnp.int32) if kv_start is None
                  else jnp.asarray(kv_start, jnp.int32))
            # Same clamp the plain forward applies for ragged batches
            # (zeros leave pos untouched — the uniform case stays
            # bit-identical).
            pos = jnp.maximum(pos - ks[:, None], 0)
            args["kv_start"] = ks
        args["pos"] = pos
        for i, (lp, (ck, cv)) in enumerate(zip(params["layers"],
                                               kv_caches)):
            p = f"l{i}."
            args[p + "attn"] = lp["attn"]
            args[p + "ln_attn"] = lp["ln_attn"]
            args[p + "ln_mlp"] = lp["ln_mlp"]
            args[p + "w_gate"] = lp["mlp"]["w_gate"]
            args[p + "w_up"] = lp["mlp"]["w_up"]
            args[p + "w_down"] = lp["mlp"]["w_down"]
            args[p + "ck"], args[p + "cv"] = ck, cv
        return [args[n] for n in self._input_names]

    def step(self, params: dict, token: jax.Array, kv_caches, offset,
             kv_start=None, table=None):
        """token: (B, 1) int32 → (logits (B, 1, V), new_caches)."""
        c = self.model.config
        bsz, s = token.shape
        out = self._step(*self.flat_args(params, token, kv_caches, offset,
                                         kv_start=kv_start, table=table))
        logits, flat = out[0], out[1:]
        caches = [(flat[2 * i], flat[2 * i + 1])
                  for i in range(c.num_hidden_layers)]
        return logits.reshape(bsz, s, c.vocab_size), caches
