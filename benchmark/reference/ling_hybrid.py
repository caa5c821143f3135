"""Plain reference of one chip's share of a Ling hybrid
(``bailing_hybrid``) decoder, and the weights of a run.

Straightforward ``jax.numpy`` in float32 with every matmul at
``Precision.HIGHEST``; no kernels, no cache, no batching tricks, no
chunked form: a whole forward pass over prompt + served tokens, one
layer at a time so that float32 copies of one layer are all it holds.
It imports nothing of ``triton_dist_tpu`` and takes nothing the program
has made.

The layer, with ``x`` the residual stream (42 layers published):
``x += attn(RMS(x))``, ``x += ffn(RMS(x))``.

* attention, layer ``i``: latent attention (MLA) where
  ``(i + 1) % layer_group_size == 0``, KDA everywhere else.
* KDA, the recurrence itself, one position after the other: ``q, k, v``
  projected, each through a causal depthwise conv of width
  ``short_conv_kernel_size`` (no bias) and SiLU; ``q`` and ``k``
  L2-normalised per head, ``q`` scaled by ``d^-1/2``; per-channel
  log-decay ``g = kda_lower_bound * sigmoid(exp(A_log) * (W_f x + b_f))``
  (``A_log`` one per head, ``W_f`` full rank: ``no_kda_lora``);
  ``beta = sigmoid(W_beta x)`` per head; ``S <- Diag(exp g) S``,
  ``S <- S - beta k (k^T S) + beta k v^T``, ``o = S^T q`` with ``S``
  (d x d) per head from zero; then ``o`` RMS-normalised per head, times
  ``sigmoid(W_gate x)`` (one scalar per head), ``W_o``.
* MLA, expanded: ``q = RMS_head(W_q x)`` (heads x 192: 128 without
  position, 64 rotated); ``[c, k_pe] = W_kva x``, ``c`` RMS-normalised
  (512), ``[k_nope, v] = c W_kvb`` per head; rotary of interleaved pairs
  (theta ``rope_theta``) on ``q``'s last 64 and on ``k_pe``, which every
  head shares; causal softmax of ``q k^T / sqrt(192)``, computed in
  blocks of queries; the same head-wise gate; ``W_o``.
* feed-forward: SwiGLU of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers; after them ``s = sigmoid(x W_r)``
  over ALL routed experts in float32, the experts chosen on ``s + b``
  (``b`` the selection bias) under the group limit written out: experts
  ``[j E/G, (j + 1) E/G)`` form group ``j`` of ``n_group``, a group's
  score is the sum of its two best ``s + b``, the ``topk_group`` best
  groups stay, and the ``num_experts_per_tok`` best experts among them
  are taken; their weights ``s`` over their sum, times
  ``routed_scaling_factor``; plus the shared SwiGLU expert.

Departures from the published description, each under ``assumed`` in
the configuration file: the form of the safe gate above (the config has
``kda_safe_gate`` and ``kda_lower_bound`` only); the state kept in
float32; the per-head output norm (``group_norm_size: 1``) and the
head-wise gate's place (after the norm, before ``W_o``) in both kinds of
attention; ``use_qk_norm`` as an RMS norm of each MLA query head (all
192) before rotation; no multi-token head (``num_nextn_predict_layers``
cut to 0). Weights are random and there is no tokenizer.

**The share** is ``exaone_moe.py``'s: ``model["expert_parallel"] =
(world, rank)``, this chip holds experts ``[rank E/world, (rank + 1)
E/world)``, the router scores and selects over all ``E``, the routed
part is the sum over the HELD experts among a token's selected ones,
the shared expert is computed here; the vocabulary is the slice given.

**The weights** are made from ``--seed`` leaf by leaf in their served
type (bfloat16; router, selection bias, ``A_log`` and ``b_f`` float32)
by ``dense_decoder.py``'s scheme; an expert's leaves are keyed by its
GLOBAL id. ``A_log`` is ``0.3 N``, ``b_f`` is ``-4 + N``: decays between
about 0.6 and 1 per position, so that the state remembers. The selection
bias is balanced per layer by the aux-loss-free rule under the group
limit (:func:`selection_bias`), so every seed offers the same expert
work. Builder and reference call the same functions.

``precision`` selects the control of the comparison that decides
``correct``: ``"int8"`` / ``"fp8"`` quantise every linear layer of the
blocks (the router stays float32) per output channel and per token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.dense_decoder import (
    HI, _gain, _linear, _normal, _rms_norm, seed_key)
from benchmark.reference.exaone_moe import (
    BALANCE_INDEX, BALANCE_SHAPE, BALANCE_STEPS, _swiglu, held_experts)

__all__ = ["seed_key", "model_items", "layer_leaves", "top_leaves",
           "selection_bias", "read_logits"]

_KEYS = ("w_q", "w_k", "w_v", "w_f", "b_f", "a_log", "w_beta", "w_gate",
         "w_o", "w_conv", "o_norm", "w_kva", "w_kvb", "q_norm", "kv_norm",
         "ln_attn", "ln_mlp", "mlp", "w_router", "experts", "shared")

_ITEMS = ("hidden_size", "intermediate_size", "num_hidden_layers",
          "num_attention_heads", "head_dim", "vocab_size", "rope_theta",
          "rms_norm_eps", "num_experts", "num_experts_per_tok",
          "moe_intermediate_size", "num_shared_experts",
          "routed_scaling_factor", "norm_topk_prob", "n_group", "topk_group",
          "first_k_dense_replace", "layer_group_size", "kv_lora_rank",
          "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
          "short_conv_kernel_size", "kda_lower_bound")


def model_items(model: dict) -> tuple:
    """The sizes the reference reads, hashable (a jit's static key)."""
    out = [(k, model[k]) for k in _ITEMS]
    out.append(("expert_parallel", tuple(model.get("expert_parallel",
                                                   (1, 0)))))
    out.append(("balance_shape", tuple(model.get("balance_shape",
                                                 BALANCE_SHAPE))))
    return tuple(out)


def kind_of(model: dict, layer: int) -> str:
    return "mla" if (layer + 1) % model["layer_group_size"] == 0 else "kda"


def is_sparse(model: dict, layer: int) -> bool:
    return layer >= model["first_k_dense_replace"]


# ---------------------------------------------------------------------------
# Weights.
# ---------------------------------------------------------------------------

def _mlp_leaves(key, h: int, inter: int, dtype) -> dict:
    kg, ku, kd = jax.random.split(key, 3)
    return {"w_gate": _normal(kg, (h, inter), h ** -0.5, dtype),
            "w_up": _normal(ku, (h, inter), h ** -0.5, dtype),
            "w_down": _normal(kd, (inter, h), inter ** -0.5, dtype)}


def _kda_leaves(k: dict, model: dict, dtype) -> dict:
    h, nh, d = (model["hidden_size"], model["num_attention_heads"],
                model["head_dim"])
    hd, width = nh * d, model["short_conv_kernel_size"]
    return {
        **{n: _normal(k[n], (h, hd), h ** -0.5, dtype)
           for n in ("w_q", "w_k", "w_v", "w_f")},
        "b_f": -4.0 + jax.random.normal(k["b_f"], (hd,), jnp.float32),
        "a_log": 0.3 * jax.random.normal(k["a_log"], (nh,), jnp.float32),
        "w_beta": _normal(k["w_beta"], (h, nh), h ** -0.5, dtype),
        "w_gate": _normal(k["w_gate"], (h, nh), h ** -0.5, dtype),
        "w_o": _normal(k["w_o"], (hd, h), hd ** -0.5, dtype),
        "w_conv": _normal(k["w_conv"], (width, 3 * hd), width ** -0.5, dtype),
        "o_norm": _gain(k["o_norm"], (d,), dtype)}


def _mla_leaves(k: dict, model: dict, dtype) -> dict:
    h, nh = model["hidden_size"], model["num_attention_heads"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rank, vd = model["kv_lora_rank"], model["v_head_dim"]
    return {
        "w_q": _normal(k["w_q"], (h, nh * (nope + rope)), h ** -0.5, dtype),
        "q_norm": _gain(k["q_norm"], (nope + rope,), dtype),
        "w_kva": _normal(k["w_kva"], (h, rank + rope), h ** -0.5, dtype),
        "kv_norm": _gain(k["kv_norm"], (rank,), dtype),
        "w_kvb": _normal(k["w_kvb"], (rank, nh * (nope + vd)), rank ** -0.5,
                         dtype),
        "w_gate": _normal(k["w_gate"], (h, nh), h ** -0.5, dtype),
        "w_o": _normal(k["w_o"], (nh * vd, h), (nh * vd) ** -0.5, dtype)}


def layer_leaves(key: jax.Array, layer, model: dict, kind: str,
                 sparse: bool, dtype=jnp.bfloat16) -> dict:
    """The leaves of decoder layer ``layer`` (an int or a traced index)
    of kind ``kind`` ("kda" / "mla") for this share, matrices as (in,
    out); experts stacked (held, in, out). Without the selection bias
    (:func:`selection_bias`)."""
    h = model["hidden_size"]
    k = dict(zip(_KEYS, jax.random.split(jax.random.fold_in(key, layer),
                                         len(_KEYS))))
    out = {"attn": (_kda_leaves if kind == "kda" else _mla_leaves)(
               k, model, dtype),
           "ln_attn": _gain(k["ln_attn"], (h,), dtype),
           "ln_mlp": _gain(k["ln_mlp"], (h,), dtype)}
    if not sparse:
        out["mlp"] = _mlp_leaves(k["mlp"], h, model["intermediate_size"],
                                 dtype)
        return out
    inter = model["moe_intermediate_size"]
    lo, n = held_experts(model)
    experts = jax.vmap(lambda e: _mlp_leaves(
        jax.random.fold_in(k["experts"], e), h, inter, dtype))(
            lo + jnp.arange(n))
    out["moe"] = {
        "w_router": _normal(k["w_router"], (h, model["num_experts"]),
                            h ** -0.5, jnp.float32),
        **experts,
        "shared": _mlp_leaves(k["shared"], h,
                              inter * model["num_shared_experts"], dtype)}
    return out


def top_leaves(key: jax.Array, model: dict, dtype=jnp.bfloat16) -> dict:
    """Embedding, final norm gain and output head, (vocab, hidden) each
    over this share's slice of the vocabulary; never tied."""
    h, v = model["hidden_size"], model["vocab_size"]
    ke, kn, kh = jax.random.split(jax.random.fold_in(key, 1_000_003), 3)
    return {"embed": _normal(ke, (v, h), 0.02, dtype),
            "final_norm": _gain(kn, (h,), dtype),
            "lm_head": _normal(kh, (v, h), 0.02, dtype)}


# ---------------------------------------------------------------------------
# The layer.
# ---------------------------------------------------------------------------

def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda(x, w, model: dict, precision: str = "f32"):
    """x: (B, S, H) float32, the normalised residual stream."""
    b, s, _ = x.shape
    nh, d = model["num_attention_heads"], model["head_dim"]
    width = model["short_conv_kernel_size"]
    qkv = jnp.concatenate([_linear(x, w[n], precision)
                           for n in ("w_q", "w_k", "w_v")], axis=-1)
    padded = jnp.pad(qkv, ((0, 0), (width - 1, 0), (0, 0)))
    conv = sum(w["w_conv"][j] * padded[:, j:j + s] for j in range(width))
    act = jax.nn.silu(conv).reshape(b, s, 3, nh, d)
    q = _l2(act[:, :, 0]) * d ** -0.5
    k = _l2(act[:, :, 1])
    v = act[:, :, 2]
    f = (_linear(x, w["w_f"], precision) + w["b_f"]).reshape(b, s, nh, d)
    g = model["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(w["a_log"])[:, None] * f)
    beta = jax.nn.sigmoid(_linear(x, w["w_beta"], precision))

    def one(state, inp):
        qt, kt, vt, gt, bt = inp                  # (B, nh, d) / (B, nh)
        state = jnp.exp(gt)[..., None] * state
        kst = jnp.einsum("bhi,bhij->bhj", kt, state, precision=HI)
        state = state - bt[..., None, None] * kt[..., :, None] * kst[..., None, :]
        state = state + bt[..., None, None] * kt[..., :, None] * vt[..., None, :]
        return state, jnp.einsum("bhi,bhij->bhj", qt, state, precision=HI)

    seq = tuple(jnp.swapaxes(t, 0, 1) for t in (q, k, v, g, beta))
    _, o = lax.scan(one, jnp.zeros((b, nh, d, d), jnp.float32), seq)
    o = _rms_norm(jnp.swapaxes(o, 0, 1), w["o_norm"], model["rms_norm_eps"])
    o = o * jax.nn.sigmoid(_linear(x, w["w_gate"], precision))[..., None]
    return _linear(o.reshape(b, s, nh * d), w["w_o"], precision)


def _rope_pairs(x, positions, theta):
    """Rotary embedding of interleaved pairs. x: (B, S, H, R)."""
    r = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * c - odd * s, even * s + odd * c],
                     axis=-1).reshape(x.shape)


# Query rows of one block of the reference's MLA scores: float32 scores of
# (rows x heads x block x S) stay near 0.5 GB at the cell's sizes.
SCORE_ELEMS = 1 << 27


def mla(x, w, model: dict, precision: str = "f32"):
    """x: (B, S, H) float32, the normalised residual stream."""
    b, s, _ = x.shape
    nh = model["num_attention_heads"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rank, vd = model["kv_lora_rank"], model["v_head_dim"]
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    q = _rms_norm(_linear(x, w["w_q"], precision).reshape(
        b, s, nh, nope + rope), w["q_norm"], eps)
    kva = _linear(x, w["w_kva"], precision)
    c = _rms_norm(kva[..., :rank], w["kv_norm"], eps)
    kv = _linear(c, w["w_kvb"], precision).reshape(b, s, nh, nope + vd)
    pos = jnp.arange(s)
    q = jnp.concatenate([q[..., :nope],
                         _rope_pairs(q[..., nope:], pos, theta)], -1)
    k_pe = _rope_pairs(kva[:, :, None, rank:], pos, theta)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (b, s, nh, rope))], -1)
    v = kv[..., nope:]
    rows = max(1, min(s, SCORE_ELEMS // (b * nh * s)))
    att = []
    for a in range(0, s, rows):
        qb = q[:, a:a + rows]
        scores = jnp.einsum("bshe,bthe->bhst", qb, k, precision=HI) \
            * (nope + rope) ** -0.5
        seen = pos[None, :] <= (a + jnp.arange(qb.shape[1]))[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        att.append(jnp.einsum("bhst,bthv->bshv", probs, v, precision=HI))
    o = jnp.concatenate(att, axis=1)
    o = o * jax.nn.sigmoid(_linear(x, w["w_gate"], precision))[..., None]
    return _linear(o.reshape(b, s, nh * vd), w["w_o"], precision)


def select(choice, model: dict):
    """The selected experts (T, k) of biased scores ``choice`` (T, E)
    under the group limit."""
    t, e = choice.shape
    groups, per = model["n_group"], e // model["n_group"]
    best2, _ = lax.top_k(choice.reshape(t, groups, per), 2)
    _, kept = lax.top_k(jnp.sum(best2, axis=-1), model["topk_group"])
    allowed = jnp.zeros((t, groups), bool).at[
        jnp.arange(t)[:, None], kept].set(True)
    masked = jnp.where(jnp.repeat(allowed, per, axis=1), choice, -jnp.inf)
    return lax.top_k(masked, model["num_experts_per_tok"])[1]


def route(x, w_router, bias, model: dict):
    """(weights (T, E) float32, zero off the selected experts; selected
    ids (T, k)). ``x``: (T, H) float32."""
    s = jax.nn.sigmoid(jnp.dot(x, w_router, precision=HI))
    idx = select(s + bias, model)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if model["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * model["routed_scaling_factor"]
    dense = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=jnp.float32)
                    * w[..., None], axis=-2)
    return dense, idx


def moe(x, w, bias, model: dict, precision: str = "f32"):
    """The sparse feed-forward of this share. x: (T, H) float32."""
    lo, n = held_experts(model)
    dense, _ = route(x, w["w_router"], bias, model)
    mine = dense[:, lo:lo + n]

    def one(acc, ew):
        e, cw = ew
        return acc + cw[:, None] * _swiglu(x, e, precision), None

    experts = {k: w[k] for k in ("w_gate", "w_up", "w_down")}
    routed, _ = lax.scan(one, jnp.zeros_like(x), (experts, mine.T))
    return routed + _swiglu(x, w["shared"], precision)


def _attention(x, w, model, kind, precision):
    h = _rms_norm(x, w["ln_attn"], model["rms_norm_eps"])
    return x + (kda if kind == "kda" else mla)(h, w["attn"], model,
                                               precision)


def layer(x, w, bias, model: dict, kind: str, precision: str = "f32"):
    """One decoder layer on x (B, S, H) float32; ``w`` as
    :func:`layer_leaves` gives it (any float type)."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    x = _attention(x, w, model, kind, precision)
    h = _rms_norm(x, w["ln_mlp"], model["rms_norm_eps"])
    if "moe" in w:
        b, s, hd = x.shape
        return x + moe(h.reshape(b * s, hd), w["moe"], bias, model,
                       precision).reshape(b, s, hd)
    return x + _swiglu(h, w["mlp"], precision)


@functools.partial(jax.jit, static_argnames=("items", "kind", "sparse",
                                             "precision"))
def _layer_of_seed(key, i, x, bias, *, items, kind, sparse, precision):
    model = dict(items)
    return layer(x, layer_leaves(key, i, model, kind, sparse), bias, model,
                 kind, precision)


# ---------------------------------------------------------------------------
# The selection bias: a weight, balanced per layer under the group limit.
# ---------------------------------------------------------------------------

def balance(scores, model: dict, steps: int = BALANCE_STEPS):
    """The bias (E,) under which the group-limited selection on
    ``scores + bias`` (scores (T, E)) loads every expert alike: the
    aux-loss-free rule, ``bias += u * sign(mean load - load)``, with
    ``u`` falling from 0.1 to 1e-4 so that it settles."""
    t, e = scores.shape
    mean = t * model["num_experts_per_tok"] / e

    def body(i, bias):
        idx = select(scores + bias, model)
        load = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0)
        u = 0.1 * (1e-3 ** (i / (steps - 1.0)))
        return bias + u * jnp.sign(mean - load)

    return lax.fori_loop(0, steps, body, jnp.zeros((e,), jnp.float32))


@functools.partial(jax.jit, static_argnames=("items", "kind"))
def _bias_of_layer(key, i, x, *, items, kind):
    """The router of sparse layer ``i`` sees the normalised residual
    stream after the layer's attention: run that half, score, balance."""
    model = dict(items)
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     layer_leaves(key, i, model, kind, True))
    mid = _rms_norm(_attention(x, w, model, kind, "f32"), w["ln_mlp"],
                    model["rms_norm_eps"])
    s = jax.nn.sigmoid(jnp.dot(mid.reshape(-1, mid.shape[-1]),
                               w["moe"]["w_router"], precision=HI))
    return balance(s, model)


@functools.lru_cache(maxsize=4)
def _selection_bias(seed: int, items: tuple):
    model = dict(items)
    key = seed_key(seed)
    ids = jax.random.randint(jax.random.fold_in(key, BALANCE_INDEX),
                             model["balance_shape"], 1, model["vocab_size"])
    x = top_leaves(key, model)["embed"][ids].astype(jnp.float32)
    zero = jnp.zeros((model["num_experts"],), jnp.float32)
    out = []
    for i in range(model["num_hidden_layers"]):
        sparse, kind = is_sparse(model, i), kind_of(model, i)
        bias = (_bias_of_layer(key, jnp.int32(i), x, items=items, kind=kind)
                if sparse else zero)
        out.append(bias)
        x = _layer_of_seed(key, jnp.int32(i), x, bias, items=items,
                           kind=kind, sparse=sparse, precision="f32")
    return jax.block_until_ready(jnp.stack(out))


def selection_bias(model: dict, seed: int):
    """(layers, E) float32, zero rows for dense layers. Computed once per
    (seed, model) in a process: the builder's call is the reference's."""
    return _selection_bias(int(seed) & 0xFFFFFFFF, model_items(model))


# ---------------------------------------------------------------------------
# The forward pass.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("items",))
def _embed(key, ids, *, items):
    return top_leaves(key, dict(items))["embed"][ids].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("items",))
def _head(key, x, read_pos, *, items):
    model = dict(items)
    top = top_leaves(key, model)
    x = _rms_norm(x, top["final_norm"].astype(jnp.float32),
                  model["rms_norm_eps"])
    x = jnp.take_along_axis(x, read_pos[:, :, None], axis=1)   # (B, G, H)
    return jnp.einsum("bgh,vh->bgv", x, top["lm_head"].astype(jnp.float32),
                      precision=HI)


def read_logits(model: dict, seed: int, ids, read_pos,
                precision: str = "f32", bias=None):
    """Logits (B, G, vocab slice) at positions ``read_pos`` (B, G) of the
    sequences ``ids`` (B, S), right-padded: both kinds of attention are
    causal, so a pad suffix is invisible to every position before it.
    ``bias``: the selection bias to use in place of the seed's balanced
    one."""
    items = model_items(model)
    model = dict(items)
    key = seed_key(seed)
    if bias is None:
        bias = selection_bias(model, seed)
    x = _embed(key, jnp.asarray(ids, jnp.int32), items=items)
    for i in range(model["num_hidden_layers"]):
        x = _layer_of_seed(key, jnp.int32(i), x, bias[i], items=items,
                           kind=kind_of(model, i), sparse=is_sparse(model, i),
                           precision=precision)
    return _head(key, x, jnp.asarray(read_pos, jnp.int32), items=items)
