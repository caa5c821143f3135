"""Plain reference of one chip's share of a K-EXAONE (``exaone_moe``)
decoder, and the weights of a run.

Straightforward ``jax.numpy`` in float32 with every matmul at
``Precision.HIGHEST``; no kernels, no cache, no batching tricks: a whole
forward pass over prompt + served tokens, one layer at a time so that
float32 copies of one layer are all it holds. It imports nothing of
``triton_dist_tpu`` and takes nothing the program has made.

The layer, with ``x`` the residual stream (48 layers published):

* attention, every layer: ``q = RMS_head(x Wq)``, ``k = RMS_head(x Wk)``,
  ``v = x Wv``, grouped-query softmax of ``q k^T / sqrt(d)``, ``Wo``.
  ``layer_types[i] == "sliding_attention"``: position ``i`` sees ``j``
  with ``i - window < j <= i``; ``"full_attention"``: every ``j <= i``.
* feed-forward: ``mlp_layer_types[i] == "dense"``: SwiGLU of width
  ``intermediate_size``. ``"sparse"``: ``s = sigmoid(x W_r)`` over ALL
  routed experts in float32; the ``num_experts_per_tok`` experts are the
  top of ``s + b`` (``b`` the selection bias); their weights are ``s``
  (without ``b``) over their sum, times ``routed_scaling_factor``; the
  output is the weighted sum of those SwiGLU experts plus the shared
  SwiGLU expert applied to every token.
* taken from the family's published EXAONE-4.0 code (the config has no
  key for either; the configuration file lists both under ``assumed``):
  each sublayer reads the residual stream itself and its OUTPUT is
  RMS-normalised before it is added (``x += RMS(attn(x))``,
  ``x += RMS(ffn(x))``); rotate-half rotary embedding (whole head) in the
  sliding layers only, full layers carry none.

**The share.** ``model["expert_parallel"] = (world, rank)``: this chip
holds experts ``[rank * E/world, (rank + 1) * E/world)`` of the ``E``
routed over. The router scores and selects over all ``E``; the routed
part of the output is the sum over the HELD experts among a token's
selected ones (what the absent experts would have added is left out, and
that partial result goes on to the next layer); the shared expert is
computed here for every token. The vocabulary is the slice the
configuration gives: a smaller vocabulary.

**The weights** are the benchmark's, made from ``--seed`` leaf by leaf in
the type they are served in (bfloat16; router and bias float32) by the
scheme of ``dense_decoder.py``: normal, 1/sqrt(fan-in); embedding and
head 0.02; norm gains 1 + 0.1 normal. An expert's leaves are keyed by its
GLOBAL id, so every share of one seed is a cut of one model. The
selection bias ``b`` is a weight too (:func:`selection_bias`): set by the
published aux-loss-free rule (raise ``b`` of an under-loaded expert,
lower it of an over-loaded one) over a fixed batch of ids drawn from the
seed, layer after layer on this reference's own activations, until each
layer's load over the batch is even. So every seed offers the same expert
work, as trained routers do; random routers do not (which experts they
favour decides how many pairs a share holds). Builder and reference call
the same function.

``precision`` selects the control of the comparison that decides
``correct``: ``"int8"`` / ``"fp8"`` quantise every linear layer of the
blocks (experts and the shared expert among them; the router stays
float32), per output channel and per token. The benchmark's own runs use
``"f32"`` only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.dense_decoder import (
    HI, _gain, _linear, _normal, _rms_norm, _rope, seed_key)

# The batch the selection bias is balanced on: sequences x positions of
# uniform ids. 16384 tokens put 1024 pairs on each of 128 experts, so the
# batch's own sampling noise in one expert's load is ~3 %. A model dict
# may give another ``balance_shape`` (the CPU tests and rehearsals do).
BALANCE_SHAPE = (32, 512)
BALANCE_STEPS = 300
BALANCE_INDEX = 1_000_033      # fold_in word of the balance batch's ids

_ATTN = ("w_q", "w_k", "w_v", "w_o", "q_norm", "k_norm")
_KEYS = _ATTN + ("ln_attn", "ln_mlp", "w_gate", "w_up", "w_down",
                 "w_router", "experts", "shared")


def model_items(model: dict) -> tuple:
    """The sizes the reference reads, hashable (a jit's static key)."""
    n = int(model["num_hidden_layers"])
    keep = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "rope_theta", "rms_norm_eps", "sliding_window",
            "num_experts", "num_experts_per_tok", "moe_intermediate_size",
            "num_shared_experts", "routed_scaling_factor", "norm_topk_prob")
    out = [(k, model[k]) for k in keep]
    out.append(("layer_types", tuple(model["layer_types"][:n])))
    out.append(("mlp_layer_types", tuple(model["mlp_layer_types"][:n])))
    out.append(("expert_parallel", tuple(model.get("expert_parallel",
                                                   (1, 0)))))
    out.append(("balance_shape", tuple(model.get("balance_shape",
                                                 BALANCE_SHAPE))))
    return tuple(out)


def held_experts(model: dict) -> tuple[int, int]:
    """(first held expert, how many are held)."""
    world, rank = model.get("expert_parallel", (1, 0))
    n = model["num_experts"] // world
    return rank * n, n


def is_sparse(model: dict, layer: int) -> bool:
    return model["mlp_layer_types"][layer] == "sparse"


def window_of(model: dict, layer: int):
    return (int(model["sliding_window"])
            if model["layer_types"][layer] == "sliding_attention" else None)


# ---------------------------------------------------------------------------
# Weights.
# ---------------------------------------------------------------------------

def _mlp_leaves(key, h: int, inter: int, dtype) -> dict:
    kg, ku, kd = jax.random.split(key, 3)
    return {"w_gate": _normal(kg, (h, inter), h ** -0.5, dtype),
            "w_up": _normal(ku, (h, inter), h ** -0.5, dtype),
            "w_down": _normal(kd, (inter, h), inter ** -0.5, dtype)}


def layer_leaves(key: jax.Array, layer, model: dict, sparse: bool,
                 dtype=jnp.bfloat16) -> dict:
    """The leaves of decoder layer ``layer`` (an int or a traced index)
    for this share, matrices as (in, out); experts stacked (held, in,
    out). Without the selection bias (:func:`selection_bias`)."""
    h, d = model["hidden_size"], model["head_dim"]
    nq = model["num_attention_heads"] * d
    nkv = model["num_key_value_heads"] * d
    k = dict(zip(_KEYS, jax.random.split(jax.random.fold_in(key, layer),
                                         len(_KEYS))))
    out = {
        "attn": {"w_q": _normal(k["w_q"], (h, nq), h ** -0.5, dtype),
                 "w_k": _normal(k["w_k"], (h, nkv), h ** -0.5, dtype),
                 "w_v": _normal(k["w_v"], (h, nkv), h ** -0.5, dtype),
                 "w_o": _normal(k["w_o"], (nq, h), nq ** -0.5, dtype),
                 "q_norm": _gain(k["q_norm"], (d,), dtype),
                 "k_norm": _gain(k["k_norm"], (d,), dtype)},
        "ln_attn": _gain(k["ln_attn"], (h,), dtype),
        "ln_mlp": _gain(k["ln_mlp"], (h,), dtype),
    }
    if not sparse:
        out["mlp"] = _mlp_leaves(k["w_gate"], h, model["intermediate_size"],
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

def route(x, w_router, bias, model: dict):
    """(weights (T, E) float32, zero off the selected experts; selected
    ids (T, k)). ``x``: (T, H) float32."""
    k = model["num_experts_per_tok"]
    s = jax.nn.sigmoid(jnp.dot(x, w_router, precision=HI))
    _, idx = lax.top_k(s + bias, k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if model["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * model["routed_scaling_factor"]
    dense = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=jnp.float32)
                    * w[..., None], axis=-2)
    return dense, idx


def _swiglu(x, w, precision):
    act = jax.nn.silu(_linear(x, w["w_gate"], precision)) \
        * _linear(x, w["w_up"], precision)
    return _linear(act, w["w_down"], precision)


def moe(x, w, bias, model: dict, precision: str = "f32"):
    """The sparse feed-forward of this share. x: (T, H) float32."""
    lo, n = held_experts(model)
    dense, _ = route(x, w["w_router"], bias, model)
    mine = dense[:, lo:lo + n]                                # (T, held)

    def one(acc, ew):
        e, cw = ew
        return acc + cw[:, None] * _swiglu(x, e, precision), None

    experts = {k: w[k] for k in ("w_gate", "w_up", "w_down")}
    routed, _ = lax.scan(one, jnp.zeros_like(x), (experts, mine.T))
    return routed + _swiglu(x, w["shared"], precision)


def attention(x, w, model: dict, window, precision: str = "f32"):
    """x: (B, S, H) float32. Rotary in the windowed layers only."""
    b, s, _ = x.shape
    nh, nkv, d = (model["num_attention_heads"], model["num_key_value_heads"],
                  model["head_dim"])
    eps = model["rms_norm_eps"]
    q = _linear(x, w["w_q"], precision).reshape(b, s, nh, d)
    k = _linear(x, w["w_k"], precision).reshape(b, s, nkv, d)
    v = _linear(x, w["w_v"], precision).reshape(b, s, nkv, d)
    q = _rms_norm(q, w["q_norm"], eps)
    k = _rms_norm(k, w["k_norm"], eps)
    pos = jnp.arange(s)
    if window is not None:
        q = _rope(q, pos, model["rope_theta"])
        k = _rope(k, pos, model["rope_theta"])
    qg = q.reshape(b, s, nkv, nh // nkv, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k, precision=HI) * d ** -0.5
    seen = pos[None, :] <= pos[:, None]                       # (S, T)
    if window is not None:
        seen &= pos[None, :] > pos[:, None] - window
    scores = jnp.where(seen[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("bkgst,btkd->bskgd", probs, v, precision=HI)
    return _linear(att.reshape(b, s, nh * d), w["w_o"], precision)


def layer(x, w, bias, model: dict, window, precision: str = "f32"):
    """One decoder layer on x (B, S, H) float32; ``w`` as
    :func:`layer_leaves` gives it (any float type)."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    eps = model["rms_norm_eps"]
    x = x + _rms_norm(attention(x, w["attn"], model, window, precision),
                      w["ln_attn"], eps)
    if "moe" in w:
        b, s, h = x.shape
        f = moe(x.reshape(b * s, h), w["moe"], bias, model,
                precision).reshape(b, s, h)
    else:
        f = _swiglu(x, w["mlp"], precision)
    return x + _rms_norm(f, w["ln_mlp"], eps)


@functools.partial(jax.jit, static_argnames=("items", "sparse", "window",
                                             "precision"))
def _layer_of_seed(key, i, x, bias, *, items, sparse, window, precision):
    model = dict(items)
    return layer(x, layer_leaves(key, i, model, sparse), bias, model, window,
                 precision)


# ---------------------------------------------------------------------------
# The selection bias: a weight, balanced per layer.
# ---------------------------------------------------------------------------

def balance(scores, k: int, steps: int = BALANCE_STEPS):
    """The bias (E,) under which the top-``k`` of ``scores + bias``
    (scores (T, E)) load every expert alike: the aux-loss-free rule,
    ``bias += u * sign(mean load - load)``, with ``u`` falling from 0.1
    to 1e-4 so that it settles."""
    t, e = scores.shape
    mean = t * k / e

    def body(i, bias):
        _, idx = lax.top_k(scores + bias, k)
        load = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0)
        u = 0.1 * (1e-3 ** (i / (steps - 1.0)))
        return bias + u * jnp.sign(mean - load)

    return lax.fori_loop(0, steps, body, jnp.zeros((e,), jnp.float32))


def balance_ids(key, model: dict):
    return jax.random.randint(jax.random.fold_in(key, BALANCE_INDEX),
                              model["balance_shape"], 1, model["vocab_size"])


@functools.partial(jax.jit, static_argnames=("items", "window"))
def _bias_of_layer(key, i, x, *, items, window):
    """The router of sparse layer ``i`` sees the residual stream after the
    layer's attention: run that half, score, balance."""
    model = dict(items)
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     layer_leaves(key, i, model, True))
    eps = model["rms_norm_eps"]
    mid = x + _rms_norm(attention(x, w["attn"], model, window), w["ln_attn"],
                        eps)
    s = jax.nn.sigmoid(jnp.dot(mid.reshape(-1, mid.shape[-1]),
                               w["moe"]["w_router"], precision=HI))
    return balance(s, model["num_experts_per_tok"])


@functools.lru_cache(maxsize=4)
def _selection_bias(seed: int, items: tuple):
    model = dict(items)
    key = seed_key(seed)
    x = top_leaves(key, model)["embed"][balance_ids(key, model)].astype(
        jnp.float32)
    zero = jnp.zeros((model["num_experts"],), jnp.float32)
    out = []
    for i in range(model["num_hidden_layers"]):
        sparse, window = is_sparse(model, i), window_of(model, i)
        bias = (_bias_of_layer(key, jnp.int32(i), x, items=items,
                               window=window) if sparse else zero)
        out.append(bias)
        x = _layer_of_seed(key, jnp.int32(i), x, bias, items=items,
                           sparse=sparse, window=window, precision="f32")
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
    sequences ``ids`` (B, S), right-padded: causal attention keeps a pad
    suffix invisible to every position before it. ``bias``: the selection
    bias to use in place of the seed's balanced one (tests plant faults
    through it)."""
    items = model_items(model)
    model = dict(items)
    key = seed_key(seed)
    if bias is None:
        bias = selection_bias(model, seed)
    x = _embed(key, jnp.asarray(ids, jnp.int32), items=items)
    for i in range(model["num_hidden_layers"]):
        x = _layer_of_seed(key, jnp.int32(i), x, bias[i], items=items,
                           sparse=is_sparse(model, i),
                           window=window_of(model, i), precision=precision)
    return _head(key, x, jnp.asarray(read_pos, jnp.int32), items=items)
