"""Plain reference of a Qwen3 dense decoder, and the weights of a run.

Straightforward ``jax.numpy`` in float32 with every matmul at
``Precision.HIGHEST`` (on a TPU a float32 matmul otherwise runs in
bfloat16 passes): token embedding, then per layer RMSNorm -> q/k/v
projections -> per-head RMSNorm of q and k (Qwen3) -> rotate-half RoPE ->
causal grouped-query attention -> output projection -> residual ->
RMSNorm -> SwiGLU MLP -> residual; final RMSNorm and the output head
(tied to the embedding when the configuration says so). No kernels, no
cache, no batching tricks: a whole forward pass over prompt + served
tokens. It imports nothing of ``triton_dist_tpu`` and takes nothing the
program has made.

The weights are the benchmark's: :func:`layer_leaves` / :func:`top_leaves`
make every leaf from ``--seed`` in the type it is served in (bfloat16).
``benchmark/harness/builders`` places the same leaves into the program's
parameter tree; the reference regenerates them layer by layer inside its
scan, so float32 copies of one layer at a time are all it holds.

Departures from the published model: weights are random (normal,
1/sqrt(fan-in); embedding and head 0.02; norm gains 1 + 0.1 normal so that
a dropped gain shows), and there is no tokenizer: ids are uniform.

``precision`` selects the control of the comparison that decides
``correct``: ``"int8"`` / ``"fp8"`` compute every linear layer of the
blocks with weights quantised per output channel and activations per
token (symmetric, absmax), which is the step below bfloat16 a later PR
would be tempted by. The benchmark's own runs use ``"f32"`` only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST

_LAYER_LEAVES = ("w_q", "w_k", "w_v", "w_o", "w_gate", "w_up", "w_down",
                 "ln_attn", "ln_mlp", "q_norm", "k_norm")


def seed_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(int(seed) & 0xFFFFFFFF)


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _gain(key, shape, dtype):
    return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


def layer_leaves(key: jax.Array, layer, model: dict,
                 dtype=jnp.bfloat16) -> dict:
    """The leaves of decoder layer ``layer`` (an int or a traced index),
    matrices as (in, out)."""
    h, d = model["hidden_size"], model["head_dim"]
    nq = model["num_attention_heads"] * d
    nkv = model["num_key_value_heads"] * d
    inter = model["intermediate_size"]
    ks = jax.random.split(jax.random.fold_in(key, layer), len(_LAYER_LEAVES))
    k = dict(zip(_LAYER_LEAVES, ks))
    return {
        "w_q": _normal(k["w_q"], (h, nq), h ** -0.5, dtype),
        "w_k": _normal(k["w_k"], (h, nkv), h ** -0.5, dtype),
        "w_v": _normal(k["w_v"], (h, nkv), h ** -0.5, dtype),
        "w_o": _normal(k["w_o"], (nq, h), nq ** -0.5, dtype),
        "w_gate": _normal(k["w_gate"], (h, inter), h ** -0.5, dtype),
        "w_up": _normal(k["w_up"], (h, inter), h ** -0.5, dtype),
        "w_down": _normal(k["w_down"], (inter, h), inter ** -0.5, dtype),
        "ln_attn": _gain(k["ln_attn"], (h,), dtype),
        "ln_mlp": _gain(k["ln_mlp"], (h,), dtype),
        "q_norm": _gain(k["q_norm"], (d,), dtype),
        "k_norm": _gain(k["k_norm"], (d,), dtype),
    }


def top_leaves(key: jax.Array, model: dict, dtype=jnp.bfloat16) -> dict:
    """Embedding, final norm gain and output head (vocab, hidden)."""
    h, v = model["hidden_size"], model["vocab_size"]
    ke, kn, kh = jax.random.split(jax.random.fold_in(key, 1_000_003), 3)
    embed = _normal(ke, (v, h), 0.02, dtype)
    head = embed if model.get("tie_word_embeddings") else \
        _normal(kh, (v, h), 0.02, dtype)
    return {"embed": embed, "final_norm": _gain(kn, (h,), dtype),
            "lm_head": head}


# ---------------------------------------------------------------------------
# The forward pass.
# ---------------------------------------------------------------------------

def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w


def _quant(x, axis, precision):
    """Symmetric absmax fake-quantisation along ``axis``."""
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30)
    if precision == "int8":
        scale = amax / 127.0
        return jnp.round(x / scale) * scale
    if precision == "fp8":
        scale = amax / 448.0
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    raise ValueError(f"unknown control precision {precision!r}")


def _linear(x, w, precision):
    """x (..., in) @ w (in, out)."""
    if precision != "f32":
        x = _quant(x, -1, precision)       # per token
        w = _quant(w, 0, precision)        # per output channel
    return jnp.dot(x, w, precision=HI)


def _rope(x, positions, theta):
    """Rotate-half RoPE. x: (B, S, H, D); positions: (S,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _layer(x, w, model, precision):
    b, s, _ = x.shape
    nh, nkv, d = (model["num_attention_heads"], model["num_key_value_heads"],
                  model["head_dim"])
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    hdn = _rms_norm(x, w["ln_attn"], eps)
    q = _linear(hdn, w["w_q"], precision).reshape(b, s, nh, d)
    k = _linear(hdn, w["w_k"], precision).reshape(b, s, nkv, d)
    v = _linear(hdn, w["w_v"], precision).reshape(b, s, nkv, d)
    q = _rms_norm(q, w["q_norm"], eps)
    k = _rms_norm(k, w["k_norm"], eps)
    pos = jnp.arange(s)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    g = nh // nkv
    qg = q.reshape(b, s, nkv, g, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k, precision=HI) * d ** -0.5
    causal = pos[None, :] <= pos[:, None]                 # (S, T)
    scores = jnp.where(causal[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("bkgst,btkd->bskgd", probs, v, precision=HI)
    x = x + _linear(att.reshape(b, s, nh * d), w["w_o"], precision)
    hdn = _rms_norm(x, w["ln_mlp"], eps)
    act = jax.nn.silu(_linear(hdn, w["w_gate"], precision)) \
        * _linear(hdn, w["w_up"], precision)
    return x + _linear(act, w["w_down"], precision)


@functools.partial(jax.jit, static_argnames=("model_items", "precision"))
def _read_logits(key, ids, read_pos, *, model_items, precision):
    model = dict(model_items)
    top = top_leaves(key, model)
    x = top["embed"][ids].astype(jnp.float32)             # (B, S, H)

    def body(x, i):
        return _layer(x, layer_leaves(key, i, model), model, precision), None

    x, _ = lax.scan(body, x, jnp.arange(model["num_hidden_layers"]))
    x = _rms_norm(x, top["final_norm"].astype(jnp.float32),
                  model["rms_norm_eps"])
    x = jnp.take_along_axis(x, read_pos[:, :, None], axis=1)   # (B, G, H)
    return jnp.einsum("bgh,vh->bgv", x, top["lm_head"].astype(jnp.float32),
                      precision=HI)


def model_items(model: dict) -> tuple:
    keep = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "rope_theta", "rms_norm_eps",
            "tie_word_embeddings")
    return tuple((k, model[k]) for k in keep)


def read_logits(model: dict, seed: int, ids, read_pos,
                precision: str = "f32"):
    """Logits (B, G, vocab) at positions ``read_pos`` (B, G) of the
    sequences ``ids`` (B, S), right-padded: causal attention keeps a pad
    suffix invisible to every position before it."""
    return _read_logits(seed_key(seed), jnp.asarray(ids, jnp.int32),
                        jnp.asarray(read_pos, jnp.int32),
                        model_items=model_items(model), precision=precision)
