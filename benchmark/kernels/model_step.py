"""Operations a dense decoder needs for one token: what ``model_step.mfu``
divides by the chips' peak. Two per multiply-add of every matmul
parameter the token passes through (attention projections, MLP, output
head; the embedding is a lookup), plus attention over the token's true
context: QK^T and PV, 2 x 2 x heads x head_dim per position attended.
Recomputation, padding to an admission bucket and frozen batch rows do
not count: this is the work the model needs, not what the program did."""

from __future__ import annotations


def matmul_params(model: dict) -> int:
    h, d = model["hidden_size"], model["head_dim"]
    nq = model["num_attention_heads"] * d
    nkv = model["num_key_value_heads"] * d
    attn = h * (nq + 2 * nkv) + nq * h
    mlp = 3 * h * model["intermediate_size"]
    return (attn + mlp) * model["num_hidden_layers"]


def head_params(model: dict) -> int:
    return model["hidden_size"] * model["vocab_size"]


def decode_token_flops(model: dict, context: int) -> float:
    """One generated token attending over ``context`` positions."""
    attn = (4.0 * model["num_attention_heads"] * model["head_dim"] * context
            * model["num_hidden_layers"])
    return 2.0 * (matmul_params(model) + head_params(model)) + attn


def prefill_flops(model: dict, prompt: int) -> float:
    """A prompt of ``prompt`` tokens, causal: position i attends over
    i + 1 positions; only the last position needs the output head."""
    attn = (4.0 * model["num_attention_heads"] * model["head_dim"]
            * (prompt * (prompt + 1) / 2.0) * model["num_hidden_layers"])
    return 2.0 * matmul_params(model) * prompt + 2.0 * head_params(model) \
        + attn
