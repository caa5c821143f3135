"""Operations and bytes of one chip's share of a K-EXAONE decoder
(``benchmark/reference/exaone_moe.py``): what ``exaone_step.mfu`` and
``exaone_step.hbm_roofline`` divide by the chip's peaks.

Operations: two per multiply-add of every matmul parameter a token
passes through HERE (attention projections, the dense layer's MLP, per
sparse layer the router, the shared expert and the held experts among
the token's selected ones, counted at the balanced router's expectation
``num_experts_per_tok x held / routed``; the head slice; the embedding
is a lookup), plus attention over the token's true context: QK^T and PV,
2 x 2 x heads x head_dim per position attended, a sliding layer's
context cut at its window. Padding, recomputation and frozen rows do not
count: this is the work the share needs, not what the program did.

Bytes of a decode step (:func:`step_bytes`): what the step cannot avoid
reading from HBM however it is scheduled: every non-expert weight once
(attention, norms, dense MLP, routers, shared experts, final norm, the
head slice), each held expert that got a token once, and the K and V of
every position a live row attends to (a sliding layer: at most its
window). Activations, the rows' new K/V and the output are left out
(under 1 % at these sizes), so the share can only read low."""

from __future__ import annotations

BF16 = 2
F32 = 4


def kinds(model: dict) -> list[tuple[int | None, bool]]:
    """Per layer: (window or None, sparse)."""
    n = model["num_hidden_layers"]
    return [(model["sliding_window"] if k == "sliding_attention" else None,
             m == "sparse")
            for k, m in zip(model["layer_types"][:n],
                            model["mlp_layer_types"][:n])]


def attn_params(model: dict) -> int:
    h, d = model["hidden_size"], model["head_dim"]
    nq = model["num_attention_heads"] * d
    nkv = model["num_key_value_heads"] * d
    return h * (nq + 2 * nkv) + nq * h


def expert_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def held_pairs_expected(model: dict) -> float:
    world, _ = model["expert_parallel"]
    return model["num_experts_per_tok"] / world


def token_params(model: dict) -> float:
    """Matmul parameters one token passes through in the layers."""
    h = model["hidden_size"]
    total = 0.0
    for _, sparse in kinds(model):
        total += attn_params(model)
        if sparse:
            total += h * model["num_experts"]                      # router
            total += expert_params(model) * (
                model["num_shared_experts"] + held_pairs_expected(model))
        else:
            total += 3 * h * model["intermediate_size"]
    return total


def head_params(model: dict) -> int:
    return model["hidden_size"] * model["vocab_size"]


def _attended(context: int, window) -> int:
    return context if window is None else min(context, window)


def decode_token_flops(model: dict, context: int) -> float:
    """One generated token attending over ``context`` positions."""
    per_pos = 4.0 * model["num_attention_heads"] * model["head_dim"]
    attn = sum(per_pos * _attended(context, w) for w, _ in kinds(model))
    return 2.0 * (token_params(model) + head_params(model)) + attn


def prefill_flops(model: dict, prompt: int) -> float:
    """A prompt of ``prompt`` tokens, causal: position i attends over
    i + 1 positions (a sliding layer: at most its window); only the last
    position needs the output head."""
    per_pos = 4.0 * model["num_attention_heads"] * model["head_dim"]
    attn = 0.0
    for w, _ in kinds(model):
        if w is None or prompt <= w:
            attn += per_pos * prompt * (prompt + 1) / 2.0
        else:
            attn += per_pos * (w * (w + 1) / 2.0 + (prompt - w) * w)
    return 2.0 * token_params(model) * prompt + 2.0 * head_params(model) \
        + attn


def fixed_weight_bytes(model: dict) -> float:
    """The weights every decode step reads whatever it routes."""
    h, d = model["hidden_size"], model["head_dim"]
    total = (head_params(model) + h) * BF16              # head, final norm
    for _, sparse in kinds(model):
        total += (attn_params(model) + 2 * d + 2 * h) * BF16
        if sparse:
            total += (h + 1) * model["num_experts"] * F32  # router and bias
            total += (expert_params(model) * model["num_shared_experts"]
                      * BF16)
        else:
            total += 3 * h * model["intermediate_size"] * BF16
    return total


def step_bytes(model: dict, experts_touched: float, window_positions: float,
               full_positions: float) -> float:
    """One decode step: ``experts_touched`` held experts got a token
    (summed over the sparse layers); the live rows attend to
    ``window_positions`` / ``full_positions`` cache positions (summed
    over the layers of each kind)."""
    kv = 2 * model["num_key_value_heads"] * model["head_dim"] * BF16
    return (fixed_weight_bytes(model)
            + experts_touched * expert_params(model) * BF16
            + (window_positions + full_positions) * kv)
