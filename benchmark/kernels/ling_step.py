"""Operations and bytes of one chip's share of a Ling hybrid decoder
(``benchmark/reference/ling_hybrid.py``): what ``ling_step.mfu``,
``ling_step.hbm_roofline`` and ``kda.state_share`` divide.

Operations: two per multiply-add of every matmul parameter a token
passes through HERE (KDA and MLA projections, the dense layers' MLP, per
sparse layer the router, the shared expert and the held experts among
the token's selected ones at the balanced router's expectation
``num_experts_per_tok x held / routed``; the head slice; the embedding
is a lookup), plus the mixing of each kind of attention in the form the
program runs it:

* KDA, a prompt position (the chunked scan, chunks of ``C`` = 64): per
  head ``4 C d + 4 d^2`` multiply-adds (the chunk's pairwise products
  of keys and of queries with keys, its triangular solve, the state's
  pass into and out of the chunk, the outputs); a generated token (the
  one-position update): ``3 d^2`` (``k^T S``, ``q^T S``, ``k delta^T``);
* MLA, a prompt position: ``(nope + rope) + v`` per head per position
  attended (the expanded form), a generated token ``2 rank + rope`` per
  head per position (the absorbed form), causal.

Padding, recomputation and frozen rows do not count: this is the work
the share needs, not what the program did.

Bytes of a decode step (:func:`step_bytes`): what the step cannot avoid
moving to or from HBM however it is scheduled: every non-expert weight
once (attention, norms, dense MLPs, routers and selection biases, shared
experts, final norm, the head slice), each held expert that got a token
once, each live row's KDA state read and written (float32, heads x d x
d a layer) with its conv tail, and the latent of every position a live
row's MLA attends to. Activations and the new latents are left out
(under 1 % at these sizes), so the share can only read low."""

from __future__ import annotations

BF16 = 2
F32 = 4
CHUNK = 64


def kinds(model: dict) -> list[tuple[str, bool]]:
    """Per layer: ("kda" | "mla", sparse)."""
    g, dense = model["layer_group_size"], model["first_k_dense_replace"]
    return [("mla" if (i + 1) % g == 0 else "kda", i >= dense)
            for i in range(model["num_hidden_layers"])]


def kda_params(model: dict) -> int:
    h, n, d = (model["hidden_size"], model["num_attention_heads"],
               model["head_dim"])
    return 5 * h * n * d + 2 * h * n          # q k v f o; beta, gate


def mla_params(model: dict) -> int:
    h, n = model["hidden_size"], model["num_attention_heads"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rank, vd = model["kv_lora_rank"], model["v_head_dim"]
    return (h * n * (nope + rope) + h * (rank + rope)
            + rank * n * (nope + vd) + h * n + n * vd * h)


def expert_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def held_pairs_expected(model: dict) -> float:
    world, _ = model["expert_parallel"]
    return model["num_experts_per_tok"] / world


def token_params(model: dict) -> float:
    """Matmul parameters one token passes through in the layers."""
    h = model["hidden_size"]
    total = 0.0
    for kind, sparse in kinds(model):
        total += kda_params(model) if kind == "kda" else mla_params(model)
        if sparse:
            total += h * model["num_experts"]                      # router
            total += expert_params(model) * (
                model["num_shared_experts"] + held_pairs_expected(model))
        else:
            total += 3 * h * model["intermediate_size"]
    return total


def head_params(model: dict) -> int:
    return model["hidden_size"] * model["vocab_size"]


def _layers(model: dict, kind: str) -> int:
    return sum(1 for k, _ in kinds(model) if k == kind)


def _conv_macs(model: dict) -> int:
    n, d = model["num_attention_heads"], model["head_dim"]
    return 3 * n * d * model["short_conv_kernel_size"]


def decode_token_flops(model: dict, context: int) -> float:
    """One generated token whose MLA attends over ``context`` positions."""
    n, d = model["num_attention_heads"], model["head_dim"]
    rank, rope = model["kv_lora_rank"], model["qk_rope_head_dim"]
    kda = _layers(model, "kda") * (n * 3 * d * d + _conv_macs(model))
    mla = _layers(model, "mla") * n * (2 * rank + rope) * context
    return 2.0 * (token_params(model) + head_params(model) + kda + mla)


def prefill_flops(model: dict, prompt: int) -> float:
    """A prompt of ``prompt`` tokens: causal MLA (position i attends
    over i + 1), the chunked KDA scan; only the last position needs the
    output head."""
    n, d = model["num_attention_heads"], model["head_dim"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    kda = _layers(model, "kda") * prompt * (
        n * (4 * CHUNK * d + 4 * d * d) + _conv_macs(model))
    mla = _layers(model, "mla") * n * (nope + rope + model["v_head_dim"]) \
        * prompt * (prompt + 1) / 2.0
    return 2.0 * (token_params(model) * prompt + head_params(model)
                  + kda + mla)


def fixed_weight_bytes(model: dict) -> float:
    """The weights every decode step reads whatever it routes."""
    h, n, d = (model["hidden_size"], model["num_attention_heads"],
               model["head_dim"])
    total = (head_params(model) + h) * BF16              # head, final norm
    for kind, sparse in kinds(model):
        total += 2 * h * BF16                            # the two norms
        if kind == "kda":
            total += (kda_params(model) + _conv_macs(model) + d) * BF16
            total += (n + n * d) * F32                   # A_log, b_f
        else:
            total += (mla_params(model) + model["qk_nope_head_dim"]
                      + model["qk_rope_head_dim"]
                      + model["kv_lora_rank"]) * BF16
        if sparse:
            total += (h + 1) * model["num_experts"] * F32  # router and bias
            total += (expert_params(model) * model["num_shared_experts"]
                      * BF16)
        else:
            total += 3 * h * model["intermediate_size"] * BF16
    return total


def state_bytes(model: dict, rows_stepped: float) -> float:
    """The KDA state and conv tails of ``rows_stepped`` (live rows x KDA
    layers), each read once and written once."""
    n, d = model["num_attention_heads"], model["head_dim"]
    tail = (model["short_conv_kernel_size"] - 1) * 3 * n * d * BF16
    return rows_stepped * 2.0 * (n * d * d * F32 + tail)


def step_bytes(model: dict, experts_touched: float, rows_stepped: float,
               latent_positions: float) -> float:
    """One decode step: ``experts_touched`` held experts got a token
    (summed over the sparse layers), ``rows_stepped`` live rows x KDA
    layers, the live rows' MLA reads ``latent_positions`` positions
    (summed over the MLA layers)."""
    latent = (model["kv_lora_rank"] + model["qk_rope_head_dim"]) * BF16
    return (fixed_weight_bytes(model)
            + experts_touched * expert_params(model) * BF16
            + state_bytes(model, rows_stepped)
            + latent_positions * latent)
