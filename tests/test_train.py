"""Training-step contract tests (beyond-reference: the reference has no
training path at all — SURVEY §2.9 "DP: not a subsystem").

What must hold for the training step to be trusted:
  * loss falls over a few steps of overfitting one tiny batch (the
    gradients point somewhere useful);
  * remat=True is numerically identical to remat=False (checkpointing
    must not change the math, only the memory schedule);
  * masked positions contribute nothing (prompt-prefix masking);
  * the step composes over a dp×tp grid with the batch sharded over dp
    (XLA inserts the gradient all-reduce from shardings alone).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_dist_tpu.models import DenseLLM, ModelConfig, make_train_step
from triton_dist_tpu.models.train import cross_entropy_loss

#: Heavy interpret-mode numerics -> full tier only (quick tier: pytest -m 'not slow').
pytestmark = pytest.mark.slow


def _tiny_cfg(world: int, dtype=jnp.float32, layers: int = 2):
    return ModelConfig(
        hidden_size=16 * world, intermediate_size=32 * world,
        num_hidden_layers=layers, num_attention_heads=world,
        num_key_value_heads=world, head_dim=16, vocab_size=64,
        max_position_embeddings=64, dtype=dtype)


def _batch(b, s, vocab, seed=0):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (b, s), 0, vocab,
                             jnp.int32)
    return {"input_ids": ids}


def test_cross_entropy_masking():
    logits = jnp.zeros((1, 4, 8))
    labels = jnp.zeros((1, 4), jnp.int32)
    full = cross_entropy_loss(logits, labels)
    # Uniform logits: NLL = log V on every row, so any mask gives log V.
    half = cross_entropy_loss(logits, labels,
                              jnp.array([[1.0, 1.0, 0.0, 0.0]]))
    np.testing.assert_allclose(full, np.log(8.0), rtol=1e-6)
    np.testing.assert_allclose(half, np.log(8.0), rtol=1e-6)
    # A masked row with a huge wrong logit must not leak into the loss.
    bad = logits.at[0, 3, 1].set(100.0)
    np.testing.assert_allclose(
        cross_entropy_loss(bad, labels, jnp.array([[1.0, 1.0, 1.0, 0.0]])),
        np.log(8.0), rtol=1e-6)


def test_loss_decreases_tp(mesh8):
    model = DenseLLM(_tiny_cfg(8), mesh=mesh8, axis="tp", impl="xla",
                     fwd_mode="xla")
    params = model.init(jax.random.PRNGKey(0))
    step, init_opt = make_train_step(model)
    opt_state = init_opt(params)
    batch = _batch(2, 8, model.config.vocab_size)

    losses = []
    for _ in range(5):
        params, opt_state, m = step(params, opt_state, batch)
        losses.append(float(m["loss"]))
        assert np.isfinite(losses[-1])
        assert np.isfinite(float(m["grad_norm"])) and float(m["grad_norm"]) > 0
    # Overfitting one tiny batch: the last loss must beat the first.
    assert losses[-1] < losses[0], losses


def test_remat_matches_no_remat(mesh8):
    """Checkpointing changes the schedule, not the math."""
    model = DenseLLM(_tiny_cfg(8), mesh=mesh8, axis="tp", impl="xla",
                     fwd_mode="xla")
    params = model.init(jax.random.PRNGKey(1))
    batch = _batch(2, 8, model.config.vocab_size, seed=1)

    results = {}
    for remat in (False, True):
        step, init_opt = make_train_step(model, remat=remat, donate=False)
        p2, _, m = step(params, init_opt(params), batch)
        results[remat] = (m["loss"], jax.tree.map(np.asarray, p2))
    np.testing.assert_allclose(results[False][0], results[True][0],
                               rtol=1e-6)
    flat_a = jax.tree.leaves(results[False][1])
    flat_b = jax.tree.leaves(results[True][1])
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


def test_loss_mask_freezes_masked_positions(mesh8):
    """With every position masked the gradients are exactly zero."""
    model = DenseLLM(_tiny_cfg(8), mesh=mesh8, axis="tp", impl="xla",
                     fwd_mode="xla")
    params = model.init(jax.random.PRNGKey(2))
    batch = _batch(2, 8, model.config.vocab_size, seed=2)
    batch["loss_mask"] = jnp.zeros((2, 8), jnp.float32)
    step, init_opt = make_train_step(model, donate=False)
    _, _, m = step(params, init_opt(params), batch)
    assert float(m["loss"]) == 0.0
    assert float(m["grad_norm"]) == 0.0


def test_dp_tp_grid(devices):
    """dp=2 × tp=4: batch sharded over dp, params sharded over tp.

    No dp-specific code exists in train.py — the gradient all-reduce
    over dp comes from XLA's sharding propagation (scaling-book recipe).
    """
    mesh = Mesh(np.array(devices).reshape(2, 4), ("dp", "tp"))
    model = DenseLLM(_tiny_cfg(4), mesh=mesh, axis="tp", impl="xla",
                     fwd_mode="xla")
    params = model.init(jax.random.PRNGKey(3))
    step, init_opt = make_train_step(model)
    opt_state = init_opt(params)
    batch = _batch(4, 8, model.config.vocab_size, seed=3)
    batch["input_ids"] = jax.device_put(
        batch["input_ids"], NamedSharding(mesh, P("dp")))

    losses = []
    for _ in range(3):
        params, opt_state, m = step(params, opt_state, batch)
        losses.append(float(m["loss"]))
        assert np.isfinite(losses[-1])
    assert losses[-1] < losses[0], losses


def test_dp_equals_single_device_math(devices):
    """The dp=2 sharded step computes the same loss as unsharded."""
    mesh_dp = Mesh(np.array(devices).reshape(2, 4), ("dp", "tp"))
    mesh_tp = Mesh(np.array(devices[:4]), ("tp",))
    batch = _batch(4, 8, 64, seed=4)

    losses = {}
    for name, mesh in (("dp", mesh_dp), ("flat", mesh_tp)):
        model = DenseLLM(_tiny_cfg(4), mesh=mesh, axis="tp", impl="xla",
                         fwd_mode="xla")
        params = model.init(jax.random.PRNGKey(5))
        step, init_opt = make_train_step(model, donate=False)
        b = dict(batch)
        if name == "dp":
            b["input_ids"] = jax.device_put(
                b["input_ids"], NamedSharding(mesh, P("dp")))
        _, _, m = step(params, init_opt(params), b)
        losses[name] = float(m["loss"])
    np.testing.assert_allclose(losses["dp"], losses["flat"], rtol=1e-5)


def test_ep_moe_trains(mesh8):
    """mode="ep": Qwen3-MoE with expert parallelism trains through the
    Pallas a2a dispatch/combine (the a2a VJP is the reverse exchange)
    and computes the same losses as the TP-sharded xla path."""
    from triton_dist_tpu.models import Qwen3MoE

    cfg = ModelConfig(
        hidden_size=32, moe_intermediate_size=32, num_hidden_layers=1,
        num_attention_heads=8, num_key_value_heads=8, head_dim=16,
        vocab_size=64, max_position_embeddings=32, dtype=jnp.float32,
        num_experts=8, num_experts_per_tok=2, intermediate_size=0)
    batch = _batch(2, 8, 64, seed=8)
    losses = {}
    for name, kw, mode in (
            ("tp", {"moe_parallel": "tp"}, "xla"),
            ("ep", {"moe_parallel": "ep", "impl": "pallas"}, "ep")):
        model = Qwen3MoE(cfg, mesh=mesh8, axis="tp", **kw)
        params = model.init(jax.random.PRNGKey(0))
        step, init_opt = make_train_step(model, mode=mode)
        opt_state = init_opt(params)
        seq = []
        for _ in range(3):
            params, opt_state, m = step(params, opt_state, batch)
            seq.append(float(m["loss"]))
            assert np.isfinite(seq[-1])
            assert np.isfinite(float(m["grad_norm"]))
        assert seq[-1] < seq[0], (name, seq)
        losses[name] = seq
    # Same math, different parallelism: EP must track TP step for step.
    np.testing.assert_allclose(losses["ep"], losses["tp"], rtol=2e-4)


def test_checkpoint_resume_training(mesh8, tmp_path):
    """Save mid-training, restore into a fresh process-state, continue:
    the resumed run must reproduce the uninterrupted run's losses
    exactly (params AND optimizer moments round-trip via orbax)."""
    from triton_dist_tpu.models.checkpoint import load_params, save_params

    model = DenseLLM(_tiny_cfg(8), mesh=mesh8, axis="tp", impl="xla",
                     fwd_mode="xla")
    params = model.init(jax.random.PRNGKey(4))
    step, init_opt = make_train_step(model, donate=False)
    opt_state = init_opt(params)
    batch = _batch(2, 8, model.config.vocab_size, seed=5)

    for _ in range(2):
        params, opt_state, _ = step(params, opt_state, batch)
    save_params(str(tmp_path / "ckpt"), {"params": params,
                                         "opt_state": opt_state})

    uninterrupted = []
    p, o = params, opt_state
    for _ in range(2):
        p, o, m = step(p, o, batch)
        uninterrupted.append(float(m["loss"]))

    restored = load_params(str(tmp_path / "ckpt"),
                           like={"params": params, "opt_state": opt_state})
    resumed = []
    p, o = restored["params"], restored["opt_state"]
    for _ in range(2):
        p, o, m = step(p, o, batch)
        resumed.append(float(m["loss"]))
    assert resumed == uninterrupted, (resumed, uninterrupted)


def test_unknown_mode_rejected(mesh8):
    model = DenseLLM(_tiny_cfg(8), mesh=mesh8, axis="tp", impl="xla",
                     fwd_mode="xla")
    with pytest.raises(ValueError, match="differentiable"):
        make_train_step(model, mode="bogus")


def test_fused_mode_trains(mesh8):
    """mode="ag_rs": the training step runs through the fused Pallas
    kernels in BOTH directions (custom VJPs, ops/autodiff.py) and its
    math matches the xla-mode step."""
    batch = _batch(2, 8, 64, seed=6)
    losses = {}
    for mode, impl in (("xla", "xla"), ("ag_rs", "pallas"),
                       ("gemm_ar", "pallas")):
        model = DenseLLM(_tiny_cfg(8), mesh=mesh8, axis="tp", impl=impl,
                         fwd_mode=mode)
        params = model.init(jax.random.PRNGKey(7))
        step, init_opt = make_train_step(model, mode=mode)
        opt_state = init_opt(params)
        seq = []
        for _ in range(3):
            params, opt_state, m = step(params, opt_state, batch)
            seq.append(float(m["loss"]))
            assert np.isfinite(seq[-1])
        losses[mode] = seq
        assert seq[-1] < seq[0], (mode, seq)
    np.testing.assert_allclose(losses["ag_rs"], losses["xla"],
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(losses["gemm_ar"], losses["xla"],
                               rtol=2e-4, atol=2e-5)


def test_training_differentiates_through_the_query_blocks(
        devices, monkeypatch):
    """A training forward is a whole-bucket prefill (caches sized exactly
    (B, S), offset 0, no left padding): where its scores pass the
    budget its attention is read in query blocks
    (layers/tp_attn._attention_core), plain XLA that differentiates as
    it stands. Same loss, same updated parameters as the one-block
    read, with and without remat."""
    from triton_dist_tpu.layers import tp_attn
    mesh = Mesh(np.array(devices[:1]), ("tp",))
    model = DenseLLM(_tiny_cfg(2), mesh=mesh, axis="tp", impl="xla",
                     fwd_mode="xla_ar")
    params = model.init(jax.random.PRNGKey(2))
    batch = _batch(2, 64, model.config.vocab_size, seed=2)

    def one_step(remat):
        step, init_opt = make_train_step(model, mode="xla_ar", remat=remat,
                                         donate=False)
        p2, _, m = step(params, init_opt(params), dict(batch))
        return float(m["loss"]), float(m["grad_norm"]), jax.tree.map(
            np.asarray, p2)

    want = one_step(False)
    monkeypatch.setattr(tp_attn, "_SCORE_BYTES", 16 << 10)
    assert len(tp_attn.prefill_blocks(2, 2, 64, None)) == 4
    for remat in (False, True):
        loss, gn, new = one_step(remat)
        assert np.isfinite(loss) and gn > 0
        np.testing.assert_allclose(loss, want[0], rtol=1e-5)
        np.testing.assert_allclose(gn, want[1], rtol=1e-4)
        for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(want[2])):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
