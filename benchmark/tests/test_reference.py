"""The plain reference against the program's DenseLLM at a tiny size, and
the weights both sides are given."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmark.harness.builders import dense
from benchmark.reference import dense_decoder as ref

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = {"hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "vocab_size": 512, "max_position_embeddings": 512,
        "rope_theta": 1000000, "rms_norm_eps": 1e-6, "model_type": "qwen3"}


@pytest.mark.parametrize("tied", [True, False])
def test_reference_matches_the_program_forward(tied):
    from triton_dist_tpu.models import AutoLLM, ModelConfig
    from triton_dist_tpu.models.kv_cache import KVCacheManager
    model = dict(TINY, tie_word_embeddings=tied)
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    mc = ModelConfig.from_hf_config(dict(model, eos_token_id=None))
    llm = AutoLLM.build(mc, mesh=mesh, impl="xla")
    params = llm.shard_params(dense.make_params(model, mesh, "tp", 2147483999))
    b, s = 2, 24
    ids = np.random.default_rng(0).integers(1, 512, (b, s)).astype(np.int32)
    kv = KVCacheManager(2, b, 32, 2, 32, mesh=mesh, axis="tp",
                        dtype=jnp.bfloat16)
    got, _ = llm.forward(params, jnp.asarray(ids), kv.init(), 0,
                         mode="xla_ar")
    pos = np.tile(np.arange(s, dtype=np.int32), (b, 1))
    want = ref.read_logits(model, 2147483999, ids, pos)
    got, want = np.asarray(got, np.float32), np.asarray(want)
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert rel < 3e-2, rel          # bfloat16 program against float32
    assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.9


def test_weights_are_a_function_of_the_seed_alone():
    model = dict(TINY, tie_word_embeddings=True)
    key = ref.seed_key(5)
    a = ref.layer_leaves(key, 1, model)
    b = jax.jit(lambda k, i: ref.layer_leaves(k, i, model))(key, jnp.int32(1))
    for name in a:                   # traced layer index == python int
        np.testing.assert_array_equal(np.asarray(a[name], np.float32),
                                      np.asarray(b[name], np.float32))
    c = ref.layer_leaves(ref.seed_key(6), 1, model)
    assert not np.array_equal(np.asarray(a["w_q"], np.float32),
                              np.asarray(c["w_q"], np.float32))
    assert a["w_q"].dtype == jnp.bfloat16 and a["w_q"].shape == (128, 128)
    top = ref.top_leaves(key, model)
    assert top["lm_head"] is top["embed"]          # tied
    # a norm gain that is dropped must show: gains are not all one
    assert float(jnp.std(a["ln_attn"].astype(jnp.float32))) > 0.05


def test_configuration_files_match_the_presets():
    """The cells run the published sizes: the files hold what
    ``models/presets.py`` holds, key for key."""
    from triton_dist_tpu.models import ModelConfig, presets
    for name, preset in (("qwen3-0.6b", presets.qwen3_0_6b()),):
        with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == []
        mc = ModelConfig.from_hf_config(
            dict(dense.model_dict(cfg), eos_token_id=None))
        assert mc == preset, name


def test_pad_suffix_is_invisible():
    model = dict(TINY, tie_word_embeddings=True)
    ids = np.random.default_rng(1).integers(1, 512, (1, 16)).astype(np.int32)
    padded = np.concatenate([ids, np.zeros((1, 16), np.int32)], axis=1)
    pos = np.arange(16, dtype=np.int32)[None]
    a = np.asarray(ref.read_logits(model, 3, ids, pos))
    b = np.asarray(ref.read_logits(model, 3, padded, pos))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
