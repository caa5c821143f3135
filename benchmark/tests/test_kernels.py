"""The model step's count against a hand-worked shape."""
import importlib.util
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
MODEL = {"hidden_size": 1024, "intermediate_size": 3072,
         "num_hidden_layers": 28, "num_attention_heads": 16,
         "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 151936}


def kernel(name):
    path = os.path.join(HERE, "..", "kernels", name + ".py")
    spec = importlib.util.spec_from_file_location("k_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_model_step_flops_per_token():
    ms = kernel("model_step")
    per_layer = 1024 * (2048 + 2 * 1024) + 2048 * 1024 + 3 * 1024 * 3072
    assert ms.matmul_params(MODEL) == 28 * per_layer
    assert ms.head_params(MODEL) == 1024 * 151936
    one = ms.decode_token_flops(MODEL, 100)
    assert one == pytest.approx(
        2 * (28 * per_layer + 1024 * 151936) + 4 * 16 * 128 * 100 * 28)
    # a prompt of one token is a decode token with a context of one
    assert ms.prefill_flops(MODEL, 1) == pytest.approx(
        ms.decode_token_flops(MODEL, 1))
