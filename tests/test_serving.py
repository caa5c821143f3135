"""Serving roundtrip test (reference model_server/chat demo, SURVEY §2.7)."""

import jax
import jax.numpy as jnp
import numpy as np

from triton_dist_tpu.models import DenseLLM, Engine, ModelConfig
from triton_dist_tpu.serving import ChatClient, ModelServer


def test_server_client_roundtrip(mesh8, key):
    cfg = ModelConfig(hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=8,
                      num_key_value_heads=8, head_dim=4, vocab_size=64,
                      max_position_embeddings=32, dtype=jnp.float32)
    model = DenseLLM(cfg, mesh=mesh8, axis="tp", impl="xla")
    params = model.init(key)
    eng = Engine(model, batch=1, max_seq=16, prefill_mode="xla_ar",
                 decode_mode="gemm_ar")
    srv = ModelServer(eng, params, port=0).start()
    try:
        client = ChatClient(srv.host, srv.port)
        resp = client.generate_ids([[1, 2, 3]], gen_len=4)
        assert "tokens" in resp and len(resp["tokens"][0]) == 4
        assert resp["latency_ms"] > 0
        # server result must equal a direct engine call
        direct = eng.serve(params, jnp.asarray([[1, 2, 3]], jnp.int32), 4)
        np.testing.assert_array_equal(np.asarray(resp["tokens"]),
                                      np.asarray(direct)[:, 3:])
        # malformed request → error response, server stays alive
        bad = client.generate_ids("nonsense", gen_len=1)
        assert "error" in bad
        ok = client.generate_ids([[5]], gen_len=2)
        assert "tokens" in ok
        client.close()
    finally:
        srv.stop()


def test_server_streams_oversized_batches(mesh8, key):
    """More prompts than engine rows route through serve_stream and
    match solo generations (continuous batching behind the protocol)."""
    cfg = ModelConfig(hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=8,
                      num_key_value_heads=8, head_dim=4, vocab_size=64,
                      max_position_embeddings=32, dtype=jnp.float32)
    model = DenseLLM(cfg, mesh=mesh8, axis="tp", impl="xla")
    params = model.init(key)
    eng = Engine(model, batch=2, max_seq=16, prefill_mode="xla_ar",
                 decode_mode="gemm_ar")
    srv = ModelServer(eng, params, port=0).start()
    prompts = [[1, 2], [3, 4, 5], [6], [7, 8]]
    try:
        client = ChatClient(srv.host, srv.port)
        resp = client.generate_ids(prompts, gen_len=3)
        assert len(resp["tokens"]) == len(prompts)
        solo = Engine(model, batch=1, max_seq=16, prefill_mode="xla_ar",
                      decode_mode="gemm_ar")
        for prompt, row in zip(prompts, resp["tokens"]):
            want = np.asarray(solo.serve(
                params, jnp.asarray([prompt], jnp.int32), 3))[0]
            np.testing.assert_array_equal(np.asarray(row),
                                          want[len(prompt):])
        client.close()
    finally:
        srv.stop()


def test_server_concurrent_clients(mesh8, key):
    """Two clients in flight at once: the ThreadingTCPServer accepts
    both, the generation lock serializes engine access, and each client
    gets exactly its own answer (reference model_server is likewise a
    threaded socket server)."""
    import threading

    cfg = ModelConfig(hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=8,
                      num_key_value_heads=8, head_dim=4, vocab_size=64,
                      max_position_embeddings=32, dtype=jnp.float32)
    model = DenseLLM(cfg, mesh=mesh8, axis="tp", impl="xla")
    params = model.init(key)
    eng = Engine(model, batch=1, max_seq=16, prefill_mode="xla_ar",
                 decode_mode="gemm_ar")
    srv = ModelServer(eng, params, port=0).start()
    results: dict[int, dict] = {}
    prompts = {0: [1, 2, 3], 1: [7, 8]}
    try:
        def worker(i):
            c = ChatClient(srv.host, srv.port)
            results[i] = c.generate_ids([prompts[i]], gen_len=3)
            c.close()

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        for i, prompt in prompts.items():
            assert "tokens" in results[i], results[i]
            direct = np.asarray(eng.serve(
                params, jnp.asarray([prompt], jnp.int32), 3))[0]
            np.testing.assert_array_equal(
                np.asarray(results[i]["tokens"][0]),
                direct[len(prompt):])
    finally:
        srv.stop()


def test_server_ragged_prompts(mesh8, key):
    """Variable-length prompt rows route through serve_ragged and match
    solo generations (greedy)."""
    cfg = ModelConfig(hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=8,
                      num_key_value_heads=8, head_dim=4, vocab_size=64,
                      max_position_embeddings=32, dtype=jnp.float32)
    model = DenseLLM(cfg, mesh=mesh8, axis="tp", impl="xla")
    params = model.init(key)
    eng = Engine(model, batch=2, max_seq=16, prefill_mode="xla_ar",
                 decode_mode="gemm_ar")
    srv = ModelServer(eng, params, port=0).start()
    try:
        client = ChatClient(srv.host, srv.port)
        resp = client.generate_ids([[1, 2, 3, 4], [9]], gen_len=3)
        assert len(resp["tokens"]) == 2
        solo = Engine(model, batch=1, max_seq=16, prefill_mode="xla_ar",
                      decode_mode="gemm_ar")
        for row, prompt in zip(resp["tokens"], [[1, 2, 3, 4], [9]]):
            direct = np.asarray(solo.serve(
                params, jnp.asarray([prompt], jnp.int32), 3))[0]
            np.testing.assert_array_equal(np.asarray(row),
                                          direct[len(prompt):])
        client.close()
    finally:
        srv.stop()
