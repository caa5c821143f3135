"""The KV caches are donated to every program that rewrites them.

Every jitted program of the engine that takes the session's caches and
returns them donates them (``models/kv_cache.jit_rewriting_caches``), so
XLA writes the new positions in place instead of copying every leaf
first. The contract, on the CPU mesh: after each verb the caller's old
leaf is deleted and the session's new caches are live, greedy tokens
equal the goldens, and ``engine.cache_donation_fallbacks`` (the counter
that says a program's caches found no output to alias) reads 0. That
the chip's compiler then really aliases them is
tests/test_chip_compile.py's case; what an admission that fails after
dispatch does to the session is tests/test_scheduler.py's.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu import obs
from triton_dist_tpu.models import DenseLLM, Engine, ModelConfig
from triton_dist_tpu.models.kv_cache import (
    KVCacheManager, PagedKVCacheManager, jit_rewriting_caches)
from triton_dist_tpu.models.spec import SpecConfig

GEN = 6
DONATION_FALLBACKS = "engine.cache_donation_fallbacks"


@pytest.fixture()
def counters():
    """Telemetry on for the test; returns a reader of one counter."""
    obs.enable(obs.Registry())
    yield lambda name: obs.snapshot()["counters"].get(name, 0)
    obs.disable()


def _model(mesh, key, heads, kv_heads, head_dim, **kw):
    cfg = ModelConfig(hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=heads,
                      num_key_value_heads=kv_heads, head_dim=head_dim,
                      vocab_size=64, max_position_embeddings=64,
                      dtype=jnp.float32)
    model = DenseLLM(cfg, mesh=mesh, axis="tp", impl="xla", **kw)
    return model, model.init(key)


def _golden(model, params, prompt):
    """The prompt served alone through ``Engine.serve`` on the
    replicated tp path (token-equal across engine families; it takes
    prompt lengths the sp world does not divide)."""
    eng = Engine(model, batch=1, max_seq=64, prefill_mode="xla_ar",
                 decode_mode="gemm_ar")
    out = np.asarray(eng.serve(params, jnp.asarray([prompt], jnp.int32),
                               GEN))[0].tolist()
    return out[len(prompt):]


def _leaves(sess):
    return jax.tree.leaves(sess.caches)


def _decode(sess, row, first):
    """Row ``row`` to GEN tokens, one burst at a time; every burst must
    delete the leaf the caller held and leave live caches behind."""
    toks = [first]
    while len(toks) < GEN:
        held = _leaves(sess)[0]
        toks.extend(sess.decode_burst()[row])
        assert held.is_deleted(), "the decode step copied its caches"
        assert not any(x.is_deleted() for x in _leaves(sess))
    return toks[:GEN]


def _admit(sess, row, prompt, **kw):
    held = _leaves(sess)[0]
    first = sess.prefill_into_row(row, prompt, gen_budget=GEN, **kw)
    return held, first


# (engine family, Engine arguments, what must have run)
CASES = {
    "dense": ({}, lambda e: e._stream_step is not None),
    "mega": ({"decode_path": "mega"},
             lambda e: e._stream_step_mega is not None),
    "spec": ({"spec": SpecConfig(k=4)}, lambda e: e._spec_step),
    "chunked": ({}, lambda e: e._admit_finish is not None),
    "paged": ({"paged": True}, lambda e: e.paged),
    "paged_prefix": ({"paged": True},
                     lambda e: e._admit_prefix is not None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_cache_rewriting_program_donates(mesh8, key, counters, case):
    kw, ran = CASES[case]
    if kw.get("paged"):
        devs = list(mesh8.devices.flat)
        mesh = Mesh(np.array(devs).reshape(1, 8), ("tp", "sp"))
        model, params = _model(mesh, key, 4, 2, 16, sp_axis="sp",
                               fwd_mode="sp")
        eng = Engine(model, batch=2, max_seq=64, prefill_mode="sp",
                     decode_mode="sp", paged=True, page_size=4)
    else:
        model, params = _model(mesh8, key, 8, 8, 4)
        eng = Engine(model, batch=2, max_seq=64, prefill_mode="xla_ar",
                     decode_mode="gemm_ar", **kw)
    sess = eng.stream_session(params)
    # Repetitive, so the n-gram drafter has something to propose; 14
    # tokens, so chunks of 4 take four slices and three pages are full.
    prompt = [5, 6, 5, 6, 5, 6, 5, 6, 5, 6, 5, 6, 5, 6]
    if case == "chunked":
        held, first = _admit(sess, 0, prompt, chunk=4)
        assert first is None
        while first is None:
            assert not held.is_deleted()    # slices run on the scratch
            scratch = jax.tree.leaves(sess._pending[0]["small"])[0]
            first = sess.prefill_step(0)
            assert scratch.is_deleted()
    elif case == "paged_prefix":
        _, first = _admit(sess, 0, prompt)
        assert _decode(sess, 0, first) == _golden(model, params, prompt)
        sess.retire_row(0)
        prompt = prompt[:12] + [9, 3]       # three cached pages, new tail
        held, first = _admit(sess, 1, prompt)
        assert sess.admit_info["cached"] == 12
    else:
        held, first = _admit(sess, 0, prompt)
    assert held.is_deleted(), "the admission copied the caches"
    assert not any(x.is_deleted() for x in _leaves(sess))
    row = 1 if case == "paged_prefix" else 0
    assert _decode(sess, row, first) == _golden(model, params, prompt)
    assert ran(eng), case
    sess.close()
    assert counters(DONATION_FALLBACKS) == 0


def test_unusable_donation_is_counted(counters):
    """A program whose caches come back in another dtype cannot alias
    them: it runs (copying) and the counter says so, once per compile."""
    @jit_rewriting_caches
    def program(params, caches):
        return params + 1, [(k.astype(jnp.bfloat16), v) for k, v in caches]

    def caches():
        return [(jnp.zeros((2, 8)), jnp.ones((2, 8)))]

    with warnings.catch_warnings():
        # JAX says the same at lowering; the counter is what a run reads.
        warnings.filterwarnings("ignore", "Some donated buffers")
        for _ in range(2):
            _, out = program(jnp.int32(0), caches())
    assert out[0][0].dtype == jnp.bfloat16
    assert counters(DONATION_FALLBACKS) == 1

    @jit_rewriting_caches
    def in_place(params, caches):
        return params, [(k + 1, v) for k, v in caches]

    in_place(jnp.int32(0), caches())
    assert counters(DONATION_FALLBACKS) == 1


@pytest.mark.parametrize("paged", [False, True])
def test_every_cache_leaf_has_a_buffer_of_its_own(devices, paged):
    """One buffer under two leaves cannot be donated twice in one call
    ("Attempt to donate the same buffer twice in Execute()"); on a
    one-device mesh ``device_put`` of an array already there hands back
    the same buffer, so the constructors make each leaf anew."""
    mesh = Mesh(np.array(devices[:1]), ("tp",))
    if paged:
        kv = PagedKVCacheManager(3, 2, 4, 2, 4, 8, mesh=mesh, axis="tp")
    else:
        kv = KVCacheManager(3, 2, 16, 4, 8, mesh=mesh, axis="tp")
    leaves = jax.tree.leaves(kv.init())
    assert len(leaves) == 6
    assert len({x.unsafe_buffer_pointer() for x in leaves}) == 6
    assert all(x.sharding.is_equivalent_to(leaves[0].sharding, x.ndim)
               for x in leaves)
