"""The main-path kernels compile for the real chip — without one.

libtpu is installed here and compiles for a chip that is described, not
attached (``jax.experimental.topologies``). Interpret mode, which every
other test runs in, enforces neither VMEM limits nor tile alignment, and
an export (tests/test_mosaic_export.py) only lowers: Mosaic's own compile
is where "Slice shape along dimension 0 must be aligned to tiling (8)"
comes from. These cases run it on the kernels of the serving path at the
widths chip_smoke.py serves: Qwen3-0.6B on one chip, and the Qwen3-8B
tensor-parallel slice on the four chips of a v5e 2x2.

A compile that passes is not a chip run: nothing executes here.

The topology is described inside a module-scoped fixture — never at
import, in a skipif, in parametrize arguments or in conftest — because
only one process may load libtpu at a time and every xdist worker imports
every test file. Compiles run in this process, not a child, and stay in
this ONE file so one worker owns the library.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def meshes(topo):
    """{1: one-chip mesh, 4: the 2x2 as a tp ring} on described devices."""
    from jax.experimental import mesh_utils
    ring = np.asarray(mesh_utils.create_device_mesh(
        (1, 4), devices=list(topo.devices))).reshape(4)
    return {1: Mesh(np.array(topo.devices[:1]), ("tp",)),
            4: Mesh(ring, ("tp",))}


@pytest.fixture(autouse=True)
def _fused_or_fail(monkeypatch):
    """No routing away from the kernel under test, and no persistent
    compile cache (an entry compiled here cannot be read back without a
    chip, and warns)."""
    monkeypatch.setenv("TDT_FORCE_FUSED", "1")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(mesh, shape, spec, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _compile(fn, *args) -> int:
    """Compile ``fn`` for the described chip(s); returns how many Mosaic
    kernels the program holds. Raises what the chip's compiler raises."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count("tpu_custom_call")


# (world, M, K, N): 0.6B decode o-proj / down-proj on one chip; the 8B
# TP4 decode at batch 8 — 2 rows per rank, refused before the row split
# was padded (ops.common.ring_padded_rows) — and at a tile-aligned batch.
@pytest.mark.parametrize("world,m,k,n", [
    (1, 8, 2048, 1024), (1, 8, 3072, 1024), (1, 1, 3072, 1024),
    (4, 8, 4096, 4096), (4, 8, 12288, 4096), (4, 128, 12288, 4096)])
def test_gemm_ar_decode(meshes, world, m, k, n):
    from triton_dist_tpu.ops.gemm_reduce_scatter import (
        create_gemm_rs_context, gemm_ar)
    mesh = meshes[world]
    ctx = create_gemm_rs_context(mesh, "tp", interpret=False)
    assert _compile(lambda a, b: gemm_ar(a, b, ctx, impl="pallas"),
                    _sds(mesh, (m, k), P(None, "tp")),
                    _sds(mesh, (k, n), P("tp"))) == 1


# Prefill front half: 0.6B on one chip; 8B TP4 at the smallest admission
# bucket the engine produces for a 4-way row split (32) and a long one.
@pytest.mark.parametrize("world,m,k,n", [
    (1, 512, 1024, 3072), (4, 32, 4096, 12288), (4, 512, 4096, 12288)])
def test_ag_gemm_and_swiglu_prefill(meshes, world, m, k, n):
    from triton_dist_tpu.ops.allgather_gemm import (
        ag_gemm, ag_swiglu, create_ag_gemm_context)
    mesh = meshes[world]
    ctx = create_ag_gemm_context(mesh, "tp", interpret=False)
    a = _sds(mesh, (m, k), P("tp"))
    w = _sds(mesh, (k, n), P(None, "tp"))
    assert _compile(lambda a, b: ag_gemm(a, b, ctx, impl="pallas"),
                    a, w) == 1
    assert _compile(lambda a, g, u: ag_swiglu(a, g, u, ctx, impl="pallas"),
                    a, w, w) == 1


@pytest.mark.parametrize("world,m,k,n", [
    (1, 512, 3072, 1024), (4, 32, 4096, 4096), (4, 512, 12288, 4096)])
def test_gemm_rs_prefill(meshes, world, m, k, n):
    from triton_dist_tpu.ops.gemm_reduce_scatter import (
        create_gemm_rs_context, gemm_rs)
    mesh = meshes[world]
    ctx = create_gemm_rs_context(mesh, "tp", interpret=False)
    assert _compile(lambda a, b: gemm_rs(a, b, ctx, impl="pallas"),
                    _sds(mesh, (m, k), P(None, "tp")),
                    _sds(mesh, (k, n), P("tp"))) == 1


# Decode attention: the tiled kernel the paged engine runs on one chip,
# and the KV split over 4 with the cross-rank combine — refused before
# the (l, m) partials were laid out on a 128-lane dimension.
@pytest.mark.parametrize("world,hq,t", [(1, 16, 4096), (4, 32, 8192)])
def test_flash_decode_tiled(meshes, world, hq, t):
    from triton_dist_tpu.ops.flash_decode import (
        create_flash_decode_context, gqa_fwd_batch_decode)
    mesh = meshes[world]
    ctx = create_flash_decode_context(mesh, "tp", interpret=False,
                                      variant="tiled")
    kv = _sds(mesh, (8, t, 8, 128), P(None, "tp"))
    assert _compile(
        lambda q, k, v, n: gqa_fwd_batch_decode(q, k, v, n, ctx,
                                                impl="pallas"),
        _sds(mesh, (8, hq, 128), P()), kv, kv,
        _sds(mesh, (8,), P(), jnp.int32)) == 1


def test_flash_decode_paged_gathered(meshes):
    """The paged engine's decode call at its real pool geometry: 16-token
    pages, batch 8 x 4096 positions on one chip."""
    from triton_dist_tpu.ops.flash_decode import (
        create_flash_decode_context, gqa_fwd_batch_decode_paged)
    mesh = meshes[1]
    ctx = create_flash_decode_context(mesh, "tp", interpret=False)
    pool = _sds(mesh, (2049, 16, 8, 128), P("tp"))
    assert _compile(
        lambda q, pk, pv, tb, n: gqa_fwd_batch_decode_paged(
            q, pk, pv, tb, n, ctx, impl="pallas"),
        _sds(mesh, (8, 16, 128), P()), pool, pool,
        _sds(mesh, (1, 8, 256), P("tp"), jnp.int32),
        _sds(mesh, (8,), P(), jnp.int32)) == 1


# World-4 collectives. all_reduce at M=8 is the decode batch: two-shot's
# row split of 2 rows per rank was refused before it was padded.
@pytest.mark.parametrize("method,m", [("one_shot", 8), ("two_shot", 8),
                                      ("two_shot", 256)])
def test_all_reduce_world4(meshes, method, m):
    from triton_dist_tpu.ops.allreduce import (
        AllReduceMethod, all_reduce, create_allreduce_context)
    mesh = meshes[4]
    ctx = create_allreduce_context(mesh, "tp", interpret=False,
                                   method=AllReduceMethod(method))
    assert _compile(lambda x: all_reduce(x, ctx, impl="pallas"),
                    _sds(mesh, (4, m, 4096), P("tp"))) == 1


def test_all_gather_and_reduce_scatter_ring_world4(meshes):
    from triton_dist_tpu.ops.allgather import (
        AllGatherMethod, all_gather, create_allgather_context)
    from triton_dist_tpu.ops.reduce_scatter import (
        create_reduce_scatter_context, reduce_scatter)
    mesh = meshes[4]
    for method in (AllGatherMethod.RING_1D, AllGatherMethod.RING_BIDIR,
                   AllGatherMethod.FULL_MESH_PUSH):
        ag = create_allgather_context(mesh, "tp", method=method,
                                      interpret=False)
        assert _compile(lambda x, ag=ag: all_gather(x, ag, impl="pallas"),
                        _sds(mesh, (1024, 4096), P("tp"))) == 1
    rs = create_reduce_scatter_context(mesh, "tp", interpret=False)
    assert _compile(lambda x: reduce_scatter(x, rs, impl="pallas"),
                    _sds(mesh, (4, 1024, 4096), P("tp"))) == 1


def test_decode_step_and_admission_write_the_caches_in_place(
        meshes, monkeypatch):
    """The engine's real stream decode step and admission program at
    the Qwen3-0.6B widths the benchmark serves (four layers of 28,
    batch 8 x 4096 positions, per-row offsets), with the engine's
    donation: the chip's compiler aliases every byte of the caches to
    the output and the entry computation holds no ``copy`` of a
    cache-shaped array. Without donation the same step read alias 0
    and one whole-leaf copy per leaf (3.5 GiB a call at 28 layers).
    The admission also carries the session's small state (last
    tokens, offsets, the sampling key) in and out and seats its row
    in-graph; a greedy engine, the benchmark's, traces no key split
    into either program (ISSUE 31)."""
    import re
    from triton_dist_tpu.models import AutoLLM, Engine, ModelConfig
    # The engine builds its own kernel contexts, which interpret where
    # the default backend is the CPU: steer them to the compiled path.
    monkeypatch.setenv("TDT_FORCE_COMPILED", "1")
    mesh, layers = meshes[1], 4
    cfg = ModelConfig.from_hf_config(dict(
        hidden_size=1024, intermediate_size=3072,
        num_hidden_layers=layers, num_attention_heads=16,
        num_key_value_heads=8, head_dim=128, vocab_size=151936,
        max_position_embeddings=40960, rope_theta=1000000,
        rms_norm_eps=1e-6, tie_word_embeddings=True, model_type="qwen3"))
    llm = AutoLLM.build(cfg, mesh=mesh, axis="tp", impl="pallas")
    eng = Engine(llm, batch=8, max_seq=4096, prefill_mode="xla_ar",
                 decode_mode="gemm_ar")

    def sds(shape, dtype=BF16):
        return _sds(mesh, shape, P(), dtype)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(llm.init, jax.random.PRNGKey(0)))
    leaf = (8, 4096, 8, 128)
    caches = [(sds(leaf), sds(leaf)) for _ in range(layers)]
    cache_bytes = 2 * layers * int(np.prod(leaf)) * 2
    key, i32 = sds((2,), jnp.uint32), jnp.int32
    token, offsets = sds((8,), i32), sds((8,), i32)
    programs = {
        "step": eng._build_stream_step().lower(
            params, caches, token, offsets, key, sds((8,), jnp.bool_),
            None),
        "admit": eng._build_admit().lower(
            params, caches, sds((1, 128), i32), sds((), i32),
            sds((), i32), token, offsets, key),
        # A bucket whose float32 scores pass the budget of the chip's
        # fast memory is read in query blocks (ISSUE 35).
        "admit_2048": eng._build_admit().lower(
            params, caches, sds((1, 2048), i32), sds((), i32),
            sds((), i32), token, offsets, key)}
    for name, lowered in programs.items():
        compiled = lowered.compile()
        # step: (tokens, caches, offsets); admit: (first token, caches,
        # tokens and offsets with the row seated, the next key).
        out = compiled.out_info
        assert [(x.shape, x.dtype) for x in (out[0], *out[2:])] == (
            [((8,), i32), ((8,), i32)] if name == "step" else
            [((), i32), ((8,), i32), ((8,), i32), ((2,), jnp.uint32)]), name
        assert "threefry" not in lowered.as_text(), name
        assert compiled.memory_analysis().alias_size_in_bytes \
            == cache_bytes, name
        text = compiled.as_text()
        entry = text[text.rindex("ENTRY"):]
        # The real step: its gemm_ar kernels (xla_ar prefill has none).
        assert ("tpu_custom_call" in text) == (name == "step")
        assert not re.findall(r"\[8,4096,8,128\]\S* copy\(", entry), name
        if name != "step":
            # The head multiplies the one row the admission reads: no
            # float32 logits of bucket x vocabulary (ISSUE 38; 1.24 GB
            # and 3.5 ms of a 2048 admission before).
            assert not re.search(r"f32\[(?:1,)?(?:128|2048),151936\]", text)
            assert re.search(r"f32\[1,151936\]", text), name
        if name == "admit_2048":
            # No (S, S) score tensor: four blocks of 512 rows against the
            # keys up to their own end, every one assigned to the fast
            # memory (``S(1)``), where the whole square went to HBM.
            assert not re.search(r"f32\[1,8,2,2048,\d+\]", text)
            for keys in (512, 1024, 1536, 2048):
                layouts = re.findall(
                    rf"f32\[1,8,2,512,{keys}\](\{{[^}}]*\}})", entry)
                assert layouts and all(
                    lay.endswith("S(1)}") for lay in layouts), (keys, layouts)
        if name != "step":
            continue
        # The step's attention picks its window in the graph (ISSUE 33): a
        # loop over 512-position chunks and, past half of the cache, the
        # whole read, one ``conditional`` per layer. Neither may copy a
        # cache leaf or a chunk of one: a ``copy`` of that shape outside a
        # fusion is materialised (a static slice under ``lax.switch`` cost
        # one whole-leaf layout copy per branch here), inside one it is the
        # operand layout of the contraction that consumes it.
        assert len(re.findall(r" conditional\(", text)) == layers
        loose = [head.split()[0] for head, body in re.findall(
            r"^((?:ENTRY )?%\S+ [^\n]*\{)\n(.*?)^\}", text, re.M | re.S)
            if "fused_computation" not in head
            and re.search(r"\[8,(?:4096|512),8,128\]\S* copy\(", body)]
        assert not loose, loose


@pytest.mark.parametrize("program", ["step", "admit"])
def test_exaone_share_programs(meshes, monkeypatch, program):
    """K-EXAONE-236B-A23B at the benchmark's own shapes (its configuration
    file: published widths, 16 of 128 experts, 5 layers, 64 rows x 4096):
    the stream step and a 2048-token admission compile for one v5e, fit
    it beside their arguments, donate every cache leaf, full layers'
    (64, 4096) and window layers' (64, 128) rings alike, and hold the
    held experts' grouped matmuls as XLA's ragged dot."""
    import json
    import os
    import re
    from benchmark.harness.builders import exaone
    from triton_dist_tpu.models import AutoLLM, Engine, ModelConfig
    monkeypatch.setenv("TDT_FORCE_COMPILED", "1")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "k-exaone-236b-a23b.json")) as f:
        cfg = json.load(f)
    mesh = meshes[1]
    hf = dict(exaone.model_dict(cfg), eos_token_id=None,
              expert_parallel=cfg["expert_parallel"])
    llm = AutoLLM.build(ModelConfig.from_hf_config(hf), mesh=mesh,
                        axis="tp", impl="pallas")
    eng = Engine(llm, **cfg["engine"])

    def sds(shape, dtype=BF16):
        return _sds(mesh, shape, P(), dtype)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(llm.init, jax.random.PRNGKey(0)))
    rows, max_seq = cfg["engine"]["batch"], cfg["engine"]["max_seq"]
    assert llm.windows == (128, 128, 128, None, 128)
    leaves = [(rows, w or max_seq, 8, 128) for w in llm.windows]
    caches = [(sds(leaf), sds(leaf)) for leaf in leaves]
    cache_bytes = sum(2 * 2 * int(np.prod(leaf)) for leaf in leaves)
    assert cache_bytes == 2 * 2 * rows * 8 * 128 * (4 * 128 + max_seq)
    i32, counts = jnp.int32, len(eng.count_names)
    key = sds((2,), jnp.uint32)
    token, offsets = sds((rows + counts,), i32), sds((rows,), i32)
    if program == "step":
        lowered = eng._build_stream_step().lower(
            params, caches, token, offsets, key, sds((rows,), jnp.bool_),
            None)
    else:
        lowered = eng._build_admit().lower(
            params, caches, sds((1, 2048), i32), sds((), i32), sds((), i32),
            token, offsets, key)
    compiled = lowered.compile()
    out = compiled.out_info
    # What the host reads back each call: the tokens (or the first token)
    # with the program's counts behind them, one vector.
    assert (out[0].shape, out[0].dtype) == (
        (rows + counts,) if program == "step" else (1 + counts,), i32)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12e9
    text = compiled.as_text()
    assert "ragged-dot" in text
    assert "threefry" not in lowered.as_text()
    if program == "admit":
        # The admission's attention is read in query blocks (ISSUE 35): 64
        # heads x 2048 keys leave a full layer 128 rows a block, a window
        # layer 256 rows against at most 384 keys; no S x S scores.
        assert not re.search(r"f32\[1,8,8,2048,\d+\]", text)
        assert re.search(r"f32\[1,8,8,128,2048\]\{[^}]*S\(1\)\}", text)
        assert re.search(r"f32\[1,8,8,256,384\]\{[^}]*S\(1\)\}", text)
