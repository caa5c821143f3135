"""Bidirectional-ring fused GEMM schedules (ISSUE 2 tentpole).

Numerics of every fused variant against the XLA golden at world sizes
1/2/4 plus the odd world 3, in BOTH ring-direction modes — the
unidirectional schedule (``ring_dirs=1``, the round-5 proven-on-chip
fallback, selectable via ``TDT_RING_DIRS=1``) must stay byte-identical
in behavior, and the bidirectional schedule (``ring_dirs=2``, the
default) must match it exactly. Plus the pure-python ring-schedule
protocol properties (permutation + arrival monotonicity) that hold
independent of Pallas, and the per-op overlap gauges.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_dist_tpu.ops.common import (
    resolve_ring_dirs, ring_chunk_schedule, ring_hop_counts)

#: Interpret-mode kernel numerics -> full tier (like test_ag_gemm.py).
pytestmark = pytest.mark.slow


def _mesh(world):
    return Mesh(np.array(jax.devices()[:world]), ("tp",))


def _sharded(a, mesh, spec):
    return jax.device_put(a, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Protocol properties (pure python/jnp — no kernels)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("world", [1, 2, 3, 4, 5, 8])
@pytest.mark.parametrize("dirs", [1, 2])
def test_ring_schedule_is_permutation(world, dirs):
    """Every rank consumes every chunk exactly once, starting with its
    own; hop counts cover all w-1 travelling chunks."""
    n_fwd, n_bwd = ring_hop_counts(world, dirs)
    assert n_fwd + n_bwd == max(world - 1, 0)
    for me in range(world):
        chunks, offs = [], {0: [], 1: []}
        for s in range(world):
            c, is_bwd, off = ring_chunk_schedule(me, s, world, dirs)
            chunks.append(int(c))
            offs[int(is_bwd)].append(int(off))
        assert chunks[0] == me
        assert sorted(chunks) == list(range(world)), (me, chunks)
        # offsets stay within each direction's hop budget
        assert all(o <= n_fwd for o in offs[0])
        assert all(o <= n_bwd for o in offs[1])


@pytest.mark.parametrize("world", [2, 3, 4, 5, 8])
def test_ring_schedule_arrival_monotone(world):
    """A chunk at hop offset o+1 is consumed at a strictly later
    schedule position than offset o (per direction) — the
    happens-before every ``advance`` wait relies on: the hop that
    delivers position s's chunk was started at an earlier position on
    the sending rank, which runs the same schedule."""
    for dirs in (1, 2):
        for me in range(world):
            pos = {0: {}, 1: {}}
            for s in range(world):
                _, is_bwd, off = ring_chunk_schedule(me, s, world, dirs)
                pos[int(is_bwd)][int(off)] = s
            for d in (0, 1):
                offsets = sorted(pos[d])
                positions = [pos[d][o] for o in offsets]
                assert positions == sorted(positions), (dirs, me, pos)


def test_resolve_ring_dirs_env(monkeypatch):
    monkeypatch.delenv("TDT_RING_DIRS", raising=False)
    assert resolve_ring_dirs(0) == 2          # default: bidirectional
    assert resolve_ring_dirs(1) == 1          # explicit ctx wins
    monkeypatch.setenv("TDT_RING_DIRS", "1")  # proven-fallback switch
    assert resolve_ring_dirs(0) == 1
    assert resolve_ring_dirs(2) == 2          # ctx still wins over env
    monkeypatch.setenv("TDT_RING_DIRS", "3")
    with pytest.raises(ValueError):
        resolve_ring_dirs(0)
    with pytest.raises(ValueError):
        resolve_ring_dirs(7)


# ---------------------------------------------------------------------------
# Kernel numerics vs the XLA golden (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("world", [1, 2, 3, 4])
@pytest.mark.parametrize("dirs", [1, 2])
def test_ag_gemm_ring_dirs_exact(world, dirs, key):
    """vmem and N-blocked hbm variants are numerics-EXACT vs the XLA
    golden (full-K dots — same per-row reduction); the k-tiled fallback
    matches to accumulation tolerance."""
    from triton_dist_tpu.ops import allgather_gemm as agm
    mesh = _mesh(world)
    m, k, n = 16 * world, 32, 64 * world
    a = (jax.random.normal(key, (m, k)) / 4).astype(jnp.float32)
    b = (jax.random.normal(jax.random.PRNGKey(1), (k, n)) / 4
         ).astype(jnp.float32)
    a_s = _sharded(a, mesh, P("tp"))
    b_s = _sharded(b, mesh, P(None, "tp"))
    golden = np.asarray(a, np.float64) @ np.asarray(b, np.float64)

    ctx = agm.create_ag_gemm_context(mesh)
    ctx.ring_dirs = dirs
    ref = agm.ag_gemm(a_s, b_s, ctx, impl="xla")
    out = agm.ag_gemm(a_s, b_s, ctx, impl="pallas")
    assert np.array_equal(np.asarray(out), np.asarray(ref)), "vmem"
    np.testing.assert_allclose(np.asarray(out), golden, rtol=1e-3,
                               atol=1e-3)

    ctx2 = agm.create_ag_gemm_context(mesh)
    ctx2.ring_dirs = dirs
    ctx2.variant = "hbm"
    ctx2.block_m, ctx2.block_n = 4, 32
    out2 = agm.ag_gemm(a_s, b_s, ctx2, impl="pallas")
    assert np.array_equal(np.asarray(out2), np.asarray(ref)), "hbm"

    ctx3 = agm.create_ag_gemm_context(mesh)
    ctx3.ring_dirs = dirs
    ctx3.variant = "hbm_kt"
    ctx3.block_m, ctx3.block_k = 4, 8
    out3 = agm.ag_gemm(a_s, b_s, ctx3, impl="pallas")
    np.testing.assert_allclose(np.asarray(out3), golden, rtol=1e-3,
                               atol=1e-3)


@pytest.mark.parametrize("world", [1, 2, 3, 4])
@pytest.mark.parametrize("dirs", [1, 2])
def test_gemm_rs_ring_dirs(world, dirs, key):
    """Bidirectional column-halved RS matches the golden at every world
    (ring summation order differs from psum only at float tolerance)."""
    from triton_dist_tpu.ops import gemm_reduce_scatter as grs
    mesh = _mesh(world)
    m, k, n = 16 * world, 32 * world, 256
    a = (jax.random.normal(key, (m, k)) / 4).astype(jnp.float32)
    b = (jax.random.normal(jax.random.PRNGKey(1), (k, n)) / 4
         ).astype(jnp.float32)
    a_s = _sharded(a, mesh, P(None, "tp"))
    b_s = _sharded(b, mesh, P("tp"))
    golden = np.asarray(a, np.float64) @ np.asarray(b, np.float64)

    ctx = grs.create_gemm_rs_context(mesh)
    ctx.ring_dirs = dirs
    out = grs.gemm_rs(a_s, b_s, ctx, impl="pallas")
    assert out.shape == (m, n)
    np.testing.assert_allclose(np.asarray(out), golden, rtol=1e-4,
                               atol=1e-4)
    ar = grs.gemm_ar(a_s, b_s, ctx, impl="pallas")
    np.testing.assert_allclose(np.asarray(ar), golden, rtol=1e-4,
                               atol=1e-4)

    ctx2 = grs.create_gemm_rs_context(mesh)
    ctx2.ring_dirs = dirs
    ctx2.variant = "hbm"
    ctx2.block_m, ctx2.block_n = max(m // world // 2, 4), 64
    out2 = grs.gemm_rs(a_s, b_s, ctx2, impl="pallas")
    np.testing.assert_allclose(np.asarray(out2), golden, rtol=1e-4,
                               atol=1e-4)
    ar2 = grs.gemm_ar(a_s, b_s, ctx2, impl="pallas")
    np.testing.assert_allclose(np.asarray(ar2), golden, rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("dirs", [1, 2])
def test_ag_swiglu_bias_epilogue(dirs, key):
    """The fused AG-SwiGLU kernel with the bias epilogue (both ring
    modes) matches the analytic golden and its own XLA impl."""
    from triton_dist_tpu.ops import allgather_gemm as agm
    world = 4
    mesh = _mesh(world)
    m, k, n = 256 * world, 64, 256 * world   # rows/n_loc = 256 (kernel)
    ks = jax.random.split(key, 5)
    a = (jax.random.normal(ks[0], (m, k)) / 4).astype(jnp.float32)
    wg = (jax.random.normal(ks[1], (k, n)) / 4).astype(jnp.float32)
    wu = (jax.random.normal(ks[2], (k, n)) / 4).astype(jnp.float32)
    bg = (jax.random.normal(ks[3], (n,)) / 4).astype(jnp.float32)
    bu = (jax.random.normal(ks[4], (n,)) / 4).astype(jnp.float32)

    ag = np.asarray(a, np.float32)
    g = ag @ np.asarray(wg, np.float32) + np.asarray(bg, np.float32)
    u = ag @ np.asarray(wu, np.float32) + np.asarray(bu, np.float32)
    golden = (g / (1 + np.exp(-g))) * u

    ctx = agm.create_ag_gemm_context(mesh)
    ctx.ring_dirs = dirs
    got = agm.ag_swiglu(a, wg, wu, ctx, impl="pallas",
                        b_gate=bg, b_up=bu)
    np.testing.assert_allclose(np.asarray(got), golden, rtol=1e-3,
                               atol=1e-3)
    ref = agm.ag_swiglu(a, wg, wu, ctx, impl="xla", b_gate=bg, b_up=bu)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError):
        agm.ag_swiglu(a, wg, wu, ctx, impl="pallas", b_gate=bg)


def test_tp_mlp_bias_fused_matches_xla(key):
    """TPMLP(use_bias=True): the fused path (bias + SwiGLU inside the
    AG-GEMM consumer loop, down-bias after the reduce) matches the xla
    golden in both layouts."""
    from triton_dist_tpu.layers.tp_mlp import TPMLP
    mesh = _mesh(4)
    mlp = TPMLP(64, 1024, mesh=mesh, axis="tp", dtype=jnp.float32,
                use_bias=True)
    params = mlp.init(key)
    assert {"b_gate", "b_up", "b_down"} <= set(params)
    ks = jax.random.split(key, 3)
    params["b_gate"] = _sharded(
        (jax.random.normal(ks[0], (1024,)) / 4).astype(jnp.float32),
        mesh, P("tp"))
    params["b_up"] = _sharded(
        (jax.random.normal(ks[1], (1024,)) / 4).astype(jnp.float32),
        mesh, P("tp"))
    params["b_down"] = _sharded(
        (jax.random.normal(ks[2], (64,)) / 4).astype(jnp.float32),
        mesh, P())
    x = _sharded((jax.random.normal(jax.random.PRNGKey(1), (1024, 64))
                  / 4).astype(jnp.float32), mesh, P("tp"))
    np.testing.assert_allclose(
        np.asarray(mlp(params, x, mode="ag_rs")),
        np.asarray(mlp(params, x, mode="xla")), rtol=2e-3, atol=2e-3)
    xr = _sharded((jax.random.normal(jax.random.PRNGKey(2), (64, 64))
                   / 4).astype(jnp.float32), mesh, P())
    np.testing.assert_allclose(
        np.asarray(mlp(params, xr, mode="gemm_ar")),
        np.asarray(mlp(params, xr, mode="xla_ar")), rtol=2e-3, atol=2e-3)


def test_overlap_gauges_in_snapshot(key):
    """comms.<op>.overlap_pct gauges land in the obs snapshot when the
    fused ops dispatch (the north-star metric stops reading a
    hardcoded 0)."""
    from triton_dist_tpu import obs
    from triton_dist_tpu.ops import allgather_gemm as agm
    from triton_dist_tpu.ops import gemm_reduce_scatter as grs
    mesh = _mesh(4)
    obs.disable()
    obs.enable()
    try:
        m, k, n = 64, 128, 256
        a = (jax.random.normal(key, (m, k)) / 4).astype(jnp.float32)
        b = (jax.random.normal(jax.random.PRNGKey(1), (k, n)) / 4
             ).astype(jnp.float32)
        agm.ag_gemm(_sharded(a, mesh, P("tp")),
                    _sharded(b, mesh, P(None, "tp")),
                    agm.create_ag_gemm_context(mesh), impl="pallas")
        grs.gemm_rs(_sharded(a, mesh, P(None, "tp")),
                    _sharded(b, mesh, P("tp")),
                    grs.create_gemm_rs_context(mesh), impl="pallas")
        gauges = obs.snapshot()["gauges"]
        assert 0.0 <= gauges["comms.ag_gemm.overlap_pct"] <= 100.0
        assert 0.0 <= gauges["comms.gemm_rs.overlap_pct"] <= 100.0
        assert "comms.ag_gemm.exposed_comm_ms" in gauges
    finally:
        obs.disable()
