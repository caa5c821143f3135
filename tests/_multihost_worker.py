"""Worker process for tests/test_multihost.py — NOT collected by pytest.

One of two cooperating `jax.distributed` processes on CPU (gloo
collectives). Exercises the code paths no single-process 8-device mesh
can touch (VERDICT r4 next-5): ``runtime/dist.py::_maybe_multihost_init``
(driven by the JAX_COORDINATOR_ADDRESS/... env the TPU pod launcher
would set), a cross-process collective through the global mesh, and one
``tools/autotuner.py`` round whose multi-host agreement protocol
(worst-rank scores via ``process_allgather``, process-0 cache-hit
broadcast) must leave both processes with the same winner.

Reference analog: every reference test runs under torchrun with
NCCL/gloo process groups (SURVEY.md §4); this is the TPU-native spine's
DCN-path equivalent.
"""

import os
import sys


def main() -> None:
    pid, port, tmpdir = int(sys.argv[1]), sys.argv[2], sys.argv[3]
    os.environ["JAX_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
    os.environ["JAX_NUM_PROCESSES"] = "2"
    os.environ["JAX_PROCESS_ID"] = str(pid)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    # Exercise the device-kind-keyed disk cache path too (shared dir —
    # both processes see the same file, like a shared NFS home on a pod).
    os.environ["TDT_AUTOTUNE_CACHE"] = os.path.join(tmpdir, "autotune.json")

    import jax

    # BEFORE any backend init: this worker is a CPU process whatever
    # the machine holds (jax.distributed over gloo).
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from triton_dist_tpu.runtime import dist as tdist

    ctx = tdist.initialize_distributed()
    assert jax.process_count() == 2, jax.process_count()
    assert ctx.num_processes == 2
    assert ctx.world_size == 8, ctx.world_size
    mesh = ctx.mesh

    # -- 1. cross-process collective through the global mesh (DCN path).
    # Data lives sharded across BOTH processes; the psum must cross them.
    x = jax.device_put(
        jnp.arange(8, dtype=jnp.float32),
        NamedSharding(mesh, P("tp")))

    @jax.jit
    def total(v):
        return jnp.sum(v)

    s = float(total(x))
    assert s == 28.0, s

    # A shard_map psum over the mesh axis — the framework's collective
    # idiom (ops use this shape) across the process boundary.
    from jax import shard_map

    @jax.jit
    def allred(v):
        return shard_map(
            lambda t: jax.lax.psum(t, "tp"),
            mesh=mesh, in_specs=P("tp"), out_specs=P())(v)

    r = np.asarray(allred(jnp.ones((8,), jnp.float32)))
    assert float(r[0]) == 8.0, r

    # -- 1b. a HIERARCHICAL collective (VERDICT r4 next-5's literal
    # ask) on a 2-D ici x dcn mesh whose dcn axis spans the process
    # boundary — the exact pod topology ops/hierarchical.py is
    # designed for (ICI stage local, DCN stage cross-process).
    from triton_dist_tpu.ops import hierarchical as hier

    ctx2 = tdist.initialize_distributed(
        mesh_shape={"dcn": 2, "ici": 4})
    assert ctx2.mesh.shape == {"dcn": 2, "ici": 4}
    h = jax.device_put(
        jnp.arange(16, dtype=jnp.float32).reshape(8, 2),
        NamedSharding(ctx2.mesh, P(None)))  # replicated partials
    ar = np.asarray(hier.all_reduce_nd(h, ctx2.mesh, ("ici", "dcn")))
    np.testing.assert_allclose(
        ar, np.arange(16, dtype=np.float32).reshape(8, 2) * 8.0)
    ag_in = jax.device_put(
        jnp.arange(16, dtype=jnp.float32).reshape(8, 2),
        NamedSharding(ctx2.mesh, P(("dcn", "ici"))))
    ag = np.asarray(hier.all_gather_nd(ag_in, ctx2.mesh, ("ici", "dcn")))
    # Global (8, 2) sharded over all 8 devices -> gathered back,
    # replicated: the ICI stage collects the 4 local shards, the DCN
    # stage crosses the process boundary for the other host's half.
    np.testing.assert_allclose(
        ag, np.arange(16, dtype=np.float32).reshape(8, 2))

    # -- 1c. op-layer entry points on the cross-process mesh: the
    # context objects + shard_map plumbing of the fused-op API must
    # work when the tp axis spans processes (impl="xla" — the
    # XLA-collective path is what rides DCN; Pallas interpret mode is
    # single-process by construction).
    from triton_dist_tpu.ops.allgather_gemm import (
        create_ag_gemm_context, ag_gemm)
    from triton_dist_tpu.ops.gemm_reduce_scatter import (
        create_gemm_rs_context, gemm_rs)

    tdist.initialize_distributed()  # flat 8-way tp across both hosts
    fmesh = tdist.get_mesh()
    m, k, nn = 16, 32, 32
    # Row-graded A (row i is all i) so a misrouted/reordered chunk in
    # the cross-process gather/scatter produces WRONG values, not a
    # coincidental pass (review r5g-1): out[i, :] = i * k.
    a_mat = jnp.broadcast_to(
        jnp.arange(m, dtype=jnp.float32)[:, None], (m, k))

    def check_shards(arr):
        expect = np.broadcast_to(
            (np.arange(arr.shape[0], dtype=np.float32)
             * float(k))[:, None], arr.shape)
        assert arr.addressable_shards, "no local shards"
        for sh in arr.addressable_shards:
            np.testing.assert_allclose(np.asarray(sh.data),
                                       expect[sh.index])

    a_g = jax.device_put(a_mat, NamedSharding(fmesh, P("tp")))
    b_g = jax.device_put(jnp.ones((k, nn), jnp.float32),
                         NamedSharding(fmesh, P(None, "tp")))
    ctx_ag = create_ag_gemm_context(fmesh, "tp")
    check_shards(jax.block_until_ready(
        ag_gemm(a_g, b_g, ctx_ag, impl="xla")))

    a_r = jax.device_put(a_mat, NamedSharding(fmesh, P(None, "tp")))
    b_r = jax.device_put(jnp.ones((k, nn), jnp.float32),
                         NamedSharding(fmesh, P("tp")))
    ctx_rs = create_gemm_rs_context(fmesh, "tp")
    check_shards(jax.block_until_ready(
        gemm_rs(a_r, b_r, ctx_rs, impl="xla")))

    # -- 2. one autotune round: both processes must agree on the winner
    # even though their local timings differ.
    from triton_dist_tpu.tools.autotuner import autotune

    a64 = jnp.ones((64, 64), jnp.float32)
    a512 = jnp.ones((512, 512), jnp.float32)

    def make_fn(n):
        mat = a64 if n == 64 else a512
        f = jax.jit(lambda: (mat @ mat).sum())

        def run():
            return jax.block_until_ready(f())
        return run

    res = autotune(make_fn, [{"n": 512}, {"n": 64}], key="mh_test")
    # Second call must be served from the (agreed) cache.
    res2 = autotune(make_fn, [{"n": 512}, {"n": 64}], key="mh_test")
    assert res2.config == res.config
    print(f"RESULT pid={pid} winner={res.config['n']} psum={float(r[0])}",
          flush=True)


if __name__ == "__main__":
    main()
