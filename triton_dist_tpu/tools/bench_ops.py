"""Per-op shape-sweep microbenchmarks.

The reference ends every op test with a perf loop over shapes
(test/nvidia/test_ag_gemm.py:72-197: correctness then `perf_func` +
`group_profile` per (M, N, K)); this is that harness as a standalone
tool. Each case checks correctness against the op's XLA golden first —
a wrong kernel's throughput is meaningless — then times both paths over
chained windows (``runtime.utils.perf_func_chained``). Every shape is
failure-isolated (a VMEM/compile failure emits an error row and the
sweep continues) and rows are written as they finish, so a crash late
in an expensive TPU session cannot discard earlier results.

Usage:
    python -m triton_dist_tpu.tools.bench_ops [--op ag_gemm]
        [--json out.jsonl]

On CPU hosts the sweep runs interpret-mode (tiny shapes, correctness
spot-check of the harness itself); real numbers need the TPU.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np


def _init_mesh():
    import jax
    from jax.sharding import Mesh
    devices = jax.devices()
    return Mesh(np.array(devices), ("tp",)), len(devices)


def _is_tpu():
    from triton_dist_tpu.runtime.platform import is_tpu
    return is_tpu()


def _time(step, x0):
    from triton_dist_tpu.runtime.utils import perf_func_chained
    # CPU interpret-mode exists only to prove the harness runs; keep the
    # chains short there (each step re-runs the Pallas interpreter).
    iters = (8, 24) if _is_tpu() else (1, 3)
    return perf_func_chained(step, x0, iters)


def _emit(row, out):
    out.write(json.dumps(row) + "\n")
    out.flush()


def _sweep_gemm_family(op_name, mesh, world, shapes, out):
    """Shared sweep for the collective-matmul ops: ag_gemm (row-sharded
    A, column-sharded B) and gemm_rs (col-sharded A, row-sharded B).
    The chain fold is CHEAP (scaled slice tiled back to the input
    shape) so the timed step is dominated by the op under test, and the
    (M/w, N) gemm_rs output is tiled back up so `x = step(x)` chains at
    any world size."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from triton_dist_tpu.runtime.utils import assert_allclose

    if op_name == "ag_gemm":
        from triton_dist_tpu.ops.allgather_gemm import (
            ag_gemm as op, create_ag_gemm_context as mk_ctx)
        a_spec, b_spec = P("tp"), P(None, "tp")
    else:
        from triton_dist_tpu.ops.gemm_reduce_scatter import (
            create_gemm_rs_context as mk_ctx, gemm_rs as op)
        a_spec, b_spec = P(None, "tp"), P("tp")

    for (m, k, n) in shapes:
        row = {"op": op_name, "m": m, "k": k, "n": n}
        try:
            ctx = mk_ctx(mesh, "tp")
            a0 = jax.device_put(
                jax.random.normal(jax.random.PRNGKey(0), (m, k),
                                  jnp.float32).astype(jnp.bfloat16),
                NamedSharding(mesh, a_spec))
            b = jax.device_put(
                (jax.random.normal(jax.random.PRNGKey(1), (k, n),
                                   jnp.float32) / 8).astype(jnp.bfloat16),
                NamedSharding(mesh, b_spec))
            assert_allclose(op(a0, b, ctx, impl="pallas"),
                            op(a0, b, ctx, impl="xla"),
                            rtol=3e-2, atol=3e-2)

            def mk(impl):
                @jax.jit
                def step(a):
                    c = op(a, b, ctx, impl=impl)
                    # cheap fold back to (m, k): scaled slice, tiled up
                    sl = (c[:, :k] if c.shape[1] >= k else
                          jnp.tile(c, (1, -(-k // c.shape[1])))[:, :k])
                    reps = -(-m // sl.shape[0])
                    return (jnp.tile(sl, (reps, 1))[:m]
                            * jnp.asarray(2 ** -4, jnp.bfloat16))
                return step

            ms_p, ms_x = _time(mk("pallas"), a0), _time(mk("xla"), a0)
            flops = 2 * m * k * n
            row.update({
                "pallas_ms": round(ms_p, 4), "xla_ms": round(ms_x, 4),
                "tflops_per_chip": round(
                    flops / world / (ms_p * 1e-3) / 1e12, 2),
                "vs_xla": round(ms_x / ms_p, 4)})
        except Exception as e:  # noqa: BLE001 — per-shape isolation
            row["error"] = repr(e)[:200]
        _emit(row, out)


def sweep_ag_gemm(mesh, world, shapes, out):
    _sweep_gemm_family("ag_gemm", mesh, world, shapes, out)


def sweep_gemm_rs(mesh, world, shapes, out):
    _sweep_gemm_family("gemm_rs", mesh, world, shapes, out)


def sweep_flash_decode(mesh, world, shapes, out):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from triton_dist_tpu.ops.flash_decode import (
        create_flash_decode_context, gqa_fwd_batch_decode)
    from triton_dist_tpu.runtime.utils import assert_allclose

    for (b, hq, hkv, d, t) in shapes:
        row = {"op": "flash_decode", "b": b, "hq": hq, "hkv": hkv,
               "d": d, "t": t}
        try:
            ctx = create_flash_decode_context(mesh, "tp", variant="tiled",
                                              t_blk=min(512, t // world))
            q0 = jax.random.normal(jax.random.PRNGKey(0), (b, hq, d),
                                   jnp.float32).astype(jnp.bfloat16)
            sh = NamedSharding(mesh, P(None, "tp"))
            kc = jax.device_put(jax.random.normal(
                jax.random.PRNGKey(1), (b, t, hkv, d), jnp.float32
            ).astype(jnp.bfloat16), sh)
            vc = jax.device_put(jax.random.normal(
                jax.random.PRNGKey(2), (b, t, hkv, d), jnp.float32
            ).astype(jnp.bfloat16), sh)
            n = jnp.int32(t - 1)
            assert_allclose(
                gqa_fwd_batch_decode(q0, kc, vc, n, ctx, impl="pallas"),
                gqa_fwd_batch_decode(q0, kc, vc, n, ctx, impl="xla"),
                rtol=3e-2, atol=3e-2)

            def mk(impl):
                @jax.jit
                def step(q):
                    o = gqa_fwd_batch_decode(q, kc, vc, n, ctx, impl=impl)
                    return (o.astype(jnp.float32) * 0.5 + 0.25
                            ).astype(q.dtype)
                return step

            ms_p, ms_x = _time(mk("pallas"), q0), _time(mk("xla"), q0)
            row.update({"pallas_ms": round(ms_p, 4),
                        "xla_ms": round(ms_x, 4),
                        "vs_xla": round(ms_x / ms_p, 4)})
        except Exception as e:  # noqa: BLE001 — per-shape isolation
            row["error"] = repr(e)[:200]
        _emit(row, out)


SWEEPS = {
    "ag_gemm": (sweep_ag_gemm,
                [(2048, 4096, 4096), (4096, 4096, 4096),
                 (1024, 8192, 4096)],
                [(64, 64, 64)]),
    "gemm_rs": (sweep_gemm_rs,
                [(2048, 4096, 4096), (4096, 4096, 4096)],
                [(64, 64, 64)]),
    "flash_decode": (sweep_flash_decode,
                     [(8, 32, 8, 128, 8192), (1, 32, 8, 128, 32768),
                      (32, 32, 8, 128, 2048)],
                     [(2, 8, 2, 32, 64)]),
}


def main(argv=None):
    # 1-core CPU hosts deadlock interpret-mode semaphore waits unless the
    # affinity shim re-execs us first (runtime/cpu_shim.py; same call
    # every user-style script makes).
    from triton_dist_tpu.runtime.cpu_shim import maybe_reexec_with_shim
    maybe_reexec_with_shim()
    ap = argparse.ArgumentParser()
    ap.add_argument("--op", choices=sorted(SWEEPS) + ["all"],
                    default="all")
    ap.add_argument("--json", default=None,
                    help="append JSON lines here (default stdout)")
    args = ap.parse_args(argv)

    mesh, world = _init_mesh()
    on_tpu = _is_tpu()
    out = open(args.json, "a") if args.json else sys.stdout
    try:
        for name, (fn, tpu_shapes, cpu_shapes) in sorted(SWEEPS.items()):
            if args.op not in ("all", name):
                continue
            fn(mesh, world, tpu_shapes if on_tpu else cpu_shapes, out)
    finally:
        if args.json:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
