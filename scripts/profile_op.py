"""Capture a jax.profiler trace of one fused op vs its XLA golden on
the chip — the evidence backing a perf concession when a world=1
`vs_xla` ratio stays below 1.0.

Usage (on the chip, nothing else running on the host):

    python scripts/profile_op.py ag_gemm [outdir]

Writes a TensorBoard-loadable trace per impl under
``<outdir>/<op>_<impl>/`` (default outdir: ``profiles/``) plus a
one-line JSON summary on stdout. Uses the same shapes as the bench's
headline parts so the trace explains the bench line directly.
"""
from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _mesh():
    import numpy as np
    return Mesh(np.array(jax.devices()[:1]), ("tp",))


def _rand(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape,
                             jnp.float32).astype(jnp.bfloat16)


def make_ag_gemm(mesh):
    from triton_dist_tpu.ops.allgather_gemm import (
        create_ag_gemm_context, ag_gemm)
    m, k, n = 2048, 4096, 4096
    ctx = create_ag_gemm_context(mesh, "tp", interpret=False)
    a = jax.device_put(_rand(0, (m, k)), NamedSharding(mesh, P("tp")))
    b = jax.device_put(_rand(1, (k, n)),
                       NamedSharding(mesh, P(None, "tp")))
    return {impl: (lambda impl=impl: ag_gemm(a, b, ctx, impl=impl))
            for impl in ("pallas", "xla")}


def make_gemm_rs(mesh):
    from triton_dist_tpu.ops.gemm_reduce_scatter import (
        create_gemm_rs_context, gemm_rs)
    m, k, n = 2048, 4096, 4096
    ctx = create_gemm_rs_context(mesh, "tp", interpret=False)
    a = jax.device_put(_rand(0, (m, k)),
                       NamedSharding(mesh, P(None, "tp")))
    b = jax.device_put(_rand(1, (k, n)), NamedSharding(mesh, P("tp")))
    return {impl: (lambda impl=impl: gemm_rs(a, b, ctx, impl=impl))
            for impl in ("pallas", "xla")}


def make_tp_mlp(mesh):
    from triton_dist_tpu.layers.tp_mlp import TPMLP
    mlp = TPMLP(4096, 3072, mesh=mesh, axis="tp", dtype=jnp.bfloat16)
    params = mlp.init(jax.random.PRNGKey(0))
    x = jax.device_put(_rand(1, (2048, 4096)),
                       NamedSharding(mesh, P("tp")))
    return {"pallas": lambda: mlp(params, x, mode="ag_rs"),
            "xla": lambda: mlp(params, x, mode="xla")}


MAKERS = {"ag_gemm": make_ag_gemm, "gemm_rs": make_gemm_rs,
          "tp_mlp": make_tp_mlp}


def main() -> int:
    op = sys.argv[1] if len(sys.argv) > 1 else "ag_gemm"
    outdir = sys.argv[2] if len(sys.argv) > 2 else "profiles"
    fns = MAKERS[op](_mesh())
    summary = {"op": op}
    for impl, fn in fns.items():
        # Warm compile outside the trace.
        jax.block_until_ready(fn())
        path = os.path.join(outdir, f"{op}_{impl}")
        os.makedirs(path, exist_ok=True)
        with jax.profiler.trace(path):
            for _ in range(8):
                out = fn()
            jax.block_until_ready(out)
        summary[impl] = path
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
