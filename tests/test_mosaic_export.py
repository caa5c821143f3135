"""Export-lint CI gate: every smoke case must pass Pallas→Mosaic
lowering + verification for the TPU platform — on this CPU host, no
chip needed.

The interpreter enforces no MXU constraint, so the interpret-mode suite
cannot see Mosaic rejections like multi-batch-dim ``tpu.matmul``;
``jax.export(platforms=('tpu',))`` runs the real lowering and its
verifier without executing anything (tpu_smoke.py --export-lint), and
the lint fails a Pallas case whose lowered program holds no
``tpu_custom_call`` — a case routed or short-circuited away from its
kernel cannot print PASS. An export only lowers: Mosaic's own compile
(VMEM limits, tile alignment) runs in tests/test_chip_compile.py."""

import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


import pytest


@pytest.mark.parametrize("world", [1, 8])
def test_export_lint_all_cases(tmp_path, world):
    """world=1 lints the on-chip smoke variants; world=8 lints the
    multi-device ring/remote-DMA variants (the interpret suite never
    lowers)."""
    r = subprocess.run(
        [sys.executable, str(REPO / "tpu_smoke.py"), "--export-lint",
         "--world", str(world), "--log", str(tmp_path / "lint.log")],
        capture_output=True, text=True, timeout=900, cwd=REPO)
    tail = "\n".join(r.stdout.splitlines()[-45:])
    assert r.returncode == 0, f"export-lint failures:\n{tail}"
    assert ", 0 failing" in r.stdout, tail


def test_export_lint_layer_bench_dims():
    """Decoder-layer compositions of Qwen3-8B/32B (per-chip TP8 slices,
    prefill ag_rs M=2048 + decode gemm_ar M=128) pass the Mosaic
    verifier at their REAL dims — K=5120 and odd
    N-widths never appear in the smoke shapes (round 4)."""
    import os
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import export as jexport
    from jax.sharding import Mesh
    from triton_dist_tpu.layers import TPAttn, precompute_rope_cache
    from triton_dist_tpu.layers.tp_mlp import TPMLP

    os.environ["TDT_FORCE_COMPILED"] = "1"
    try:
        mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
        for tag, h, nq, nkv, d, inter in (
                ("8b", 4096, 4, 1, 128, 1536),
                ("32b", 5120, 8, 1, 128, 3200)):
            attn = TPAttn(h, nq, nkv, d, mesh=mesh, axis="tp",
                          dtype=jnp.bfloat16)
            mlp = TPMLP(h, inter, mesh=mesh, axis="tp",
                        dtype=jnp.bfloat16)
            pa = attn.init(jax.random.PRNGKey(0))
            pm = mlp.init(jax.random.PRNGKey(1))
            rope = precompute_rope_cache(d, 512)
            for phase, b, s, mode in (("prefill", 16, 128, "ag_rs"),
                                      ("decode", 128, 1, "gemm_ar")):
                m = b * s
                pos = (jnp.tile(jnp.arange(s), (b, 1))
                       if phase == "prefill"
                       else jnp.full((b, 1), 256, jnp.int32))
                offset = jnp.int32(0 if phase == "prefill" else 256)
                cache = tuple(
                    jnp.zeros((b, 512, nkv, d), jnp.bfloat16)
                    for _ in range(2))
                x = jnp.zeros((m, h), jnp.bfloat16)

                def f(x, pa=pa, pm=pm, cache=cache, pos=pos,
                      offset=offset, mode=mode, attn=attn, mlp=mlp):
                    a_out, _ = attn(pa, x, pos, rope, cache, offset,
                                    mode=mode)
                    y = x + a_out
                    return y + mlp(pm, y, mode=mode)
                jexport.export(jax.jit(f), platforms=("tpu",))(x)
    finally:
        os.environ.pop("TDT_FORCE_COMPILED", None)
