"""Real-TPU smoke: compile + run every Pallas op once at world=1.

Every test in the suite forces interpret mode on a CPU mesh, where
Mosaic (the TPU kernel compiler) never sees a kernel. This script runs
each op's ``impl="pallas"`` entry compiled (no interpret) on the chip
with a 1-device mesh, so Mosaic rejections surface as an actionable
list.

World=1 collapses the ring loops (the ``world > 1`` branches are static
Python), so this smokes the local DMA/VMEM/MXU structure of each kernel:
HBM<->VMEM async copies, double-buffered tile pipelines, scratch
semaphores, accumulation, layout constraints. The multi-chip ring
protocol is validated by the interpret-mode suite, and on four real
chips by ``chip_smoke.py --chips 4``.

Usage: ``python tpu_smoke.py [--log tpu_smoke.log]``. Exit code 0 iff
every op compiled and ran; non-zero if any op failed or there is no
TPU. ``--export-lint`` needs no chip: it lowers every case for the TPU
platform on the CPU host and checks the kernel is really in it.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
import traceback


def run_preflight() -> int:
    """Static-analysis preflight (docs/analysis.md): model-check the
    ring protocols and vet every autotune candidate table's VMEM
    footprint — pure Python, before the first Mosaic compile — plus
    the repo contract lints. A finding here stops the queue."""
    from triton_dist_tpu.tools.tdt_check import preflight
    print("== tdt-check preflight ==", flush=True)
    return preflight()


def run_smoke(log_path: str | None = None, only: str | None = None,
              interpret: bool = False, list_only: bool = False,
              skip: str | None = None, export_lint: bool = False,
              world: int = 1, case_timeout: float = 420.0,
              preflight: bool = True) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    # The smoke exists to exercise the FUSED kernels: the resilience
    # router must never silently divert a case to its XLA fallback
    # (a smoke that "passed" on XLA would be worse than one that
    # failed; under FORCE_FUSED the router records infra failures and
    # re-raises instead of falling back). The compile watchdog below
    # still guards every case.
    os.environ.setdefault("TDT_FORCE_FUSED", "1")
    # Arm the router's OWN per-op watchdog below the case deadline so
    # a hang is recorded under the real (op, config, device_kind) key
    # the production router checks — the cross-process protection the
    # known-bad cache promises. The case-level watchdog (below) stays
    # as the backstop for hangs outside any op entry (jit, transfer).
    if not list_only:
        os.environ.setdefault("TDT_COMPILE_TIMEOUT_S",
                              str(max(case_timeout * 0.8, 1.0)))

    # Every smoke run records the event timeline and leaves a merged,
    # validated trace artifact next to the log (docs/observability.md
    # "Tracing") — a smoke hang then comes with its flight record for
    # free (the router auto-dumps on the watchdog trip).
    from triton_dist_tpu import obs as _obs
    from triton_dist_tpu.obs import trace as _trace
    if not list_only:
        _obs.enable()
        _trace.enable()

    if preflight and not list_only:
        rc = run_preflight()
        if rc != 0:
            print("tdt-check preflight FAILED — queue not started "
                  "(--no-preflight overrides)", flush=True)
            return rc

    results: list[tuple[str, str, str]] = []  # (name, status, detail)

    from triton_dist_tpu.runtime.utils import tree_all_finite as _finite

    skips = [s for s in (skip or "").split(",") if s]

    def case(name, fn, kernel=True):
        """Queue one op. ``kernel=False`` marks a case that is an XLA
        composition by design (ring/ulysses schedules): every other
        case must lower to at least one Mosaic ``tpu_custom_call`` —
        a case routed or short-circuited away from its kernel must not
        print PASS."""
        if list_only:
            print(name)
            return
        if any(s == name for s in skips):
            return
        if only:
            # "=name" selects exactly; otherwise substring filter.
            if only.startswith("="):
                if name != only[1:]:
                    return
            elif only not in name:
                return
        from triton_dist_tpu.resilience import (CompileTimeout,
                                                known_bad_cache,
                                                run_with_timeout)
        t0 = time.perf_counter()

        def run_case():
            if export_lint:
                # Lower + serialize the case for the TPU platform on
                # this (CPU) host: runs the Pallas→Mosaic lowering and
                # its VERIFIER, which rejects e.g. multi-batch-dim
                # tpu.matmul — the class the interpret-mode suite cannot
                # see (the interpreter enforces no MXU or layout
                # constraint). No kernel executes, and Mosaic's own
                # compile (VMEM limits, tile alignment) does not run:
                # tests/test_chip_compile.py covers that.
                from jax import export as jexport
                exp = jexport.export(jax.jit(fn), platforms=("tpu",))()
                n_kernels = exp.mlir_module().count("tpu_custom_call")
                if kernel and not n_kernels:
                    raise AssertionError(
                        "lowered program holds no tpu_custom_call: the "
                        "case never reached its Pallas kernel")
                return n_kernels, True
            out = fn()
            jax.block_until_ready(out)
            return out, _finite(out)

        try:
            # Every case runs under the compile watchdog: a Mosaic
            # hang marks THIS case TIMEOUT and the queue advances (a
            # Python thread cannot be killed; the worker is left to
            # finish or hang in the background). The span's un-ended
            # begin event is what a flight record of a hung case shows
            # as "in flight".
            with _trace.span(f"smoke.{name}", "op"):
                out, ok = run_with_timeout(run_case, case_timeout,
                                           op=f"smoke:{name}")
            dt = time.perf_counter() - t0
            results.append((name, "PASS" if ok else "NONFINITE",
                            f"{dt:.1f}s" + (f" kernels={out}"
                                            if export_lint else "")))
        except CompileTimeout as e:
            dt = time.perf_counter() - t0
            known_bad_cache().record(f"smoke:{name}", "case",
                                    dev.device_kind
                                    if hasattr(dev, "device_kind")
                                    else dev.platform,
                                    reason=str(e))
            # e.timeout_s distinguishes the router's inner per-op trip
            # (0.8x, real op key recorded) from the case-level backstop.
            results.append((name, "TIMEOUT",
                            f"{dt:.1f}s abandoned after "
                            f"{e.timeout_s:.0f}s (known-bad recorded; "
                            f"queue advances)"))
        except Exception as e:  # noqa: BLE001 — record and continue
            dt = time.perf_counter() - t0
            tb = traceback.format_exc().strip().splitlines()
            # The exception repr, not tb[-1]: JAX appends its
            # traceback-filter notice as the last line.
            head = f"{type(e).__name__}: {e}".replace("\n", " ")
            results.append((name, "FAIL", f"{dt:.1f}s " + head[:160]))
            if log_path:
                with open(log_path, "a") as f:
                    f.write(f"\n=== {name} ===\n")
                    f.write("\n".join(tb) + "\n")
        print(f"  {results[-1][0]:<28} {results[-1][1]:<9} "
              f"{results[-1][2]}", flush=True)

    # Device-profile capture for the fused-family cases (ISSUE 10,
    # docs/perf.md "Overlap accounting" measured tier): each wrapped
    # case runs under jax.profiler and the capture is parsed back via
    # obs.devprof — the end-of-run PROFILE lines carry measured
    # compute/comm attribution per op, and an unparseable capture
    # fails the run (same contract as the TRACE artifact).
    prof_results: dict[str, dict] = {}

    def profiled(op, fn):
        if list_only or export_lint:
            return fn

        def wrapped():
            from triton_dist_tpu.obs import devprof
            from triton_dist_tpu.tools.profiler import group_profile
            try:
                cm = group_profile(f"smoke_{op.replace('/', '_')}",
                                   devprof.devprof_dir())
                cap = cm.__enter__()
            except Exception as e:  # noqa: BLE001 — still smoke the op
                prof_results[op] = {
                    "error": f"capture failed: {type(e).__name__}: {e}"}
                return fn()
            try:
                out = fn()
                jax.block_until_ready(out)
            finally:
                cm.__exit__(None, None, None)
            try:
                summary = devprof.parse_capture(cap.path)
                devprof.publish(summary)
                prof_results[op] = {"path": cap.path,
                                    "summary": summary}
            except Exception as e:  # noqa: BLE001 — reported, fails the run
                prof_results[op] = {
                    "path": cap.path,
                    "error": f"{type(e).__name__}: {e}"}
            return out
        return wrapped

    from triton_dist_tpu.runtime.compile_cache import (
        configure_compile_cache)
    configure_compile_cache()
    if list_only or export_lint:
        # Name-collection and export-lint run on the CPU backend;
        # export-lint lowers each case FOR the tpu platform without
        # executing it.
        jax.config.update("jax_platforms", "cpu")
        if export_lint:
            os.environ["TDT_FORCE_COMPILED"] = "1"
    devices = jax.devices()
    if not (list_only or export_lint) and devices[0].platform != "tpu":
        raise SystemExit(
            f"tpu_smoke: no TPU (platform {devices[0].platform!r}); "
            f"--export-lint is the mode that needs no chip")
    dev = devices[0]
    if not list_only:
        mode = "EXPORT-LINT (tpu lowering on cpu host)" if export_lint \
            else f"{dev.platform}:{getattr(dev, 'device_kind', '?')}"
        print(f"SMOKE on {mode}", flush=True)
    assert world == 1 or export_lint, (
        "world > 1 is an export-lint mode (multi-device execution is "
        "the interpret suite's and chip_smoke.py --chips 4's job)")
    assert len(devices) >= world, (len(devices), world)
    mesh = Mesh(np.array(devices[:world]), ("tp",))
    key = jax.random.PRNGKey(0)
    bf16 = jnp.bfloat16

    def sharded(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    def randn(shape, dtype=bf16, k=0):
        return jax.random.normal(jax.random.PRNGKey(k), shape, jnp.float32
                                 ).astype(dtype)

    # --- collectives ------------------------------------------------------
    from triton_dist_tpu.ops.allgather import (
        AllGatherMethod, create_allgather_context, all_gather)
    x = sharded(randn((256, 256)), P("tp"))
    for method in (AllGatherMethod.RING_1D, AllGatherMethod.RING_BIDIR,
                   AllGatherMethod.FULL_MESH_PUSH):
        ctx = create_allgather_context(mesh, "tp", method=method,
                                       interpret=interpret)
        case(f"allgather/{method.name.lower()}",
             lambda ctx=ctx: all_gather(x, ctx, impl="pallas"))

    # Latency-class payload: one (16,128) bf16 tile per rank (reference
    # test_ag_small_msg.py / LL-allgather regime).
    xsm = sharded(randn((16, 128)), P("tp"))
    sm_ctx = create_allgather_context(
        mesh, "tp", method=AllGatherMethod.FULL_MESH_PUSH,
        interpret=interpret)
    case("allgather/small_msg",
         lambda: all_gather(xsm, sm_ctx, impl="pallas"))

    from triton_dist_tpu.ops.reduce_scatter import (
        ReduceScatterMethod, create_reduce_scatter_context, reduce_scatter)
    xp = sharded(randn((world, 256, 256)), P("tp"))  # (w, M, N) partials
    for method in (ReduceScatterMethod.RING, ReduceScatterMethod.ONE_SHOT):
        ctx = create_reduce_scatter_context(mesh, "tp", interpret=interpret)
        ctx.method = method
        case(f"reduce_scatter/{method.value}",
             lambda ctx=ctx: reduce_scatter(xp, ctx, impl="pallas"))

    from triton_dist_tpu.ops.allreduce import (
        AllReduceMethod, create_allreduce_context, all_reduce)
    for method in (AllReduceMethod.ONE_SHOT, AllReduceMethod.TWO_SHOT,
                   AllReduceMethod.RECURSIVE_DOUBLING):
        ctx = create_allreduce_context(mesh, "tp", interpret=interpret)
        ctx.method = method
        case(f"allreduce/{method.value}",
             lambda ctx=ctx: all_reduce(xp, ctx, impl="pallas"))

    # --- fused GEMM ops ---------------------------------------------------
    from triton_dist_tpu.ops.allgather_gemm import (
        create_ag_gemm_context, ag_gemm, ag_gemm_multi)
    a = sharded(randn((512, 512)), P("tp"))
    b = sharded(randn((512, 512), k=1), P(None, "tp"))
    for variant in ("vmem", "hbm"):
        ctx = create_ag_gemm_context(mesh, "tp", interpret=interpret)
        ctx.variant = variant
        case(f"ag_gemm/{variant}",
             lambda ctx=ctx: ag_gemm(a, b, ctx, impl="pallas"))
    ctx = create_ag_gemm_context(mesh, "tp", interpret=interpret)
    b2 = sharded(randn((512, 256), k=2), P(None, "tp"))
    case("ag_gemm_multi",
         lambda: ag_gemm_multi(a, [b, b2], ctx, impl="pallas"))

    # Bench-shape hbm cases: 512^2 alone misses a config whose scratch
    # only outgrows the VMEM cap at 2048x4096x4096.
    ab = sharded(randn((2048, 4096)), P("tp"))
    bb = sharded(randn((4096, 4096), k=13), P(None, "tp"))
    bench_ctx = create_ag_gemm_context(mesh, "tp", interpret=interpret)
    case("ag_gemm/bench_shape",
         profiled("ag_gemm",
                  lambda: ag_gemm(ab, bb, bench_ctx, impl="pallas")))
    inj_ctx = create_ag_gemm_context(mesh, "tp", interpret=interpret)
    inj_ctx.for_correctness = True
    inj_ctx.straggler_option = (0, 10000)
    case("ag_gemm/injection",
         lambda: ag_gemm(a, b, inj_ctx, impl="pallas"))

    # Fused AG + dual-GEMM + SwiGLU (the MLP front half as one kernel).
    from triton_dist_tpu.ops.allgather_gemm import ag_swiglu
    sw_ctx = create_ag_gemm_context(mesh, "tp", interpret=interpret)
    case("ag_swiglu/small",
         lambda: ag_swiglu(a, b, b, sw_ctx, impl="pallas"))
    bu = sharded(randn((4096, 4096), k=17), P(None, "tp"))
    sw_bench_ctx = create_ag_gemm_context(mesh, "tp", interpret=interpret)
    case("ag_swiglu/bench_shape",
         profiled("ag_swiglu",
                  lambda: ag_swiglu(ab, bb, bu, sw_bench_ctx,
                                    impl="pallas")))

    from triton_dist_tpu.ops.gemm_reduce_scatter import (
        create_gemm_rs_context, gemm_rs, gemm_ar)
    rs_ctx2 = create_gemm_rs_context(mesh, "tp", interpret=interpret)
    a_rs = sharded(randn((512, 512)), P(None, "tp"))
    b_rs = sharded(randn((512, 512), k=3), P("tp"))
    case("gemm_rs", lambda: gemm_rs(a_rs, b_rs, rs_ctx2, impl="pallas"))
    case("gemm_ar", lambda: gemm_ar(a_rs, b_rs, rs_ctx2, impl="pallas"))
    a_rsb = sharded(randn((2048, 4096)), P(None, "tp"))
    b_rsb = sharded(randn((4096, 4096), k=14), P("tp"))
    case("gemm_rs/bench_shape",
         profiled("gemm_rs",
                  lambda: gemm_rs(a_rsb, b_rsb, rs_ctx2,
                                  impl="pallas")))
    # Decode GEMM-AR at production width via the hbm epilogue path.
    a_ar = sharded(randn((128, 4096)), P(None, "tp"))
    case("gemm_ar/decode_shape",
         profiled("gemm_ar",
                  lambda: gemm_ar(a_ar, b_rsb, rs_ctx2,
                                  impl="pallas")))

    # --- EP / MoE ---------------------------------------------------------
    from triton_dist_tpu.ops.all_to_all import (
        create_all_to_all_context, fast_all_to_all)
    a2a_ctx = create_all_to_all_context(mesh, "tp", interpret=interpret)
    send = sharded(randn((world * world, 128, 256)), P("tp"))
    counts = sharded(jnp.full((world * world,), 64, jnp.int32), P("tp"))
    # The a2a, p2p and EP kernels are pure communication: at world == 1
    # the ops return their input / XLA path, so no kernel exists there.
    case("fast_all_to_all",
         lambda: fast_all_to_all(send, counts, a2a_ctx, impl="pallas")[0],
         kernel=world > 1)

    from triton_dist_tpu.ops.group_gemm import (
        create_ag_group_gemm_context, ag_group_gemm)
    gg_ctx = create_ag_group_gemm_context(mesh, "tp")
    xg = sharded(randn((128, 256)), P("tp"))
    wg = sharded(randn((4, 256, 512), k=4), P(None, None, "tp"))
    eid = sharded(jax.random.randint(key, (128,), 0, 4, jnp.int32), P("tp"))
    case("ag_group_gemm",
         lambda: ag_group_gemm(xg, wg, eid, 4, gg_ctx, impl="ring"),
         kernel=False)
    case("ag_group_gemm/fused",
         lambda: ag_group_gemm(xg, wg, eid, 4, gg_ctx, impl="fused"))

    from triton_dist_tpu.ops.moe_reduce_rs import (
        create_moe_rs_context, moe_reduce_rs)
    t_tok, topk, n_exp, inter, hid = 64, 2, 4, 512, 256
    mrs_ctx = create_moe_rs_context(mesh, "tp", num_experts=n_exp,
                                    topk=topk)
    act = sharded(randn((t_tok * topk, inter)), P(None, "tp"))
    wdown = sharded(randn((n_exp, inter, hid), k=5), P(None, "tp"))
    eid2 = jax.random.randint(key, (t_tok * topk,), 0, n_exp, jnp.int32)
    wts = jax.nn.softmax(randn((t_tok, topk), jnp.float32, k=6))
    case("moe_reduce_rs",
         lambda: moe_reduce_rs(act, wdown, eid2, wts, mrs_ctx,
                               impl="ring"), kernel=False)
    case("moe_reduce_rs/fused",
         lambda: moe_reduce_rs(act, wdown, eid2, wts, mrs_ctx,
                               impl="fused"))

    # --- SP attention -----------------------------------------------------
    from triton_dist_tpu.ops.flash_decode import (
        create_flash_decode_context, gqa_fwd_batch_decode)
    fd_ctx = create_flash_decode_context(mesh, "tp", interpret=interpret)
    bq, hq, hkv, hd, t = 2, 8, 2, 128, 1024
    q = randn((bq, hq, hd))
    kc = sharded(randn((bq, t, hkv, hd), k=7), P(None, "tp"))
    vc = sharded(randn((bq, t, hkv, hd), k=8), P(None, "tp"))
    case("flash_decode",
         lambda: gqa_fwd_batch_decode(q, kc, vc, jnp.int32(t // 2), fd_ctx,
                                      impl="pallas"))

    from triton_dist_tpu.ops.flash_decode import gqa_fwd_batch_decode_paged
    fd_tiled = create_flash_decode_context(mesh, "tp", variant="tiled",
                                           t_blk=256, interpret=interpret)
    case("flash_decode/tiled",
         lambda: gqa_fwd_batch_decode(q, kc, vc, jnp.int32(t // 2),
                                      fd_tiled, impl="pallas"))
    n_pages, page = 4, 256
    # n_pages is PER-DEVICE; pools/tables are per-device slabs sharded
    # on the leading dim (world-parametric for --export-lint --world N).
    pool_k = sharded(randn((world * (bq * n_pages + 2), page, hkv, hd),
                           k=11), P("tp"))
    pool_v = sharded(randn((world * (bq * n_pages + 2), page, hkv, hd),
                           k=12), P("tp"))
    table = sharded(
        jnp.tile(jnp.arange(bq * n_pages, dtype=jnp.int32
                            ).reshape(1, bq, n_pages), (world, 1, 1)),
        P("tp"))
    # The production paged route: table-gather view + the proven dense
    # tiled kernel (paged_variant="gathered", the context default).
    # The former "flash_decode/paged" case — the DIRECT block-table
    # kernel pinned as the compile watchdog's live canary — is RETIRED
    # after wedging two rounds of smoke queues without producing a
    # root cause; docs/resilience.md "Retired canary" has the full
    # rationale. The direct kernel itself remains available as the
    # TDT_PAGED_VARIANT="direct" opt-in, guarded by the known-bad
    # cache like every other config.
    fd_paged_g = create_flash_decode_context(mesh, "tp",
                                             interpret=interpret)
    case("flash_decode/paged_gathered",
         lambda: gqa_fwd_batch_decode_paged(
             q, pool_k, pool_v, table,
             jnp.int32(world * n_pages * page // 2), fd_paged_g))

    # Serving shape: B=8, 32 heads, t=8k.
    def fd_serving():
        bs, hqs, hkvs, ds, ts = 8, 32, 8, 128, 8192
        qv = randn((bs, hqs, ds), k=15)
        kcs = sharded(randn((bs, ts, hkvs, ds), k=16), P(None, "tp"))
        vcs = sharded(randn((bs, ts, hkvs, ds), k=17), P(None, "tp"))
        ctx = create_flash_decode_context(mesh, "tp", variant="tiled",
                                          t_blk=512, interpret=interpret)
        return gqa_fwd_batch_decode(qv, kcs, vcs, jnp.int32(ts - 7), ctx,
                                    impl="pallas")
    case("flash_decode/serving_shape", fd_serving)

    from triton_dist_tpu.ops.sp_attention import (
        create_sp_attention_context, sp_ag_attention)
    sp_ctx = create_sp_attention_context(mesh, "tp", causal=True,
                                         interpret=interpret)
    s = 512
    hkv_sp = max(2, world)          # ulysses needs heads % world == 0
    qs = sharded(randn((2, s, 4 * hkv_sp, 128)), P(None, "tp"))
    ks = sharded(randn((2, s, hkv_sp, 128), k=9), P(None, "tp"))
    vs = sharded(randn((2, s, hkv_sp, 128), k=10), P(None, "tp"))
    for impl in ("ring", "pallas"):
        case(f"sp_ag_attention/{impl}",
             lambda impl=impl: sp_ag_attention(qs, ks, vs, sp_ctx,
                                               impl=impl),
             kernel=impl == "pallas")
    case("sp_ag_attention/ulysses",
         lambda: sp_ag_attention(qs, ks, vs, sp_ctx, impl="ulysses"),
         kernel=False)

    # EP-mode MoE layer end-to-end, world=1-compilable (reference
    # test_ep_moe_inference.py).
    def ep_moe_case():
        from triton_dist_tpu.layers.ep_moe import EPMoE
        layer = EPMoE(256, 512, num_experts=max(4, 2 * world),
                      topk=2, mesh=mesh,
                      axis="tp", dtype=bf16)
        params = layer.init(jax.random.PRNGKey(3))
        xe = sharded(randn((64, 256), k=18), P("tp"))
        return layer(params, xe)
    case("ep_moe", ep_moe_case, kernel=world > 1)

    # --- PP ---------------------------------------------------------------
    from triton_dist_tpu.ops.p2p import create_p2p_context, pp_shift
    pp_ctx = create_p2p_context(mesh, "tp", interpret=interpret)
    xpp = sharded(randn((world, 128, 256)), P("tp"))
    case("pp_shift", lambda: pp_shift(xpp, pp_ctx, impl="pallas"),
         kernel=world > 1)

    # --- layers / models --------------------------------------------------
    from triton_dist_tpu.layers.tp_mlp import TPMLP
    mlp = TPMLP(512, 1024, mesh=mesh, axis="tp", dtype=bf16)
    mlp_p = mlp.init(key)
    xm = sharded(randn((256, 512)), P("tp"))
    for mode in ("ag_rs", "gemm_ar"):
        case(f"tp_mlp/{mode}", lambda mode=mode: mlp(mlp_p, xm, mode=mode))

    def dense_step():
        import __graft_entry__ as ge
        fn, args = ge.entry()
        out, _ = jax.jit(fn)(*args)
        return out
    case("dense_llm_step", dense_step)

    def mega_step():
        from triton_dist_tpu.mega import MegaQwen3
        from triton_dist_tpu.models import DenseLLM, ModelConfig
        from triton_dist_tpu.models.kv_cache import KVCacheManager
        cfg = ModelConfig(hidden_size=128, intermediate_size=256,
                          num_hidden_layers=2,
                          num_attention_heads=max(4, world),
                          num_key_value_heads=max(2, world), head_dim=64,
                          vocab_size=128, max_position_embeddings=32,
                          dtype=bf16)
        model = DenseLLM(cfg, mesh=mesh, axis="tp", impl="pallas")
        params = model.init(key)
        kv = KVCacheManager(cfg.num_hidden_layers, 2, 16,
                            cfg.num_key_value_heads, cfg.head_dim,
                            mesh=mesh, axis="tp", dtype=cfg.dtype)
        mega = MegaQwen3(model, decode_mode="gemm_ar")
        token = jnp.array([[5], [7]], jnp.int32)
        out, _ = mega.step(params, token, kv.init(), 0)
        return out
    case("mega_qwen3", mega_step)

    # Fused kernel nested under an outer DP axis (compiled-mode path the
    # CPU suite cannot cover — tests/test_dp_compose.py docstring).
    def dp_nested():
        # Use a real 2-slice dp axis when the host has >1 device; the
        # 1-chip bench host degenerates to 1x1 (structure-only check).
        nd = len(devices) if len(devices) % 2 == 0 else 1
        shape = (2, nd // 2) if nd >= 2 else (1, 1)
        mesh2 = Mesh(np.array(devices[:max(nd, 1)]).reshape(shape),
                     ("dp", "tp"))
        ctx = create_ag_gemm_context(mesh2, "tp", interpret=interpret)
        ad = jax.device_put(randn((256, 256)),
                            NamedSharding(mesh2, P(("dp", "tp"), None)))
        bd = jax.device_put(randn((256, 256), k=19),
                            NamedSharding(mesh2, P(None, "tp")))
        f = jax.jit(jax.shard_map(
            lambda a, b: ag_gemm(a, b, ctx, impl="pallas"),
            mesh=mesh2, in_specs=(P("dp", None), P(None, None)),
            out_specs=P("dp", None), axis_names={"dp"}, check_vma=False))
        return f(ad, bd)
    case("dp_compose/nested", dp_nested)

    def sp_model_step():
        # Model-level SP (round 3): forward_sp prefill + one flash-
        # decode step over the seq-sharded cache. world=1 on the bench
        # chip; the pallas flash-decode path still compiles.
        from triton_dist_tpu.models import DenseLLM, ModelConfig
        from triton_dist_tpu.models.kv_cache import KVCacheManager
        # (1, world) tp x sp grid: at --export-lint --world N this
        # lints the seq-sharded model path's multi-device lowering
        # (review r3h finding 1: it was pinned to 1 device).
        mesh2 = Mesh(np.array(devices[:world]).reshape(1, world),
                     ("tp", "sp"))
        cfg = ModelConfig(hidden_size=512, intermediate_size=1024,
                          num_hidden_layers=2,
                          num_attention_heads=max(8, world),
                          num_key_value_heads=max(4, world), head_dim=64,
                          vocab_size=2048, max_position_embeddings=512,
                          dtype=bf16)
        model = DenseLLM(cfg, mesh=mesh2, axis="tp", sp_axis="sp",
                         impl="pallas", fwd_mode="sp")
        params = model.init(jax.random.PRNGKey(30))
        kv = KVCacheManager(cfg.num_hidden_layers, 2,
                            cfg.max_position_embeddings,
                            cfg.num_key_value_heads, cfg.head_dim,
                            mesh=mesh2, axis="sp", seq_shard=True,
                            dtype=bf16)
        ids = jax.random.randint(jax.random.PRNGKey(31), (2, 256), 0,
                                 2048, jnp.int32)
        lo, caches = jax.jit(
            lambda p, i, c: model.forward(p, i, c, 0, mode="sp"))(
            params, ids, kv.init())
        dec, _ = jax.jit(
            lambda p, i, c: model.forward(p, i, c, 256, mode="sp"))(
            params, ids[:, :1], caches)
        return lo, dec
    case("sp_model/prefill_decode", sp_model_step)

    def moe_sp_step():
        # Model-level SP MoE (round 3 session 5): seq-sharded forward
        # with the row-local MoE FFN; world=1 on the bench chip — the
        # kernels inside (ring attn, flash decode, ragged_dot) are
        # individually smoked above, this compiles the composition.
        from triton_dist_tpu.models import ModelConfig, Qwen3MoE
        from triton_dist_tpu.models.kv_cache import KVCacheManager
        mesh3 = Mesh(np.array(devices[:1]).reshape(1, 1), ("tp", "sp"))
        cfgm = ModelConfig(hidden_size=512, intermediate_size=0,
                           moe_intermediate_size=512,
                           num_hidden_layers=2, num_attention_heads=8,
                           num_key_value_heads=4, head_dim=64,
                           vocab_size=2048, max_position_embeddings=512,
                           dtype=bf16, num_experts=8,
                           num_experts_per_tok=2)
        mm = Qwen3MoE(cfgm, mesh=mesh3, axis="tp", sp_axis="sp",
                      impl="pallas", fwd_mode="sp")
        pm = mm.init(jax.random.PRNGKey(40))
        kvm = KVCacheManager(cfgm.num_hidden_layers, 2, 512,
                             cfgm.num_key_value_heads, cfgm.head_dim,
                             mesh=mesh3, axis="sp", seq_shard=True,
                             dtype=bf16)
        idsm = jax.random.randint(jax.random.PRNGKey(41), (2, 256), 0,
                                  2048, jnp.int32)
        lo, cachesm = jax.jit(
            lambda p, i, c: mm.forward(p, i, c, 0, mode="sp"))(
            pm, idsm, kvm.init())
        dec, _ = jax.jit(
            lambda p, i, c: mm.forward(p, i, c, 256, mode="sp"))(
            pm, idsm[:, :1], cachesm)
        return lo, dec
    case("moe_sp_model/prefill_decode", moe_sp_step)

    # fp8-wire a2a last among non-risky cases: first-ever int8-payload
    # DMA compile (reference's headline LL-a2a fp8 config).
    def a2a_fp8_case():
        from triton_dist_tpu.ops.all_to_all import fast_all_to_all_fp8
        send8 = sharded(randn((world * world, 128, 256)), P("tp"))
        counts8 = sharded(jnp.full((world * world,), 64, jnp.int32), P("tp"))
        return fast_all_to_all_fp8(send8, counts8, a2a_ctx,
                                   impl="pallas")[0]
    case("fast_all_to_all/fp8", a2a_fp8_case, kernel=world > 1)

    def train_step():
        # Fused-mode training step (round 3): compiles the TRANSPOSE
        # fused kernels in the backward (ops/autodiff.py) on the chip —
        # forward AG-GEMM/GEMM-RS plus their GEMM-RS/AG-GEMM adjoints.
        from triton_dist_tpu.models import (DenseLLM, ModelConfig,
                                            make_train_step)
        cfg = ModelConfig(hidden_size=512, intermediate_size=1024,
                          num_hidden_layers=2,
                          num_attention_heads=max(8, world),
                          num_key_value_heads=max(4, world), head_dim=64,
                          vocab_size=2048, max_position_embeddings=256,
                          dtype=bf16)
        model = DenseLLM(cfg, mesh=mesh, axis="tp", impl="pallas",
                         fwd_mode="ag_rs")
        params = model.init(jax.random.PRNGKey(32))
        step, init_opt = make_train_step(model, mode="ag_rs")
        batch = {"input_ids": jax.random.randint(
            jax.random.PRNGKey(33), (2, 128), 0, 2048, jnp.int32)}
        _, _, metrics = step(params, init_opt(params), batch)
        return metrics
    case("train/fused_step", train_step)

    # --- report -----------------------------------------------------------
    if list_only:
        return 0
    n_fail = sum(1 for _, st, _ in results if st != "PASS")
    width = max(len(n) for n, _, _ in results) if results else 1
    lines = [f"{n:<{width}}  {st:<9} {d}" for n, st, d in results]
    # The merged trace artifact: every host's events gathered rank-0
    # style, written next to the log, schema-validated — so each smoke
    # run ends with a Perfetto-loadable timeline of what it did.
    # Single-exact-case runs get the case name in the path so
    # per-case artifacts of one --log don't clobber each other.
    try:
        from triton_dist_tpu.tools import trace_export as _texp
        suffix = ""
        if only and only.startswith("="):
            suffix = "." + only[1:].replace("/", "_")
        trace_path = ((log_path or "tpu_smoke.log") + suffix
                      + ".trace.json")
        chrome = _texp.gather_to_chrome(process_name="tpu_smoke")
        _texp.write_trace(chrome, trace_path)
        errors, warns = _texp.validate(chrome)
        lines.append(
            f"TRACE {trace_path} "
            f"({len(chrome['traceEvents'])} events, "
            f"{len(warns)} in-flight) "
            + ("valid" if not errors
               else f"INVALID: {'; '.join(errors[:3])}"))
        if errors:
            n_fail += 1
    except Exception as e:  # noqa: BLE001 — the artifact must not fail the run
        lines.append(f"TRACE export failed: {type(e).__name__}: {e}")
    # Measured device-time attribution per fused-family op (parsed
    # back from the per-case jax.profiler captures). An unparseable
    # capture IS a failure: the next chip window's overlap numbers
    # must be machine-recorded, not eyeballed (ROADMAP item 5).
    for op in sorted(prof_results):
        rec = prof_results[op]
        if "error" in rec or "summary" not in rec:
            lines.append(f"PROFILE {op} INVALID "
                         f"{rec.get('error', 'no summary')} "
                         f"({rec.get('path', '-')})")
            n_fail += 1
            continue
        m = rec["summary"].get("ops", {}).get(op)
        if m is None:
            lines.append(
                f"PROFILE {op} UNATTRIBUTED (no device.{op} label in "
                f"window — see tdt-check annotation-coverage) "
                f"({rec['path']})")
            n_fail += 1
            continue
        ov = (f"overlap_measured {m['overlap_pct']}%"
              if m["overlap_pct"] is not None
              else "overlap_requires_chip (no comm in window)")
        lines.append(f"PROFILE {op} compute {m['compute_ms']} ms "
                     f"comm {m['comm_ms']} ms {ov} ({rec['path']})")
    lines.append(f"TOTAL {len(results)} ops, {n_fail} failing")
    report = "\n".join(lines)
    print(report)
    if log_path:
        with open(log_path, "a") as f:
            f.write(report + "\n")
    return 1 if n_fail else 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--log", default="tpu_smoke.log")
    ap.add_argument("--only", default=None,
                    help="substring filter on case names (=name exact)")
    ap.add_argument("--list", action="store_true",
                    help="print case names (CPU; no kernels run)")
    ap.add_argument("--case-timeout", type=float, default=420.0,
                    help="per-case compile-watchdog budget (seconds); a "
                         "trip marks the case TIMEOUT, records it in "
                         "the known-bad cache, and the queue advances")
    ap.add_argument("--skip", default=None,
                    help="comma-separated exact case names to exclude")
    ap.add_argument("--export-lint", action="store_true",
                    help="lower every case for the TPU platform on this "
                         "host (Pallas/Mosaic verifier, no execution; "
                         "works without a chip)")
    ap.add_argument("--world", type=int, default=1,
                    help="mesh size for --export-lint: verifies the "
                         "world-N ring/remote-DMA variants' Mosaic "
                         "lowering (world>1 never executes)")
    ap.add_argument("--no-preflight", action="store_true",
                    help="skip the tdt-check static-analysis preflight "
                         "(docs/analysis.md)")
    args = ap.parse_args()
    if args.world != 1:
        # Early, clear validation: the smoke shapes divide by powers of
        # two up to 8; anything else produces a wall of shape-assert
        # FAILs that read like lint regressions.
        assert args.export_lint, "--world N>1 requires --export-lint"
        assert args.world in (2, 4, 8), (
            f"--world {args.world}: smoke shapes support 2/4/8")
    if args.list:
        sys.exit(run_smoke(None, None, list_only=True))
    with open(args.log, "w") as f:
        f.write(f"tpu_smoke @ {time.strftime('%Y-%m-%d %H:%M:%S')}\n")
    if args.world > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags +
                f" --xla_force_host_platform_device_count={args.world}"
            ).strip()
    sys.exit(run_smoke(args.log, args.only, skip=args.skip,
                       export_lint=args.export_lint, world=args.world,
                       case_timeout=args.case_timeout,
                       preflight=not args.no_preflight))
