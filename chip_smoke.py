"""The quickest proof that the system still starts on the chip.

Default (one chip, one process): serve the full published Qwen3-0.6B
(28 layers, hidden 1024, vocab 151936, bf16, random weights from
``--seed``) through the entry points a user calls — ``AutoLLM.build`` →
``Engine`` → ``ModelServer`` → ``ChatClient`` — on the two engine families
the serving features sit on: (a) the default dense engine
(``prefill_mode="xla_ar"``, ``decode_mode="gemm_ar"``) and (b) the paged
sequence-parallel engine with the prefix cache on. Each answers a few
concurrent requests; logits of one prefill and one decode step are
compared with the same params under ``impl="xla"``.

``--chips 4`` runs ONLY the four-chip phase: the world-4 collectives
against their ``impl="xla"`` goldens, then the full Qwen3-8B at ``tp=4``
served through the same ``ModelServer``.

It fails (non-zero exit, no ``"ok": true``) when the platform is not
``tpu``, when any phase raises, when any op on the path was served by its
fallback, when a Pallas call would run interpreted, or when a comparison
fails. One JSON object per line; the last line on success is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.

``--size tiny`` rehearses the control flow on the CPU (a toy model, Pallas
interpret mode). It can never end in ``"ok": true``: the result line is
printed only for a TPU.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import faulthandler
import gc
import json
import os
import sys
import threading
import time
import traceback

REL_TOL = 5e-2
"""Largest |pallas - xla| logit difference allowed, relative to the
largest |xla| logit. bf16 keeps 8 bits (eps 7.8e-3); the two
implementations round partial sums at different points (a ring adds
bf16 partials hop by hop, psum does not), and the difference grows with
depth about as sqrt(layers): a CPU interpret-mode run of a 36-layer
tp=4 model gave 0.025. A wrong kernel is wrong in every layer: O(1)."""

OP_TOL = 2e-2
"""The same measure for one collective against its golden: one or two
bf16 roundings of a sum of four partials."""


class SmokeFailure(Exception):
    """A check of this script failed; the run ends non-zero."""


def emit(**obj) -> None:
    print(json.dumps(obj, default=str), flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def fail(reason: str, code: int = 1):
    """End the run now. ``os._exit``: a pump thread or a device teardown
    stuck behind a hung kernel must not keep a failed run alive."""
    emit(event="failed", reason=reason)
    sys.stderr.flush()
    os._exit(code)


class Deadline:
    """Ends the process when a step overruns. A kernel that waits on a
    semaphore nobody signals neither raises nor returns, and on four
    chips every second of a hang is charged four times."""

    def __init__(self, total_s: float):
        self._lock = threading.Lock()
        self._total_end = time.monotonic() + total_s
        self._end, self._what = self._total_end, "the whole run"
        threading.Thread(target=self._watch, daemon=True).start()

    def _watch(self):
        while True:
            time.sleep(1.0)
            with self._lock:
                end, what = self._end, self._what
            if time.monotonic() > end:
                faulthandler.dump_traceback(file=sys.stderr)
                fail(f"deadline: {what} did not finish in time", code=2)

    @contextlib.contextmanager
    def within(self, seconds: float, what: str):
        with self._lock:
            prev = self._end, self._what
            self._end = min(time.monotonic() + seconds, self._total_end)
            self._what = what
        try:
            yield
        finally:
            with self._lock:
                self._end, self._what = prev


# ---------------------------------------------------------------------------
# Observation: compiles, cache, counters, memory.
# ---------------------------------------------------------------------------

class CompileWatch:
    """Counts backend compiles (seconds and number) and persistent-cache
    hits through jax.monitoring — the split of a phase's wall time into
    compile and steady comes from here, not from subtraction."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_writes = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def mark(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits,
                "cache_writes": self.cache_writes}

    def since(self, mark: dict) -> dict:
        now = self.mark()
        return {k: round(now[k] - mark[k], 3) for k in now}


def cache_entries(path: str) -> int:
    """Number of files the compile-cache directory holds (0 if absent)."""
    try:
        return sum(1 for e in os.scandir(path) if e.is_file())
    except FileNotFoundError:
        return 0


def resilience_counters() -> dict:
    """The router's counters that say what really ran: fused calls,
    fallbacks and their reasons, watchdog trips."""
    from triton_dist_tpu import obs
    counters = obs.snapshot().get("counters", {})
    keep = ("fused_total", "fallbacks_total", ".fallback.", "watchdog")
    return {k: v for k, v in sorted(counters.items())
            if k.startswith("resilience.") and any(s in k for s in keep)}


def check_no_fallback(delta: dict, phase: str) -> int:
    """No op of the phase was served by its fallback; returns how many
    fused kernels were counted."""
    bad = {k: v for k, v in delta.items()
           if "fallback" in k or "watchdog" in k}
    check(not bad, f"{phase}: ops left their fused path: {bad}")
    fused = sum(v for k, v in delta.items() if k.endswith(".fused_total"))
    check(fused > 0, f"{phase}: no fused kernel was counted")
    return int(fused)


class Phase:
    """One phase's bookkeeping: what the router counted and how long it
    took, from construction to :meth:`end`."""

    def __init__(self, tag: str, devices):
        self.tag, self.devices = tag, devices
        self.t0 = time.perf_counter()
        self.before = resilience_counters()

    def end(self, **fields) -> None:
        after = resilience_counters()
        delta = {k: v - self.before.get(k, 0) for k, v in after.items()
                 if v != self.before.get(k, 0)}
        emit(event="phase", phase=self.tag,
             fused_kernels_counted=check_no_fallback(delta, self.tag),
             resilience=delta,
             wall_s=round(time.perf_counter() - self.t0, 3),
             memory=memory(self.devices), **fields)


def engine_fields(eng) -> dict:
    return {"prefill_mode": eng.prefill_mode, "decode_mode": eng.decode_mode,
            "decode_path": eng.decode_path}


def emit_model(name: str, cfg, seed: int, **fields) -> None:
    import numpy as np
    emit(event="model", name=name, layers=cfg.num_hidden_layers,
         hidden=cfg.hidden_size, heads=cfg.num_attention_heads,
         kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
         vocab=cfg.vocab_size, dtype=str(np.dtype(cfg.dtype)), seed=seed,
         **fields)


def memory(devices) -> list:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append({"id": d.id,
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return out


def native_libraries() -> dict:
    from triton_dist_tpu.mega import native as sched_native
    from triton_dist_tpu.models import kv_native
    from triton_dist_tpu.ops import moe_utils
    from triton_dist_tpu.tools import data as data_native
    return {"libtdtsched": sched_native.have_native(),
            "libtdtkv": kv_native.have_native(),
            "libtdtdata": data_native.have_native(),
            "libtdtmoe": moe_utils._moe_native() is not None}


# ---------------------------------------------------------------------------
# Comparison with impl="xla".
# ---------------------------------------------------------------------------

def rel_max_diff(name: str, got, ref) -> tuple[float, float]:
    """max|got - ref| over max|ref| (and that scale), after checking
    shapes and finiteness."""
    import numpy as np
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    check(got.shape == ref.shape, f"{name}: shape {got.shape} vs {ref.shape}")
    check(np.isfinite(got).all() and np.isfinite(ref).all(),
          f"{name}: non-finite values")
    scale = float(np.abs(ref).max())
    return float(np.abs(got - ref).max()) / max(scale, 1e-30), scale


def logits_agree(name: str, got, ref, vocab: int) -> dict:
    check(got.shape[-1] == vocab, f"{name}: {got.shape[-1]} logits, "
                                  f"vocabulary {vocab}")
    rel, scale = rel_max_diff(name, got, ref)
    check(rel <= REL_TOL,
          f"{name}: pallas vs xla logits differ by {rel:.4g} of the "
          f"largest logit (tolerance {REL_TOL})")
    agree = (got.argmax(-1) == ref.argmax(-1)).mean()
    return {"rel_max_diff": rel, "ref_abs_max": scale,
            "argmax_agree": float(agree)}


def compare_with_xla(tag, model_p, model_x, params, make_caches, *,
                     prefill_mode, decode_mode, batch, seq, table=None):
    """One prefill and one per-row decode step of the full-depth model
    under impl="pallas" and impl="xla": same params, same inputs."""
    import jax
    import jax.numpy as jnp
    vocab = model_p.config.vocab_size
    ids = jax.random.randint(jax.random.PRNGKey(7), (batch, seq), 0, vocab,
                             jnp.int32)
    tok = jax.random.randint(jax.random.PRNGKey(8), (batch, 1), 0, vocab,
                             jnp.int32)
    offsets = jnp.full((batch,), seq, jnp.int32)
    kw = {} if table is None else {"block_table": table}
    out = {}
    for impl, model in (("pallas", model_p), ("xla", model_x)):
        @jax.jit
        def prefill(p, i, c):
            logits, c = model.forward(p, i, c, 0, mode=prefill_mode, **kw)
            return logits[:, -1], c

        @jax.jit
        def decode(p, t, c, o):
            logits, _ = model.forward(p, t, c, o, mode=decode_mode, **kw)
            return logits[:, -1]

        last, caches = prefill(params, ids, make_caches())
        out[impl] = (last, decode(params, tok, caches, offsets))
        del caches, prefill, decode
    res = {"prefill": logits_agree(f"{tag} prefill", out["pallas"][0],
                                   out["xla"][0], vocab),
           "decode": logits_agree(f"{tag} decode", out["pallas"][1],
                                  out["xla"][1], vocab)}
    emit(event="compare", phase=tag, tolerance=REL_TOL, **res)


# ---------------------------------------------------------------------------
# Serving through ModelServer + the real client.
# ---------------------------------------------------------------------------

def make_prompts(lengths, vocab: int, seed: int, shared_preamble: int = 0):
    """Token-id prompts of the given lengths from ``seed``; with
    ``shared_preamble`` the first two share that many leading tokens."""
    import numpy as np
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, vocab, n).tolist() for n in lengths]
    if shared_preamble:
        prompts[1][:shared_preamble] = prompts[0][:shared_preamble]
    return prompts


def serve_round(srv, prompts, gen_lens, timeout: float):
    """All requests at once, one connection each; returns the generated
    token lists in request order."""
    from triton_dist_tpu.serving.client import fanout
    reqs = [{"prompt_ids": [p], "gen_len": g}
            for p, g in zip(prompts, gen_lens)]
    t0 = time.perf_counter()
    resps = fanout(srv.host, srv.port, reqs, timeout=timeout)
    wall = time.perf_counter() - t0
    for i, r in enumerate(resps):
        check("tokens" in r, f"request {i} failed: {r}")
    return [r["tokens"][0] for r in resps], wall


def serve_phase(tag, eng, params, prompts, gen_lens, repeat_idx, vocab,
                watch, timeout: float, same_path=None):
    """Start a ModelServer on ``eng`` and answer the requests in rounds
    until a round compiles nothing (the first compiles every program; a
    prefix-cached engine compiles its suffix programs in the second),
    then repeat one request alone, and check every token.

    ``same_path``: the requests that run the same programs in every
    round, and so must give the same tokens every time (all of them by
    default; with a prefix cache, a later round prefills only a suffix —
    other programs, other rounding, and random weights make greedy argmax
    a coin-flip between near ties)."""
    from triton_dist_tpu.serving import ModelServer
    from triton_dist_tpu.serving.client import ChatClient
    if same_path is None:
        same_path = range(len(prompts))
    srv = ModelServer(eng, params, port=0).start()
    rounds, answers = [], []
    try:
        client = ChatClient(srv.host, srv.port, timeout=timeout)
        try:
            while len(rounds) < 4:
                mark = watch.mark()
                toks, wall = serve_round(srv, prompts, gen_lens, timeout)
                counters = client.request(
                    {"cmd": "metrics"})["metrics"].get("counters", {})
                rounds.append({
                    "wall_s": round(wall, 3), **watch.since(mark),
                    "prefix_hit_blocks":
                        counters.get("serving.prefix_hit_blocks", 0)})
                answers.append(toks)
                if len(rounds) > 1 and not rounds[-1]["compiles"]:
                    break
            again = client.generate_ids(
                [prompts[repeat_idx]], gen_len=gen_lens[repeat_idx])
            counters = client.request(
                {"cmd": "metrics"})["metrics"].get("counters", {})
        finally:
            client.close()
    finally:
        srv.stop()
    check(not rounds[-1]["compiles"],
          f"{tag}: still compiling in round {len(rounds)}: {rounds}")
    check("tokens" in again, f"{tag}: repeated request failed: {again}")
    for toks in answers:
        for i, (row, g) in enumerate(zip(toks, gen_lens)):
            check(len(row) == g,
                  f"{tag}: request {i} gave {len(row)} tokens, asked {g}")
            check(all(0 <= t < vocab for t in row),
                  f"{tag}: request {i} gave a token outside the vocabulary")
    for i in same_path:
        check(all(toks[i] == answers[0][i] for toks in answers),
              f"{tag}: request {i} gave other tokens in a later round")
    check(repeat_idx in same_path
          and again["tokens"][0] == answers[0][repeat_idx],
          f"{tag}: request {repeat_idx} repeated alone gave other tokens")
    check(not counters.get("serving.pump_errors")
          and not counters.get("serving.admit_errors"),
          f"{tag}: the scheduler recorded errors")
    n_tok = sum(gen_lens)
    emit(event="served", phase=tag, requests=len(prompts),
         prompt_lens=[len(p) for p in prompts], gen_lens=gen_lens,
         tokens_per_round=n_tok, rounds=rounds,
         compile_s=round(sum(r["compile_s"] for r in rounds), 3),
         steady_round_s=rounds[-1]["wall_s"],
         steady_tokens_per_s=round(n_tok / rounds[-1]["wall_s"], 2),
         repeat_checked=sorted(same_path),
         decode_path={k: v for k, v in counters.items()
                      if k.startswith("engine.decode_path.")},
         admitted=counters.get("serving.admitted"),
         retired=counters.get("serving.retired"))
    return rounds


# ---------------------------------------------------------------------------
# Sizes.
# ---------------------------------------------------------------------------

def sizes(name: str) -> dict:
    from triton_dist_tpu.models import ModelConfig, presets
    import jax.numpy as jnp
    if name == "full":
        return {
            "one_chip_model": presets.qwen3_0_6b(),
            "four_chip_model": presets.qwen3_8b(),
            "batch": 8, "max_seq": 4096, "page": 16,
            # Three admission buckets (32, 128, 512) and two generation
            # lengths: 12 requests over 8 rows, so the scheduler admits
            # into rows freed mid-decode.
            "prompt_lens": [20, 30, 100, 120, 300, 400, 25, 110, 90, 350,
                            12, 28],
            "gen_lens": [32, 16] * 6,
            "shared_preamble": 64, "repeat_idx": 10,
            "compare_batch": 8, "compare_seq": 128, "compare_cache": 256,
            "tp4_prompt_lens": [20, 30, 100, 120, 25, 110, 90, 100, 12, 28],
            "tp4_gen_lens": [32, 16] * 5,
            "op_m": 512, "op_n": 4096, "op_k": 4096,
        }
    tiny = ModelConfig(hidden_size=128, intermediate_size=256,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=4, head_dim=32, vocab_size=512,
                       max_position_embeddings=256, dtype=jnp.bfloat16)
    return {
        "one_chip_model": tiny,
        "four_chip_model": dataclasses.replace(tiny, num_hidden_layers=1),
        "batch": 2, "max_seq": 128, "page": 16,
        "prompt_lens": [20, 40, 9, 12], "gen_lens": [4, 2, 4, 2],
        "shared_preamble": 16, "repeat_idx": 2,
        "compare_batch": 2, "compare_seq": 32, "compare_cache": 64,
        "tp4_prompt_lens": [20, 9, 40], "tp4_gen_lens": [2, 3, 2],
        "op_m": 64, "op_n": 256, "op_k": 256,
    }


# ---------------------------------------------------------------------------
# One chip: the two engine families.
# ---------------------------------------------------------------------------

def run_one_chip(sz, seed, devices, watch, timeout):
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from triton_dist_tpu.models import AutoLLM, DenseLLM, Engine
    from triton_dist_tpu.models.kv_cache import (KVCacheManager,
                                                 PagedKVCacheManager)

    cfg = sz["one_chip_model"]
    emit_model("one_chip", cfg, seed)
    dev = np.array(devices[:1])

    # -- (a) the default dense engine ------------------------------------
    phase = Phase("dense", devices[:1])
    mesh = Mesh(dev, ("tp",))
    model = AutoLLM.build(cfg, mesh=mesh)          # impl="pallas"
    check(model.attn.impl == model.mlp.impl == "pallas",
          "AutoLLM.build no longer defaults to impl='pallas'")
    params = model.init(jax.random.PRNGKey(seed))
    model_x = AutoLLM.build(cfg, mesh=mesh, impl="xla")
    cb, cs, ct = sz["compare_batch"], sz["compare_seq"], sz["compare_cache"]
    compare_with_xla(
        "dense", model, model_x, params,
        KVCacheManager(cfg.num_hidden_layers, cb, ct,
                       cfg.num_key_value_heads, cfg.head_dim, mesh=mesh,
                       axis="tp", dtype=cfg.dtype).init,
        prefill_mode="xla_ar", decode_mode="gemm_ar", batch=cb, seq=cs)
    eng = Engine(model, batch=sz["batch"], max_seq=sz["max_seq"])
    check((eng.prefill_mode, eng.decode_mode) == ("xla_ar", "gemm_ar"),
          "the default engine modes changed")
    prompts = make_prompts(sz["prompt_lens"], cfg.vocab_size, seed)
    serve_phase("dense", eng, params, prompts, sz["gen_lens"],
                sz["repeat_idx"], cfg.vocab_size, watch, timeout)
    phase.end(
        engine="Engine(batch, max_seq)", **engine_fields(eng),
        prefill_attention="xla (TPAttn._attention_core, no Pallas kernel)",
        decode_attention="xla (TPAttn._attention_core, no Pallas kernel)")
    del eng, model, model_x
    gc.collect()
    emit(event="released", what="dense engine", memory=memory(devices[:1]))

    # -- (b) the paged sequence-parallel engine ---------------------------
    phase = Phase("paged_sp", devices[:1])
    mesh2 = Mesh(dev.reshape(1, 1), ("tp", "sp"))
    model = DenseLLM(cfg, mesh=mesh2, axis="tp", sp_axis="sp",
                     impl="pallas", fwd_mode="sp")
    model_x = DenseLLM(cfg, mesh=mesh2, axis="tp", sp_axis="sp",
                       impl="xla", fwd_mode="sp")
    params = model.shard_params(params)            # same weights, mesh (b)
    kv = PagedKVCacheManager(cfg.num_hidden_layers, cb, sz["page"],
                             ct // sz["page"], cfg.num_key_value_heads,
                             cfg.head_dim, mesh=mesh2, axis="sp",
                             dtype=cfg.dtype)
    kv.alloc_many(range(cb))
    compare_with_xla("paged_sp", model, model_x, params, kv.init,
                     prefill_mode="sp", decode_mode="sp", batch=cb, seq=cs,
                     table=kv.block_table())
    del kv
    eng = Engine(model, batch=sz["batch"], max_seq=sz["max_seq"],
                 prefill_mode="sp", decode_mode="sp", paged=True,
                 page_size=sz["page"], prefix_cache=True)
    prompts = make_prompts(sz["prompt_lens"], cfg.vocab_size, seed + 1,
                           shared_preamble=sz["shared_preamble"])
    # Shorter than a page: never cached, so every round prefills it whole.
    cold = [i for i, p in enumerate(prompts) if len(p) < sz["page"]]
    rounds = serve_phase("paged_sp", eng, params, prompts, sz["gen_lens"],
                         sz["repeat_idx"], cfg.vocab_size, watch, timeout,
                         same_path=cold)
    check(rounds[0]["prefix_hit_blocks"] > 0,
          "paged_sp: the shared preamble gave no prefix-cache hit")
    phase.end(
        engine="Engine(paged=True, prefix_cache=True)", **engine_fields(eng),
        prefill_attention=f"sp_ag_attention impl={model.sp_impl!r} "
                          "(ppermute schedule, no Pallas kernel)",
        decode_attention=f"gqa_fwd_batch_decode_paged impl="
                         f"{model.fd_impl!r} variant="
                         f"{model.fd_ctx.paged_variant!r} (tiled kernel)")


# ---------------------------------------------------------------------------
# Four chips: collectives against their goldens, then Qwen3-8B at tp=4.
# ---------------------------------------------------------------------------

def run_collectives(mesh, sz, deadline):
    """Every world-4 collective of the library against its impl="xla"
    golden on the same inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from triton_dist_tpu.ops.all_to_all import (create_all_to_all_context,
                                                fast_all_to_all)
    from triton_dist_tpu.ops.allgather import (AllGatherMethod, all_gather,
                                               create_allgather_context)
    from triton_dist_tpu.ops.allgather_gemm import (ag_gemm,
                                                    create_ag_gemm_context)
    from triton_dist_tpu.ops.allreduce import (AllReduceMethod, all_reduce,
                                               create_allreduce_context)
    from triton_dist_tpu.ops.flash_decode import (
        create_flash_decode_context, gqa_fwd_batch_decode)
    from triton_dist_tpu.ops.gemm_reduce_scatter import (
        create_gemm_rs_context, gemm_ar, gemm_rs)
    from triton_dist_tpu.ops.reduce_scatter import (
        ReduceScatterMethod, create_reduce_scatter_context, reduce_scatter)

    w = mesh.shape["tp"]
    m, n, k = sz["op_m"], sz["op_n"], sz["op_k"]
    bf16 = jnp.bfloat16

    def rand(shape, spec, key, dtype=bf16, scale=1.0):
        x = (jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)
             * scale).astype(dtype)
        return jax.device_put(x, NamedSharding(mesh, spec))

    results = []

    def case(name, fn, *args, exact=False):
        """``fn(impl, *args)`` under jit for pallas and xla; compare."""
        t0 = time.perf_counter()
        with deadline.within(180.0, f"collective {name}"):
            got, ref = (jax.block_until_ready(
                jax.jit(lambda *a, impl=impl: fn(impl, *a))(*args))
                for impl in ("pallas", "xla"))
        worst = max(rel_max_diff(name, g, r)[0]
                    for g, r in zip(jax.tree_util.tree_leaves(got),
                                    jax.tree_util.tree_leaves(ref)))
        tol = 0.0 if exact else OP_TOL
        check(worst <= tol, f"{name}: differs from its xla golden by "
                            f"{worst:.4g} (tolerance {tol})")
        results.append({"op": name, "rel_max_diff": worst,
                        "s": round(time.perf_counter() - t0, 2)})

    x = rand((m, n), P("tp"), 1)
    for method in (AllGatherMethod.RING_1D, AllGatherMethod.RING_BIDIR,
                   AllGatherMethod.FULL_MESH_PUSH):
        ctx = create_allgather_context(mesh, "tp", method=method)
        case(f"all_gather/{method.name.lower()}",
             lambda impl, x, ctx=ctx: all_gather(x, ctx, impl=impl),
             x, exact=True)

    parts = rand((w, m, n), P("tp"), 2)
    for method in (ReduceScatterMethod.RING, ReduceScatterMethod.ONE_SHOT):
        ctx = create_reduce_scatter_context(mesh, "tp")
        ctx.method = method
        case(f"reduce_scatter/{method.value}",
             lambda impl, x, ctx=ctx: reduce_scatter(x, ctx, impl=impl),
             parts)
    small = rand((w, 8, n), P("tp"), 3)            # the decode batch
    for method in (AllReduceMethod.ONE_SHOT, AllReduceMethod.TWO_SHOT):
        ctx = create_allreduce_context(mesh, "tp", method=method)
        for label, buf in (("m8", small), (f"m{m}", parts)):
            case(f"all_reduce/{method.value}/{label}",
                 lambda impl, x, ctx=ctx: all_reduce(x, ctx, impl=impl),
                 buf)

    cap = 128
    a2a = create_all_to_all_context(mesh, "tp", capacity=cap)
    send = rand((w * w, cap, 256), P("tp"), 4)
    counts = jax.device_put(jnp.full((w * w,), cap, jnp.int32),
                            NamedSharding(mesh, P("tp")))
    case("fast_all_to_all",
         lambda impl, s, c: fast_all_to_all(s, c, a2a, impl=impl),
         send, counts, exact=True)

    ag = create_ag_gemm_context(mesh, "tp")
    case("ag_gemm", lambda impl, a, b: ag_gemm(a, b, ag, impl=impl),
         rand((m, k), P("tp"), 5, scale=k ** -0.5),
         rand((k, n), P(None, "tp"), 6))
    rs = create_gemm_rs_context(mesh, "tp")
    b_rows = rand((k, n), P("tp"), 8)
    case("gemm_rs", lambda impl, a, b: gemm_rs(a, b, rs, impl=impl),
         rand((m, k), P(None, "tp"), 7, scale=k ** -0.5), b_rows)
    case("gemm_ar/m8", lambda impl, a, b: gemm_ar(a, b, rs, impl=impl),
         rand((8, k), P(None, "tp"), 9, scale=k ** -0.5), b_rows)

    bq, hq, hkv, d, t = 8, 32, 8, 128, 8 * m
    fd = create_flash_decode_context(mesh, "tp", variant="tiled")
    case("gqa_fwd_batch_decode/kv_split",
         lambda impl, q, kc, vc, n: gqa_fwd_batch_decode(q, kc, vc, n, fd,
                                                         impl=impl),
         rand((bq, hq, d), P(), 10),
         rand((bq, t, hkv, d), P(None, "tp"), 11),
         rand((bq, t, hkv, d), P(None, "tp"), 12),
         jnp.asarray([t - 7 * i for i in range(bq)], jnp.int32))
    emit(event="collectives", world=w, cases=results)


def run_four_chips(sz, seed, devices, watch, timeout, deadline):
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from triton_dist_tpu.models import AutoLLM, Engine
    from triton_dist_tpu.models.kv_cache import KVCacheManager
    from triton_dist_tpu.runtime.topology import topology_aware_grid

    check(len(devices) == 4, f"--chips 4 found {len(devices)} devices")
    ring = topology_aware_grid(np.array(devices), (4,))
    emit(event="mesh", axis="tp",
         ring=[{"id": d.id, "coords": getattr(d, "coords", None)}
               for d in ring])
    mesh = Mesh(ring, ("tp",))

    phase = Phase("collectives", devices)
    run_collectives(mesh, sz, deadline)
    phase.end()

    phase = Phase("tp4", devices)
    cfg = sz["four_chip_model"]
    emit_model("four_chip", cfg, seed, tp=4)
    model = AutoLLM.build(cfg, mesh=mesh)
    with deadline.within(300.0, "tp4 model.init"):
        params = jax.block_until_ready(model.init(jax.random.PRNGKey(seed)))
    emit(event="params_placed", memory=memory(devices),
         devices_holding_wq=sorted(
             d.id for d in params["layers"][0]["attn"]["w_q"].devices()))
    model_x = AutoLLM.build(cfg, mesh=mesh, impl="xla")
    cb, cs, ct = sz["compare_batch"], sz["compare_seq"], sz["compare_cache"]
    with deadline.within(480.0, "tp4 comparison with impl='xla'"):
        compare_with_xla(
            "tp4", model, model_x, params,
            KVCacheManager(cfg.num_hidden_layers, cb, ct,
                           cfg.num_key_value_heads, cfg.head_dim, mesh=mesh,
                           axis="tp", dtype=cfg.dtype).init,
            prefill_mode="ag_rs", decode_mode="gemm_ar", batch=cb, seq=cs)
    eng = Engine(model, batch=sz["batch"], max_seq=sz["max_seq"],
                 prefill_mode="ag_rs", decode_mode="gemm_ar")
    prompts = make_prompts(sz["tp4_prompt_lens"], cfg.vocab_size, seed)
    with deadline.within(720.0, "tp4 serving"):
        serve_phase("tp4", eng, params, prompts, sz["tp4_gen_lens"],
                    len(prompts) - 2, cfg.vocab_size, watch, timeout)
    phase.end(engine="Engine(prefill_mode='ag_rs', decode_mode='gemm_ar')",
              **engine_fields(eng))


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs ONLY the four-chip phase")
    ap.add_argument("--size", choices=("full", "tiny"), default="full",
                    help="tiny: CPU rehearsal of the control flow; never "
                         "ends in ok:true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--timeout", type=float, default=900.0,
                    help="seconds one client round trip may take "
                         "(the first one compiles)")
    ap.add_argument("--deadline", type=float, default=1150.0,
                    help="seconds after which the run ends itself")
    ap.add_argument("--fail-op", default=None,
                    help="inject one runtime failure into this op (e.g. "
                         "gemm_ar): proves a fallback fails the run")
    args = ap.parse_args(argv)

    import importlib.metadata as md

    import jax

    from triton_dist_tpu import obs
    from triton_dist_tpu.ops.common import resolve_interpret
    from triton_dist_tpu.runtime.compile_cache import (
        configure_compile_cache)
    from triton_dist_tpu.runtime.platform import is_tpu

    deadline = Deadline(args.deadline)
    cache_dir = configure_compile_cache()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            versions[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            versions[pkg] = None
    emit(event="start", device=device, versions=versions, chips=args.chips,
         size=args.size, seed=args.seed, compile_cache_dir=cache_dir,
         compile_cache_entries=cache_entries(cache_dir),
         compile_cache_max_bytes=jax.config.jax_compilation_cache_max_size)

    if args.size == "full":
        # Before anything is built: the real size only means something
        # on the chip, compiled by Mosaic.
        check(is_tpu(), f"no TPU: JAX reports platform "
                        f"{device['platform']!r}")
        check(resolve_interpret(None) is False,
              "Pallas kernels would run interpreted")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} but JAX reports {len(devices)} devices")

    obs.enable()
    libs = native_libraries()
    emit(event="native_libraries", loaded=libs)
    check(libs["libtdtkv"], "libtdtkv (the paged KV allocator the serving "
                            "path uses) did not build or load")

    watch = CompileWatch()
    sz = sizes(args.size)
    t0 = time.perf_counter()
    with contextlib.ExitStack() as stack:
        if args.fail_op:
            from triton_dist_tpu.testing import faults
            stack.enter_context(faults.inject("comm_error",
                                              op=args.fail_op))
        if args.chips == 4:
            run_four_chips(sz, args.seed, devices[:4], watch, args.timeout,
                           deadline)
        else:
            run_one_chip(sz, args.seed, devices, watch, args.timeout)
    emit(event="done", wall_s=round(time.perf_counter() - t0, 3),
         compile=watch.mark(),
         compile_cache_entries=cache_entries(cache_dir),
         resilience=resilience_counters(), memory=memory(devices))

    if not is_tpu():
        emit(event="refused", reason=f"rehearsal on platform "
             f"{device['platform']!r}: only a TPU run gives a result")
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except SmokeFailure as e:
        fail(str(e))
    except Exception as e:  # noqa: BLE001 — any phase that raises ends the run
        traceback.print_exc()
        fail(f"{type(e).__name__}: {e}"[:2000])
    sys.exit(code)
