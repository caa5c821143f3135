"""Inference engine: prefill + jit-compiled decode loop.

TPU-native redesign of the reference ``Engine``
(python/triton_dist/models/engine.py:113-190: prefill with the torch path,
switch layers to the fused mode, capture the decode step in a CUDA graph,
then replay per token). On TPU the CUDA-graph capture is ``jax.jit`` of
the whole decode step (SURVEY.md §7 stage 7: "CUDA graph ≙ jit-compiled
decode step — XLA gives this for free"): one compiled program containing
every layer's fused kernels, replayed per token with no launch overhead.

Backends mirror the reference's (engine.py:116):
``xla_ar`` ≙ torch, ``ag_rs`` ≙ triton_dist, ``gemm_ar`` ≙
triton_dist_gemm_ar (replicated small-batch decode).
"""

from __future__ import annotations

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np

from triton_dist_tpu import obs
from triton_dist_tpu.obs import trace as _trace
from triton_dist_tpu.layers.kda import scan_chunks
from triton_dist_tpu.layers.tp_attn import (
    decode_window, prefill_positions_scored)
from triton_dist_tpu.models.kv_cache import (
    KVCacheLost, KVCacheManager, jit_rewriting_caches)


def sample_token(logits: jax.Array, key: jax.Array | None = None,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0) -> jax.Array:
    """Greedy / temperature / top-k / nucleus sampling (reference
    sampling utils, models/utils.py). logits: (B, V) → (B,) int32."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    assert key is not None
    logits = logits / temperature
    if top_k > 0 or top_p < 1.0:
        # ONE descending sort serves both filters (the hot decode step
        # must not pay two O(V log V) passes).
        v = logits.shape[-1]
        s = jnp.sort(logits, axis=-1)[:, ::-1]
        if top_k > 0:
            logits = jnp.where(logits < s[:, top_k - 1:top_k], -jnp.inf,
                               logits)
            s = jnp.where(jnp.arange(v)[None, :] < top_k, s, -jnp.inf)
        if top_p < 1.0:
            # Nucleus over the (top-k-filtered) distribution: keep the
            # smallest sorted prefix whose mass reaches top_p. `<=`
            # keeps the top token even at top_p == 0 (degenerates to
            # argmax, not to categorical-over-all--inf ≡ token 0).
            probs = jax.nn.softmax(s, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            keep = cum - probs <= top_p                 # (B, V) sorted
            kept_min = jnp.min(
                jnp.where(keep, s, jnp.inf), axis=-1)[:, None]
            logits = jnp.where(logits >= kept_min, logits, -jnp.inf)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def _seat_row(token, offsets, row, first, length):
    """Row ``row`` starts decoding: ``first`` is its last token,
    ``length`` its write offset. Inlined into every admission program;
    a program of its own only where none ran (``adopt_row``)."""
    return token.at[row].set(first), offsets.at[row].set(length)


_seat_row = jax.jit(_seat_row, inline=True)


#: What ``StreamSession.launch_into_row`` returns for an admission whose
#: first token is still on the device.
DEFERRED = object()


def _cache_lost(cause: BaseException) -> KVCacheLost:
    return KVCacheLost(
        "the session's KV cache was lost: an admission program failed "
        f"after its caches were donated ({type(cause).__name__}: {cause}); "
        "every row's K/V went with it, the session must be reopened")


#: The auto policy's prior when no measurement exists: the only silicon
#: evidence on record has the mega one-program step 1.49x the plain
#: jitted step (docs/perf.md "First chip contact").
DEFAULT_AUTO_PATH = "mega"


class DecodePathPolicy:
    """``Engine(decode_path="auto")`` arbitration: measured device-step
    gauges pick mega vs plain.

    The devprof pump sampler (obs.devprof, docs/observability.md
    "Device-time truth") labels each profiled pump iteration with the
    decode path that drove it, so parsed captures land in SEPARATE
    ``device.step.mega.*`` / ``device.step.plain.*`` gauges. The
    comparison is PER WINDOW — ``total_ms / windows``, since a
    multi-iteration breach capture unions several step windows into
    one total and a union is not comparable across capture spans. When
    both paths hold a measured per-iteration time, the faster one
    wins; the decision is re-taken per batch (every pump iteration /
    serve call), so the selection tracks the batch shape the captures
    were taken at. With no measurement (or only one path
    measured) the default is :data:`DEFAULT_AUTO_PATH` — except every
    :data:`PROBE_EVERY`-th decision, which runs the OTHER path so the
    sampler can ever measure it (a policy that only runs its prior can
    never collect the numbers to correct it; outputs are bit-identical,
    so a probe costs only the paths' speed difference). Probes are
    doubly gated on measurability: only SAMPLABLE decisions probe
    (stream-session decode steps under the scheduler —
    ``decide(samplable=True)``; a serve() call resolved
    outside the pump would run its whole generation on the probed
    path with nothing able to capture it), and only while a devprof
    sampler is alive (``obs.devprof.sampler_active()`` — the same
    consumer-gating rationale as ``devprof.arm``). Every decision is
    provenance-counted
    (``engine.decode_path.auto_source.*``) so a dashboard can tell
    measured decisions from prior-based and probe ones.
    ``TDT_MEGA_AUTO=0`` opts out: auto resolves to plain, counted as
    ``env_off``. Either path is greedily bit-identical
    (tests/test_scheduler.py), so the policy is a pure perf choice.
    """

    #: Every Nth decision probes the non-default (or measured-stale)
    #: path — keeps both device.step.* gauges collectable/refreshable.
    PROBE_EVERY = 32

    def __init__(self, enabled: bool | None = None):
        if enabled is None:
            import os
            enabled = os.environ.get("TDT_MEGA_AUTO",
                                     "1").strip() != "0"
        self.enabled = bool(enabled)
        self._n = 0

    @staticmethod
    def measured_step_ms(kind: str) -> float | None:
        """The measured device time of one ``kind`` pump iteration
        (per annotation window) from the last parsed capture, or None
        when never measured (gauges default to 0 — a zero-length
        capture is not a measurement)."""
        total = float(obs.gauge(f"device.step.{kind}.total_ms").value)
        if total <= 0.0:
            return None
        windows = float(obs.gauge(f"device.step.{kind}.windows").value)
        return total / windows if windows > 0 else total

    @staticmethod
    def _can_probe() -> bool:
        """A probe only makes sense where some sampler could capture
        it into the gauges this policy reads."""
        from triton_dist_tpu.obs import devprof
        return devprof.sampler_active()

    def decide(self, samplable: bool = False) -> str:
        """"mega" or "plain" for the next decode step/serve call.

        ``samplable``: this decision drives work a pump sampler could
        actually capture (a StreamSession decode step under the
        scheduler). Only those decisions may probe — a serve() call
        resolved outside the pump would run its WHOLE generation on
        the probed path with no possibility of measurement."""
        if not self.enabled:
            kind, source = "plain", "env_off"
        else:
            self._n += 1
            mega_ms = self.measured_step_ms("mega")
            plain_ms = self.measured_step_ms("plain")
            if mega_ms is not None and plain_ms is not None:
                kind = "mega" if mega_ms <= plain_ms else "plain"
                source = "measured"
            else:
                kind, source = DEFAULT_AUTO_PATH, "default"
            if samplable and self._n % self.PROBE_EVERY == 0 \
                    and self._can_probe():
                # Exploration beat: run the other path this once so a
                # live sampler can (re)measure it — otherwise only the
                # winning path's gauge ever refreshes and the policy
                # can neither correct its prior nor notice staleness.
                kind = "plain" if kind == "mega" else "mega"
                source = "probe"
        obs.counter(f"engine.decode_path.auto_{kind}").inc()
        obs.counter(f"engine.decode_path.auto_source.{source}").inc()
        obs.gauge("serving.mega_selected").set(
            1.0 if kind == "mega" else 0.0)
        return kind


class Engine:
    """Serve loop around a DenseLLM / Qwen3MoE model."""

    def __init__(self, model, batch: int, max_seq: int,
                 prefill_mode: str = "xla_ar", decode_mode: str = "gemm_ar",
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, seed: int = 0,
                 profile_dir: str | None = None, profile_steps: int = 64,
                 paged: bool = False, page_size: int = 16,
                 prefill_chunk: int | None = None,
                 use_mega: bool = False,
                 decode_path: str | None = None,
                 prefix_cache: bool | None = None,
                 kv_slots_per_dev: int | None = None,
                 slo=None, spec=None):
        self.model = model
        c = model.config
        self.paged = paged
        # Speculative decoding (ISSUE 13, docs/serving.md "Speculative
        # decoding"): a SpecConfig turns stream-session decode into
        # variable-tokens-per-step bursts — a drafter proposes up to k
        # tokens per row, one widened verify step scores them, the
        # accepted prefix commits atomically. Greedy-only: the verify
        # step's acceptance rule IS argmax equality, which is what
        # makes spec-on output bit-identical to spec-off
        # (tests/test_scheduler.py). TDT_SPEC=0 disables at runtime.
        if spec is not None and spec.enabled:
            if temperature > 0.0:
                # ValueError, not assert: user-facing config checks
                # survive ``python -O``.
                raise ValueError(
                    "SpecConfig requires greedy decoding "
                    f"(temperature=0), got temperature={temperature} — "
                    "stochastic speculative sampling needs rejection "
                    "resampling, which this engine does not implement")
            self.spec = spec
        else:
            self.spec = None
        self._spec_step: dict = {}       # verify-window k → jitted step
        # Declarative serving SLO targets (obs.slo.SLOTarget list) the
        # scheduler's SLO tracker evaluates for this engine; None keeps
        # the env-overridable defaults (docs/observability.md "SLOs
        # and burn rates").
        self.slo = slo
        # Cross-request prefix caching (ISSUE 6; paged stream sessions
        # only): full prompt blocks are indexed by token-hash chain and
        # shared across requests, so a warm shared-prefix admission
        # prefills only its suffix. Default on; TDT_PREFIX_CACHE=0 (or
        # prefix_cache=False) opts out — greedy outputs are
        # bit-identical either way (tests/test_scheduler.py).
        if prefix_cache is None:
            import os
            prefix_cache = os.environ.get("TDT_PREFIX_CACHE",
                                          "1").strip() != "0"
        self.prefix_cache = bool(prefix_cache) and paged
        # decode_path: which decode-step program serves this engine.
        # "plain" runs model.forward under jit; "mega" runs the
        # MegaQwen3 fused one-program task-graph step (measured 1.49x
        # the plain jitted step on chip, docs/perf.md "First chip
        # contact"); "auto" arbitrates per batch on the measured
        # device.step.{mega,plain}.total_ms gauges the devprof pump
        # sampler publishes (DecodePathPolicy; TDT_MEGA_AUTO=0 opts
        # out). use_mega=True is the legacy spelling of
        # decode_path="mega". Every engine family serves every path —
        # the mega graph takes per-row kv_start/offset vectors and
        # paged block tables (ISSUE 11), so the old
        # use_mega x (paged|sp|ragged) ValueErrors are gone.
        if decode_path is None:
            decode_path = "mega" if use_mega else "plain"
        elif use_mega and decode_path != "mega":
            # ValueError, not assert: user-facing configuration
            # validation must survive ``python -O`` (ADVICE r5 low).
            raise ValueError(
                f"conflicting config: use_mega=True with "
                f"decode_path={decode_path!r} — pass one or the other")
        if decode_path not in ("plain", "mega", "auto"):
            raise ValueError(
                f"decode_path must be 'plain', 'mega' or 'auto': "
                f"{decode_path!r}")
        self.decode_path = decode_path
        self.use_mega = decode_path == "mega"
        self.decode_policy = (DecodePathPolicy()
                              if decode_path == "auto" else None)
        self._mega = None
        # Sliding-window layers (a model's ``windows``: one entry per
        # layer) keep a ring of ``window`` positions per row, which the
        # stream session's admission and step programs write and read.
        # The paths that know no rings refuse such a model here: none
        # of them may compute full attention in a window layer's place.
        # So are latent lanes and recurrent state (a model's
        # ``cache_kinds``, models/kv_cache.LatentLane / StateLanes): only
        # the stream session's programs seat and step them.
        windows = tuple(getattr(model, "windows", ()) or ())
        kinds = tuple(getattr(model, "cache_kinds", ()) or ())
        if any(windows) or any(kinds):
            cannot = [what for what, on in (
                ("paged KV pools (and with them the prefix cache and "
                 "adopt_row)", paged),
                ("the sequence-parallel modes (forward_sp)",
                 "sp" in (prefill_mode, decode_mode)),
                ("the mega decode step", decode_path != "plain"),
                ("the speculative verify step", self.spec is not None),
            ) if on]
            has = ("sliding-window layers (ROADMAP R3)" if any(windows)
                   else "latent-cache and recurrent-state layers "
                        "(ROADMAP R2, R8)")
            if cannot:
                raise NotImplementedError(
                    f"{type(model).__name__} has {has}; "
                    f"{' and '.join(cannot)} cannot serve them yet")
        #: Names of the counters the model makes inside its programs
        #: (``forward(counted=True)``), or () — see
        #: :meth:`_build_stream_step_counted`.
        self.count_names = tuple(getattr(model, "count_names", ()) or ())
        if "sp" in (prefill_mode, decode_mode):
            # Sequence-parallel serving (long context): both phases must
            # share the sequence-sharded cache layout.
            assert prefill_mode == decode_mode == "sp", (
                "mode='sp' applies to prefill and decode together")
            assert getattr(model, "sp_axis", None), (
                "build the model with sp_axis=... for sp serving")
            if paged:
                # vLLM-style paged pools: physical page slots + per-row
                # block tables, admission-controlled per serve() call
                # (models/kv_cache.PagedKVCacheManager + csrc/kvpool).
                from triton_dist_tpu.models.kv_cache import (
                    PagedKVCacheManager)
                world = model.mesh.shape[model.sp_axis]
                assert max_seq % (world * page_size) == 0, (
                    f"max_seq {max_seq} must divide into "
                    f"{world} devices x {page_size}-token pages")
                # kv_slots_per_dev sizes the allocatable pool (default:
                # whole-batch capacity; the sentinel page rides outside
                # it). SMALLER pools are legal — oversubscription
                # streams through block-granular admission; plain
                # serve() still needs whole rows.
                self.kv = PagedKVCacheManager(
                    c.num_hidden_layers, batch, page_size,
                    max_seq // (world * page_size),
                    c.num_key_value_heads, c.head_dim, mesh=model.mesh,
                    axis=model.sp_axis, dtype=c.dtype,
                    slots_per_dev=kv_slots_per_dev)
            else:
                self.kv = KVCacheManager(
                    c.num_hidden_layers, batch, max_seq,
                    c.num_key_value_heads, c.head_dim, mesh=model.mesh,
                    axis=model.sp_axis, dtype=c.dtype, seq_shard=True)
        else:
            assert not paged, "paged serving requires the sp modes"
            self.kv = KVCacheManager(
                c.num_hidden_layers, batch, max_seq, c.num_key_value_heads,
                c.head_dim, mesh=model.mesh, axis=model.axis, dtype=c.dtype,
                windows=windows, kinds=kinds)
        self.prefill_mode = prefill_mode
        self.decode_mode = decode_mode
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.key = jax.random.PRNGKey(seed)
        # Decode-loop profile hook (reference engine.py:153-179: a
        # 64-step torch-profiler window inside serve): when set, the
        # first ``profile_steps`` decode steps of each serve() are traced
        # per-host under ``profile_dir``.
        self.profile_dir = profile_dir
        self.profile_steps = profile_steps
        # Chunked sp prefill: bound activation memory on very long
        # prompts by prefilling ``prefill_chunk`` positions at a time
        # (cache-aware ring attention; dense.forward_sp chunked path).
        if prefill_chunk is not None:
            assert prefill_mode == "sp" and not paged, (
                "prefill_chunk applies to the (non-paged) sp engine")
        self.prefill_chunk = prefill_chunk
        self._decode_step: dict = {}        # decode path → jitted step
        self._decode_step_stop: dict = {}
        self._stream_step = None
        self._stream_step_mega = None
        self._admit = None
        self._admit_prefix = None
        self._admit_chunk = None
        self._admit_finish = None

    # -- decode step (jit once = graph capture, engine.py:75-105) ----------
    def _get_mega(self):
        if self._mega is None:
            from triton_dist_tpu.mega import MegaQwen3
            self._mega = MegaQwen3(self.model,
                                   decode_mode=self.decode_mode,
                                   paged=self.paged)
        return self._mega

    def _mega_forward(self, params, caches, token, offset, kv_start,
                      table):
        """The mega one-program step as a decode forward: scalar OR
        per-row ``offset``, ragged ``kv_start``, contiguous or paged
        caches — the same surface the plain forward serves, so the two
        paths interchange under every serving mode (ISSUE 11)."""
        return self._get_mega().step(
            params, token[:, None], caches, offset,
            kv_start=None if self.decode_mode == "sp" else kv_start,
            table=table)

    def resolve_decode_path(self, samplable: bool = False) -> str:
        """The decode path this call runs: the static config, or the
        auto policy's measured-gauge decision — re-taken per call, so
        the selection follows the batch as it changes (docs/serving.md
        "Decode-path selection"). ``samplable`` marks decisions whose
        work a pump sampler could capture (stream-session decode
        steps) — the only ones allowed to probe."""
        if self.decode_path != "auto":
            return self.decode_path
        return self.decode_policy.decide(samplable=samplable)

    def _decode_forward(self, path: str = "plain"):
        """The decode-step forward for one decode path: the mega
        one-program step or model.forward — one place, so the sampling
        and stop bookkeeping below exist once per builder."""
        if path == "mega":
            return self._mega_forward
        model, mode = self.model, self.decode_mode

        def fwd(params, caches, token, offset, kv_start, table):
            return model.forward(
                params, token[:, None], caches, offset, mode=mode,
                kv_start=None if mode == "sp" else kv_start,
                **({"block_table": table} if table is not None else {}))
        return fwd

    def _build_decode_step(self, path: str = "plain"):
        fwd = self._decode_forward(path)

        @jit_rewriting_caches
        def step(params, caches, token, offset, key, kv_start, table):
            logits, caches = fwd(params, caches, token, offset,
                                 kv_start, table)
            nxt = sample_token(logits[:, -1], key, self.temperature,
                               self.top_k, self.top_p)
            return nxt, caches
        return step

    def _build_decode_step_stop(self, path: str = "plain"):
        """Decode step with in-graph stop bookkeeping: still ONE compiled
        program per token (jit caches per stop-set shape); stopped rows
        keep emitting their stop token."""
        fwd = self._decode_forward(path)

        @jit_rewriting_caches
        def step(params, caches, token, offset, key, done, stop, kv_start,
                 table):
            logits, caches = fwd(params, caches, token, offset,
                                 kv_start, table)
            nxt = sample_token(logits[:, -1], key, self.temperature,
                               self.top_k, self.top_p)
            nxt = jnp.where(done, token, nxt)
            return nxt, caches, done | jnp.isin(nxt, stop)
        return step

    def serve(self, params, input_ids: jax.Array, gen_len: int,
              stop_tokens=None, kv_start=None) -> jax.Array:
        """Prefill ``input_ids`` (B, S) then generate up to ``gen_len``
        tokens. Returns (B, S + gen_len) (reference ``Engine.serve``
        engine.py:113-190).

        ``stop_tokens``: iterable of token ids ending a row's generation
        (default: the model config's ``eos_token_id`` if set). Rows that
        have stopped keep emitting their stop token (the output stays a
        rectangle — static shapes); the loop exits early once every row
        has stopped.
        """
        if getattr(self.kv, "windows", ()) or getattr(self.kv, "kinds", ()):
            raise NotImplementedError(
                "Engine.serve prefills a whole batch into the row caches, "
                "where a sliding-window layer keeps rings and a latent or "
                "recurrent layer its own lanes: serve this model through "
                "a stream session (serve_stream, the scheduler, "
                "ModelServer)")
        if self.spec is not None:
            # Explicit refusal, not a silent ignore (the PR-10 config-
            # check discipline): serve()'s rectangular decode loop has
            # no draft/verify machinery — speculation serves through
            # the stream path (StreamSession / serve_stream / the
            # scheduler), which is where every client route already
            # lands (ModelServer schedules by default).
            raise ValueError(
                "serve() does not run speculative decoding — "
                "SpecConfig engines serve through the stream path "
                "(StreamSession / serve_stream / the scheduler); "
                "build the engine with spec=None for serve()")
        b, s = input_ids.shape
        if gen_len <= 0:
            return input_ids
        # Telemetry (docs/observability.md). ``timed`` gates every
        # clock read and block_until_ready: with the default no-op
        # registry AND tracing off, the serve path pays a handful of
        # no-op calls per CALL (not per token) and the decode loop's
        # span is a shared null context manager. With only tracing on
        # (the flight-recorder posture) the clocks run and the
        # histogram observes land in the no-op registry.
        tel = obs.enabled()
        tr = _trace.enabled()
        timed = tel or tr
        t_serve0 = time.perf_counter() if timed else 0.0
        obs.counter("engine.serve_calls").inc()
        # Resolve the decode path ONCE per serve call (auto re-decides
        # here — per batch); the mega graph serves paged tables and
        # ragged kv_start like the plain forward, so no shape guard.
        path = self.resolve_decode_path()
        obs.counter(f"engine.decode_path.{path}").inc()
        if stop_tokens is None:
            eos = getattr(self.model.config, "eos_token_id", -1)
            stop_tokens = (eos,) if eos >= 0 else ()
        stop_tokens = tuple(stop_tokens)
        has_stop = bool(stop_tokens)
        stop = jnp.asarray(list(stop_tokens) or [-1], jnp.int32)
        kv_start = (jnp.zeros((b,), jnp.int32) if kv_start is None
                    else jnp.asarray(kv_start, jnp.int32))
        self.kv.reset()
        table = None
        if self.paged:
            # Admission control per serve() call: reset the pool (a
            # prior stream session may have left it block-granular),
            # then reserve this batch's whole rows atomically (rollback
            # on exhaustion — csrc/kvpool alloc_many).
            self.kv.reset_pool()
            self.kv.alloc_many(range(b))
            table = self.kv.block_table()
        caches = self.kv.init()

        if self.prefill_mode == "sp":
            # SP serving has no ragged support (forward_sp's contract).
            assert not bool(kv_start.any()), "sp serving is non-ragged"
        t_pre0 = time.perf_counter() if timed else 0.0
        chunk = self.prefill_chunk
        if chunk and self.prefill_mode == "sp" and s > chunk:
            # Cache-aware chunked prefill: activation memory is bounded
            # by the chunk, the cache accumulates the prefix.
            done_pos = 0
            while done_pos < s:
                step_s = min(chunk, s - done_pos)
                logits, caches = self.model.forward(
                    params, input_ids[:, done_pos:done_pos + step_s],
                    caches, done_pos, mode="sp", logits_at=step_s - 1)
                done_pos += step_s
        else:
            logits, caches = self.model.forward(
                params, input_ids, caches, 0, mode=self.prefill_mode,
                logits_at=s - 1,
                kv_start=None if self.prefill_mode == "sp" else kv_start,
                **({"block_table": table} if table is not None else {}))
        self.kv.inc_offset(s)
        token = sample_token(logits[:, -1], self.key, self.temperature,
                             self.top_k, self.top_p)
        if timed:
            # Block so prefill/TTFT measure completed device work, not
            # async dispatch — the observer cost of enabling telemetry.
            jax.block_until_ready(token)
            now = time.perf_counter()
            obs.histogram("engine.prefill_ms").observe(
                (now - t_pre0) * 1e3)
            obs.histogram("engine.ttft_ms").observe(
                (now - t_serve0) * 1e3)
            if tr:
                # Back-dated complete event: the prefill region on the
                # timeline, under the request's bound trace ID.
                _trace.complete(
                    "engine.prefill", "engine",
                    _trace.perf_to_us(t_pre0), (now - t_pre0) * 1e6,
                    args={"batch": b, "prompt_len": s,
                          "chunked": bool(chunk and s > (chunk or 0))})

        if path not in self._decode_step:
            self._decode_step[path] = self._build_decode_step(path)
        decode_step = self._decode_step[path]
        if has_stop and path not in self._decode_step_stop:
            self._decode_step_stop[path] = \
                self._build_decode_step_stop(path)
        decode_step_stop = self._decode_step_stop.get(path)
        # With stop tokens the bookkeeping lives INSIDE the jitted step —
        # still one dispatch per token; without, the plain step runs.
        done = jnp.isin(token, stop) if has_stop else None
        stopped = has_stop and bool(done.all())  # prefill may already stop
        out = [input_ids, token[:, None]]

        def run_steps(n):
            nonlocal token, caches, done, stopped, steps_run
            for i in range(n):
                if stopped:
                    out.append(jnp.broadcast_to(
                        token[:, None], (b, n - i)).astype(token.dtype))
                    return
                with obs.span("engine.decode_step"):
                    self.key, sub = jax.random.split(self.key)
                    off = jnp.int32(self.kv.offset)
                    if has_stop:
                        token, caches, done = decode_step_stop(
                            params, caches, token, off, sub, done, stop,
                            kv_start, table)
                    else:
                        token, caches = decode_step(
                            params, caches, token, off, sub, kv_start,
                            table)
                    if timed:
                        # Block INSIDE the span so the histogram holds
                        # real per-token device latency, not the ~µs
                        # async enqueue — the per-step observer cost of
                        # enabling telemetry (docs/observability.md).
                        jax.block_until_ready(token)
                steps_run += 1
                self.kv.inc_offset(1)
                out.append(token[:, None])
                # the all-done check is a host sync; amortize it
                if has_stop and i % 8 == 7 and bool(done.all()):
                    stopped = True

        n_total = gen_len - 1
        steps_run = 0
        t_dec0 = time.perf_counter() if timed else 0.0
        if self.profile_dir and n_total > 1:
            from triton_dist_tpu.tools.profiler import group_profile
            # One REAL warm-up step before the window: it populates the
            # jit dispatch cache (AOT lower().compile() would not), so
            # the trace shows steady-state per-token replay rather than
            # the one-off XLA compile — and because it goes through the
            # same run_steps path, the RNG stream matches an unprofiled
            # serve() exactly.
            run_steps(1)
            jax.block_until_ready(token)
            n_prof = min(self.profile_steps, n_total - 1)
            with group_profile("engine_decode", self.profile_dir):
                run_steps(n_prof)
                jax.block_until_ready(token)
            run_steps(n_total - 1 - n_prof)
        else:
            run_steps(n_total)
        if timed:
            jax.block_until_ready(token)
            dt = time.perf_counter() - t_dec0
            # Real computed tokens only (first token + executed decode
            # steps) — early-stopped rows' broadcast padding is NOT
            # generation and must not inflate throughput.
            obs.counter("engine.tokens_generated").inc(
                b * (steps_run + 1))
            if steps_run > 0 and dt > 0:
                # Decode-loop throughput (excludes prefill + TTFT,
                # which have their own histograms above).
                obs.gauge("engine.tokens_per_s").set(b * steps_run / dt)
            if tr:
                now = time.perf_counter()
                _trace.complete(
                    "engine.serve", "engine",
                    _trace.perf_to_us(t_serve0),
                    (now - t_serve0) * 1e6,
                    args={"batch": b, "prompt_len": s,
                          "gen_len": gen_len, "steps_run": steps_run,
                          "mega": path == "mega"})
        return jnp.concatenate(out, axis=1)


    # -- continuous batching ----------------------------------------------
    def _build_spec_verify_step(self, k: int):
        """The widened verify step of speculative decoding (ISSUE 13):
        ONE forward scores a k+1-token window per row — the last
        committed token plus k draft tokens — at per-row positions
        ``offsets[b]+[0, k]``, writing their K/V exactly where k+1
        sequential stream steps would and returning the argmax at
        every window position. Compiled once per k (the chunked-
        prefill compile-cache pattern: k buckets are few and small).
        Greedy by construction — acceptance compares these argmaxes
        against the drafts, so emitted tokens are bit-identical to the
        sequential path (models/spec.py). Frozen rows ride along like
        the plain stream step: paged lanes point at the sentinel, and
        contiguous-lane overshoot is dropped or overwritten before any
        mask exposes it."""
        model, mode = self.model, self.decode_mode

        @jit_rewriting_caches
        def step(params, caches, tokens, offsets, table):
            logits, caches = model.forward(
                params, tokens, caches, offsets, mode=mode,
                **({"block_table": table} if table is not None
                   else {}))
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                    caches)
        return step

    # The admission programs below carry the session's small state
    # (sampling key, last token and write offset per row) through the
    # device: each takes it, seats its row in-graph and hands it back,
    # so the thread that drives a session makes ONE dispatch per
    # admission and no eager op around it (ISSUE 31).
    def _draw_key(self, key):
        """``(next key, this draw's key)``, traced inside the program
        that samples. Only a sampling engine splits (``key, sub =
        split(key)``, the sequence the host-side splits produced);
        greedy traces no split and hands the key back as it came."""
        if self.temperature > 0.0:
            key, sub = jax.random.split(key)
            return key, sub
        return key, None

    def _first_token(self, logits, idx, caches, token, offsets, key, row,
                     length, counts=None):
        """Tail of the admission programs: sample the first token at
        position ``idx`` of ``logits`` (0 in every admission: the
        forward computed the one row that is read) and seat the row.
        ``counts`` (a counting model's, else None) ride home behind the
        first token: what the host reads back is then a vector."""
        last = jax.lax.dynamic_slice_in_dim(logits, idx, 1, axis=1)[:, 0]
        key, sub = self._draw_key(key)
        first = sample_token(last, sub, self.temperature, self.top_k,
                             self.top_p)[0]
        token, offsets = _seat_row(token, offsets, row, first, length)
        if counts is not None:
            first = jnp.concatenate([first[None], counts])
        return first, caches, token, offsets, key

    def _prompt_forward(self, params, ids, small, offset, length):
        """An admission's forward over ``ids`` (1, S) at ``offset`` into
        the scratch caches: ``(logits, small, counts or None)``. One
        contract for every model: it computes the ONE logit row that is
        read, the prompt's last position (clipped into this slice of a
        chunked admission), so the logits are (1, 1, V). A counting
        model is also told which positions are the prompt's (the
        bucket's pad is routed to no expert) and hands its counts back."""
        s = ids.shape[1]
        counting = ({"live": (offset + jnp.arange(s) < length)[None],
                     "counted": True} if self.count_names else {})
        out = self.model.forward(
            params, ids, small, offset, mode=self.prefill_mode,
            logits_at=jnp.clip(length - 1 - offset, 0, s - 1), **counting)
        return out if counting else (*out, None)

    def _seat_lanes(self, caches, small, row, length):
        """Row ``row``'s lane of every layer's cache rewritten from the
        scratch prefix ``small``: the prefix itself at slot 0, or a
        window layer's ring of its last positions
        (``KVCacheManager.lane``); a recurrent layer's state whole, as
        the prefill left it at the prompt's true length."""
        lane = getattr(self.kv, "lane", lambda i, x, n: x)
        return [tuple(
            jax.lax.dynamic_update_slice(
                c, lane(i, sc, length), (row,) + (0,) * (c.ndim - 1))
            for c, sc in zip(layer, scratch))
            for i, (layer, scratch) in enumerate(zip(caches, small))]

    def _build_stream_step(self):
        """One decode step with PER-ROW write offsets: each live row
        decodes at its own cache position (frozen rows re-emit their
        token and do not advance). One compiled program per token.

        The step tells the attention how far its live rows reach
        (``kv_need``: the longest LIVE row's offset + 1; a dead row's
        stale offset must not pick the window), and the attention
        reads the cache only in the leading chunks that cover them,
        counted inside this one program
        (layers/tp_attn._attention_core). A frozen row whose stale
        offset lies beyond that window computes finite garbage that
        ``where(done, token, nxt)`` discards: the pad-slot argument of
        :meth:`_build_admit`, applied to the read."""
        model, mode = self.model, self.decode_mode
        if self.count_names:
            return self._build_stream_step_counted()

        @jit_rewriting_caches
        def step(params, caches, token, offsets, key, done, table):
            logits, caches = model.forward(
                params, token[:, None], caches, offsets, mode=mode,
                kv_need=jnp.max(jnp.where(done, 0, offsets)) + 1,
                **({"block_table": table} if table is not None else {}))
            nxt = sample_token(logits[:, -1], key, self.temperature,
                               self.top_k, self.top_p)
            nxt = jnp.where(done, token, nxt)
            return nxt, caches, jnp.where(done, offsets, offsets + 1)
        return step

    def _build_stream_step_counted(self):
        """:meth:`_build_stream_step` for a model that counts inside its
        programs (``model.count_names``). The step's counts ride behind
        the tokens in the ONE vector the session reads back each step,
        so they cost no dispatch and no transfer of their own:
        ``token`` is (batch + counts,) on both sides, its tail ignored
        on the way in. Frozen rows are told apart (``live``) so that
        they reach no expert and no counter."""
        model, mode = self.model, self.decode_mode

        @jit_rewriting_caches
        def step(params, caches, token, offsets, key, done, table):
            token = token[:offsets.shape[0]]
            logits, caches, counts = model.forward(
                params, token[:, None], caches, offsets, mode=mode,
                kv_need=jnp.max(jnp.where(done, 0, offsets)) + 1,
                live=~done[:, None], counted=True)
            nxt = sample_token(logits[:, -1], key, self.temperature,
                               self.top_k, self.top_p)
            nxt = jnp.where(done, token, nxt)
            return (jnp.concatenate([nxt, counts]), caches,
                    jnp.where(done, offsets, offsets + 1))
        return step

    def _build_stream_step_mega(self):
        """The continuous-batching decode step through the mega
        one-program task graph: the per-row offset vector threads into
        the graph's attention position math and per-row KV scatter
        (contiguous lanes or paged table lanes) — same contract and
        same ops as :meth:`_build_stream_step`, so greedy outputs are
        bit-identical (tests/test_scheduler.py) and a session can flip
        between the two steps mid-request (decode_path="auto")."""
        fwd = self._mega_forward

        @jit_rewriting_caches
        def step(params, caches, token, offsets, key, done, table):
            logits, caches = fwd(params, caches, token, offsets, None,
                                 table)
            nxt = sample_token(logits[:, -1], key, self.temperature,
                               self.top_k, self.top_p)
            nxt = jnp.where(done, token, nxt)
            return nxt, caches, jnp.where(done, offsets, offsets + 1)
        return step

    def _build_admit(self):
        """Admission program: prefill on a batch-1 scratch cache, scatter
        the prefix into row ``row``'s lane at slot 0, emit the first
        token.

        Prompts arrive RIGHT-padded to a power-of-two bucket so jit
        compiles one program per bucket, not per distinct length (a
        public stream of arbitrary lengths must not compile-storm —
        code-review r3g). The pad suffix is causally invisible to the
        first token (the one logit row computed, traced position
        ``length``-1), and its
        scattered K/V slots are overwritten by the row's own decode
        steps before the per-row mask ever exposes them — the same
        argument that makes stale-lane reuse safe."""
        @jit_rewriting_caches
        def admit(params, caches, ids, length, row, token, offsets, key):
            small = self.kv.scratch(caches, ids.shape[1])  # bucket long
            logits, small, counts = self._prompt_forward(
                params, ids, small, 0, length)
            new_caches = self._seat_lanes(caches, small, row, length)
            return self._first_token(logits, 0, new_caches, token, offsets,
                                     key, row, length, counts)
        return admit

    def _build_admit_paged(self):
        """Paged admission: the batch-1 prefill scatters straight into
        the freshly-allocated pages of the admitted row (its
        (w, 1, n_pages) slice of ``table``, cut in-graph) — no scratch
        cache, no row copy; the pool IS the row's storage (vLLM-style)."""
        model, mode = self.model, self.prefill_mode

        @jit_rewriting_caches
        def admit(params, pools, ids, length, row, table, token, offsets,
                  key):
            logits, pools = model.forward(
                params, ids, pools, 0, mode=mode, logits_at=length - 1,
                block_table=jax.lax.dynamic_slice_in_dim(table, row, 1,
                                                         axis=1))
            return self._first_token(logits, 0, pools, token, offsets,
                                     key, row, length)
        return admit

    def _build_admit_paged_prefix(self):
        """Prefix-cache-hit admission: only the prompt SUFFIX runs.

        The suffix's K/V scatter at absolute positions start+[0, S) and
        the attention over the shared cached-prefix blocks both go
        through the paged chunked-prefill path (dense.forward_sp: a
        traced nonzero offset with S > 1). ``start``/``length`` are
        traced, so jit compiles once per padded SUFFIX bucket — the pad
        tail is causally invisible to the real positions and its
        scattered pages sit beyond kv_len until decode overwrites
        them (the standard pad-slot safety argument)."""
        model, mode = self.model, self.prefill_mode

        @jit_rewriting_caches
        def admit(params, pools, ids, start, length, row, table, token,
                  offsets, key):
            logits, pools = model.forward(
                params, ids, pools, start, mode=mode,
                logits_at=length - 1,
                block_table=jax.lax.dynamic_slice_in_dim(table, row, 1,
                                                         axis=1))
            return self._first_token(logits, 0, pools, token, offsets,
                                     key, row, start + length)
        return admit

    def _build_admit_chunk(self):
        """One slice of a CHUNKED admission prefill: forward ``chunk``
        positions into the batch-1 scratch cache at ``offset`` (rope
        and causal mask from the absolute position — the plain
        ``_attention_core`` chunk-at-offset path). Compiled once per
        (chunk, scratch-length) pair; the serving scheduler interleaves
        these between shared decode steps so a long prompt's admission
        never stalls the rows already decoding (docs/serving.md).

        The slice takes the prompt's ``length`` and hands back the one
        logit row the admission may read, (1, 1, V): the last slice's is
        the prompt's last position. ``counts`` are a counting model's
        from the slices before this one (None otherwise) and come back
        with this slice's added: the admission's counts reach the host
        once, with its first token (_build_admit_finish)."""
        @jit_rewriting_caches
        def chunk_step(params, small, ids, offset, length, counts):
            logits, small, more = self._prompt_forward(
                params, ids, small, offset, length)
            return logits, small, more if counts is None else counts + more
        return chunk_step

    def _build_admit_finish(self):
        """Tail of a chunked admission: sample the first token from the
        final chunk's one logit row (the prompt's true last position),
        then scatter the scratch prefix into row ``row``'s lane — the
        same pad-slot safety argument as ``_build_admit`` (pad K/V are
        causally invisible and overwritten before any mask exposes
        them). A counting model's ``counts`` come along (else None)."""
        @jit_rewriting_caches
        def finish(small, caches, logits, length, row, counts, token,
                   offsets, key):
            new_caches = self._seat_lanes(caches, small, row, length)
            return self._first_token(logits, 0, new_caches, token, offsets,
                                     key, row, length, counts)
        return finish

    @staticmethod
    def _bucket_len(n: int) -> int:
        b = 8
        while b < n:
            b *= 2
        return b

    def stream_session(self, params) -> "StreamSession":
        """Open an incremental continuous-batching session over this
        engine's decode window (resets the KV cache). The serving
        scheduler drives one of these; ``serve_stream`` is the
        single-caller convenience driver."""
        return StreamSession(self, params)

    def serve_stream(self, params, prompts, gen_len: int,
                     stop_tokens=None) -> list:
        """Continuous batching (beyond the reference; vLLM-style): pump
        a stream of prompts through a fixed ``batch``-row decode window,
        admitting the next prompt into a row the moment its occupant
        finishes — no head-of-line blocking on the longest generation.

        Every row runs at its own cache position: admission resets the
        row's lane (batch-1 prefill scattered to slot 0, rope and mask
        from the per-row offset), so a freed row is reusable
        immediately. Greedy results equal serving each prompt alone
        (tests/test_engine_stream.py). Returns prompt+generated token
        lists in input order.

        Works across all three engine families:
          * dense tp — per-row offsets thread through
            ``_attention_core``'s scatter path; admission scatters a
            scratch prefill into the freed row's private lane;
          * sp (seq-sharded cache) — same, through ``forward_sp``'s
            per-row write/mask/rope path;
          * sp + paged — BLOCK-granular (ISSUE 6): admission maps any
            cached shared-prefix blocks into the row's lanes and
            allocates private blocks for the rest of the prompt, the
            table grows one block at a time as decode crosses page
            boundaries, and retirement returns blocks to the pool
            immediately. Unoccupied rows' lanes point at a per-device
            SENTINEL block, so frozen-row writes are harmless by
            construction; an oversubscribed pool simply admits fewer
            rows at a time instead of refusing to stream
            (docs/serving.md "Block-granular admission").
        """
        obs.counter("engine.serve_stream_calls").inc()
        b = self.kv.batch
        if stop_tokens is None:
            eos = getattr(self.model.config, "eos_token_id", -1)
            stop_tokens = (eos,) if eos >= 0 else ()
        stop_set = set(int(t) for t in stop_tokens)
        if gen_len <= 0:
            return [list(p) for p in prompts]
        n_req = len(prompts)
        assert all(len(p) for p in prompts), "prompts must be non-empty"
        assert all(len(p) + gen_len <= self.kv.max_seq for p in prompts), \
            "prompt + gen_len must fit max_seq"
        if self.paged:
            # Rejecting a never-fitting request up front keeps the
            # admission loop below deadlock-free: a queued head always
            # becomes admissible once enough rows retire.
            bad = [i for i, p in enumerate(prompts)
                   if not self.kv.fits_pool(len(p), gen_len)]
            assert not bad, (
                f"prompts {bad} can never fit the block pool "
                f"({self.kv.slots_per_dev} slots/device)")

        sess = self.stream_session(params)
        row_req = [None] * b                 # request id occupying a row
        row_budget = [0] * b                 # tokens left to generate
        results: list[list[int] | None] = [None] * n_req
        generated: dict[int, list[int]] = {}
        next_req = 0

        def record(r, tok: int):
            """Book one generated token for row r; retire the row when
            its budget is spent or a stop token lands. Returns True if
            the row was freed."""
            nonlocal row_req
            rid = row_req[r]
            generated[rid].append(tok)
            row_budget[r] -= 1
            if row_budget[r] <= 0 or tok in stop_set:
                results[rid] = list(prompts[rid]) + generated.pop(rid)
                row_req[r] = None
                sess.retire_row(r)
                return True
            return False

        def admit_free_rows():
            nonlocal next_req
            for r in range(b):
                if next_req >= n_req:
                    return
                while row_req[r] is None and next_req < n_req:
                    if not sess.can_admit(len(prompts[next_req]),
                                          gen_len):
                        # Not enough blocks yet: FIFO order holds, the
                        # head re-checks after the next retirement.
                        return
                    rid = next_req
                    next_req += 1
                    first = sess.prefill_into_row(r, prompts[rid],
                                                  gen_budget=gen_len)
                    row_req[r] = rid
                    row_budget[r] = gen_len
                    generated[rid] = []
                    # gen_len == 1 or an immediate stop frees the row
                    # again; the inner while then admits the next
                    # request into the same row.
                    record(r, first)

        admit_free_rows()
        while any(rid is not None for rid in row_req):
            # Variable tokens per row per iteration (ISSUE 13): the
            # base paths burst exactly one token, a speculative verify
            # step 1..k+1 — a row retiring mid-burst (stop token /
            # budget) discards the burst's tail, so outputs match the
            # sequential path exactly.
            bursts = sess.decode_burst()
            for r in range(b):
                for tok in bursts.get(r, ()):
                    if row_req[r] is None:
                        break
                    if record(r, int(tok)):
                        break
            admit_free_rows()
        assert all(r is not None for r in results), (
            "stream ended with unserved prompts — admission stalled "
            "with no live rows (block-pool accounting bug)")
        return results

    def serve_ragged(self, params, prompts, gen_len: int,
                     stop_tokens=None, pad_token: int = 0) -> list:
        """Serve prompts of DIFFERENT lengths in one batch.

        Left-pads to a rectangle; the pad prefix is invisible to
        attention (per-row ``kv_start`` mask) and rope positions count
        from each row's first real token — under greedy decoding the
        results match serving each prompt alone (stochastic sampling
        draws differ by batch position). Returns a list of 1-D arrays
        (prompt + generated, pads stripped).
        """
        b = len(prompts)
        lens = [len(p) for p in prompts]
        assert b and all(lens), "serve_ragged needs non-empty prompts"
        s = max(lens)
        ids = np.full((b, s), pad_token, np.int32)
        for i, pr in enumerate(prompts):
            ids[i, s - lens[i]:] = np.asarray(pr, np.int32)
        kv_start = jnp.asarray([s - L for L in lens], jnp.int32)
        out = np.asarray(self.serve(params, jnp.asarray(ids), gen_len,
                                    stop_tokens=stop_tokens,
                                    kv_start=kv_start))
        return [out[i, s - lens[i]:] for i in range(b)]


class StreamSession:
    """Incremental row-level API over an Engine's fixed decode window.

    Owns the mutable continuous-batching state (caches, per-row
    offsets, last tokens, live mask) that ``Engine.serve_stream`` used
    to keep in locals, exposed as the three verbs a scheduler drives:

    * :meth:`prefill_into_row` — admit a prompt into a free row: the
      whole prompt in one admission program, or (``chunk=N``) the
      first N tokens with the rest advanced by :meth:`prefill_step`
      between decode steps, so a long prompt's admission never stalls
      the rows already decoding; :meth:`launch_into_row` is the same
      verb for a driver that can take the first token later, with the
      next :meth:`decode_burst`'s (:meth:`take_first_tokens`);
    * :meth:`decode_step` — ONE shared decode step for every live row
      (frozen rows re-emit their token and do not advance);
      :meth:`decode_turn` is the step for a caller that can take its
      tokens a turn later, behind the next step's dispatch;
    * :meth:`retire_row` — free a finished row for the next admission.

    ``Engine.serve_stream`` is a thin single-caller driver over this
    class; the serving scheduler (``serving/scheduler.py``) is another
    — one that feeds rows from MANY client connections into the same
    batch. Exactly one thread may drive a session (the engine state is
    not locked).
    """

    def __init__(self, engine: Engine, params):
        self.engine = engine
        self.params = params
        b = engine.kv.batch
        # Admission buckets must split the way the prefill shards them.
        # sp prefill shards S over the sp axis: buckets must divide
        # (keyed on EITHER mode being "sp" — init asserts they only
        # come together, but the prefill is what shards S). The
        # row-sharded tp prefills (xla / ag_rs) hand each rank M/world
        # rows as its ring chunk, which the fused kernels can only
        # slice in whole row tiles (ops.common.ring_padded_rows).
        mesh = engine.model.mesh
        if "sp" in (engine.prefill_mode, engine.decode_mode):
            self._bucket_quantum = mesh.shape[engine.model.sp_axis]
        elif engine.prefill_mode in ("xla", "ag_rs"):
            from triton_dist_tpu.ops.common import ring_padded_rows
            self._bucket_quantum = ring_padded_rows(
                1, mesh.shape[engine.model.axis])
        else:
            self._bucket_quantum = 1
        engine.kv.reset()
        self.cur_table = None
        if engine.paged:
            # Block-granular mode (ISSUE 6): no lane pre-allocation —
            # the pool resets, every row's table lanes point at the
            # per-device sentinel block (so the shared decode step's
            # frozen-row writes are harmless by construction), and
            # admission/decode/retirement move individual blocks. An
            # oversubscribed pool streams fine: it just admits fewer
            # rows at a time (docs/serving.md "Block-granular
            # admission").
            engine.kv.stream_setup(prefix_cache=engine.prefix_cache)
            self.cur_table = engine.kv.block_table()
        self.caches = engine.kv.init()
        if engine._stream_step is None:
            engine._stream_step = engine._build_stream_step()
        if engine._admit is None:
            engine._admit = (engine._build_admit_paged() if engine.paged
                             else engine._build_admit())
        # Last token and write offset per row: handed to every stream
        # program and rebound to what it returns, never touched by an
        # eager op in between (host values until the first program).
        # A counting model's programs append their counts to it
        # (Engine._build_stream_step_counted).
        self.token = np.zeros((b + len(engine.count_names),), np.int32)
        self.offsets = np.zeros((b,), np.int32)
        self.live = [False] * b
        self._decode_kind: str | None = None  # decided path, unconsumed
        self._host_off = [0] * b     # host shadow of per-row offsets
        self._pending: dict[int, dict] = {}   # row → chunked-prefill state
        # Admissions launched and not read yet, (row, un-read first
        # token) in admission order, and what was read of them behind a
        # step, (row, token, perf_counter() at the read), until
        # take_first_tokens() hands it over.
        self._deferred: list[tuple] = []
        self._first_tokens: list[tuple] = []
        # The step decode_turn() left in flight, (un-read tokens, the
        # rows it served), until the next decode_turn() reads it.
        self._ahead: tuple | None = None
        # Speculative decoding (ISSUE 13): drafter + per-row budget
        # clamps; decode_burst() runs draft → widened verify → atomic
        # multi-token commit when this is set (docs/serving.md
        # "Speculative decoding").
        self.spec = None
        if engine.spec is not None:
            from triton_dist_tpu.models.spec import SpecState
            self.spec = SpecState(engine.spec, b, engine.kv.max_seq)
        #: Draft/verify wall time of the most recent decode_burst
        #: (None for base-path steps) — the scheduler folds these into
        #: each live request's attribution waterfall (obs.attrib).
        self.last_burst_timing: dict | None = None
        #: Facts about the most recent completed admission (currently
        #: the prefix-cached token count) — the scheduler reads this
        #: right after prefill_into_row/prefill_step returns a first
        #: token, for the request's latency-attribution waterfall
        #: (obs.attrib).
        self.admit_info: dict | None = None

    @property
    def batch(self) -> int:
        return self.engine.kv.batch

    def free_rows(self) -> list:
        """Rows with no occupant (neither live nor mid-prefill)."""
        return [r for r in range(self.batch)
                if not self.live[r] and r not in self._pending]

    def can_admit(self, prompt_len: int, gen_len: int,
                  extra=None) -> bool:
        """Block-granular admission control (paged engines): enough
        free/evictable blocks for this request's worst-case demand,
        net of live rows' commitments and of ``extra`` (an
        accumulated per-device demand for same-batch admissions not
        yet executed). Non-paged sessions always admit."""
        if not self.engine.paged:
            return True
        return self.engine.kv.can_admit(prompt_len, gen_len,
                                        extra=extra)

    def admission_need(self, prompt_len: int, gen_len: int):
        """Per-device worst-case block demand (the ``extra`` operand
        for :meth:`can_admit`); ``None`` for non-paged sessions."""
        if not self.engine.paged:
            return None
        return self.engine.kv.need_per_dev(prompt_len, gen_len)

    # -- admission ---------------------------------------------------------
    def prefill_into_row(self, row: int, prompt, chunk: int | None = None,
                         gen_budget: int | None = None):
        """Admit ``prompt`` into free row ``row``.

        Whole-prompt (``chunk=None``): runs the admission prefill now
        and returns the first sampled token (int). Chunked: runs only
        the first ``chunk``-token slice and returns ``None``; call
        :meth:`prefill_step` (between decode steps) until it returns
        the first token. Chunking applies to the non-paged, non-sp
        scratch-prefill path; other engine families fall back to the
        one-shot admission.

        ``gen_budget`` (paged engines): the tokens this request may
        still generate — the block-granular admission commits that many
        future blocks so a later admission cannot starve this row
        mid-decode. Both shipped drivers (serve_stream, the serving
        scheduler) pass it; omitting it risks a mid-decode pool
        exhaustion on a tight pool.

        This is :meth:`launch_into_row` with the first token read at
        once, whatever the admission is.
        """
        first = self.launch_into_row(row, prompt, chunk, gen_budget)
        if first is DEFERRED:
            first = self._collect_first(self._deferred.pop()[1])
        return first

    def launch_into_row(self, row: int, prompt, chunk: int | None = None,
                        gen_budget: int | None = None):
        """:meth:`prefill_into_row` for a driver that runs the shared
        step next and can take the first token after launching it.

        Returns what :meth:`prefill_into_row` returns, or ``DEFERRED``:
        the admission program is dispatched, the row is live and the
        session's state is the program's outputs, but the first token
        has not been read. The next :meth:`decode_burst` dispatches its
        step BEHIND the admission and only then reads it, so the chip
        goes from one program to the next while the host catches up;
        :meth:`take_first_tokens` hands it over. Which admissions are
        deferred the session decides from what it is (``_admit_whole``).
        """
        assert not self.live[row] and row not in self._pending, \
            f"row {row} is occupied"
        eng = self.engine
        n = len(prompt)
        assert n, "prompts must be non-empty"
        chunked = bool(
            chunk and not eng.paged and eng.prefill_mode != "sp"
            and n > chunk and -(-n // chunk) * chunk <= eng.kv.max_seq)
        if chunked and eng.kv.recurrent:
            raise NotImplementedError(
                f"{type(eng.model).__name__} has recurrent-state layers: "
                f"a chunked admission cannot carry their state from one "
                f"slice to the next yet (ROADMAP R8)")
        # The padded length the admission program will run: whole
        # chunks, else the prompt's bucket (a prefix-cache hit on the
        # paged path shrinks it to the suffix's, see _admit_paged).
        lb = (-(-n // chunk) * int(chunk) if chunked
              else min(self._bucket(n), eng.kv.max_seq))
        args = {"row": row, "prompt_len": n, "bucket": lb}
        with obs.span("engine.stream_admission", args=args):
            prompt = [int(t) for t in prompt]
            try:
                if chunked:
                    return self._start_chunked(row, prompt, int(chunk),
                                               lb, gen_budget=gen_budget)
                return self._admit_whole(row, prompt, lb, args,
                                         gen_budget=gen_budget)
            except Exception as e:
                self._check_caches(e)
                raise

    def take_first_tokens(self) -> list:
        """``[(row, token, t), ...]`` in admission order: the first
        tokens of the ``DEFERRED`` admissions that a step has been
        launched behind since the last call, ``t`` the
        ``time.perf_counter()`` at which each reached the host (while
        that step ran, before its own tokens)."""
        taken, self._first_tokens = self._first_tokens, []
        return taken

    def _check_caches(self, cause: BaseException) -> None:
        """Called on an exception out of an admission's CALL. A program
        call that raises (tracing, a host-side upload, a shape error)
        has consumed nothing and degrades its one request; should the
        caches it was given be gone all the same, that is every row's
        K/V, not only the admitted row's: say so, and name the culprit,
        instead of letting the next shared step die on "Array has been
        deleted". (A call that raised rebound nothing, so the leaves
        are the ones it was given. The other kind of failure, a
        program that dies on the device, surfaces where its first
        token is read: ``_collect_first``.)"""
        if any(leaf.is_deleted() for leaf in jax.tree.leaves(self.caches)):
            raise _cache_lost(cause) from cause

    def _bucket(self, n: int) -> int:
        """Power-of-two prompt bucket rounded up to a multiple of the
        prefill's row split (``_bucket_quantum``)."""
        lb = self.engine._bucket_len(n)
        return -(-lb // self._bucket_quantum) * self._bucket_quantum

    @staticmethod
    def _padded_ids(tokens, lb: int) -> np.ndarray:
        """The prompt as the (1, lb) int32 host buffer an admission
        program takes: zero-padded on the right to its bucket."""
        ids = np.zeros((1, lb), np.int32)
        ids[0, :len(tokens)] = tokens
        return ids

    def _launch_admission(self, program, head, *inputs):
        """THE dispatch of an admission: ``program(head, caches,
        *inputs, token, offsets, key)`` seats the row in-graph (its
        first token, its write offset, the next sampling key), so what
        the host adds is NumPy values that upload with the call.
        Returns the first token UNREAD, for ``_collect_first``.

        The session's state is rebound to the program's outputs at
        once: the next program, another admission or the shared step,
        takes them without anything having been read. A call that
        raises rebinds nothing."""
        eng = self.engine
        first, self.caches, self.token, self.offsets, eng.key = program(
            head, self.caches, *inputs, self.token, self.offsets, eng.key)
        return first

    def _collect_first(self, first) -> int:
        """The blocking read of a launched admission's first token (a
        counting model's counts come home behind it). An error HERE is
        the program's, after it was given the caches: they are lost
        whatever the leaves say, and the session with them."""
        try:
            first = np.asarray(first).reshape(-1)
        except Exception as e:
            raise _cache_lost(e) from e
        self._note_counts(first[1:])
        return int(first[0])

    def _run_admission(self, program, head, *inputs) -> int:
        """Launch and collect with nothing between them: every
        admission whose first token the host needs at once."""
        return self._collect_first(
            self._launch_admission(program, head, *inputs))

    def _collect_deferred(self) -> None:
        """Behind a step's dispatch: read the first tokens of the
        launched admissions, in admission order, each stamped as it
        reaches the host."""
        pending, self._deferred = self._deferred, []
        for row, first in pending:
            self._first_tokens.append(
                (row, self._collect_first(first), time.perf_counter()))
        obs.counter("engine.admit_deferred").inc(len(pending))

    def _note_counts(self, counts) -> None:
        """A counting model's in-program counts, as they came back
        behind a first token or a step's tokens, into the registry."""
        for name, n in zip(self.engine.count_names, counts.tolist()):
            if n:
                obs.counter(name).inc(n)

    def _admit_whole(self, row: int, prompt: list, lb: int, args: dict,
                     gen_budget: int | None = None):
        eng = self.engine
        if eng.paged:
            return self._admit_paged(row, prompt, lb, args, gen_budget)
        first = self._launch_admission(
            eng._admit, self.params, self._padded_ids(prompt, lb),
            np.int32(len(prompt)), np.int32(row))
        self.admit_info = {"cached": 0}
        self._count_admitted(len(prompt), lb, head_rows=1, whole=True)
        self._mark_admitted(row, len(prompt))
        if self.spec is None and (gen_budget or 0) > 1:
            # Nothing on the host needs the token's VALUE before the
            # next step is launched: the program seated it on the
            # device, and the step's done mask is the host's own. (A
            # drafter is seeded with it, and a request's only token
            # retires its row before any step: those read it now.)
            self._deferred.append((row, first))
            return DEFERRED
        first = self._collect_first(first)
        self._spec_start(row, prompt, first, gen_budget)
        return first

    def _admit_paged(self, row: int, prompt: list, lb: int, args: dict,
                     gen_budget: int | None) -> int:
        """Block-granular paged admission with cross-request prefix
        reuse: map cached prefix blocks into the row's lanes, then run
        only the SUFFIX through the prefill (the whole prompt when the
        cache misses). Greedy outputs are bit-identical to a cold
        prefill — the cached blocks hold exactly the K/V a cold prefill
        of the same tokens would write."""
        eng, kv = self.engine, self.engine.kv
        L = len(prompt)
        # Size the suffix program against the pool geometry BEFORE
        # claiming hits: the padded suffix bucket scatters at absolute
        # positions cached+[0, lb) and must not run off max_seq. Fewer
        # hits → longer suffix but more room; k=0 (the cold path, lb
        # clamped to max_seq) always fits.
        hashes = kv.prefix_hashes(prompt)
        k = kv.prefix_probe(prompt, hashes=hashes)
        while k > 0:
            if k * kv.page_size + self._bucket(L - k * kv.page_size) \
                    <= kv.max_seq:
                break
            k -= 1
        cached = kv.admit_row(row, prompt,
                              gen_budget=int(gen_budget or 0),
                              use_hits=k, hashes=hashes)
        try:
            # Inside the rollback window: the device upload itself can
            # raise (device OOM), and a failure after admit_row must
            # hand the row's blocks back like any program failure.
            self.cur_table = kv.block_table()
            # _run_admission materializes the first token in HERE: jit
            # returns futures, so an async runtime failure (device OOM,
            # comm error) would otherwise surface past the rollback
            # window and leave a zombie live row holding its blocks
            # forever.
            if cached:
                suffix = prompt[cached:]
                # Only the uncached suffix runs: the span's begin event
                # (which holds ``args``) says the bucket that ran.
                lb = args["bucket"] = self._bucket(len(suffix))
                if eng._admit_prefix is None:
                    eng._admit_prefix = eng._build_admit_paged_prefix()
                first = self._run_admission(
                    eng._admit_prefix, self.params,
                    self._padded_ids(suffix, lb), np.int32(cached),
                    np.int32(len(suffix)), np.int32(row), self.cur_table)
            else:
                first = self._run_admission(
                    eng._admit, self.params, self._padded_ids(prompt, lb),
                    np.int32(L), np.int32(row), self.cur_table)
        except Exception:
            # The program never ran to completion: hand the row's
            # blocks straight back (a stranded allocation is a slow
            # production OOM — the quick-tier leak audit's target).
            kv.release_row(row)
            self.cur_table = kv.block_table()
            raise
        kv.register_prefix(row, prompt, hashes=hashes)
        self._note_prefix(row, L, cached)
        self.admit_info = {"cached": cached}
        self._count_admitted(L - cached, lb, head_rows=1)
        self._mark_admitted(row, L)
        self._spec_start(row, prompt, first, gen_budget)
        return first

    def _note_prefix(self, row: int, prompt_len: int,
                     cached: int) -> None:
        """Prefix-cache telemetry for one admission
        (docs/observability.md): tokens saved, block-weighted hit
        rate, and a trace instant on the request's timeline."""
        kv = self.engine.kv
        if kv.prefix is None:
            return
        obs.counter("serving.prefill_tokens_saved").inc(cached)
        hits = obs.counter("serving.prefix_hit_blocks")
        hits.inc(cached // kv.page_size)
        lookups = obs.counter("serving.prefix_lookup_blocks")
        lookups.inc(kv.prefix_lookup_blocks(prompt_len))
        # Gauge derived from the cumulative counters, NOT the
        # session-local PrefixCache stats: a pump restart recreates the
        # cache object empty, and the documented contract is the
        # lifetime hit/lookup ratio of the sibling counters.
        if lookups.value > 0:
            obs.gauge("serving.prefix_hit_rate").set(
                round(hits.value / lookups.value, 4))
        if cached:
            _trace.instant("serving.prefix_hit", "serving",
                           args={"row": row, "prompt_len": prompt_len,
                                 "cached_tokens": cached})

    def _start_chunked(self, row: int, prompt: list, chunk: int,
                       lb: int, gen_budget: int | None = None):
        eng = self.engine
        if eng._admit_chunk is None:
            eng._admit_chunk = eng._build_admit_chunk()
            eng._admit_finish = eng._build_admit_finish()
        self._pending[row] = {
            "ids": self._padded_ids(prompt, lb), "len": len(prompt),
            "chunk": chunk, "pos": 0, "budget": gen_budget, "head_rows": 0,
            "counts": (np.zeros((len(eng.count_names),), np.int32)
                       if eng.count_names else None),
            "small": eng.kv.scratch(self.caches, lb)}
        return self._prefill_slice(row)

    def prefill_step(self, row: int):
        """Advance row ``row``'s chunked admission by one slice; returns
        the first sampled token (int) once the last slice lands, else
        ``None``."""
        st = self._pending[row]
        with obs.span("engine.stream_admission",
                      args={"row": row, "prompt_len": st["len"],
                            "bucket": st["ids"].shape[1]}):
            try:
                return self._prefill_slice(row)
            except Exception as e:
                self._check_caches(e)
                raise

    def _prefill_slice(self, row: int):
        """One slice, inside the caller's admission span (the first
        slice runs in ``prefill_into_row``'s)."""
        eng = self.engine
        st = self._pending[row]
        c = st["chunk"]
        ids = st["ids"][:, st["pos"]:st["pos"] + c]
        logits, st["small"], st["counts"] = eng._admit_chunk(
            self.params, st["small"], ids, np.int32(st["pos"]),
            np.int32(st["len"]), st["counts"])
        st["head_rows"] += logits.shape[1]      # what the slice computed
        st["pos"] += c
        if st["pos"] < st["ids"].shape[1]:
            return None
        del self._pending[row]
        first = self._run_admission(
            eng._admit_finish, st["small"], logits, np.int32(st["len"]),
            np.int32(row), st["counts"])
        self.admit_info = {"cached": 0}
        self._count_admitted(st["len"], st["ids"].shape[1],
                             head_rows=st["head_rows"])
        self._mark_admitted(row, st["len"])
        self._spec_start(row, st["ids"][0, :st["len"]].tolist(), first,
                         st.get("budget"))
        return first

    def cancel_prefill(self, row: int) -> None:
        """Drop a mid-chunk admission (its scratch cache was never
        scattered into the batch, so the session stays consistent)."""
        self._pending.pop(row, None)

    # -- disaggregated handoff (ISSUE 18) ----------------------------------
    def export_row(self, row: int, prompt) -> dict:
        """Extract row ``row``'s finished prompt KV blocks for a
        disaggregated handoff (serving/disagg.py): per-block packed
        payloads plus the dedup-eligible hash chain. Must run while
        the row still holds its blocks — the scheduler invokes the
        request's ``kv_export`` callback just BEFORE ``retire_row``
        (a retired row's private blocks return to the free stack and
        may be overwritten by the next admission)."""
        from triton_dist_tpu.serving import kv_stream
        eng, kv = self.engine, self.engine.kv
        assert eng.paged, "export_row needs a paged engine"
        prompt = [int(t) for t in prompt]
        L = len(prompt)
        n_blocks = kv_stream.block_span(L, kv.page_size)
        hashes = kv.prefix_hashes(prompt) or []
        lookup = kv.prefix_lookup_blocks(L)
        blocks = {}
        for j in range(n_blocks):
            r, lp = kv._block_lane(j)
            idx = (r * kv.phys_slots_per_dev
                   + int(kv._table[r, row, lp]))
            layers = [(np.asarray(pk[idx]), np.asarray(pv[idx]))
                      for pk, pv in self.caches]
            blocks[j] = kv_stream.pack_block(layers)
        return {"hashes": [h.hex() for h in hashes[:lookup]],
                "n_blocks": n_blocks, "blocks": blocks,
                "meta": {"layers": len(self.caches),
                         "page": kv.page_size,
                         "heads": kv.num_kv_heads,
                         "dim": kv.head_dim, "prompt_len": L}}

    def adopt_row(self, row: int, prompt, first: int,
                  gen_budget: int | None, blocks: dict) -> int:
        """Admit row ``row`` DECODE-ONLY from a verified handoff: no
        prefill program runs. The block allocator maps whatever prefix
        the local cache already holds (the dedup the ``kv_need``
        negotiation promised), the SHIPPED payloads are written into
        the privately-allocated remaining blocks, and the row starts
        decoding from the prefill side's first sampled token — under
        greedy decoding the output is bit-identical to a local prefill
        of the same prompt (the shipped blocks hold exactly the K/V a
        local prefill would have written; docs/serving.md
        "Disaggregated prefill/decode"). ``blocks`` maps block index →
        packed payload; a block neither held locally nor shipped fails
        the admission with ``ValueError`` (the caller's re-prefill
        fallback), with full rollback like any failed admission."""
        # bucket 0: no admission program runs, only the block uploads.
        with obs.span("engine.stream_admission",
                      args={"row": row, "prompt_len": len(prompt),
                            "bucket": 0}):
            return self._adopt_row(row, prompt, first, gen_budget, blocks)

    def _adopt_row(self, row: int, prompt, first: int,
                   gen_budget: int | None, blocks: dict) -> int:
        from triton_dist_tpu.serving import kv_stream
        eng, kv = self.engine, self.engine.kv
        assert eng.paged, "adopt_row needs a paged engine"
        assert not self.live[row] and row not in self._pending, \
            f"row {row} is occupied"
        prompt = [int(t) for t in prompt]
        assert prompt, "prompts must be non-empty"
        L = len(prompt)
        n_blocks = kv_stream.block_span(L, kv.page_size)
        hashes = kv.prefix_hashes(prompt)
        k = kv.prefix_probe(prompt, hashes=hashes)
        cached = kv.admit_row(row, prompt,
                              gen_budget=int(gen_budget or 0),
                              use_hits=k, hashes=hashes)
        try:
            self.cur_table = kv.block_table()
            k_blocks = cached // kv.page_size
            missing = [j for j in range(k_blocks, n_blocks)
                       if j not in blocks]
            if missing:
                raise ValueError(
                    f"adopt_row: blocks {missing} neither held "
                    f"locally nor shipped — incomplete handoff")
            shape = (kv.page_size, kv.num_kv_heads, kv.head_dim)
            caches = self.caches
            for j in range(k_blocks, n_blocks):
                layers = kv_stream.unpack_block(
                    blocks[j], len(caches), shape)
                r, lp = kv._block_lane(j)
                idx = (r * kv.phys_slots_per_dev
                       + int(kv._table[r, row, lp]))
                caches = [
                    (pk.at[idx].set(jnp.asarray(lk, pk.dtype)),
                     pv.at[idx].set(jnp.asarray(lv, pv.dtype)))
                    for (pk, pv), (lk, lv) in zip(caches, layers)]
            if n_blocks > k_blocks:
                # Materialize inside the rollback window, like the
                # admission programs: an async upload failure must not
                # leave a zombie live row holding its blocks.
                jax.block_until_ready(caches[0][0])
            self.caches = caches
        except Exception:
            kv.release_row(row)
            self.cur_table = kv.block_table()
            raise
        kv.register_prefix(row, prompt, hashes=hashes)
        self._note_prefix(row, L, cached)
        self.admit_info = {"cached": cached, "adopted": True}
        # No admission program ran to seat the row: the same in-graph
        # update, as one program of its own.
        self.token, self.offsets = _seat_row(
            self.token, self.offsets, np.int32(row), np.int32(first),
            np.int32(L))
        self._mark_admitted(row, L)
        self._spec_start(row, prompt, int(first), gen_budget)
        return int(first)

    def _count_admitted(self, ran: int, padded: int, *, head_rows: int,
                        whole: bool = False) -> None:
        """One admission's work: the tokens the request needed run (the
        uncached suffix on the paged path) and the padded length the
        program(s) ran; their ratio is the work the buckets waste.
        ``head_rows``: the logit rows its programs computed, 1 for each
        that was told the row it reads (``logits_at``) and its S for one
        that was not: beside the padded length, the share of the bucket
        that went through the output head. And
        its attention's: the query-key pairs a head scored, summed over
        the layers, beside the bucket's square — less than it where a
        ``whole``-bucket admission was read in query blocks
        (layers/tp_attn.prefill_positions_scored: what lies above a
        block's diagonal or before a window layer's band is not
        scored); every other admission path counts the square."""
        obs.counter("engine.admit_prompt_tokens").inc(ran)
        obs.counter("engine.admit_bucket_tokens").inc(padded)
        obs.counter("engine.admit_head_rows").inc(head_rows)
        model = self.engine.model
        # The softmax-attention layers: a model with other kinds names
        # them (``attn_windows``), the others are all of them.
        windows = (getattr(model, "attn_windows", None)
                   or getattr(model, "windows", None)
                   or (None,) * model.config.num_hidden_layers)
        square = len(windows) * padded * padded
        scored = square
        if whole and self.engine.prefill_mode != "sp":
            heads = model.attn.num_heads // model.mesh.shape[model.axis]
            scored = sum(n * prefill_positions_scored(heads, padded, w)
                         for w, n in collections.Counter(windows).items())
        obs.counter("attn.prefill_positions_scored").inc(scored)
        obs.counter("attn.prefill_positions_square").inc(square)
        kda = getattr(model, "kda_layers", 0)
        if kda:
            # The sequential chunks of the recurrent layers' scan: those
            # that hold a prompt position, of the bucket's
            # (layers/kda.scan_chunks).
            obs.counter("kda.scan_chunks_run").inc(
                kda * scan_chunks(ran, padded))
            obs.counter("kda.scan_chunks_bucket").inc(
                kda * scan_chunks(padded, padded))

    def _mark_admitted(self, row: int, prompt_len: int) -> None:
        """Host bookkeeping of an admission; the device's copy of the
        row's offset and token was written by the admission program."""
        obs.counter("engine.stream_admissions").inc()
        self._host_off[row] = prompt_len
        self.live[row] = True

    def _spec_start(self, row: int, prompt, first: int,
                    gen_budget) -> None:
        """Seed the drafter for a freshly-admitted row (no-op without
        spec). ``gen_budget`` bounds later bursts; both shipped
        drivers pass it — without it only the max_seq room clamps, so
        a tight paged pool could exhaust mid-burst."""
        if self.spec is not None:
            self.spec.start_row(row, prompt, first, gen_budget)

    # -- decode / retire ---------------------------------------------------
    def decode_kind(self) -> str:
        """The decode path the NEXT :meth:`decode_step` /
        :meth:`decode_burst` will run ("spec"/"mega"/"plain"): "spec"
        when the engine carries a SpecConfig (the burst may still fall
        back to the base path on a 0-draft iteration), otherwise the
        engine's static config or the auto policy's measured-gauge
        decision for the current batch. The scheduler calls this right
        before opening a devprof iteration window so the capture's
        ``device.step.<kind>`` label names the path that actually
        drove it; the decision is cached and consumed by the following
        step. Stream decode steps are samplable work, so these
        decisions may probe."""
        if self.spec is not None:
            self._decode_kind = "spec"
        else:
            self._decode_kind = self.engine.resolve_decode_path(
                samplable=True)
        return self._decode_kind

    def decode_burst(self) -> dict:
        """One shared decode ITERATION with variable tokens per row
        (ISSUE 13): ``{row: [tok, ...]}`` for every live row — exactly
        one token each on the base paths, 1..k+1 on a speculative
        verify step. ``serve_stream`` consumes this verb, the
        scheduler's pump through :meth:`decode_turn`; :meth:`decode_step`
        remains the single-token base-path step."""
        kind = self._decode_kind or self.decode_kind()
        self._decode_kind = None
        self.last_burst_timing = None
        if kind != "spec":
            toks = self._base_step(kind)
            return {r: [int(toks[r])] for r in range(self.batch)
                    if self.live[r]}
        return self._spec_burst()

    def decode_step(self) -> np.ndarray:
        """One shared BASE decode step: every live row decodes at its
        own cache position, frozen rows re-emit their token. Returns
        the (batch,) token vector as numpy.

        Runs the plain stream step or the mega one-program step per
        :meth:`decode_kind` — both are greedily bit-identical, so the
        auto policy may flip paths between steps of one request.
        (Speculative engines burst through :meth:`decode_burst`; this
        verb always runs the base path.)"""
        kind = self._decode_kind
        self._decode_kind = None
        if kind not in ("mega", "plain"):
            kind = self.engine.resolve_decode_path(samplable=True)
        return self._base_step(kind)

    def decode_turn(self, deliver) -> tuple:
        """:meth:`decode_burst` for a caller that can take a step's
        tokens a turn later: the scheduler's pump.

        Where the session can, the step is dispatched BEHIND the one
        left in flight by the last call and this turn's admissions, and
        only then is the step in flight read: the chip goes from step to
        step while the host books, retires and replies. ``deliver(firsts,
        bursts, earlier)`` is handed what was read, in the order it was
        read: the in-flight step's tokens (``earlier`` true: its rows are
        those of the call that dispatched it), then the first tokens of
        this turn's ``DEFERRED`` admissions (:meth:`take_first_tokens`'s
        form). Returns the rows of the step left in flight, ``()`` where
        none is.

        Which steps are left unread the session decides from what it
        is: the plain step of a session that defers its admissions (not
        paged, no drafter: its kind is "spec") with no chunked admission
        under way. Every
        other step is :meth:`decode_burst` itself, its tokens and first
        tokens delivered at once (``earlier`` false), after the step in
        flight, if any, was read. With no live row nothing is
        dispatched: the step in flight is read, and the pipeline is
        empty."""
        rows = tuple(r for r in range(self.batch) if self.live[r])
        kind = (self._decode_kind or self.decode_kind()) if rows else None
        if kind != "plain" or self.engine.paged or self._pending:
            self.drain_step(deliver)
            if rows:
                bursts = self.decode_burst()
                deliver(self.take_first_tokens(), bursts, False)
            return ()
        self._decode_kind = None
        prev, self._ahead = self._ahead, None
        step_fn, done = self._step_inputs(kind)
        # The span holds the step's start on the chip, which is the end
        # of the program before it: the read of that program closes it.
        with obs.span("engine.stream_step"):
            token = self._dispatch_step(step_fn, done)
            if prev is not None:
                obs.counter("engine.step_ahead").inc()
                deliver([], self._burst_of(*prev), True)
            if self._deferred:
                with obs.span("engine.first_token_wait"):
                    self._collect_deferred()
                deliver(self.take_first_tokens(), {}, False)
        self._ahead = (token, rows)
        return rows

    def drain_step(self, deliver) -> None:
        """Read the step :meth:`decode_turn` left in flight, if any, and
        hand its tokens to ``deliver`` (``earlier`` true); dispatch
        nothing. A caller that stops calls it last."""
        if self._ahead is not None:
            prev, self._ahead = self._ahead, None
            deliver([], self._burst_of(*prev), True)

    def _burst_of(self, token, rows) -> dict:
        """The blocking read of a dispatched step's tokens, as a burst
        for the rows it served."""
        tokens = self._read_step(token)
        return {r: [int(tokens[r])] for r in rows}

    def _base_step(self, kind: str) -> np.ndarray:
        step_fn, done = self._step_inputs(kind)
        with obs.span("engine.stream_step"):
            token = self._dispatch_step(step_fn, done)
            if self._deferred:
                # The step is queued behind this turn's admissions:
                # their first tokens are read only now, while the
                # device runs on, and the step's own after them.
                with obs.span("engine.first_token_wait"):
                    self._collect_deferred()
            if obs.enabled() or _trace.enabled():
                # Real step latency, not the async enqueue (same
                # observer cost as the serve() decode span).
                jax.block_until_ready(token)
        return self._read_step(token)

    def _step_inputs(self, kind: str) -> tuple:
        """What a base step is dispatched with besides the session's
        state, and its counters: ``(step program, done mask)``."""
        eng = self.engine
        if kind == "mega":
            if eng._stream_step_mega is None:
                eng._stream_step_mega = eng._build_stream_step_mega()
            step_fn = eng._stream_step_mega
        else:
            step_fn = eng._stream_step
        if eng.paged:
            # Incremental block allocation: grow any live row whose
            # NEXT write position crosses into an unallocated page —
            # the admission commitment guarantees the block is there.
            grew = False
            for r in range(len(self.live)):
                if self.live[r]:
                    grew |= eng.kv.ensure_position(r, self._host_off[r])
            if grew:
                self.cur_table = eng.kv.block_table()
        done = ~np.asarray(self.live)
        obs.counter(f"engine.decode_path.{kind}").inc()
        obs.counter("engine.decode_live_rows").inc(sum(self.live))
        if kind == "plain" and eng.decode_mode != "sp":
            # The cache positions this step's attention reads per row:
            # the host shadow of the window the step program picks
            # from the same live offsets (layers/tp_attn.window_chunks;
            # the mega and sp steps pass no kv_need).
            need = max((off + 1 for off, live
                        in zip(self._host_off, self.live) if live),
                       default=1)
            obs.counter("engine.decode_window_positions").inc(
                decode_window(need, eng.kv.max_seq))
        return step_fn, done

    def _dispatch_step(self, step_fn, done):
        """Split the key and dispatch a base step, inside the caller's
        ``engine.stream_step`` span; the session's state becomes the
        step's outputs at once. Returns its tokens unread."""
        eng = self.engine
        # The step still splits its key HERE, eagerly (two small device
        # programs, ~1 ms of dispatch on the v5e, wasted on a greedy
        # engine), and not in-graph as the admissions do. The
        # benchmark's clock check (benchmark/harness/hostspans.
        # paired_steps) wants the step program's START inside the span,
        # on a device timeline that half of all captures put 1.1-1.3 ms
        # early: where the chip is free when the step is launched (a
        # host-bound turn), it starts at this dispatch, and the ~1.6 ms
        # these dispatches make up before it survive that; the ~0.6 ms
        # of the bare dispatch did not (8 of 95 spans matched; PERF.md).
        # They go, through Engine._draw_key, once that check is
        # repaired.
        eng.key, sub = jax.random.split(eng.key)
        self.token, self.caches, self.offsets = step_fn(
            self.params, self.caches, self.token, self.offsets, sub,
            done, self.cur_table)
        for r in range(len(self.live)):
            if self.live[r]:
                self._host_off[r] += 1
        return self.token

    def _read_step(self, token) -> np.ndarray:
        """The blocking read of a base step's ``(batch,)`` tokens; a
        counting model's counts come home behind them."""
        tokens = np.asarray(token)
        if self.engine.count_names:
            self._note_counts(tokens[self.batch:])
            tokens = tokens[:self.batch]
        return tokens

    def _spec_burst(self) -> dict:
        """Draft → widened verify → atomic commit (ISSUE 13).

        The drafter proposes up to k tokens per live row (clamped to
        each row's remaining budget and max_seq room — models/spec.py
        SpecState.plan); ONE widened step scores every window position;
        the longest argmax-matching draft prefix plus the bonus token
        commit per row. Paged pools grow blocks for every position a
        row may KEEP before the step (multi-block ensure_position) and
        rewind the rejected tail after it (rollback_position — blocks
        freed, commitments restored, no leaks: tests/test_block_pool).
        A 0-draft iteration composes with the base paths: the plain/
        mega/auto machinery serves it unchanged."""
        eng = self.engine
        live_rows = [r for r in range(self.batch) if self.live[r]]
        timed = obs.enabled() or _trace.enabled()
        t0 = time.perf_counter() if timed else 0.0
        with obs.span("engine.spec_draft"):
            drafts = self.spec.plan(live_rows, self._host_off)
        t1 = time.perf_counter() if timed else 0.0
        k_step = max((len(d) for d in drafts.values()), default=0)
        if k_step == 0:
            # Nothing to verify: the base path serves this iteration
            # (mega/plain/auto arbitration included) — spec composes
            # with decode-path selection instead of replacing it.
            # samplable=False: the scheduler already labeled this
            # iteration's capture window device.step.spec (decode_kind
            # is "spec" for spec engines), so an auto-policy probe here
            # could never land in the device.step.mega/plain gauges the
            # policy reads — the unmeasurable-probe case the
            # samplable gate exists to prevent.
            obs.counter("serving.spec_fallback_steps").inc()
            kind = eng.resolve_decode_path(samplable=False)
            toks = self._base_step(kind)
            bursts = {r: [int(toks[r])] for r in live_rows}
            for r in live_rows:
                self.spec.observe(r, bursts[r])
            return bursts
        if eng.paged:
            # Cover every position a row may keep BEFORE the step
            # (writes happen in-program; an unallocated position lands
            # on the sentinel and would LOSE an accepted token's K/V).
            # Rows drafted narrower than k_step stay unallocated past
            # their own clamp — their pad writes are sentinel-routed.
            grew = False
            for r in live_rows:
                grew |= eng.kv.ensure_position(
                    r, self._host_off[r] + len(drafts[r]))
            if grew:
                self.cur_table = eng.kv.block_table()
        # Power-of-two k bucket (the admission-bucket pattern): jit
        # compiles one verify program per bucket, not per distinct
        # draft width — pad positions past a row's own drafts are
        # never accepted and their writes are sentinel-routed/dropped.
        k_w = 1
        while k_w < k_step:
            k_w *= 2
        b = self.batch
        toks_in = np.zeros((b, k_w + 1), np.int32)
        toks_in[:, 0] = np.asarray(self.token)
        for r in live_rows:
            d = drafts[r]
            toks_in[r, 1:1 + len(d)] = d
        if k_w not in eng._spec_step:
            eng._spec_step[k_w] = eng._build_spec_verify_step(k_w)
        step_fn = eng._spec_step[k_w]
        obs.counter("engine.decode_path.spec").inc()
        obs.counter("engine.decode_live_rows").inc(len(live_rows))
        with obs.span("engine.spec_verify"):
            nxt, self.caches = step_fn(self.params, self.caches, toks_in,
                                       self.offsets, self.cur_table)
            if timed:
                jax.block_until_ready(nxt)
        nxt = np.asarray(nxt)
        bursts: dict = {}
        n_drafted = n_accepted = n_emitted = 0
        rolled = False
        from triton_dist_tpu.models.spec import accept_greedy
        for r in live_rows:
            a, emitted = accept_greedy(drafts[r], nxt[r])
            if eng.paged and a < len(drafts[r]):
                rolled |= eng.kv.rollback_position(
                    r, self._host_off[r] + a)
            self._host_off[r] += a + 1
            bursts[r] = emitted
            self.spec.observe(r, emitted)
            n_drafted += len(drafts[r])
            n_accepted += a
            n_emitted += len(emitted)
        if rolled:
            self.cur_table = eng.kv.block_table()
        # Commit the device-side state from the host shadows (frozen
        # rows keep their stale offset/token like the base step).
        self.offsets = np.asarray(self._host_off, np.int32)
        tok_vec = toks_in[:, 0].copy()
        for r in live_rows:
            tok_vec[r] = bursts[r][-1]
        self.token = tok_vec
        self._note_spec(n_drafted, n_accepted, n_emitted)
        if timed:
            t2 = time.perf_counter()
            self.last_burst_timing = {
                "draft_ms": round((t1 - t0) * 1e3, 3),
                "verify_ms": round((t2 - t1) * 1e3, 3)}
        return bursts

    @staticmethod
    def _note_spec(drafted: int, accepted: int, emitted: int) -> None:
        """Speculation telemetry (docs/observability.md): cumulative
        counters plus the two derived gauges the acceptance bar names
        — accept rate (accepted/drafted) and emitted tokens per verify
        step (the tokens/s multiplier speculation buys)."""
        steps = obs.counter("serving.spec_steps")
        steps.inc()
        dc = obs.counter("serving.spec_draft_tokens")
        dc.inc(drafted)
        ac = obs.counter("serving.spec_accepted_tokens")
        ac.inc(accepted)
        ec = obs.counter("serving.spec_emitted_tokens")
        ec.inc(emitted)
        if dc.value > 0:
            obs.gauge("serving.spec_accept_rate").set(
                round(ac.value / dc.value, 4))
        if steps.value > 0:
            obs.gauge("serving.spec_tokens_per_step").set(
                round(ec.value / steps.value, 4))

    def retire_row(self, row: int) -> None:
        """Free a finished row; the next admission may reuse its lane
        immediately. Paged engines release the row's blocks EAGERLY —
        shared prefix blocks drop a reference (refcount-zero indexed
        blocks stay cached, LRU-evictable), private blocks return to
        the free stack, and the row's lanes point back at the sentinel
        so its frozen writes stay harmless. A step left in flight
        (:meth:`decode_turn`) still brings the row's token home; what
        the caller makes of it is its own business."""
        self.live[row] = False
        if self.spec is not None:
            self.spec.retire_row(row)
        if self.engine.paged:
            self.engine.kv.release_row(row)
            self.cur_table = self.engine.kv.block_table()

    def close(self) -> None:
        """Release whatever the session still holds: every live (or
        mid-prefill) row retires, returning its blocks to the pool.
        Rows that already retired released eagerly — a block still
        active for a retired row after close() is a leak the
        quick-tier audit flags (tests/test_scheduler.py)."""
        self._pending.clear()
        self._deferred.clear()
        self._ahead = None
        for r in range(self.batch):
            if self.live[r]:
                self.retire_row(r)
