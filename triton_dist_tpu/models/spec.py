"""Speculative decoding: drafters + acceptance for the shared batch.

ISSUE 13 / ROADMAP item 3 — the scheduler's "exactly one token per row
per pump iteration" invariant generalized to 0..k tokens. A DRAFTER
proposes up to ``k`` continuation tokens per live row; the target model
scores every draft position in ONE widened decode step (the verify
window, ``Engine._build_spec_verify_step`` — compiled per k like the
chunked-prefill programs); the longest draft prefix matching the
target's own greedy argmax commits atomically, plus the target's next
token after it (the "bonus" token — under greedy acceptance the emitted
stream is BIT-IDENTICAL to non-speculative decode, which is the whole
acceptance bar: a verify window's logits equal k+1 sequential decode
steps' logits, and every emitted token is the target's argmax).

Two drafters:

- :class:`NGramDrafter` (default, model-free): prompt-lookup /
  n-gram continuation — the most recent earlier occurrence of the
  row's trailing n-gram proposes the tokens that followed it. Zero
  model cost; it wins exactly on repetition-heavy workloads (code,
  templated text, self-repeating greedy decodes).
- :class:`ModelDrafter`: a small model (e.g. ``presets.qwen3_0_6b``
  drafting for an 8B/32B target — :func:`draft_model_from_preset`
  shares the preset machinery) runs its own per-row KV cache in
  lockstep with the committed stream: each burst it first ingests the
  newly committed tokens (catch-up), then autoregressively drafts k
  tokens into scratch cache positions the next catch-up overwrites.

:class:`SpecState` owns the per-row bookkeeping a
``StreamSession`` needs (drafter lifecycle, remaining-budget clamps so
a burst can never write past the row's admission commitment or
max_seq) and the pure acceptance rule (:func:`accept_greedy`).
Greedy-only by design: ``Engine(spec=...)`` refuses stochastic
sampling — correct spec sampling needs rejection-resampling, and the
bit-identity guarantee is the contract everything here is tested
against (docs/serving.md "Speculative decoding").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from triton_dist_tpu import obs
from triton_dist_tpu.models.kv_cache import (
    KVCacheManager, jit_rewriting_caches)

__all__ = ["DEFAULT_K", "SpecConfig", "NGramDrafter", "ModelDrafter",
           "SpecState", "accept_greedy", "draft_model_from_preset"]

#: Default maximum draft tokens per row per verify step.
DEFAULT_K = 4


class SpecConfig:
    """Speculative-decoding configuration for ``Engine(spec=...)``.

    ``k``: max draft tokens per row per step (``TDT_SPEC_K`` env
    overrides; each verify step emits 1..k+1 tokens per live row).
    ``drafter``: ``"ngram"`` (model-free prompt lookup, default) or
    ``"model"`` (requires ``draft_model`` + ``draft_params`` — a small
    model sharing the target's vocabulary).
    ``ngram_n``: longest trailing n-gram the lookup drafter matches
    (falls back through shorter n-grams down to 1).
    ``TDT_SPEC=0`` disables speculation process-wide (the engine then
    behaves exactly as ``spec=None``) — the kill switch is env so a
    misbehaving drafter can be turned off without a redeploy.
    """

    def __init__(self, k: int | None = None, drafter: str = "ngram",
                 ngram_n: int = 3, draft_model=None, draft_params=None,
                 draft_mode: str = "xla_ar"):
        import os
        if k is None:
            k = obs.env_int("TDT_SPEC_K", DEFAULT_K, minimum=1)
        if k < 1:
            raise ValueError(f"SpecConfig.k must be >= 1: {k}")
        if drafter not in ("ngram", "model"):
            raise ValueError(
                f"SpecConfig.drafter must be 'ngram' or 'model': "
                f"{drafter!r}")
        if drafter == "model" and (draft_model is None
                                   or draft_params is None):
            raise ValueError(
                "drafter='model' needs draft_model= and draft_params= "
                "(a small preset sharing the target's vocab — "
                "spec.draft_model_from_preset)")
        if ngram_n < 1:
            raise ValueError(f"SpecConfig.ngram_n must be >= 1: "
                             f"{ngram_n}")
        self.k = int(k)
        self.drafter = drafter
        self.ngram_n = int(ngram_n)
        self.draft_model = draft_model
        self.draft_params = draft_params
        self.draft_mode = draft_mode
        self.enabled = os.environ.get("TDT_SPEC", "1").strip() != "0"


def draft_model_from_preset(name: str, mesh=None, axis: str = "tp",
                            impl: str = "xla", **overrides):
    """Build a drafter model from a named preset (``models.presets``)
    — the qwen3-0.6b-drafts-for-qwen3-8b/32b pairing the reference's
    model menu implies. Returns the (uninitialized) model; load or
    init params with the same checkpoint machinery as any model, then
    pass both to ``SpecConfig(drafter="model", ...)``."""
    from triton_dist_tpu.models import presets
    from triton_dist_tpu.models.dense import DenseLLM
    if name not in presets.PRESETS:
        raise ValueError(f"unknown preset {name!r} "
                         f"(known: {sorted(presets.PRESETS)})")
    cfg = presets.PRESETS[name](**overrides)
    return DenseLLM(cfg, mesh=mesh, axis=axis, impl=impl)


def accept_greedy(draft: list, target: np.ndarray) -> tuple:
    """The greedy acceptance rule for one row: ``target`` holds the
    verify window's argmax at positions 0..k (``target[i]`` = the
    target model's next token after consuming draft position i-1, with
    ``target[0]`` following the last committed token). Returns
    ``(accepted, emitted)`` — the longest prefix of ``draft`` the
    target reproduces, and the tokens the row emits this burst
    (``accepted + 1``: the accepted prefix re-emitted from the
    target's own argmax, plus the bonus token after it). Bit-identity
    with sequential decode is by construction: every emitted token IS
    the target's argmax given exactly the committed prefix."""
    a = 0
    while a < len(draft) and int(draft[a]) == int(target[a]):
        a += 1
    return a, [int(t) for t in target[:a + 1]]


class NGramDrafter:
    """Model-free prompt-lookup drafter.

    Per row, the committed token stream (prompt + emitted) is indexed
    by its n-grams (for n = ``ngram_n`` down to 1, most recent
    occurrence wins): a draft looks up the stream's trailing n-gram
    and proposes the tokens that followed its previous occurrence.
    O(ngram_n) per committed token, O(ngram_n + k) per draft — cheap
    enough that a miss (empty draft) costs nothing but the lookup."""

    def __init__(self, k: int, ngram_n: int = 3):
        self.k = int(k)
        self.n = int(ngram_n)
        self._hist: dict[int, list] = {}
        self._index: dict[int, list] = {}   # row -> [dict per n]

    def start_row(self, row: int, prompt) -> None:
        self._hist[row] = []
        self._index[row] = [dict() for _ in range(self.n)]
        self.observe(row, prompt)

    def retire_row(self, row: int) -> None:
        self._hist.pop(row, None)
        self._index.pop(row, None)

    def observe(self, row: int, tokens) -> None:
        """Append committed tokens; index the n-grams that now have a
        known continuation (the gram ENDING one before each new token,
        so a lookup always finds a non-empty continuation)."""
        h = self._hist[row]
        idx = self._index[row]
        for t in tokens:
            h.append(int(t))
            p = len(h) - 1          # position of the continuation t
            for n in range(1, self.n + 1):
                if p >= n:
                    idx[n - 1][tuple(h[p - n:p])] = p

    def draft_batch(self, rows, kmax: dict) -> dict:
        return {r: self._draft(r, kmax[r]) for r in rows}

    def _draft(self, row: int, kmax: int) -> list:
        h = self._hist[row]
        idx = self._index[row]
        kmax = min(self.k, kmax)
        if kmax <= 0:
            return []
        for n in range(min(self.n, len(h)), 0, -1):
            p = idx[n - 1].get(tuple(h[-n:]))
            if p is not None and p < len(h):
                return h[p:p + kmax]
        return []


class ModelDrafter:
    """Small-model drafter: its own per-row KV cache follows the
    COMMITTED stream (never the drafts).

    Admission prefills the prompt through a bucketed batch-1 program
    scattered into the row's lane (the engine's admission pattern);
    each ``draft_batch`` first CATCHES UP — ingesting the tokens the
    target committed since the last draft, one shared (B,)-row step
    per token (rows with nothing pending ride along frozen; their
    scratch writes are overwritten before any mask exposes them) —
    then drafts autoregressively from the last catch-up step's argmax,
    writing k-1 scratch positions the next catch-up overwrites. The
    drafter's committed offset therefore always equals the target's,
    which is what makes its proposals conditionally correct."""

    def __init__(self, model, params, k: int, batch: int, max_seq: int,
                 mode: str = "xla_ar"):
        self.model, self.params = model, params
        self.k = int(k)
        self.mode = mode
        c = model.config
        self.max_seq = int(max_seq)
        self.kv = KVCacheManager(
            c.num_hidden_layers, batch, max_seq, c.num_key_value_heads,
            c.head_dim, mesh=model.mesh, axis=model.axis, dtype=c.dtype)
        self.caches = self.kv.init()
        self.batch = batch
        self._off = [0] * batch          # committed ingest position
        self._pending: dict[int, list] = {}
        self._seed: dict[int, int] = {}  # argmax after last catch-up
        self._step = None
        self._admit = None

    # -- jitted programs ---------------------------------------------------
    def _build_step(self):
        model, mode = self.model, self.mode

        @jit_rewriting_caches
        def step(params, caches, token, offsets):
            logits, caches = model.forward(params, token[:, None],
                                           caches, offsets, mode=mode)
            return (jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32),
                    caches)
        return step

    def _build_admit(self):
        model, mode = self.model, self.mode

        @jit_rewriting_caches
        def admit(params, caches, ids, row):
            lb = ids.shape[1]
            small = [(jnp.zeros((1, lb) + ck.shape[2:], ck.dtype),
                      jnp.zeros((1, lb) + cv.shape[2:], cv.dtype))
                     for ck, cv in caches]
            _, small = model.forward(params, ids, small, 0, mode=mode)
            out = []
            for (ck, cv), (sk, sv) in zip(caches, small):
                ck = jax.lax.dynamic_update_slice(ck, sk, (row, 0, 0, 0))
                cv = jax.lax.dynamic_update_slice(cv, sv, (row, 0, 0, 0))
                out.append((ck, cv))
            return out
        return admit

    @staticmethod
    def _bucket(n: int) -> int:
        b = 8
        while b < n:
            b *= 2
        return b

    # -- row lifecycle -----------------------------------------------------
    def start_row(self, row: int, prompt) -> None:
        prompt = [int(t) for t in prompt]
        assert len(prompt) <= self.max_seq, "draft cache too small"
        if self._admit is None:
            self._admit = self._build_admit()
        lb = min(self._bucket(len(prompt)), self.max_seq)
        ids = np.zeros((1, lb), np.int32)
        ids[0, :len(prompt)] = prompt
        self.caches = self._admit(self.params, self.caches, ids,
                                  np.int32(row))
        self._off[row] = len(prompt)
        self._pending[row] = []
        self._seed.pop(row, None)

    def retire_row(self, row: int) -> None:
        self._pending.pop(row, None)
        self._seed.pop(row, None)

    def observe(self, row: int, tokens) -> None:
        self._pending[row].extend(int(t) for t in tokens)

    # -- drafting ----------------------------------------------------------
    def draft_batch(self, rows, kmax: dict) -> dict:
        if self._step is None:
            self._step = self._build_step()
        rows = [r for r in rows]
        # Phase 1 — catch-up: ingest pending committed tokens, one
        # shared step per token. A row whose pending ran out rides
        # along frozen (offset pinned; its scratch write at its own
        # next position is overwritten by its next real ingest before
        # any consumed output attends it).
        while any(self._pending.get(r) for r in rows):
            toks = np.zeros((self.batch,), np.int32)
            active = []
            for r in rows:
                pend = self._pending.get(r)
                if pend:
                    toks[r] = pend.pop(0)
                    active.append(r)
                else:
                    toks[r] = self._seed.get(r, 0)
            nxt, self.caches = self._step(
                self.params, self.caches, jnp.asarray(toks),
                jnp.asarray(self._off, jnp.int32))
            nxt = np.asarray(nxt)
            for r in active:
                self._off[r] += 1
                if not self._pending[r]:
                    self._seed[r] = int(nxt[r])
        # Phase 2 — autoregressive drafting from each row's seed into
        # scratch positions (committed offsets NOT advanced; the next
        # catch-up overwrites these writes).
        lim = {r: min(self.k, kmax[r], self.max_seq - 1 - self._off[r])
               for r in rows}
        k_step = max((lim[r] for r in rows), default=0)
        drafts = {r: [] for r in rows}
        if k_step <= 0:
            return {r: [] for r in rows}
        cur = np.zeros((self.batch,), np.int32)
        for r in rows:
            if lim[r] >= 1 and r in self._seed:
                drafts[r].append(self._seed[r])
            cur[r] = self._seed.get(r, 0)
        for i in range(1, k_step):
            nxt, self.caches = self._step(
                self.params, self.caches, jnp.asarray(cur),
                jnp.asarray(self._off, jnp.int32) + jnp.int32(i - 1))
            nxt = np.asarray(nxt)
            for r in rows:
                if len(drafts[r]) == i and lim[r] > i:
                    drafts[r].append(int(nxt[r]))
            cur = nxt.astype(np.int32)
        return drafts


class SpecState:
    """Per-session speculative-decoding state a ``StreamSession``
    drives: drafter lifecycle + the per-row budget/room clamps that
    keep a burst's writes inside the row's admission commitment and
    ``max_seq`` (docs/serving.md "Speculative decoding")."""

    def __init__(self, cfg: SpecConfig, batch: int, max_seq: int):
        self.cfg = cfg
        self.max_seq = int(max_seq)
        self._budget: dict[int, int | None] = {}
        if cfg.drafter == "model":
            self.drafter = ModelDrafter(cfg.draft_model,
                                        cfg.draft_params, cfg.k, batch,
                                        max_seq, mode=cfg.draft_mode)
        else:
            self.drafter = NGramDrafter(cfg.k, cfg.ngram_n)

    def start_row(self, row: int, prompt, first_token: int,
                  gen_budget: int | None) -> None:
        """Row admitted: seed the drafter with prompt + the admission's
        first token; ``gen_budget`` (tokens the row may still emit,
        INCLUDING the first token) bounds every later burst so spec
        writes never outrun the admission's block commitment."""
        self.drafter.start_row(row, prompt)
        self.drafter.observe(row, [int(first_token)])
        self._budget[row] = (int(gen_budget) - 1
                             if gen_budget else None)

    def observe(self, row: int, tokens) -> None:
        self.drafter.observe(row, tokens)
        if self._budget.get(row) is not None:
            self._budget[row] -= len(tokens)

    def retire_row(self, row: int) -> None:
        self.drafter.retire_row(row)
        self._budget.pop(row, None)

    def plan(self, rows, host_off) -> dict:
        """Clamped drafts per live row. A burst with n drafts writes
        positions offset..offset+n and emits <= n+1 tokens, so n is
        capped at (remaining budget - 1) — keeping writes inside the
        committed positions [0, L+G-2] — and at max_seq-1-offset."""
        kmax = {}
        for r in rows:
            room = self.max_seq - 1 - int(host_off[r])
            bud = self._budget.get(r)
            lim = room if bud is None else min(bud - 1, room)
            kmax[r] = max(0, min(self.cfg.k, lim))
        drafts = self.drafter.draft_batch(rows, kmax)
        return {r: list(drafts.get(r) or [])[:kmax[r]] for r in rows}
