"""KV cache (reference ``KV_Cache``,
python/triton_dist/models/kv_cache.py: per-layer (B, T, Hkv, D) tensors +
a host-side offset with ``inc_offset``).

Functional JAX shape: the cache is a pytree (list of per-layer (k, v)
pairs) threaded through the forward; ``KVCacheManager`` owns allocation,
sharding, and the offset bookkeeping the reference keeps on the module.
Head-sharded over TP by default (each rank caches its local heads — same
as the reference, which caches after the column-parallel KV projection);
``seq_shard=True`` shards the T dim instead for SP decode
(ops/flash_decode.py). A layer may keep another kind of cache instead
(``kinds``): a latent lane (:class:`LatentLane`) or recurrent state
(:class:`StateLanes`), replicated.
"""

from __future__ import annotations

import collections
import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_dist_tpu import obs


class KVCacheLost(RuntimeError):
    """A program that had been given (donated) a session's KV caches
    failed after dispatch: the buffers are gone, and every row's K/V
    with them. The session is dead; its driver reopens one."""


def jit_rewriting_caches(fn, cache_argnum: int = 1):
    """``jax.jit`` for a program that takes the KV caches as argument
    ``cache_argnum`` and returns them rewritten.

    The caches are DONATED: every caller rebinds its caches to the
    program's output, so the input buffers are garbage the moment the
    call returns, and XLA may alias them to the output and write the
    new positions in place instead of copying every leaf first (one
    whole-cache copy per decode step and per admission otherwise). The
    caller's old leaves are deleted by the call — a program that fails
    after dispatch takes the caches with it (``KVCacheLost``).

    A donated leaf only aliases an output of its own shape and dtype
    (JAX's own rule), so that is checked where it is free: while
    tracing, once per compile. A program whose caches changed shape or
    dtype on the way out counts under
    ``engine.cache_donation_fallbacks`` and runs (copying): the counter
    reads 0 while the mechanism is engaged, and the benchmark counts
    any ``fallback`` counter against ``correct``."""
    @functools.wraps(fn)
    def program(*args):
        out = fn(*args)
        spare = collections.Counter(
            (x.shape, x.dtype) for x in jax.tree.leaves(out))
        spare.subtract(
            (x.shape, x.dtype) for x in jax.tree.leaves(args[cache_argnum]))
        if min(spare.values()) < 0:
            obs.counter("engine.cache_donation_fallbacks").inc()
        return out
    return jax.jit(program, donate_argnums=(cache_argnum,))


def _zero_leaves(shape, dtype, sharding, num_layers: int):
    """[(k, v)] * L of zeros, every leaf a buffer of its own: the
    programs that rewrite the caches donate them, and one buffer under
    two leaves cannot be donated twice in one call."""
    return [(jnp.zeros(shape, dtype, device=sharding),
             jnp.zeros(shape, dtype, device=sharding))
            for _ in range(num_layers)]


def ring_lane(prefix: jax.Array, length, window: int) -> jax.Array:
    """The ring a sliding-window layer keeps of one row, from the K (or
    V) of the row's prompt: ``prefix`` (1, S, Hkv, D) holds positions
    [0, S), of which the first ``length`` (traced) are the prompt's.
    Slot ``p % window`` of the result (1, window, Hkv, D) holds position
    ``p`` for the last ``window`` positions of the prompt; a slot the
    prompt has not reached holds an arbitrary position's K/V, which the
    decode step's mask hides until the row's own step overwrites it
    (layers/tp_attn._attend_ring)."""
    from triton_dist_tpu.layers.tp_attn import ring_positions
    pos = ring_positions(jnp.asarray(length, jnp.int32) - 1, window)
    return jnp.take(prefix, jnp.clip(pos, 0, prefix.shape[1] - 1), axis=1)


@dataclasses.dataclass(frozen=True)
class LatentLane:
    """A latent-attention layer's cache: ONE (B, T, ``width``) leaf, a
    row's compressed K/V per position (``layers/mla``). ``width`` is the
    stored width, the latent's rounded up to whole 128-wide lane tiles
    (``MLAttn.latent_width``): the TPU then keeps the positions major
    and the decode step writes and reads the leaf as stored, with no
    transposed copy. Seated at an admission like K and V: the prompt's
    positions at slot 0."""
    width: int


@dataclasses.dataclass(frozen=True)
class StateLanes:
    """A recurrent layer's cache: leaves (B,) + shape, one per
    ``(shape, dtype)`` of ``shapes``, that do not grow with the context
    (``layers/kda``: the state matrices and the conv's tail). An
    admission computes them at the prompt's true length and REPLACES the
    row's whole; a row's decode step rewrites them in place."""
    shapes: tuple


class KVCacheManager:
    """Per-layer (B, T, Hkv, D) caches. ``windows`` (one entry per layer:
    a sliding window, or None) gives a window layer a RING of ``window``
    positions per row in place of ``max_seq``: its cache does not grow
    with the context. ``kinds`` (one entry per layer: a
    :class:`LatentLane` or :class:`StateLanes`) gives the layers other
    kinds of cache; every layer's cache is a tuple of leaves whose first
    axis is the row."""

    def __init__(self, num_layers: int, batch: int, max_seq: int,
                 num_kv_heads: int, head_dim: int,
                 mesh: Mesh | None = None, axis: str = "tp",
                 dtype=jnp.bfloat16, seq_shard: bool = False,
                 windows=None, kinds=None):
        if mesh is None:
            from triton_dist_tpu.runtime.dist import get_mesh
            mesh = get_mesh()
        self.mesh, self.axis = mesh, axis
        self.num_layers = num_layers
        self.batch, self.max_seq = batch, max_seq
        self.num_kv_heads, self.head_dim = num_kv_heads, head_dim
        self.dtype = dtype
        self.seq_shard = seq_shard
        spec = P(None, axis) if seq_shard else P(None, None, axis)
        self.sharding = NamedSharding(mesh, spec)
        self.offset = 0  # host-side write position (reference kv_offset)
        windows = tuple(windows or ())
        assert not any(windows) or (len(windows) == num_layers
                                    and not seq_shard), windows
        self.windows = windows if any(windows) else ()
        self.kinds = tuple(kinds or ())
        assert not self.kinds or (
            len(self.kinds) == num_layers and not seq_shard
            and not self.windows and all(isinstance(
                k, (LatentLane, StateLanes)) for k in self.kinds)), kinds

    @property
    def recurrent(self) -> bool:
        """Whether some layer keeps recurrent state (:class:`StateLanes`)."""
        return any(isinstance(k, StateLanes) for k in self.kinds)

    def init(self):
        """Allocate the cache pytree: [(k, v)] * L, a layer of another
        kind its own leaves."""
        shape = (self.batch, self.max_seq, self.num_kv_heads, self.head_dim)
        if self.kinds:
            whole = NamedSharding(self.mesh, P())
            return [(jnp.zeros((self.batch, self.max_seq, kind.width),
                               self.dtype, device=whole),)
                    if isinstance(kind, LatentLane) else
                    tuple(jnp.zeros((self.batch,) + tuple(s), dt,
                                    device=whole) for s, dt in kind.shapes)
                    for kind in self.kinds]
        if not self.windows:
            return _zero_leaves(shape, self.dtype, self.sharding,
                                self.num_layers)
        return [_zero_leaves((self.batch, w or self.max_seq) + shape[2:],
                             self.dtype, self.sharding, 1)[0]
                for w in self.windows]

    def scratch(self, caches, lb: int) -> list:
        """What an admission's prefill starts from, traced: per layer
        a batch-1 copy of its leaves, zeros, a positional leaf (K, V, a
        latent lane) ``lb`` positions long."""
        kinds = self.kinds or (None,) * len(caches)
        return [tuple(jnp.zeros((1,) + c.shape[1:] if isinstance(
            kind, StateLanes) else (1, lb) + c.shape[2:], c.dtype)
            for c in layer) for kind, layer in zip(kinds, caches)]

    def lane(self, layer: int, prefix: jax.Array, length) -> jax.Array:
        """What layer ``layer`` keeps of an admitted row whose prompt's
        K (or V) is ``prefix`` (1, S, Hkv, D): the prefix itself, or a
        window layer's ring of its last positions. A latent lane's or a
        state's leaf is kept as the admission left it."""
        if not self.windows or not self.windows[layer]:
            return prefix
        return ring_lane(prefix, length, self.windows[layer])

    def inc_offset(self, n: int) -> int:
        """Advance the write position (reference ``inc_offset``)."""
        self.offset += n
        assert self.offset <= self.max_seq, "KV cache overflow"
        return self.offset

    def reset(self):
        self.offset = 0


class PagedKVCacheManager:
    """Paged KV pools + block tables for SP decode serving.

    Integrates ``ops.flash_decode.gqa_fwd_batch_decode_paged`` (reference
    paged split-KV kernels, flash_decode.py:130-393) with a host-side
    slot allocator: each SP device owns a pool of ``slots_per_dev``
    physical (page_size, Hkv, D) pages and backs global positions
    [r*t_loc, (r+1)*t_loc) of every sequence. Sequences allocate their
    logical pages from per-device free lists (``alloc_seq``/``free_seq``
    — vLLM-style paging; the reference manages tables statically in its
    megakernel attn task).

    Layout contract (matches gqa_fwd_batch_decode_paged):
      pool_k/pool_v: (w*phys_slots_per_dev, page_size, Hkv, D), dim 0
                     sharded. phys_slots_per_dev = slots_per_dev + 1:
                     the last physical page per device is the reserved
                     SENTINEL (stream sessions point unoccupied rows at
                     it) and lives OUTSIDE the accounted pool, so the
                     full slots_per_dev capacity stays allocatable.
      block_table:   (w, B, pages_per_seq_dev) int32, dim 0 sharded,
                     entries are device-LOCAL slot ids.
    """

    def __init__(self, num_layers: int, batch: int, page_size: int,
                 pages_per_seq_dev: int, num_kv_heads: int, head_dim: int,
                 mesh: Mesh | None = None, axis: str = "tp",
                 dtype=jnp.bfloat16, slots_per_dev: int | None = None):
        if mesh is None:
            from triton_dist_tpu.runtime.dist import get_mesh
            mesh = get_mesh()
        self.mesh, self.axis = mesh, axis
        self.world = mesh.shape[axis]
        self.num_layers = num_layers
        self.batch = batch
        self.page_size = page_size
        self.pages_per_seq_dev = pages_per_seq_dev
        self.t_loc = page_size * pages_per_seq_dev
        self.max_seq = self.t_loc * self.world
        self.num_kv_heads, self.head_dim = num_kv_heads, head_dim
        self.dtype = dtype
        self.slots_per_dev = (slots_per_dev if slots_per_dev is not None
                              else batch * pages_per_seq_dev)
        # Pools SMALLER than one whole row are legal: block-granular
        # stream sessions admit by blocks (ISSUE 6), and the
        # seq-granular alloc path fails a too-big request gracefully
        # ("device pool exhausted") rather than at construction.
        assert self.slots_per_dev >= 1, "pool too small"
        # The reserved sentinel page sits past the allocatable slots:
        # physical pools carry one extra row per device that no free
        # stack ever hands out, so pointing a frozen row at it costs
        # zero request capacity.
        self.phys_slots_per_dev = self.slots_per_dev + 1
        self.offset = 0
        # Host-side allocator state (numpy buffers shared verbatim with
        # the native allocator, csrc/kvpool/kvpool.cc): per-device free
        # STACKS + block tables + per-row owned flags. The serving hot
        # path (admit/evict) runs these through the C library when a
        # toolchain exists; the Python fallback below is bit-identical
        # (tests replay randomized traces through both).
        import numpy as np
        w, slots = self.world, self.slots_per_dev
        self._stack = np.empty((w, slots), np.int32)
        self._top = np.empty((w,), np.int32)
        self._table = np.zeros((w, batch, pages_per_seq_dev), np.int32)
        self._owned = np.zeros((batch,), np.uint8)
        from triton_dist_tpu.models import kv_native
        self._lib = kv_native._load()
        self._init_allocator()
        # Block-granular serving substrate (stream sessions): populated
        # by stream_setup(); the seq-granular API above never reads it.
        self._blockwise = False
        self.prefix = None           # PrefixCache when enabled
        self._sentinel = None        # (w,) slot ids unowned rows point at
        self._ref = np.zeros((w, slots), np.int32)
        self._row_blocks = np.zeros((batch,), np.int32)
        self._committed = np.zeros((w,), np.int64)
        self._row_commit = np.zeros((batch, w), np.int64)
        # Admission-time geometry rollback_position needs to restore
        # commitments EXACTLY: per (row, dev), the prompt-block count
        # (_row_base) and the committed decode tail (_row_tail0, an
        # immutable copy of the admission's _row_commit). A decode
        # block consumed commitment iff its per-device decode ordinal
        # sits below _row_tail0 — blocks allocate in order and the
        # commitment decrements while positive, so the rule is exact
        # and a rollback can never mint commitment a growth outside
        # the admission budget never consumed.
        self._row_base = np.zeros((batch, w), np.int64)
        self._row_tail0 = np.zeros((batch, w), np.int64)
        self._evicted_total = 0

    def _init_allocator(self) -> None:
        """(Re)initialize the free stacks + tables + ownership flags —
        the constructor's allocator state, also the pool reset between
        serving modes (seq-granular serve() vs block-granular stream
        sessions must never inherit each other's stack state)."""
        import numpy as np
        w, slots = self.world, self.slots_per_dev
        ok = (self._lib is not None
              and self._lib.tdt_kv_init(w, slots, self._stack,
                                        self._top) == 0)
        if not ok:  # no toolchain OR degenerate dims the C init rejects
            self._lib = None
            self._top[:] = slots
            self._stack[:] = np.arange(slots, dtype=np.int32)
        self._table[:] = 0
        self._owned[:] = 0
        self._table_dev = None  # device copy, invalidated on alloc/free

    def _args(self):
        return (self.world, self.batch, self.pages_per_seq_dev,
                self.slots_per_dev, self._stack, self._top, self._table,
                self._owned)

    @staticmethod
    def _raise(rc: int, what: str):
        if rc == -1:
            raise RuntimeError(f"row {what}: not allocatable/freeable "
                               "(bad index or ownership state)")
        if rc == -2:
            raise RuntimeError(f"row {what}: device pool exhausted")

    # -- allocation (vLLM-style; host-side) --------------------------------
    def alloc_seq(self, b: int) -> None:
        """Reserve every logical page of row ``b`` on every device —
        all-or-nothing (exhaustion changes no state). (Lazy
        page-at-a-time allocation would also fit this table; the decode
        kernel only reads slots below kv_len.)"""
        if self._lib is not None:
            rc = self._lib.tdt_kv_alloc_seq(*self._args(), b)
        else:
            rc = self._py_alloc_seq(b)
        self._raise(rc, str(b))
        self._table_dev = None

    def _py_alloc_seq(self, b: int) -> int:
        if not (0 <= b < self.batch) or self._owned[b]:
            return -1
        pages = self.pages_per_seq_dev
        if any(self._top[r] < pages for r in range(self.world)):
            return -2  # check EVERY device first: no partial pops
        for r in range(self.world):
            for i in range(pages):
                self._top[r] -= 1
                self._table[r, b, i] = self._stack[r, self._top[r]]
        self._owned[b] = 1
        return 0

    def free_seq(self, b: int) -> None:
        if self._lib is not None:
            rc = self._lib.tdt_kv_free_seq(*self._args(), b)
        else:
            rc = self._py_free_seq(b)
        self._raise(rc, str(b))
        self._table_dev = None

    def _py_free_seq(self, b: int) -> int:
        if not (0 <= b < self.batch) or not self._owned[b]:
            return -1
        for r in range(self.world):
            for i in range(self.pages_per_seq_dev):
                self._stack[r, self._top[r]] = self._table[r, b, i]
                self._top[r] += 1
        self._owned[b] = 0
        return 0

    def owned_rows(self) -> list:
        """Rows currently holding an allocation."""
        return [int(b) for b in range(self.batch) if self._owned[b]]

    def alloc_many(self, rows) -> None:
        """Admission control: allocate a whole REQUEST of rows
        all-or-nothing — on any failure every row of this call is
        rolled back before raising."""
        import numpy as np
        rows = np.asarray(list(rows), np.int32)
        if self._lib is not None:
            rc = self._lib.tdt_kv_alloc_many(*self._args(), rows,
                                             len(rows))
        else:
            rc = 0
            done = []
            for b in rows:
                rc = self._py_alloc_seq(int(b))
                if rc != 0:
                    for k in done:
                        self._py_free_seq(k)
                    break
                done.append(int(b))
        self._raise(rc, str(list(map(int, rows))))
        self._table_dev = None

    # -- block-granular serving substrate (stream sessions, ISSUE 6) ------
    # The seq-granular API above reserves whole max_seq rows (plain
    # serve()'s admission unit). Stream sessions instead run the pool
    # BLOCK-granular: a request is admitted when enough physical blocks
    # are free for its prompt + decode budget, its table lanes grow one
    # block at a time as decode crosses page boundaries, and its blocks
    # return to the pool the moment it retires. Full prompt blocks are
    # indexed in a cross-request prefix cache (models/prefix_cache.py):
    # refcounted sharing for hits, LRU eviction of refcount-zero blocks
    # when the free stacks run dry. One thread drives all of this (the
    # stream-session contract), so no locking.

    def reset_pool(self) -> None:
        """Return the pool to the constructor state: every slot free,
        tables zeroed, prefix index dropped, both serving modes clear.
        serve() and stream_setup() both start from here — the two
        admission disciplines must never inherit each other's stacks."""
        self._init_allocator()
        self._blockwise = False
        self.prefix = None
        self._sentinel = None
        self._ref[:] = 0
        self._row_blocks[:] = 0
        self._committed[:] = 0
        self._row_commit[:] = 0
        self._row_base[:] = 0
        self._row_tail0[:] = 0
        self.offset = 0
        self._emit_gauges()

    def stream_setup(self, prefix_cache: bool = True) -> None:
        """Reset the pool and enter block-granular mode.

        Points every row's table lanes at the per-device SENTINEL page:
        the shared decode step runs the per-row KV write for ALL rows
        (frozen rows included), so an unoccupied row needs somewhere
        harmless to write — the sentinel is that page (never read below
        any live row's kv_len, never indexed, never allocatable). This
        is what lets retired rows release their real blocks EAGERLY
        instead of holding them until a replacement is admitted. The
        sentinel is the reserved extra physical slot past the accounted
        pool (slot id ``slots_per_dev``), so it costs no capacity: a
        request needing every accounted slot still fits."""
        import numpy as np
        self.reset_pool()
        self._blockwise = True
        if prefix_cache:
            from triton_dist_tpu.models.prefix_cache import PrefixCache
            self.prefix = PrefixCache(self.world, self.page_size)
        self._sentinel = np.full((self.world,), self.slots_per_dev,
                                 np.int32)
        for b in range(self.batch):
            self._point_at_sentinel(b)
        self._table_dev = None
        self._emit_gauges()

    def _point_at_sentinel(self, b: int) -> None:
        self._table[:, b, :] = self._sentinel[:, None]

    def _pop_block(self, r: int) -> int:
        """One free block on device ``r``: the free stack first, then
        LRU eviction of a refcount-zero cached block."""
        if self._top[r] > 0:
            self._top[r] -= 1
            return int(self._stack[r, self._top[r]])
        victim = (self.prefix.evict_lru(r)
                  if self.prefix is not None else None)
        if victim is None:
            raise RuntimeError(f"device {r} pool exhausted")
        self._evicted_total += 1
        obs.counter("kv.blocks_evicted").inc()
        return victim

    def _push_block(self, r: int, slot: int) -> None:
        self._stack[r, self._top[r]] = slot
        self._top[r] += 1

    def _deref(self, r: int, slot: int) -> None:
        self._ref[r, slot] -= 1
        assert self._ref[r, slot] >= 0, f"double free: dev {r} slot {slot}"
        if self._ref[r, slot] == 0:
            if self.prefix is not None and self.prefix.is_indexed(r, slot):
                # Data stays resident for future hits; the block is now
                # the MRU eviction candidate.
                self.prefix.release(r, slot)
            else:
                self._push_block(r, slot)

    # -- admission arithmetic ---------------------------------------------
    def _block_lane(self, j: int):
        """Logical block ``j`` of a row → (device r, table lane lp).
        THE one spelling of the layout invariant — blocks stripe
        contiguously, ``pages_per_seq_dev`` per device; every demand
        tally below derives from it."""
        return j // self.pages_per_seq_dev, j % self.pages_per_seq_dev

    def _blocks_per_dev(self, j0: int, j1: int):
        """Per-device count of logical blocks [j0, j1) under
        ``_block_lane``'s striping."""
        import numpy as np
        out = np.zeros((self.world,), np.int64)
        js = np.arange(j0, j1) // self.pages_per_seq_dev
        if len(js):
            out += np.bincount(js, minlength=self.world)
        return out

    def need_per_dev(self, prompt_len: int, gen_len: int):
        """Worst-case block demand of one request, per device: blocks
        covering every position it will ever WRITE — prefill writes
        [0, L), decode steps write [L, L+G-1) (the budget's last token
        is sampled from the step that writes position L+G-2)."""
        last = max(prompt_len + max(gen_len, 1) - 1, prompt_len)
        n = -(-last // self.page_size)
        assert n <= self.pages_per_seq_dev * self.world, (
            f"request spans {n} blocks > max_seq capacity "
            f"(check prompt+gen_len <= max_seq first)")
        return self._blocks_per_dev(0, n)

    def available_per_dev(self):
        """Free-stack depth plus evictable (refcount-zero cached)
        blocks, per device — everything an admission could claim."""
        import numpy as np
        avail = self._top.astype(np.int64).copy()
        if self.prefix is not None:
            avail += np.asarray([self.prefix.evictable_count(r)
                                 for r in range(self.world)], np.int64)
        return avail

    def fits_pool(self, prompt_len: int, gen_len: int) -> bool:
        """Could this request EVER be admitted (empty pool)? False
        means the submit must be rejected as unservable, not queued
        (it would deadlock the admission queue). The sentinel lives
        outside the accounted pool, so every slot counts."""
        return bool((self.need_per_dev(prompt_len, gen_len)
                     <= self.slots_per_dev).all())

    def can_admit(self, prompt_len: int, gen_len: int,
                  extra=None) -> bool:
        """Admission control: enough blocks free (or evictable) on
        every device for this request's worst-case demand, net of what
        is already committed to live rows' un-allocated decode tails
        (and of ``extra`` — same-batch admissions not yet executed).
        Conservative: prefix-cache hits can only reduce the true
        demand, never raise it."""
        avail = self.available_per_dev() - self._committed
        if extra is not None:
            avail = avail - extra
        return bool((avail >= self.need_per_dev(prompt_len,
                                                gen_len)).all())

    # -- request lifecycle -------------------------------------------------
    def prefix_hashes(self, prompt) -> list | None:
        """Full block-hash chain for ``prompt`` (``None`` without a
        prefix cache). Admission walks the chain three times
        (probe → admit → register); computing it once here and passing
        it down keeps long-preamble admissions off the sha1 treadmill."""
        if self.prefix is None:
            return None
        return self.prefix.block_hashes(prompt)

    def prefix_lookup_blocks(self, prompt_len: int) -> int:
        """Blocks eligible for a prefix-cache lookup: every FULL
        prompt block except the last one of an exactly page-aligned
        prompt, which is always recomputed (the admission program
        needs the final position's logits). The single home of that
        trim rule — probe, admit, and the obs lookup counter all
        derive from it."""
        n = prompt_len // self.page_size
        if n and prompt_len % self.page_size == 0:
            n -= 1
        return n

    def prefix_probe(self, prompt, hashes=None) -> int:
        """Upper bound on cache-hit BLOCKS for ``prompt`` (stateless;
        the engine sizes the suffix admission program off this before
        committing to the hits)."""
        if self.prefix is None:
            return 0
        if hashes is None:
            hashes = self.prefix.block_hashes(prompt)
        return self.prefix.probe(
            hashes[:self.prefix_lookup_blocks(len(prompt))])

    def admit_row(self, b: int, prompt, gen_budget: int = 0,
                  use_hits: int | None = None, hashes=None) -> int:
        """Block-granular admission of ``prompt`` into row ``b``:

        1. map up to ``use_hits`` cached prefix blocks into the row's
           lanes (refcounted, shared, read-only);
        2. allocate private blocks for the rest of the prompt;
        3. commit (without allocating) the decode-tail blocks the
           ``gen_budget`` may still demand, so a later admission cannot
           starve this row mid-decode.

        All-or-nothing: on exhaustion every hit ref is rolled back and
        the row's lanes return to the sentinel. Returns the number of
        prefix TOKENS served from cache (a page multiple)."""
        import numpy as np
        assert self._blockwise, "admit_row needs stream_setup() first"
        assert self._row_blocks[b] == 0, f"row {b} already holds blocks"
        L = len(prompt)
        page = self.page_size
        hits, n_lookup = [], 0
        if self.prefix is not None:
            if hashes is None:
                hashes = self.prefix.block_hashes(prompt)
            hashes = hashes[:self.prefix_lookup_blocks(L)]
            n_lookup = len(hashes)
            hits = self.prefix.resolve(hashes, max_hits=use_hits)
        k = len(hits)
        n_prompt = -(-L // page)
        last = max(L + max(gen_budget, 1) - 1, L)
        n_total = max(n_prompt, -(-last // page))
        # Map the hits FIRST (claiming them out of the evictable pool)
        # so the availability check sees the exact post-hit state.
        for j, (r, slot) in enumerate(hits):
            rj, lp = self._block_lane(j)
            assert r == rj, "prefix index device/layout mismatch"
            if self._ref[r, slot] == 0:
                self.prefix.claim(r, slot)
            self._ref[r, slot] += 1
            self._table[r, b, lp] = slot
        need = self._blocks_per_dev(k, n_total)
        avail = self.available_per_dev() - self._committed
        if np.any(avail < need):
            for j, (r, slot) in enumerate(hits):    # roll back
                self._deref(r, slot)
            self._point_at_sentinel(b)
            self._table_dev = None
            raise RuntimeError(
                f"row {b}: device pool exhausted "
                f"(short {int(np.max(need - avail))} block(s); "
                f"{int(self._committed.sum())} committed to live rows)")
        for j in range(k, n_prompt):
            r, lp = self._block_lane(j)
            slot = self._pop_block(r)
            self._ref[r, slot] = 1
            self._table[r, b, lp] = slot
        tail = self._blocks_per_dev(n_prompt, n_total)
        self._row_commit[b] = tail
        self._committed += tail
        self._row_base[b] = self._blocks_per_dev(0, n_prompt)
        self._row_tail0[b] = tail
        self._row_blocks[b] = n_prompt
        if self.prefix is not None:     # account only admissions that
            self.prefix.account(n_lookup, k)    # actually succeeded
        self._table_dev = None
        self._emit_gauges()
        return k * page

    def ensure_position(self, b: int, pos: int) -> bool:
        """Grow row ``b``'s allocation to cover write position ``pos``
        (called before each decode step). Returns True when new blocks
        were allocated — the caller must refresh its device table.

        Grows one block per step under plain decode; a SPECULATIVE
        burst (ISSUE 13) writes up to k+1 positions per step and may
        cross several page boundaries at once, so growth allocates
        every block from the current edge through ``pos``'s block.
        Each allocation consumes the row's decode commitment where one
        exists; ``rollback_position`` restores exactly the commitments
        growth consumed (the per-device decode-ordinal rule there)."""
        j = pos // self.page_size
        n = int(self._row_blocks[b])
        if j < n:
            return False
        for jj in range(n, j + 1):
            r, lp = self._block_lane(jj)
            slot = self._pop_block(r)
            self._ref[r, slot] = 1
            self._table[r, b, lp] = slot
            self._row_blocks[b] = jj + 1
            if self._row_commit[b, r] > 0:   # consume the commitment
                self._row_commit[b, r] -= 1
                self._committed[r] -= 1
        self._table_dev = None
        self._emit_gauges()
        return True

    def rollback_position(self, b: int, pos: int) -> bool:
        """Shrink row ``b``'s allocation back to the blocks covering
        write positions [0, ``pos``] — the rejected-tail rewind of a
        speculative burst (ISSUE 13): blocks allocated for draft
        positions past the accepted prefix return to the pool (deref —
        a decode-tail block is always private, so this is a free), the
        lanes point back at the sentinel, and the commitments those
        allocations consumed are restored so a later admission still
        cannot starve this row's remaining budget. Returns True when
        blocks were freed — the caller must refresh its device table.
        Stale K/V inside the KEPT tail block needs no rewind: positions
        past the committed offset are never exposed by any mask before
        the next step overwrites them."""
        keep = int(pos) // self.page_size + 1
        n = int(self._row_blocks[b])
        if n <= keep:
            return False
        for jj in range(keep, n):
            r, lp = self._block_lane(jj)
            self._deref(r, int(self._table[r, b, lp]))
            self._table[r, b, lp] = self._sentinel[r]
            # This block consumed commitment iff its per-device decode
            # ordinal sits below the admission tail (allocation order
            # is monotone, so the rule is exact — a block grown PAST
            # the budget restores nothing).
            d = lp - int(self._row_base[b, r])
            if 0 <= d < int(self._row_tail0[b, r]):
                self._row_commit[b, r] += 1
                self._committed[r] += 1
        self._row_blocks[b] = keep
        self._table_dev = None
        self._emit_gauges()
        return True

    def release_row(self, b: int) -> None:
        """Eager retirement: deref every block (shared blocks drop a
        ref; indexed refcount-zero blocks stay cached and evictable;
        private blocks return to the free stack), release the row's
        remaining decode commitment, and point its lanes back at the
        sentinel so frozen-row writes stay harmless."""
        for j in range(int(self._row_blocks[b])):
            r, lp = self._block_lane(j)
            self._deref(r, int(self._table[r, b, lp]))
        self._committed -= self._row_commit[b]
        self._row_commit[b] = 0
        self._row_base[b] = 0
        self._row_tail0[b] = 0
        self._row_blocks[b] = 0
        self._point_at_sentinel(b)
        self._table_dev = None
        self._emit_gauges()

    def register_prefix(self, b: int, tokens, hashes=None) -> int:
        """Index row ``b``'s full PROMPT blocks in the prefix cache
        (called once the admission prefill has been dispatched — the
        pool arrays carrying the data are threaded through the session
        caches, so a later hit reads exactly what was computed). The
        partial tail block is mutable (decode writes it) and is never
        indexed; full blocks are immutable for their pool lifetime —
        the copy-on-write discipline with the copy statically
        unreachable. Returns how many blocks were newly indexed."""
        if self.prefix is None:
            return 0
        n_full = min(len(tokens) // self.page_size,
                     int(self._row_blocks[b]))
        if hashes is None:
            hashes = self.prefix.block_hashes(tokens)
        new = 0
        for j in range(n_full):
            r, lp = self._block_lane(j)
            new += bool(self.prefix.register(
                hashes[j], r, int(self._table[r, b, lp])))
        return new

    # -- introspection -----------------------------------------------------
    def block_audit(self) -> dict:
        """Pool accounting snapshot (the quick-tier leak audit: after
        every request retires, free + evictable must equal the whole
        pool — a stranded block is a slow OOM). The sentinel pages are
        outside the accounted pool and never appear here."""
        free = int(self._top.sum())
        evictable = (sum(self.prefix.evictable_count(r)
                         for r in range(self.world))
                     if self.prefix is not None else 0)
        total = self.world * self.slots_per_dev
        return {"free": free, "evictable": evictable,
                "active": total - free - evictable,
                "committed": int(self._committed.sum()),
                "evicted_total": self._evicted_total,
                "total": total}

    def _emit_gauges(self) -> None:
        if not obs.enabled():
            return
        a = self.block_audit()
        obs.gauge("kv.blocks_free").set(a["free"])
        obs.gauge("kv.blocks_cached").set(a["evictable"])
        obs.gauge("kv.blocks_active").set(a["active"])
        if a["total"]:
            obs.gauge("kv.block_utilization").set(
                round(1.0 - (a["free"] + a["evictable"]) / a["total"], 4))

    def block_table(self) -> jax.Array:
        """Device copy of the (w, B, n_pages) table — pass this into
        jitted reads AND writes so table changes retrace instead of being
        baked in as constants (cached until the next alloc/free)."""
        if self._table_dev is None:
            self._table_dev = jax.device_put(
                jnp.asarray(self._table),
                NamedSharding(self.mesh, P(self.axis)))
        return self._table_dev

    # -- device state -------------------------------------------------------
    def init(self):
        """[(pool_k, pool_v)] * L, all slots zeroed. The +1 physical
        slot per device is the reserved sentinel page; consumers derive
        the slot stride from the array shape, never from
        ``slots_per_dev``."""
        shape = (self.world * self.phys_slots_per_dev, self.page_size,
                 self.num_kv_heads, self.head_dim)
        return _zero_leaves(shape, self.dtype,
                            NamedSharding(self.mesh, P(self.axis)),
                            self.num_layers)

    @staticmethod
    def _addr(offset, page_size: int, n_pages: int):
        """THE one definition of the page-layout address math — every
        slot resolver below derives from it, so a layout change cannot
        silently diverge between write()/forward_sp/the XLA golden.
        Returns (device index r, local page lp, in-page row)."""
        offset = jnp.asarray(offset, jnp.int32)
        t_loc = page_size * n_pages
        return offset // t_loc, (offset % t_loc) // page_size, \
            offset % page_size

    @staticmethod
    def position_to_slot(table: jax.Array, offset, page_size: int,
                         slots_per_dev: int):
        """Global position(s) → (global pool rows, in-page row).

        ``offset`` may be a scalar (one decode step → rows (B,)) or a
        vector of T positions (golden reconstruction → rows (T, B)).
        """
        r, lp, inpage = PagedKVCacheManager._addr(offset, page_size,
                                                  table.shape[2])
        # expand_dims makes scalar r broadcast as (1,)+(B,)->(B,) and
        # vector r as (T,1)+(T,B)->(T,B).
        gslots = jnp.expand_dims(r * slots_per_dev, -1) + table[r, :, lp]
        return gslots, inpage

    @staticmethod
    def gathered_view(pool: jax.Array, table: jax.Array, world: int):
        """Contiguous (B, T, Hkv, D) view of one pooled layer via table
        gathers — THE shared reconstruction consumed by both the
        "gathered"/xla paged decode (ops/flash_decode.py) and the paged
        chunked-prefill attention (dense.forward_sp), so the pool-gather
        geometry cannot diverge between the read paths. Positions past
        a row's live length resolve to sentinel/stale pages the callers'
        kv_len masks never expose. Known cost: O(max_seq) gather, like
        _paged_scatter's staging (optimization candidate). Callers apply
        their own sharding constraint to the result."""
        page_size = pool.shape[1]
        t_total = page_size * table.shape[2] * world
        posn = jnp.arange(t_total, dtype=jnp.int32)
        g, ip = PagedKVCacheManager.position_to_slot(
            table, posn, page_size, pool.shape[0] // world)
        return pool[g, ip[:, None]].transpose(1, 0, 2, 3)

    @staticmethod
    def position_to_slot_rows(table: jax.Array, offsets, page_size: int,
                              slots_per_dev: int):
        """PER-ROW positions → (global pool rows (B,), in-page rows (B,)).

        Row b's position ``offsets[b]`` resolves through row b's OWN
        table lane (aligned indexing ``table[r[b], b, lp[b]]``) — the
        continuous-batching decode step where every sequence sits at a
        different write position (Engine.serve_stream paged mode).
        """
        r, lp, inpage = PagedKVCacheManager._addr(offsets, page_size,
                                                  table.shape[2])
        rows = jnp.arange(table.shape[1])
        gslots = r * slots_per_dev + table[r, rows, lp]
        return gslots, inpage

    def write(self, pools, layer: int, new_k: jax.Array, new_v: jax.Array,
              offset, table: jax.Array) -> list:
        """Scatter one decode step's (B, Hkv, D) K/V into the pools at
        global position ``offset`` (jit-compatible: pure gather/scatter
        on traced values).

        ``table``: pass :meth:`block_table`'s result through the jit
        boundary — closing over the host table would bake slot ids in as
        compile-time constants and go stale after ``free_seq``/
        ``alloc_seq`` (silent cross-sequence corruption).
        """
        pool_k, pool_v = pools[layer]
        gslots, inpage = self.position_to_slot(
            table, offset, self.page_size, self.phys_slots_per_dev)
        pool_k = pool_k.at[gslots, inpage].set(new_k.astype(pool_k.dtype))
        pool_v = pool_v.at[gslots, inpage].set(new_v.astype(pool_v.dtype))
        out = list(pools)
        out[layer] = (pool_k, pool_v)
        return out

    def inc_offset(self, n: int) -> int:
        self.offset += n
        assert self.offset <= self.max_seq, "paged KV overflow"
        return self.offset

    def reset(self):
        self.offset = 0
