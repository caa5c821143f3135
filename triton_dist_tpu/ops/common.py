"""Shared helpers for the kernel library (reference
python/triton_dist/kernels/nvidia/common_ops.py — barriers, signal ops —
plus the per-op boilerplate every kernel repeats)."""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu import obs
from triton_dist_tpu.obs import record_comm  # noqa: F401  (op entries)
from triton_dist_tpu.runtime.platform import default_interpret


def _abstract_mesh():
    """Current thread's AbstractMesh, or None outside any mesh context."""
    am = jax.sharding.get_abstract_mesh()
    return None if am.empty else am


def _manual_axis_flags(am) -> list[bool]:
    """Per-axis is-Manual flags of an AbstractMesh."""
    return [t == jax.sharding.AxisType.Manual for t in am.axis_types]


def resolve_interpret(interpret: bool | None):
    """Auto-select interpret mode: compiled on TPU, interpreted elsewhere.

    Interpreted kernels simulate remote DMA + semaphores on a multi-device
    CPU mesh — the framework's single-process distributed test mode.

    With ``TDT_DETECT_RACES=1`` the interpreter's vector-clock race
    detector is enabled: missing semaphore waits in kernel signal
    protocols are reported as data races. This is the framework's race
    sanitizer — the reference has no equivalent (SURVEY.md §5 "no custom
    sanitizer"; it relies on sleep-injection + stress runs).
    """
    import os
    if interpret is None:
        interpret = default_interpret()
    if interpret:
        # A *mixed* mesh context — some axes already Manual (an enclosing
        # user shard_map, e.g. a DP wrap) while this op's axis is still
        # Auto — means the op's own shard_map will nest, which the
        # interpreter cannot lower (io_callback trips an XLA
        # sharding-validation CHECK). All-Manual (called from inside a
        # kernel-level shard_map body) and empty (host) contexts are the
        # normal working paths.
        am = _abstract_mesh()
        if am is not None:
            manual = _manual_axis_flags(am)
            if any(manual) and not all(manual):
                raise NotImplementedError(
                    "interpret-mode Pallas cannot run nested inside an "
                    "outer manual shard_map. Under DP composition on the "
                    "CPU simulator use impl='xla'; compiled TPU mode is "
                    "the path for nested fused kernels.")
        from triton_dist_tpu.runtime.interpret_compat import (
            patch_interpreter_spin)
        patch_interpreter_spin()
        return pltpu.InterpretParams(
            detect_races=bool(os.environ.get("TDT_DETECT_RACES")))
    return False


def sync_interpret(out, interpret) -> object:
    """Block on eager interpret-mode results before returning.

    JAX dispatches asynchronously: an interpreted multi-device kernel may
    still be executing (its device programs + io_callbacks occupying CPU
    client pool threads) when the caller dispatches follow-on
    computations into the same pool — on low-core hosts the queued work
    can starve the in-flight kernel's device programs: a resource
    deadlock (observed: TP_Attn xla-then-ag_rs hang). Compiled TPU
    kernels don't need this; under jit tracing outputs are Tracers and
    are passed through untouched.
    """
    if not interpret:
        return out
    leaves = jax.tree_util.tree_leaves(out)
    if any(isinstance(leaf, jax.core.Tracer) for leaf in leaves):
        return out
    return jax.block_until_ready(out)


#: Mosaic scoped-VMEM limit requested for every comm kernel. Mosaic's
#: default cap is 16 MB, but a v5e core has 128 MB of physical VMEM
#: (public TPU flash kernels run with vmem_limit_bytes up to 128 MB).
#: Scoped use exceeds the declared scratch (one compile of the fused SP
#: kernel reported 16.14 MB scoped for ~7.4 MB declared); 64 MB absorbs
#: that overhead for every budget-sized shape while leaving headroom
#: for XLA's own scoped uses. tests/test_chip_compile.py compiles the
#: main-path kernels for the v5e under this limit.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024

#: Ceiling on a kernel's DECLARED scratch footprint. Mosaic's scoped
#: accounting carries roughly 2.2x of window/staging overhead on top of
#: the declared buffers (16.14 MB scoped for ~7.4 MB declared in the
#: one compile on record), so declared footprints up to ~26 MB fit under
#: :data:`VMEM_LIMIT_BYTES`. Config tables list over-soft-budget
#: "aggressive tier" entries up to this cap for the autotuner; the
#: per-op clamps reject anything beyond it so an uncompilable config
#: never reaches Mosaic.
HARD_FOOTPRINT_CAP = 26 * 1024 * 1024

#: Soft VMEM budget the fused ops' "auto" tile choice and default-path
#: clamps target: 12 MB declared, the largest default footprint that
#: has compiled for the chip — a default that Mosaic refuses (a GEMM
#: config once declared 16.5 MB of scratch against the 16 MB default
#: cap) takes the whole program down. Larger declared footprints are
#: reachable only through paths with per-config compile-failure
#: isolation:
#: autotune sweeps and tuned winners run against
#: :data:`TUNED_VMEM_BUDGET` / :data:`HARD_FOOTPRINT_CAP` (the sweep
#: scores a config that fails to compile as inf instead of crashing).
DEFAULT_VMEM_BUDGET = 12 * 1024 * 1024

#: Budget-tier boundary for AUTOTUNE candidate tables: 24 MB declared x
#: the ~2.2x scoped overhead ~= 53 MB, under the 64 MB
#: :data:`VMEM_LIMIT_BYTES` with margin. Only swept / trust_blocks
#: paths — which carry per-config failure isolation — use it; the
#: default path keeps :data:`DEFAULT_VMEM_BUDGET`.
TUNED_VMEM_BUDGET = 24 * 1024 * 1024
assert DEFAULT_VMEM_BUDGET < TUNED_VMEM_BUDGET < HARD_FOOTPRINT_CAP


def cap_config_tiers(budget_cfgs, aggressive_cfgs, n_budget: int = 5,
                     n_aggressive: int = 4):
    """Prune an autotune config table for sweep tractability: each
    entry costs a ~30 s cold Mosaic compile on chip, so keep the
    ``n_budget`` best in-budget entries and ``n_aggressive`` best
    aggressive (over-soft-budget) entries. Both lists are generated
    best-first (larger block_n = fewer A re-reads, then larger
    block_m), so a prefix of each preserves the heuristic ranking.
    Callers pass the tiers as separate lists — tier membership is
    decided once, at generation (review r5l finding 2: re-deriving it
    in a closure invited drift), and fallback variants a downstream
    clamp depends on (hbm_kt) must be appended by the caller OUTSIDE
    the cap so pruning can never remove them (r5l finding 1)."""
    return budget_cfgs[:n_budget] + aggressive_cfgs[:n_aggressive]


def record_overlap(op: str, cost, world: int | None = None,
                   dirs: int | None = None) -> None:
    """Per-op overlap gauges from a :class:`tools.perf_model
    .FusedGemmCost` breakdown: ``comms.<op>.overlap_pct`` (hidden
    fraction of the ring communication under the chosen tile schedule;
    the upstream system's target is >= 90 %) and
    ``comms.<op>.exposed_comm_ms``.

    Model-derived from the tile-loop timing structure at DISPATCH time
    (trace time under jit, like ``record_comm``), not a trace
    decomposition: nothing here is measured. At world=1 there is no
    communication to expose, so the gauge reads 100.

    With event tracing on and ``world``/``dirs`` passed, the ring
    schedule additionally lands on the timeline as per-chunk
    begin/end events (``comms.<op>.compute`` / ``comms.<op>.comm``
    tracks) so ``tools/trace_export.py --overlap`` reconstructs
    overlap from the trace's interval geometry rather than from this
    gauge (docs/observability.md "Tracing")."""
    from triton_dist_tpu.obs import trace as _trace
    if obs.enabled():
        obs.gauge(f"comms.{op}.overlap_pct").set(cost.overlap_pct)
        obs.gauge(f"comms.{op}.exposed_comm_ms").set(
            cost.exposed_comm_ms)
    if _trace.enabled() and world is not None and world > 1:
        _trace.ring_schedule_events(
            op, world=world, dirs=dirs if dirs is not None else 1,
            compute_ms=cost.compute_ms, comm_ms=cost.comm_ms)


def comm_params(collective_id: int | None = 0,
                vmem_limit_bytes: int | None = None,
                world: int | None = None) -> pltpu.CompilerParams:
    """CompilerParams for kernels that communicate: side effects must be kept
    (DMA-only kernels would be DCE'd) and a collective_id is required for the
    global barrier semaphore.

    At ``world == 1`` kernels skip ``dl.barrier_all`` so no barrier semaphore
    exists — Mosaic then rejects a ``collective_id`` ("has to be unspecified
    ... when not using a custom barrier").

    ``vmem_limit_bytes`` defaults to :data:`VMEM_LIMIT_BYTES`; pass an
    explicit value only to tighten it for a specific kernel."""
    kwargs = dict(has_side_effects=True)
    if world != 1 and collective_id is not None:
        kwargs["collective_id"] = collective_id
    limit = (VMEM_LIMIT_BYTES if vmem_limit_bytes is None
             else vmem_limit_bytes)
    kwargs["vmem_limit_bytes"] = limit
    if obs.enabled():
        # Requested-vs-declared VMEM gauges (docs/observability.md):
        # the scoped limit each comm kernel asks Mosaic for, next to
        # the declared-footprint budget/cap the tile choosers target —
        # the pair whose confusion ADVICE r5 flagged.
        obs.gauge("vmem.scoped_limit_bytes").set(limit)
        obs.gauge("vmem.declared_budget_bytes").set(DEFAULT_VMEM_BUDGET)
        obs.gauge("vmem.declared_cap_bytes").set(HARD_FOOTPRINT_CAP)
    return pltpu.CompilerParams(**kwargs)


def maybe_straggle(straggler_option, axis: str, interpret=False) -> None:
    """Spin one rank before it starts communicating
    (reference ``straggler_option`` / ``_run_straggler``,
    allreduce.py:137): correctness must not depend on rank arrival
    order. ``pl.delay`` is a hardware spin — skipped in interpret mode,
    where the interpreter's own thread scheduling provides the skew."""
    if straggler_option is None or interpret:
        return
    from jax import lax
    rank, cycles = straggler_option

    @pl.when(lax.axis_index(axis) == rank)
    def _():
        pl.delay(cycles)


def maybe_noise(for_correctness: bool, axis: str, world: int,
                salt: int = 0, base_cycles: int = 512,
                interpret=False) -> None:
    """Per-rank pseudo-random delay for correctness-debug runs
    (reference ``for_correctness`` sleep injection, allgather.py:74-79,
    allgather_gemm.py:507-508): shakes the rank schedule so stale-signal
    / missing-wait bugs reproduce instead of hiding behind lockstep
    timing. Deterministic per (rank, salt) so failures replay."""
    if not for_correctness or interpret or world <= 1:
        return
    from jax import lax
    me = lax.axis_index(axis)
    for r in range(world):
        amt = ((r * 2654435761 + salt * 40503) >> 7) % 8 + 1

        @pl.when(me == r)
        def _(amt=amt):
            pl.delay(base_cycles * amt)


# -- bidirectional ring scheduling ------------------------------------------
# ICI links are full duplex, so a ring collective can run both directions
# at once: chunks travel the SHORTER way round and the hop count halves
# (ops/allgather.py RING_BIDIR documents the win for the plain
# collective). These helpers give the fused GEMM kernels the same
# schedule: a rank-rotated consumption order that starts at the local
# chunk and then alternates between arrivals from the left (forward
# ring) and the right (backward ring).

def resolve_ring_dirs(ring_dirs: int = 0) -> int:
    """Ring direction count for the fused comm-GEMM schedules.

    ``2`` = bidirectional (default), ``1`` = unidirectional. ``0``
    consults ``TDT_RING_DIRS`` (so either schedule stays selectable
    without code changes) and falls back to 2.
    """
    if ring_dirs not in (0, 1, 2):
        raise ValueError(f"ring_dirs must be 0 (auto), 1 or 2: {ring_dirs}")
    if ring_dirs:
        return ring_dirs
    env = obs.env_int("TDT_RING_DIRS", 2)
    if env not in (1, 2):
        raise ValueError(f"TDT_RING_DIRS must be 1 or 2: {env!r}")
    return env


def ring_hop_counts(world: int, dirs: int) -> tuple[int, int]:
    """(forward, backward) hop counts of the ring schedule. Odd worlds
    split the w-1 travelling chunks as ceil/floor; world <= 2 has no
    shorter way round, so bidir degenerates to the unidirectional ring
    (same split as ``ops/allgather._ring_ag_kernel``)."""
    if world <= 1:
        return 0, 0
    if dirs == 1 or world == 2:
        return world - 1, 0
    n_bwd = (world - 1) // 2
    return (world - 1) - n_bwd, n_bwd


def ring_chunk_schedule(me, s, world: int, dirs: int):
    """Chunk consumed at position ``s`` of the rank-rotated schedule.

    dirs=1: chunk ``me - s`` (all forward — today's proven order).
    dirs=2: own chunk first, then alternating arrivals from the left
    (forward ring: me-1, me-2, ...) and the right (backward ring: me+1,
    me+2, ...); even worlds end with a forward-only tail because the
    backward ring carries floor((w-1)/2) chunks.

    Returns ``(chunk, is_bwd, offset)``: ``offset`` is the hop count
    from the chunk's origin rank to this rank along its travel
    direction (0 for the local chunk). ``me``/``s`` may be traced;
    ``world``/``dirs`` are static.
    """
    import jax.numpy as jnp
    from jax import lax
    if dirs == 1 or world <= 2:
        off = jnp.asarray(s, jnp.int32)
        chunk = lax.rem(me - off + world, world)
        return chunk, jnp.zeros((), jnp.bool_), off
    s = jnp.asarray(s, jnp.int32)
    n_bwd = (world - 1) // 2
    in_alt = s <= 2 * n_bwd
    is_bwd = in_alt & (lax.rem(s, 2) == 0) & (s > 0)
    off = jnp.where(in_alt, jnp.where(is_bwd, s // 2, (s + 1) // 2),
                    s - n_bwd)
    chunk = lax.rem(jnp.where(is_bwd, me + off, me - off) + world, world)
    return chunk, is_bwd, off


#: Row granularity of one rank's ring chunk. The ring kernels slice an
#: (M, N) operand into ``world`` row chunks, and Mosaic refuses a row
#: slice that is not whole 8-sublane tiles ("Slice shape along dimension
#: 0 must be aligned to tiling (8)", "cannot statically prove that index
#: in dimension 0 is a multiple of 8").
RING_ROW_TILE = 8


def ring_padded_rows(m: int, world: int) -> int:
    """Smallest row count >= ``m`` that splits into ``world`` chunks of
    whole :data:`RING_ROW_TILE` tiles. ``world == 1`` takes no row
    slice, so any ``m`` stands."""
    return m if world == 1 else round_up(m, world * RING_ROW_TILE)


def vmem_spec(block_shape=None, index_map=None):
    return pl.BlockSpec(block_shape, index_map, memory_space=pltpu.VMEM)


def any_spec():
    return pl.BlockSpec(memory_space=pl.ANY)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


@functools.cache
def min_tile(dtype) -> tuple[int, int]:
    """Minimum TPU tile (sublane, lane) for ``dtype`` — layout constraint for
    block shapes (pallas_guide: Tiling Constraints)."""
    import jax.numpy as jnp
    dtype = jnp.dtype(dtype)
    sublane = {4: 8, 2: 16, 1: 32}[dtype.itemsize]
    return (sublane, 128)


def nestable_shard_map(fn, *, mesh=None, in_specs, out_specs,
                       check_vma: bool = False):
    """``jax.shard_map`` for op entry points, callable inside an enclosing
    shard_map.

    When an op runs under an outer manual region — e.g. the user wraps a
    whole model step in ``shard_map(..., axis_names={"dp"})`` for data
    parallelism and the op communicates along "tp" inside it — the inner
    shard_map must reuse the context's AbstractMesh (passing the concrete
    mesh raises a context-mismatch error). Inside the nested region every
    mesh axis is manual, so ``language.logical_device_id`` sees the outer
    (dp) coordinate via ``lax.axis_index`` and remote DMAs stay within the
    dp slice — every fused op composes with outer DP/FSDP axes,
    parallelism the reference delegates to torchrun replication
    (SURVEY.md §2.9 "DP: not a subsystem").
    """
    am = _abstract_mesh()
    if am is not None and any(_manual_axis_flags(am)):
        mesh = am
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def shard_map_1d(fn, mesh, axis: str = "tp"):
    """Wrap ``fn`` in a shard_map over a single mesh axis with everything
    sharded on its leading dim. Convenience for op entry points."""
    from jax.sharding import PartitionSpec as P
    spec = P(axis)
    return nestable_shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec)
