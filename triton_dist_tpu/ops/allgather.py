"""AllGather variants over the ICI mesh.

TPU-native redesign of the reference's copy-engine AllGather family
(python/triton_dist/kernels/nvidia/allgather.py: ``AllGatherMethod`` enum
:46-73, per-variant producers :81-370, device put kernels :380-470).

The reference picks among full-mesh push/pull, 1-D ring, 2-D numa ring and
broadcast based on NVLink topology. On a TPU torus the natural methods are:

- ``RING_1D``     — neighbor ring over the mesh axis; each hop rides one ICI
  link. Bandwidth-optimal for large payloads.
- ``RING_BIDIR``  — both ring directions at once (ICI links are full
  duplex): halves the number of steps. The analog of the reference's 2-D
  ring exploiting extra links.
- ``FULL_MESH_PUSH`` — every device puts its shard directly to all peers;
  minimizes latency for small payloads (analog of reference full-mesh
  push, allgather.py:81-170).
- ``AUTO``        — size-based choice (analog of
  ``get_auto_all_gather_method``, allgather.py:46-73).

Implementations: ``impl="xla"`` lowers to ``jax.lax.all_gather`` (golden /
fallback); ``impl="pallas"`` is the explicit remote-DMA kernel.
"""

from __future__ import annotations

import dataclasses
import enum
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

import triton_dist_tpu.language as dl
from triton_dist_tpu.resilience import resilient
from triton_dist_tpu.ops.common import (
    comm_params,
    maybe_noise,
    maybe_straggle,
    nestable_shard_map,
    record_comm,
    resolve_interpret,
    sync_interpret)


class AllGatherMethod(enum.Enum):
    AUTO = "auto"
    RING_1D = "ring_1d"
    RING_BIDIR = "ring_bidir"
    FULL_MESH_PUSH = "full_mesh_push"
    # One source rank pushes its buffer to every peer (reference
    # low_latency_allgather.py broadcast variants :48-210).
    BROADCAST = "broadcast"


# LL (flag-in-data) packet mapping: the reference's low-latency AG packs
# an 8-byte flag into each 16-byte data quantum so the receiver can spin
# on the DATA buffer instead of a separate signal
# (low_latency_allgather.py:531-549 _pack_ll_block/_recv_ll_block) — an
# artifact of NVLink writes carrying no completion signal. On TPU the
# transport signals the receiver's DMA semaphore ON DELIVERY of each
# remote copy, so every `impl="pallas"` method here already has LL
# semantics: the per-chunk recv-semaphore wait IS the flag spin, with no
# bandwidth tax and no two-pass packing. The 2d/3d multinode variants
# (:48-780) map to ops/hierarchical.all_gather_2d (ICI x DCN two-level).


def get_auto_all_gather_method(world_size: int, nbytes_per_rank: int,
                               spec=None) -> AllGatherMethod:
    """Perf-model-driven method choice (reference
    get_auto_all_gather_method allgather.py:46-73 picks from probed
    bandwidth models, comm_perf_model.py:94-116): full-mesh push wins
    when its single-launch latency beats the ring's per-step fixed
    costs; the bidirectional ring wins once payloads are
    bandwidth-bound (through-traffic makes full-mesh scale as w·w/4
    hops)."""
    from triton_dist_tpu.tools.perf_model import (
        estimate_all_gather_time_ms, estimate_full_mesh_push_time_ms)
    if world_size <= 2:
        return AllGatherMethod.FULL_MESH_PUSH
    t_fm = estimate_full_mesh_push_time_ms(nbytes_per_rank, world_size,
                                           spec)
    t_ring = estimate_all_gather_time_ms(nbytes_per_rank, world_size,
                                         spec, bidir=True)
    return (AllGatherMethod.FULL_MESH_PUSH if t_fm <= t_ring
            else AllGatherMethod.RING_BIDIR)


@dataclasses.dataclass
class AllGatherContext:
    """Per-op context (reference ``create_ag_context`` pattern: the reference
    allocates symmetric workspaces here; on TPU the kernel's output buffer
    *is* the symmetric workspace, so the context carries config only)."""
    mesh: Mesh
    axis: str = "tp"
    method: AllGatherMethod = AllGatherMethod.AUTO
    interpret: bool | None = None
    # Correctness-debug injection (reference for_correctness sleeps
    # allgather.py:74-79 and straggler_option): see ops/common.py.
    straggler_option: tuple[int, int] | None = None
    for_correctness: bool = False

    @property
    def world_size(self) -> int:
        return self.mesh.shape[self.axis]

    def resolve_method(self, nbytes_per_rank: int) -> AllGatherMethod:
        if self.method is AllGatherMethod.AUTO:
            return get_auto_all_gather_method(self.world_size,
                                              nbytes_per_rank)
        return self.method


def create_allgather_context(mesh: Mesh | None = None, axis: str = "tp",
                             method: AllGatherMethod = AllGatherMethod.AUTO,
                             interpret: bool | None = None) -> AllGatherContext:
    if mesh is None:
        from triton_dist_tpu.runtime.dist import get_mesh
        mesh = get_mesh()
    return AllGatherContext(mesh=mesh, axis=axis, method=method,
                            interpret=interpret)


# ---------------------------------------------------------------------------
# Pallas kernels (per-device bodies under shard_map)
# ---------------------------------------------------------------------------

def _ring_ag_kernel(x_ref, o_ref, send_sem, recv_sem, *, axis: str,
                    world: int, rows: int, bidir: bool,
                    straggler_option=None, for_correctness=False,
                    interp=False):
    """Ring all-gather. Unidirectional: w-1 hops to the right.
    Bidirectional: chunks travel the shorter way round; ceil((w-1)/2) steps.

    Analog of the reference's ring copy chain (allgather.py:232-370) with
    the copy engine replaced by in-kernel remote DMA (SURVEY.md §5:
    copy-engine AG ≙ RDMA inside the kernel)."""
    me = lax.axis_index(axis)
    right = lax.rem(me + 1, world)
    left = lax.rem(me - 1 + world, world)

    o_ref[pl.ds(me * rows, rows), :] = x_ref[:]
    if world == 1:
        return
    # Peers must have written their own chunk (and exist) before remote
    # writes into their o_ref land.
    dl.barrier_all(axis)
    maybe_straggle(straggler_option, axis, interp)
    maybe_noise(for_correctness, axis, world, salt=1, interpret=interp)

    n_fwd = (world - 1 + 1) // 2 if bidir else world - 1
    n_bwd = (world - 1) - n_fwd if bidir else 0

    # Semaphore slots are PER CHUNK, not per step: delivery is not assumed
    # FIFO, and a fast upstream neighbor may run several steps ahead. With
    # one reused semaphore its later-chunk signal could satisfy an earlier
    # wait and we would forward a not-yet-arrived region (the reference
    # avoids the same race with per-(rank,segment) flags, allgather.py
    # set_ready/wait protocol).
    def chunk_copy(idx, peer, direction):
        return dl.remote_copy(
            o_ref.at[pl.ds(idx * rows, rows), :],
            o_ref.at[pl.ds(idx * rows, rows), :],
            peer, send_sem.at[idx], recv_sem.at[direction, idx], axis=axis)

    def step(s, _):
        fwd_idx = lax.rem(me - s + world, world)
        fwd_recv = lax.rem(me - s - 1 + world, world)

        # Start both directions before waiting on either: the two copies
        # ride opposite (full-duplex) ICI links concurrently.
        @pl.when(s < n_fwd)
        def _():
            chunk_copy(fwd_idx, right, 0).start()

        if bidir:
            bwd_idx = lax.rem(me + s, world)
            bwd_recv = lax.rem(me + s + 1, world)

            @pl.when(s < n_bwd)
            def _():
                chunk_copy(bwd_idx, left, 1).start()

            @pl.when(s < n_bwd)
            def _():
                # wait for the chunk arriving from the RIGHT (it travels
                # leftwards); it is next step's bwd send.
                chunk_copy(bwd_recv, left, 1).wait_recv()

        @pl.when(s < n_fwd)
        def _():
            # chunk arriving from the LEFT; next step's fwd send.
            chunk_copy(fwd_recv, right, 0).wait_recv()
        return _

    lax.fori_loop(0, max(n_fwd, n_bwd), step, None)

    # Drain send completions so the kernel does not retire with DMAs in
    # flight.
    def drain(s, _):
        @pl.when(s < n_fwd)
        def _():
            chunk_copy(lax.rem(me - s + world, world), right, 0).wait_send()
        if bidir:
            @pl.when(s < n_bwd)
            def _():
                chunk_copy(lax.rem(me + s, world), left, 1).wait_send()
        return _

    lax.fori_loop(0, max(n_fwd, n_bwd), drain, None)


def _broadcast_kernel(x_ref, o_ref, send_sem, recv_sem, *, axis: str,
                      world: int, root: int):
    """Root pushes its full buffer to every peer (reference LL-AG
    broadcast, low_latency_allgather.py:48-210). Non-root ranks just
    wait for delivery on their recv semaphore (the LL flag analog)."""
    me = lax.axis_index(axis)

    @pl.when(me == root)
    def _():
        o_ref[...] = x_ref[...]
    if world == 1:
        return
    dl.barrier_all(axis)

    def copy_to(peer):
        return dl.remote_copy(o_ref, o_ref, peer, send_sem.at[peer],
                              recv_sem, axis=axis)

    @pl.when(me == root)
    def _():
        def send(p, _):
            peer = lax.rem(root + p, world)
            copy_to(peer).start()
            return _
        lax.fori_loop(1, world, send, None)

        def drain(p, _):
            copy_to(lax.rem(root + p, world)).wait_send()
            return _
        lax.fori_loop(1, world, drain, None)

    @pl.when(me != root)
    def _():
        copy_to(me).wait_recv()


def _full_mesh_push_kernel(x_ref, o_ref, send_sem, recv_sem, *, axis: str,
                           world: int, rows: int, straggler_option=None,
                           for_correctness=False, interp=False):
    """Every device puts its chunk to all peers (reference full-mesh push,
    allgather.py:81-170). Latency-optimal: one hop, w-1 concurrent DMAs."""
    me = lax.axis_index(axis)
    o_ref[pl.ds(me * rows, rows), :] = x_ref[:]
    if world == 1:
        return
    dl.barrier_all(axis)
    maybe_straggle(straggler_option, axis, interp)
    maybe_noise(for_correctness, axis, world, salt=2, interpret=interp)

    def send(p, _):
        peer = lax.rem(me + p, world)
        dl.remote_copy(
            o_ref.at[pl.ds(me * rows, rows), :],
            o_ref.at[pl.ds(me * rows, rows), :],
            peer, send_sem.at[peer], recv_sem.at[me], axis=axis).start()
        return _

    lax.fori_loop(1, world, send, None)

    def wait_one(p, _):
        src = lax.rem(me - p + world, world)
        # Mirror descriptor: wait for the copy that src issued into our
        # recv_sem[src] slot (standard Pallas pattern for waiting on an
        # incoming remote DMA).
        dl.remote_copy(
            o_ref.at[pl.ds(src * rows, rows), :],
            o_ref.at[pl.ds(src * rows, rows), :],
            me, send_sem.at[src], recv_sem.at[src], axis=axis).wait_recv()
        return _

    lax.fori_loop(1, world, wait_one, None)

    def wait_send(p, _):
        peer = lax.rem(me + p, world)
        dl.remote_copy(
            o_ref.at[pl.ds(me * rows, rows), :],
            o_ref.at[pl.ds(me * rows, rows), :],
            peer, send_sem.at[peer], recv_sem.at[me], axis=axis).wait_send()
        return _

    lax.fori_loop(1, world, wait_send, None)


# ---------------------------------------------------------------------------
# Functional entry
# ---------------------------------------------------------------------------

@resilient("allgather")
def all_gather(x: jax.Array, ctx: AllGatherContext | None = None,
               impl: str = "pallas", stacked: bool = False) -> jax.Array:
    """Gather ``x`` (sharded on dim 0 over ``ctx.axis``) onto every device.

    Functional entry (reference ``cp_engine_producer_all_gather_*`` host
    wrappers). Returns the gathered array, replicated — or, with
    ``stacked=True``, with a leading per-device dim (w, M, N) so tests can
    check every device's copy.
    """
    ctx = ctx or create_allgather_context()
    mesh, axis, world = ctx.mesh, ctx.axis, ctx.world_size
    record_comm("allgather", x)
    assert x.shape[0] % world == 0, (x.shape, world)
    rows = x.shape[0] // world
    method = ctx.resolve_method(
        rows * x.dtype.itemsize * math.prod(x.shape[1:]))

    out_spec = P(axis) if stacked else P()

    if impl == "xla":
        def body(xs):
            g = lax.all_gather(xs, axis, tiled=True)
            return g
        f = nestable_shard_map(body, mesh=mesh, in_specs=P(axis),
                          out_specs=out_spec, check_vma=False)
        return f(x)

    interpret = resolve_interpret(ctx.interpret)

    if method is AllGatherMethod.BROADCAST:
        raise ValueError(
            "BROADCAST is one-to-all, not an all-gather — call "
            "ops.allgather.broadcast(x, root, ctx) instead")

    inject = dict(straggler_option=ctx.straggler_option,
                  for_correctness=ctx.for_correctness,
                  interp=bool(interpret))
    if method in (AllGatherMethod.RING_1D, AllGatherMethod.RING_BIDIR):
        kernel = functools.partial(
            _ring_ag_kernel, axis=axis, world=world, rows=rows,
            bidir=method is AllGatherMethod.RING_BIDIR, **inject)
        scratch = [pltpu.SemaphoreType.DMA((world,)),
                   pltpu.SemaphoreType.DMA((2, world))]
    else:
        kernel = functools.partial(
            _full_mesh_push_kernel, axis=axis, world=world, rows=rows,
            **inject)
        scratch = [pltpu.SemaphoreType.DMA((world,)),
                   pltpu.SemaphoreType.DMA((world,))]

    def body(xs):
        return pl.pallas_call(
            kernel,
            name=f"all_gather_{method.value}",
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=scratch,
            compiler_params=comm_params(collective_id=1, world=world),
            interpret=interpret,
        )(xs)

    f = nestable_shard_map(body, mesh=mesh, in_specs=P(axis),
                      out_specs=out_spec, check_vma=False)
    return sync_interpret(f(x), interpret)


@resilient("broadcast")
def broadcast(x: jax.Array, root: int = 0,
              ctx: AllGatherContext | None = None,
              impl: str = "pallas") -> jax.Array:
    """Rank ``root``'s row-chunk of ``x`` on every device (reference
    LL-AG broadcast variants, low_latency_allgather.py:48-210).

    Args:
      x: (w·M, N) row-sharded over ``ctx.axis`` — chunk r is rank r's
        buffer.
    Returns:
      (M, N) — the root's chunk, replicated.
    """
    ctx = ctx or create_allgather_context()
    mesh, axis, world = ctx.mesh, ctx.axis, ctx.world_size
    record_comm("broadcast", x)
    assert x.shape[0] % world == 0
    if not 0 <= root < world:
        raise ValueError(f"root {root} out of range for world {world}")
    rows = x.shape[0] // world

    if impl == "xla":
        def body(xs):
            src = jnp.zeros((world,), x.dtype).at[root].set(1).reshape(
                (world,) + (1,) * (x.ndim - 1)).astype(x.dtype)
            return lax.psum(xs * src[lax.axis_index(axis)], axis)
        f = nestable_shard_map(body, mesh=mesh, in_specs=P(axis),
                          out_specs=P(), check_vma=False)
        return f(x)

    interpret = resolve_interpret(ctx.interpret)
    kernel = functools.partial(_broadcast_kernel, axis=axis, world=world,
                               root=root)

    def body(xs):
        return pl.pallas_call(
            kernel,
            name="broadcast",
            out_shape=jax.ShapeDtypeStruct((rows,) + x.shape[1:], x.dtype),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.SemaphoreType.DMA((world,)),
                            pltpu.SemaphoreType.DMA],
            compiler_params=comm_params(collective_id=1, world=world),
            interpret=interpret,
        )(xs)

    f = nestable_shard_map(body, mesh=mesh, in_specs=P(axis),
                      out_specs=P(), check_vma=False)
    return sync_interpret(f(x), interpret)
