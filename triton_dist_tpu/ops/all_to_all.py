"""Low-latency AllToAll for expert-parallel dispatch/combine.

TPU-native redesign of the reference's LL AllToAll
(python/triton_dist/kernels/nvidia/low_latency_all_to_all.py: single kernel
doing per-peer ``putmem_nbi_block`` of tokens + splits with
``putmem_signal`` / ``signal_wait_until`` :36-120, context + host entry
``fast_all_to_all`` :127-258) and the train-style dispatch/combine
(ep_a2a.py:37-244).

Data model (static shapes — SURVEY.md §7 "Dynamic shapes in EP"): each
device holds a rank-major send buffer ``(world, capacity, H)`` where slab
``p`` carries the ``send_counts[p]`` rows destined for rank ``p``. The
exchange transposes slabs: after the op, recv slab ``j`` holds the rows
rank ``j`` sent here.

The Pallas path sends each slab in row chunks and only transmits the
chunks that contain live rows — the TPU analog of the reference sending
exactly ``splits[expert]`` tokens per peer rather than the whole MAX_M
buffer. Chunk arrival is signalled per (src, chunk) DMA semaphore
(putmem_signal ≙ remote copy's recv semaphore). Counts are exchanged
first via a (tiny) XLA all-to-all — the analog of the reference's splits
pre-exchange (`get_ag_splits_and_recv_offset_for_dispatch`,
ep_a2a.py:244).

The reference double-buffers by call parity (low_latency_all_to_all.py:
140-143) because its symmetric buffers persist across calls; on TPU each
``pallas_call`` owns its buffers and semaphores start/finish at zero, so
the parity protocol collapses — documented design decision.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

import triton_dist_tpu.language as dl
from triton_dist_tpu.resilience import resilient
from triton_dist_tpu.ops.common import (
    cdiv,
    comm_params,
    maybe_noise,
    maybe_straggle,
    nestable_shard_map,
    record_comm,
    resolve_interpret,
    sync_interpret)


def _default_chunk_rows(capacity: int, itemsize: int = 2) -> int:
    """Largest divisor of ``capacity`` that is ≤128 and sublane-tile-
    aligned for the element width: native tiles are (8/16/32, 128) rows
    for 4/2/1-byte elements, so 1-byte wires (the fp8 path's int8
    transport) only take 32-row-aligned chunk offsets. Falls back to the
    full slab (offset 0 — trivially aligned) when no divisor fits."""
    aligned = {4: (128, 64, 32, 16, 8), 2: (128, 64, 32, 16),
               1: (128, 64, 32)}.get(itemsize, (128, 64, 32))
    for c in aligned:
        if capacity % c == 0:
            return c
    return capacity


@dataclasses.dataclass
class AllToAllContext:
    """Analog of the reference's ``create_all_to_all_context``
    (low_latency_all_to_all.py:127): capacity and chunking config; the
    symmetric send/recv buffers and signal arrays live in the kernel."""
    mesh: Mesh
    axis: str = "ep"
    capacity: int = 128          # max rows per (src, dst) pair
    chunk_rows: int | None = None
    interpret: bool | None = None
    # Correctness-debug injection (reference for_correctness sleeps /
    # straggler_option, low_latency_all_to_all.py): see ops/common.py.
    straggler_option: tuple[int, int] | None = None
    for_correctness: bool = False

    @property
    def world_size(self) -> int:
        return self.mesh.shape[self.axis]

    def resolve_chunk(self, itemsize: int = 2) -> int:
        return self.chunk_rows or _default_chunk_rows(self.capacity,
                                                      itemsize)


def create_all_to_all_context(mesh: Mesh | None = None, axis: str = "ep",
                              capacity: int = 128,
                              chunk_rows: int | None = None,
                              interpret: bool | None = None
                              ) -> AllToAllContext:
    if mesh is None:
        from triton_dist_tpu.runtime.dist import get_mesh
        mesh = get_mesh()
    return AllToAllContext(mesh=mesh, axis=axis, capacity=capacity,
                           chunk_rows=chunk_rows, interpret=interpret)


# ---------------------------------------------------------------------------
# Schedule helpers — exposed for symbolic execution (the a2a-protocol
# model checker, analysis/a2a_model.py, executes THESE with concrete
# (rank, position) values, exactly as the ring checker executes
# ``ring_chunk_schedule``). The kernel calls the same functions with
# traced values, so checker and kernel cannot drift apart.
# ---------------------------------------------------------------------------

def a2a_send_peer(me, i, world: int):
    """Peer targeted at send position ``i`` (1..world-1): rank-rotated
    right so no two senders hammer one receiver in lockstep (the
    reference staggers per-peer putmem the same way)."""
    return lax.rem(me + i, world)


def a2a_wait_src(me, i, world: int):
    """Source waited on at wait position ``i`` (1..world-1): the
    left-rotation mirror of :func:`a2a_send_peer` — rank me waits
    first on the peer that targeted it first."""
    return lax.rem(me - i + world, world)


def a2a_live_chunks(count, chunk: int):
    """Chunks actually transmitted for a slab with ``count`` live rows
    (``cdiv``; trailing dead rows of a slab never ride the wire)."""
    return lax.div(count + (chunk - 1), chunk)


def a2a_footprint(world: int, capacity: int, h: int,
                  itemsize: int = 2) -> int:
    """Declared VMEM bytes of one ``fast_all_to_all`` dispatch: the
    (world, capacity, H) send slab input + same-shape recv output both
    live whole in VMEM (counts are SMEM; the per-(slab, chunk) DMA
    semaphore arrays are not VMEM). Consumed by the static
    ``vmem-budget`` sweep (analysis/vmem.py)."""
    return 2 * world * capacity * h * itemsize


def _xla_a2a(mesh: Mesh, axis: str, arr: jax.Array) -> jax.Array:
    """Slab-transposing XLA all-to-all on the leading dim — the one
    sideband exchange pattern (counts, scales, expert ids) written once
    (code-review r3e finding 3)."""
    def body(a):
        return lax.all_to_all(a, axis, split_axis=0, concat_axis=0,
                              tiled=True)
    return nestable_shard_map(body, mesh=mesh, in_specs=(P(axis),),
                              out_specs=P(axis), check_vma=False)(arr)


def _a2a_kernel(send_counts_ref, recv_counts_ref, send_ref, recv_ref,
                send_sem, recv_sem, *, axis: str, world: int, capacity: int,
                chunk: int, straggler_option=None, for_correctness=False,
                interp=False):
    """Per-device body: push live chunks of each slab to its peer.

    Per peer p: ``n = cdiv(send_counts[p], chunk)`` chunk DMAs
    ``send[p, c*chunk : (c+1)*chunk] → peer_p.recv[me, ...]`` (reference
    ``putmem_nbi_block`` per expert range, low_latency_all_to_all.py:52-99).
    Then wait ``cdiv(recv_counts[j], chunk)`` arrivals per source j
    (reference ``signal_wait_until`` :108-118). Per-(slab, chunk)
    semaphore slots — no FIFO assumption across chunks.
    """
    me = lax.axis_index(axis)
    n_chunks = capacity // chunk

    # Self slab: plain VMEM copy, no DMA (reference skips rank==me too).
    recv_ref[me] = send_ref[me]
    if world == 1:
        return
    # Peers' recv buffers must exist before remote writes land.
    dl.barrier_all(axis)
    maybe_straggle(straggler_option, axis, interp)
    maybe_noise(for_correctness, axis, world, salt=6, interpret=interp)

    def chunk_copy(p, c):
        # dst slab on peer p is indexed by *our* rank; semaphore slot
        # (me→slab, c) on the receiver.
        return dl.remote_copy(
            send_ref.at[p, pl.ds(c * chunk, chunk), :],
            recv_ref.at[me, pl.ds(c * chunk, chunk), :],
            p, send_sem.at[p, c], recv_sem.at[me, c], axis=axis)

    def send_to(i, _):
        p = a2a_send_peer(me, i, world)
        live = a2a_live_chunks(send_counts_ref[p], chunk)

        def one(c, _):
            @pl.when(c < live)
            def _():
                chunk_copy(p, c).start()
            return _
        lax.fori_loop(0, n_chunks, one, None)
        return _

    lax.fori_loop(1, world, send_to, None)

    def wait_from(i, _):
        j = a2a_wait_src(me, i, world)
        live = a2a_live_chunks(recv_counts_ref[j], chunk)

        def one(c, _):
            @pl.when(c < live)
            def _():
                # Mirror descriptor for the incoming DMA from j.
                dl.remote_copy(
                    send_ref.at[j, pl.ds(c * chunk, chunk), :],
                    recv_ref.at[j, pl.ds(c * chunk, chunk), :],
                    me, send_sem.at[j, c], recv_sem.at[j, c],
                    axis=axis).wait_recv()
            return _
        lax.fori_loop(0, n_chunks, one, None)
        return _

    lax.fori_loop(1, world, wait_from, None)

    def drain(i, _):
        p = a2a_send_peer(me, i, world)
        live = a2a_live_chunks(send_counts_ref[p], chunk)

        def one(c, _):
            @pl.when(c < live)
            def _():
                chunk_copy(p, c).wait_send()
            return _
        lax.fori_loop(0, n_chunks, one, None)
        return _

    lax.fori_loop(1, world, drain, None)


@resilient("all_to_all")
def fast_all_to_all(send_buf: jax.Array, send_counts: jax.Array,
                    ctx: AllToAllContext | None = None,
                    impl: str = "pallas"):
    """Exchange rank-major slabs (functional entry, reference
    ``fast_all_to_all`` low_latency_all_to_all.py:198).

    Args:
      send_buf: (world, capacity, H) per device — slab p goes to rank p.
        Sharded as the *local* buffer of each device (global shape
        (world*world, capacity, H) with leading dim sharded).
      send_counts: (world,) int32 per device (global (world*world,)).

    Returns:
      (recv_buf, recv_counts) with the same layouts; recv slab j came from
      rank j. Rows past ``recv_counts[j]`` in a slab are undefined (the
      reference leaves stale data there too — consumers mask by splits).
    """
    ctx = ctx or create_all_to_all_context()
    mesh, axis, world = ctx.mesh, ctx.axis, ctx.world_size
    record_comm("all_to_all", send_buf)
    capacity = ctx.capacity
    chunk = ctx.resolve_chunk(send_buf.dtype.itemsize)
    assert capacity % chunk == 0
    assert send_buf.shape[0] == world * world and send_buf.shape[1] == capacity

    if impl == "xla" or world == 1:
        return (_xla_a2a(mesh, axis, send_buf),
                _xla_a2a(mesh, axis, send_counts))

    interpret = resolve_interpret(ctx.interpret)
    kernel = functools.partial(_a2a_kernel, axis=axis, world=world,
                               capacity=capacity, chunk=chunk,
                               straggler_option=ctx.straggler_option,
                               for_correctness=ctx.for_correctness,
                               interp=bool(interpret))
    n_chunks = capacity // chunk

    def body(buf, counts, rcounts):
        recv = pl.pallas_call(
            kernel,
            name="all_to_all",
            out_shape=jax.ShapeDtypeStruct(buf.shape, buf.dtype),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.SemaphoreType.DMA((world, n_chunks)),
                            pltpu.SemaphoreType.DMA((world, n_chunks))],
            compiler_params=comm_params(collective_id=6, world=world),
            interpret=interpret,
        )(counts, rcounts, buf)
        return recv

    def outer(buf, counts):
        rcounts = lax.all_to_all(counts, axis, split_axis=0, concat_axis=0,
                                 tiled=True)
        return body(buf, counts, rcounts), rcounts

    f = nestable_shard_map(outer, mesh=mesh, in_specs=(P(axis), P(axis)),
                      out_specs=(P(axis), P(axis)), check_vma=False)
    return sync_interpret(f(send_buf, send_counts), interpret)


# ---------------------------------------------------------------------------
# FP8-quantized dispatch (the reference's headline LL-a2a configuration:
# 128 tok/rank, hidden 7168, **fp8** + per-token scales — README.md:97,
# low_latency_all_to_all.py:60-99 sends tokens as fp8 blocks and their
# scales via a separate putmem_signal channel).
# ---------------------------------------------------------------------------

_FP8_MAX = 448.0        # float8_e4m3fn finite max


def quantize_fp8_rows(x: jax.Array):
    """Per-row symmetric fp8(e4m3) quantization.

    Returns (q, scales): ``q = fp8(x / scale)`` with
    ``scale = max|row| / 448`` broadcast per leading-row, f32 scales of
    shape ``x.shape[:-1]``. Rows of zeros get scale 1 (exact zeros)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(amax > 0, amax / _FP8_MAX, 1.0)
    q = (x.astype(jnp.float32) / scale[..., None]
         ).astype(jnp.float8_e4m3fn)
    return q, scale


def dequantize_fp8_rows(q: jax.Array, scale: jax.Array,
                        dtype=jnp.bfloat16) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fast_all_to_all_fp8(send_buf: jax.Array, send_counts: jax.Array,
                        ctx: AllToAllContext | None = None,
                        impl: str = "pallas"):
    """LL AllToAll at fp8 wire precision: 2x (bf16) / 4x (f32) less ICI
    traffic for the token payload.

    Tokens are row-quantized to float8_e4m3fn, BITCAST to int8 for
    transport (the exchange kernel then only ever moves bytes — no
    Mosaic fp8 arithmetic on the hot path; chunk offsets are 32-row
    aligned for the 1-byte tile via ``resolve_chunk(itemsize=1)``), and
    dequantized with the exchanged scales on arrival. Scales ride the
    sideband XLA all-to-all, the analog of the reference's separate
    scale channel with its own ``putmem_signal``
    (low_latency_all_to_all.py:60-99).

    Inference-only: differentiating through the quantizer is
    meaningless; a jax.grad over this op raises a pointed error instead
    of the opaque bitcast one (use ``wire_dtype=None`` to train).

    Args/returns: as :func:`fast_all_to_all`, plus the received scales
    are folded back in — the result is dequantized to ``send_buf.dtype``.
    Rows past ``recv_counts[j]`` remain undefined.
    """
    ctx = ctx or create_all_to_all_context()
    out_dtype = send_buf.dtype
    q, scale = quantize_fp8_rows(send_buf)
    wire = lax.bitcast_convert_type(q, jnp.int8)
    recv_wire, recv_counts = fast_all_to_all(wire, send_counts, ctx,
                                             impl=impl)
    recv_scale = _xla_a2a(ctx.mesh, ctx.axis, scale)
    recv_q = lax.bitcast_convert_type(recv_wire, jnp.float8_e4m3fn)
    return dequantize_fp8_rows(recv_q, recv_scale, out_dtype), recv_counts


def _fp8_fwd(send_buf, send_counts, ctx, impl):
    return fast_all_to_all_fp8(send_buf, send_counts, ctx, impl), None


def _fp8_bwd(ctx, impl, res, cots):
    raise NotImplementedError(
        "fast_all_to_all_fp8 / wire_dtype='fp8' is inference-only: the "
        "fp8 wire quantizer has no useful gradient. Train with the "
        "plain wire (wire_dtype=None; ops.autodiff.fast_all_to_all).")


fast_all_to_all_fp8.defvjp(_fp8_fwd, _fp8_bwd)
