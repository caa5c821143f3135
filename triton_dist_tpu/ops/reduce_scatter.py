"""ReduceScatter over the ICI mesh.

TPU-native redesign of the reference's ReduceScatter
(python/triton_dist/kernels/nvidia/reduce_scatter.py: ctx :47-146, ring push
variants :285-504, ``ring_reduce`` :674-826, 2-D intra+inter op :857).

Methods:

- ``RING``      — classic ring reduce-scatter: w-1 hops, each device
  accumulates a travelling partial and forwards it; bandwidth-optimal.
  The reference's ``ring_reduce`` on a torus axis.
- ``ONE_SHOT``  — every device pushes each peer's chunk directly to that
  peer's staging slots, then each peer reduces w partials locally. One hop
  (latency-optimal, small payloads) — analog of the reference's
  scatter-then-local-reduce consumer (gemm_reduce_scatter.py scatter path).

The 2-D (intra-node × inter-node) hierarchy of the reference maps to
composing this op over two mesh axes ("tp" within a pod slice, "dcn"
across) — see ops/hierarchical.py.
"""

from __future__ import annotations

import dataclasses
import enum
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
    comm_params,
    nestable_shard_map,
    record_comm,
    resolve_interpret,
    sync_interpret)


class ReduceScatterMethod(enum.Enum):
    AUTO = "auto"
    RING = "ring"
    ONE_SHOT = "one_shot"


@dataclasses.dataclass
class ReduceScatterContext:
    mesh: Mesh
    axis: str = "tp"
    method: ReduceScatterMethod = ReduceScatterMethod.AUTO
    interpret: bool | None = None

    @property
    def world_size(self) -> int:
        return self.mesh.shape[self.axis]

    def resolve_method(self, nbytes_per_chunk: int) -> ReduceScatterMethod:
        """Perf-model crossover (reference comm_perf_model.py:116):
        one-shot's single push round wins at small chunks; the ring wins
        once its per-step fixed costs are amortized."""
        if self.method is not ReduceScatterMethod.AUTO:
            return self.method
        if self.world_size <= 2:
            return ReduceScatterMethod.ONE_SHOT
        from triton_dist_tpu.tools.perf_model import (
            estimate_one_shot_reduce_time_ms,
            estimate_reduce_scatter_time_ms)
        t_one = estimate_one_shot_reduce_time_ms(nbytes_per_chunk,
                                                 self.world_size)
        t_ring = estimate_reduce_scatter_time_ms(nbytes_per_chunk,
                                                 self.world_size)
        return (ReduceScatterMethod.ONE_SHOT if t_one <= t_ring
                else ReduceScatterMethod.RING)


def create_reduce_scatter_context(
        mesh: Mesh | None = None, axis: str = "tp",
        method: ReduceScatterMethod = ReduceScatterMethod.AUTO,
        interpret: bool | None = None) -> ReduceScatterContext:
    if mesh is None:
        from triton_dist_tpu.runtime.dist import get_mesh
        mesh = get_mesh()
    return ReduceScatterContext(mesh=mesh, axis=axis, method=method,
                                interpret=interpret)


def _ring_rs_kernel(x_ref, o_ref, send_buf, recv_buf, send_sem, recv_sem, *,
                    axis: str, world: int, rows: int):
    """Ring reduce-scatter (reference ``ring_reduce``
    reduce_scatter.py:674-826).

    Chunk c starts at device (c+1)%w and travels right, accumulating each
    device's local contribution; after w-1 hops it lands, fully reduced, on
    device c.

    Buffers and semaphores are PER STEP (send_buf/recv_buf: (w-1, rows, N)):
    a neighbor may run ahead, and delivery is not assumed FIFO — with reused
    slots its step-(s+2) payload could clobber an unconsumed step-s payload
    (the reference serializes with per-segment flags instead,
    reduce_scatter.py ring push protocol).
    """
    me = lax.axis_index(axis)
    right = lax.rem(me + 1, world)

    if world == 1:
        o_ref[:] = x_ref[pl.ds(me * rows, rows), :]
        return

    dl.barrier_all(axis)

    def step_copy(s):
        return dl.remote_copy(send_buf.at[s], recv_buf.at[s], right,
                              send_sem.at[s], recv_sem.at[s], axis=axis)

    def step(s, _):
        send_idx = lax.rem(me - s - 1 + world, world)

        # Partial to forward: my contribution + the travelling partial
        # received last step (if any).
        @pl.when(s == 0)
        def _():
            send_buf[s] = x_ref[pl.ds(send_idx * rows, rows), :]

        @pl.when(s > 0)
        def _():
            send_buf[s] = (recv_buf[jnp.maximum(s - 1, 0)] +
                           x_ref[pl.ds(send_idx * rows, rows), :])

        step_copy(s).start()
        # Wait for the incoming step-s partial from the left neighbor
        # (it feeds next step's send).
        step_copy(s).wait_recv()
        return _

    lax.fori_loop(0, world - 1, step, None)
    o_ref[:] = recv_buf[world - 2] + x_ref[pl.ds(me * rows, rows), :]

    def drain(s, _):
        step_copy(s).wait_send()
        return _

    lax.fori_loop(0, world - 1, drain, None)


def _one_shot_rs_kernel(x_ref, o_ref, stage_ref, send_sem, recv_sem, *,
                        axis: str, world: int, rows: int):
    """Scatter-then-reduce: push chunk p to peer p's staging slot [me], then
    locally sum the w staged partials (analog of the reference's
    scatter+local-reduce path, reduce_scatter.py:285-360)."""
    me = lax.axis_index(axis)
    stage_ref[me] = x_ref[pl.ds(me * rows, rows), :]
    if world == 1:
        o_ref[:] = stage_ref[me]
        return
    dl.barrier_all(axis)

    def send(p, _):
        peer = lax.rem(me + p, world)
        dl.remote_copy(
            x_ref.at[pl.ds(peer * rows, rows), :],
            stage_ref.at[me],
            peer, send_sem.at[peer], recv_sem.at[me], axis=axis).start()
        return _

    lax.fori_loop(1, world, send, None)

    def wait_recv(p, _):
        src = lax.rem(me - p + world, world)
        dl.remote_copy(
            x_ref.at[pl.ds(me * rows, rows), :],
            stage_ref.at[src],
            me, send_sem.at[src], recv_sem.at[src], axis=axis).wait_recv()
        return _

    lax.fori_loop(1, world, wait_recv, None)

    acc = stage_ref[0]
    for p in range(1, world):
        acc = acc + stage_ref[p]
    o_ref[:] = acc

    def wait_send(p, _):
        peer = lax.rem(me + p, world)
        dl.remote_copy(
            x_ref.at[pl.ds(peer * rows, rows), :],
            stage_ref.at[me],
            peer, send_sem.at[peer], recv_sem.at[me], axis=axis).wait_send()
        return _

    lax.fori_loop(1, world, wait_send, None)


@resilient("reduce_scatter")
def reduce_scatter(x: jax.Array, ctx: ReduceScatterContext | None = None,
                   impl: str = "pallas") -> jax.Array:
    """Reduce-scatter ``x`` along dim 0: every device holds the full (M, N)
    partial; device i receives the fully-reduced rows [i*M/w, (i+1)*M/w).

    Input: replicated-shape partials (each device's local (M, N)); passed as
    a global (w*M_chunkful...)? No — input is the per-device partial
    expressed as a global array of shape (w, M, N) sharded on dim 0 (one
    partial per device). Output: (M, N) sharded on dim 0 over the axis.
    """
    ctx = ctx or create_reduce_scatter_context()
    mesh, axis, world = ctx.mesh, ctx.axis, ctx.world_size
    record_comm("reduce_scatter", x)
    assert x.shape[0] == world, (x.shape, world)
    m, n = x.shape[1], x.shape[2]
    assert m % world == 0
    rows = m // world
    method = ctx.resolve_method(rows * n * x.dtype.itemsize)

    if impl == "xla":
        def body(xs):
            local = xs[0]  # (M, N) partial
            return lax.psum_scatter(local, axis, scatter_dimension=0,
                                    tiled=True)[None]
        f = nestable_shard_map(body, mesh=mesh, in_specs=P(axis),
                          out_specs=P(axis), check_vma=False)
        return f(x).reshape(m, n)

    interpret = resolve_interpret(ctx.interpret)

    if method is ReduceScatterMethod.RING:
        kernel = functools.partial(_ring_rs_kernel, axis=axis, world=world,
                                   rows=rows)
        scratch = [pltpu.VMEM((world - 1, rows, n), x.dtype),
                   pltpu.VMEM((world - 1, rows, n), x.dtype),
                   pltpu.SemaphoreType.DMA((world - 1,)),
                   pltpu.SemaphoreType.DMA((world - 1,))]
    else:
        kernel = functools.partial(_one_shot_rs_kernel, axis=axis,
                                   world=world, rows=rows)
        scratch = [pltpu.VMEM((world, rows, n), x.dtype),
                   pltpu.SemaphoreType.DMA((world,)),
                   pltpu.SemaphoreType.DMA((world,))]

    def body(xs):
        return pl.pallas_call(
            kernel,
            name=f"reduce_scatter_{method.value}",
            out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=scratch,
            compiler_params=comm_params(collective_id=2, world=world),
            interpret=interpret,
        )(xs[0])

    f = nestable_shard_map(body, mesh=mesh, in_specs=P(axis),
                      out_specs=P(axis), check_vma=False)
    return sync_interpret(f(x), interpret)
