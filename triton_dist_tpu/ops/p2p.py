"""Point-to-point pipeline-parallel transfers.

TPU-native redesign of the reference's PP p2p kernels
(python/triton_dist/kernels/nvidia/p2p.py: ``p2p_copy_kernel`` push :31 /
pull :54 — one-sided copies between pp ranks' symmetric buffers, with
per-rank set/wait signals).

On an ICI mesh a pipeline hop is a neighbor transfer:

- ``impl="xla"``    — ``lax.ppermute`` shift along the pp axis (XLA
  schedules it asynchronously; this is the idiomatic path).
- ``impl="pallas"`` — explicit remote DMA kernel: each device pushes its
  buffer to the next stage and waits the incoming DMA's recv semaphore
  (the signal set/wait protocol of the reference collapses into the DMA
  semaphore pair).
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
    comm_params,
    nestable_shard_map,
    resolve_interpret,
    sync_interpret)


@dataclasses.dataclass
class P2PContext:
    mesh: Mesh
    axis: str = "pp"
    interpret: bool | None = None

    @property
    def world_size(self) -> int:
        return self.mesh.shape[self.axis]


def create_p2p_context(mesh: Mesh | None = None, axis: str = "pp",
                       interpret: bool | None = None) -> P2PContext:
    if mesh is None:
        from triton_dist_tpu.runtime.dist import get_mesh
        mesh = get_mesh()
    return P2PContext(mesh=mesh, axis=axis, interpret=interpret)


def shift_partners(me, delta: int, world: int):
    """(dst, src) of one pipeline hop: push to ``me+delta``, receive
    from ``me-delta``. Exposed for symbolic execution — the
    p2p-protocol model checker (analysis/p2p_model.py) executes this
    with concrete ranks, exactly as the ring checker executes
    ``ring_chunk_schedule``; the kernel calls it with traced values so
    the two cannot drift apart."""
    span = (abs(delta) // world + 1) * world    # keep lax.rem args >= 0
    return (lax.rem(me + delta + span, world),
            lax.rem(me - delta + span, world))


def _shift_kernel(x_ref, o_ref, send_sem, recv_sem, *, axis: str,
                  world: int, delta: int):
    """Push local buffer to rank (me+delta); receive from (me-delta)."""
    me = lax.axis_index(axis)
    dst, src = shift_partners(me, delta, world)
    dl.barrier_all(axis)
    dl.remote_copy(x_ref.at[:], o_ref.at[:], dst, send_sem, recv_sem,
                   axis=axis).start()
    # Mirror descriptor: wait for the DMA arriving from src.
    dl.remote_copy(x_ref.at[:], o_ref.at[:], me, send_sem, recv_sem,
                   axis=axis).wait_recv()
    dl.remote_copy(x_ref.at[:], o_ref.at[:], dst, send_sem, recv_sem,
                   axis=axis).wait_send()


@resilient("pp_shift")
def pp_shift(x: jax.Array, ctx: P2PContext | None = None, delta: int = 1,
             impl: str = "pallas") -> jax.Array:
    """Shift per-stage activations one pipeline hop (functional entry;
    reference ``p2p_copy_kernel`` push, p2p.py:31).

    Args:
      x: (stages, ...) with the leading dim sharded over the pp axis —
        each stage's activation block.
      delta: +1 forward (stage i → i+1), -1 backward.
    Returns:
      same layout; stage i now holds what stage i-delta had. The wrap
      entry (stage 0 for delta=+1) carries stage w-1's buffer — pipeline
      schedulers treat it as the bubble slot.
    """
    ctx = ctx or create_p2p_context()
    mesh, axis, world = ctx.mesh, ctx.axis, ctx.world_size
    if world == 1:
        return x

    if impl == "xla":
        perm = [(i, (i + delta) % world) for i in range(world)]

        def body(xs):
            return lax.ppermute(xs, axis, perm)
        return nestable_shard_map(body, mesh=mesh, in_specs=P(axis),
                             out_specs=P(axis), check_vma=False)(x)

    interpret = resolve_interpret(ctx.interpret)
    kernel = functools.partial(_shift_kernel, axis=axis, world=world,
                               delta=delta)

    def body(xs):
        return pl.pallas_call(
            kernel,
            name="p2p_shift",
            out_shape=jax.ShapeDtypeStruct(xs.shape, xs.dtype),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.SemaphoreType.DMA,
                            pltpu.SemaphoreType.DMA],
            compiler_params=comm_params(collective_id=8, world=world),
            interpret=interpret,
        )(xs)

    out = nestable_shard_map(body, mesh=mesh, in_specs=P(axis),
                        out_specs=P(axis), check_vma=False)(x)
    return sync_interpret(out, interpret)
