"""AllReduce variants over the ICI mesh.

TPU-native redesign of the reference's standalone AllReduce
(python/triton_dist/kernels/nvidia/allreduce.py: 6 device algorithms
:214-683, auto method-by-size :1101, dispatcher ``all_reduce`` :1129,
straggler injection ``_run_straggler`` :137).

Method mapping (reference → TPU):

- one-shot push / one-shot TMA   → ``ONE_SHOT``: every device pushes its
  full buffer to all peers' staging slots; each reduces locally. One hop,
  latency-optimal.
- two-shot push                  → ``TWO_SHOT``: ring reduce-scatter then
  ring all-gather inside one kernel; bandwidth-optimal.
- double-tree                    → ``RECURSIVE_DOUBLING``: log-depth
  XOR-partner exchange (the same latency class; tree topologies
  themselves don't map to ICI neighbor links).
- one/two-shot multimem (NVLS)   → no ICI multicast exists; the XLA
  ``psum`` path is the hardware-tuned equivalent. Documented gap.

Straggler injection (reference allreduce.py:137) is supported via
``straggler_option=(rank, cycles)`` — that rank spins ``pl.delay`` before
communicating, to expose missing waits under stress tests.
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
    ring_padded_rows,
    sync_interpret)


class AllReduceMethod(enum.Enum):
    AUTO = "auto"
    ONE_SHOT = "one_shot"
    TWO_SHOT = "two_shot"
    # Log-depth exchange (the latency class of the reference's
    # double-tree, allreduce.py:214-683 double-tree rows): requires a
    # power-of-two world.
    RECURSIVE_DOUBLING = "recursive_doubling"


def get_auto_allreduce_method(world_size: int, nbytes: int,
                              spec=None) -> AllReduceMethod:
    """Perf-model-driven selection (reference allreduce.py:1101-1127
    picks from measured bandwidth models): one-shot's single full-buffer
    exchange wins at small payloads; the two-shot RS+AG decomposition
    moves 2·nbytes/w per link instead of (w-1)·nbytes and wins once
    bandwidth-bound."""
    from triton_dist_tpu.tools.perf_model import estimate_all_reduce_time_ms
    if world_size <= 2:
        return AllReduceMethod.ONE_SHOT
    t_one = estimate_all_reduce_time_ms(nbytes, world_size, spec,
                                        method="one_shot")
    t_two = estimate_all_reduce_time_ms(nbytes, world_size, spec,
                                        method="two_shot")
    return (AllReduceMethod.ONE_SHOT if t_one <= t_two
            else AllReduceMethod.TWO_SHOT)


@dataclasses.dataclass
class AllReduceContext:
    mesh: Mesh
    axis: str = "tp"
    method: AllReduceMethod = AllReduceMethod.AUTO
    interpret: bool | None = None
    # (rank, delay_cycles) — that rank delays before communicating
    # (reference straggler_option / _run_straggler, allreduce.py:137).
    straggler_option: tuple[int, int] | None = None

    @property
    def world_size(self) -> int:
        return self.mesh.shape[self.axis]


def create_allreduce_context(mesh: Mesh | None = None, axis: str = "tp",
                             method: AllReduceMethod = AllReduceMethod.AUTO,
                             interpret: bool | None = None,
                             straggler_option=None) -> AllReduceContext:
    if mesh is None:
        from triton_dist_tpu.runtime.dist import get_mesh
        mesh = get_mesh()
    return AllReduceContext(mesh=mesh, axis=axis, method=method,
                            interpret=interpret,
                            straggler_option=straggler_option)


def _maybe_straggle(straggler_option, axis):
    if straggler_option is None:
        return
    rank, cycles = straggler_option

    @pl.when(lax.axis_index(axis) == rank)
    def _():
        pl.delay(cycles)


def _one_shot_ar_kernel(x_ref, o_ref, stage_ref, send_sem, recv_sem, *,
                        axis: str, world: int, straggler_option=None):
    """Push my full buffer to every peer's stage[me]; sum all stages
    (reference one-shot push kernel, allreduce.py:214-300)."""
    me = lax.axis_index(axis)
    stage_ref[me] = x_ref[:]
    if world == 1:
        o_ref[:] = x_ref[:]
        return
    _maybe_straggle(straggler_option, axis)
    dl.barrier_all(axis)

    def send(p, _):
        peer = lax.rem(me + p, world)
        dl.remote_copy(x_ref, stage_ref.at[me], peer,
                       send_sem.at[peer], recv_sem.at[me], axis=axis).start()
        return _

    lax.fori_loop(1, world, send, None)

    def wait_recv(p, _):
        src = lax.rem(me - p + world, world)
        dl.remote_copy(x_ref, stage_ref.at[src], me,
                       send_sem.at[src], recv_sem.at[src],
                       axis=axis).wait_recv()
        return _

    lax.fori_loop(1, world, wait_recv, None)

    acc = stage_ref[0]
    for p in range(1, world):
        acc = acc + stage_ref[p]
    o_ref[:] = acc

    def wait_send(p, _):
        peer = lax.rem(me + p, world)
        dl.remote_copy(x_ref, stage_ref.at[me], peer,
                       send_sem.at[peer], recv_sem.at[me],
                       axis=axis).wait_send()
        return _

    lax.fori_loop(1, world, wait_send, None)


def _recursive_doubling_ar_kernel(x_ref, o_ref, send_stage, recv_stage,
                                  send_sem, recv_sem, *, axis: str,
                                  world: int, straggler_option=None):
    """Log-depth allreduce: step j exchanges the running partial with
    partner ``me XOR 2^j`` and adds — log2(w) hops of the full buffer.

    The TPU answer to the reference's double-tree kernels (log-latency
    class, allreduce.py:214-683): on a torus the XOR partner at step j is
    2^j links away, so total traffic matches one-shot but the incast is
    pairwise (2 flows/link) instead of (w-1)-way. The exchange is
    symmetric: both partners use step-slot j, so one descriptor serves
    start (my push), wait_recv (partner's delivery into my stage) and
    wait_send (my push drained)."""
    me = lax.axis_index(axis)
    o_ref[:] = x_ref[:]
    if world == 1:
        return
    n_steps = world.bit_length() - 1
    _maybe_straggle(straggler_option, axis)
    dl.barrier_all(axis)

    cps = []
    for j in range(n_steps):                 # static log2(w) unroll
        partner = jnp.bitwise_xor(me, 1 << j)
        send_stage[j] = o_ref[:]
        cp = dl.remote_copy(send_stage.at[j], recv_stage.at[j], partner,
                            send_sem.at[j], recv_sem.at[j], axis=axis)
        cp.start()
        cp.wait_recv()                       # partner's partial landed
        o_ref[:] = o_ref[:] + recv_stage[j]
        cps.append(cp)
    for cp in cps:
        cp.wait_send()


def _two_shot_ar_kernel(x_ref, o_ref, send_buf, recv_buf, send_sem, recv_sem,
                        ag_send_sem, ag_recv_sem, *, axis: str, world: int,
                        rows: int, straggler_option=None):
    """Ring reduce-scatter + ring all-gather in one kernel (reference
    two-shot push, allreduce.py:301-430). Bandwidth-optimal: each element
    crosses each link twice. Per-step buffers/semaphores — see
    _ring_rs_kernel for why reuse races."""
    me = lax.axis_index(axis)
    right = lax.rem(me + 1, world)

    if world == 1:
        o_ref[:] = x_ref[:]
        return
    _maybe_straggle(straggler_option, axis)
    dl.barrier_all(axis)

    # Phase 1: ring reduce-scatter of my (M, N) into my chunk [me].
    def rs_copy(s):
        return dl.remote_copy(send_buf.at[s], recv_buf.at[s], right,
                              send_sem.at[s], recv_sem.at[s], axis=axis)

    def rs_step(s, _):
        send_idx = lax.rem(me - s - 1 + world, world)

        @pl.when(s == 0)
        def _():
            send_buf[s] = x_ref[pl.ds(send_idx * rows, rows), :]

        @pl.when(s > 0)
        def _():
            send_buf[s] = (recv_buf[jnp.maximum(s - 1, 0)] +
                           x_ref[pl.ds(send_idx * rows, rows), :])

        rs_copy(s).start()
        rs_copy(s).wait_recv()
        return _

    lax.fori_loop(0, world - 1, rs_step, None)
    o_ref[pl.ds(me * rows, rows), :] = (recv_buf[world - 2] +
                                        x_ref[pl.ds(me * rows, rows), :])

    # Phase 2: ring all-gather of the reduced chunks (per-chunk semaphores;
    # o_ref chunk slots are naturally distinct so no staging needed).
    def ag_copy(idx):
        return dl.remote_copy(
            o_ref.at[pl.ds(idx * rows, rows), :],
            o_ref.at[pl.ds(idx * rows, rows), :],
            right, ag_send_sem.at[idx], ag_recv_sem.at[idx], axis=axis)

    def ag_step(s, _):
        ag_copy(lax.rem(me - s + world, world)).start()
        ag_copy(lax.rem(me - s - 1 + world, world)).wait_recv()
        return _

    lax.fori_loop(0, world - 1, ag_step, None)

    def drain(s, _):
        rs_copy(s).wait_send()
        ag_copy(lax.rem(me - s + world, world)).wait_send()
        return _

    lax.fori_loop(0, world - 1, drain, None)


@resilient("allreduce")
def all_reduce(x: jax.Array, ctx: AllReduceContext | None = None,
               impl: str = "pallas", stacked: bool = False) -> jax.Array:
    """Sum per-device partials; every device receives the total.

    Input: (w, M, N) sharded on dim 0 (one partial per device). Output:
    (M, N) replicated — or (w, M, N) stacked copies with ``stacked=True``.
    Dispatcher analog of reference ``all_reduce`` (allreduce.py:1129).
    """
    ctx = ctx or create_allreduce_context()
    mesh, axis, world = ctx.mesh, ctx.axis, ctx.world_size
    record_comm("allreduce", x)
    assert x.shape[0] == world, (x.shape, world)
    m, n = x.shape[1], x.shape[2]
    method = ctx.method
    if method is AllReduceMethod.AUTO:
        method = get_auto_allreduce_method(world, m * n * x.dtype.itemsize)
    # TWO_SHOT slices the buffer into ``world`` row chunks; an M that
    # does not split into whole row tiles is zero-padded for the kernel
    # and sliced back (the method asked for is the method that runs).
    m_pad = (ring_padded_rows(m, world)
             if method is AllReduceMethod.TWO_SHOT else m)
    if (method is AllReduceMethod.RECURSIVE_DOUBLING
            and world & (world - 1)):
        method = AllReduceMethod.ONE_SHOT    # needs power-of-two world

    out_spec = P(axis) if stacked else P()

    if impl == "xla":
        def body(xs):
            r = lax.psum(xs[0], axis)
            return r[None] if stacked else r
        f = nestable_shard_map(body, mesh=mesh, in_specs=P(axis),
                          out_specs=out_spec, check_vma=False)
        return f(x)

    interpret = resolve_interpret(ctx.interpret)

    if method is AllReduceMethod.ONE_SHOT:
        kernel = functools.partial(_one_shot_ar_kernel, axis=axis,
                                   world=world,
                                   straggler_option=ctx.straggler_option)
        scratch = [pltpu.VMEM((world, m, n), x.dtype),
                   pltpu.SemaphoreType.DMA((world,)),
                   pltpu.SemaphoreType.DMA((world,))]
    elif method is AllReduceMethod.RECURSIVE_DOUBLING:
        n_steps = max(world.bit_length() - 1, 1)
        kernel = functools.partial(
            _recursive_doubling_ar_kernel, axis=axis, world=world,
            straggler_option=ctx.straggler_option)
        scratch = [pltpu.VMEM((n_steps, m, n), x.dtype),
                   pltpu.VMEM((n_steps, m, n), x.dtype),
                   pltpu.SemaphoreType.DMA((n_steps,)),
                   pltpu.SemaphoreType.DMA((n_steps,))]
    else:
        rows = m_pad // world
        kernel = functools.partial(_two_shot_ar_kernel, axis=axis,
                                   world=world, rows=rows,
                                   straggler_option=ctx.straggler_option)
        scratch = [pltpu.VMEM((world - 1, rows, n), x.dtype),
                   pltpu.VMEM((world - 1, rows, n), x.dtype),
                   pltpu.SemaphoreType.DMA((world - 1,)),
                   pltpu.SemaphoreType.DMA((world - 1,)),
                   pltpu.SemaphoreType.DMA((world,)),
                   pltpu.SemaphoreType.DMA((world,))]

    def body(xs):
        part = xs[0]
        if m_pad != m:
            part = jnp.pad(part, ((0, m_pad - m), (0, 0)))
        r = pl.pallas_call(
            kernel,
            name=f"all_reduce_{method.value}",
            out_shape=jax.ShapeDtypeStruct((m_pad, n), x.dtype),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=scratch,
            compiler_params=comm_params(collective_id=3, world=world),
            interpret=interpret,
        )(part)[:m]
        return r[None] if stacked else r

    f = nestable_shard_map(body, mesh=mesh, in_specs=P(axis),
                      out_specs=out_spec, check_vma=False)
    return sync_interpret(f(x), interpret)
