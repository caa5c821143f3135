"""Device-side distributed primitives for Pallas TPU kernels.

TPU-native analog of the reference's device language layer (L3):
``triton_dist.language`` builtins ``wait / consume_token / rank / num_ranks /
symm_at / notify`` (python/triton_dist/language/distributed_ops.py:56-111)
and the ``libshmem_device`` stub API
(python/triton_dist/language/extra/libshmem_device.py).

Mapping (SURVEY.md §5 "Distributed communication backend"):

=====================  =========================================
reference primitive    TPU-native primitive
=====================  =========================================
symmetric heap ptr     peer shard of a mesh-sharded array,
                       addressed by ``device_id`` on a remote DMA
``putmem(_signal)``    ``pltpu.make_async_remote_copy`` (the recv
                       semaphore *is* the signal)
``dl.notify``          ``pltpu.semaphore_signal(device_id=peer)``
``dl.wait``            ``pltpu.semaphore_wait``
``dl.consume_token``   data dependence (Pallas orders by SSA use;
                       provided as an identity for API parity)
``barrier_all``        all-peer signal + wait on the global
                       barrier semaphore
teams / scopes         mesh axis names ("tp", "ep", ...)
=====================  =========================================

Import convention mirrors the reference::

    import triton_dist_tpu.language as dl
    ...
    dl.wait(sem, 1)
"""

from __future__ import annotations

import jax
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# Identity / topology (reference distributed_ops.py:70-83 rank/num_ranks)
# ---------------------------------------------------------------------------

def rank(axis: str = "tp") -> jax.Array:
    """This device's index along ``axis`` (reference ``dl.rank``)."""
    return lax.axis_index(axis)


def num_ranks(axis: str = "tp") -> jax.Array:
    """World size along ``axis`` (reference ``dl.num_ranks``)."""
    return lax.axis_size(axis)


def _current_mesh_axes() -> tuple[str, ...] | None:
    """Axis names of the mesh enclosing the current trace (shard_map body),
    in mesh order. Lets primitives compute global logical device ids without
    the caller having to plumb mesh_axes through."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
        names = tuple(mesh.axis_names)
        return names if names else None
    except Exception:
        return None


def logical_device_id(peer: jax.Array, axis: str,
                      mesh_axes: tuple[str, ...] | None = None):
    """Flattened logical device id of the device at ``peer`` on ``axis``,
    keeping this device's coordinates on every other mesh axis.

    For a 1-D mesh this is just ``peer``. For multi-axis meshes, remote DMA
    ``device_id`` must be the *logical* id over the full mesh
    (``pltpu.DeviceIdType.LOGICAL``); this computes it from mesh coordinates
    — the analog of NVSHMEM team-relative→global PE translation
    (``nvshmem_team_translate_pe``). ``mesh_axes`` defaults to the axes of
    the mesh enclosing the current trace.
    """
    if mesh_axes is None:
        mesh_axes = _current_mesh_axes()
    if mesh_axes is None or tuple(mesh_axes) == (axis,):
        return peer
    did = 0
    for name in mesh_axes:
        idx = peer if name == axis else lax.axis_index(name)
        did = did * lax.axis_size(name) + idx
    return did


# ---------------------------------------------------------------------------
# Signal / wait (reference distributed_ops.py:56-68 wait, :95-111 notify;
# lowering DistributedOpToLLVM.cpp:187-342)
# ---------------------------------------------------------------------------

def wait(sem, value: int | jax.Array = 1) -> None:
    """Block until ``sem`` has accumulated ``value`` signals, consuming them.

    Analog of ``dl.wait(barrier_ptr, n, scope, "acquire")`` — the PTX spin
    loop (DistributedOpToLLVM.cpp:187-206) becomes a hardware semaphore
    wait; acquire ordering is implied by the TPU DMA/semaphore model.
    """
    pltpu.semaphore_wait(sem, value)


def notify(sem, peer=None, inc: int = 1, axis: str | None = None,
           mesh_axes: tuple[str, ...] | None = None) -> None:
    """Signal ``sem`` (optionally on a remote device) — analog of
    ``dl.notify(ptr, rank, signal="add", comm_scope=...)``
    (distributed_ops.py:95-111).

    ``peer``: target rank along ``axis`` (None = local). CommScope GPU vs
    INTRA_NODE vs INTER_NODE collapses on TPU: ICI remote signal is one
    mechanism.
    """
    if peer is None:
        pltpu.semaphore_signal(sem, inc=inc)
    else:
        did = logical_device_id(peer, axis, mesh_axes) if axis else peer
        pltpu.semaphore_signal(
            sem, inc=inc, device_id=did,
            device_id_type=pltpu.DeviceIdType.LOGICAL)


# Signaling without ``axis`` treats ``peer`` as an already-global logical id;
# pass ``axis=`` whenever the peer index is axis-relative.


def consume_token(value, token=None):
    """API-parity identity (reference ``dl.consume_token``,
    distributed_ops.py:85-93; lowering is identity too,
    DistributedOpToLLVM.cpp:228). Pallas orders memory ops by data/effect
    dependence, so no token plumbing is needed."""
    del token
    return value


def semaphore_read(sem) -> jax.Array:
    """Non-blocking semaphore read (debug; reference has no direct analog —
    closest is reading the uint64 flag with ``ld.acquire``)."""
    return pltpu.semaphore_read(sem)


# ---------------------------------------------------------------------------
# One-sided data movement (reference libshmem_device putmem family)
# ---------------------------------------------------------------------------

def remote_copy(src_ref, dst_ref, peer, send_sem, recv_sem,
                axis: str | None = None,
                mesh_axes: tuple[str, ...] | None = None):
    """Build (don't start) an async remote copy ``src_ref → dst_ref@peer``.

    The analog of ``libshmem_device.putmem_nbi_block`` + signal: on TPU the
    receiver's ``recv_sem`` is signalled by the transport on delivery, which
    subsumes ``putmem_signal`` (libshmem_device.py:139-219). Returns the
    descriptor: call ``.start()`` / ``.wait()`` / ``.wait_send()`` /
    ``.wait_recv()``.
    """
    did = logical_device_id(peer, axis, mesh_axes) if axis else peer
    return pltpu.make_async_remote_copy(
        src_ref=src_ref, dst_ref=dst_ref,
        send_sem=send_sem, recv_sem=recv_sem,
        device_id=did, device_id_type=pltpu.DeviceIdType.LOGICAL)


def local_copy(src_ref, dst_ref, sem):
    """Async same-chip DMA (HBM↔VMEM) — the analog of the reference's
    cudaMemcpyAsync copy-engine path (allgather.py:158-230)."""
    return pltpu.make_async_copy(src_ref, dst_ref, sem)


# ---------------------------------------------------------------------------
# Barriers (reference barrier_all_intra_node_* common_ops.py:57-392,
# nvshmem_barrier_all_on_stream utils.py:162)
# ---------------------------------------------------------------------------

def barrier_all(axis: str = "tp",
                mesh_axes: tuple[str, ...] | None = None) -> None:
    """Full barrier across ``axis`` from inside a kernel.

    Signals every peer on the global barrier semaphore and waits for
    world-many signals (including self, keeping the count uniform).
    Requires ``collective_id`` in ``pltpu.CompilerParams``. Analog of
    ``barrier_all_intra_node_atomic_cas_block`` (common_ops.py).
    """
    sem = pltpu.get_barrier_semaphore()
    world = lax.axis_size(axis)

    def signal_one(i, _):
        did = logical_device_id(i, axis, mesh_axes)
        pltpu.semaphore_signal(
            sem, inc=1, device_id=did,
            device_id_type=pltpu.DeviceIdType.LOGICAL)
        return _

    lax.fori_loop(0, world, signal_one, None)
    pltpu.semaphore_wait(sem, world)


def barrier_neighbors(axis: str = "tp",
                      mesh_axes: tuple[str, ...] | None = None) -> None:
    """Ring-neighbor barrier (cheaper than ``barrier_all``): sync with the
    left and right neighbors only — sufficient between ring steps."""
    sem = pltpu.get_barrier_semaphore()
    world = lax.axis_size(axis)
    me = lax.axis_index(axis)
    left = lax.rem(me - 1 + world, world)
    right = lax.rem(me + 1, world)
    for peer in (left, right):
        pltpu.semaphore_signal(
            sem, inc=1,
            device_id=logical_device_id(peer, axis, mesh_axes),
            device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(sem, 2)


# Re-exports so kernels can use one namespace.
ds = pl.ds
when = pl.when
program_id = pl.program_id
num_programs = pl.num_programs
