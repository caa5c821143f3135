"""Tutorial 01: device-side distributed primitives.

Analog of the reference's tutorials/01 (notify/wait/symm-at basics): a toy
Pallas kernel where each device pushes a value to its right neighbor with
a remote DMA and waits for the incoming one — the put+signal / wait
pattern every fused kernel builds on.

Run (no TPU needed — CPU simulation):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/01_primitives.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# 8-device CPU simulation by default (forced in-config, whatever the
# machine holds); set TDT_EXAMPLES_ON_TPU=1 to run on real devices instead.
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
import jax

if not os.environ.get("TDT_EXAMPLES_ON_TPU"):
    jax.config.update("jax_platforms", "cpu")

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

import triton_dist_tpu.language as dl
from triton_dist_tpu.ops.common import comm_params, resolve_interpret


def ring_pass_kernel(x_ref, o_ref, send_sem, recv_sem, *, axis, world):
    me = lax.axis_index(axis)
    right = lax.rem(me + 1, world)
    dl.barrier_all(axis)                       # peers' buffers exist
    copy = dl.remote_copy(x_ref.at[:], o_ref.at[:], right, send_sem,
                          recv_sem, axis=axis)
    copy.start()                               # put to right neighbor
    # wait for the put arriving from the LEFT neighbor (mirror descriptor)
    dl.remote_copy(x_ref.at[:], o_ref.at[:], me, send_sem, recv_sem,
                   axis=axis).wait_recv()
    copy.wait_send()


def main():
    devs = jax.devices()
    world = len(devs)
    mesh = Mesh(np.array(devs), ("x",))
    kernel = functools.partial(ring_pass_kernel, axis="x", world=world)

    def body(xs):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(xs.shape, xs.dtype),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.SemaphoreType.DMA,
                            pltpu.SemaphoreType.DMA],
            compiler_params=comm_params(collective_id=0, world=world),
            interpret=resolve_interpret(None),
        )(xs)

    x = jnp.arange(world * 8, dtype=jnp.float32).reshape(world, 8)
    out = jax.shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                        check_vma=False)(x)
    print("input rows :", x[:, 0])
    print("output rows:", out[:, 0], "(each row shifted from the left)")
    assert np.allclose(np.asarray(out), np.roll(np.asarray(x), 1, axis=0))
    print("OK")


if __name__ == "__main__":
    main()
