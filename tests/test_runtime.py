"""Runtime tests (reference analog: test/nvidia/test_utils.py — but runnable
single-process, see conftest)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import triton_dist_tpu as tdt
from triton_dist_tpu.runtime import (
    assert_allclose, local_shard, perf_func, symm_tensor)


def test_initialize_default(devices):
    ctx = tdt.initialize_distributed()
    assert ctx.world_size == 8
    assert ctx.axis_names == ("tp",)
    assert ctx.axis_size("tp") == 8
    tdt.finalize_distributed()
    with pytest.raises(RuntimeError):
        tdt.get_context()


def test_initialize_2d(devices):
    ctx = tdt.initialize_distributed({"dp": 2, "tp": 4})
    assert ctx.axis_size("dp") == 2
    assert ctx.axis_size("tp") == 4
    assert tdt.get_mesh().shape["tp"] == 4
    tdt.finalize_distributed()


def test_initialize_bad_shape(devices):
    with pytest.raises(ValueError):
        tdt.initialize_distributed({"tp": 3})


def test_symm_tensor(mesh8):
    buf = symm_tensor((4, 128), jnp.float32, mesh8, axis="tp")
    assert buf.shape == (8, 4, 128)
    # one addressable shard of local shape per device
    shards = buf.addressable_shards
    assert len(shards) == 8
    assert shards[0].data.shape == (1, 4, 128)
    assert local_shard(buf, 3).shape == (4, 128)


def test_perf_func():
    f = jax.jit(lambda: jnp.ones((64, 64)) * 2)
    out, ms = perf_func(lambda: f(), iters=3, warmup_iters=1)
    assert ms > 0
    assert float(out[0, 0]) == 2.0


def test_assert_allclose():
    a = np.ones((4, 4))
    assert_allclose(a, a + 1e-4)
    with pytest.raises(AssertionError):
        assert_allclose(a, a + 1.0)
    with pytest.raises(AssertionError):
        assert_allclose(a, np.ones((2, 2)))


def test_perf_func_chained_measures_real_work():
    """The chained windows return a positive per-step ms and the chain
    actually advances (step applied n2 times)."""
    from triton_dist_tpu.runtime.utils import perf_func_chained
    calls = [0]

    @jax.jit
    def step(x):
        return x * 1.0000001

    def counted(x):
        calls[0] += 1
        return step(x)

    ms = perf_func_chained(counted, jnp.ones((8, 8)), iters=(2, 6))
    assert ms > 0
    assert calls[0] >= 7   # warmup + n2 chain


class TestTopology:
    def test_describe_topology_mocked_coords(self):
        from triton_dist_tpu.runtime.topology import describe_topology

        class FakeDev:
            def __init__(self, coords, proc):
                self.platform = "tpu"
                self.device_kind = "TPU v5 lite"
                self.coords = coords
                self.process_index = proc

        devs = [FakeDev((x, y, 0), x // 2) for x in range(4)
                for y in range(2)]
        info = describe_topology(devs)
        assert info["n_devices"] == 8
        assert info["torus_extent"] == (4, 2, 1)
        assert info["coords_contiguous"] is True
        assert info["n_hosts"] == 2

    def test_describe_topology_cpu_no_coords(self):
        from triton_dist_tpu.runtime.topology import describe_topology
        info = describe_topology()
        assert info["platform"] == "cpu"
        assert "torus_extent" not in info

    def test_grid_cpu_falls_back_to_reshape(self):
        import numpy as np
        from triton_dist_tpu.runtime.topology import topology_aware_grid
        devs = np.array(jax.devices())
        grid = topology_aware_grid(devs, (2, 4))
        assert grid.shape == (2, 4)
        assert list(grid.ravel()) == list(devs)   # order preserved

    def test_grid_tpu_routes_through_mesh_utils(self, monkeypatch):
        """TPU device grids must go through mesh_utils (torus-aware
        placement); a mesh_utils failure must fall back, not raise."""
        import numpy as np
        from triton_dist_tpu.runtime import topology
        from jax.experimental import mesh_utils

        calls = []

        def spy(shape, devices=None):
            calls.append(shape)
            return np.array(devices).reshape(shape)

        monkeypatch.setattr(mesh_utils, "create_device_mesh", spy)

        class FakeTpu:
            platform = "tpu"

        # len must match jax.devices() for the TPU path to engage
        devs = np.array([FakeTpu() for _ in jax.devices()])
        grid = topology.topology_aware_grid(devs, (len(devs),))
        # a 1-D request is asked as (1, n): that is what makes
        # mesh_utils lay the devices out as a ring of neighbours
        assert calls == [(1, len(devs))]
        assert grid.shape == (len(devs),)
        topology.topology_aware_grid(devs, (2, len(devs) // 2))
        assert calls[-1] == (2, len(devs) // 2)

        def boom(shape, devices=None):
            raise RuntimeError("no topology info")

        monkeypatch.setattr(mesh_utils, "create_device_mesh", boom)
        grid = topology.topology_aware_grid(devs, (len(devs),))
        assert grid.shape == (len(devs),)   # reshape fallback
