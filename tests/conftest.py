"""Test configuration: a forced 8-device CPU mesh.

The reference can only test distributed code with real multi-GPU torchrun
(SURVEY.md §4). On TPU/JAX we get a single-process multi-device simulation:
8 virtual CPU devices + Pallas TPU interpret mode (which simulates remote
DMAs and semaphores), so the whole distributed test suite runs on any
machine.
"""

import os

# NOTE: on 1-core hosts the run is re-exec'd with the CPU-affinity shim by
# triton_dist_tpu.testing.shim_plugin (loaded via addopts) before capture
# starts — see runtime/cpu_shim.py for why.

# Must be set before the CPU backend is initialized.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# Tests run on the virtual CPU mesh whatever the machine holds; what runs
# on a real chip is chip_smoke.py.
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture()
def mesh8(devices):
    """1-D tp=8 mesh (the reference's default TP group of all ranks)."""
    from jax.sharding import Mesh
    return Mesh(np.array(devices), ("tp",))


@pytest.fixture()
def mesh4x2(devices):
    from jax.sharding import Mesh
    return Mesh(np.array(devices).reshape(4, 2), ("tp", "ep"))


@pytest.fixture()
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _isolate_autotune_cache(monkeypatch):
    """Tests must not read or write the developer's persistent autotune
    cache (TDT_AUTOTUNE_CACHE); the disk-cache tests opt back in with
    their own tmp_path setenv."""
    monkeypatch.delenv("TDT_AUTOTUNE_CACHE", raising=False)


@pytest.fixture(autouse=True)
def _isolate_trace(monkeypatch, tmp_path):
    """Tracing state is process-global like the metrics registry:
    every test starts and ends with the tracer disabled and the flight
    recorder's dump history cleared, and dumps land in a per-test temp
    dir (never the developer's /tmp/tdt_trace)."""
    monkeypatch.delenv("TDT_TRACE", raising=False)
    monkeypatch.delenv("TDT_FLIGHT_SECONDS", raising=False)
    monkeypatch.setenv("TDT_TRACE_DIR", str(tmp_path / "traces"))
    # Device-profile captures isolate the same way: per-test artifact
    # dir, sampler knobs cleared, armed/last-profile state reset.
    monkeypatch.delenv("TDT_DEVPROF_EVERY", raising=False)
    monkeypatch.delenv("TDT_DEVPROF_ON_BREACH", raising=False)
    monkeypatch.setenv("TDT_DEVPROF_DIR", str(tmp_path / "devprof"))
    # The history sampler reads its knobs at scheduler construction;
    # a developer's TDT_HISTORY* must not leak a sampler (or
    # detectors) into tests that assert the off-by-default contract.
    for k in ("TDT_HISTORY", "TDT_HISTORY_LEN", "TDT_HISTORY_TICK_S",
              "TDT_HISTORY_DUMP_S", "TDT_HISTORY_SLOPE",
              "TDT_HISTORY_STEP"):
        monkeypatch.delenv(k, raising=False)
    from triton_dist_tpu.obs import devprof, flight, trace
    trace.reset()
    flight.reset()
    devprof.reset()
    yield
    trace.reset()
    flight.reset()
    devprof.reset()


@pytest.fixture(autouse=True)
def _isolate_observatory():
    """The SLO observatory's process-local rings (request-attribution
    waterfalls) start empty for every test, so
    one test's requests cannot leak into another's
    ``request_stats``."""
    from triton_dist_tpu.obs import attrib
    attrib.reset()
    yield
    attrib.reset()


@pytest.fixture(autouse=True)
def _isolate_resilience(monkeypatch, tmp_path):
    """Point the resilience known-bad cache at a per-test temp file
    (never the developer's ~/.cache) and reset all process-local
    resilience state (breakers, compiled-key set, fault plan) around
    each test, so a breaker tripped in one test cannot silently route
    another test's fused path to XLA."""
    monkeypatch.setenv("TDT_KNOWN_BAD_CACHE",
                       str(tmp_path / "known_bad.json"))
    # Defense in depth: a module imported by one test (tpu_smoke.py
    # sets this for real runs) must not pin routing for every later test.
    monkeypatch.delenv("TDT_FORCE_FUSED", raising=False)
    from triton_dist_tpu import resilience
    from triton_dist_tpu.testing import faults
    resilience.reset_for_tests()
    faults.clear()
    yield
    resilience.reset_for_tests()
    faults.clear()
