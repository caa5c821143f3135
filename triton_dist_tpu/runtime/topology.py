"""ICI/DCN topology description (reference topology probes,
utils.py:823-967: NVLink fullmesh / NUMA / multicast detection — on TPU
the questions become torus extents, hosts, and chip generation).

The actionable consumer is mesh construction:
``initialize_distributed`` routes TPU device grids through
``jax.experimental.mesh_utils.create_device_mesh`` so the logical mesh
axes are laid onto physical ICI neighbors (a naive ``reshape`` can put
a TP ring across the torus diagonal, turning every hop into multiple
physical links). This module surfaces what that decision sees.
"""
from __future__ import annotations

import numpy as np

import jax


def describe_topology(devices=None) -> dict:
    """Best-effort physical-topology summary of ``devices``.

    Returns keys: ``n_devices``, ``platform``, ``device_kind``,
    ``n_hosts``, and — when per-device coordinates are exposed (real
    TPU backends) — ``torus_extent`` (inclusive extent per coordinate
    axis) and ``coords_contiguous`` (whether the slice fills its
    bounding box, i.e. no holes from a twisted/partial slice).
    """
    devices = list(devices if devices is not None else jax.devices())
    if not devices:
        return {"n_devices": 0, "platform": "?", "device_kind": "?",
                "n_hosts": 0}
    d0 = devices[0]
    out = {
        "n_devices": len(devices),
        "platform": getattr(d0, "platform", "?"),
        "device_kind": getattr(d0, "device_kind", "?"),
        "n_hosts": len({getattr(d, "process_index", 0) for d in devices}),
    }
    coords = [getattr(d, "coords", None) for d in devices]
    if coords and all(c is not None for c in coords):
        arr = np.asarray(coords)
        extent = arr.max(axis=0) - arr.min(axis=0) + 1
        out["torus_extent"] = tuple(int(x) for x in extent)
        out["coords_contiguous"] = bool(
            int(np.prod(extent)) == len({tuple(c) for c in coords}))
    return out


def topology_aware_grid(devices: np.ndarray, shape) -> np.ndarray:
    """Arrange ``devices`` into ``shape`` honoring physical topology.

    TPU grids go through ``mesh_utils.create_device_mesh`` (torus-aware
    axis assignment); anything else — CPU simulation meshes, explicit
    device subsets, or a mesh_utils failure — falls back to the plain
    ``reshape`` (order-preserving, what the tests' 8-virtual-device
    meshes assume).
    """
    flat = np.asarray(devices).ravel()
    shape = tuple(shape)
    if (getattr(flat[0], "platform", "?") == "tpu"
            and flat.size == len(jax.devices()) and flat.size > 1):
        try:
            from jax.experimental import mesh_utils
            # A 1-D request comes back in enumeration order (on a 2x2,
            # 0-1-2-3 puts a diagonal between ring neighbours); asked
            # for a leading unit axis, mesh_utils lays the same devices
            # out as a ring of physical neighbours (0-1-3-2).
            ask = (1,) + shape if len(shape) == 1 else shape
            return np.asarray(mesh_utils.create_device_mesh(
                ask, devices=list(flat))).reshape(shape)
        except Exception as e:  # noqa: BLE001 — layout is an optimization
            import warnings
            warnings.warn(
                "mesh_utils.create_device_mesh failed "
                f"({type(e).__name__}: {e}); falling back to a naive "
                "device reshape — TP rings may span the torus diagonal "
                "(multiple physical ICI links per hop)", stacklevel=2)
    return np.asarray(devices).reshape(shape)
