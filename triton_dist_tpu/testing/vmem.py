"""Static VMEM-footprint assertion for Pallas kernels.

Mosaic's default scoped-VMEM cap is 16 MB: a default GEMM config that
declared 16.5 MB of scratch was refused by the chip's compiler, and
nothing between the config table and the compiler had checked the
budget. This helper makes such config bugs fail in CI instead of on the
chip. The reference has no analog (its
configs are validated by running on the GPU); on TPU the budget is
statically computable from the ``pallas_call`` signature.

Usage::

    with assert_vmem_within():          # HARD_FOOTPRINT_CAP default
        jax.eval_shape(entry, *bench_shaped_args)

Every ``pl.pallas_call`` traced inside the context has its VMEM-resident
bytes summed — whole-array VMEM operands/outputs (the library's kernels
use whole-array specs or ``pl.ANY``) plus VMEM scratch buffers — and a
``VmemBudgetError`` is raised when a kernel exceeds the limit.
``jax.eval_shape`` makes the check trace-only: bench-shaped kernels are
checked in milliseconds on any host, no TPU (and no interpret-mode
execution) required.

The bound is approximate in the compiler's favor: Mosaic additionally
allocates stack for live intermediates, so a kernel passing this check
can still OOM — but a kernel failing it is guaranteed dead on hardware.
"""

from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Ceiling on the DECLARED footprint that still compiles: the library's
# comm kernels request a 64 MB Mosaic scoped-VMEM limit (a v5e core has
# 128 MB physical VMEM) and Mosaic's scoped accounting carries ~2.2x of
# window/staging overhead over the declared buffers (measured round-5:
# 16.14 MB scoped for ~7.4 MB declared) — see the constants in
# ops/common.py.
from triton_dist_tpu.ops.common import HARD_FOOTPRINT_CAP

__all__ = ["DECLARED_FOOTPRINT_CAP", "HARD_FOOTPRINT_CAP",
           "VmemBudgetError", "assert_vmem_within", "check_entry_vmem"]

#: This module's name for the 26 MB declared-footprint cap. The old
#: alias ``VMEM_LIMIT_BYTES`` collided with ``ops.common``'s UNRELATED
#: 64 MB Mosaic scoped limit of the same name (2.5x apart — ADVICE r5);
#: it survives only as a deprecation shim below.
DECLARED_FOOTPRINT_CAP = HARD_FOOTPRINT_CAP


def __getattr__(name):
    if name == "VMEM_LIMIT_BYTES":
        import warnings
        warnings.warn(
            "triton_dist_tpu.testing.vmem.VMEM_LIMIT_BYTES is "
            "deprecated: it is the 26 MB DECLARED-footprint cap, NOT "
            "ops.common.VMEM_LIMIT_BYTES (the 64 MB Mosaic scoped "
            "limit). Use testing.vmem.DECLARED_FOOTPRINT_CAP (or "
            "HARD_FOOTPRINT_CAP) for the former, ops.common."
            "VMEM_LIMIT_BYTES for the latter.",
            DeprecationWarning, stacklevel=2)
        return DECLARED_FOOTPRINT_CAP
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")


class VmemBudgetError(AssertionError):
    pass


def _nbytes(shape, dtype) -> int:
    return math.prod(shape) * jnp.dtype(dtype).itemsize


def _is_vmem_space(space) -> bool:
    # pltpu.VMEM (MemorySpace enum member) or an unset spec (Pallas
    # defaults unset memory space to VMEM on TPU).
    if space is None:
        return True
    return "VMEM" in str(space).upper() and "SMEM" not in str(space).upper()


def _spec_bytes(spec, shape_struct) -> int:
    """VMEM bytes one operand/output contributes: its block if blocked,
    the whole array otherwise; 0 for ANY/SMEM/semaphore spaces."""
    space = getattr(spec, "memory_space", None) if spec is not None else None
    if space is not None and not _is_vmem_space(space):
        return 0
    block = getattr(spec, "block_shape", None) if spec is not None else None
    shape = tuple(block) if block is not None else tuple(shape_struct.shape)
    return _nbytes(shape, shape_struct.dtype)


def _scratch_bytes(scratch) -> int:
    """VMEM bytes of one scratch entry (semaphores cost no VMEM)."""
    shape = getattr(scratch, "shape", None)
    dtype = getattr(scratch, "dtype", None)
    if shape is None or dtype is None:
        return 0
    if "semaphore" in str(dtype).lower():
        return 0
    space = getattr(scratch, "memory_space", None)
    if space is not None and not _is_vmem_space(space):
        return 0
    try:
        return _nbytes(tuple(shape), dtype)
    except TypeError:
        return 0


@contextlib.contextmanager
def assert_vmem_within(limit: int = HARD_FOOTPRINT_CAP):
    """Patch ``pl.pallas_call`` so every kernel traced in the context has
    its static VMEM footprint checked against ``limit``."""
    orig = pl.pallas_call

    def checked(kernel, *call_args, **kw):
        inner = orig(kernel, *call_args, **kw)

        def run(*args):
            total = 0
            in_specs = kw.get("in_specs") or [None] * len(args)
            for spec, arg in zip(in_specs, args):
                total += _spec_bytes(spec, arg)
            out_shape = kw.get("out_shape")
            outs = (out_shape if isinstance(out_shape, (tuple, list))
                    else [out_shape])
            out_specs = kw.get("out_specs")
            if not isinstance(out_specs, (tuple, list)):
                out_specs = [out_specs] * len(outs)
            for spec, o in zip(out_specs, outs):
                total += _spec_bytes(spec, o)
            for s in kw.get("scratch_shapes") or ():
                total += _scratch_bytes(s)
            if total > limit:
                raise VmemBudgetError(
                    f"pallas_call static VMEM footprint {total / 2**20:.2f}"
                    f" MB exceeds {limit / 2**20:.2f} MB "
                    f"(kernel={getattr(kernel, 'func', kernel)})")
            return inner(*args)
        return run

    pl.pallas_call = checked
    try:
        yield
    finally:
        pl.pallas_call = orig


def check_entry_vmem(fn, *args, limit: int = HARD_FOOTPRINT_CAP):
    """Trace ``fn(*args)`` shape-only with the budget check active.

    ``args`` may be ``jax.ShapeDtypeStruct``s — nothing executes, so
    bench-shaped configs are validated on any host in milliseconds."""
    with assert_vmem_within(limit):
        return jax.eval_shape(fn, *args)
