"""Speed-of-light performance models for TPU compute and ICI collectives.

TPU-native redesign of the reference's perf models
(python/triton_dist/kernels/nvidia/gemm_perf_model.py:232
``estimate_gemm_sol_time_ms`` and comm_perf_model.py:94-116
``estimate_all_gather_time_ms`` / ``estimate_reduce_scatter_time_ms``
against probed NVLink/PCIe bandwidth). The reference budgets SMs between
GEMM and comm with these; on TPU the analog decision is whether overlap
is compute- or bandwidth-bound per shape (``overlap_efficiency``), which
drives method choice (e.g. ring vs one-shot, ops/allgather.py).

Chip tables are public-spec numbers; ``probe_*`` measure the live system
(the analog of the reference's topology probes utils.py:823-967).
"""

from __future__ import annotations

import dataclasses

import jax


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    bf16_tflops: float          # MXU peak, bf16
    hbm_gbps: float             # HBM bandwidth GB/s
    ici_gbps_per_link: float    # per-direction per-link ICI GB/s
    ici_links: int              # torus links per chip


# Public-spec table (order matters: first matching substring wins).
# "lite" keys first: real device_kind strings are e.g. "TPU v5 lite" /
# "TPU v6 lite", which no bare "v5e"/"v6e" substring matches.
CHIP_SPECS = {
    "v5 lite": ChipSpec("v5e", 197.0, 819.0, 50.0, 4),
    "v6 lite": ChipSpec("v6e", 918.0, 1640.0, 100.0, 4),
    "v6": ChipSpec("v6e", 918.0, 1640.0, 100.0, 4),
    "v5p": ChipSpec("v5p", 459.0, 2765.0, 100.0, 6),
    "v5e": ChipSpec("v5e", 197.0, 819.0, 50.0, 4),
    "v4": ChipSpec("v4", 275.0, 1228.0, 50.0, 6),
}

#: Stand-in spec for the CPU test meshes only — never a default for an
#: accelerator the table does not know.
CPU_SIM_SPEC = ChipSpec("cpu-sim", 1.0, 50.0, 10.0, 2)


def get_chip_spec(device=None) -> ChipSpec:
    """Identify the local chip (reference topology probes). A CPU
    device gets the simulator spec; an accelerator whose
    ``device_kind`` is not in :data:`CHIP_SPECS` is an error — a
    roofline against made-up peaks is worse than none."""
    if device is None:
        device = jax.devices()[0]
    if device.platform == "cpu":
        return CPU_SIM_SPEC
    kind = device.device_kind.lower()
    for key, spec in CHIP_SPECS.items():
        if key in kind:
            return spec
    raise ValueError(
        f"no chip spec for device_kind {device.device_kind!r} "
        f"(platform {device.platform!r}); add it to "
        f"tools/perf_model.CHIP_SPECS with its published peaks")


def estimate_gemm_sol_time_ms(m: int, n: int, k: int,
                              spec: ChipSpec | None = None,
                              dtype_bytes: int = 2) -> float:
    """max(FLOP-bound, HBM-bound) GEMM time (reference
    gemm_perf_model.py:232)."""
    spec = spec or get_chip_spec()
    flops = 2.0 * m * n * k
    t_flops = flops / (spec.bf16_tflops * 1e12)
    bytes_moved = dtype_bytes * (m * k + k * n + m * n)
    t_mem = bytes_moved / (spec.hbm_gbps * 1e9)
    return max(t_flops, t_mem) * 1e3


# Fixed costs per DMA/step (ICI hop launch + semaphore signalling). These
# are what make small payloads latency-bound and large ones
# bandwidth-bound — the axis every AUTO crossover below turns on (the
# reference's analog constants live in its probed bandwidth tables,
# comm_perf_model.py:94-116).
DMA_STARTUP_US = 2.0
ICI_HOP_LATENCY_US = 1.0


def _ring_time_s(nbytes_per_rank: int, world: int, link_gbps: float,
                 n_hops: int) -> float:
    return (nbytes_per_rank * n_hops) / (link_gbps * 1e9)


def estimate_all_gather_time_ms(nbytes_per_rank: int, world: int,
                                spec: ChipSpec | None = None,
                                bidir: bool = True) -> float:
    """Ring AG over ICI: (w-1) hops of the shard per direction plus
    per-step fixed costs (reference comm_perf_model.py:94)."""
    spec = spec or get_chip_spec()
    if world <= 1:
        return 0.0
    hops = (world - 1 + 1) // 2 if bidir else world - 1
    bw = _ring_time_s(nbytes_per_rank, world, spec.ici_gbps_per_link, hops)
    fixed = hops * (DMA_STARTUP_US + ICI_HOP_LATENCY_US) * 1e-6
    return (bw + fixed) * 1e3


def estimate_full_mesh_push_time_ms(nbytes_per_rank: int, world: int,
                                    spec: ChipSpec | None = None) -> float:
    """Full-mesh push AG: one logical hop (all w-1 puts launch at once),
    but non-neighbor puts traverse the torus (mean distance ~w/4 on a
    ring), consuming through-bandwidth on intermediate links."""
    spec = spec or get_chip_spec()
    if world <= 1:
        return 0.0
    avg_hops = max(world / 4.0, 1.0)
    # A 1-D gather axis owns 2 of the chip's links (one per direction);
    # every put occupies avg_hops link-segments of that capacity.
    bw = 2.0 * spec.ici_gbps_per_link
    t = nbytes_per_rank * (world - 1) * avg_hops / (bw * 1e9)
    fixed = (DMA_STARTUP_US + avg_hops * ICI_HOP_LATENCY_US) * 1e-6
    return (t + fixed) * 1e3


def estimate_reduce_scatter_time_ms(nbytes_per_rank: int, world: int,
                                    spec: ChipSpec | None = None,
                                    bidir: bool = True) -> float:
    """Ring RS ≙ AG mirror (reference comm_perf_model.py:116)."""
    return estimate_all_gather_time_ms(nbytes_per_rank, world, spec, bidir)


def estimate_one_shot_reduce_time_ms(nbytes_per_chunk: int, world: int,
                                     spec: ChipSpec | None = None) -> float:
    """One-shot RS/AR gather phase: every peer pushes its contribution
    directly (full-mesh), then a local w-way sum (HBM-bound)."""
    spec = spec or get_chip_spec()
    if world <= 1:
        return 0.0
    push = estimate_full_mesh_push_time_ms(nbytes_per_chunk, world, spec)
    reduce_ms = world * nbytes_per_chunk / (spec.hbm_gbps * 1e9) * 1e3
    return push + reduce_ms


def estimate_all_reduce_time_ms(nbytes: int, world: int,
                                spec: ChipSpec | None = None,
                                method: str = "two_shot") -> float:
    """two_shot: RS + AG decomposition; one_shot: full-buffer full-mesh
    exchange + local sum (reference allreduce.py:1101-1127 budgets the
    same trade)."""
    if world <= 1:
        return 0.0
    if method == "one_shot":
        return estimate_one_shot_reduce_time_ms(nbytes, world, spec)
    per = nbytes // max(world, 1)
    return (estimate_all_gather_time_ms(per, world, spec)
            + estimate_reduce_scatter_time_ms(per, world, spec))


# ---------------------------------------------------------------------------
# Fused-kernel config cost model (VMEM/ICI/MXU roofline)
# ---------------------------------------------------------------------------

#: Per-MXU-dispatch fixed cost inside a Mosaic tile loop (loop
#: bookkeeping, semaphore ops, the VMEM C-stage copy). Measured round 5
#: on v5e: at block_m=128/block_n=512 each ~1.4 us dot carried ~0.5 us
#: of overhead — the gap between the kernel's 135 TFLOPS and the 167
#: TFLOPS calibration dot (docs/perf.md "Why 135 TFLOPS"). This term is
#: what makes the model prefer big tiles: halving the tile count halves
#: the overhead while the roofline terms stay put.
TILE_OVERHEAD_US = 0.5


@dataclasses.dataclass(frozen=True)
class FusedGemmCost:
    """Roofline breakdown of one fused comm-GEMM config.

    ``total_ms`` ranks autotune candidates (tile loop + the comm the
    schedule could not hide); ``overlap_pct`` is the hidden fraction of
    the ring communication — the per-op ``comms.<op>.overlap_pct``
    gauge the ops emit (docs/perf.md "Overlap accounting")."""
    total_ms: float
    compute_ms: float          # max(mxu, hbm) + tile overhead
    mxu_ms: float              # FLOP-bound term
    hbm_ms: float              # HBM-traffic term (tile re-reads incl.)
    tile_overhead_ms: float    # n_tiles * TILE_OVERHEAD_US
    comm_ms: float             # ring ICI time for the full payload
    exposed_comm_ms: float     # comm the tile loop cannot hide
    overlap_pct: float         # 100 * (1 - exposed/comm); 100 if no comm
    n_tiles: int


def _ring_hops(world: int, ring_dirs: int) -> int:
    """Critical-path hop count of the AG ring schedule — derived from
    the kernels' own ``ops.common.ring_hop_counts`` (single source of
    truth: a future change to the direction split must reprice the
    cost model automatically). Lazy import: ops.common imports nothing
    from tools at module scope, but keeping tools → ops edges lazy
    mirrors the ops → tools convention."""
    if world <= 1:
        return 0
    from triton_dist_tpu.ops.common import ring_hop_counts
    return max(ring_hop_counts(world, ring_dirs))


def _fused_cost(flops: float, hbm_bytes: float, n_tiles: int,
                comm_ms: float, world: int, hops: int,
                spec: ChipSpec) -> FusedGemmCost:
    """Combine the roofline terms with the ring schedule's per-step
    overlap structure: the hop moving chunk s+1 overlaps the tile loop
    of chunk s, so each hop hides up to one chunk's compute; the rest
    is exposed."""
    mxu_ms = flops / (spec.bf16_tflops * 1e12) * 1e3
    hbm_ms = hbm_bytes / (spec.hbm_gbps * 1e9) * 1e3
    tile_ms = n_tiles * TILE_OVERHEAD_US * 1e-3
    compute_ms = max(mxu_ms, hbm_ms) + tile_ms
    if world <= 1 or comm_ms <= 0.0 or hops <= 0:
        exposed_ms, pct = 0.0, 100.0
    else:
        t_hop = comm_ms / hops
        per_chunk = compute_ms / world
        exposed_ms = hops * max(0.0, t_hop - per_chunk)
        pct = 100.0 * (1.0 - exposed_ms / comm_ms)
    return FusedGemmCost(
        total_ms=compute_ms + exposed_ms, compute_ms=compute_ms,
        mxu_ms=mxu_ms, hbm_ms=hbm_ms, tile_overhead_ms=tile_ms,
        comm_ms=comm_ms, exposed_comm_ms=exposed_ms,
        overlap_pct=round(max(0.0, min(100.0, pct)), 1),
        n_tiles=n_tiles)


def estimate_ag_gemm_cost(cfg: dict, *, m: int, rows: int, k: int,
                          n_loc: int, itemsize: int, world: int,
                          spec: ChipSpec | None = None,
                          ring_dirs: int = 2) -> FusedGemmCost:
    """Cost of one ``ag_gemm_configs`` entry at (M, K) x (K, N_loc).

    Traffic model per variant (mirrors the kernels' DMA structure):
    ``vmem`` — operands once, one dot per chunk; ``hbm`` (N-blocked) —
    B panel once, A re-read once per N-block, C once; ``hbm_kt`` — A
    once but the B panel re-read per (chunk, m-tile) — the re-read that
    makes it the huge-K fallback, priced here instead of hidden."""
    spec = spec or get_chip_spec()
    variant = cfg.get("variant", "hbm")
    flops = 2.0 * m * k * n_loc
    if variant == "vmem":
        hbm_bytes = itemsize * (rows * k + k * n_loc + m * n_loc + m * k)
        n_tiles = max(world, 1)
    elif variant == "hbm":
        bm = cfg.get("block_m", 256)
        bn = cfg.get("block_n", 512)
        n_blocks = max(n_loc // max(bn, 1), 1)
        m_tiles = max(rows // max(bm, 1), 1)
        hbm_bytes = itemsize * (m * k * (n_blocks + 1) + k * n_loc
                                + m * n_loc)
        n_tiles = world * m_tiles * n_blocks
    else:  # hbm_kt
        bm = cfg.get("block_m", 128)
        bk = cfg.get("block_k", 256)
        m_tiles = max(rows // max(bm, 1), 1)
        k_tiles = max(k // max(bk, 1), 1)
        hbm_bytes = itemsize * (2 * m * k + world * m_tiles * k * n_loc
                                + m * n_loc)
        n_tiles = world * m_tiles * k_tiles
    comm_ms = estimate_all_gather_time_ms(
        rows * k * itemsize, world, spec,
        bidir=(ring_dirs == 2 and world > 2))
    return _fused_cost(flops, hbm_bytes, n_tiles, comm_ms, world,
                       _ring_hops(world, ring_dirs), spec)


def estimate_ag_swiglu_cost(cfg: dict, *, m: int, rows: int, k: int,
                            n_loc: int, itemsize: int, world: int,
                            spec: ChipSpec | None = None,
                            ring_dirs: int = 2) -> FusedGemmCost:
    """Cost of one ``ag_swiglu_configs`` entry: the N-blocked dual-GEMM
    kernel (gate AND up panels resident, two dots + the activation per
    tile, one fused C write)."""
    spec = spec or get_chip_spec()
    bm = cfg.get("block_m", 256)
    bn = cfg.get("block_n", 512)
    n_blocks = max(n_loc // max(bn, 1), 1)
    m_tiles = max(rows // max(bm, 1), 1)
    flops = 2.0 * 2.0 * m * k * n_loc
    hbm_bytes = itemsize * (m * k * (n_blocks + 1) + 2 * k * n_loc
                            + m * n_loc)
    n_tiles = 2 * world * m_tiles * n_blocks   # two dots per tile
    comm_ms = estimate_all_gather_time_ms(
        rows * k * itemsize, world, spec,
        bidir=(ring_dirs == 2 and world > 2))
    return _fused_cost(flops, hbm_bytes, n_tiles, comm_ms, world,
                       _ring_hops(world, ring_dirs), spec)


def estimate_gemm_rs_cost(cfg: dict, *, m: int, rows: int, k_loc: int,
                          n: int, itemsize: int, world: int,
                          spec: ChipSpec | None = None,
                          ring_dirs: int = 2) -> FusedGemmCost:
    """Cost of one ``gemm_rs_configs`` entry at (M, K_loc) x (K_loc, N).

    The bidirectional RS halves per-link traffic by sending the two
    column halves opposite ways, which ``estimate_reduce_scatter_time_ms
    (bidir=True)`` already prices as half the hops of a full payload."""
    spec = spec or get_chip_spec()
    variant = cfg.get("variant", "hbm")
    flops = 2.0 * m * k_loc * n
    slab_bytes = 2 * max(world - 1, 0) * rows * n * itemsize
    if variant == "vmem":
        hbm_bytes = itemsize * (m * k_loc + k_loc * n + rows * n)
        n_tiles = max(world, 1)
    elif variant == "hbm":
        bm = cfg.get("block_m", 256)
        bn = cfg.get("block_n", 512)
        n_blocks = max(n // max(bn, 1), 1)
        m_tiles = max(rows // max(bm, 1), 1)
        hbm_bytes = (itemsize * (m * k_loc * n_blocks
                                 + world * k_loc * n + m * n)
                     + slab_bytes)
        n_tiles = world * m_tiles * n_blocks
    else:  # hbm_kt
        bm = cfg.get("block_m", 128)
        bk = cfg.get("block_k", 256)
        m_tiles = max(rows // max(bm, 1), 1)
        k_tiles = max(k_loc // max(bk, 1), 1)
        hbm_bytes = (itemsize * (m * k_loc
                                 + world * m_tiles * k_loc * n + m * n)
                     + slab_bytes)
        n_tiles = world * m_tiles * k_tiles
    comm_ms = estimate_reduce_scatter_time_ms(
        rows * n * itemsize, world, spec,
        bidir=(ring_dirs == 2))
    return _fused_cost(flops, hbm_bytes, n_tiles, comm_ms, world,
                       world - 1 if world > 1 else 0, spec)


def prune_configs(cfgs, cost_ms_fn, *, factor: int = 4,
                  keep_min: int = 2, always_keep=None):
    """Cost-model pruning of an autotune candidate table.

    Keeps ``max(keep_min, len(cfgs) // factor)`` entries: first the
    best-cost config matching ``always_keep`` (the downstream-clamp
    fallback variants pruning must never drop — review r5l finding 1),
    then the best-ranked remainder. Every kept entry still runs under
    the sweep's per-config compile-failure isolation; pruning trims the
    ~30 s-per-candidate Mosaic compile bill, it does not relax safety.

    Returns ``(pruned, n_before)`` so callers can log the counts
    (``tools.autotuner.record_prune``).
    """
    cfgs = list(cfgs)
    n_before = len(cfgs)
    if n_before <= keep_min:
        return cfgs, n_before
    costs = [float(cost_ms_fn(c)) for c in cfgs]
    order = sorted(range(n_before), key=lambda i: costs[i])
    n_keep = max(keep_min, n_before // factor)
    picked: list[int] = []
    if always_keep is not None:
        musts = [i for i in order if always_keep(cfgs[i])]
        if musts:
            picked.append(musts[0])
    for i in order:
        if len(picked) >= n_keep:
            break
        if i not in picked:
            picked.append(i)
    picked.sort(key=lambda i: costs[i])
    return [cfgs[i] for i in picked], n_before


def declared_footprint(op: str, cfg: dict, *, rows: int,
                       itemsize: int = 2, world: int = 1,
                       m: int | None = None, k: int | None = None,
                       k_loc: int | None = None, n: int | None = None,
                       n_loc: int | None = None) -> int:
    """Declared VMEM bytes of one fused-family candidate config — the
    number the per-op clamps compare against ``DEFAULT_VMEM_BUDGET`` /
    ``HARD_FOOTPRINT_CAP`` (ops/common.py). Delegates to the kernels'
    own footprint helpers where they exist so this stays a single
    source of truth; the inline vmem/k-tiled formulas mirror the
    config generators (``ag_gemm_configs`` / ``gemm_rs_configs``).

    Used by the static analysis vet (``triton_dist_tpu.analysis.vmem``)
    and the autotuner's pre-compile candidate gate — an over-budget
    config is rejected from Python, before Mosaic ever sees it."""
    variant = cfg.get("variant", "hbm")
    bm = cfg.get("block_m", 256)
    bn = cfg.get("block_n", 512)
    bk = cfg.get("block_k", 256)
    if op in ("ag_gemm", "ag_swiglu"):
        from triton_dist_tpu.ops.allgather_gemm import (
            _hbm_footprint, _swiglu_footprint)
        if op == "ag_swiglu":
            return _swiglu_footprint(bm, bn, k, itemsize)
        if variant == "vmem":
            return itemsize * (m * k + k * n_loc + m * n_loc + rows * k)
        if variant == "hbm":
            return _hbm_footprint(bm, bn, k, itemsize)
        return (2 * bm * bk + 2 * bk * n_loc) * itemsize \
            + bm * n_loc * (4 + 2 * itemsize)
    if op in ("gemm_rs", "gemm_ar"):
        from triton_dist_tpu.ops.gemm_reduce_scatter import (
            _hbm_nb_footprint)
        if variant == "vmem":
            return itemsize * (m * k_loc + k_loc * n + rows * n
                               + 2 * max(world - 1, 1) * rows * n)
        if variant == "hbm":
            return _hbm_nb_footprint(bm, bn, k_loc, itemsize)
        return (2 * bm * bk + 2 * bk * n) * itemsize \
            + bm * n * (4 + 3 * itemsize)
    if op == "all_to_all":
        # send slab input + recv output, both whole in VMEM — the
        # op's own formula (ops/all_to_all.py a2a_footprint).
        from triton_dist_tpu.ops.all_to_all import a2a_footprint
        return a2a_footprint(world, cfg["capacity"], cfg["h"], itemsize)
    if op == "moe_reduce_rs":
        # The fused kernel's scratch at the h-block it will actually
        # run: delegate BOTH the clamp and the formula to the kernel's
        # own helpers so the vet prices the real tiling.
        from triton_dist_tpu.ops.moe_reduce_rs import (
            moe_rs_fused_footprint, moe_rs_resolve_h_blk)
        h_blk = moe_rs_resolve_h_blk(
            cfg["h"], cfg.get("block_h", 512), cfg.get("block_m", 128),
            cfg["i_loc"], rows, itemsize, cfg["vmem_budget"])
        return moe_rs_fused_footprint(
            cfg.get("block_m", 128), cfg["i_loc"], h_blk, rows,
            itemsize)
    raise ValueError(f"no footprint model for op {op!r}")


def vet_vmem(op: str, cfg: dict, *, cap: int | None = None,
             **dims) -> str | None:
    """Static VMEM gate for one autotune candidate: a rejection reason
    when the declared footprint exceeds ``cap`` (default
    ``HARD_FOOTPRINT_CAP``), else ``None``. Pure Python — no compile
    is invoked, so a config Mosaic would refuse (or hang on) for its
    VMEM footprint is refused up front."""
    if cap is None:
        from triton_dist_tpu.ops.common import HARD_FOOTPRINT_CAP
        cap = HARD_FOOTPRINT_CAP
    fp = declared_footprint(op, cfg, **dims)
    if fp > cap:
        return (f"{op} config {cfg} declares {fp / 2**20:.1f} MB VMEM "
                f"> {cap / 2**20:.1f} MB cap")
    return None


def overlap_efficiency(gemm_ms: float, comm_ms: float) -> float:
    """Upper bound on fused-op gain: serial/(overlapped) time ratio. 1.0 =
    no win, 2.0 = perfect hiding of the shorter phase (a measured
    overlap efficiency divides by this bound)."""
    serial = gemm_ms + comm_ms
    overlapped = max(gemm_ms, comm_ms)
    return serial / overlapped


def probe_matmul_tflops(m: int = 4096, n: int = 4096, k: int = 4096,
                        dtype=None, iters: int = 10) -> float:
    """Measured MXU throughput (the live analog of the spec table)."""
    import jax.numpy as jnp
    from triton_dist_tpu.runtime.utils import perf_func
    dtype = dtype or jnp.bfloat16
    a = jnp.ones((m, k), dtype)
    b = jnp.ones((k, n), dtype)
    f = jax.jit(lambda: a @ b)
    _, ms = perf_func(f, iters=iters, warmup_iters=3, return_output=False)
    return 2.0 * m * n * k / (ms * 1e-3) / 1e12
