"""ctypes bindings for the native scheduler (csrc/scheduler/scheduler.cc).

Reference analog: the mega runtime's scheduler + ModelBuilder dependency
resolution (mega_triton_kernel/core/scheduler.py:40-95,
models/model_builder.py) — kept native like the reference's csrc/
components. Falls back to pure-Python implementations when no compiler is
available (results are bit-identical; tests assert so).
"""

from __future__ import annotations

import ctypes
import os

import numpy as np

_SRC = os.path.join(os.path.dirname(__file__), "..", "csrc", "scheduler",
                    "scheduler.cc")
_SO = os.path.join(os.path.dirname(_SRC), "libtdtsched.so")
_LIB = None
_TRIED = False


def _configure(lib):
    lib.tdt_toposort.restype = ctypes.c_int32
    lib.tdt_wavefronts.restype = ctypes.c_int32
    lib.tdt_schedule_critical_path.restype = ctypes.c_int64
    lib.tdt_priority_order.restype = ctypes.c_int32


def _load():
    global _LIB, _TRIED
    if not _TRIED:
        _TRIED = True
        from triton_dist_tpu.runtime.native_lib import load_native
        _LIB = load_native(_SRC, _SO, _configure)
    return _LIB


def have_native() -> bool:
    return _load() is not None


def _i32(a):
    return np.ascontiguousarray(a, np.int32)


def schedule(n_tasks: int, n_queues: int, policy: str = "round_robin",
             costs=None) -> np.ndarray:
    """Assign tasks to queues. Policies: round_robin | zigzag |
    least_loaded (reference ROUND_ROBIN / ZIG_ZAG, scheduler.py:86)."""
    lib = _load()
    out = np.empty(n_tasks, np.int32)
    if lib is not None:
        p = out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
        if policy == "round_robin":
            lib.tdt_schedule_round_robin(n_tasks, n_queues, p)
        elif policy == "zigzag":
            lib.tdt_schedule_zigzag(n_tasks, n_queues, p)
        elif policy == "least_loaded":
            c = (np.ascontiguousarray(costs, np.int64)
                 .ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
                 if costs is not None else None)
            lib.tdt_schedule_least_loaded(n_tasks, n_queues, c, p)
        else:
            raise ValueError(policy)
        return out
    return _schedule_py(n_tasks, n_queues, policy, costs)


def _schedule_py(n_tasks, n_queues, policy, costs=None) -> np.ndarray:
    out = np.empty(n_tasks, np.int32)
    if policy == "round_robin":
        out[:] = np.arange(n_tasks) % n_queues
    elif policy == "zigzag":
        r = np.arange(n_tasks) % (2 * n_queues)
        out[:] = np.where(r < n_queues, r, 2 * n_queues - 1 - r)
    elif policy == "least_loaded":
        load = np.zeros(n_queues, np.int64)
        c = (np.asarray(costs, np.int64) if costs is not None
             else np.ones(n_tasks, np.int64))
        for i in range(n_tasks):
            q = int(np.argmin(load))
            out[i] = q
            load[q] += c[i]
    else:
        raise ValueError(policy)
    return out


def schedule_critical_path(n_tasks: int, edges, n_queues: int,
                           costs=None) -> tuple[np.ndarray, int]:
    """HEFT-style dependency-aware list scheduling: tasks prioritized by
    upward rank (longest cost-weighted path to a sink), each placed on
    the queue with the earliest dependency-respecting start.

    Returns (queue_of_task, makespan). The makespan is a
    speed-of-light estimate of the fused step on ``n_queues``-way
    hardware — usable as a perf model for the mega graph. Raises on
    cycles. Native C++ with a bit-identical Python fallback.

    Costs must be >= 0 (zero is fine for free ops like reshapes; rank
    ties are broken in topological order so dependencies hold).
    """
    if costs is not None and int(np.min(np.asarray(costs))) < 0:
        raise ValueError("costs must be >= 0")
    edges = _i32(np.asarray(edges).reshape(-1, 2))
    lib = _load()
    if lib is not None:
        out = np.empty(n_tasks, np.int32)
        c = (np.ascontiguousarray(costs, np.int64)
             .ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
             if costs is not None else None)
        span = lib.tdt_schedule_critical_path(
            n_tasks, len(edges),
            edges.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            n_queues, c,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        if span < 0:
            raise ValueError("task graph has a cycle")
        return out, int(span)
    return _schedule_critical_path_py(n_tasks, edges, n_queues, costs)


def _schedule_critical_path_py(n_tasks, edges, n_queues,
                               costs=None) -> tuple[np.ndarray, int]:
    c = (np.asarray(costs, np.int64) if costs is not None
         else np.ones(n_tasks, np.int64))
    children = [[] for _ in range(n_tasks)]
    parents = [[] for _ in range(n_tasks)]
    for s, d in edges:
        children[s].append(int(d))
        parents[d].append(int(s))
    # upward ranks in reverse topological order
    order = _toposort_py(n_tasks, edges)
    pos = np.empty(n_tasks, np.int64)
    pos[order] = np.arange(n_tasks)
    rank = np.zeros(n_tasks, np.int64)
    for t in reversed(order):
        best = max((rank[ch] for ch in children[t]), default=0)
        rank[t] = c[t] + best
    # ties broken by topo position (zero-cost parents must precede)
    prio = sorted(range(n_tasks), key=lambda i: (-rank[i], pos[i]))
    queue_free = np.zeros(n_queues, np.int64)
    finish = np.zeros(n_tasks, np.int64)
    out = np.empty(n_tasks, np.int32)
    makespan = 0
    for t in prio:
        ready = max((finish[p] for p in parents[t]), default=0)
        starts = np.maximum(queue_free, ready)
        q = int(np.argmin(starts))
        out[t] = q
        finish[t] = starts[q] + c[t]
        queue_free[q] = finish[t]
        makespan = max(makespan, int(finish[t]))
    return out, makespan


def priority_order(n_tasks: int, edges, costs=None) -> np.ndarray:
    """HEFT priority linearization: task ids in (descending upward
    rank, ties by topological position) — the visit order of
    :func:`schedule_critical_path`, and itself a valid topological
    order (a parent's rank exceeds any child's by >= its own cost;
    zero-cost ties fall back to topo position).

    This is the schedule's RUNTIME hook: the mega executor emits tasks
    in this order, which biases XLA's buffer-liveness/latency-hiding
    scheduling toward the critical path (the effect to look for is a
    lower peak of temporary memory in the lowered program)."""
    edges = _i32(np.asarray(edges).reshape(-1, 2))
    lib = _load()
    if lib is not None:
        out = np.empty(n_tasks, np.int32)
        c = (np.ascontiguousarray(costs, np.int64)
             .ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
             if costs is not None else None)
        rc = lib.tdt_priority_order(
            n_tasks, len(edges),
            edges.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            c, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        if rc != 0:
            raise ValueError("task graph has a cycle")
        return out
    return _priority_order_py(n_tasks, edges, costs)


def _priority_order_py(n_tasks, edges, costs=None) -> np.ndarray:
    c = (np.asarray(costs, np.int64) if costs is not None
         else np.ones(n_tasks, np.int64))
    children = [[] for _ in range(n_tasks)]
    for s, d in edges:
        children[s].append(int(d))
    order = _toposort_py(n_tasks, edges)
    pos = np.empty(n_tasks, np.int64)
    pos[order] = np.arange(n_tasks)
    rank = np.zeros(n_tasks, np.int64)
    for t in reversed(order):
        best = max((rank[ch] for ch in children[t]), default=0)
        rank[t] = c[t] + best
    return np.asarray(
        sorted(range(n_tasks), key=lambda i: (-rank[i], pos[i])),
        np.int32)


def toposort(n_tasks: int, edges) -> np.ndarray:
    """Stable topological order (ties by task id). edges: (E, 2) int
    (src, dst). Raises on cycles."""
    edges = _i32(np.asarray(edges).reshape(-1, 2))
    lib = _load()
    if lib is not None:
        out = np.empty(n_tasks, np.int32)
        rc = lib.tdt_toposort(
            n_tasks, len(edges),
            edges.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        if rc != 0:
            raise ValueError("task graph has a cycle")
        return out
    return _toposort_py(n_tasks, edges)


def _toposort_py(n_tasks, edges) -> np.ndarray:
    import heapq
    adj = [[] for _ in range(n_tasks)]
    indeg = [0] * n_tasks
    for s, d in edges:
        adj[s].append(int(d))
        indeg[d] += 1
    ready = [i for i in range(n_tasks) if indeg[i] == 0]
    heapq.heapify(ready)
    order = []
    while ready:
        t = heapq.heappop(ready)
        order.append(t)
        for d in adj[t]:
            indeg[d] -= 1
            if indeg[d] == 0:
                heapq.heappush(ready, d)
    if len(order) != n_tasks:
        raise ValueError("task graph has a cycle")
    return np.asarray(order, np.int32)


def wavefronts(n_tasks: int, edges) -> tuple[int, np.ndarray]:
    """(n_waves, wave_of_task): longest-path depth partition — fusion
    groups for the jit executor (scoreboard-phase analog)."""
    edges = _i32(np.asarray(edges).reshape(-1, 2))
    lib = _load()
    if lib is not None:
        out = np.empty(n_tasks, np.int32)
        n = lib.tdt_wavefronts(
            n_tasks, len(edges),
            edges.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        if n < 0:
            raise ValueError("task graph has a cycle")
        return int(n), out
    return _wavefronts_py(n_tasks, edges)


def _wavefronts_py(n_tasks, edges) -> tuple[int, np.ndarray]:
    order = _toposort_py(n_tasks, edges)
    depth = np.zeros(n_tasks, np.int32)
    adj = [[] for _ in range(n_tasks)]
    for s, d in edges:
        adj[s].append(int(d))
    for t in order:
        for d in adj[t]:
            depth[d] = max(depth[d], depth[t] + 1)
    return int(depth.max(initial=0)) + 1, depth
