"""The program's own spans on the pump's path, read from a capture.

Every ``obs.span`` of the program is a ``jax.profiler.TraceAnnotation``,
so it lies on a host plane of the capture, on its thread's line and on
the capture's own clock, beside the device planes. This module turns the
spans that ``benchmark/trace_names/pump.json`` names into trees (a span's
children are the named spans it contains on the same line; its self time
is its duration minus what they cover) and pairs the decode-step spans
with the device programs they launched. The readers
``layer_metrics/{sched.pump_self_ms,sched.starved_share,
engine.admit_host_ms,engine.step_launch_ms,engine.step_return_ms}.py``
share it; no name of the program is written here or there.
"""

from __future__ import annotations

import bisect
import functools
import json
import os
import re

from benchmark.harness import intervals

NAMES_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "trace_names", "pump.json")


@functools.lru_cache(maxsize=None)
def names() -> dict:
    with open(NAMES_FILE) as f:
        return json.load(f)


class Span:
    __slots__ = ("name", "t0", "t1", "children")

    def __init__(self, name: str, t0: float, t1: float):
        self.name, self.t0, self.t1 = name, t0, t1
        self.children: list[Span] = []

    @property
    def dur(self) -> float:
        return self.t1 - self.t0

    @property
    def self_s(self) -> float:
        """Duration minus the part of it the children cover."""
        covered = intervals.clip([(c.t0, c.t1) for c in self.children],
                                 self.t0, self.t1)
        return self.dur - intervals.union_len(covered)

    def has(self, name: str) -> bool:
        return any(c.name == name for c in self.children)


@functools.lru_cache(maxsize=1)
def trees(trace) -> dict:
    """``{(plane, line): [top-level Span, ...]}`` over the host planes:
    the named spans of each line, nested by containment. Kept for the
    one capture of a run: every reader asks, and a host line holds
    hundreds of thousands of the profiler's own events to pass over."""
    wanted = frozenset(names()["spans"].values())
    out = {}
    for (plane, line), evs in trace.lines.items():
        if plane.startswith("/device:"):
            continue
        mine = sorted((e for e in evs if e[0] in wanted),
                      key=lambda e: (e[1], -e[2]))
        if not mine:
            continue
        tops, stack = [], []
        for name, t0, t1 in mine:
            span = Span(name, t0, t1)
            while stack and stack[-1].t1 < t1:
                stack.pop()
            (stack[-1].children if stack else tops).append(span)
            stack.append(span)
        out[(plane, line)] = tops
    return out


def spans(trace, key: str) -> list[Span]:
    """Every span named ``names()["spans"][key]``, at any depth, in
    time order."""
    name = names()["spans"][key]
    found, todo = [], [s for tops in trees(trace).values() for s in tops]
    while todo:
        s = todo.pop()
        if s.name == name:
            found.append(s)
        todo += s.children
    return sorted(found, key=lambda s: s.t0)


def programs_started_in(span: Span, progs) -> list:
    """The device programs of ``progs`` (sorted by start) that start
    inside ``span``."""
    return progs[bisect.bisect_left(progs, span.t0, key=_start):
                 bisect.bisect_right(progs, span.t1, key=_start)]


def _start(event) -> float:
    return event[1]


def inside_device_extent(reduced, found: list[Span]) -> list[Span]:
    """The spans that lie wholly between the first and the last device
    program of the fullest chip: one cut by an edge of the capture may
    have lost its program to the edge."""
    mods = reduced.modules[reduced.fullest]
    if not mods:
        return []
    lo, hi = mods[0][1], max(e[2] for e in mods)
    return [s for s in found if s.t0 >= lo and s.t1 <= hi]


def paired_steps(ctx) -> list | None:
    """``[(decode-step span, its decode program), ...]`` on the fullest
    chip; ``None`` when the capture has no device plane or no such
    span. The decode program is the configuration's
    ``trace_names.decode_program``. Raises when fewer than
    ``matched_steps_min`` of the spans hold exactly one start of it:
    the program's spans and the device trace then do not share a
    clock, and no difference between them means anything."""
    reduced = ctx["trace"]
    if not reduced.chips:
        return None
    steps = inside_device_extent(reduced,
                                 spans(reduced.trace, "decode_step"))
    if not steps:
        return None
    progs = reduced.module_events(
        ctx["config"]["trace_names"]["decode_program"])
    pairs = []
    for s in steps:
        mine = programs_started_in(s, progs)
        if len(mine) == 1:
            pairs.append((s, mine[0]))
    need = float(names()["matched_steps_min"])
    if len(pairs) < need * len(steps):
        raise LookupError(
            f"{len(pairs)} of {len(steps)} {steps[0].name!r} spans hold "
            f"exactly one device program matching "
            f"{ctx['config']['trace_names']['decode_program']!r} "
            f"(at least {need:.0%} must): host spans and device trace "
            f"do not share a clock")
    return pairs


def admission_programs(reduced) -> list:
    rx = re.compile(names()["programs"]["admission"])
    return [e for e in reduced.modules[reduced.fullest] if rx.search(e[0])]
