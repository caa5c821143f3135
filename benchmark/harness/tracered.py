"""From a parsed capture to what the per-layer readers and the result
line need: device busy time per chip, the programs (XLA modules) and
operations that ran, and the idle gaps with what the host was doing.

Device planes are ``/device:TPU:<n>``. Their ``XLA Ops`` line holds one
event per executed HLO operation (a Pallas kernel is one custom-call
event), ``XLA Modules`` one event per executed program (``jit_step``,
``jit_admit``). Host spans are the program's own ``obs.span`` regions
(profiler annotations on the host planes) that the configuration file
names under ``trace_names.host_spans``; the benchmark plants none.
"""

from __future__ import annotations

import bisect
import re

from benchmark.harness import intervals

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _strip(name: str) -> str:
    """``jit_step(1234567)`` -> ``jit_step``; ``fusion.12`` stays."""
    return re.sub(r"\(\d+\)$", "", name)


def short(name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction
    (``%copy.1017 = bf16[...] copy(...)``): keep the instruction's name."""
    m = re.match(r"%?([\w.\-]+)", name)
    return m.group(1) if m else name[:64]


def family(name: str) -> str:
    """``copy.1017`` -> ``copy``: instructions of one kind together."""
    return re.sub(r"[.\d]+$", "", short(name)) or short(name)


class Reduced:
    def __init__(self, trace, window_s: float | None = None,
                 host_span_names=()):
        self.trace = trace
        self.host_span_names = frozenset(host_span_names)
        self.chips = [p for p in trace.device_planes()
                      if trace.events(p, OPS_LINE)]
        self.ops = {p: trace.events(p, OPS_LINE) for p in self.chips}
        self.modules = {p: [(_strip(n), a, b)
                            for n, a, b in trace.events(p, MODULES_LINE)]
                        for p in self.chips}
        if not self.chips:
            self.lo = self.hi = 0.0
            self.window_s = window_s or 0.0
            self.busy = {}
            return
        self.lo = min(evs[0][1] for evs in self.ops.values())
        self.hi = max(max(e[2] for e in evs) for evs in self.ops.values())
        extent = self.hi - self.lo
        self.window_s = max(window_s or 0.0, extent)
        self.busy = {p: intervals.union_len((a, b) for _, a, b in evs)
                     for p, evs in self.ops.items()}

    # -- what the result line carries --------------------------------------
    @property
    def busy_s(self) -> float:
        """Mean over the chips used of the union of device-op intervals."""
        return sum(self.busy.values()) / max(len(self.busy), 1)

    @property
    def fullest(self) -> str:
        return max(self.busy, key=self.busy.get)

    def device_ops(self, k: int = 10) -> list[list]:
        """The kinds of operation that took most device time on the
        fullest chip (instructions of one family summed)."""
        tot: dict[str, float] = {}
        for name, a, b in self.ops[self.fullest]:
            f = family(name)
            tot[f] = tot.get(f, 0.0) + (b - a)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, s] for n, s in top]

    def host_spans(self) -> list[tuple[str, float, float]]:
        out = []
        for (plane, _line), evs in self.trace.lines.items():
            if plane.startswith("/device:"):
                continue
            out += [e for e in evs if e[0] in self.host_span_names]
        return sorted(out, key=lambda e: e[1])

    def idle_gaps(self, k: int = 10) -> list[list]:
        """Idle seconds of the fullest chip by what was going on at the
        middle of each gap: ``inside.<program>`` when a program was
        running on the chip (the device waits on itself); else the
        program's host span that was open (the innermost); else
        ``before.<program>``, the host getting the next program ready
        (the pump, the socket, the GIL)."""
        chip = self.fullest
        evs = self.ops[chip]
        gaps = intervals.gaps([(a, b) for _, a, b in evs], self.lo, self.hi)
        spans = self.host_spans()
        mods = self.modules[chip]
        span_t0 = [e[1] for e in spans]
        mod_t0 = [e[1] for e in mods]
        tot: dict[str, float] = {}
        for a, b in gaps:
            mid = 0.5 * (a + b)
            j = bisect.bisect_right(mod_t0, mid)
            if j and mods[j - 1][2] >= mid:
                name = "inside." + mods[j - 1][0]
            else:
                name = ("before." + mods[j][0] if j < len(mods)
                        else "after_last_program")
                i = bisect.bisect_right(span_t0, mid)
                for e in reversed(spans[max(i - 8, 0):i]):
                    if e[2] >= mid:
                        name = e[0]
                        break
            tot[name] = tot.get(name, 0.0) + (b - a)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, s] for n, s in top]

    def describe(self, k: int = 30) -> dict:
        """What the capture holds, for the log: the fullest chip's lines,
        its programs and its costliest operations by name."""
        if not self.chips:
            return {"planes": self.trace.planes(),
                    "host_spans": len(self.host_spans())}
        chip = self.fullest
        mods: dict[str, list[float]] = {}
        for n, a, b in self.modules[chip]:
            mods.setdefault(n, []).append(b - a)
        ops: dict[str, list[float]] = {}
        custom: dict[str, str] = {}
        for n, a, b in self.ops[chip]:
            ops.setdefault(family(n), []).append(b - a)
            # Pallas kernels are custom calls named after what wraps them
            # (``shard_map.<n>``, or the jitted function at world 1).
            if len(custom) < 6 and "tpu_custom_call" in n:
                custom.setdefault(short(n), n[:900])
        top = sorted(ops.items(), key=lambda kv: -sum(kv[1]))[:k]
        return {
            "planes": self.trace.planes(), "chip": chip,
            "lines": self.trace.line_names(chip),
            "modules": {n: [len(v), sum(v) / len(v)]
                        for n, v in mods.items()},
            "ops": [[n, len(v), sum(v)] for n, v in top],
            "host_spans": len(self.host_spans()),
            "custom_calls": custom,
        }

    # -- what the readers ask ------------------------------------------------
    def module_events(self, pattern: str, chip: str | None = None):
        rx = re.compile(pattern)
        return [e for e in self.modules[chip or self.fullest]
                if rx.search(e[0])]
