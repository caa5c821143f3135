"""Read a profiler capture (``*.xplane.pb``) into plain lists.

The reader is ``jax.profiler.ProfileData`` (part of JAX itself: planes,
their lines, events with a start and a duration in nanoseconds); the
normal form below is the benchmark's own, modelled on
``triton_dist_tpu/obs/devprof.parse_xplane``: one record per event with
its plane, line, name, start and end in seconds on the capture's clock.
Only the process that holds the chip can capture, so this module is
imported by ``run.py`` alone, after JAX is up.
"""

from __future__ import annotations

import glob
import os


class Trace:
    """``lines[(plane, line)] -> [(name, t0_s, t1_s), ...]``."""

    def __init__(self):
        self.lines: dict[tuple[str, str], list[tuple[str, float, float]]] = {}

    def planes(self) -> list[str]:
        return sorted({p for p, _ in self.lines})

    def device_planes(self) -> list[str]:
        return [p for p in self.planes() if p.startswith("/device:")]

    def events(self, plane: str, line: str):
        return self.lines.get((plane, line), [])

    def line_names(self, plane: str) -> list[str]:
        return sorted(l for p, l in self.lines if p == plane)


def from_profile_data(pd) -> Trace:
    tr = Trace()
    for plane in pd.planes:
        for line in plane.lines:
            evs = tr.lines.setdefault((plane.name, line.name), [])
            for ev in line.events:
                t0 = ev.start_ns * 1e-9
                evs.append((ev.name, t0, t0 + ev.duration_ns * 1e-9))
    for evs in tr.lines.values():
        evs.sort(key=lambda e: e[1])
    return tr


def load(path: str) -> Trace:
    """A capture directory (as given to ``jax.profiler.start_trace``) or
    one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise ValueError(f"no .xplane.pb under {path!r}")
        path = found[-1]
    return from_profile_data(ProfileData.from_file(path))


def load_text(text: str) -> Trace:
    """From an XSpace text proto (the small recorded trace of the tests)."""
    from jax.profiler import ProfileData
    return from_profile_data(ProfileData.from_text_proto(text))
