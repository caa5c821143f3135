"""Model step of the K-EXAONE share against the chip's memory bandwidth:
the bytes a decode step cannot avoid reading (``benchmark/kernels/
exaone_step.py``: every non-expert weight and the head slice once, each
held expert that got a token once, the K and V of the positions the live
rows attend to, a sliding layer's at most its window) over what the HBM
moves in the step's device time. Bytes: the window's mean step, from the
program's own counters (``moe.experts_touched``,
``attn.positions_read.window`` / ``.full``, made inside the step program)
over its delta of decode steps. Time: the mean device time of the decode
program (``trace_names.decode_program``) on the ``XLA Modules`` line of
the traced part of the window, as ``engine.decode_step_ms`` reads it. A
step that read only what it must at the HBM's peak reads 100 %. A
program without the counters reads nothing."""
import re

from benchmark.harness import hostspans

TOUCHED = "moe.experts_touched"
WINDOW = "attn.positions_read.window"
FULL = "attn.positions_read.full"


def read(ctx):
    tr, c = ctx["trace"], ctx["counters"]
    if ctx["peaks"] is None or not tr.chips or TOUCHED not in c:
        return None
    rx = re.compile(hostspans.names()["counters"]["decode_steps"])
    steps = sum(v for k, v in c.items() if rx.search(k))
    evs = tr.module_events(ctx["config"]["trace_names"]["decode_program"])
    if steps <= 0 or not evs:
        return None
    ks = ctx["load_kernel"]("exaone_step")
    need = ks.step_bytes(ctx["model"], c[TOUCHED] / steps,
                         c.get(WINDOW, 0) / steps, c.get(FULL, 0) / steps)
    seconds = sum(b - a for _, a, b in evs) / len(evs)
    return 100.0 * need / (seconds * ctx["peaks"]["hbm_bytes_per_s"])
