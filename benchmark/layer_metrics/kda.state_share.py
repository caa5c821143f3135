"""KDA: the share of a decode step's unavoidable bytes that is the
recurrent state. Over the window, from the program's own counters made
inside the step (``kda.rows_stepped``, ``moe.experts_touched``,
``attn.positions_read.latent``) per decode step: the KDA state and conv
tails read and written (``benchmark/kernels/ling_step.state_bytes``)
over that step's bytes as ``ling_step.hbm_roofline`` counts them
(``step_bytes``). A program without the counters reads nothing."""
import re

from benchmark.harness import hostspans

ROWS = "kda.rows_stepped"


def read(ctx):
    c = ctx["counters"]
    if ROWS not in c:
        return None
    rx = re.compile(hostspans.names()["counters"]["decode_steps"])
    steps = sum(v for k, v in c.items() if rx.search(k))
    if steps <= 0:
        return None
    ks = ctx["load_kernel"]("ling_step")
    rows = c[ROWS] / steps
    need = ks.step_bytes(ctx["model"], c.get("moe.experts_touched", 0) / steps,
                         rows, c.get("attn.positions_read.latent", 0) / steps)
    return 100.0 * ks.state_bytes(ctx["model"], rows) / need
