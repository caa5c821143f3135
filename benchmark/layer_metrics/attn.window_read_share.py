"""Attention: the cache positions a sliding-window layer's decode step
has to read over those a full layer's has to, per layer, over the window:
delta of ``attn.positions_read.window`` / number of sliding layers over
delta of ``attn.positions_read.full`` / number of full layers (both made
inside the step program from the live rows' offsets: min(length, window)
and length). What the ring saves of a full layer's read."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("attn.positions_read.full"):
        return None
    kinds = ctx["load_kernel"]("exaone_step").kinds(ctx["model"])
    n_window = sum(1 for w, _ in kinds if w is not None)
    n_full = len(kinds) - n_window
    if not n_window or not n_full:
        return None
    return 100.0 * (c.get("attn.positions_read.window", 0) / n_window) \
        / (c["attn.positions_read.full"] / n_full)
