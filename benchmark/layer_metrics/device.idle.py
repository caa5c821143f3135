"""Device: the share of the traced window in which no operation ran on
the fullest chip (1 - union of its device-op intervals / window)."""


def read(ctx):
    tr = ctx["trace"]
    if not tr.chips or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy[tr.fullest] / tr.window_s)
