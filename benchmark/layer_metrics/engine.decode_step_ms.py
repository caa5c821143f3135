"""Engine: device time of one shared decode step, the mean duration of
the decode program's events on the fullest chip's ``XLA Modules`` line
over the traced window. The program's name is the configuration file's
``trace_names.decode_program``; a capture that holds device programs but
none of that name fails the run: a renamed program must not make the
metric vanish quietly. (The scheduler's host span ``device.step.plain``
exists only while its own sampler captures; it is not used.)"""


def read(ctx):
    tr = ctx["trace"]
    if not tr.chips:
        return None
    pattern = ctx["config"]["trace_names"]["decode_program"]
    evs = tr.module_events(pattern)
    if not evs:
        seen = sorted({n for n, _, _ in tr.modules[tr.fullest]})
        raise LookupError(f"no device program matches {pattern!r}; the "
                          f"capture holds {seen}")
    return 1e3 * sum(b - a for _, a, b in evs) / len(evs)
