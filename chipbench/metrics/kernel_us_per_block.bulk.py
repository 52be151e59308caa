"""Device time of one ``cgra_exec`` call (one 128-lane block), from the
kernel's events in the trace: their summed durations over their count."""


def read(ctx):
    evs = ctx.trace.kernel_events() if ctx.trace is not None else []
    if not evs:
        return None
    return sum(e.end - e.start for e in evs) / len(evs) / 1e3
