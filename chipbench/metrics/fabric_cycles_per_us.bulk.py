"""Simulated fabric cycles per microsecond of kernel device time: the
window's growth of the engine's ``fabric_cycles`` counter (the kernel's
rounds x II for every real image it ran, host arithmetic) over the summed
durations of the window's ``cgra_exec`` events."""


def read(ctx):
    evs = ctx.trace.kernel_events() if ctx.trace is not None else []
    try:
        cycles = (ctx.after["engine"]["fabric_cycles"]
                  - ctx.before["engine"]["fabric_cycles"])
    except (KeyError, TypeError):
        return None
    us = sum(e.end - e.start for e in evs) / 1e3
    if not evs or cycles <= 0 or us <= 0:
        return None
    return cycles / us
