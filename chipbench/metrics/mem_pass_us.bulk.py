"""Kernel device time per scratchpad pass: the summed durations of the
window's ``cgra_exec`` events over the window's growth of the engine's
``mem_passes`` counter (one pass per fired LOAD/STORE slot of each
kernel call, host arithmetic)."""


def read(ctx):
    evs = ctx.trace.kernel_events() if ctx.trace is not None else []
    try:
        passes = (ctx.after["engine"]["mem_passes"]
                  - ctx.before["engine"]["mem_passes"])
    except (KeyError, TypeError):
        return None
    if not evs or passes <= 0:
        return None
    return sum(e.end - e.start for e in evs) / 1e3 / passes
