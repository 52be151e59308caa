"""Idle share of the chip over the traced window of a bulk cell:
1 - (union of the device's op intervals) / window (``xtrace``)."""


def read(ctx):
    return ctx.trace.idle_pct() if ctx.trace is not None else None
