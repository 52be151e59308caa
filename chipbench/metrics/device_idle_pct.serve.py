"""Idle share of the chips over the traced window of a served cell:
1 - (union of each device's op intervals) / window, averaged over the
chips that ran anything (``xtrace``)."""


def read(ctx):
    return ctx.trace.idle_pct() if ctx.trace is not None else None
