"""Share of the window's wall time in which the host was not blocked on
the device: the ``run_stream`` summary's ``overlap_frac`` (1 - wait/wall)
over the whole measured stream."""


def read(ctx):
    frac = getattr(ctx.driver, "summary", {}).get("overlap_frac")
    return None if frac is None else 100.0 * float(frac)
