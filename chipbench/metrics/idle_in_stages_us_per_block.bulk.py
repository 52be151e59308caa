"""Device idle time per kernel call while the host was inside one of the
engine's host stages (``stages.HOST``): the ring's stage spans mapped
onto the trace's clock by the window's closing anchor, their union laid
over the device's idle gaps (``stages.idle_us_per_block``)."""
from chipbench import stages


def read(ctx):
    if (ctx.trace is None or not hasattr(ctx.driver, "t1")
            or stages.us_per_block(ctx.spans, stages.HOST) is None):
        return None
    return stages.idle_us_per_block(ctx.trace, ctx.spans, ctx.driver,
                                    stages.HOST)
