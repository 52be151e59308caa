"""Share of the HBM roofline the ``cgra_exec`` kernel reaches: the least
time its calls could take at the chip's HBM peak (each call reads and
writes its whole int32 block, ``shapes.block_bytes``, with the block's
shape read from the call's HLO) over the kernel's device time.  The
compute bound is not given (``peaks``), so this is the memory bound."""
from chipbench import shapes


def read(ctx):
    evs = ctx.trace.kernel_events() if ctx.trace is not None else []
    bw = ctx.peaks.get("hbm_bytes_per_s")
    if not evs or not bw:
        return None
    least = sum(shapes.hbm_bound_s(e.words, e.lanes, bw) for e in evs)
    spent = sum(e.end - e.start for e in evs) / 1e9
    return 100.0 * least / spent
