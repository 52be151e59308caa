"""The engine's per-block stream stages as the program names its spans
(``repro.obs``, ``docs/observability.md``): their time per block from the
tracer's ring, and the device's idle time under them in a traced bulk
window."""
from typing import Iterable, List, Optional, Sequence

from chipbench import xtrace

#: host work per block: pack, pad + upload + dispatch, copy back, unpack
HOST = ("stream:flatten", "stream:upload", "stream:download",
        "stream:unflatten")
#: the host<->device part of it
TRANSFER = ("stream:upload", "stream:download")


def us_per_block(spans: Iterable, names: Sequence[str]) -> Optional[float]:
    """Microseconds per block in the stages ``names``: each stage's mean
    span duration, summed; None unless every stage has spans.  Means per
    stage, never a division by the window's block count: on a long
    window the ring has dropped its oldest spans."""
    tot = {n: [0.0, 0] for n in names}
    for s in spans:
        t = tot.get(s.name)
        if t is not None:
            t[0] += s.dur_s
            t[1] += 1
    if not all(n for _, n in tot.values()):
        return None
    return 1e6 * sum(d / n for d, n in tot.values())


def on_trace_clock(spans: Iterable, names: Sequence[str], driver,
                   window: xtrace.Interval) -> List[xtrace.Interval]:
    """The ring's spans named ``names`` as intervals on the trace's clock.

    The bulk driver reads ``perf_counter`` as ``t1`` just before the
    trace's ``window`` annotation closes: that pair of readings gives the
    offset between the two clocks, to within the microsecond or two
    between the read and the annotation's end.  The clocks tick at one
    rate (``perf_counter`` reads CLOCK_MONOTONIC, the profiler stamps
    CLOCK_REALTIME; the kernel slews both alike), so one anchor does."""
    shift = window[1] - driver.t1 * 1e9
    return [(s.t0 * 1e9 + shift, (s.t0 + s.dur_s) * 1e9 + shift)
            for s in spans if s.name in names]


def idle_us_per_block(trace: xtrace.Trace, spans: Iterable, driver,
                      names: Sequence[str]) -> Optional[float]:
    """Device idle microseconds per kernel call while the host was inside
    one of the stages ``names`` (the union of their spans), averaged over
    the active devices.  Read over the part of the window that the ring
    still holds, from its first such span to the window's end."""
    cover = xtrace.union(on_trace_clock(spans, names, driver, trace.window),
                         trace.window)
    devs = trace.active_devices()
    if not cover or not devs:
        return None
    part = (cover[0][0], trace.window[1])
    calls = sum(len(d.kernel_events(part)) for d in devs)
    if not calls:
        return None
    idle = sum(_overlap_ns(xtrace.gaps(d.busy_intervals(part), part), cover)
               for d in devs)
    return idle / 1e3 / calls


def _overlap_ns(a: Sequence[xtrace.Interval],
                b: Sequence[xtrace.Interval]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return tot
