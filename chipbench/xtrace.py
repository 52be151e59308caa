"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation that ran, on the same clock as the host planes.
Busy time is the union of those intervals inside the harness's
``window`` annotation; the ``cgra_exec`` kernel is the one Pallas
(Mosaic) custom call of the program, so its events are the ops whose HLO
text is a ``custom-call``.  Idle gaps are attributed to the harness's
own host annotations (``HOST_SPANS``) that cover them.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

WINDOW = "window"
#: the harness's host annotations that idle gaps are attributed to
HOST_SPANS = ("generate", "submit", "stream-step", "drain")
KERNEL_MARK = " custom-call("
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]          # (start_ns, end_ns)
_OP_KIND = re.compile(r"\s([a-z][\w-]*)\(")
#: the kernel's result: the (M, lanes) int32 scratchpad block
_BLOCK = re.compile(r"= s32\[(\d+),(\d+)\]")


class KernelEvent(NamedTuple):
    start: float        # ns
    end: float          # ns
    words: int          # M, scratchpad words per image
    lanes: int          # images in the block


@dataclass
class DeviceTrace:
    name: str
    ops: List[Tuple[str, float, float]] = field(default_factory=list)

    def busy_intervals(self, window: Interval) -> List[Interval]:
        return union([(s, e) for _, s, e in self.ops], window)

    def busy_ns(self, window: Interval) -> float:
        return sum(e - s for s, e in self.busy_intervals(window))

    def kernel_events(self, window: Interval) -> List[KernelEvent]:
        lo, hi = window
        out = []
        for n, s, e in self.ops:
            if KERNEL_MARK in n and s >= lo and e <= hi:
                m = _BLOCK.search(n)
                if m:
                    out.append(KernelEvent(s, e, int(m.group(1)),
                                           int(m.group(2))))
        return out


@dataclass
class Trace:
    window: Optional[Interval]
    devices: List[DeviceTrace]
    host: List[Tuple[str, float, float]]

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) / 1e9

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices that ran anything."""
        devs = self.active_devices()
        return (sum(d.busy_ns(self.window) for d in devs) / len(devs) / 1e9
                if devs else 0.0)

    def active_devices(self) -> List[DeviceTrace]:
        if self.window is None:
            return []
        return [d for d in self.devices if d.busy_intervals(self.window)]

    def idle_pct(self) -> Optional[float]:
        """Idle share of the window, averaged over the active devices."""
        if not self.active_devices():
            return None
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    def kernel_events(self) -> List[KernelEvent]:
        return [ev for d in self.active_devices()
                for ev in d.kernel_events(self.window)]

    def top_ops(self, n: int = 10) -> List[List[object]]:
        """Device ops by total time inside the window, averaged over the
        active devices: ``[[name, seconds], ...]``."""
        devs = self.active_devices()
        lo, hi = self.window
        tot: Dict[str, float] = {}
        for d in devs:
            for name, s, e in d.ops:
                if s >= lo and e <= hi:
                    key = op_label(name)
                    tot[key] = tot.get(key, 0.0) + (e - s)
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / len(devs) / 1e9] for k, v in ranked]

    def idle_by_host_span(self, n: int = 10) -> List[List[object]]:
        """Idle seconds of the window by what the harness's host thread
        was doing (the annotation covering most of each gap; ``none``
        where no annotation covers it), averaged over active devices."""
        devs = self.active_devices()
        spans = sorted((s, e, name) for name, s, e in self.host
                       if name in HOST_SPANS)
        tot: Dict[str, float] = {}
        for d in devs:
            for gs, ge in gaps(d.busy_intervals(self.window), self.window):
                cover: Dict[str, float] = {}
                for s, e, name in spans:
                    if e <= gs:
                        continue
                    if s >= ge:
                        break
                    cover[name] = cover.get(name, 0.0) + min(e, ge) - max(s, gs)
                label = max(cover, key=cover.get) if cover else "none"
                tot[label] = tot.get(label, 0.0) + (ge - gs)
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / len(devs) / 1e9] for k, v in ranked]


def op_label(hlo_text: str) -> str:
    """``%name = type op(...)`` -> ``op %name``: short and stable."""
    lhs, _, rhs = hlo_text.partition(" = ")
    m = _OP_KIND.search(" " + rhs)
    return f"{m.group(1)} {lhs}" if m else lhs


def union(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    """Merged intervals clipped to ``window``."""
    lo, hi = window
    clipped = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                     if e > lo and s < hi)
    out: List[Interval] = []
    for s, e in clipped:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """The complement of merged ``busy`` inside ``window``."""
    lo, hi = window
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def find_xplane(logdir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: List[DeviceTrace] = []
    host: List[Tuple[str, float, float]] = []
    wanted = set(HOST_SPANS) | {WINDOW}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = DeviceTrace(plane.name)
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops.extend((e.name, e.start_ns, e.end_ns)
                                   for e in line.events)
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events if e.name in wanted)
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[-1]))
    wins = [(s, e) for name, s, e in host if name == WINDOW]
    window = max(wins, key=lambda w: w[1] - w[0]) if wins else None
    return Trace(window, devices, [h for h in host if h[0] != WINDOW])
