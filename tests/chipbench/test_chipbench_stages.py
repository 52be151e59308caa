"""The engine's stage spans as the benchmark reads them: the program's
``stream:`` spans in the tracer's ring and in the profiler's trace of a
tiny traced bulk run on the CPU, and the ring mapped onto the trace's
clock; the three stage readers on a synthetic trace and on a recorded
chip trace; and the reduction of the recorded trace without stage spans,
as it was."""
import statistics

import pytest

from conftest import ROOT, run_tiny, tiny

DATA = ROOT / "tests" / "chipbench" / "data"
STAGES = ("stream:flatten", "stream:upload", "stream:wait",
          "stream:download", "stream:unflatten")
READERS = ("host_us_per_block.bulk", "transfer_us_per_block.bulk",
           "idle_in_stages_us_per_block.bulk")
K = "%cgra_exec.1 = s32[8192,128]{1,0} custom-call(s32[1,1] %a)"


def _ctx(trace=None, spans=()):
    from chipbench import harness
    return harness.Context(cell=None, driver=None, trace=trace,
                           spans=list(spans), before={}, after={},
                           peaks={"hbm_bytes_per_s": 819e9})


def _read(name, ctx):
    from chipbench import harness
    return harness.load_reader(name)(ctx)


def _span(name, dur_s, t0=0.0):
    from repro import obs
    return obs.Span(name=name, t0=t0, dur_s=dur_s, trace_id="t",
                    span_id="s")


# -- a tiny traced run on the CPU ---------------------------------------------

def _stage_events(path):
    """The program's ``stream:`` spans as the profiler recorded them:
    ``[(name, start_ns, end_ns)]`` of the host planes, by start."""
    from jax.profiler import ProfileData
    return sorted(((e.name, e.start_ns, e.end_ns)
                   for plane in ProfileData.from_file(str(path)).planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith("stream:")), key=lambda x: x[1])


def _check_mapping(ctx, events, tol_ns):
    """The ring's spans, mapped by the window's closing anchor, fall on
    the profiler's own events of the same stage (matched from the end:
    the ring may have dropped its oldest)."""
    from chipbench import stages
    for stage in STAGES:
        ring = sorted(stages.on_trace_clock(ctx.spans, [stage], ctx.driver,
                                            ctx.trace.window))
        prof = [(s, e) for n, s, e in events if n == stage][-len(ring):]
        assert len(prof) == len(ring), stage
        off = [abs(a[0] - b[0]) for a, b in zip(ring, prof)]
        assert statistics.median(off) < tol_ns, (stage, off)


def test_stage_spans_reach_the_ring_and_the_profiler(cpu_only, monkeypatch):
    from chipbench import harness, stages, xtrace
    got = {}
    real_load = xtrace.load

    def load(path):
        got["events"] = _stage_events(path)
        return real_load(path)

    class Context(harness.Context):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            got["ctx"] = self

    monkeypatch.setattr(xtrace, "load", load)
    monkeypatch.setattr(harness, "Context", Context)
    cell = tiny("hycube4x4-gemm.bulk")
    res = run_tiny(cell, trace=True, seconds=1.0)
    assert res["correct"] is True, res["checks"]
    blocks = res["attempted"] // int(cell.mix["chunk"])
    assert blocks >= 2
    ctx, events = got["ctx"], got["events"]
    ring = [s for s in ctx.spans if s.name.startswith("stream:")]
    for stage in STAGES:
        assert sum(s.name == stage for s in ring) == blocks, stage
        assert sum(n == stage for n, _, _ in events) == blocks, stage
    assert len({s.trace_id for s in ring}) == 1
    # every stage span lies inside one of the harness's stream-step spans
    steps = [(s, e) for n, s, e in ctx.trace.host if n == "stream-step"]
    for name, s, e in events:
        assert any(a <= s and e <= b for a, b in steps), name
    # both sinks give each stage the same duration, to 5 % or to the
    # few microseconds the annotation itself costs on a host CPU (the
    # tiny blocks copy back in about 50 us here)
    for stage in STAGES:
        r = statistics.median(s.dur_s for s in ring if s.name == stage)
        p = statistics.median((e - s) / 1e9 for n, s, e in events
                              if n == stage)
        assert p == pytest.approx(r, rel=0.05, abs=5e-6), stage
    _check_mapping(ctx, events, tol_ns=20e3)
    m = res["metrics"]
    assert m["host_us_per_block.bulk"]["value"] == pytest.approx(
        stages.us_per_block(ring, stages.HOST))
    assert m["transfer_us_per_block.bulk"]["value"] == pytest.approx(
        stages.us_per_block(ring, stages.TRANSFER))
    # no device plane on the CPU: no idle to attribute
    assert "idle_in_stages_us_per_block.bulk" not in m


# -- the readers on known intervals ---------------------------------------------

class _Driver:
    """The bulk driver's read of the ring's clock as the window closes."""
    def __init__(self, t1):
        self.t1 = t1


#: two blocks' stages in a 10-us window, in ns; kernels (1000, 4000) and
#: (5000, 8000) leave idle (0, 1000), (4000, 5000), (8000, 10000), of
#: which the host stages cover 1000 + 500 + 300 + 200 + 600 ns
STAGE_NS = [("stream:flatten", 0, 600), ("stream:upload", 600, 1200),
            ("stream:wait", 1200, 3900), ("stream:download", 4000, 4500),
            ("stream:unflatten", 4500, 4800), ("stream:flatten", 4800, 5200),
            ("stream:upload", 5200, 5400), ("stream:wait", 5400, 7900),
            ("stream:download", 8000, 8300),
            ("stream:unflatten", 8300, 8600)]


def _synthetic(stage_ns=STAGE_NS):
    """A trace of the window, the ring's spans on a clock that reads
    2.0 s at the window's start, and the driver's read of that clock as
    the window closes."""
    from chipbench import xtrace
    trace = xtrace.Trace((0.0, 10_000.0),
                         [xtrace.DeviceTrace("/device:TPU:0",
                                             [(K, 1000, 4000),
                                              (K, 5000, 8000)])],
                         [("stream-step", 0, 10_000)])
    spans = [_span(n, (e - s) * 1e-9, t0=2.0 + s * 1e-9)
             for n, s, e in stage_ns]
    return trace, spans, _Driver(2.0 + 10e-6)


def test_ring_spans_map_onto_the_trace_clock():
    from chipbench import stages
    trace, spans, driver = _synthetic()
    got = stages.on_trace_clock(spans, ["stream:upload"], driver,
                                trace.window)
    assert got == [pytest.approx((600, 1200)), pytest.approx((5200, 5400))]


def test_idle_under_stage_spans_on_known_intervals():
    trace, spans, driver = _synthetic()
    ctx = _ctx(trace, spans)
    ctx.driver = driver
    # 2,600 ns over 2 calls
    assert _read("idle_in_stages_us_per_block.bulk",
                 ctx) == pytest.approx(1.3)
    # the ring dropped the first block: read from the second's flatten,
    # 200 + 600 ns over the one call after it
    trace, spans, driver = _synthetic(STAGE_NS[5:])
    ctx = _ctx(trace, spans)
    ctx.driver = driver
    assert _read("idle_in_stages_us_per_block.bulk",
                 ctx) == pytest.approx(0.8)
    # the existing reduction does not see the program's spans
    assert trace.idle_by_host_span() == [["stream-step",
                                          pytest.approx(4e-6)]]


def test_host_and_transfer_readers_add_the_stage_means():
    spans = [_span("stream:flatten", 100e-6), _span("stream:flatten", 300e-6),
             _span("stream:upload", 50e-6), _span("stream:wait", 1e-3),
             _span("stream:download", 400e-6),
             _span("stream:download", 600e-6),
             _span("stream:unflatten", 80e-6), _span("queue", 5e-3)]
    assert _read("host_us_per_block.bulk",
                 _ctx(spans=spans)) == pytest.approx(200 + 50 + 500 + 80)
    assert _read("transfer_us_per_block.bulk",
                 _ctx(spans=spans)) == pytest.approx(50 + 500)


@pytest.mark.parametrize("reader", READERS)
def test_stage_readers_need_every_stage(reader):
    """A program without the stage spans (only ``stream:upload`` in the
    ring) reads as nothing, not as a partial sum."""
    trace, spans, driver = _synthetic()
    ctx = _ctx(trace, [s for s in spans if s.name == "stream:upload"])
    ctx.driver = driver
    assert _read(reader, ctx) is None


# -- the recorded chip traces -----------------------------------------------------

def test_recorded_trace_without_stage_spans_reads_as_before():
    """The recorded bulk window of one TPU v5e, taken before the program
    had stage spans: every existing reading is what it was."""
    from chipbench import xtrace
    t = xtrace.load(str(DATA / "bulk_window.xplane.pb"))
    assert t.window == (44635728.0, 78782927.0)
    assert t.busy_s() == pytest.approx(0.026708029, rel=1e-12)
    evs = t.kernel_events()
    assert len(evs) == 12
    assert sum(e.end - e.start for e in evs) == 26525256.0
    assert sorted({n for n, _, _ in t.host}) == ["drain", "stream-step"]
    assert len(t.host) == 25
    assert t.top_ops() == [
        ["custom-call %_traced.1", pytest.approx(0.026525256, rel=1e-12)],
        ["copy %copy.1", pytest.approx(9.6693e-05, rel=1e-12)],
        ["copy %copy", pytest.approx(8.5757e-05, rel=1e-12)],
        ["copy-start %copy-start", pytest.approx(7.8e-08, rel=1e-12)],
        ["copy-start %copy-start.2", pytest.approx(7.8e-08, rel=1e-12)],
        ["copy-start %copy-start.1", pytest.approx(7.3e-08, rel=1e-12)],
        ["copy-done %copy-done", pytest.approx(3.3e-08, rel=1e-12)],
        ["copy-done %copy-done.2", pytest.approx(3.3e-08, rel=1e-12)],
        ["copy-done %copy-done.1", pytest.approx(2.8e-08, rel=1e-12)]]
    assert t.idle_by_host_span() == [
        ["stream-step", pytest.approx(0.00743917, rel=1e-12)]]
    ctx = _ctx(t)
    want = {"cgra_exec_roofline": 0.46336973081808264,
            "kernel_us_per_block.bulk": 2210.438,
            "device_idle_pct.bulk": 21.78559360022473}
    for name, v in want.items():
        assert _read(name, ctx) == pytest.approx(v, rel=1e-12), name
    for name in READERS:
        assert _read(name, ctx) is None, name


def _recorded_stages():
    """A 13-block bulk window of hycube on one TPU v5e with the stage
    spans on: its trace, the profiler's own stage events, and the ring's
    stage spans with the driver's closing read, saved beside it."""
    import json

    from chipbench import xtrace
    path = DATA / "bulk_stages.xplane.pb"
    ring = json.loads((DATA / "bulk_stages.ring.json").read_text())
    ctx = _ctx(xtrace.load(str(path)),
               [_span(n, d, t0=t0) for n, t0, d in ring["spans"]])
    ctx.driver = _Driver(ring["t1"])
    return ctx, _stage_events(path)


def test_recorded_chip_window_with_stage_spans():
    ctx, events = _recorded_stages()
    assert len(ctx.trace.kernel_events()) == 13
    for stage in STAGES:
        ring = [s.dur_s for s in ctx.spans if s.name == stage]
        prof = [(e - s) / 1e9 for n, s, e in events if n == stage]
        assert len(ring) == len(prof) == 13, stage
        assert sum(prof) == pytest.approx(sum(ring), rel=0.05), stage
    steps = [(s, e) for n, s, e in ctx.trace.host if n == "stream-step"]
    for name, s, e in events:
        assert any(a <= s and e <= b for a, b in steps), name
    _check_mapping(ctx, events, tol_ns=5e3)
    assert ctx.trace.top_ops(1)[0][0] == "custom-call %cgra_exec.1"


def test_stage_readers_on_the_recorded_chip_window():
    from chipbench import stages
    ctx, events = _recorded_stages()
    want = {"host_us_per_block.bulk": 2242.4066923091546,
            "transfer_us_per_block.bulk": 1552.9335384660324,
            "idle_in_stages_us_per_block.bulk": 318.07807692307694}
    for name, v in want.items():
        assert _read(name, ctx) == pytest.approx(v, rel=1e-9), name
    # the same idle read from the profiler's own annotations: identical
    # clocks, so the events stand in for the ring unmapped
    own = [_span(n, (e - s) / 1e9, t0=s / 1e9) for n, s, e in events]
    own_idle = stages.idle_us_per_block(
        ctx.trace, own, _Driver(ctx.trace.window[1] / 1e9), stages.HOST)
    assert want["idle_in_stages_us_per_block.bulk"] == pytest.approx(
        own_idle, rel=0.01)
