"""Flight-recorder telemetry: tracing, the metrics registry, and the
observability surfaces threaded through the serving stack.

Contract under test:

  * context-manager spans nest (child inherits trace id, parents under
    the enclosing span) per-thread — two threads never parent under each
    other's open spans,
  * the buffer is a bounded flight recorder: capacity holds, eviction is
    oldest-first, and ``stats()`` counts every recorded/dropped span,
  * a DISABLED tracer is a strict no-op: ``span()`` hands back one
    shared singleton (no ``Span`` allocation, no clock read, nothing
    recorded) and traced producers skip all capture work,
  * the Chrome-trace export is schema-valid, carries one metadata event
    per track, and ``ingest`` re-bases foreign-process spans onto the
    local timebase with one pid lane per worker prefix,
  * the registry is get-or-create by dotted name (kind mismatch is a
    ``TypeError``), namespaces are unique per producer instance and
    ``drop()`` removes them, sources sample at snapshot time and a dead
    source cannot poison the view,
  * ``ServiceMetrics`` keeps its historical ``snapshot()`` shape on top
    of registry instruments, attributes batch errors per tenant, and
    survives empty/reject-only/stream-only windows,
  * ``merge_latency`` computes real cluster percentiles from shipped
    sample windows (falling back to max-of-workers without them),
  * a traced ``Service.submit`` yields a complete span tree whose
    per-stage breakdown accounts for the reported request latency.
"""
import json
import os
import sys
import threading

import numpy as np
import pytest

from repro import obs, ual
from repro.obs import trace as trace_mod
from repro.ual.cluster.service import merge_latency
from repro.ual.service.metrics import ServiceMetrics


@pytest.fixture
def fresh_obs():
    """Swap in a fresh enabled tracer + empty registry; restore after."""
    tr = obs.Tracer(enabled=True)
    reg = obs.MetricsRegistry()
    prev_tr = obs.set_tracer(tr)
    prev_reg = obs.set_registry(reg)
    yield tr, reg
    obs.set_tracer(prev_tr)
    obs.set_registry(prev_reg)


def _program(kname="gemm"):
    return ual.Program.from_kernel(kname)


def _target(**knobs):
    return ual.Target.from_name("hycube", rows=4, cols=4, **knobs)


# ---------------------------------------------------------------------------
# tracer core: nesting, ids, ring buffer
# ---------------------------------------------------------------------------

def test_nested_spans_share_trace_and_parent():
    tr = obs.Tracer(enabled=True)
    with tr.span("outer") as outer:
        with tr.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
    spans = {s.name: s for s in tr.spans()}
    assert set(spans) == {"outer", "inner"}
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["outer"].parent_id is None
    # inner closed first, so it records first but ends inside the outer
    assert spans["inner"].t0 >= spans["outer"].t0
    assert spans["inner"].span_id != spans["outer"].span_id


def test_span_nesting_is_per_thread():
    tr = obs.Tracer(enabled=True)
    entered = threading.Event()
    release = threading.Event()

    def other():
        with tr.span("thread-b"):
            entered.set()
            release.wait(timeout=30)

    t = threading.Thread(target=other)
    with tr.span("thread-a") as a:
        t.start()
        assert entered.wait(timeout=30)
        # thread-b's open span must not become a child of thread-a's
        release.set()
        t.join(timeout=30)
    spans = {s.name: s for s in tr.spans()}
    assert spans["thread-b"].parent_id is None
    assert spans["thread-b"].trace_id != a.trace_id
    assert spans["thread-a"].track != spans["thread-b"].track


def test_record_retrospective_spans_and_explicit_parentage():
    tr = obs.Tracer(enabled=True)
    root = tr.record("root", 1.0, 2.0, trace="t1")
    child = tr.record("child", 1.25, 1.5, trace="t1", parent=root,
                      args={"k": "v"})
    spans = {s.span_id: s for s in tr.spans()}
    assert spans[child].parent_id == root
    assert spans[child].trace_id == "t1"
    assert spans[child].args == {"k": "v"}
    assert spans[root].dur_s == pytest.approx(1.0)
    # negative intervals clamp rather than exporting negative durations
    weird = tr.record("clock-skew", 5.0, 4.0, trace="t1")
    assert spans_by_id(tr)[weird].dur_s == 0.0


def spans_by_id(tr):
    return {s.span_id: s for s in tr.spans()}


def test_ring_buffer_bounds_memory_and_counts_drops():
    tr = obs.Tracer(enabled=True, capacity=8)
    for i in range(20):
        tr.record(f"s{i}", float(i), float(i) + 0.5, trace="t")
    st = tr.stats()
    assert st["buffered"] == 8
    assert st["recorded"] == 20
    assert st["dropped"] == 12
    # oldest-first snapshot of the survivors: the last 8 recorded
    assert [s.name for s in tr.spans()] == [f"s{i}" for i in range(12, 20)]
    tr.clear()
    assert tr.stats() == {"enabled": True, "capacity": 8, "buffered": 0,
                          "recorded": 0, "dropped": 0}


def test_drain_empties_the_buffer_exactly_once():
    tr = obs.Tracer(enabled=True)
    tr.record("a", 0.0, 1.0, trace="t")
    first = tr.drain()
    assert [s.name for s in first] == ["a"]
    assert tr.drain() == []
    assert tr.spans() == []


# ---------------------------------------------------------------------------
# disabled tracer: strict no-op
# ---------------------------------------------------------------------------

def test_disabled_span_is_shared_singleton_with_no_capture(monkeypatch):
    tr = obs.Tracer(enabled=False)
    allocs = []
    monkeypatch.setattr(trace_mod, "Span",
                        lambda *a, **k: allocs.append(1))
    s1 = tr.span("x", args={"big": list(range(100))})
    s2 = tr.span("y")
    assert s1 is s2                       # the shared null singleton
    with s1 as s:
        s.set(ignored=True)
    assert allocs == []                   # no Span ever constructed
    assert tr.spans() == [] and tr.stats()["recorded"] == 0


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs its events."""
    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


@pytest.fixture
def fake_profiler(monkeypatch):
    import types
    mod = types.ModuleType("jax.profiler")
    mod.TraceAnnotation = _FakeAnnotation
    monkeypatch.setitem(sys.modules, "jax.profiler", mod)
    _FakeAnnotation.log = []
    return _FakeAnnotation.log


def test_enabled_span_annotates_the_profiler_while_open(fake_profiler):
    tr = obs.Tracer(enabled=True)
    with tr.span("stream:outer"):
        with tr.span("stream:inner"):
            assert fake_profiler == [("enter", "stream:outer"),
                                     ("enter", "stream:inner")]
    assert fake_profiler[2:] == [("exit", "stream:inner"),
                                 ("exit", "stream:outer")]
    assert [s.name for s in tr.spans()] == ["stream:inner", "stream:outer"]


def test_disabled_and_retrospective_spans_annotate_nothing(fake_profiler):
    tr = obs.Tracer(enabled=False)
    with tr.span("off"):
        pass
    tr.enable()
    tr.record("after-the-fact", 1.0, 2.0)
    tr.record_tree(tr.new_trace_id(), (("request", 1.0, 2.0, "s", None),))
    assert fake_profiler == []
    assert len(tr.spans()) == 2


def test_span_without_jax_loaded_reaches_the_ring(monkeypatch):
    monkeypatch.delitem(sys.modules, "jax.profiler", raising=False)
    tr = obs.Tracer(enabled=True)
    with tr.span("ring-only"):
        pass
    assert [s.name for s in tr.spans()] == ["ring-only"]


def test_spans_land_in_the_jax_profiler_trace(tmp_path):
    """Inside a profiler session an enabled span is a host event of the
    trace, on the profiler's clock, with the ring's duration (compared
    as medians: a thread switch between the annotation's stamp and the
    ring's read lengthens one side of a single span)."""
    import glob
    import statistics

    import jax
    from jax.profiler import ProfileData
    tr = obs.Tracer(enabled=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(7):
            with tr.span("stream:probe"):
                sum(range(20000))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    got = [(e.end_ns - e.start_ns) / 1e9
           for plane in ProfileData.from_file(path).planes
           if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name == "stream:probe"]
    ring = [s.dur_s for s in tr.spans()]
    assert len(got) == len(ring) == 7
    assert statistics.median(got) == pytest.approx(
        statistics.median(ring), rel=0.05, abs=5e-6)


def test_obs_spans_never_import_jax():
    """``repro.obs`` annotates the profiler only when someone else has
    loaded JAX; its own spans never load it (a fresh process)."""
    import subprocess
    from pathlib import Path
    code = ("import sys\n"
            "from repro import obs\n"
            "tr = obs.Tracer(enabled=True)\n"
            "with tr.span('stream:x'):\n"
            "    pass\n"
            "print('jax' in sys.modules, len(tr.spans()))\n")
    src = Path(__file__).resolve().parents[1] / "src"
    out = subprocess.run([sys.executable, "-c", code],
                         env={**os.environ, "PYTHONPATH": str(src)},
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.strip()
    assert out == "False 1"


def test_disabled_service_attaches_no_trace_info(fresh_obs):
    tr, _reg = fresh_obs
    tr.disable()
    program, target = _program(), _target()
    mem = program.random_inputs(np.random.default_rng(0))
    with ual.Service(max_batch=4, max_wait_ms=2) as svc:
        fut = svc.submit(program, target, mem)
        fut.result(timeout=300)
    assert "trace" not in fut.info
    assert tr.spans() == []


# ---------------------------------------------------------------------------
# export: chrome schema, tracks, cross-process ingest
# ---------------------------------------------------------------------------

def test_export_chrome_is_schema_valid_and_loadable(tmp_path):
    tr = obs.Tracer(enabled=True)
    with tr.span("outer", cat="test", args={"n": 3}):
        with tr.span("inner"):
            pass
    out = tr.export_chrome(tmp_path / "t.json")
    doc = json.loads(out.read_text())
    assert obs.validate_chrome(doc) == []
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {e["name"] for e in xs} == {"outer", "inner"}
    assert {e["name"] for e in metas} == {"process_name", "thread_name"}
    outer = next(e for e in xs if e["name"] == "outer")
    inner = next(e for e in xs if e["name"] == "inner")
    assert inner["args"]["parent_id"] == outer["args"]["span_id"]
    assert outer["args"]["n"] == 3
    assert all(isinstance(e["ts"], (int, float)) and e["ts"] >= 0
               for e in xs)


def test_validate_chrome_flags_malformed_docs():
    assert obs.validate_chrome({}) == ["traceEvents missing or not a list"]
    bad = {"traceEvents": [{"ph": "Q"}, {"ph": "X", "name": "a"},
                           "not-an-object"]}
    problems = obs.validate_chrome(bad)
    assert any("unexpected ph" in p for p in problems)
    assert any("missing" in p for p in problems)
    assert any("not an object" in p for p in problems)


def test_ingest_rebases_foreign_epoch_and_prefixes_tracks():
    local = obs.Tracer(enabled=True)
    foreign = obs.Tracer(enabled=True)
    foreign.epoch = local.epoch + 5.0     # foreign clock started 5s "later"
    foreign.record("remote-span", 100.0, 101.0, trace="t", track="engine-0")
    n = local.ingest(foreign.drain(), epoch=foreign.epoch,
                     track_prefix="worker3")
    assert n == 1
    got = local.spans()[0]
    assert got.t0 == pytest.approx(105.0)
    assert got.track == "worker3/engine-0"
    # the prefixed track becomes its own pid lane in the chrome doc
    with local.span("local-span"):
        pass
    doc = local.to_chrome()
    pids = {e["args"]["name"]: e["pid"]
            for e in doc["traceEvents"]
            if e["ph"] == "M" and e["name"] == "process_name"}
    assert set(pids) == {"worker3", "proc"}
    assert pids["worker3"] != pids["proc"]


def test_tree_renders_one_request_hierarchy():
    tr = obs.Tracer(enabled=True)
    root = tr.record("request", 0.0, 1.0, trace="tX")
    tr.record("queue", 0.0, 0.4, trace="tX", parent=root)
    tr.record("exec", 0.4, 0.9, trace="tX", parent=root)
    roots = tr.tree("tX")
    assert len(roots) == 1 and roots[0]["name"] == "request"
    assert [c["name"] for c in roots[0]["children"]] == ["queue", "exec"]
    text = obs.Tracer.render_tree(roots)
    assert "request" in text and "  queue" in text


# ---------------------------------------------------------------------------
# metrics: instruments, registry, namespaces, sources
# ---------------------------------------------------------------------------

def test_percentile_nearest_rank():
    assert obs.percentile([], 99) is None
    assert obs.percentile([7.0], 50) == 7.0
    xs = list(range(1, 101))              # 1..100
    assert obs.percentile(xs, 0) == 1
    assert obs.percentile(xs, 50) == 51   # nearest-rank on n-1 intervals
    assert obs.percentile(xs, 100) == 100


def test_registry_get_or_create_and_kind_mismatch():
    reg = obs.MetricsRegistry()
    c = reg.counter("a.b")
    assert reg.counter("a.b") is c
    with pytest.raises(TypeError):
        reg.gauge("a.b")
    h = reg.histogram("a.h", window=4)
    for v in (1, 2, 3, 4, 5):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 5 and snap["window"] == 4
    assert snap["mean"] == pytest.approx(3.0)   # lifetime mean, not window


def test_namespace_uniqueness_and_drop():
    reg = obs.MetricsRegistry()
    a = reg.namespace("service")
    b = reg.namespace("service")
    assert a.prefix == "service" and b.prefix == "service#1"
    a.counter("completed").inc(3)
    b.counter("completed").inc(5)
    assert reg.get("service.completed").value == 3
    assert reg.get("service#1.completed").value == 5
    a.drop()
    assert reg.get("service.completed") is None
    assert reg.get("service#1.completed").value == 5


def test_snapshot_is_json_serializable_and_guards_dead_sources():
    reg = obs.MetricsRegistry()
    reg.counter("n").inc(2)
    reg.gauge("g", fn=lambda: 1.5)
    reg.register_source("ok", lambda: {"x": 1})
    reg.register_source("dead", lambda: 1 / 0)
    with pytest.raises(ValueError):
        reg.register_source("ok", lambda: {})
    reg.register_source("ok", lambda: {"x": 2}, replace=True)
    snap = reg.snapshot()
    json.dumps(snap)                       # the whole view must serialize
    assert snap["metrics"]["n"] == {"type": "counter", "value": 2}
    assert snap["metrics"]["g"]["value"] == 1.5
    assert snap["sources"]["ok"] == {"x": 2}
    assert "ZeroDivisionError" in snap["sources"]["dead"]["error"]


def test_process_registry_carries_mapping_cache_source():
    # the default cache registers itself into the registry that was
    # current at its first creation — the process-wide one
    program, target = _program(), _target()
    ual.compile(program, target)           # touches the default cache
    snap = obs.registry().snapshot()
    assert "mapping_cache" in snap["sources"]
    assert isinstance(snap["sources"]["mapping_cache"], dict)


# ---------------------------------------------------------------------------
# ServiceMetrics: historical shape on registry instruments
# ---------------------------------------------------------------------------

def test_service_metrics_empty_snapshot_shape():
    m = ServiceMetrics(registry=obs.MetricsRegistry())
    snap = m.snapshot(queue_depth=0)
    assert snap["completed"] == 0 and snap["rejected"] == 0
    assert snap["p50_ms"] is None and snap["p99_ms"] is None
    assert snap["mean_batch"] is None and snap["max_batch"] is None
    assert snap["stream"]["spans"] == 0
    assert snap["stream"]["overlap_frac"] is None


def test_service_metrics_reject_only_and_stream_only():
    m = ServiceMetrics(registry=obs.MetricsRegistry())
    m.record_reject("t0", "queue-full")
    m.record_reject("t0", "queue-full")
    m.record_reject("t1", "deadline-exceeded")
    snap = m.snapshot()
    assert snap["rejects"] == {"queue-full": 2, "deadline-exceeded": 1}
    assert snap["tenants"]["t0"] == {"completed": 0, "rejected": 2,
                                     "errors": 0}
    m2 = ServiceMetrics(registry=obs.MetricsRegistry())
    m2.record_stream_span(chunks=3, samples=96, wall_s=0.5, overlap=0.25)
    s2 = m2.snapshot()
    assert s2["completed"] == 0
    assert s2["stream"] == {"spans": 1, "chunks": 3, "samples": 96,
                            "overlap_frac": 0.25, "samples_per_s": 192.0}


def test_record_error_attributes_per_tenant():
    m = ServiceMetrics(registry=obs.MetricsRegistry())
    m.record_error(["a", "a", "b"])
    assert m.errors == 3
    snap = m.snapshot()
    assert snap["tenants"]["a"]["errors"] == 2
    assert snap["tenants"]["b"]["errors"] == 1
    assert snap["errors"] == 3


def test_service_metrics_registers_and_closes_namespace():
    reg = obs.MetricsRegistry()
    m1 = ServiceMetrics(registry=reg)
    m2 = ServiceMetrics(registry=reg)
    assert m1.namespace == "service" and m2.namespace == "service#1"
    m1.record_completed("t", 0.010)
    assert reg.get("service.completed").value == 1
    m1.close()
    assert reg.get("service.completed") is None
    assert reg.get("service#1.completed") is not None
    # instruments stay usable after close — snapshot() still reads them
    assert m1.snapshot()["completed"] == 1


# ---------------------------------------------------------------------------
# cluster percentile merge
# ---------------------------------------------------------------------------

def test_merge_latency_computes_real_percentiles_from_windows():
    snaps = {
        0: {"p50_ms": 2.0, "p99_ms": 4.0,
            "latency_window_ms": [1.0] * 90},
        1: {"p50_ms": 50.0, "p99_ms": 100.0,
            "latency_window_ms": [100.0] * 10},
    }
    got = merge_latency(snaps)
    # 90 fast samples + 10 slow: merged p50 is 1ms (NOT the mid-value a
    # max/mean-of-percentiles would suggest), p99 lands in the slow tail
    assert got["p50_ms"] == 1.0
    assert got["p99_ms"] == 100.0
    assert got["worst_worker_p99_ms"] == 100.0
    assert got["latency_samples_merged"] == 100
    # windows are popped so per-worker views don't ship megabytes
    assert "latency_window_ms" not in snaps[0]


def test_merge_latency_falls_back_without_windows():
    snaps = {0: {"p50_ms": 2.0, "p99_ms": 4.0},
             1: {"p50_ms": 3.0, "p99_ms": 9.0}}
    got = merge_latency(snaps)
    assert got == {"p50_ms": 3.0, "p99_ms": 9.0,
                   "worst_worker_p99_ms": 9.0,
                   "latency_samples_merged": 0}
    assert merge_latency({})["p99_ms"] is None


# ---------------------------------------------------------------------------
# end to end: a traced request through the service
# ---------------------------------------------------------------------------

def test_traced_request_breakdown_accounts_for_latency(fresh_obs):
    tr, _reg = fresh_obs
    program, target = _program(), _target()
    rng = np.random.default_rng(1)
    mems = [program.random_inputs(rng) for _ in range(6)]
    with ual.Service(max_batch=4, max_wait_ms=2) as svc:
        svc.submit(program, target, mems[0]).result(timeout=300)  # warm
        futs = [svc.submit(program, target, m, tenant="traced")
                for m in mems[1:]]
        for f in futs:
            f.result(timeout=300)
    for f in futs:
        trace = f.info["trace"]
        assert trace["trace_id"]
        parts = (trace["queue_ms"] + trace["coalesce_ms"]
                 + trace["exec_ms"])
        lat = f.info["latency_ms"]
        assert parts == pytest.approx(lat, rel=0.10)
        assert trace["resolve_ms"] >= 0
        names = {s.name for s in tr.spans(trace["trace_id"])}
        assert {"request", "queue", "coalesce", "exec",
                "resolve"} <= names
    # distinct requests get distinct trace ids
    ids = {f.info["trace"]["trace_id"] for f in futs}
    assert len(ids) == len(futs)
    # the whole recording exports as a valid chrome doc
    assert obs.validate_chrome(tr.to_chrome()) == []


def test_compile_emits_pass_spans(fresh_obs):
    tr, _reg = fresh_obs
    program, target = _program(), _target()
    exe = ual.compile(program, target)
    assert exe.success
    names = [s.name for s in tr.spans()]
    assert any(n.startswith("compile:") for n in names)
    assert sum(1 for n in names if n.startswith("pass:")) >= 3
    root = next(s for s in tr.spans() if s.name.startswith("compile:"))
    passes = [s for s in tr.spans() if s.name.startswith("pass:")]
    assert all(p.trace_id == root.trace_id for p in passes)


def test_bench_timer_records_span(fresh_obs):
    tr, _reg = fresh_obs
    from benchmarks.common import Timer
    with Timer("phase"):
        pass
    assert [s.name for s in tr.spans()] == ["bench:phase"]
    assert tr.spans()[0].cat == "bench"


def test_record_tree_expands_lazily_with_stable_ids():
    tr = obs.Tracer(enabled=True)
    tid = tr.new_trace_id()
    tr.record_tree(tid, (
        ("request", 1.0, 2.0, "service", {"tenant": "a"}),
        ("queue", 1.0, 1.2, "service", None),
        ("exec", 1.2, 2.0, "engine", None),
    ))
    # one ring entry, but stats count the spans it carries
    assert tr.stats()["recorded"] == 3
    assert tr.stats()["buffered"] == 3
    first = tr.spans()
    assert [s.name for s in first] == ["request", "queue", "exec"]
    root = first[0]
    assert root.parent_id is None and root.args == {"tenant": "a"}
    assert all(s.parent_id == root.span_id and s.trace_id == tid
               for s in first[1:])
    # expansion is cached: a second read returns the same span ids
    assert [s.span_id for s in tr.spans()] == [s.span_id for s in first]


def test_record_tree_drops_count_span_weight():
    tr = obs.Tracer(enabled=True, capacity=2)
    for _ in range(3):
        tr.record_tree(tr.new_trace_id(), (
            ("request", 0.0, 1.0, "service", None),
            ("exec", 0.0, 1.0, "engine", None),
        ))
    stats = tr.stats()
    assert stats["recorded"] == 6
    assert stats["buffered"] == 4     # 2 entries x 2 spans survive
    assert stats["dropped"] == 2      # the evicted entry carried 2 spans
