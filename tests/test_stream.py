"""Streaming elastic execution: the double-buffered pipelined path.

The contract under test:

  * streamed chunks are bit-exact vs the discrete ``run_batch`` path and
    the unpadded DFG-interpreter oracle — including a ragged final chunk
    and the chunk == 1 degenerate — and ``run_stream`` matches the oracle
    on every published (kernel, fabric) deployment,
  * streaming on a warm engine adds ZERO traces, and cold streaming
    traffic stays O(#buckets) (monkeypatch-counted on the shared
    ``make_cgra_call`` constructor, PR-5 pattern),
  * the stream summary schema: ``stream_chunks``, ``overlap_frac`` in
    [0, 1], ``throughput_sps``, mirrored into ``last_info`` and the
    engine's ``streams``/``stream_chunks`` counters,
  * ``Service.submit_stream`` pipelines one tenant's chunked request
    bit-exact while discrete tenants' micro-batches interleave, surfaces
    aggregate stream stats under ``stats()["stream"]``, and keeps the
    admission verdicts (all-or-nothing ``queue-full``, ``shutdown``),
  * the satellite fast paths: a batch that IS a bucket size skips the
    pad/copy staging entirely, and ``validate``'s multi-backend sweep
    flattens its test vectors exactly once,
  * packed blocks: the stream carries only the declared arrays' words,
    bit-exact against the full-width run with a ragged last chunk; a
    warmed backend traces one packed shape per bucket and nothing after;
    each benchmark deployment moves 2 x L words an image.
"""
import numpy as np
import pytest

from deployments import DEPLOYMENTS, FABRICS, program as deployed
from repro import ual
from repro.core.dfg import interpret
from repro.ual.engine import CompiledKernelCache

N_ITERS = 6


@pytest.fixture(scope="module")
def compiled():
    program = ual.Program.from_kernel("gemm", bank_words=64)
    target = ual.Target.from_name("hycube", rows=4, cols=4,
                                  backend="pallas")
    exe = ual.compile(program, target)
    assert exe.success
    return program, exe


def _mems(program, B, seed=0):
    rng = np.random.default_rng(seed)
    return [program.random_inputs(rng) for _ in range(B)]


def _drain(gen):
    """Consume a streaming generator; returns (chunks, summary)."""
    chunks = []
    while True:
        try:
            chunks.append(next(gen))
        except StopIteration as stop:
            return chunks, dict(stop.value or {})


# ---------------------------------------------------------------------------
# parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,chunk", [(37, 8), (32, 32), (9, 1), (70, 32)])
def test_stream_bitexact_vs_run_batch_and_oracle(compiled, B, chunk):
    """Every chunking — ragged tail (37 @ 8), exact bucket (32 @ 32),
    chunk == 1, beyond-ladder B — matches the discrete path and the
    oracle bit for bit, in order."""
    program, exe = compiled
    mems = _mems(program, B, seed=B)
    ref = exe.run_batch(mems, n_iters=N_ITERS)
    chunks, summary = _drain(exe.run_stream(mems, n_iters=N_ITERS,
                                            chunk=chunk))
    flat = [d for c in chunks for d in c]
    assert len(flat) == B
    assert sum(len(c) for c in chunks) == B
    for m, got, want in zip(mems, flat, ref):
        oracle = interpret(program.dfg, m, N_ITERS)
        for name in program.outputs:
            np.testing.assert_array_equal(got[name], want[name])
            np.testing.assert_array_equal(got[name], oracle[name])
    assert summary["stream_chunks"] == len(chunks)


@pytest.mark.parametrize("kernel_name,fabric", DEPLOYMENTS)
def test_run_stream_matches_oracle_every_deployment(kernel_name, fabric):
    """``Executable.run_stream``, the path the chip benchmark times, on
    every published deployment at the default scratchpad: three seeded
    images at chunk=2 through the pallas engine, every output array
    bit-exact against the DFG interpreter."""
    program = deployed(kernel_name)
    exe = ual.compile(program, ual.Target.from_name(
        fabric, backend="pallas", **FABRICS[fabric]))
    assert exe.success
    mems = _mems(program, 3, seed=31)
    chunks, summary = _drain(exe.run_stream(mems, chunk=2))
    assert [len(c) for c in chunks] == [2, 1]
    assert summary["stream_chunks"] == 2
    for m, got in zip(mems, (d for c in chunks for d in c)):
        want = interpret(program.dfg, m, program.n_iters)
        for name in program.outputs:
            np.testing.assert_array_equal(got[name], want[name])


def test_run_batch_stream_flag_collects_and_reports(compiled):
    """``run_batch(stream=True)`` returns the flat result list and lands
    the stream summary in ``last_info``."""
    program, exe = compiled
    mems = _mems(program, 20, seed=3)
    ref = exe.run_batch(mems, n_iters=N_ITERS)
    outs = exe.run_batch(mems, n_iters=N_ITERS, stream=True, chunk=8)
    for got, want in zip(outs, ref):
        for name in program.outputs:
            np.testing.assert_array_equal(got[name], want[name])
    info = exe.last_info
    assert info["stream"] is True
    assert info["batch"] == 20
    assert info["stream_chunks"] == 3
    assert 0.0 <= info["overlap_frac"] <= 1.0
    assert info["throughput_sps"] > 0


def test_stream_chunked_sync_fallback_on_sim(compiled):
    """Backends without an async device path fall back to chunked
    synchronous delivery — same results, honest overlap_frac == 0."""
    program, exe = compiled
    mems = _mems(program, 5, seed=4)
    ref = exe.run_batch(mems, n_iters=N_ITERS, backend="sim")
    chunks, summary = _drain(exe.run_stream(mems, n_iters=N_ITERS,
                                            backend="sim", chunk=2))
    flat = [d for c in chunks for d in c]
    for got, want in zip(flat, ref):
        for name in program.outputs:
            np.testing.assert_array_equal(got[name], want[name])
    assert summary["streamed"] == "chunked-sync"
    assert summary["stream_chunks"] == 3
    assert summary["overlap_frac"] == 0.0


# ---------------------------------------------------------------------------
# trace economy
# ---------------------------------------------------------------------------

def test_warm_engine_streams_with_zero_new_traces(compiled):
    """Streaming rides the same bucket-ladder traces as ``run``: after a
    warmup, a whole streamed sweep (ragged tail included) adds none."""
    program, exe = compiled
    cache = CompiledKernelCache()
    eng = cache.engine_for(exe.lowered)
    eng.warmup(program.layout.total_words)
    before = eng.traces
    flats = program.flatten_batch(_mems(program, 37, seed=9))
    rows, summary = [], None
    gen = eng.run_stream(flats, N_ITERS, chunk=8)
    while True:
        try:
            out, _cinfo = next(gen)
        except StopIteration as stop:
            summary = dict(stop.value or {})
            break
        rows.append(out)
    assert eng.traces == before
    assert summary["traced"] == 0
    assert sum(len(r) for r in rows) == 37


def test_cold_stream_traces_bounded_by_ladder(compiled, monkeypatch):
    """Cold streaming traffic traces at most once per ladder bucket —
    proved by counting ``pallas_call`` constructions (PR-5 pattern)."""
    import repro.ual.engine as engine_mod

    program, exe = compiled
    builds = []
    real = engine_mod.make_cgra_call
    monkeypatch.setattr(engine_mod, "make_cgra_call",
                        lambda *a, **k: builds.append(1) or real(*a, **k))
    cache = CompiledKernelCache(buckets=(1, 8))
    flats = program.flatten_batch(_mems(program, 8, seed=10))
    for B, chunk in ((7, 8), (8, 4), (3, 1), (8, 8)):
        gen = cache.run_stream(exe.lowered, flats[:B], N_ITERS, chunk=chunk)
        _drain_rows = []
        while True:
            try:
                out, _ = next(gen)
            except StopIteration:
                break
            _drain_rows.append(out)
        assert sum(len(r) for r in _drain_rows) == B
    eng = cache.engine_for(exe.lowered)
    assert len(builds) == eng.traces <= 2
    assert eng.streams == 4


# ---------------------------------------------------------------------------
# metrics schema
# ---------------------------------------------------------------------------

def test_stream_summary_schema_and_engine_counters(compiled):
    program, exe = compiled
    cache = CompiledKernelCache()
    eng = cache.engine_for(exe.lowered)
    flats = program.flatten_batch(_mems(program, 17, seed=12))
    gen = eng.run_stream(flats, N_ITERS, chunk=8)
    n = 0
    while True:
        try:
            out, cinfo = next(gen)
        except StopIteration as stop:
            summary = dict(stop.value or {})
            break
        assert cinfo["chunk"] == n
        assert cinfo["samples"] == len(out)
        assert cinfo["bucket"] >= len(out)
        n += 1
    for key in ("stream_chunks", "samples", "overlap_frac",
                "throughput_sps", "wall_s", "wait_s", "traced", "engine"):
        assert key in summary, key
    assert summary["stream_chunks"] == n == 3
    assert summary["samples"] == 17
    assert 0.0 <= summary["overlap_frac"] <= 1.0
    assert summary["throughput_sps"] > 0
    stats = eng.stats()
    assert stats["streams"] == 1
    assert stats["stream_chunks"] == 3
    agg = cache.stats()
    assert agg["streams"] == 1 and agg["stream_chunks"] == 3


# ---------------------------------------------------------------------------
# service: submit_stream
# ---------------------------------------------------------------------------

def test_submit_stream_interleaves_with_discrete_tenants(compiled):
    """One bulk tenant's chunked stream and a discrete tenant's singles
    share the service: both resolve bit-exact, spans are bounded (no
    coalescer monopolization — more than one span for a long stream),
    and stream stats surface under ``stats()['stream']``."""
    program, exe = compiled
    target = ual.Target.from_name("hycube", rows=4, cols=4,
                                  backend="pallas")
    mems = _mems(program, 70, seed=20)
    ref = exe.run_batch(mems, n_iters=N_ITERS)
    with ual.Service(max_batch=16, max_wait_ms=2.0, max_queue=512) as svc:
        d_futs = [svc.submit(program, target, m, tenant="discrete",
                             n_iters=N_ITERS) for m in mems[:10]]
        sr = svc.submit_stream(program, target, mems, tenant="bulk",
                               n_iters=N_ITERS, chunk=8, span=2)
        assert len(sr) == 70
        got = []
        for chunk_outs in sr.chunks(timeout=300):
            assert len(chunk_outs) <= 8
            got.extend(chunk_outs)
        d_outs = [f.result(timeout=300) for f in d_futs]
        stats = svc.stats()
    for g, want in zip(got, ref):
        for name in program.outputs:
            np.testing.assert_array_equal(g[name], want[name])
    for g, want in zip(d_outs, ref[:10]):
        for name in program.outputs:
            np.testing.assert_array_equal(g[name], want[name])
    # 70 samples at chunk=8, span=2 -> ceil(70/16) = 5 spans
    assert stats["stream"]["spans"] == 5
    assert stats["stream"]["samples"] == 70
    assert stats["stream"]["chunks"] >= 9
    assert stats["stream"]["samples_per_s"] > 0
    info = sr.info
    assert info["spans"] == 5 and info["samples"] == 70
    assert 0.0 <= info["overlap_frac"] <= 1.0
    assert sr.responses[0].info.get("stream") is True
    # discrete traffic still coalesced normally alongside the stream
    assert stats["completed"] == 80


def test_stream_span_counted_before_its_last_chunk_resolves(compiled,
                                                           monkeypatch):
    """Every span is merged into ``StreamResponse.info`` (and counted in
    the service's stats) while its last chunk is still pending, so a
    client that has drained the stream reads every span, whatever the
    threads' timing."""
    from repro.ual.service.queue import StreamResponse
    program, _exe = compiled
    target = ual.Target.from_name("hycube", rows=4, cols=4,
                                  backend="pallas")
    all_done_at_merge = []
    merge = StreamResponse._merge_span

    def spy(self, summary):
        all_done_at_merge.append(self.done())
        merge(self, summary)

    monkeypatch.setattr(StreamResponse, "_merge_span", spy)
    with ual.Service(max_batch=16, max_wait_ms=2.0) as svc:
        sr = svc.submit_stream(program, target, _mems(program, 20, seed=23),
                               n_iters=N_ITERS, chunk=4, span=2)
        sr.results(timeout=300)
        assert sr.info["spans"] == 3 and sr.info["samples"] == 20
        assert svc.stats()["stream"]["spans"] == 3
    assert all_done_at_merge == [False, False, False]


def test_submit_stream_queue_full_is_all_or_nothing(compiled):
    program, _exe = compiled
    target = ual.Target.from_name("hycube", rows=4, cols=4,
                                  backend="pallas")
    mems = _mems(program, 24, seed=21)
    svc = ual.Service(max_batch=8, max_queue=16, start=False)
    try:
        sr = svc.submit_stream(program, target, mems, n_iters=N_ITERS)
        assert sr.rejected and sr.reason == "queue-full"
        assert all(r.rejected for r in sr.responses)
        # a fitting stream is still admitted after the rejection
        ok = svc.submit_stream(program, target, mems[:4], n_iters=N_ITERS)
        assert not ok.done() or not ok.rejected
    finally:
        svc.shutdown()
    assert all(r.rejected and r.reason == "shutdown" for r in ok.responses)


def test_submit_stream_after_shutdown_rejected(compiled):
    program, _exe = compiled
    target = ual.Target.from_name("hycube", rows=4, cols=4,
                                  backend="pallas")
    svc = ual.Service(max_batch=8)
    svc.shutdown()
    sr = svc.submit_stream(program, target, _mems(program, 3, seed=22),
                           n_iters=N_ITERS)
    assert sr.rejected and sr.reason == "shutdown"
    assert svc.stats()["stream"]["spans"] == 0


def test_submit_stream_empty_is_a_noop(compiled):
    program, _exe = compiled
    target = ual.Target.from_name("hycube", rows=4, cols=4,
                                  backend="pallas")
    with ual.Service(max_batch=8) as svc:
        sr = svc.submit_stream(program, target, [], n_iters=N_ITERS)
        assert len(sr) == 0 and sr.done() and not sr.rejected
        assert sr.results() == []


# ---------------------------------------------------------------------------
# satellites: pad-free fast path, validate flatten-once
# ---------------------------------------------------------------------------

def test_exact_bucket_batch_skips_padding(compiled):
    """A batch whose size IS a bucket takes the pad-free fast path: no
    padded samples, results still bit-exact."""
    program, exe = compiled
    cache = CompiledKernelCache()
    eng = cache.engine_for(exe.lowered)
    mems = _mems(program, 8, seed=30)
    flats = program.flatten_batch(mems)
    out, info = eng.run(flats, N_ITERS)
    assert info["padded"] == 0
    assert eng.padded_samples == 0
    want = interpret(program.dfg, mems[0], N_ITERS)
    got = program.unflatten(out[0])
    for name in program.outputs:
        np.testing.assert_array_equal(got[name], want[name])
    # a non-bucket size still pads (the fast path is conditional)
    out7, info7 = eng.run(flats[:7], N_ITERS)
    assert info7["padded"] == 1
    assert out7.shape[0] == 7


def test_validate_flattens_once_per_multi_backend_sweep(compiled,
                                                        monkeypatch):
    program, exe = compiled
    calls = []
    real = ual.Program.flatten_batch
    monkeypatch.setattr(ual.Program, "flatten_batch",
                        lambda self, ms: calls.append(len(ms))
                        or real(self, ms))
    report = exe.validate(seed=5, backends=("sim", "pallas"), n_vectors=4)
    assert report.passed
    assert calls == [4]          # one flatten feeds both backend sweeps


# ---------------------------------------------------------------------------
# packed blocks: the stream moves only the declared arrays
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,chunk", [(37, 8), (70, 32)])
def test_packed_stream_matches_full_width_run(compiled, B, chunk):
    """The packed stream, its last chunk ragged and padded up a bucket,
    gives every declared array bit-exact against the full-width
    ``KernelEngine.run`` of the same images."""
    program, exe = compiled
    mems = _mems(program, B, seed=B + 1)
    cache = CompiledKernelCache()
    full, _ = cache.run(exe.lowered, program.flatten_batch(mems), N_ITERS)
    gen = ual.backends.PallasBackend(engine=cache).execute_stream(
        program, None, mems, N_ITERS, chunk=chunk, lowered=exe.lowered)
    chunks, summary = _drain(gen)
    assert len(chunks[-1][0]) == B % chunk
    assert summary["padded"] > 0
    got = [d for outs, _ in chunks for d in outs]
    for g, f in zip(got, program.unflatten_batch(full)):
        for name in program.arrays:
            np.testing.assert_array_equal(g[name], f[name])


def test_warm_backend_runs_stream_and_batch_with_zero_new_traces(compiled):
    """``PallasBackend.warmup`` traces one packed shape per bucket and no
    full-width one; after it, batch and stream traffic of every size
    (a bucket, ragged, beyond the top bucket) adds no trace."""
    program, exe = compiled
    cache = CompiledKernelCache(buckets=(1, 8))
    prev = ual.set_default_engine(cache)
    try:
        stats = exe.warmup()
        L = sum(n for _, n in program.live)
        M = program.layout.total_words
        assert stats["traces"] == 2
        assert stats["warm_shapes"] == [(M, 1, program.live),
                                        (M, 8, program.live)]
        for B in (1, 5, 8, 12):
            exe.run_batch(_mems(program, B, seed=B), n_iters=N_ITERS)
            assert exe.last_info["traced"] == 0
        for B, chunk in ((11, 8), (3, 1), (16, None)):
            outs = list(exe.run_stream(_mems(program, B, seed=B),
                                       n_iters=N_ITERS, chunk=chunk))
            assert sum(len(o) for o in outs) == B
            assert exe.last_info["traced"] == 0
        eng = cache.engine_for(exe.lowered)
        assert eng.traces == 2
        assert eng.transfer_words % (2 * L) == 0
    finally:
        ual.set_default_engine(prev)


#: words an image moves, up plus back: the live words of each cell's
#: configuration (``chipbench/configs``), against 2 x 8192 full-width
CELL_WORDS = {"hycube4x4-gemm": 258, "pace8x8-fft": 320,
              "pace8x8-fft1024": 6144}


@pytest.mark.parametrize("config", sorted(CELL_WORDS))
def test_transfer_words_per_image_of_each_cell(config):
    """The benchmark's three deployments, as their configuration files
    state them, move 2 x L words an image on the packed path (a full
    8-image block, so no padding row) and 2 x M on the full-width one."""
    import json
    from pathlib import Path
    repo = Path(__file__).resolve().parents[1]
    cfg = json.loads((repo / "chipbench" / "configs" / f"{config}.json")
                     .read_text())
    program = ual.Program.from_kernel(
        cfg["kernel"], n_banks=cfg["n_banks"], bank_words=cfg["bank_words"])
    exe = ual.compile(program, ual.Target.from_name(
        cfg["fabric"], backend=cfg["backend"], **cfg["fabric_args"]))
    assert exe.success and program.layout.total_words == 8192
    mems = _mems(program, 8, seed=37)
    cache = CompiledKernelCache(buckets=(8,))
    eng = cache.engine_for(exe.lowered)
    packed = ual.backends.PallasBackend(engine=cache).execute_batch(
        program, None, mems, 1, lowered=exe.lowered)[0]
    assert eng.transfer_words / 8 == CELL_WORDS[config]
    full, _ = eng.run(program.flatten_batch(mems), 1)
    assert (eng.transfer_words / 8 - CELL_WORDS[config]) == 2 * 8192
    for got, want in zip(packed, program.unflatten_batch(full)):
        for name in program.arrays:
            np.testing.assert_array_equal(got[name], want[name])
