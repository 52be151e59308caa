"""Streaming elastic execution: double-buffered pipelining vs discrete.

The STRELA move at the host level: instead of upload -> sweep -> download
in strict sequence (``KernelEngine.run``'s per-block blocking
``np.asarray``), ``run_stream`` cuts a large batch into warm-bucket
chunks and pipelines them — while chunk *i* computes on device, chunk
*i+1* uploads and chunk *i-1* drains, riding jax async dispatch.  This
bench holds the PR's claims at equal total B:

  * streaming steady-state samples/s >= a floor ratio of the discrete
    ``run``'s samples/s (1.0 where the machine can actually overlap,
    degraded to a collapse detector on a 1-core container — PR-2/PR-7
    calibration precedent: the floor is derived from *measured*
    multiprocessing parallelism, recorded alongside),
  * measured transfer/compute overlap (``overlap_frac`` = fraction of
    stream wall the host was NOT blocked in ``block_until_ready``)
    >= a parallelism-calibrated floor,
  * streamed chunks are bit-exact vs the discrete path and the
    DFG-interpreter oracle (ragged tail included),
  * a warm engine streams with ZERO new traces (trace count flat across
    the whole streaming phase — the bucket ladder is the trace budget),
  * ``Service.submit_stream`` pipelines a chunked tenant request
    bit-exact while discrete tenants interleave, with stream stats
    surfaced under ``stats()["stream"]``.
"""
from __future__ import annotations

import multiprocessing as _mp
import time

import numpy as np

from repro import obs, ual
from repro.core.dfg import interpret

from benchmarks.common import ART, Timer, fmt_table, save

KERNEL = "gemm"
BANK_WORDS = 64
B_TOTAL = 192            # equal-B comparison: 6 full top-bucket chunks
CHUNK = 32               # == the ladder's top bucket (warm trace reuse)
N_REPS = 7               # steady-state medians over this many sweeps
SERVICE_STREAM_N = 96
SERVICE_DISCRETE_N = 16


def _busy(n: int) -> int:
    acc = 0
    for i in range(n):
        acc = (acc + i * i) % 1000003
    return acc


def _measured_parallelism(n_procs: int = 2, work: int = 2_000_000) -> float:
    """CPU-bound multiprocessing speedup THIS machine delivers (~1.0 on a
    1-core container) — the honest basis for the overlap/throughput
    floors; cgroup quotas and noisy neighbors show up here, unlike
    ``os.cpu_count()`` (PR-2/PR-7 precedent)."""
    _busy(work // 10)
    t0 = time.perf_counter()
    for _ in range(n_procs):
        _busy(work)
    serial = time.perf_counter() - t0
    ctx = _mp.get_context("spawn")
    with ctx.Pool(n_procs) as pool:
        t0 = time.perf_counter()
        pool.map(_busy, [work] * n_procs)
        par = time.perf_counter() - t0
    return max(1.0, serial / par) if par > 0 else 1.0


def _throughput_floor(parallelism: float) -> float:
    """Streaming must deliver >= this ratio of discrete throughput.
    Where the machine can genuinely run host and device work in parallel
    (measured parallelism >= 2) pipelining must not lose to the discrete
    path (1.0); on a 1-core container the chunked python loop serializes
    with the compute it would otherwise hide behind, so the ratio
    degrades to a collapse detector (0.7) with the measured parallelism
    recorded alongside."""
    return 1.0 if parallelism >= 2.0 else 0.7


def _overlap_floor(parallelism: float) -> float:
    """Minimum acceptable ``overlap_frac``.  The metric is the fraction
    of stream wall the host spent NOT blocked on the device — genuine
    double buffering pushes it toward 1 on multi-core; on 1 core only
    the host's own pad/drain work registers (measured ~0.025-0.03 here),
    so the floor degrades to 1.5% — still a collapse detector for a
    fully-blocking regression, where every chunk waits out its whole
    compute and overlap falls toward 0."""
    return min(0.25, max(0.015, 0.5 * (parallelism - 1.0)))


def run(seed: int = 0, verbose: bool = True) -> dict:
    # jax first touched here (not at module import): fork-based benches
    # in the same harness run must spawn workers before jax threads
    from repro.ual.engine import CompiledKernelCache

    parallelism = _measured_parallelism()
    sps_floor = _throughput_floor(parallelism)
    ov_floor = _overlap_floor(parallelism)

    target = ual.Target.from_name("hycube", rows=4, cols=4, seed=seed,
                                  backend="pallas")
    program = ual.Program.from_kernel(KERNEL,
                                      n_banks=target.fabric.n_mem_ports,
                                      bank_words=BANK_WORDS)
    exe = ual.compile(program, target)
    if not exe.success:
        payload = {"mapped": False, "claims": {"mapped": False}}
        save("stream", payload)
        return payload
    n_iters = program.n_iters
    rng = np.random.default_rng(seed)
    mems = [program.random_inputs(rng) for _ in range(B_TOTAL)]
    flats = program.flatten_batch(mems)
    oracle = np.stack([program.flatten(interpret(program.dfg, m, n_iters))
                       for m in mems])

    engine = CompiledKernelCache()
    eng = engine.engine_for(exe.lowered)
    eng.warmup(program.layout.total_words)
    traces_after_warmup = eng.stats()["traces"]

    # -- discrete baseline: the existing blocking path, same total B
    discrete_walls = []
    for _ in range(N_REPS):
        t0 = time.perf_counter()
        disc_out, _info = engine.run(exe.lowered, flats, n_iters)
        discrete_walls.append(time.perf_counter() - t0)
    discrete_s = float(np.median(discrete_walls))
    discrete_sps = B_TOTAL / discrete_s

    # -- streaming: same flats, same engine, chunks drained as they land
    stream_walls, summaries = [], []
    stream_out = None
    for _ in range(N_REPS):
        rows_out = np.empty_like(disc_out)
        pos = 0
        t0 = time.perf_counter()
        gen = eng.run_stream(flats, n_iters, chunk=CHUNK)
        while True:
            try:
                out, _cinfo = next(gen)
            except StopIteration as stop:
                summaries.append(dict(stop.value or {}))
                break
            rows_out[pos:pos + len(out)] = out
            pos += len(out)
        stream_walls.append(time.perf_counter() - t0)
        stream_out = rows_out
    stream_s = float(np.median(stream_walls))
    stream_sps = B_TOTAL / stream_s
    overlap = float(np.median([s["overlap_frac"] for s in summaries]))
    traces_after_stream = eng.stats()["traces"]

    # -- ragged tail: B that straddles the ladder must stay bit-exact
    ragged_gen = eng.run_stream(flats[:CHUNK + 5], n_iters, chunk=CHUNK)
    ragged_rows = []
    while True:
        try:
            out, _cinfo = next(ragged_gen)
        except StopIteration:
            break
        ragged_rows.append(out)
    ragged = np.concatenate(ragged_rows)

    bitexact = (np.array_equal(stream_out, disc_out)
                and np.array_equal(stream_out, oracle)
                and np.array_equal(ragged, oracle[:CHUNK + 5]))

    # -- serving path: one chunked tenant pipelined through submit_stream
    # while a discrete tenant's singles coalesce in between
    prev_engine = ual.set_default_engine(engine)
    try:
        with ual.Service(max_batch=CHUNK, max_wait_ms=2.0,
                         max_queue=4 * SERVICE_STREAM_N) as svc:
            d_resps = [svc.submit(program, target, m, tenant="discrete")
                       for m in mems[:SERVICE_DISCRETE_N]]
            sr = svc.submit_stream(program, target,
                                   mems[:SERVICE_STREAM_N], tenant="bulk",
                                   chunk=CHUNK, span=2)
            got = sr.results(timeout=600)
            d_outs = [r.result(timeout=600) for r in d_resps]
            svc_stats = svc.stats()["stream"]
        svc_parity = all(
            np.array_equal(program.flatten(o), oracle[i])
            for i, o in enumerate(got)) and all(
            np.array_equal(program.flatten(o), oracle[i])
            for i, o in enumerate(d_outs))
        stream_info = sr.info
    finally:
        ual.set_default_engine(prev_engine)

    # -- trace artifact: one streaming sweep with the flight recorder on,
    # exported next to the claims JSON so the upload/wait/download
    # pipeline is inspectable at https://ui.perfetto.dev
    tracer = obs.Tracer(enabled=True)
    prev = obs.set_tracer(tracer)
    try:
        with Timer("stream_traced"):
            gen = eng.run_stream(flats, n_iters, chunk=CHUNK)
            while True:
                try:
                    next(gen)
                except StopIteration:
                    break
        trace_path = tracer.export_chrome(ART / "stream_trace.json")
        stage_spans: dict = {}
        for s in tracer.spans():
            if s.name.startswith("stream:"):
                stage_spans[s.name] = stage_spans.get(s.name, 0) + 1
    finally:
        obs.set_tracer(prev)

    data = {
        "mapped": True, "ii": exe.II, "B": B_TOTAL, "chunk": CHUNK,
        "reps": N_REPS,
        "parallelism_measured": round(parallelism, 2),
        "throughput_floor_ratio": sps_floor,
        "overlap_floor": round(ov_floor, 3),
        "discrete_sps": round(discrete_sps, 1),
        "stream_sps": round(stream_sps, 1),
        "stream_vs_discrete": round(stream_sps / discrete_sps, 3),
        "overlap_frac": round(overlap, 4),
        "traces_after_warmup": traces_after_warmup,
        "traces_after_stream": traces_after_stream,
        "bitexact": bitexact,
        "service": {"stream_requests": SERVICE_STREAM_N,
                    "discrete_requests": SERVICE_DISCRETE_N,
                    "parity": svc_parity, "stats": svc_stats,
                    "stream_info": stream_info},
        "trace": {"file": str(trace_path),
                  "chunk_spans": sum(stage_spans.values()),
                  "stage_spans": stage_spans},
    }
    claims = {
        "mapped": True,
        "stream_bitexact_vs_oracle_and_discrete": bitexact,
        "stream_sps_ge_floor_x_discrete":
            stream_sps >= sps_floor * discrete_sps,
        "overlap_ge_calibrated_floor": overlap >= ov_floor,
        "no_new_traces_while_streaming":
            traces_after_stream == traces_after_warmup,
        "service_stream_parity_with_interleaved_discrete": svc_parity,
        # the engine's three stages, once per chunk of the traced sweep
        "trace_upload_wait_download_per_chunk": stage_spans == {
            name: B_TOTAL // CHUNK for name in
            ("stream:upload", "stream:wait", "stream:download")},
        "service_stream_stats_surfaced":
            svc_stats["spans"] > 0 and svc_stats["samples"]
            == SERVICE_STREAM_N,
    }
    payload = {"data": data, "claims": claims, "kernel": KERNEL}
    save("stream", payload)
    if verbose:
        print("== streaming vs discrete at equal total B "
              f"(B={B_TOTAL}, chunk={CHUNK}, medians of {N_REPS}) ==")
        print(fmt_table(
            ["path", "samples/s", "overlap", "traces", "bitexact"],
            [["discrete run", data["discrete_sps"], "-",
              traces_after_warmup, "ok"],
             ["run_stream", data["stream_sps"], data["overlap_frac"],
              traces_after_stream, "ok" if bitexact else "MISMATCH"]]))
        print(f"measured parallelism {data['parallelism_measured']} -> "
              f"floors: sps ratio {sps_floor}, overlap {ov_floor:.3f}; "
              f"achieved ratio {data['stream_vs_discrete']}")
        print(f"service stream: {svc_stats} "
              f"(parity={'ok' if svc_parity else 'FAIL'})")
        print("claims:", claims)
    return payload


def main():
    run()


if __name__ == "__main__":
    main()
