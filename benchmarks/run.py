"""Benchmark harness entry: one bench per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--smoke]

Each bench prints its table, records artifacts/bench/<name>.json, and
returns machine-checkable claim booleans; the run fails (exit 1) if any
paper claim is violated.

``--smoke`` skips the full benches and instead compiles one kernel per
registered temporal fabric through the UAL, cache-cold then cache-warm,
runs a B=16 batched-sim throughput check off the shared lowered artifact
(oracle parity + nonzero samples/s), a pallas JIT-engine gate (mixed-size
batches through the persistent engine: oracle parity spot-check, trace
count == bucket count, plus a chunked streaming run on the warm engine —
parity, populated overlap metrics, zero new traces, recorded in
``smoke.json["stream"]``), a 2-fabric x 2-strategy mini-sweep through
``compile_many(workers=2)``, a dynamic-batching service gate
(32 requests through a ``max_batch=8`` ``ual.Service``, oracle parity
spot-checked, nonzero samples/s), and a 2-process mini cluster gate
(32 requests through ``ual.ClusterService(workers=2)`` sharing one disk
cache, parity spot-checked, recorded in ``smoke.json["cluster"]``), a
chaos gate (16 requests through a 2-process cluster while a
deterministic ``FaultPlan`` hard-kills worker 0 mid-load: every future
must resolve, survivors bit-exact, the worker must respawn under its
``RestartPolicy`` — recorded in ``smoke.json["chaos"]``), and
a telemetry gate (one traced request through the service on a fresh
flight recorder: complete span tree, per-stage breakdown within 10% of
the reported latency, schema-valid Chrome-trace export to
``artifacts/bench/smoke_trace.json``, recorded in
``smoke.json["telemetry"]``) — a fast regression gate for the
toolchain, mapping cache, execution engines, DSE front-end, serving
layer and telemetry (used by CI, which uploads
``artifacts/bench/smoke.json``).

``--trace OUT.json`` runs anything above with the flight recorder on
for the whole run and exports one Chrome-trace JSON at the end.
"""
from __future__ import annotations

import argparse
import sys
import tempfile
import time

from benchmarks import (bench_chaos, bench_dse, bench_exec,
                        bench_fig9_spatial_vs_st,
                        bench_fig10_voltage, bench_fig11_breakdown,
                        bench_serve, bench_stream,
                        bench_table2_validation, bench_table3_multihop,
                        bench_table4_efficiency)
from benchmarks.common import ART, fmt_table, save

BENCHES = {
    "table2_validation": bench_table2_validation.run,
    "table3_multihop": bench_table3_multihop.run,
    "fig9_spatial_vs_st": bench_fig9_spatial_vs_st.run,
    "table4_efficiency": bench_table4_efficiency.run,
    "fig10_voltage": bench_fig10_voltage.run,
    "fig11_breakdown": bench_fig11_breakdown.run,
    "dse_explore": bench_dse.run,
    "exec_throughput": bench_exec.run,
    "serve_throughput": bench_serve.run,
    "serve_scaling": bench_serve.run_cluster,
    "stream_throughput": bench_stream.run,
    "chaos": bench_chaos.run,
}

SMOKE_TARGETS = (
    ("hycube", dict(rows=4, cols=4)),
    ("n2n", dict(rows=4, cols=4)),
    ("pace", {}),
    ("spatial", dict(rows=4, cols=4)),
)
SMOKE_KERNEL = "gemm"


def smoke() -> int:
    """Compile one kernel per fabric (cold + warm), validate on sim, run a
    B=16 batched-sim throughput check, push mixed-size batches through
    the pallas persistent JIT engine, mini-sweep 2 fabrics x
    2 strategies through ``compile_many(workers=2)``, push 32
    single-sample requests through a ``max_batch=8`` ``ual.Service``,
    then 32 more through a 2-process ``ual.ClusterService`` sharing one
    disk cache, then 16 through the same cluster shape while a
    ``FaultPlan`` kills worker 0 mid-load (self-healing gate).

    Exit non-zero if any compile fails, any compiled config carries
    verifier findings (``exe.check_report`` must be clean — recorded
    per fabric under ``smoke.json["verifier"]``), any validation
    mismatches, the
    warm compile misses the cache, the batched engine loses oracle parity
    or reports zero throughput, the JIT engine loses parity or retraces
    on a warm bucket, the sweep pays redundant mappings, either
    serving gate (service / mini cluster) loses parity or reports zero
    samples/s, or the chaos gate loses a future / a survivor's parity /
    the killed worker.
    Writes ``artifacts/bench/smoke.json`` (uploaded by CI).
    """
    import numpy as np

    from repro import ual
    failures = []
    rows = []
    verifier_json = []
    with tempfile.TemporaryDirectory() as d:
        cache = ual.MappingCache(disk_dir=d)
        for fab_name, kwargs in SMOKE_TARGETS:
            spatial = fab_name == "spatial"
            target = ual.Target.from_name(
                fab_name, backend="interp" if spatial else "sim", **kwargs)
            program = ual.Program.from_kernel(
                SMOKE_KERNEL, n_banks=target.fabric.n_mem_ports)
            t0 = time.perf_counter()
            exe = ual.compile(program, target, cache=cache)
            t_cold = time.perf_counter() - t0
            t0 = time.perf_counter()
            warm = ual.compile(program, target, cache=cache)
            t_warm = time.perf_counter() - t0
            fail = None if exe.success else "compile failed"
            # every config the smoke compiles must verify CLEAN — a
            # warning here is a mapper/lowering regression, not noise
            # (spatial/mapping-free targets carry no report: recorded
            # as skipped, not failed)
            rep = exe.check_report
            if rep is not None:
                verifier_json.append(rep.to_json())
                if fail is None and rep.diagnostics:
                    fail = f"verifier findings: {rep.summary()}"
            else:
                verifier_json.append(
                    {"name": f"{SMOKE_KERNEL} @ {target.fabric.name}",
                     "skipped": "no machine configuration"})
            if fail is None and spatial:
                # spatial: no config to validate, but the analytic model and
                # the interp execution path must still behave
                out = exe.run(program.random_inputs(
                    np.random.default_rng(0)))
                if not (exe.II >= 1 and exe.spatial_subgraphs >= 1
                        and set(out) == set(program.arrays)):
                    fail = "spatial model/interp regression"
            elif fail is None:
                if not exe.validate(seed=0).passed:
                    fail = "validation mismatch"
                elif not warm.compile_info.cache_hit:
                    fail = "warm compile missed cache"
            ok = fail is None
            if fail:
                failures.append(f"{fab_name}: {fail}")
            rows.append([f"{SMOKE_KERNEL}@{target.fabric.name}",
                         exe.II if exe.success else -1,
                         f"{t_cold:.2f}s", f"{t_warm * 1e3:.1f}ms",
                         "clean" if rep is not None and not rep.diagnostics
                         else ("-" if rep is None else rep.summary()),
                         "ok" if ok else "FAIL"])
    print("== smoke: one kernel per fabric, cache-cold then cache-warm ==")
    print(fmt_table(["kernel@fabric", "II", "cold", "warm", "verify",
                     "check"], rows))
    print(f"cache: {cache.stats}")
    # the aggregate view (MappingCache.stats()): ratios + disk entries.
    # Rendered after the tempdir closes, so disk_entries reads 0 here —
    # the ratios are the point; disk counts are live in the service gate
    agg = cache.stats()
    print("cache aggregate: " + " | ".join(
        f"{layer}: hit_ratio={v['hit_ratio']} "
        f"({v['hits']}/{v['lookups']}), stores={v['stores']}, "
        f"disk_entries={v['disk_entries']}"
        for layer, v in agg.items() if isinstance(v, dict))
        + f" | quarantined={agg['quarantined']}")

    # -- batched-sim throughput gate: one kernel, B=16, vectorized engine
    # off the shared lowered artifact; parity with the oracle + nonzero
    # samples/s, so the lower-once/run-many path can't silently regress
    batched_json = None
    with tempfile.TemporaryDirectory() as d:
        bcache = ual.MappingCache(disk_dir=d)
        target = ual.Target.from_name("hycube", rows=4, cols=4)
        program = ual.Program.from_kernel(
            SMOKE_KERNEL, n_banks=target.fabric.n_mem_ports)
        exe = ual.compile(program, target, cache=bcache)
        B = 16
        ok = exe.success and exe.lowered is not None
        if not ok:
            failures.append("batched sim: compile/lowering failed")
        else:
            rep = exe.validate(seed=0, backends=("sim",), n_vectors=B)
            rng = np.random.default_rng(1)
            exe.run_batch([program.random_inputs(rng) for _ in range(B)])
            sps = exe.last_info.get("throughput_sps", 0.0)
            if not rep.passed:
                failures.append("batched sim: oracle parity mismatch")
            if not sps > 0:
                failures.append("batched sim: zero throughput reported")
            if bcache.stats.lowered_stores != 1:
                failures.append("batched sim: expected exactly one lowering")
            batched_json = {"B": B, "parity": rep.passed,
                            "throughput_sps": round(float(sps), 1),
                            "relowered": bcache.stats.lowered_stores != 1}
            print(f"\n== smoke: batched sim B={B} on the lowered artifact: "
                  f"{batched_json['throughput_sps']} samples/s, "
                  f"parity={'ok' if rep.passed else 'FAIL'} ==")

    # -- mini-DSE: 2 fabrics x 2 strategies through compile_many(workers=2)
    sweep_json = None
    with tempfile.TemporaryDirectory() as d:
        sweep_cache = ual.MappingCache(disk_dir=d)
        program = ual.Program.from_kernel(SMOKE_KERNEL)
        space = {"fabric": [("hycube", dict(rows=4, cols=4)),
                            ("n2n", dict(rows=4, cols=4))],
                 "strategy": ["adaptive", "sa"]}
        t0 = time.perf_counter()
        report = ual.explore(program, space, workers=2, cache=sweep_cache)
        t_sweep = time.perf_counter() - t0
        rewarm = ual.explore(program, space, workers=2, cache=sweep_cache)
        print(f"\n== smoke: 2x2 DSE mini-sweep via compile_many(workers=2), "
              f"{t_sweep:.1f}s ==")
        print(report.render())
        if not all(p.success for p in report.points):
            failures.append("dse sweep: point failed to map")
        if sweep_cache.stats.stores != len(report.points):
            failures.append(f"dse sweep: {sweep_cache.stats.stores} mappings "
                            f"stored for {len(report.points)} unique points")
        if rewarm.n_mapped != 0 or rewarm.n_warm != len(report.points):
            failures.append("dse sweep: warm re-sweep paid mappings")
        sweep_json = report.to_json()
        sweep_json["rewarm_all_cached"] = rewarm.n_mapped == 0

    # -- service gate: >=32 single-sample requests through a max_batch=8
    # dynamic-batching service; oracle-parity spot-check on 4 responses,
    # nonzero samples/s — so the queue->coalesce->sweep path can't rot
    service_json = None
    with tempfile.TemporaryDirectory() as d:
        from repro.core.dfg import interpret
        scache = ual.MappingCache(disk_dir=d)
        target = ual.Target.from_name("hycube", rows=4, cols=4)
        program = ual.Program.from_kernel(
            SMOKE_KERNEL, n_banks=target.fabric.n_mem_ports)
        n_req = 32
        rng = np.random.default_rng(2)
        mems = [program.random_inputs(rng) for _ in range(n_req)]
        with ual.Service(max_batch=8, max_wait_ms=5.0,
                         max_queue=2 * n_req, cache=scache) as svc:
            resps = [svc.submit(program, target, m, tenant="smoke")
                     for m in mems]
            outs = [r.result(timeout=300) for r in resps]
            stats = svc.stats()
        spot = [0, 9, 17, n_req - 1]
        parity = all(
            np.array_equal(interpret(program.dfg, mems[i],
                                     program.n_iters)[name], outs[i][name])
            for i in spot for name in program.outputs)
        sps = stats["samples_per_s"]
        if not parity:
            failures.append("service: oracle parity mismatch")
        if not sps > 0:
            failures.append("service: zero samples/s")
        if stats["completed"] != n_req:
            failures.append(f"service: {stats['completed']}/{n_req} "
                            f"requests completed")
        service_json = {"requests": n_req, "max_batch": 8,
                        "parity_spot_checked": len(spot), "parity": parity,
                        "samples_per_s": sps,
                        "mean_batch": stats["mean_batch"],
                        "p50_ms": stats["p50_ms"],
                        "p99_ms": stats["p99_ms"],
                        "rejects": stats["rejects"]}
        print(f"\n== smoke: service {n_req} requests @ max_batch=8: "
              f"{sps} samples/s, mean batch {stats['mean_batch']}, "
              f"parity={'ok' if parity else 'FAIL'} ==")

    # -- telemetry gate: one traced request end to end on a fresh flight
    # recorder — the span tree must be complete (request/queue/coalesce/
    # exec/resolve), the per-stage breakdown must account for the
    # reported latency within 10%, and the Chrome-trace export must be
    # schema-valid (written to artifacts/bench/smoke_trace.json, uploaded
    # by CI)
    telemetry_json = None
    with tempfile.TemporaryDirectory() as d:
        from repro import obs
        from repro.obs.trace import validate_chrome
        tcache = ual.MappingCache(disk_dir=d)
        target = ual.Target.from_name("hycube", rows=4, cols=4)
        program = ual.Program.from_kernel(
            SMOKE_KERNEL, n_banks=target.fabric.n_mem_ports)
        rng = np.random.default_rng(5)
        tracer = obs.Tracer(enabled=True)
        prev_tracer = obs.set_tracer(tracer)
        try:
            with ual.Service(max_batch=8, max_wait_ms=5.0,
                             cache=tcache) as svc:
                svc.submit(program, target,
                           program.random_inputs(rng)).result(timeout=300)
                fut = svc.submit(program, target, program.random_inputs(rng),
                                 tenant="traced")
                fut.result(timeout=300)
            trace = fut.info.get("trace") or {}
            latency_ms = float(fut.info["latency_ms"])
            span_names = {s.name for s in tracer.spans(trace.get("trace_id"))}
            want = {"request", "queue", "coalesce", "exec", "resolve"}
            missing = sorted(want - span_names)
            parts = sum(trace.get(k) or 0.0
                        for k in ("queue_ms", "coalesce_ms", "exec_ms"))
            parity = (latency_ms > 0
                      and abs(parts - latency_ms) <= 0.10 * latency_ms)
            problems = validate_chrome(tracer.to_chrome())
            trace_path = tracer.export_chrome(ART / "smoke_trace.json")
        finally:
            obs.set_tracer(prev_tracer)
        if missing:
            failures.append(f"telemetry: span tree incomplete, "
                            f"missing {missing}")
        if not parity:
            failures.append(f"telemetry: breakdown {parts:.3f}ms vs "
                            f"latency {latency_ms:.3f}ms (>10% apart)")
        if problems:
            failures.append(f"telemetry: invalid Chrome trace: "
                            f"{problems[:3]}")
        telemetry_json = {"trace_id": trace.get("trace_id"),
                          "breakdown": trace, "latency_ms": latency_ms,
                          "span_tree_complete": not missing,
                          "breakdown_parity_10pct": parity,
                          "chrome_valid": not problems,
                          "spans_recorded": tracer.stats()["recorded"],
                          "trace_file": str(trace_path)}
        print(f"\n== smoke: telemetry — traced request breakdown "
              f"{ {k: round(v, 3) for k, v in trace.items() if isinstance(v, float)} } "
              f"vs latency {latency_ms:.3f}ms, "
              f"tree={'ok' if not missing else 'INCOMPLETE'}, "
              f"chrome={'ok' if not problems else 'INVALID'} ==")

    # -- mini cluster gate: 32 requests through a 2-process
    # ClusterService (spawn — safe at any point, unlike fork); parity
    # spot-check + nonzero samples/s + merged-stats sanity, so the
    # multi-process front-end can't rot between full serve_scaling runs
    cluster_json = None
    with tempfile.TemporaryDirectory() as d:
        from repro.core.dfg import interpret
        target = ual.Target.from_name("hycube", rows=4, cols=4)
        program = ual.Program.from_kernel(
            SMOKE_KERNEL, n_banks=target.fabric.n_mem_ports)
        n_req = 32
        rng = np.random.default_rng(4)
        mems = [program.random_inputs(rng) for _ in range(n_req)]
        with ual.ClusterService(workers=2, max_batch=8, max_wait_ms=5.0,
                                max_queue=2 * n_req,
                                warmup_buckets=(1, 8),
                                cache_dir=d) as cs:
            resps = [cs.submit(program, target, m, tenant="smoke")
                     for m in mems]
            outs = [r.result(timeout=300) for r in resps]
            cstats = cs.stats()
        spot = [0, 9, 17, n_req - 1]
        parity = all(
            np.array_equal(interpret(program.dfg, mems[i],
                                     program.n_iters)[name], outs[i][name])
            for i in spot for name in program.outputs)
        sps = cstats["samples_per_s"]
        if not parity:
            failures.append("cluster: oracle parity mismatch")
        if not sps > 0:
            failures.append("cluster: zero samples/s")
        if cstats["completed"] != n_req:
            failures.append(f"cluster: {cstats['completed']}/{n_req} "
                            f"requests completed")
        if cstats["workers"] != 2:
            failures.append(f"cluster: {cstats['workers']}/2 workers live")
        cluster_json = {"requests": n_req, "workers": cstats["workers"],
                        "parity_spot_checked": len(spot), "parity": parity,
                        "samples_per_s": sps,
                        "p99_ms": cstats["p99_ms"],
                        "routing": cstats["routing"],
                        "rejects": cstats["rejects"]}
        print(f"\n== smoke: 2-process cluster, {n_req} requests: "
              f"{sps} samples/s, "
              f"routing {cstats['routing']['decisions']}, "
              f"parity={'ok' if parity else 'FAIL'} ==")

    # -- chaos gate: same mini cluster, but a deterministic FaultPlan
    # hard-kills worker 0 after its 3rd request, mid-load.  The
    # self-healing contract is binary: every future resolves (retried
    # transparently, zero rejects), survivors are bit-exact, and the
    # watchdog respawns the slot within its RestartPolicy — so the
    # failure paths run on every CI pass, not just in full bench runs
    chaos_json = None
    with tempfile.TemporaryDirectory() as d:
        from repro.core.dfg import interpret
        target = ual.Target.from_name("hycube", rows=4, cols=4)
        program = ual.Program.from_kernel(
            SMOKE_KERNEL, n_banks=target.fabric.n_mem_ports)
        n_req = 16
        rng = np.random.default_rng(6)
        mems = [program.random_inputs(rng) for _ in range(n_req)]
        plan = ual.FaultPlan(
            [ual.FaultSpec("kill_worker", worker=0, after=2)], seed=0)
        policy = ual.RestartPolicy(max_restarts=2, backoff_base_s=0.25)
        with ual.ClusterService(workers=2, max_batch=8, max_wait_ms=5.0,
                                max_queue=2 * n_req, cache_dir=d,
                                worker_env=plan.to_env(),
                                restart_policy=policy) as cs:
            resps = [cs.submit(program, target, m, tenant="chaos")
                     for m in mems]
            outs, rejected = [], 0
            for r in resps:
                try:
                    outs.append(r.result(timeout=300))
                except ual.ServiceRejected:
                    outs.append(None)
                    rejected += 1
            deadline = time.time() + 60.0
            wsnap = None
            while time.time() < deadline:
                wsnap = cs.stats(timeout=30)["supervision"]["workers"][0]
                if wsnap["restarts"] >= 1 and wsnap["alive"]:
                    break
                time.sleep(0.2)
            cstats = cs.stats(timeout=30)
        sup = cstats["supervision"]
        parity = all(
            np.array_equal(interpret(program.dfg, mems[i],
                                     program.n_iters)[name], outs[i][name])
            for i, out in enumerate(outs) if out is not None
            for name in program.outputs)
        if rejected:
            failures.append(f"chaos: {rejected} requests rejected (retry "
                            f"not transparent)")
        if not parity:
            failures.append("chaos: survivor parity mismatch after retry")
        if sup["deaths_total"] < 1:
            failures.append("chaos: fault plan never killed the worker")
        if not (wsnap and wsnap["alive"] and wsnap["restarts"] >= 1):
            failures.append(f"chaos: worker 0 not respawned ({wsnap})")
        chaos_json = {"requests": n_req, "rejected": rejected,
                      "parity": parity,
                      "fault_plan": plan.to_json(),
                      "deaths_total": sup["deaths_total"],
                      "restarts_total": sup["restarts_total"],
                      "retries_total": sup["retries_total"],
                      "recovery_s": wsnap["last_recovery_s"] if wsnap
                      else None}
        print(f"\n== smoke: chaos — kill worker 0 mid-load, {n_req} "
              f"requests: {sup['retries_total']} retried, "
              f"{rejected} rejected, recovery "
              f"{chaos_json['recovery_s']}s, "
              f"parity={'ok' if parity else 'FAIL'} ==")

    # -- pallas engine gate: mixed-size batches through the persistent
    # JIT engine; parity spot-check vs the oracle, trace count must equal
    # the number of distinct buckets touched (trace-once/run-many).
    # Runs LAST: this is the smoke's first jax use, and the fork-based
    # mini-sweep above must spawn its workers before jax starts threads
    engine_json = None
    stream_json = None
    with tempfile.TemporaryDirectory() as d:
        from repro.core.dfg import interpret
        ecache = ual.MappingCache(disk_dir=d)
        target = ual.Target.from_name("hycube", rows=4, cols=4,
                                      backend="pallas")
        program = ual.Program.from_kernel(
            SMOKE_KERNEL, n_banks=target.fabric.n_mem_ports, bank_words=64)
        exe = ual.compile(program, target, cache=ecache)
        engine = ual.CompiledKernelCache()
        prev_engine = ual.set_default_engine(engine)
        try:
            if not exe.success:
                failures.append("pallas engine: compile failed")
            else:
                rng = np.random.default_rng(3)
                mems = [program.random_inputs(rng) for _ in range(12)]
                out_a = exe.run_batch(mems[:3])    # bucket 8
                out_b = exe.run_batch(mems)        # bucket 32
                exe.run_batch(mems[3:8])           # bucket 8, warm
                stats = engine.stats()
                parity = all(
                    np.array_equal(interpret(program.dfg, m,
                                             program.n_iters)[n], o[n])
                    for m, o in ((mems[0], out_a[0]), (mems[11], out_b[11]))
                    for n in program.outputs)
                eng = engine.engine_for(exe.lowered)
                n_buckets = len(eng.bucket_calls)
                if not parity:
                    failures.append("pallas engine: oracle parity mismatch")
                if stats["traces"] != n_buckets:
                    failures.append(
                        f"pallas engine: {stats['traces']} traces for "
                        f"{n_buckets} buckets (retrace on the warm path)")
                engine_json = {"batches": [3, 12, 5], "parity": parity,
                               "traces": stats["traces"],
                               "buckets_used": sorted(eng.bucket_calls),
                               "hit_ratio": stats["hit_ratio"]}
                print(f"\n== smoke: pallas JIT engine, 3 mixed-size "
                      f"batches: {stats['traces']} traces / "
                      f"{n_buckets} buckets, "
                      f"parity={'ok' if parity else 'FAIL'} ==")

                # -- streaming gate: a small chunked run through the
                # double-buffered path on the SAME warm engine — parity
                # spot-check, overlap metrics populated, zero new traces
                traces_before = engine.stats()["traces"]
                s_outs = exe.run_batch(mems, stream=True, chunk=8)
                s_info = exe.last_info
                s_parity = all(
                    np.array_equal(interpret(program.dfg, m,
                                             program.n_iters)[n], o[n])
                    for m, o in ((mems[0], s_outs[0]),
                                 (mems[11], s_outs[11]))
                    for n in program.outputs)
                s_traces = engine.stats()["traces"] - traces_before
                if not s_parity:
                    failures.append("stream: oracle parity mismatch")
                if not (s_info.get("stream_chunks", 0) > 0
                        and s_info.get("throughput_sps", 0) > 0):
                    failures.append("stream: overlap metrics missing "
                                    f"({s_info})")
                if s_info.get("overlap_frac") is None:
                    failures.append("stream: no overlap_frac reported")
                if s_traces != 0:
                    failures.append(f"stream: {s_traces} new traces on a "
                                    f"warm engine")
                stream_json = {"B": len(mems), "chunk": 8,
                               "parity": s_parity,
                               "stream_chunks":
                                   s_info.get("stream_chunks"),
                               "overlap_frac": s_info.get("overlap_frac"),
                               "throughput_sps":
                                   round(float(s_info.get(
                                       "throughput_sps", 0.0)), 1),
                               "new_traces": s_traces}
                print(f"== smoke: streaming B={len(mems)} chunk=8: "
                      f"{stream_json['stream_chunks']} chunks, overlap "
                      f"{stream_json['overlap_frac']}, {s_traces} new "
                      f"traces, parity={'ok' if s_parity else 'FAIL'} ==")
        finally:
            ual.set_default_engine(prev_engine)

    save("smoke", {"fabrics": rows, "verifier": verifier_json,
                   "sweep": sweep_json,
                   "batched_sim": batched_json, "pallas_engine": engine_json,
                   "service": service_json, "cluster": cluster_json,
                   "chaos": chaos_json,
                   "stream": stream_json, "telemetry": telemetry_json,
                   "failures": failures})
    for f in failures:
        print(f"FAIL {f}")
    return 1 if failures else 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=sorted(BENCHES))
    ap.add_argument("--smoke", action="store_true",
                    help="fast regression gate: compile one kernel per "
                         "fabric, cold + warm, instead of the full benches")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="run with the flight recorder on and export the "
                         "whole run as Chrome-trace JSON to OUT (open at "
                         "https://ui.perfetto.dev)")
    args = ap.parse_args()
    tracer = prev_tracer = None
    if args.trace:
        from repro import obs
        tracer = obs.Tracer(enabled=True, capacity=1 << 17)
        prev_tracer = obs.set_tracer(tracer)
    try:
        if args.smoke:
            sys.exit(smoke())
        names = [args.only] if args.only else list(BENCHES)
        failed = []
        for name in names:
            t0 = time.perf_counter()
            print(f"\n########## {name} ##########")
            payload = BENCHES[name]()
            claims = payload.get("claims", {})
            bad = [k for k, v in claims.items() if not v]
            if bad:
                failed.append((name, bad))
            print(f"[{name}] done in {time.perf_counter() - t0:.1f}s"
                  + (f"  VIOLATED: {bad}" if bad else "  all claims hold"))
        print("\n================ SUMMARY ================")
        if failed:
            for name, bad in failed:
                print(f"FAIL {name}: {bad}")
            sys.exit(1)
        print(f"all {len(names)} benches passed their paper-claim checks")
    finally:
        if tracer is not None:
            from repro import obs
            out = tracer.export_chrome(args.trace)
            print(f"trace: {len(tracer.spans())} spans -> {out}")
            obs.set_tracer(prev_tracer)


if __name__ == "__main__":
    main()
