"""Smoke run of the main path on a TPU: proves the system starts on the chip.

    python chip_smoke.py                # one chip: phases A, B and C
    python chip_smoke.py --four-chips   # four chips: the multi-chip phase only

Runs ``Program -> ual.compile -> Executable / Service / run_stream`` on the
``pallas`` backend at the repo's full default width: HyCUBE 4x4, the
default scratchpad of 4 banks x 2048 words (M = 8192) and 128 lanes.  Every
output is checked bit-exact against the DFG-interpreter oracle, inputs are
random from ``--seed``, and mapping starts cold in a memory-only
``MappingCache``.

  A. gemm compiled and warmed (buckets 1/8/32/128), then ``run_batch`` over
     1024 scratchpad images: bit-exact, run on the TPU, one trace per
     bucket and none inside the run.
  B. ``ual.Service(max_batch=32)`` with two tenants (gemm, fft) answering
     256 single-sample requests: all resolve bit-exact, no rejects, no
     degraded batches, no traces once the classes are warm.
  C. ``run_stream`` over the phase-A images in chunks of 128: bit-exact,
     no new traces.

``--four-chips`` instead runs ``Service(replicas=4)`` on the phase-B traffic
(one replica per chip) and the ``pallas_sharded`` backend on the phase-A
batch, each against one chip on the same inputs.

Wall times printed here are smoke timings, not benchmark numbers.  The
script never falls back to the CPU: without a TPU it exits non-zero, and
its last line — only when every check passed — is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BUCKETS = (1, 8, 32, 128)
N_IMAGES = 1024           # phase A/C batch: 8 full 128-lane tiles
N_REQUESTS = 256          # phase B single-sample requests
MAX_BATCH = 32


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def same_outputs(program, got, want) -> bool:
    import numpy as np
    return all(np.array_equal(got[n], want[n]) for n in program.outputs)


def oracle(program, mems):
    from repro.core.dfg import interpret
    return [interpret(program.dfg, m, program.n_iters) for m in mems]


def engine_traces() -> int:
    from repro.ual.engine import default_engine
    return default_engine().stats()["traces"]


def check_engines(platform: str) -> dict:
    """Every engine so far ran the compiled kernel on ``platform``."""
    from repro.ual.engine import default_engine
    stats = default_engine().stats()
    for name, e in stats["per_engine"].items():
        check(e["platform"] == platform,
              f"engine {name} ran on {e['platform']}, not {platform}")
        want_mode = "tpu" if platform == "tpu" else "interp"
        check(e["mode"] == want_mode,
              f"engine {name} mode {e['mode']}, expected {want_mode}")
    return stats


def compile_gemm(cache):
    """Cold compile of gemm on HyCUBE 4x4 (pallas); returns the program,
    the executable and the set-up seconds: mapping, lowering, Mosaic."""
    from repro import ual
    program = ual.Program.from_kernel("gemm")
    target = ual.Target.from_name("hycube", rows=4, cols=4,
                                  backend="pallas")
    exe = ual.compile(program, target, cache=cache)
    check(exe.success, "gemm did not map on hycube 4x4")
    check(exe.program.layout.total_words == 8192,
          f"scratchpad M={exe.program.layout.total_words}, expected 8192")
    passes = exe.compile_info.pass_times
    t0 = time.perf_counter()
    stats = exe.warmup(BUCKETS)
    mosaic_s = time.perf_counter() - t0
    setup = {"mapping_s": passes.get("mapping", 0.0),
             "lowering_s": passes.get("lowering", 0.0),
             "other_passes_s": sum(v for k, v in passes.items()
                                   if k not in ("mapping", "lowering")),
             "warmup_trace_and_compile_s": mosaic_s}
    check(stats["traces"] == len(BUCKETS),
          f"warmup traced {stats['traces']} times, expected {len(BUCKETS)}")
    return program, exe, setup


def phase_a(program, exe, mems, want, platform: str) -> dict:
    traces0 = engine_traces()
    t0 = time.perf_counter()
    outs = exe.run_batch(mems)
    wall = time.perf_counter() - t0
    info = exe.last_info
    check(engine_traces() == traces0,
          f"run_batch traced {engine_traces() - traces0} times")
    check(info.get("traced", 0) == 0, f"run_batch info traced={info}")
    check(len(outs) == len(mems), "run_batch lost samples")
    bad = sum(not same_outputs(program, o, w) for o, w in zip(outs, want))
    check(bad == 0, f"phase A: {bad}/{len(mems)} outputs differ from oracle")
    check_engines(platform)
    return {"wall_s": wall, "samples": len(mems),
            "buckets": info.get("buckets")}


def serve_requests(svc, tenants, rng):
    """Submit N_REQUESTS single-sample requests alternating over
    ``tenants``; returns [(tenant, program, mem, future)]."""
    sent = []
    for i in range(N_REQUESTS):
        name, program, target = tenants[i % len(tenants)]
        mem = program.random_inputs(rng)
        sent.append((name, program, mem,
                     svc.submit(program, target, mem, tenant=name)))
    return sent


def check_served(sent, svc, what: str) -> dict:
    from repro.core.dfg import interpret
    bad = 0
    for name, program, mem, fut in sent:
        out = fut.result(timeout=600)
        check("degraded_to" not in fut.info,
              f"{what}: request degraded to {fut.info.get('degraded_to')}")
        bad += not same_outputs(program, out,
                                interpret(program.dfg, mem,
                                          program.n_iters))
    check(bad == 0, f"{what}: {bad}/{len(sent)} responses differ from oracle")
    stats = svc.stats()
    check(stats["rejected"] == 0, f"{what}: rejects {stats['rejects']}")
    check(stats["breaker"]["degraded_batches_total"] == 0,
          f"{what}: degraded batches {stats['breaker']}")
    return stats


def tenants_hycube():
    from repro import ual
    target = ual.Target.from_name("hycube", rows=4, cols=4,
                                  backend="pallas")
    return [(name, ual.Program.from_kernel(
        name, n_banks=target.fabric.n_mem_ports), target)
        for name in ("gemm", "fft")]


def warm_service(svc, tenants, rng) -> float:
    """Set-up: one request per tenant class compiles and warms it."""
    t0 = time.perf_counter()
    for name, program, target in tenants:
        fut = svc.submit(program, target, program.random_inputs(rng),
                         tenant=name)
        fut.result(timeout=600)
    return time.perf_counter() - t0


def phase_b(cache, rng, platform: str) -> dict:
    from repro import ual
    tenants = tenants_hycube()
    with ual.Service(max_batch=MAX_BATCH, max_wait_ms=5, max_queue=1024,
                     cache=cache, warmup_buckets=BUCKETS) as svc:
        setup_s = warm_service(svc, tenants, rng)
        traces0 = engine_traces()
        t0 = time.perf_counter()
        sent = serve_requests(svc, tenants, rng)
        for *_, fut in sent:
            fut.result(timeout=600)
        wall = time.perf_counter() - t0
        check(engine_traces() == traces0,
              f"phase B traced {engine_traces() - traces0} times once warm")
        stats = check_served(sent, svc, "phase B")
    check_engines(platform)
    lat = sorted(fut.info["latency_ms"] for *_, fut in sent)
    return {"setup_s": setup_s, "wall_s": wall, "requests": len(sent),
            "mean_batch": stats["mean_batch"],
            "p50_ms": lat[len(lat) // 2],
            "p99_ms": lat[min(len(lat) - 1, int(len(lat) * 0.99))]}


def phase_c(program, exe, mems, want, platform: str) -> dict:
    traces0 = engine_traces()
    t0 = time.perf_counter()
    outs = []
    for chunk in exe.run_stream(mems, chunk=128):
        outs.extend(chunk)
    wall = time.perf_counter() - t0
    info = exe.last_info
    check(engine_traces() == traces0,
          f"run_stream traced {engine_traces() - traces0} times")
    check(len(outs) == len(mems), "run_stream lost samples")
    bad = sum(not same_outputs(program, o, w) for o, w in zip(outs, want))
    check(bad == 0, f"phase C: {bad}/{len(mems)} outputs differ from oracle")
    check_engines(platform)
    return {"wall_s": wall, "stream_chunks": info.get("stream_chunks"),
            "overlap_frac": info.get("overlap_frac")}


def one_chip(seed: int, platform: str) -> None:
    import numpy as np

    from repro.ual.cache import MappingCache
    cache = MappingCache(disk_dir=None)             # cold: nothing on disk
    rng = np.random.default_rng(seed)
    program, exe, setup = compile_gemm(cache)
    log(f"set-up (cold compile, seconds): {json.dumps(setup)}")
    mems = [program.random_inputs(rng) for _ in range(N_IMAGES)]
    want = oracle(program, mems)
    for name, fn in (("A run_batch", lambda: phase_a(program, exe, mems,
                                                     want, platform)),
                     ("B service", lambda: phase_b(cache, rng, platform)),
                     ("C run_stream", lambda: phase_c(program, exe, mems,
                                                      want, platform))):
        res = fn()
        log(f"phase {name} OK (smoke timing, not a benchmark): "
            f"{json.dumps(res, default=str)}")


def four_chips(seed: int, platform: str) -> None:
    import jax
    import numpy as np

    from repro import ual
    from repro.ual.cache import MappingCache
    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 devices, "
                             f"found {len(devices)}")
    cache = MappingCache(disk_dir=None)
    tenants = tenants_hycube()

    def serve(replicas, devs, label):
        rng = np.random.default_rng(seed)         # same traffic each time
        with ual.Service(max_batch=MAX_BATCH, max_wait_ms=5,
                         max_queue=1024, cache=cache, replicas=replicas,
                         devices=devs, warmup_buckets=BUCKETS) as svc:
            t0 = time.perf_counter()
            sent = serve_requests(svc, tenants, rng)
            outs = [fut.result(timeout=900) for *_, fut in sent]
            wall = time.perf_counter() - t0
            stats = check_served(sent, svc, label)
        log(f"{label} OK (smoke timing, not a benchmark): "
            f"{json.dumps({'wall_s': wall, 'requests': len(sent)})}")
        return sent, outs, stats

    sent, one, _ = serve(1, None, "service replicas=1")
    _, four, stats = serve(4, devices[:4], "service replicas=4")
    check(all(same_outputs(p, a, b)
              for (_, p, _, _), a, b in zip(sent, one, four)),
          "replicas=4 differs from replicas=1")
    slots = stats["router"]["slots"]
    log(f"router slots: {json.dumps(slots)}")
    for i, s in enumerate(slots):
        check(s["device"] == str(devices[i]),
              f"slot {i} on {s['device']}, expected {devices[i]}")
        check(s["batches"] > 0, f"slot {i} served no batches")
    placements = {name.rsplit("/", 1)[-1]
                  for name in stats["engine"]["per_engine"]}
    for i in range(4):
        check(f"dev:{devices[i].id}" in placements,
              f"no engine placed on dev:{devices[i].id}: {placements}")

    program, exe, setup = compile_gemm(cache)
    rng = np.random.default_rng(seed)
    mems = [program.random_inputs(rng) for _ in range(N_IMAGES)]
    single = exe.run_batch(mems)
    t0 = time.perf_counter()
    sharded = exe.run_batch(mems, backend="pallas_sharded")
    wall = time.perf_counter() - t0
    info = exe.last_info
    check(info.get("n_devices") == len(devices),
          f"pallas_sharded ran on {info.get('n_devices')} devices")
    want = oracle(program, mems)
    check(all(same_outputs(program, s, w) for s, w in zip(single, want)),
          "single-device run_batch differs from oracle")
    check(all(same_outputs(program, s, o) for s, o in zip(sharded, single)),
          "pallas_sharded differs from single-device run_batch")
    check_engines(platform)
    log(f"pallas_sharded OK over {info.get('n_devices')} devices "
        f"(smoke timing, not a benchmark): "
        f"{json.dumps({'wall_s': wall, 'samples': len(mems)})}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{dev.platform!r}); this smoke runs only on a TPU",
              file=sys.stderr)
        return 1
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: the repro sources are missing ({src})",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(src))

    log(f"device_kind: {dev.device_kind}  platform: {dev.platform}  "
        f"device_count: {len(devices)}")
    from repro.kernels.cgra_exec.kernel import use_compile_cache
    from repro.ual.engine import CompiledKernelCache, set_default_engine
    log(f"jax compilation cache: {use_compile_cache()}")
    set_default_engine(CompiledKernelCache())
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(args.seed, dev.platform)
    else:
        one_chip(args.seed, dev.platform)
    from repro.ual.engine import default_engine
    log(f"engine stats: {json.dumps(default_engine().stats(), default=str)}")
    log(f"total wall (smoke timing, not a benchmark): "
        f"{time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
