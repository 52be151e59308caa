"""Batched-execution throughput: the lower-once / run-many payoff.

The cycle-accurate simulator is the validation workhorse of the whole
flow (Morpher's integrated map->simulate->validate loop), so its
per-sample cost gates every validate/DSE/serving scenario.  This bench
measures what the shared lowering pass + vectorized batched engine buy:
for one kernel per temporal fabric it sweeps batch sizes B in
{1, 8, 64, 256} through ``simulate_batch`` (all PEs of a cycle as array
ops, B scratchpad images stepping through the fabric simultaneously) and
compares per-sample cost against the scalar reference engine
(``simulate_reference``) on the very same lowered configuration —
asserting bit-exact outputs while it measures.

Claims checked (recorded as machine-checkable booleans):

  * >= 10x per-sample speedup at B=64 on every fabric,
  * bit-exact outputs between batched engine and reference on every
    checked sample,
  * throughput (samples/s) grows with the batch size.

The second half is the **pallas steady-state sweep** — the persistent
JIT engine's trace-once/run-many claim: N=256 mixed-size calls through
``ual.engine.CompiledKernelCache`` trace at most once per bucket of the
ladder (trace count stays O(#buckets)), and the post-warmup per-call
latency beats the old trace-every-call path (``cgra_exec`` rebuilding its
``pallas_call`` per invocation) by >= 10x — bit-exact vs the oracle.
"""
from __future__ import annotations

import time

import numpy as np

from repro import ual
from repro.core.dfg import interpret
from repro.core.simulator import (batched_engine, simulate_batch,
                                  simulate_reference)

from benchmarks.common import fmt_table, save

KERNEL = "gemm"
BATCHES = (1, 8, 64, 256)
FABRICS = (("hycube", dict(rows=4, cols=4)),
           ("n2n", dict(rows=4, cols=4)),
           ("pace", {}))

# pallas steady-state sweep: mixed micro-batch sizes (what the execution
# service's coalescer actually emits), cycled over N calls; a small
# scratchpad keeps the interpret-mode kernel cheap enough for CI
PALLAS_N_CALLS = 256
PALLAS_SIZES = (1, 2, 3, 5, 8, 13, 21, 32)
PALLAS_BUCKETS = (1, 8, 32)
PALLAS_BASELINE_CALLS = 2
PALLAS_BANK_WORDS = 64


def _pallas_steady_state(seed: int, verbose: bool) -> dict:
    """Trace-once/run-many vs trace-every-call on the pallas path."""
    # imported here, not at module top: this is the bench harness's first
    # jax use, and fork-based benches (dse_explore's compile_many pool)
    # must be able to spawn workers before jax starts its threads
    from repro.kernels.cgra_exec.kernel import cgra_exec
    from repro.ual.engine import CompiledKernelCache

    target = ual.Target.from_name("hycube", rows=4, cols=4, seed=seed,
                                  backend="pallas")
    program = ual.Program.from_kernel(KERNEL,
                                      n_banks=target.fabric.n_mem_ports,
                                      bank_words=PALLAS_BANK_WORDS)
    exe = ual.compile(program, target)
    if not exe.success:
        return {"mapped": False}
    n_iters = program.n_iters
    rng = np.random.default_rng(seed)
    pool = [program.random_inputs(rng) for _ in range(max(PALLAS_SIZES))]
    flats = program.flatten_batch(pool)
    oracle = [program.flatten(interpret(program.dfg, m, n_iters))
              for m in pool]

    # baseline: the old per-call path — cgra_exec rebuilds (re-traces,
    # re-lowers, re-uploads) its pallas_call on EVERY invocation
    base_wall = []
    for _ in range(PALLAS_BASELINE_CALLS):
        t0 = time.perf_counter()
        out = np.asarray(cgra_exec(exe.lowered, flats[:8], n_iters))
        base_wall.append(time.perf_counter() - t0)
    baseline_s = sum(base_wall) / len(base_wall)
    bitexact = all(np.array_equal(out[b], oracle[b]) for b in range(8))

    # steady state: a fresh engine (isolated counters), ladder warmed,
    # then N mixed-size calls — the service's traffic shape
    engine = CompiledKernelCache(buckets=PALLAS_BUCKETS)
    eng = engine.engine_for(exe.lowered)
    eng.warmup(program.layout.total_words)
    walls, by_size = [], {}
    for i in range(PALLAS_N_CALLS):
        B = PALLAS_SIZES[i % len(PALLAS_SIZES)]
        t0 = time.perf_counter()
        out, info = engine.run(exe.lowered, flats[:B], n_iters)
        wall = time.perf_counter() - t0
        walls.append(wall)
        by_size.setdefault(B, []).append(wall)
        if i % 37 == 0:                       # rolling parity spot-check
            bitexact &= all(np.array_equal(out[b], oracle[b])
                            for b in range(B))
    steady_b8_s = float(np.median(by_size[8]))
    stats = eng.stats()
    data = {
        "mapped": True, "ii": exe.II, "n_calls": PALLAS_N_CALLS,
        "sizes": list(PALLAS_SIZES), "buckets": list(eng.buckets),
        "traces": stats["traces"], "hit_ratio": stats["hit_ratio"],
        "padded_samples": stats["padded_samples"],
        "baseline_retrace_per_call_s": round(baseline_s, 4),
        "steady_state_b8_per_call_s": round(steady_b8_s, 5),
        "steady_state_mean_per_call_s": round(float(np.mean(walls)), 5),
        "speedup_vs_retrace": round(baseline_s / steady_b8_s, 1),
        "bitexact": bitexact,
    }
    if verbose:
        print("\n== pallas steady state: persistent JIT engine vs "
              "trace-every-call ==")
        print(fmt_table(
            ["calls", "traces", "buckets", "retrace ms", "steady ms (B=8)",
             "speedup", "bitexact"],
            [[PALLAS_N_CALLS, stats["traces"], str(list(eng.buckets)),
              round(baseline_s * 1e3, 1), round(steady_b8_s * 1e3, 2),
              f"{data['speedup_vs_retrace']}x",
              "ok" if bitexact else "MISMATCH"]]))
    return data


def run(seed: int = 0, verbose: bool = True) -> dict:
    rows, data = [], {}
    for fab_name, kwargs in FABRICS:
        target = ual.Target.from_name(fab_name, seed=seed, **kwargs)
        program = ual.Program.from_kernel(
            KERNEL, n_banks=target.fabric.n_mem_ports)
        exe = ual.compile(program, target)
        if not exe.success:
            data[fab_name] = {"mapped": False}
            continue
        n_iters = program.n_iters
        rng = np.random.default_rng(seed)
        B_max = max(BATCHES)
        flats = np.stack([program.flatten(program.random_inputs(rng))
                          for _ in range(B_max)])

        # scalar reference: time + outputs on a bounded sample count
        # (large fabrics pay ~P per cycle in pure Python; 8 samples give a
        # stable per-sample figure there, small fabrics check all 64)
        n_ref = 64 if target.fabric.n_pes <= 16 else 8
        t0 = time.perf_counter()
        ref_outs = [simulate_reference(exe.map_result.config, flats[b],
                                       n_iters)[0] for b in range(n_ref)]
        ref_wall = time.perf_counter() - t0
        ref_per_sample = ref_wall / n_ref

        # batched engine: every batch size, parity on the reference prefix.
        # Build the per-slot plans once, untimed, so the B=1 figure measures
        # steady-state execution, not one-time plan construction
        batched_engine(exe.lowered)
        per_b = {}
        bitexact = True
        for B in BATCHES:
            t0 = time.perf_counter()
            outs, stats = simulate_batch(exe.lowered, flats[:B], n_iters)
            wall = time.perf_counter() - t0
            for b in range(min(B, n_ref)):
                if not np.array_equal(outs[b], ref_outs[b]):
                    bitexact = False
            per_b[B] = {
                "wall_s": round(wall, 4),
                "per_sample_ms": round(wall / B * 1e3, 3),
                "throughput_sps": round(B / wall, 1),
                "speedup_vs_ref": round(ref_per_sample / (wall / B), 1),
            }
        data[fab_name] = {
            "mapped": True, "ii": exe.II, "n_pes": target.fabric.n_pes,
            "n_iters": n_iters, "ref_per_sample_ms":
                round(ref_per_sample * 1e3, 3),
            "ref_samples_checked": n_ref, "bitexact": bitexact,
            "batches": per_b,
            "lowered_cm_bytes": exe.lowered.cm_bytes(),
        }
        for B in BATCHES:
            d = per_b[B]
            rows.append([f"{KERNEL}@{target.fabric.name}", B,
                         d["per_sample_ms"], d["throughput_sps"],
                         f"{d['speedup_vs_ref']}x",
                         "ok" if bitexact else "MISMATCH"])

    pallas = _pallas_steady_state(seed, verbose)

    mapped = {k: v for k, v in data.items() if v.get("mapped")}
    claims = {
        "all_mapped": len(mapped) == len(FABRICS),
        "speedup_ge_10x_at_b64": all(
            d["batches"][64]["speedup_vs_ref"] >= 10 for d in mapped.values()),
        "bitexact_vs_reference": all(d["bitexact"] for d in mapped.values()),
        "throughput_scales_with_batch": all(
            d["batches"][256]["throughput_sps"]
            > d["batches"][1]["throughput_sps"] for d in mapped.values()),
        "pallas_mapped": bool(pallas.get("mapped")),
        "pallas_traces_bounded_by_buckets": bool(
            pallas.get("mapped")
            and pallas["traces"] <= len(pallas["buckets"])),
        "pallas_steady_state_ge_10x_vs_retrace": bool(
            pallas.get("mapped") and pallas["speedup_vs_retrace"] >= 10),
        "pallas_bitexact_vs_oracle": bool(pallas.get("mapped")
                                          and pallas["bitexact"]),
    }
    payload = {"data": data, "pallas_steady_state": pallas, "claims": claims,
               "kernel": KERNEL, "batches": list(BATCHES)}
    save("exec_throughput", payload)
    if verbose:
        print("== batched execution: vectorized sim vs scalar reference ==")
        print(fmt_table(["kernel@fabric", "B", "ms/sample", "samples/s",
                         "speedup", "bitexact"], rows))
        print("claims:", claims)
    return payload


def main():
    run()


if __name__ == "__main__":
    main()
