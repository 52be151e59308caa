"""One-time sweep of an open-loop cell's offered rate, to find its knee.

    python3 chipbench/sweep.py --workload hycube4x4-gemm.open \\
        --rates 1000,2000,4000 --seconds 5 --seed 7 [--out FILE]

Sets the cell up once, then offers each rate for ``--seconds`` through
the same driver the benchmark runs, and prints one JSON line per rate:
p50/p95 from due time, requests failed, the backlog (requests due by the
window's close and not answered by then), p95 of the last fifth of the
requests, the generator's lateness, the achieved batch size, and the
collector's pauses and the process's stalls seen by a watchdog.  The
knee is the highest rate whose p95 stays under the chosen limit with no
growing backlog; the cell's rate is set at about 4/5 of it, by hand, in
its traffic file.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


class Watchdog(threading.Thread):
    """Oversleeps of a 1 ms sleep loop on a thread of its own: a gap much
    longer than 1 ms is a stall of the whole process (the collector, or
    a call that holds the interpreter lock), not of one thread."""

    def __init__(self) -> None:
        super().__init__(name="sweep-watchdog", daemon=True)
        self.gaps = []
        self.stop = threading.Event()

    def run(self) -> None:
        t = time.perf_counter()
        while not self.stop.is_set():
            time.sleep(0.001)
            now = time.perf_counter()
            if now - t > 0.02:
                self.gaps.append(now - t)
            t = now

    def take(self) -> dict:
        gaps, self.gaps = self.gaps, []
        return {"stalls_over_20ms": len(gaps),
                "stall_max_ms": 1e3 * max(gaps, default=0.0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / "artifacts" /
                                                  "jax_cache")
    os.environ["REPRO_UAL_CACHE"] = str(ROOT / "artifacts" / "ual_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np

    from chipbench import harness
    from chipbench.stats import percentile
    from repro.ual.engine import CompiledKernelCache, set_default_engine

    cell = harness.load_cell(args.workload)
    devices = harness.devices_for(cell.chips, require_tpu=True)
    set_default_engine(CompiledKernelCache())
    drv = harness.Open(cell, args.seed, devices)
    print(json.dumps({"setup_s": time.perf_counter() - T_START}),
          flush=True)
    rows = []
    dog = Watchdog()
    dog.start()
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            before = drv.svc.stats()
            dog.take()
            with harness.GcPauses() as pauses:
                e2e = drv.window(args.seconds,
                                 lambda n: contextlib.nullcontext(), rate=rate)
            after = drv.svc.stats()
            done = np.where(np.isnan(drv.done), np.inf, drv.done)
            backlog = int(((drv.due_abs <= drv.t_close)
                           & (done > drv.t_close)).sum())
            lat = np.where(drv.failed_mask, np.inf, done - drv.due_abs) * 1e3
            tail = lat[int(len(lat) * 0.8):]
            batches = after["batches"] - before["batches"]
            row = {"rate_per_s": rate, "requests": len(lat),
                   "failed": int(drv.failed_mask.sum()), **e2e,
                   "p95_last_fifth_ms": (percentile(list(tail), 95)
                                         if len(tail) else None),
                   "backlog_at_close": backlog,
                   "mean_batch": ((after["completed"] - before["completed"])
                                  / batches if batches else None),
                   **drv.lateness(), "gc": pauses.summary(), **dog.take()}
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        dog.stop.set()
        dog.join()
        drv.close()
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("\n".join(json.dumps(r) for r in rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
