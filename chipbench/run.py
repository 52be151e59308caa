"""Run one benchmark cell once on the chip(s) of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``.  Set-up (JAX start,
mapping, the warm-up of every bucket the cell uses, the input pool) is
timed as ``setup_s``; then the window runs for ``--seconds``; then every
answer kept from the window is compared with the plain reference.  The
last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``checks``); the numbers compared are also
the last lines of standard error.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiler
trace of the window.

JAX's persistent compilation cache and the mapping cache live inside the
checkout (``artifacts/``).  Without a TPU, or with fewer chips than the
cell needs, the run exits 2 and prints no result.  ``--control 1``
replaces the answers with the reference on a 16-bit datapath, which the
comparison must reject; the benchmark's own runs never set it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: the system under test is missing "
              f"({ROOT / 'src' / 'repro'})", file=sys.stderr)
        return 2
    artifacts = ROOT / "artifacts"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(artifacts / "jax_cache")
    os.environ["REPRO_UAL_CACHE"] = str(artifacts / "ual_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    from chipbench import harness
    cell = harness.load_cell(args.workload)

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START,
                                  control=bool(args.control), log=log)
    except harness.NoChip as exc:
        log(f"chipbench: {exc}")
        return 2
    for name, c in result["checks"].items():
        bound = (f"limit {c['limit']}" if "limit" in c
                 else f"at least {c['min']}")
        log(f"check {name}: {c['value']} ({bound})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
