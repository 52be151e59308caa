"""Paper Table II (validation rows): automated end-to-end checking.

Morpher's distinguishing features vs other open CGRA frameworks are test
data generation + validation against test data.  This bench runs the full
flow — layout -> map -> lower -> random test vectors -> DFG oracle vs the
vectorized batched simulator — for every kernel on HyCUBE and N2N, and
reports II, MII, mapper wall time and the validation verdict.  Each
kernel is checked on ``N_VECTORS`` random test vectors in ONE batched
engine sweep over the shared lowered artifact (the lower-once/run-many
path), not a per-sample Python loop.
"""
from __future__ import annotations

from repro import ual
from repro.core.kernel_lib import KERNELS

from benchmarks.common import fmt_table, save

N_VECTORS = 4
#: the paper's loop bodies at the repo's trip count; the MachSuite-size
#: deployments (``fft1024``: 5,120 iterations) belong to the chip benchmark
TABLE2_KERNELS = tuple(k for k in KERNELS if k != "fft1024")


def run(seed: int = 0, verbose: bool = True) -> dict:
    rows, data = [], {}
    targets = (("hycube4x4", ual.Target.from_name("hycube", rows=4, cols=4,
                                                  seed=seed)),
               ("n2n4x4", ual.Target.from_name("n2n", rows=4, cols=4,
                                               seed=seed)))
    for fab_name, target in targets:
        for name in TABLE2_KERNELS:
            program = ual.Program.from_kernel(
                name, n_banks=target.fabric.n_mem_ports)
            exe = ual.compile(program, target)
            rep = exe.validate(seed=seed, n_vectors=N_VECTORS)
            key = f"{name}@{fab_name}"
            data[key] = {
                "passed": rep.passed, "ii": rep.map_result.II,
                "mii": rep.map_result.mii,
                "wall_s": round(rep.map_result.wall_s, 2),
                "fu_util": round(rep.map_result.fu_util, 3),
                "mismatches": rep.mismatches,
                "n_vectors": rep.n_vectors,
                "cache_hit": exe.compile_info.cache_hit,
            }
            rows.append([key, rep.map_result.II, rep.map_result.mii,
                         data[key]["wall_s"], data[key]["fu_util"],
                         "PASS" if rep.passed else "FAIL"])
    claims = {
        "all_validated": all(d["passed"] for d in data.values()),
        "ii_reaches_mii_somewhere": any(d["ii"] == d["mii"]
                                        for d in data.values()),
        "compile_time_seconds": all(d["wall_s"] < 120 for d in data.values()),
    }
    payload = {"data": data, "claims": claims}
    save("table2_validation", payload)
    if verbose:
        print("== Table II: automated map->simulate->validate flow ==")
        print(fmt_table(["kernel@fabric", "II", "MII", "map s", "FU util",
                         "check"], rows))
        print("claims:", claims)
    return payload


def main():
    run()


if __name__ == "__main__":
    main()
