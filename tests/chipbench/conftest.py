"""Shared helpers of the benchmark's CPU tests: the repository root on
``sys.path`` (the benchmark lives beside ``src/``), tiny cells, the
faults planted under the timed path, and the four-replica cell run on
four virtual CPU devices."""
import dataclasses
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# Imported at collection, before any test runs, so that a test which
# monkeypatches ``repro.core.lowering.link_config`` and then
# ``repro.kernels.cgra_exec.ops.link_config`` cannot be the first to import
# ``ops``: that import would bind the patched function, and the patch's undo
# would restore it, leaking into every later test of the worker
# (``tests/test_exec_engine.py::test_lowering_cached_with_zero_relowering``).
import repro.kernels.cgra_exec.ops  # noqa: E402,F401


#: end-to-end metrics of a served (open-loop) cell
SERVED_METRICS = [{"name": "setup_s", "unit": "s"},
                  {"name": "p50_ms", "unit": "ms"},
                  {"name": "p95_ms", "unit": "ms"}]


def served_cell(config: str, chips: int = 1):
    """The deployment ``configs/<config>.json`` under the ``open`` mix: the
    open-loop cells that BENCHMARK.json does not hold yet, whose files
    the benchmark keeps (PERF.md, Open questions)."""
    from chipbench import generator, harness
    cfg = json.loads((ROOT / "chipbench" / "configs" / f"{config}.json")
                     .read_text())
    return harness.Cell(name=f"{config}.open", chips=chips, config=cfg,
                        mix=generator.load_mix("open"),
                        end_to_end=SERVED_METRICS, per_layer=[])


def cell_for(name: str):
    """A cell of BENCHMARK.json, or ``served:<config>``: the deployment
    under the open-loop mix that the benchmark keeps for later."""
    if name.startswith("served:"):
        return served_cell(name.split(":", 1)[1])
    from chipbench import harness
    return harness.load_cell(name)


def tiny(name, **mix):
    """The cell ``name`` of BENCHMARK.json (or a ``Cell``) at a size the
    CPU's Pallas interpreter runs in seconds: 4 banks x 64 words and
    8-lane chunks."""
    from chipbench import harness
    cell = name if isinstance(name, harness.Cell) else harness.load_cell(name)
    over = dict(pool=64)
    if cell.mix["loop"] == "closed":
        over.update(chunk=8, buckets=[8], sample_every=2)
    else:
        over.update(rate_per_s=100.0)
    over.update(mix)
    return dataclasses.replace(cell, config=dict(cell.config, bank_words=64),
                               mix=dict(cell.mix, **over))


def run_tiny(cell, *, seed=2**31 + 7, seconds=0.5, trace=False,
             control=False):
    from chipbench import harness
    return harness.run_cell(cell, seed, seconds, trace,
                            t_start=time.perf_counter(), control=control,
                            require_tpu=False, log=lambda msg: None)


# -- faults: each replaces the engine's traced kernel call --------------------

def _unchanged(real):
    def traced(self, niter, mem):
        self.traces += 1
        return mem
    return traced


def _half_left_out(real):
    def traced(self, niter, mem):
        out = real(self, niter, mem)
        half = mem.shape[0] // 2
        return out.at[half:].set(mem[half:])
    return traced


def _one_answer_altered(real):
    def traced(self, niter, mem):
        return real(self, niter, mem).at[0].add(1)
    return traced


FAULTS = {"state-unchanged": _unchanged, "half-left-out": _half_left_out,
          "answer-altered": _one_answer_altered}


def faulty_traced(fault: str):
    """``KernelEngine._traced`` with ``fault`` planted in it."""
    from repro.ual.engine import KernelEngine
    return FAULTS[fault](KernelEngine._traced)


def run_four_replicas(runs, seconds: float = 1.0) -> list:
    """The four-replica cell at a tiny size on four virtual CPU devices,
    in one child process that sets them up: one result line for each
    ``(fault, control)`` of ``runs`` (fault ``""`` for none)."""
    code = ("import json, sys; sys.path[:0] = [sys.argv[1], sys.argv[2]]\n"
            "from conftest import faulty_traced, run_tiny, served_cell, tiny\n"
            "from repro.ual.engine import KernelEngine\n"
            "sound = KernelEngine._traced\n"
            "for fault, control in json.loads(sys.argv[4]):\n"
            "    KernelEngine._traced = (faulty_traced(fault) if fault\n"
            "                            else sound)\n"
            "    r = run_tiny(tiny(served_cell('hycube4x4-gemm-r4', chips=4)),\n"
            "                 seconds=float(sys.argv[3]), control=control)\n"
            "    print(json.dumps(r), flush=True)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code, str(HERE),
                        str(ROOT / "src"), str(seconds), json.dumps(runs)],
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    return [json.loads(line) for line in p.stdout.strip().splitlines()
            if line.startswith("{")]


@pytest.fixture
def cpu_only():
    import jax
    if jax.default_backend() != "cpu":
        pytest.skip("runs the harness on the CPU's Pallas interpreter")
