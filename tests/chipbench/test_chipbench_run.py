"""The harness end to end at a tiny size on the CPU: every cell's path is
correct against the plain reference, the 16-bit control is rejected, and
the command refuses to run without a TPU or without the system."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT, cell_for, run_four_replicas, run_tiny, tiny

CELLS = ["hycube4x4-gemm.bulk", "pace8x8-fft.bulk", "served:hycube4x4-gemm"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct_at_a_tiny_size(cpu_only, name):
    cell = cell_for(name)
    res = run_tiny(tiny(cell))
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["answers_compared"]["value"] > 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in res["metrics"].values())


@pytest.mark.parametrize("name", CELLS)
def test_the_control_is_not_correct(cpu_only, name):
    res = run_tiny(tiny(cell_for(name)), control=True)
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] > 0


def _run_cli(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "hycube4x4-gemm.bulk", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_command_exits_nonzero_without_a_tpu():
    p = _run_cli(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_command_exits_nonzero_without_the_system(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_four_replica_cell_on_four_virtual_devices():
    """The four-replica cell's path (one replica per device, warmed from
    the harness) on four CPU devices; its control is rejected."""
    res, ctl = run_four_replicas([["", False], ["", True]])
    assert res["correct"] is True and res["device"]["count"] == 4
    assert set(res["metrics"]) == {"setup_s", "p50_ms", "p95_ms"}
    assert ctl["correct"] is False
