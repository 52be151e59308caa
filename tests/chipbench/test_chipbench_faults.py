"""Faults planted under the timed path must turn ``correct`` false.

Each fault replaces the engine's traced kernel call (``conftest.FAULTS``):
a step that returns the scratchpad unchanged, half of every block left
out (returned as it went in), and one answer altered where it is
produced.  The cells run no exchange between chips (replicas are
independent), so that fault does not apply."""
import pytest

from conftest import (FAULTS, cell_for, faulty_traced, run_four_replicas,
                      run_tiny, tiny)


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", ["hycube4x4-gemm.bulk", "pace8x8-fft.bulk",
                                  "served:hycube4x4-gemm"])
def test_planted_fault_is_not_correct(cpu_only, monkeypatch, name, fault):
    from repro.ual.engine import KernelEngine
    monkeypatch.setattr(KernelEngine, "_traced", faulty_traced(fault))
    res = run_tiny(tiny(cell_for(name)))
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["wrong_answers"]["value"] > 0


@pytest.fixture(scope="module")
def four_replica_faults():
    faults = sorted(FAULTS)
    return dict(zip(faults, run_four_replicas([[f, False] for f in faults])))


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct_on_four_replicas(four_replica_faults,
                                                       fault):
    res = four_replica_faults[fault]
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["wrong_answers"]["value"] > 0
