"""cgra_exec validation: the Pallas kernel (interpret mode off a TPU)
BIT-EXACT against the cycle-accurate simulator on every deployment of
``deployments.DEPLOYMENTS`` (the Morpher validation flow, Table II), once
through the dense whole-scratchpad tables and once through the bounded
scratchpad passes that the compile pipeline lowers.
"""
import numpy as np
import pytest

from deployments import DEPLOYMENTS, FABRICS
from repro import ual
from repro.kernels.cgra_exec.ops import cgra_exec_op
from repro.kernels.cgra_exec.ref import cgra_exec_ref


def _compiled(kernel_name, fabric):
    """Compile via the UAL so identical pairs are mapped once per session
    (the conftest installs a shared mapping cache)."""
    exe = ual.compile(ual.Program.from_kernel(kernel_name),
                      ual.Target.from_name(fabric, **FABRICS[fabric]))
    assert exe.success, f"{kernel_name} failed to map on {fabric}"
    return exe


def _images(program, seed, n=3):
    rng = np.random.default_rng(seed)
    return program.flatten_batch([program.make_mem(rng) for _ in range(n)])


@pytest.mark.parametrize("kernel_name,fabric", DEPLOYMENTS)
def test_cgra_exec_bitexact(kernel_name, fabric):
    """The dense path: the config lowered by ``link_config(cfg)`` without
    a layout, so every LOAD/STORE pass scans the whole scratchpad."""
    exe = _compiled(kernel_name, fabric)
    cfg, n_iters = exe.map_result.config, exe.program.n_iters
    mems = _images(exe.program, seed=5)
    np.testing.assert_array_equal(cgra_exec_op(cfg, mems, n_iters),
                                  cgra_exec_ref(cfg, mems, n_iters))


@pytest.mark.parametrize("kernel_name,fabric", DEPLOYMENTS)
def test_cgra_exec_bounded_passes_bitexact(kernel_name, fabric):
    """The compile pipeline's tables bound every LOAD/STORE pass to its
    array's chunks (``LinkedConfig.mem_rows``): bit-exact against the
    cycle-accurate simulator on every kernel, at the default M = 8192."""
    from repro.core.lowering import mem_chunk_counts
    exe = _compiled(kernel_name, fabric)
    cfg, n_iters = exe.map_result.config, exe.program.n_iters
    bounded, dense = mem_chunk_counts(exe.lowered,
                                      exe.program.layout.total_words)
    assert 0 < bounded < dense
    mems = _images(exe.program, seed=7)
    np.testing.assert_array_equal(
        cgra_exec_op(cfg, mems, n_iters, linked=exe.lowered),
        cgra_exec_ref(cfg, mems, n_iters))


def test_cgra_exec_matches_dfg_oracle():
    """Three-way agreement: DFG interpreter == simulator == Pallas kernel."""
    from repro.core.dfg import interpret
    exe = _compiled("gemm", "hycube")
    program = exe.program
    mem_named = program.make_mem(np.random.default_rng(9))
    expect = interpret(program.dfg, mem_named, program.n_iters)
    out = cgra_exec_op(exe.map_result.config, program.flatten_batch([mem_named]),
                       program.n_iters)[0]
    got = program.unflatten(out)
    for name in program.dfg.outputs:
        np.testing.assert_array_equal(got[name], expect[name])
