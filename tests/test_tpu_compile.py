"""Compile-only guard: the ``cgra_exec`` kernel compiles for a TPU v5e.

The TPU compiler is installed even where no chip is attached, and it
compiles for a *described* chip.  These tests compile the kernel of the
main path at the real width — the default scratchpad (M = 8192) and the
engine's bucket sizes 8 and 128, on the published fabrics, and the
benchmark's two FFTs on PACE 8x8 at 128 lanes — for one chip
of a described ``v5e:2x2``, and check that the Mosaic kernel is in the
compiled program.  Nothing
runs, so they say nothing about results or times; the interpret-mode
tests (``test_engine.py`` and friends) hold the semantics.

The topology is described inside a fixture, never at import: only one
process may load the TPU runtime, and every test worker imports this
file.  The persistent compile cache is off around these compiles — an
entry compiled for a described chip cannot be read back without one.
"""
import os

import pytest

from deployments import FABRICS
from repro import ual

M = 8192


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


#: gemm on every fabric at both widths, and the benchmark's two FFTs on
#: PACE (a butterfly stage, and the whole 1,024-point transform)
CASES = [pytest.param(fabric, bB, "gemm", id=f"{fabric}-{bB}")
         for fabric in sorted(FABRICS) for bB in (8, 128)] + [
    pytest.param("pace", 128, k, id=f"pace-128-{k}")
    for k in ("fft", "fft1024")]


@pytest.mark.parametrize("fabric,bB,kernel_name", CASES)
def test_cgra_exec_compiles_for_v5e(fabric, bB, kernel_name, one_chip,
                                    no_persistent_cache, monkeypatch):
    import jax
    import jax.numpy as jnp

    from repro.core.lowering import kernel_tables
    from repro.kernels.cgra_exec import kernel

    # the platform here is the CPU: steer the kernel to its TPU branch,
    # and keep the persistent cache where the fixture put it
    monkeypatch.setattr(kernel, "interpret_mode", lambda: False)
    monkeypatch.setattr(kernel, "use_compile_cache", lambda: None)

    program = ual.Program.from_kernel(kernel_name)
    assert program.layout.total_words == M
    exe = ual.compile(program, ual.Target.from_name(
        fabric, backend="pallas", **FABRICS[fabric]))
    assert exe.success
    call = kernel.make_cgra_call(exe.lowered, M=M, bB=bB)

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.int32, sharding=one_chip)

    args = ([shape(1, 1)]
            + [shape(*t.shape) for t in kernel_tables(exe.lowered)]
            + [shape(M, bB)])
    text = jax.jit(call).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    # the kernel's own name on the custom call, which the profiler's
    # device events carry whatever jitted function encloses it
    call_lines = [ln for ln in text.splitlines() if " custom-call(" in ln]
    assert call_lines
    assert all("%cgra_exec" in ln.partition(" = ")[0] for ln in call_lines)
