"""The (kernel, fabric) deployments that every test of the kernel path covers.

The published fabrics are HyCUBE 4x4, N2N 4x4 and PACE 8x8; the kernels
are the ``core.kernel_lib`` entries small enough for interpret mode (the
1,024-point ``fft1024`` has tests of its own), and MachSuite's flattened
``gemm/ncubed`` at n = 8 (``SIZED``; ``kernel_lib`` registers it at the
suite's n = 64 as ``gemm64``).  PACE dct is left out: it maps cold in
about 166 s on a CPU host, more than every other pair together, and each
test file on its own xdist worker would pay it again.
"""
import pytest

from repro import ual
from repro.core import kernel_lib

#: builder keyword arguments for each published fabric
FABRICS = {"hycube": dict(rows=4, cols=4), "n2n": dict(rows=4, cols=4),
           "pace": {}}

#: kernels built here at a size interpret mode runs in seconds
SIZED = {"gemm_ncubed8": lambda: kernel_lib.gemm_ncubed(8)}

KERNELS = ("fft", "adpcm", "aes", "disparity", "dct", "nw", "gemm",
           "jax_poly", *SIZED)

#: ``(kernel_name, fabric)`` parameters, ids ``<fabric>-<kernel>``
DEPLOYMENTS = tuple(
    pytest.param(kernel, fabric, id=f"{fabric}-{kernel}")
    for fabric in FABRICS for kernel in KERNELS
    if (kernel, fabric) != ("dct", "pace"))


def program(kernel: str, **layout) -> ual.Program:
    """The ``ual.Program`` of a deployment's kernel: ``SIZED``'s builder,
    or ``Program.from_kernel``; ``layout`` is ``n_banks``/``bank_words``."""
    if kernel in SIZED:
        dfg, make_mem, n_iters = SIZED[kernel]()
        return ual.Program.from_dfg(dfg, n_iters, make_mem=make_mem,
                                    **layout)
    return ual.Program.from_kernel(kernel, **layout)
