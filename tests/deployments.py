"""The (kernel, fabric) deployments that every test of the kernel path covers.

The published fabrics are HyCUBE 4x4, N2N 4x4 and PACE 8x8; the kernels
are the ``core.kernel_lib`` entries small enough for interpret mode (the
1,024-point ``fft1024`` has tests of its own).  PACE dct is left out: it
maps cold in about 166 s on a CPU host, more than every other pair
together, and each test file on its own xdist worker would pay it again.
"""
import pytest

#: builder keyword arguments for each published fabric
FABRICS = {"hycube": dict(rows=4, cols=4), "n2n": dict(rows=4, cols=4),
           "pace": {}}

KERNELS = ("fft", "adpcm", "aes", "disparity", "dct", "nw", "gemm",
           "jax_poly")

#: ``(kernel_name, fabric)`` parameters, ids ``<fabric>-<kernel>``
DEPLOYMENTS = tuple(
    pytest.param(kernel, fabric, id=f"{fabric}-{kernel}")
    for fabric in FABRICS for kernel in KERNELS
    if (kernel, fabric) != ("dct", "pace"))
