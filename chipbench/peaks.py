"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
16 GB of HBM2 at 819 GB/s per chip, 197 TFLOP/s bf16, 393 TOP/s int8.
The ``cgra_exec`` kernel is int32 VPU work (selects, compares, adds and
multiplies over (rows, lanes) blocks); no int32 VPU rate of the v5e is
published, so its compute bound is not given and no roofline share is
taken against an assumed one.
"""
from __future__ import annotations

from typing import Dict, Optional

PEAKS: Dict[str, Dict[str, Optional[float]]] = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "int32_vpu_ops_per_s": None,     # not given
    },
}

SOURCE = 'Google Cloud documentation, "TPU v5e"'


def peaks_for(device_kind: str) -> Dict[str, Optional[float]]:
    """The peaks of ``device_kind``; a kind that is not in the table is
    an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
