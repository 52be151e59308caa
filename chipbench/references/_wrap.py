"""Two's-complement wrap-around at a datapath width."""
from __future__ import annotations

import numpy as np


def wrap(x: np.ndarray, bits: int) -> np.ndarray:
    """``x`` (any integer array) wrapped to a signed ``bits``-bit value,
    returned as int64 so that the next operation cannot overflow."""
    x = np.asarray(x, np.int64)
    half = np.int64(1) << (bits - 1)
    return ((x + half) & ((half << 1) - 1)) - half
