"""Work of one ``cgra_exec`` call, computed from its shapes.

One call executes one (M, lanes) block of int32 scratchpad images: it has
to read the whole block from HBM and write it back, whatever implements
the simulation inside.  The linked configuration tables are left out:
they are II x PEs x a few dozen words, under 0.1 % of the block at
M = 8192 and 128 lanes, so leaving them out can only understate the
share.  The compute side has no published int32 VPU peak (``peaks``),
so only the memory bound is formed.
"""
from __future__ import annotations

WORD_BYTES = 4


def block_bytes(words: int, lanes: int) -> int:
    """HBM bytes one call moves: the block read in and written back."""
    return 2 * words * lanes * WORD_BYTES


def hbm_bound_s(words: int, lanes: int, hbm_bytes_per_s: float) -> float:
    """The least time one call could take at the HBM peak."""
    return block_bytes(words, lanes) / hbm_bytes_per_s
