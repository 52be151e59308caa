"""fft_strided: MachSuite ``fft/strided``, the whole radix-2
decimation-in-frequency transform in place over ``xr``/``xi`` (output in
bit-reversed order), fixed point with Q8 twiddles ``wr``/``wi``.

Stage ``s`` (span ``n/2 >> s``) runs ``n/2`` independent butterflies; the
one on ``(even, odd)``, with ``root = (even << s) & (n - 1)``, computes

    dr = (xr[even] - xr[odd]) >> 1          di = (xi[even] - xi[odd]) >> 1
    xr[even] = (xr[even] + xr[odd]) >> 1    xi[even] = (xi[even] + xi[odd]) >> 1
    xr[odd] = (wr[root]*dr - wi[root]*di) >> 8
    xi[odd] = (wr[root]*di + wi[root]*dr) >> 8

(``>>`` is an arithmetic shift).  ``n_iters`` counts butterflies, so it
has to be a whole number of stages."""
from __future__ import annotations

from typing import Dict

import numpy as np

from chipbench.references._wrap import wrap


def run(inputs: Dict[str, np.ndarray], n_iters: int, bits: int = 32
        ) -> Dict[str, np.ndarray]:
    xr = np.asarray(inputs["xr"], np.int64).copy()
    xi = np.asarray(inputs["xi"], np.int64).copy()
    wr = np.asarray(inputs["wr"], np.int64)
    wi = np.asarray(inputs["wi"], np.int64)
    n = xr.shape[1]
    half = n // 2
    stages, rest = divmod(int(n_iters), half)
    if rest or not 0 <= stages <= half.bit_length():
        raise ValueError(f"fft_strided: {n_iters} iterations are not a whole "
                         f"number of the {half.bit_length()} stages of "
                         f"{half} butterflies")
    bf = np.arange(half)
    for s in range(stages):
        span = half >> s
        low = bf & (span - 1)
        odd = ((bf - low) << 1) | span | low
        even = odd ^ span
        root = (even << s) & (n - 1)
        er, orr, ei, oi = xr[:, even], xr[:, odd], xi[:, even], xi[:, odd]
        w_r, w_i = wr[:, root], wi[:, root]
        dr = wrap(er - orr, bits) >> 1
        di = wrap(ei - oi, bits) >> 1
        xr[:, even] = wrap(er + orr, bits) >> 1
        xi[:, even] = wrap(ei + oi, bits) >> 1
        xr[:, odd] = wrap(wrap(w_r * dr, bits) - wrap(w_i * di, bits),
                          bits) >> 8
        xi[:, odd] = wrap(wrap(w_r * di, bits) + wrap(w_i * dr, bits),
                          bits) >> 8
    return {"xr": xr.astype(np.int32), "xi": xi.astype(np.int32)}
