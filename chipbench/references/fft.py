"""fft: one radix-2 butterfly per iteration, fixed point with Q8
twiddles.  Iteration i computes

    tr = (br*wr - bi*wi) >> 8        ti = (br*wi + bi*wr) >> 8
    or0 = ar + tr    oi0 = ai + ti    or1 = ar - tr    oi1 = ai - ti

on element i of each array (``>>`` is an arithmetic shift)."""
from __future__ import annotations

from typing import Dict

import numpy as np

from chipbench.references._wrap import wrap

OUTPUTS = ("or0", "oi0", "or1", "oi1")


def run(inputs: Dict[str, np.ndarray], n_iters: int, bits: int = 32
        ) -> Dict[str, np.ndarray]:
    x = {k: np.asarray(v, np.int64) for k, v in inputs.items()}
    n, length = x["ar"].shape
    out = {k: np.zeros((n, length), np.int64) for k in OUTPUTS}
    i = slice(0, n_iters)
    ar, ai, br, bi = (x[k][:, i] for k in ("ar", "ai", "br", "bi"))
    wr, wi = x["wr"][:, i], x["wi"][:, i]
    t1, t2 = wrap(br * wr, bits), wrap(bi * wi, bits)
    t3, t4 = wrap(br * wi, bits), wrap(bi * wr, bits)
    tr = wrap(t1 - t2, bits) >> 8
    ti = wrap(t3 + t4, bits) >> 8
    out["or0"][:, i] = wrap(ar + tr, bits)
    out["oi0"][:, i] = wrap(ai + ti, bits)
    out["or1"][:, i] = wrap(ar - tr, bits)
    out["oi1"][:, i] = wrap(ai - ti, bits)
    return {k: v.astype(np.int32) for k, v in out.items()}
