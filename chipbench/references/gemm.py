"""gemm: inner-product accumulation, k-loop unrolled by 4.

Iteration i (0 <= i < n_iters) reads A[4i..4i+3] and B[4i..4i+3], adds
their four products to the running sum and stores the sum to C[0]."""
from __future__ import annotations

from typing import Dict

import numpy as np

from chipbench.references._wrap import wrap


def run(inputs: Dict[str, np.ndarray], n_iters: int, bits: int = 32
        ) -> Dict[str, np.ndarray]:
    a = np.asarray(inputs["A"], np.int64)
    b = np.asarray(inputs["B"], np.int64)
    acc = np.zeros(a.shape[0], np.int64)
    for i in range(n_iters):
        k = 4 * i
        p = [wrap(a[:, k + u] * b[:, k + u], bits) for u in range(4)]
        s = wrap(wrap(p[0] + p[1], bits) + wrap(p[2] + p[3], bits), bits)
        acc = wrap(acc + s, bits)
    return {"C": acc.astype(np.int32).reshape(-1, 1)}
