"""gemm_ncubed: MachSuite ``gemm/ncubed``, the whole product
``prod = m1 @ m2`` of two ``n`` x ``n`` row-major matrices per image:

    for i, j:  prod[i*n + j] = sum over k of m1[i*n + k] * m2[k*n + j]

``n_iters`` counts iterations of four products (``k`` unrolled by 4),
so the whole product is ``n**3 / 4`` of them.  The datapath wraps every
product and sum at ``bits``; the body has no shift, and the wrap commutes
with addition and multiplication, so the product is formed in int64 and
wrapped once at the end."""
from __future__ import annotations

import math
from typing import Dict

import numpy as np

from chipbench.references._wrap import wrap


def run(inputs: Dict[str, np.ndarray], n_iters: int, bits: int = 32
        ) -> Dict[str, np.ndarray]:
    m1 = np.asarray(inputs["m1"], np.int64)
    m2 = np.asarray(inputs["m2"], np.int64)
    images, words = m1.shape
    n = math.isqrt(words)
    if n * n != words or int(n_iters) != n ** 3 // 4:
        raise ValueError(f"gemm_ncubed: {n_iters} iterations over "
                         f"{words}-word matrices are not the whole product "
                         f"of two n x n matrices (n**3 / 4 iterations)")
    # int64 arithmetic wraps modulo 2**64, a multiple of 2**bits
    prod = np.matmul(m1.reshape(images, n, n), m2.reshape(images, n, n))
    return {"prod": wrap(prod.reshape(images, words), bits)
            .astype(np.int32)}
