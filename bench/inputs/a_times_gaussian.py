"""A right-hand side ``b = A x*`` with ``x*`` standard normal, in float32."""
from __future__ import annotations

import numpy as np

from harness.reference import csr


def draw(matrix: dict, request: dict, count: int, rng: np.random.Generator) -> list:
    a = csr(matrix)
    n = matrix["shape"][1]
    return [(a @ rng.standard_normal(n)).astype(np.float32) for _ in range(count)]
