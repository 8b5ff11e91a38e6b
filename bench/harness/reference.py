"""The arithmetic of the plain references, in float64 and in the control's
lower precision.

Nothing here imports the program: the matrix comes from the
configuration's own generator, the product is SciPy's CSR product. Each
solver's algorithm is in ``bench/solvers/<solver>.py`` and runs on an
:class:`Arith`. ``precision="bfloat16"`` is the control: every stored
value and every vector rounded to bfloat16 (products accumulated in
float32, as a bfloat16 matrix unit does), the step below the float32
that the configurations state.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.sparse as sp

PRECISIONS = ("float64", "bfloat16")


def csr(matrix: dict, dtype=np.float64) -> sp.csr_matrix:
    """The generated matrix as a SciPy CSR product operator."""
    return sp.csr_matrix(
        (matrix["val"].astype(dtype), (matrix["row"], matrix["col"])),
        shape=matrix["shape"],
    )


def _bf(x: np.ndarray) -> np.ndarray:
    """Round to bfloat16 and back to float32 (the control's storage)."""
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


class Arith:
    """One precision's operator and rounding, shared by every solver."""

    def __init__(self, matrix: dict, precision: str, value_map=None):
        if precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}, got {precision!r}")
        self.low = precision == "bfloat16"
        vals = matrix["val"].astype(np.float64)
        if value_map is not None:
            vals = value_map(vals)
        a = csr({**matrix, "val": vals})
        self.a = a.astype(np.float32) if self.low else a
        if self.low:
            self.a.data = _bf(self.a.data)

    def r(self, x):
        """Round a vector to this precision's storage type."""
        return _bf(x) if self.low else np.asarray(x, np.float64)

    def mv(self, x):
        """``A @ x`` (``x`` ``[N]`` or ``[N, B]``), rounded."""
        return self.r(self.a @ self.r(x))
