"""HPCG's operator: the 27-point stencil on an ``nx x ny x nz`` grid.

Row ``i = x + nx * (y + ny * z)`` couples to every grid neighbour within
the box (up to 26 of them) with value -1, and to itself with 26, as the
HPCG 3.1 reference code's ``GenerateProblem`` builds it. The matrix is
symmetric positive definite; a cube of side ``n`` has ``(3n - 2)**3``
non-zeros. The operator does not depend on the seed.
"""
from __future__ import annotations

import numpy as np


def generate(params: dict, seed: int) -> dict:
    """``{"shape", "row", "col", "val"}`` in row-major, column-sorted order."""
    del seed  # HPCG's operator is fixed; the right-hand sides carry the seed
    nx, ny, nz = (int(params[k]) for k in ("nx", "ny", "nz"))
    n = nx * ny * nz
    idx = np.arange(n, dtype=np.int64)
    gx, gy, gz = idx % nx, (idx // nx) % ny, idx // (nx * ny)
    # Offsets in (dz, dy, dx) order: the column index grows with the
    # offset, so the concatenation below is already sorted within a row.
    offsets = [(dz, dy, dx) for dz in (-1, 0, 1) for dy in (-1, 0, 1) for dx in (-1, 0, 1)]
    ok = np.empty((len(offsets), n), dtype=bool)
    for k, (dz, dy, dx) in enumerate(offsets):
        ok[k] = (
            (gx + dx >= 0) & (gx + dx < nx)
            & (gy + dy >= 0) & (gy + dy < ny)
            & (gz + dz >= 0) & (gz + dz < nz)
        )
    shift = np.array([dx + nx * (dy + ny * dz) for dz, dy, dx in offsets], np.int64)
    # [n, 27] layout, masked and flattened: rows ascending, columns
    # ascending within each row.
    okt = ok.T
    row = np.broadcast_to(idx[:, None], okt.shape)[okt]
    col = (idx[:, None] + shift[None, :])[okt]
    val = np.where(row == col, 26.0, -1.0).astype(np.float32)
    return {
        "shape": (n, n),
        "row": row.astype(np.int32),
        "col": col.astype(np.int32),
        "val": val,
    }
