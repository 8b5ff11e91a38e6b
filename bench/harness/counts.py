"""What a sparse product needs, counted from the matrix, and the chip's peaks.

The counts are of the work the product needs, not of the plan that
stores it: a plan that pads its tiles moves more bytes than these, and
its roofline share shows that as lost time. So a later format that
stores fewer padded entries raises the share and can never push it past
100%.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "peaks.json")


def spmm_bytes(nnz: int, rows: int, cols: int, batch: int) -> int:
    """Bytes one ``y = A @ X`` needs from HBM with ``B`` right-hand sides:
    each non-zero's float32 value and int32 column index once, ``X`` read
    and ``Y`` written once in float32."""
    return 8 * nnz + 4 * batch * (rows + cols)


def spmm_flops(nnz: int, batch: int) -> int:
    """A multiply and an add per non-zero and right-hand side."""
    return 2 * nnz * batch


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The peaks of ``device_kind``; a device the table lacks is an error."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def roofline_seconds(bytes_: float, flops: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds.

    The compute bound uses the bfloat16 peak, which no float32 product
    exceeds, so it never overstates the least time."""
    return max(bytes_ / peak["hbm_bytes_per_s"], flops / peak["bf16_flops_per_s"])
