import json

import pytest

from harness import counts


def test_spmm_bytes_count_the_matrix_and_the_vectors():
    # 8 bytes per non-zero (f32 value + i32 column), x read and y written once
    assert counts.spmm_bytes(nnz=10, rows=3, cols=4, batch=1) == 80 + 4 * (3 + 4)
    assert counts.spmm_bytes(nnz=10, rows=3, cols=4, batch=8) == 80 + 32 * 7
    # HPCG 104^3: 247 MB per product, whatever the plan stores
    assert counts.spmm_bytes(29_791_000, 1_124_864, 1_124_864, 1) == 247_326_912


def test_spmm_flops():
    assert counts.spmm_flops(nnz=10, batch=1) == 20
    assert counts.spmm_flops(nnz=882_046, batch=8) == 14_112_736


def test_roofline_takes_the_larger_bound():
    peak = {"hbm_bytes_per_s": 100.0, "bf16_flops_per_s": 1000.0}
    assert counts.roofline_seconds(200.0, 10.0, peak) == 2.0
    assert counts.roofline_seconds(1.0, 5000.0, peak) == 5.0


def test_peaks_table_by_device_kind(tmp_path):
    v5e = counts.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    with open(counts.PEAKS_FILE) as f:
        assert "TPU v5e" in json.load(f)["source"]
    with pytest.raises(KeyError):
        counts.peaks("cpu")
    other = tmp_path / "peaks.json"
    other.write_text(json.dumps({"source": "x", "devices": {"chip": {"hbm_bytes_per_s": 1.0}}}))
    assert counts.peaks("chip", str(other)) == {"hbm_bytes_per_s": 1.0}
