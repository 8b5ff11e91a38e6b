import os

import numpy as np
import pytest

from harness.files import BENCH, load_module

stencil27 = load_module(os.path.join(BENCH, "matrices", "stencil27.py"))
kronecker = load_module(os.path.join(BENCH, "matrices", "kronecker.py"))


def dense(m):
    a = np.zeros(m["shape"])
    a[m["row"], m["col"]] = m["val"]
    return a


@pytest.mark.parametrize("n", [2, 3, 5, 8])
def test_stencil_nnz_is_3n_minus_2_cubed(n):
    m = stencil27.generate({"nx": n, "ny": n, "nz": n}, seed=0)
    assert m["shape"] == (n**3, n**3)
    assert m["val"].shape[0] == (3 * n - 2) ** 3


def test_stencil_is_symmetric_with_hpcg_values():
    m = stencil27.generate({"nx": 4, "ny": 3, "nz": 5}, seed=0)
    a = dense(m)
    assert np.array_equal(a, a.T)
    assert np.all(np.diag(a) == 26.0)
    off = a[~np.eye(a.shape[0], dtype=bool)]
    assert set(np.unique(off)) <= {0.0, -1.0}
    # an interior point of a 3x3x3 box touches all 26 neighbours
    inner = stencil27.generate({"nx": 3, "ny": 3, "nz": 3}, seed=0)
    assert np.count_nonzero(dense(inner)[13]) == 27
    assert np.all(np.linalg.eigvalsh(a) > 0)


def test_stencil_rows_sorted_and_seed_free():
    m = stencil27.generate({"nx": 5, "ny": 4, "nz": 3}, seed=1)
    key = m["row"].astype(np.int64) * m["shape"][1] + m["col"]
    assert np.all(np.diff(key) > 0)
    other = stencil27.generate({"nx": 5, "ny": 4, "nz": 3}, seed=2**31 + 7)
    assert np.array_equal(m["col"], other["col"])


def test_kronecker_edge_list_count_and_range():
    rng = np.random.default_rng(0)
    src, dst = kronecker.edge_list(10, 16, (0.57, 0.19, 0.19, 0.05), rng)
    assert src.shape == dst.shape == (16 << 10,)
    assert src.min() >= 0 and max(src.max(), dst.max()) < 1 << 10
    # the initiator's skew: vertex degrees are far from uniform
    deg = np.bincount(np.concatenate([src, dst]), minlength=1 << 10)
    assert deg.max() > 8 * deg.mean()


def test_kronecker_graph_symmetric_unit_deduplicated():
    params = {"scale": 8, "edgefactor": 16, "abcd": [0.57, 0.19, 0.19, 0.05]}
    m = kronecker.generate(params, seed=2**33 + 1)
    a = dense(m)
    assert np.array_equal(a, a.T)
    assert np.all(m["val"] == 1.0)
    assert np.all(m["row"] != m["col"])
    key = m["row"].astype(np.int64) * m["shape"][1] + m["col"]
    assert np.unique(key).shape[0] == key.shape[0]
    # at most twice the generated edges survive symmetrising
    assert m["val"].shape[0] <= 2 * (16 << 8)
    same = kronecker.generate(params, seed=2**33 + 1)
    assert np.array_equal(same["col"], m["col"])
    assert not np.array_equal(kronecker.generate(params, seed=5)["col"], m["col"])
