import os

import numpy as np
import pytest

from harness.files import BENCH, load_module

cg = load_module(os.path.join(BENCH, "solvers", "cg.py"))
pagerank = load_module(os.path.join(BENCH, "solvers", "pagerank.py"))


def small_spd():
    rng = np.random.default_rng(0)
    n = 30
    m = rng.random((n, n)) * (rng.random((n, n)) < 0.2)
    a = m + m.T + n * np.eye(n)
    row, col = np.nonzero(a)
    return {"shape": (n, n), "row": row, "col": col, "val": a[row, col].astype(np.float32)}, a


def test_cg_reaches_the_solution_in_float64():
    m, a = small_spd()
    b = np.arange(1.0, 31.0)
    x = cg.cg(m, b, iters=60)
    assert np.allclose(x, np.linalg.solve(a.astype(np.float32).astype(np.float64), b), rtol=1e-10)


def test_bfloat16_control_is_far_from_float64():
    m, _ = small_spd()
    b = np.linspace(-1.0, 2.0, 30)
    x64 = cg.cg(m, b, iters=10)
    xbf = cg.cg(m, b, iters=10, precision="bfloat16")
    err = np.linalg.norm(xbf - x64) / np.linalg.norm(x64)
    assert 1e-4 < err < 1e-1
    with pytest.raises(ValueError):
        cg.cg(m, b, iters=1, precision="float16")


def test_pagerank_matches_a_dense_power_iteration():
    g = np.array([[0, 1, 1, 0], [1, 0, 0, 0], [1, 0, 0, 0], [0, 0, 0, 0]], float)  # vertex 3 dangling
    row, col = np.nonzero(g)
    m = {"shape": (4, 4), "row": row, "col": col, "val": -g[row, col]}  # |A| is used
    d = 0.85
    colsum = g.sum(axis=0)
    p = np.divide(g, colsum, out=np.zeros_like(g), where=colsum > 0)
    for seeds in (None, np.array([[0.0, 0.0, 3.0, 1.0]])):
        s = np.full(4, 0.25) if seeds is None else seeds[0] / seeds[0].sum()
        r = s.copy()
        for _ in range(25):
            r = d * (p @ r + (r * (colsum == 0)).sum() * s) + (1 - d) * s
            r /= r.sum()
        got = pagerank.pagerank(m, seeds, 25, d)
        got = got if seeds is None else got[0]
        assert got.sum() == pytest.approx(1.0)
        assert np.allclose(got, r, rtol=1e-12)
