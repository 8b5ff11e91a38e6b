"""Graph500's Kronecker graph generator, symmetrised for PageRank.

``2**scale`` vertices and ``edgefactor * 2**scale`` generated edges, each
edge placing its endpoints bit by bit with the initiator probabilities
``(a, b, c, d)`` as the Graph500 reference generator does, then a
random relabelling of the vertices and of the edge order, all from the
seed. The edge list is then symmetrised, its duplicates and self-loops
dropped, and every stored entry given the weight 1.
"""
from __future__ import annotations

import numpy as np


def edge_list(scale: int, edgefactor: int, abcd, rng: np.random.Generator):
    """The raw directed edge list ``(src, dst)`` of the Graph500 generator."""
    a, b, c, _ = (float(p) for p in abcd)
    m = edgefactor << scale
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for bit in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        src |= ii.astype(np.int64) << bit
        dst |= jj.astype(np.int64) << bit
    perm = rng.permutation(1 << scale)
    order = rng.permutation(m)
    return perm[src[order]], perm[dst[order]]


def generate(params: dict, seed: int) -> dict:
    """``{"shape", "row", "col", "val"}``: symmetric, unit weights, sorted."""
    scale = int(params["scale"])
    n = 1 << scale
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x6500]))
    src, dst = edge_list(scale, int(params["edgefactor"]), params["abcd"], rng)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    key = np.unique(np.concatenate([src * n + dst, dst * n + src]))
    return {
        "shape": (n, n),
        "row": (key // n).astype(np.int32),
        "col": (key % n).astype(np.int32),
        "val": np.ones(key.shape[0], np.float32),
    }
