"""PageRank: ``session.solve("pagerank", ...)`` against LDBC Graphalytics'
definition in float64.

The request gives ``"iters"`` (a fixed count: ``"tol"`` 0), ``"damping"``
and ``"device_loop"``; an input, where given, is one teleport vector
``seeds`` ``[N]`` (personalised PageRank), else the teleport is uniform.
"""
from __future__ import annotations

import numpy as np

from harness.reference import Arith


def program_kwargs(request: dict, inp) -> dict:
    kw = {
        "iters": int(request["iters"]),
        "tol": float(request.get("tol", 0.0)),
        "damping": float(request["damping"]),
        "device_loop": bool(request.get("device_loop", False)),
    }
    if inp is not None:
        kw["seeds"] = inp
    return kw


def spmm_programs(request: dict) -> dict:
    """``{program: (products per run, right-hand sides)}`` of the programs
    that hold the sparse product. The host loop runs the program's
    ``jax.jit(body)`` (``repro.pmvc.dist.make_simulate_fn``) once per
    iteration; ``device_loop`` runs all iterations in one ``lax.while_loop``
    program."""
    if request.get("device_loop"):
        return {"jit_while": (int(request["iters"]), 1)}
    return {"jit_body": (1, 1)}


def reference(matrix: dict, request: dict, inputs: list, precision: str) -> list:
    iters, damping = int(request["iters"]), float(request["damping"])
    if inputs[0] is None:
        return [pagerank(matrix, None, iters, damping, precision)] * len(inputs)
    return list(pagerank(matrix, np.stack(inputs), iters, damping, precision))


def pagerank(
    matrix: dict,
    seeds: np.ndarray | None,
    iters: int,
    damping: float,
    precision: str = "float64",
) -> np.ndarray:
    """PageRank over the column-stochastic ``P = |A| D^-1`` with dangling
    columns restarting at the teleport vector, L1-renormalised each
    iteration (LDBC Graphalytics' definition, with personalised
    teleports when ``seeds`` is given).

    ``seeds`` is ``None`` (uniform teleport, returns ``[N]``) or ``[B, N]``
    teleport weights (returns ``[B, N]``)."""
    ar = Arith(matrix, precision, value_map=np.abs)
    r_ = ar.r
    n = matrix["shape"][1]
    colsum = np.bincount(matrix["col"], weights=np.abs(matrix["val"].astype(np.float64)), minlength=n)
    dangling = colsum == 0.0
    inv_col = np.where(dangling, 0.0, 1.0 / np.where(dangling, 1.0, colsum))
    if seeds is None:
        s = np.full((n, 1), 1.0 / n)
    else:
        s = np.asarray(seeds, np.float64).T
        s = s / np.abs(s).sum(axis=0, keepdims=True)
    s = r_(s)
    x = s.copy()
    for _ in range(iters):
        dmass = (x * dangling[:, None]).sum(axis=0, keepdims=True)
        y = ar.mv(r_(x * inv_col[:, None])) + dmass * s
        x = r_(damping * y + (1.0 - damping) * s)
        x = r_(x / np.abs(x).sum(axis=0, keepdims=True))
    return x[:, 0] if seeds is None else x.T
