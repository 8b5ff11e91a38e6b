"""Conjugate gradients: ``session.solve("cg", b=[N])`` against textbook CG.

The request's ``"iters"`` iterations run from ``x = 0`` with ``"tol"``
(0: no early stop); the input is the right-hand side ``b``.
"""
from __future__ import annotations

import numpy as np

from harness.reference import Arith


def program_kwargs(request: dict, inp) -> dict:
    return {"iters": int(request["iters"]), "tol": float(request.get("tol", 0.0)), "b": inp}


def spmm_programs(request: dict) -> dict:
    """``{program: (products per run, right-hand sides)}`` of the programs
    that hold the sparse product: the host loop runs the program's
    ``jax.jit(body)`` (``repro.pmvc.dist.make_simulate_fn``) once per
    iteration."""
    return {"jit_body": (1, 1)}


def reference(matrix: dict, request: dict, inputs: list, precision: str) -> list:
    return [cg(matrix, b, int(request["iters"]), precision) for b in inputs]


def cg(matrix: dict, b: np.ndarray, iters: int, precision: str = "float64") -> np.ndarray:
    """``iters`` textbook CG iterations from ``x = 0`` (no early stop)."""
    ar = Arith(matrix, precision)
    r_ = ar.r
    x = np.zeros_like(r_(b))
    r = r_(b)
    p = r.copy()
    rs = float(r @ r)
    for _ in range(iters):
        ap = ar.mv(p)
        denom = float(p @ ap)
        if denom == 0.0:
            break
        alpha = rs / denom
        x = r_(x + alpha * p)
        r = r_(r - alpha * ap)
        rs_new = float(r @ r)
        p = r_(r + (rs_new / rs) * p)
        rs = rs_new
    return x
