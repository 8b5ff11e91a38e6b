"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches JAX device state — the dry-run sets
``xla_force_host_platform_device_count`` *before* any JAX init and only
then calls these.

Axis semantics (DESIGN.md §2): ``pod`` = inter-pod DP (the paper's
grid-site level), ``data`` = intra-pod DP / sequence sharding (the
paper's cluster nodes), ``model`` = TP/EP (the paper's cores).
"""
from __future__ import annotations

from typing import Tuple

import jax

__all__ = [
    "make_production_mesh",
    "make_test_mesh",
    "batch_axes_of",
]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many host devices exist (tests / smoke)."""
    return jax.make_mesh((data, model), ("data", "model"))


def batch_axes_of(mesh) -> Tuple[str, ...]:
    names = tuple(mesh.axis_names)
    return tuple(a for a in ("pod", "data") if a in names)
