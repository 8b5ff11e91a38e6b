"""Host spans at the layer boundaries of the solve path.

Each span is a ``jax.profiler.TraceAnnotation``: it lands in the
profiler's trace, on the same clock as the device's operations, when a
trace is being recorded (``jax.profiler.trace(dir)``), and is dropped at
once otherwise. The profiler is the only switch. Counts ride on the span
that does the work, as arguments, which the profiler stores as stats of
the event.

Every span of one solve nests inside its :data:`SOLVE` span on the same
thread; a span's self time is its duration less that of the spans nested
in it.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

__all__ = ["FETCH", "HOIST", "PUT", "SOLVE", "TRACE", "UPDATE", "span"]

SOLVE = "sparse.solve"
"""One ``SparseSession.solve``; args ``solver``, ``iters`` (the budget),
``batch`` (right-hand sides) and ``solve`` (a per-process sequence number)."""

HOIST = "sparse.hoist"
"""A plan's arrays moved to the device (tiles and index arrays), once per
session and executor; args ``bytes`` (all of them), ``units`` and
``bytes_per_device`` (what the fullest device receives: all of it on one
device, a unit's share where the ``shard_map`` executor places each
unit's arrays on its own device)."""

TRACE = "sparse.trace"
"""The ``lax.while_loop`` call of a device loop: trace, lower, compile or
load from the persistent cache, and dispatch; args ``solver`` and
``cached``, true when the session's loop program was reused (dispatch
only)."""

PUT = "sparse.put"
"""Host vectors copied to the device: an executor's x, padded into column
blocks, or a device loop's operands; arg ``bytes``. On the ``shard_map``
path also ``halo_bytes``, the x blocks the product's exchange moves
between units (from the plan), and ``reduce_bytes``, the partial y that
its ``psum`` sums."""

FETCH = "sparse.fetch"
"""The host waits for a device result and copies it back; arg ``bytes``."""

UPDATE = "sparse.update"
"""A solver's host arithmetic between two products."""


def span(name: str, **args) -> TraceAnnotation:
    """A context manager that records ``name`` with ``args`` while a trace
    is being recorded."""
    return TraceAnnotation(name, **args)
