"""Executor registry: how a planned PMVC actually runs.

An executor is a factory ``(session: SparseSession) -> Callable[[x],
y]`` — it may capture compiled steps, meshes, or host-side state; the
returned closure is **batch-first**: it maps a length-M numpy vector to
the length-N product, or a ``[B, M]`` stack of right-hand sides to the
``[B, N]`` stack of products through one SpMM (one exchange for all B).

Plan arrays are hoisted to device once, at executor construction — the
per-call hot path never re-pays host→device conversion.

Built-ins:

* ``"simulate"`` — vmap over a stacked unit axis on a single host (the
  CPU test / paper-reproduction path). Honors the session's exchange
  strategy: replicated gathers from the padded global x, selective runs
  the emulated all_to_all workspace path, overlap the pipelined
  local/halo split (DESIGN.md §9).
* ``"shard_map"`` — jitted shard_map over a device mesh, one unit per
  device (the production path; needs ``topology.units`` JAX devices,
  e.g. via ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
  Each unit's plan arrays and x shard are placed on its own device, so
  a device holds only its unit's tiles. :func:`default_executor` picks
  it for a multi-unit session where JAX has that many accelerators.
* ``"reference"`` — the thesis' sequential CSR algorithm (ch.1 §5),
  accumulated in float64: the oracle every other cell of the
  (partitioner × exchange × executor) space is pinned against.
  Vectorized over rows (segmented ``np.add.reduceat``) and over the
  batch, but numerically identical to the per-row loop.
"""
from __future__ import annotations

from typing import TYPE_CHECKING, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.api.registry import Registry
from repro.pmvc.dist import (
    hoist_plan,
    make_pmvc_step,
    make_unit_mesh,
    scatter_x_owned,
    unblock_y,
)
from repro.pmvc.plan_device import OverlapPlan
from repro.sparse.bell import pad_x_blocks
from repro.sparse.formats import csr_from_coo
from repro.tracing import FETCH, PUT, span

if TYPE_CHECKING:  # pragma: no cover - import cycle guard
    from repro.api.session import SparseSession

__all__ = ["EXECUTORS", "default_executor", "register_executor"]

EXECUTORS = Registry("executor")
register_executor = EXECUTORS.register

SpmvFn = Callable[[np.ndarray], np.ndarray]


def default_executor(units: int, devices=None) -> str:
    """The executor a session of ``units`` units runs by default:
    ``"shard_map"`` (one unit per device) where there are several units
    and ``devices`` (JAX's, if not given) hold an accelerator for each,
    else ``"simulate"`` (every unit on one device; all a CPU runs)."""
    if units > 1:
        devices = jax.devices() if devices is None else devices
        if sum(d.platform != "cpu" for d in devices) >= units:
            return "shard_map"
    return "simulate"


def _put_blocks(dp, x: np.ndarray, owned_by=None, sharding=None, halo_blocks=None) -> jax.Array:
    """``x`` padded into column blocks (laid out by unit as the selective
    plan ``owned_by`` says, if given) and copied to the device — whole to
    the default device, or as ``sharding`` lays it out — in one
    ``sparse.put`` span that counts its ``bytes``.

    On the ``shard_map`` path (``halo_blocks`` given: the x blocks the
    plan's all_to_all carries between units per right-hand side) the span
    also counts what the product then moves between the devices:
    ``halo_bytes``, those x blocks, and ``reduce_bytes``, the partial y
    that every unit adds into the ``psum``."""
    with span(PUT) as s:
        xb = pad_x_blocks(np.asarray(x, np.float32), dp.num_col_blocks, dp.bn)
        if owned_by is not None:
            xb = scatter_x_owned(owned_by, xb)
        counts = {"bytes": int(xb.nbytes)}
        if halo_blocks is not None:
            rhs = 4 * (1 if np.ndim(x) == 1 else len(x))  # float32 bytes per entry of a block
            counts["halo_bytes"] = halo_blocks * dp.bn * rhs
            counts["reduce_bytes"] = dp.num_units * dp.num_row_blocks * dp.bm * rhs
        s.set_metadata(**counts)
        return jnp.asarray(xb) if sharding is None else jax.device_put(xb, sharding)


def _fetch(y, n: int) -> np.ndarray:
    """Wait for the product ``y`` and bring it back unblocked, in one
    ``sparse.fetch`` span."""
    with span(FETCH, bytes=int(y.nbytes)):
        return unblock_y(jax.block_until_ready(y), n)


@register_executor("reference")
def reference_executor(session: "SparseSession") -> SpmvFn:
    csr = csr_from_coo(session.matrix)
    val64 = csr.val.astype(np.float64)
    col = np.asarray(csr.col)
    nrows = csr.shape[0]
    # Segment boundaries for the row-sum: starts of the non-empty rows.
    # Consecutive non-empty starts bound exactly one row's elements (empty
    # rows contribute no entries in between), so one reduceat replaces the
    # per-row Python loop; empty rows keep their zero.
    lengths = np.diff(csr.ptr)
    nonempty = np.nonzero(lengths > 0)[0]
    starts = np.asarray(csr.ptr[:-1])[nonempty]

    def spmv(x: np.ndarray) -> np.ndarray:
        xf = np.asarray(x, dtype=np.float64)
        squeeze = xf.ndim == 1
        x2 = xf[None] if squeeze else xf
        y = np.zeros((x2.shape[0], nrows), dtype=np.float64)
        if starts.size:
            y[:, nonempty] = np.add.reduceat(val64 * x2[:, col], starts, axis=1)
        out = y.astype(np.float32)
        return out[0] if squeeze else out

    return spmv


@register_executor("simulate")
def simulate_executor(session: "SparseSession") -> SpmvFn:
    dp = session.device_plan
    ops, body = session._hoisted()  # the tiles the device loops use too
    run = jax.jit(body)
    n = dp.shape[0]

    def spmv(x: np.ndarray) -> np.ndarray:
        return _fetch(run(ops, _put_blocks(dp, x)), n)

    return spmv


@register_executor("shard_map")
def shard_map_executor(session: "SparseSession") -> SpmvFn:
    dp, sp = session.device_plan, session.selective
    mesh = make_unit_mesh(dp.num_units)
    step = make_pmvc_step(dp, mesh, selective=sp)
    n = dp.shape[0]
    tt = session.tile_transform
    # Plan arrays and x shards go to their unit's device directly; y
    # comes back replicated from the psum.
    by_unit, whole = NamedSharding(mesh, P("unit")), NamedSharding(mesh, P())

    def hoist(arrays, tiles=(0,)):
        return hoist_plan(arrays, tt, tiles=tiles, sharding=by_unit)

    if isinstance(sp, OverlapPlan):
        op = sp
        ops = hoist(
            (
                op.local_tiles,
                op.local_row,
                op.local_slot,
                op.halo_tiles,  # [U, K, TH, bm, bn]
                op.halo_row,
                op.halo_slot,
                op.wave_send_idx,
                op.wave_recv_src,
                op.wave_recv_lane,
            ),
            tiles=(0, 3),
        )
        halo = op.selective.wire_blocks

        def spmv_overlap(x: np.ndarray) -> np.ndarray:
            # x_owned goes between the halo arrays and the wave schedule.
            xo = _put_blocks(dp, x, op.selective, by_unit, halo)
            return _fetch(step(*ops[:6], xo, *ops[6:]), n)

        return spmv_overlap

    if sp is None:
        ops = hoist((dp.tiles, dp.tile_row, dp.tile_col))

        def spmv(x: np.ndarray) -> np.ndarray:
            return _fetch(step(*ops, _put_blocks(dp, x, sharding=whole, halo_blocks=0)), n)

        return spmv

    ops = hoist((dp.tiles, dp.tile_row, sp.tile_col_local, sp.send_idx, sp.recv_src, sp.recv_lane))

    def spmv_selective(x: np.ndarray) -> np.ndarray:
        # x_owned goes between the tile arrays and the exchange schedule.
        xo = _put_blocks(dp, x, sp, by_unit, sp.wire_blocks)
        return _fetch(step(*ops[:3], xo, *ops[3:]), n)

    return spmv_selective
