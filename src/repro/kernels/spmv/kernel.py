"""Pallas TPU kernel for Block-ELL SpMM — the PMVC hot spot.

TPU adaptation of the paper's ``csr_double_mv`` (spBLAS level 2/3):
instead of scalar CSR gathers, each grid step streams one dense
(bm × bn) tile from HBM into VMEM, multiplies it against the matching
block of stacked right-hand sides (fetched via a *scalar-prefetched*
data-dependent BlockSpec index — the TPU equivalent of the paper's
"selective X exchange"), and accumulates into a VMEM-resident local y.
The y shard is flushed once, at the last grid step.

Batch-first: x arrives as ``[NCB, bn, B]`` — B stacked vectors per
block-column — so each grid step is a ``(bm × bn) @ (bn × B)`` MXU
matmul. The scatter/gather phases the paper measures in ch.4 are paid
once per *batch*, not once per vector; B is the amortization knob.
``bell_spmv`` keeps the single-vector entry as the B = 1 special case.

VMEM working set per step: bm·bn·4 (tile) + bn·B·4 (x block) +
R·bm·B·4 (y accumulator). With bm = bn = 128, B = 8 and R ≤ 64
block-rows this is ~64 KiB + 4 KiB + 256 KiB — comfortably inside the
~16 MiB VMEM budget, leaving room for double-buffered tile streaming
(Pallas pipelines the next tile fetch automatically).

Grid iterations are sequential on a TensorCore, so read-modify-write of
the accumulator across steps is sound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["bell_spmv", "bell_spmm"]


def _spmm_kernel(
    # scalar-prefetch refs
    tile_row_ref,
    tile_col_ref,
    # inputs
    tiles_ref,  # [1, bm, bn] block of the padded tile stream
    x_ref,  # [1, bn, B]  x block selected by tile_col (prefetch index map)
    # outputs
    y_ref,  # [R, bm, B]  local y shard (written at last step)
    # scratch
    acc_ref,  # VMEM [R, bm, B] accumulator
):
    t = pl.program_id(0)
    nt = pl.num_programs(0)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    r = tile_row_ref[t]
    # (bm, bn) @ (bn, B) on the MXU; padded tiles are all-zero so they
    # are numerically inert (the padding cost is exactly the LB waste).
    contrib = jnp.dot(
        tiles_ref[0], x_ref[0], preferred_element_type=jnp.float32
    )
    acc_ref[pl.ds(r, 1), :, :] += contrib[None]

    @pl.when(t == nt - 1)
    def _flush():
        y_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("num_row_blocks", "interpret"))
def bell_spmm(
    tiles: jax.Array,  # [T, bm, bn]
    tile_row: jax.Array,  # [T] int32 local block-row
    tile_col: jax.Array,  # [T] int32 global block-col
    x_blocks: jax.Array,  # [NCB, bn, B] stacked x's reshaped into blocks
    num_row_blocks: int | jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Compute the local y shard ``[R, bm, B]`` for one compute unit."""
    t, bm, bn = tiles.shape
    b = x_blocks.shape[-1]
    r = int(num_row_blocks)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, bm, bn), lambda i, rows, cols: (i, 0, 0)),
            pl.BlockSpec((1, bn, b), lambda i, rows, cols: (cols[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((r, bm, b), lambda i, rows, cols: (0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((r, bm, b), jnp.float32)],
    )
    return pl.pallas_call(
        _spmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, bm, b), jnp.float32),
        interpret=interpret,
    )(tile_row, tile_col, tiles, x_blocks)


@functools.partial(jax.jit, static_argnames=("num_row_blocks", "interpret"))
def bell_spmv(
    tiles: jax.Array,  # [T, bm, bn]
    tile_row: jax.Array,  # [T] int32 local block-row
    tile_col: jax.Array,  # [T] int32 global block-col
    x_blocks: jax.Array,  # [NCB, bn] x reshaped into blocks
    num_row_blocks: int | jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Compute the local y shard ``[R, bm]`` for one compute unit (B = 1)."""
    y = bell_spmm(
        tiles,
        tile_row,
        tile_col,
        x_blocks[..., None],
        int(num_row_blocks),
        interpret=interpret,
    )
    return y[..., 0]
