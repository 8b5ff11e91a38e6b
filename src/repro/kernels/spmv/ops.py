"""Jitted public entry points for the BELL SpMM/SpMV kernel.

``spmv_shard`` / ``spmm_shard`` run the Pallas kernel, compiled for the
TPU unless the caller asks for ``interpret=True`` (how it runs on a
CPU); ``pack_inputs`` converts a host-side
:class:`repro.sparse.bell.BellShard` plus a single ``[N]`` vector or a
``[B, N]`` batch into device arrays.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.sparse.bell import BellShard, pad_x_blocks
from repro.kernels.spmv.kernel import bell_spmm, bell_spmv
from repro.kernels.spmv.ref import bell_spmm_ref, bell_spmv_ref

__all__ = [
    "spmv_shard",
    "spmm_shard",
    "pack_inputs",
    "spmv_shard_ref",
    "spmm_shard_ref",
]


def pack_inputs(
    shard: BellShard, x: np.ndarray, bn: int
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Device arrays for one shard. ``x`` may be ``[N]`` (x blocks come
    back ``[NCB, bn]``) or a batch ``[B, N]`` (``[NCB, bn, B]``)."""
    n = x.shape[-1]
    ncb = -(-n // bn)
    return (
        jnp.asarray(shard.tiles),
        jnp.asarray(shard.tile_row),
        jnp.asarray(shard.tile_col),
        jnp.asarray(pad_x_blocks(x, ncb, bn)),
    )


def spmv_shard(
    tiles: jax.Array,
    tile_row: jax.Array,
    tile_col: jax.Array,
    x_blocks: jax.Array,
    num_row_blocks: int,
    *,
    interpret: bool = False,
) -> jax.Array:
    """One shard's PMVC: returns the local y block ``[R, bm]``."""
    return bell_spmv(
        tiles, tile_row, tile_col, x_blocks, num_row_blocks, interpret=interpret
    )


def spmm_shard(
    tiles: jax.Array,
    tile_row: jax.Array,
    tile_col: jax.Array,
    x_blocks: jax.Array,  # [NCB, bn, B]
    num_row_blocks: int,
    *,
    interpret: bool = False,
) -> jax.Array:
    """One shard's batched PMVC: returns the local y block ``[R, bm, B]``."""
    return bell_spmm(
        tiles, tile_row, tile_col, x_blocks, num_row_blocks, interpret=interpret
    )


def spmv_shard_ref(
    tiles: jax.Array,
    tile_row: jax.Array,
    tile_col: jax.Array,
    x_blocks: jax.Array,
    num_row_blocks: int,
) -> jax.Array:
    return bell_spmv_ref(tiles, tile_row, tile_col, x_blocks, num_row_blocks)


def spmm_shard_ref(
    tiles: jax.Array,
    tile_row: jax.Array,
    tile_col: jax.Array,
    x_blocks: jax.Array,
    num_row_blocks: int,
) -> jax.Array:
    return bell_spmm_ref(tiles, tile_row, tile_col, x_blocks, num_row_blocks)
