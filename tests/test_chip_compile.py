"""Compile the main path's device programs for a described TPU v5e.

Nothing runs: the TPU compiler builds each program for a v5e that is
described, not attached, and refuses what the chip would refuse (a
program that does not fit its HBM, a kernel that does not lower). The
shapes are those of the chip smoke run's plan — a banded 60k x 60k /
1.2M-nnz operator on ``Topology(4, 4)`` at 128 x 128 tiles — written as
constants so no test plans at that size.
"""
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.api import Topology, distribute
from repro.kernels.spmv import bell_spmm
from repro.pmvc.dist import _unit_spmm, make_pmvc_step
from repro.pmvc.plan_device import SelectivePlan
from repro.sparse.generate import banded_coo

# The smoke plan: 16 units x 1382 padded tiles, 469 block rows/cols.
UNITS, TILES, NRB, NCB, BLOCK = 16, 1382, 469, 469, 128
V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache off around these.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits_hbm(compiled):
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
    return total < V5E_HBM_BYTES


@pytest.mark.parametrize("batch", [1, 64])
def test_unit_contraction_compiles_at_smoke_size(one_chip, batch):
    """The simulate executor's program: the unit contraction vmapped over
    16 units, partials summed — fits one v5e at B = 1 and B = 64."""

    def run(tiles, rows, cols, xb):
        def one_unit(t, r, c):
            return _unit_spmm(t, r, xb[c], NRB)

        return jax.vmap(one_unit)(tiles, rows, cols).sum(axis=0)

    compiled = (
        jax.jit(run)
        .lower(
            _sds((UNITS, TILES, BLOCK, BLOCK), jnp.float32, one_chip),
            _sds((UNITS, TILES), jnp.int32, one_chip),
            _sds((UNITS, TILES), jnp.int32, one_chip),
            _sds((NCB, BLOCK, batch), jnp.float32, one_chip),
        )
        .compile()
    )
    assert _fits_hbm(compiled)


def test_selective_step_compiles_on_four_chips(topo):
    """The shard_map selective step over a 4-device v5e mesh: the
    compiled program carries the exchange (all-to-all) and the fan-in
    of partial y (all-reduce)."""
    sess = distribute(
        banded_coo(4096, 81920, seed=0),
        topology=Topology(2, 2),
        combo="NL-HC",
        exchange="selective",
        block=BLOCK,
    )
    dp, sp = sess.device_plan, sess.selective
    mesh = Mesh(np.asarray(topo.devices[:4]), ("unit",))
    unit = NamedSharding(mesh, P("unit"))
    step = make_pmvc_step(dp, mesh, selective=sp)
    x_owned = np.zeros((sp.num_units, sp.blocks_per_unit, BLOCK, 8), np.float32)
    args = (dp.tiles, dp.tile_row, sp.tile_col_local, x_owned, sp.send_idx, sp.recv_src, sp.recv_lane)
    compiled = step.lower(*(_sds(a.shape, a.dtype, unit) for a in args)).compile()
    text = compiled.as_text()
    assert "all-to-all" in text
    assert "all-reduce" in text


def test_selective_step_fits_each_chip_at_the_four_chip_hpcg_size(topo):
    """The step of the four-chip HPCG cell (``hpcg-4x104``: a 208 x 208 x
    104 stencil, 35,152 block rows and cols of 128) with each unit at the
    most tiles its plan may store (1.05 x a quarter of the 522,040
    distinct tiles) and its exchange at the widest it can be (every
    owned block on every route, every block in the workspace): what one
    chip holds of it fits in 9.4 GB, a quarter of the operator and not
    the whole."""
    units, tiles, nb = 4, 137036, 35152
    per = nb // units
    dp = types.SimpleNamespace(num_row_blocks=nb)
    sp = SelectivePlan(units, per, per, *(None,) * 6, 0, 0)
    mesh = Mesh(np.asarray(topo.devices[:4]), ("unit",))
    unit = NamedSharding(mesh, P("unit"))
    shapes = [
        ((units, tiles, BLOCK, BLOCK), jnp.float32),
        ((units, tiles), jnp.int32),
        ((units, tiles), jnp.int32),
        ((units, per, BLOCK), jnp.float32),
        ((units, units, per), jnp.int32),
        ((units, nb), jnp.int32),
        ((units, nb), jnp.int32),
    ]
    step = make_pmvc_step(dp, mesh, selective=sp)
    compiled = step.lower(*(_sds(s, d, unit) for s, d in shapes)).compile()
    m = compiled.memory_analysis()
    per_chip = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
    assert tiles * BLOCK * BLOCK * 4 < per_chip < 9.4e9


@pytest.mark.parametrize("batch", [1, 8, 64])
def test_bell_spmm_compiles(one_chip, batch):
    """The Pallas kernel lowers to a TPU custom call at 128 x 128 tiles
    (one unit's tile stream, 32 local block rows)."""
    compiled = bell_spmm.lower(
        _sds((TILES, BLOCK, BLOCK), jnp.float32, one_chip),
        _sds((TILES,), jnp.int32, one_chip),
        _sds((TILES,), jnp.int32, one_chip),
        _sds((NCB, BLOCK, batch), jnp.float32, one_chip),
        num_row_blocks=32,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
