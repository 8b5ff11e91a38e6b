"""The tile-line partitioner (``"NL-HC:tiles"``), the executor a session
picks by default, and the sharded path they feed: a CG solve through the
``shard_map`` executor whose units each hold only their own tiles.

Sharded cases run in a subprocess with 4 host devices (the tests keep the
default 1-device view, as in ``test_pmvc_dist.py``)."""
import importlib.util
import os
import subprocess
import sys
import textwrap
import types

import numpy as np
import pytest

from repro.api import Topology, distribute, resolve_partitioner
from repro.api.executors import default_executor
from repro.sparse.formats import COO
from repro.sparse.generate import banded_coo, powerlaw_coo, random_coo

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _stencil(nx, ny, nz) -> COO:
    """HPCG's 27-point operator, from the benchmark's own generator."""
    spec = importlib.util.spec_from_file_location(
        "stencil27", os.path.join(ROOT, "bench", "matrices", "stencil27.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    m = mod.generate({"nx": nx, "ny": ny, "nz": nz}, 0)
    return COO(m["shape"], m["row"], m["col"], m["val"])


MATRICES = {
    "stencil-24x24x12": lambda: _stencil(24, 24, 12),
    "banded": lambda: banded_coo(3000, 60000, seed=2),
    "random": lambda: random_coo(2000, 30000, seed=3),
    "powerlaw": lambda: powerlaw_coo(2048, 40000, seed=4),
}


def _tiles_of(a, elem_unit, block):
    """``{unit: set of (block-row, block-col)}`` the units store."""
    keys = (a.row // block).astype(np.int64) * (a.shape[1] // block + 1) + a.col // block
    pairs = np.unique(np.stack([elem_unit, keys]), axis=1)
    return {u: set(pairs[1, pairs[0] == u].tolist()) for u in np.unique(pairs[0])}


@pytest.mark.parametrize("name", sorted(MATRICES))
def test_every_tile_lies_on_one_unit_and_units_share_them_evenly(name):
    a, block, units = MATRICES[name](), 16, 4
    part = resolve_partitioner("NL-HC:tiles")(a, Topology(2, 2), block=block)
    assert part.name == "NL-HC:tiles" and part.elem_unit.shape == (a.nnz,)
    held = _tiles_of(a, part.elem_unit, block)
    distinct = set().union(*held.values())
    assert sum(len(t) for t in held.values()) == len(distinct)  # no tile on two units
    assert len(held) == units
    assert max(len(t) for t in held.values()) <= 1.05 * -(-len(distinct) // units)


def test_plan_stores_each_tile_once():
    """Through ``distribute``: the packed plan holds every distinct tile
    once, padded only to the fullest unit."""
    a = _stencil(24, 24, 12)
    sess = distribute(a, topology=Topology(2, 2), combo="NL-HC:tiles", exchange="selective", block=16)
    dp = sess.device_plan
    distinct = np.unique((a.row // 16).astype(np.int64) * dp.num_col_blocks + a.col // 16).shape[0]
    assert int(dp.real_tiles.sum()) == distinct
    assert dp.t == int(dp.real_tiles.max()) <= 1.05 * -(-distinct // 4)


def test_the_tile_name_is_no_element_combo():
    from repro.api.partitioners import _COMBO_RE

    assert not _COMBO_RE.match("NL-HC:tiles")
    with pytest.raises(TypeError):  # a tile partitioner needs the tile shape
        resolve_partitioner("NL-HC:tiles")(random_coo(64, 300, seed=0), Topology(2, 2))


def _devices(platform, n):
    return [types.SimpleNamespace(platform=platform) for _ in range(n)]


def test_default_executor_follows_the_devices():
    assert default_executor(4) == "simulate"  # the CPU this runs on
    assert default_executor(1, _devices("tpu", 4)) == "simulate"
    assert default_executor(4, _devices("tpu", 4)) == "shard_map"
    assert default_executor(4, _devices("tpu", 2)) == "simulate"
    assert default_executor(4, _devices("cpu", 8)) == "simulate"
    a = random_coo(128, 800, seed=1)
    assert distribute(a, topology=Topology(2, 2), combo="NL-HC").executor == "simulate"
    assert distribute(a, topology=Topology(1, 1), combo="NL-HC").executor == "simulate"
    assert distribute(a, topology=Topology(2, 2), combo="NL-HC", executor="reference").executor == "reference"


_SUBPROC = textwrap.dedent(
    """
    import glob, os, shutil, sys, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, "bench")
    import numpy as np, jax
    from jax.profiler import ProfileData
    from harness.files import load_module, part
    from harness.reference import csr
    from repro.api import Topology, distribute
    from repro.sparse.formats import COO

    m = load_module(part(".", "matrices", "stencil27")).generate({"nx": 24, "ny": 24, "nz": 12}, 0)
    cg = load_module(part(".", "solvers", "cg"))
    a = COO(m["shape"], m["row"], m["col"], m["val"])
    sess = distribute(a, topology=Topology(2, 2), combo="NL-HC:tiles", exchange="selective",
                      block=16, executor="shard_map")
    b = (csr(m) @ np.random.default_rng(5).standard_normal(a.shape[0])).astype(np.float32)
    trace_dir = tempfile.mkdtemp()
    with jax.profiler.trace(trace_dir):
        x = sess.solve("cg", b=b, iters=50, tol=0.0).x
    ref = cg.cg(m, b, 50)
    print("REL_SHARD", np.linalg.norm(x - ref) / np.linalg.norm(ref))
    xs = sess.with_executor("simulate").solve("cg", b=b, iters=50, tol=0.0).x
    print("REL_SIM", np.linalg.norm(xs - ref) / np.linalg.norm(ref))
    print("SHARD_VS_SIM", float(np.abs(x - xs).max() / np.abs(xs).max()))

    dp, sp = sess.device_plan, sess.selective
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.pmvc.dist import hoist_plan, make_unit_mesh
    (tiles,) = hoist_plan((dp.tiles,), sharding=NamedSharding(make_unit_mesh(4), P("unit")))
    shards = sorted(tiles.addressable_shards, key=lambda s: s.device.id)
    devs = jax.devices()
    for u, s in enumerate(shards):
        assert s.device == devs[u], (u, s.device)
        assert s.data.shape == (1, dp.t, dp.bm, dp.bn), s.data.shape
        np.testing.assert_array_equal(np.asarray(s.data)[0], dp.tiles[u])
    print("SHARDS_OK")

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    profile = ProfileData.from_file(path)
    shutil.rmtree(trace_dir)
    spans = {}
    for plane in profile.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("sparse."):
                    spans.setdefault(e.name, []).append(dict(e.stats))
    (hoist,) = spans["sparse.hoist"]
    plan_bytes = sum(int(v.nbytes) for v in (dp.tiles, dp.tile_row, sp.tile_col_local,
                                             sp.send_idx, sp.recv_src, sp.recv_lane))
    assert hoist["bytes"] == plan_bytes and hoist["units"] == 4
    assert hoist["bytes_per_device"] == plan_bytes // 4
    puts = spans["sparse.put"]
    assert len(puts) == 51  # the first residual and 50 iterations
    for p in puts:
        assert p["halo_bytes"] == sp.wire_blocks * dp.bn * 4
        assert p["reduce_bytes"] == 4 * dp.num_row_blocks * dp.bm * 4
    print("SPANS_OK")
    """
)


@pytest.fixture(scope="module")
def sharded_run():
    res = subprocess.run(
        [sys.executable, "-c", _SUBPROC],
        capture_output=True,
        text=True,
        timeout=600,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"},
        cwd=ROOT,
    )
    out = res.stdout + res.stderr
    values = {}
    for line in res.stdout.splitlines():
        key, _, val = line.partition(" ")
        values[key] = val
    return out, values


def test_sharded_cg_matches_float64_cg_and_simulate(sharded_run):
    out, got = sharded_run
    # 50 float32 CG iterations against the float64 textbook CG: rounding
    # alone reads ~2e-7 here; the benchmark's limit is 1e-4.
    assert float(got["REL_SHARD"]) < 1e-5, out
    assert float(got["REL_SIM"]) < 1e-5, out
    # The same plan under the two executors: the same per-unit partial
    # products, summed by a psum instead of a vmapped sum; the order of
    # the float32 sums may differ, so agree to float32 rounding of 50
    # iterations, not bitwise.
    assert float(got["SHARD_VS_SIM"]) < 1e-5, out


def test_each_unit_tiles_live_on_their_own_device(sharded_run):
    out, got = sharded_run
    assert "SHARDS_OK" in got, out


def test_hoist_and_put_spans_count_the_exchange(sharded_run):
    out, got = sharded_run
    assert "SPANS_OK" in got, out
