"""Launch layer: sharding rules validity, input specs, mesh factories.

Uses abstract trees only (no 512-device init — that's the dry-run's
job); specs are validated structurally against an AbstractMesh of the
production shape.
"""
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.config import SHAPES, get_arch, shape_applicable
from repro.configs import ARCH_IDS
from repro.launch.shardings import param_spec, tree_path_map
from repro.launch.specs import abstract_params, input_specs
from repro.models import build

PROD_MESH = AbstractMesh((16, 16), ("data", "model"))
POD_MESH = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _check_spec(path, leaf, cfg, mesh):
    spec = param_spec(path, leaf, cfg, mesh)
    assert len(spec) <= leaf.ndim, (path, spec, leaf.shape)
    for dim, axis in enumerate(spec):
        if axis is None:
            continue
        size = mesh.shape[axis] if isinstance(axis, str) else int(
            np.prod([mesh.shape[a] for a in axis])
        )
        assert leaf.shape[dim] % size == 0, (
            f"{path}: dim {dim} ({leaf.shape[dim]}) not divisible by {axis}={size}"
        )
    return spec


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_divisible(arch):
    cfg = get_arch(arch)
    model = build(cfg)
    params = abstract_params(model)
    sharded_bytes = [0.0]
    total_bytes = [0.0]

    def check(path, leaf):
        spec = _check_spec(path, leaf, cfg, PROD_MESH)
        b = float(np.prod(leaf.shape))
        total_bytes[0] += b
        if any(s is not None for s in spec):
            sharded_bytes[0] += b
        return spec

    tree_path_map(check, params)
    # The bulk of parameter BYTES must actually shard (params are
    # layer-stacked, so leaf counts are small).
    assert sharded_bytes[0] / total_bytes[0] > 0.9


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_big_weights_are_sharded(arch):
    """No parameter > 64 MiB (bf16) may stay fully replicated at 16-way
    TP — the memory-fit precondition of the dry-run."""
    cfg = get_arch(arch)
    model = build(cfg)
    params = abstract_params(model)

    def check(path, leaf):
        bytes_ = int(np.prod(leaf.shape)) * 2
        spec = param_spec(path, leaf, cfg, PROD_MESH)
        if bytes_ > 64 * 2**20:
            assert any(s is not None for s in spec), (
                f"{path} ({bytes_/2**20:.0f} MiB) replicated"
            )
        return spec

    tree_path_map(check, params)


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_input_specs_cells(arch, shape):
    cfg = get_arch(arch)
    sh = SHAPES[shape]
    ok, why = shape_applicable(cfg, sh)
    if not ok:
        pytest.skip(why)
    specs = input_specs(arch, shape)
    assert specs["tokens"].shape[0] == sh.global_batch
    if sh.kind == "decode":
        assert specs["tokens"].shape[1] == 1
    else:
        assert specs["tokens"].shape[1] == sh.seq_len
    if cfg.frontend:
        assert "frontend_embeds" in specs
        assert specs["frontend_embeds"].shape[-1] == cfg.d_model


def test_long500k_skips():
    skips = [a for a in ARCH_IDS
             if not shape_applicable(get_arch(a), SHAPES["long_500k"])[0]]
    assert "granite-20b" in skips and "qwen3-1.7b" in skips
    runs = [a for a in ARCH_IDS
            if shape_applicable(get_arch(a), SHAPES["long_500k"])[0]]
    assert set(runs) == {"mamba2-2.7b", "hymba-1.5b", "h2o-danube-1.8b"}


def test_mesh_factories_are_lazy():
    """Importing repro.launch must not initialize devices; only calling
    the factories does."""
    import repro.launch  # noqa: F401 — import side-effect free
    import repro.launch.mesh  # noqa: F401
