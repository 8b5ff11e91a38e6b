"""The four-chip HPCG cell (``hpcg-4x104.cg-4chip``): found by its names,
its readers on synthetic four-device traces, and a tiny four-unit version
of it run whole on the CPU."""
import json
import os
import subprocess
import sys
import textwrap
import types

import pytest
from test_bench_trace import _plane
from tiny import tiny_cell

from harness import spans, trace
from harness.cell import Cell, reader_path, run
from harness.files import ROOT, load_module

NAME = "hpcg-4x104.cg-4chip"
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
PLAN = {"nnz": 1_000_000, "rows": 100_000, "cols": 100_000, "tiles": 4, "stored_entries": 4 * 2**20}


def reader(name):
    return load_module(reader_path(name))


def test_the_cell_is_found_by_its_names():
    cell = Cell(NAME)
    assert cell.chips == 4
    assert cell.config["name"] == "hpcg-4x104" and cell.config["reduced"] == []
    assert cell.config["matrix"] == {"generator": "stencil27", "nx": 208, "ny": 208, "nz": 104}
    assert cell.config["plan"] == {"topology": [2, 2], "combo": "NL-HC:tiles", "block": 128, "exchange": "selective"}
    assert cell.mix.request == Cell("hpcg104.cg-direct").mix.request  # the one-chip cell's requests
    assert cell.limits == {"rel_err_l2": 1e-4}
    assert [m["name"] for m in cell.end_to_end] == ["solve_s", "setup_s"]
    # its own three, and those of the one-chip cells its host loop has to read
    assert {m["name"] for m in cell.per_layer} == {
        "shard_roofline.cg4", "exchange_share.cg4", "halo_mb.cg4", "tile_fill.solve",
        "device_idle.solve", "idle_ms_per_iter.solve", "update_idle.solve", "transfer_idle.solve",
    }
    # the cell's sizes are HPCG's four ranks of 104^3: (3n - 2) non-zeros per axis
    assert cell.config["rows"] == 208 * 208 * 104
    assert cell.config["nnz"] == (3 * 208 - 2) ** 2 * (3 * 104 - 2)


def four_chips(module_runs, ops=()):
    """Four device planes, each running ``module_runs`` of the sharded step
    (``(start_us, end_us)``) and the ``ops`` ``(name, start_us, end_us)``."""
    text = "".join(
        _plane(f"/device:TPU:{d}", [
            ("XLA Modules", [(f"jit_step_selective({d})", s, e) for s, e in module_runs]),
            ("XLA Ops", list(ops) or [("fusion.1", s, e) for s, e in module_runs]),
        ])
        for d in range(4)
    )
    text += _plane("/host:CPU", [("main", [(trace.WINDOW, 0, 100)])])
    from jax.profiler import ProfileData

    return trace.summarize(ProfileData.from_text_proto(text))


def test_idle_readers_take_the_mean_over_the_chips():
    """Chip d busy (d + 1) * 10 us of the 100 us window: 25 us on the mean chip."""
    from jax.profiler import ProfileData

    text = "".join(
        _plane(f"/device:TPU:{d}", [("XLA Ops", [("fusion.1", 0, 10 * (d + 1))])]) for d in range(4)
    )
    text += _plane("/host:CPU", [("main", [(trace.WINDOW, 0, 100)])])
    ctx = types.SimpleNamespace(trace=trace.summarize(ProfileData.from_text_proto(text)), solver_iters=3)
    assert reader("device_idle").read(ctx) == pytest.approx(75.0)
    assert reader("idle_ms_per_iter").read(ctx) == pytest.approx(1e3 * 75e-6 / 3)


def test_shard_roofline_is_the_one_chip_share_over_the_chips():
    ctx = types.SimpleNamespace(trace=four_chips([(10, 40), (50, 90)]), plan=PLAN, peak=PEAK,
                                spmm={"jit_body": (1, 1)})
    assert ctx.trace.devices == 4
    got = reader("shard_roofline").read(ctx)
    # spmm_roofline credits the one chip's peak with the step's four runs
    ctx.spmm = {"jit_step_selective": (1, 1)}
    assert got == pytest.approx(reader("spmm_roofline").read(ctx) / 4)
    assert 0.0 < got <= 100.0
    ctx.trace = four_chips([])  # no run of the step: nothing to read
    assert reader("shard_roofline").read(ctx) is None


def test_exchange_share_counts_collectives_and_nothing_else():
    ops = [
        ("fusion.1", 0, 30),  # the contraction
        ("all_to_all.3", 30, 35),  # JAX's names for its collectives
        ("psum_invariant.7", 35, 45),
        ("all-reduce-start.2", 45, 46),  # XLA's, async halves too
        ("all-reduce-done.2", 50, 51),
        ("copy.4", 51, 60),
        ("all_to_all_fusion", 60, 70),  # a fusion is no collective
        ("reduce.9", 70, 80),
    ]
    ctx = types.SimpleNamespace(trace=four_chips([(0, 100)], ops))
    # 5 + 10 + 1 + 1 us of the 100 us window, on every chip
    assert reader("exchange_share").read(ctx) == pytest.approx(17.0)
    ctx = types.SimpleNamespace(trace=four_chips([(0, 100)], [("fusion.1", 0, 30)]))
    assert reader("exchange_share").read(ctx) is None


def _host_with_stats(events):
    """A host plane of ``(name, start_us, end_us, {stat: int})`` events."""
    meta, stat_ids, evs = {}, {}, []
    for name, s, e, stats in events:
        mid = meta.setdefault(name, len(meta) + 1)
        st = " ".join(
            f"stats {{ metadata_id: {stat_ids.setdefault(k, len(stat_ids) + 1)} int64_value: {v} }}"
            for k, v in stats.items()
        )
        evs.append(f"events {{ metadata_id: {mid} offset_ps: {s * 10**6} duration_ps: {(e - s) * 10**6} {st} }}")
    md = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{k}" }} }}' for k, i in meta.items())
    sd = " ".join(f'stat_metadata {{ key: {i} value {{ id: {i} name: "{k}" }} }}' for k, i in stat_ids.items())
    return f'planes {{ name: "/host:CPU" lines {{ name: "main" timestamp_ns: 0 {" ".join(evs)} }} {md} {sd} }}'


def test_halo_mb_reads_the_put_spans_arguments(tmp_path, monkeypatch):
    from jax.profiler import ProfileData

    counts = {"bytes": 4096, "halo_bytes": 27_000_000, "reduce_bytes": 72_000_000}
    text = _host_with_stats([
        (trace.WINDOW, 0, 100, {}),
        ("sparse.put", 5, 10, counts),
        ("sparse.put", 50, 55, counts),
        ("sparse.put", 120, 125, {**counts, "halo_bytes": 1}),  # after the window
        ("sparse.put", 60, 62, {"bytes": 4096}),  # a device loop's operands
    ])
    (tmp_path / "run").mkdir()
    (tmp_path / "run" / "x.xplane.pb").write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    monkeypatch.setattr(spans, "TRACE_DIR", str(tmp_path))
    ctx = types.SimpleNamespace(trace=trace.summarize(ProfileData.from_text_proto(text)))
    assert reader("halo_mb").read(ctx) == pytest.approx(99.0)
    # a program that counts no exchange: nothing to read
    (tmp_path / "run" / "x.xplane.pb").write_bytes(ProfileData.text_proto_to_serialized_xspace(
        _host_with_stats([(trace.WINDOW, 0, 100, {}), ("sparse.put", 5, 10, {"bytes": 4096})])))
    assert reader("halo_mb").read(ctx) is None


def tiny_four_unit_cell():
    """The cell at the tiny stencil, its tiles cut to 16 so that the four
    units share some hundreds of them."""
    cell = tiny_cell(NAME)
    cell.config["plan"]["block"] = 16
    return cell


def test_tiny_four_unit_cell_is_correct_and_its_control_is_not():
    result = run(tiny_four_unit_cell(), seed=2**31 + 77, seconds=0.3, traced=False, compile_cache=False)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"solve_s", "setup_s"}
    control = run(tiny_four_unit_cell(), seed=2**31 + 77, seconds=0.3, traced=False,
                  compile_cache=False, control=True)
    assert not control["correct"]
    assert control["checks"]["rel_err_l2"]["value"] > control["checks"]["rel_err_l2"]["limit"]


_SHARDED = textwrap.dedent(
    """
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = ["bench", "bench/tests", "src"]
    from repro.api import session
    # The CPU's devices stand in for the chips: steer the session's default
    # executor to the one a four-chip host picks.
    session.default_executor = lambda units, devices=None: "shard_map"
    from test_bench_cg4 import tiny_four_unit_cell
    from harness.cell import plan, run
    cell = tiny_four_unit_cell()
    assert plan(cell, cell.generate(0)).executor == "shard_map"
    result = run(cell, seed=2**33 + 1, seconds=0.3, traced=False, compile_cache=False)
    print(json.dumps({"correct": result["correct"], "checks": result["checks"]}))
    """
)


def test_tiny_four_unit_cell_is_correct_through_shard_map():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    res = subprocess.run([sys.executable, "-c", _SHARDED], capture_output=True, text=True,
                         timeout=300, env=env, cwd=ROOT)
    assert res.returncode == 0, res.stderr[-3000:]
    line = json.loads(res.stdout.strip().splitlines()[-1])
    assert line["correct"], line["checks"]
