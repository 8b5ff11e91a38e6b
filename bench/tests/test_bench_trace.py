import os

import pytest

from harness import trace

US = 1_000_000  # picoseconds per microsecond


def _line(name, events, meta):
    evs = []
    for label, start_us, end_us in events:
        mid = meta.setdefault(label, len(meta) + 1)
        evs.append(f"events {{ metadata_id: {mid} offset_ps: {start_us * US} duration_ps: {(end_us - start_us) * US} }}")
    return f'lines {{ name: "{name}" timestamp_ns: 0 {" ".join(evs)} }}'


def _plane(name, lines):
    meta: dict = {}
    body = " ".join(_line(n, evs, meta) for n, evs in lines)
    md = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{k}" }} }}' for k, i in meta.items())
    return f'planes {{ name: "{name}" {body} {md} }}'


def synthetic():
    """A 100 us window: two runs of one program, overlapping ops, host spans."""
    from jax.profiler import ProfileData

    device = _plane("/device:TPU:0", [
        ("XLA Modules", [("jit_body(7)", 10, 40), ("jit_body(7)", 60, 90), ("jit_other(3)", 95, 120)]),
        ("XLA Ops", [("fusion.1", 10, 25), ("fusion.2", 20, 40), ("fusion.1", 60, 70),
                     ("fusion.2", 80, 90), ("copy", 95, 120)]),
    ])
    host = _plane("/host:CPU", [
        ("main", [(trace.WINDOW, 0, 100), ("bench.request", 0, 100), ("np.asarray", 40, 60)]),
    ])
    return ProfileData.from_text_proto(device + host)


def test_busy_is_the_union_of_ops_inside_the_window():
    s = trace.summarize(synthetic())
    assert s.devices == 1
    assert s.window_s == pytest.approx(100e-6)
    # [10,40] + [60,70] + [80,90] + [95,100] (clipped at the window's end)
    assert s.busy_s == pytest.approx(55e-6)


def test_module_time_and_ops_by_program():
    s = trace.summarize(synthetic())
    assert s.module_seconds("jit_body") == (2, pytest.approx(60e-6))
    assert s.module_seconds("jit_other") == (1, pytest.approx(5e-6))
    assert s.module_seconds("jit_none") == (0, 0.0)
    ops = dict(s.device_ops)
    assert ops["jit_body/fusion.1"] == pytest.approx(25e-6)
    assert ops["jit_body/fusion.2"] == pytest.approx(30e-6)
    assert ops["jit_other/copy"] == pytest.approx(5e-6)


def test_idle_gaps_named_by_the_most_specific_host_span():
    s = trace.summarize(synthetic())
    gaps = dict(s.idle_gaps)
    # [0,10], [70,80], [90,95] lie only under bench.request; [40,60] under np.asarray
    assert gaps == {"bench.request": pytest.approx(25e-6), "np.asarray": pytest.approx(20e-6)}
    assert s.breakdown(top=1)["idle_gaps"] == [["bench.request", pytest.approx(25e-6)]]


def test_a_trace_without_the_window_span_is_refused():
    from jax.profiler import ProfileData

    with pytest.raises(ValueError):
        trace.summarize(ProfileData.from_text_proto(_plane("/host:CPU", [("main", [("x", 0, 1)])])))


def test_base_name():
    assert trace.base_name("jit_body(1234)") == "jit_body"
    assert trace.base_name("jit_while") == "jit_while"


def test_a_listed_metric_that_finds_nothing_fails_the_run():
    """A cell that a metric lists has to yield it: a program renamed or fused
    away must not silently drop the roofline from the line."""
    import types

    from harness.cell import Cell, read_layers

    cell = Cell("g500s15.pr-direct")
    ctx = types.SimpleNamespace(
        trace=trace.summarize(synthetic()),  # holds jit_body and jit_other, no jit_while
        plan={"nnz": 100, "rows": 10, "cols": 10, "tiles": 1, "stored_entries": 400},
        peak={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        spmm=cell.mix.solver.spmm_programs(cell.mix.request),
        solver_iters=20,
    )
    with pytest.raises(RuntimeError, match="spmm_roofline"):
        read_layers(cell, ctx)
    ctx.spmm = {"jit_body": (20, 1)}
    got = read_layers(cell, ctx)
    assert set(got) == {m["name"] for m in cell.per_layer}
    assert 0.0 < got["spmm_roofline.solve"]["value"] <= 100.0

