import types

import pytest
from test_bench_trace import _plane

from harness import spans, trace
from harness.cell import Cell, read_layers, reader_path
from harness.files import load_module

US = 1e-6  # seconds per microsecond

# A 100 us window. The device runs ops over [15, 40] and [70, 80], so it
# idles over [0, 15], [40, 70] and [80, 100]: 65 us.
DEVICE = _plane("/device:TPU:0", [
    ("XLA Modules", [("jit_body(7)", 15, 40), ("jit_body(7)", 70, 80)]),
    ("XLA Ops", [("fusion.1", 15, 40), ("fusion.1", 70, 80)]),
])


def _text(host_events, window=(0, 100)):
    """The device above and one host thread holding the window and ``host_events``."""
    return DEVICE + _plane("/host:CPU", [("main", [(trace.WINDOW, *window), *host_events])])


def _profile(host_events, window=(0, 100)):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(_text(host_events, window))


# One solve with a put, a fetch that a runtime event nests in, and an update.
SOLVE = [
    ("bench.request", 0, 100),
    ("sparse.solve", 0, 90),
    ("sparse.put", 10, 20),
    ("sparse.fetch", 30, 60),
    ("np.asarray", 35, 60),
    ("sparse.update", 60, 75),
]


def test_self_time_leaves_out_nested_spans():
    program = [trace.Span(n, s, e) for n, s, e in SOLVE if n.startswith("sparse.")]
    got = spans._self_intervals(program)
    assert sorted(got, key=lambda p: p[1]) == [
        ("sparse.solve", 0, 10),
        ("sparse.put", 10, 20),
        ("sparse.solve", 20, 30),
        ("sparse.fetch", 30, 60),
        ("sparse.update", 60, 75),
        ("sparse.solve", 75, 90),
    ]


def test_idle_goes_to_the_span_whose_self_time_holds_it():
    window_ns, idle = spans.split_idle(_profile(SOLVE))
    assert window_ns == 100_000
    # solve: [0,10] + [75,90] minus busy [70,80] -> 10 + 10; put: [10,15];
    # fetch: [40,60], not the runtime event nested in it; update: [60,70].
    assert idle == {
        "sparse.solve": pytest.approx(20 * US),
        "sparse.put": pytest.approx(5 * US),
        "sparse.fetch": pytest.approx(20 * US),
        "sparse.update": pytest.approx(10 * US),
    }
    # [90, 100] lies under no span of the program: it goes to none
    assert sum(idle.values()) == pytest.approx(55 * US)


def test_spans_are_clipped_at_the_window():
    events = [("sparse.solve", 0, 150), ("sparse.update", 5, 45), ("sparse.fetch", 95, 140)]
    window_ns, idle = spans.split_idle(_profile(events, window=(20, 100)))
    assert window_ns == 80_000
    assert idle == {
        "sparse.solve": pytest.approx(40 * US),  # [45, 70] and [80, 95]
        "sparse.update": pytest.approx(5 * US),  # [40, 45]; [5, 15] lies before the window
        "sparse.fetch": pytest.approx(5 * US),  # [95, 100]
    }


def test_a_window_without_a_solve_span_reads_none():
    assert spans.split_idle(_profile([("sparse.update", 10, 20)]))[1] is None
    # a solve wholly outside the window does not count
    assert spans.split_idle(_profile([("sparse.solve", 200, 300)]))[1] is None


def _recorded(tmp_path, monkeypatch, host_events):
    """Write the profile as a run's trace under a temporary trace directory,
    and return the readers' context reduced from it."""
    from jax.profiler import ProfileData

    path = tmp_path / "cell" / "plugins" / "profile" / "1" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(_text(host_events)))
    monkeypatch.setattr(spans, "TRACE_DIR", str(tmp_path))
    return types.SimpleNamespace(trace=trace.summarize(trace.load(str(path))))


def _read(metric, ctx):
    return load_module(reader_path(metric)).read(ctx)


def test_the_readers_split_the_idle_share(tmp_path, monkeypatch):
    after = [("sparse.trace", 90, 95), ("sparse.hoist", 95, 100)]
    ctx = _recorded(tmp_path, monkeypatch, SOLVE + after)
    assert _read("update_idle.solve", ctx) == pytest.approx(10.0)
    assert _read("transfer_idle.solve", ctx) == pytest.approx(5.0 + 20.0 + 5.0)
    assert _read("trace_idle.solve", ctx) == pytest.approx(5.0)
    # with the solve's own self time, the split is all of the device's idle time
    idle_pct = 100.0 * (1 - ctx.trace.busy_s / ctx.trace.window_s)
    assert idle_pct == pytest.approx(10.0 + 30.0 + 5.0 + 20.0)


def test_a_reader_whose_spans_did_not_run_reads_zero(tmp_path, monkeypatch):
    ctx = _recorded(tmp_path, monkeypatch, SOLVE)
    assert _read("trace_idle.solve", ctx) == 0.0


def _layer_context(ctx, cell):
    ctx.plan = {"nnz": 100, "rows": 10, "cols": 10, "tiles": 1, "stored_entries": 400}
    ctx.peak = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    ctx.spmm = cell.mix.solver.spmm_programs(cell.mix.request)
    ctx.solver_iters = 20
    return ctx


def test_spans_gone_from_the_program_fail_the_run(tmp_path, monkeypatch):
    """A window with no ``sparse.solve`` from a program that records spans
    (they were renamed or taken off the path) reads ``None``, and the run
    of a cell that lists the metrics fails."""
    ctx = _recorded(tmp_path, monkeypatch, [("bench.request", 0, 100)])
    for metric in ("update_idle.solve", "transfer_idle.solve", "trace_idle.solve"):
        assert _read(metric, ctx) is None
    cell = Cell("hpcg104.cg-direct")
    with pytest.raises(RuntimeError, match="update_idle"):
        read_layers(cell, _layer_context(ctx, cell))


def test_a_program_from_before_its_spans_reads_zero(tmp_path, monkeypatch):
    """The parent of the change that added the spans has none: its traced
    run reads 0.0 and goes on, and reports the other metrics."""
    ctx = _recorded(tmp_path, monkeypatch, [("bench.request", 0, 100)])
    monkeypatch.setattr(spans, "program_has_spans", lambda: False)
    cell = Cell("g500s15.pr-direct")
    ctx = _layer_context(ctx, cell)
    ctx.spmm = {"jit_body": (20, 1)}
    got = read_layers(cell, ctx)
    assert set(got) == {m["name"] for m in cell.per_layer}
    assert got["update_idle.solve"]["value"] == got["trace_idle.solve"]["value"] == 0.0
    assert got["device_idle.solve"]["value"] == pytest.approx(65.0)


def test_another_runs_trace_is_refused(tmp_path, monkeypatch):
    ctx = _recorded(tmp_path, monkeypatch, SOLVE)
    ctx.trace.window_s += 2e-6
    with pytest.raises(ValueError, match="not this run's trace"):
        _read("update_idle.solve", ctx)


def test_no_recorded_trace_reads_zero(tmp_path, monkeypatch):
    """A context built by hand, with no trace recorded beside it, has no
    program spans to split its idle time by."""
    monkeypatch.setattr(spans, "TRACE_DIR", str(tmp_path))
    ctx = types.SimpleNamespace(trace=trace.summarize(_profile(SOLVE)))
    assert _read("update_idle.solve", ctx) == 0.0
