"""The solve path's host spans, read back from a trace recorded on the CPU."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import tracing
from repro.api import Topology, distribute
from repro.sparse.formats import coo_from_dense

ITERS = 4
NAMES = (tracing.SOLVE, tracing.HOIST, tracing.TRACE, tracing.PUT, tracing.FETCH, tracing.UPDATE)


def _host_events(profile):
    """``(line, name, start, end, stats)`` of every host event; stats of the spans only."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                start = int(e.start_ns)
                stats = dict(e.stats) if e.name.startswith("sparse.") else {}
                out.append(((plane.name, i), e.name, start, start + int(e.duration_ns), stats))
    return out


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A 1-D CG solve and a device-loop PageRank on one unit, traced."""
    rng = np.random.default_rng(3)
    n = 96
    b = np.where(rng.random((n, n)) < 0.06, rng.standard_normal((n, n)), 0.0)
    a = coo_from_dense((b @ b.T + n * np.eye(n)).astype(np.float32))
    sess = distribute(a, topology=Topology(1, 1), combo="NL-HC", exchange="replicated")
    rhs = rng.standard_normal(n).astype(np.float32)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(log_dir):
        sess.solve("cg", iters=ITERS, b=rhs)
        sess.solve("pagerank", iters=ITERS, device_loop=True)
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    return sess, _host_events(ProfileData.from_file(path))


def _solves(events):
    """``{solver: (solve event, [events nested in it])}``."""
    spans = [e for e in events if e[1].startswith("sparse.")]
    out = {}
    for s in (e for e in spans if e[1] == tracing.SOLVE):
        inner = [e for e in spans if e is not s and e[0] == s[0] and s[2] <= e[2] and e[3] <= s[3]]
        out[s[4]["solver"]] = (s, inner)
    return out


def test_every_span_appears_nested_under_a_solve(recorded):
    _, events = recorded
    spans = [e for e in events if e[1].startswith("sparse.")]
    assert {e[1] for e in spans} == set(NAMES)
    solves = _solves(events)
    assert set(solves) == {"cg", "pagerank"}
    nested = sum(len(inner) for _, inner in solves.values())
    assert nested == len(spans) - len(solves)  # nothing outside a solve
    args = {k: v[0][4] for k, v in solves.items()}
    assert args["cg"]["iters"] == ITERS and args["cg"]["batch"] == 1
    assert args["pagerank"]["iters"] == ITERS and args["pagerank"]["batch"] == 1
    assert args["pagerank"]["solve"] == args["cg"]["solve"] + 1


def test_span_counts_and_bytes(recorded):
    sess, events = recorded
    dp = sess.device_plan
    plan_bytes = dp.tiles.nbytes + dp.tile_row.nbytes + dp.tile_col.nbytes
    solves = _solves(events)

    _, cg = solves["cg"]
    count = {name: sum(e[1] == name for e in cg) for name in NAMES}
    # The first product (the initial residual) adds a put and a fetch.
    assert count == {
        tracing.SOLVE: 0,
        tracing.HOIST: 1,
        tracing.TRACE: 0,
        tracing.PUT: ITERS + 1,
        tracing.FETCH: ITERS + 1,
        tracing.UPDATE: ITERS,
    }
    (hoist,) = [e for e in cg if e[1] == tracing.HOIST]
    assert hoist[4]["bytes"] == plan_bytes
    for put in (e for e in cg if e[1] == tracing.PUT):
        assert put[4]["bytes"] == 4 * dp.num_col_blocks * dp.bn
    for fetch in (e for e in cg if e[1] == tracing.FETCH):
        assert fetch[4]["bytes"] == 4 * dp.num_row_blocks * dp.bm

    _, pr = solves["pagerank"]
    expect = [tracing.HOIST, tracing.PUT, tracing.TRACE, tracing.FETCH]
    assert sorted(e[1] for e in pr) == sorted(expect)
    by_name = {e[1]: e[4] for e in pr}
    # the |A| view is a session of its own: its first solve hoists its plan
    assert by_name[tracing.HOIST]["bytes"] == plan_bytes
    n = sess.matrix.shape[1]
    # the teleport, the first ranks, the column scaling and the dangling mask
    assert by_name[tracing.PUT]["bytes"] == 4 * 4 * n
    assert by_name[tracing.TRACE]["solver"] == "pagerank"
    assert by_name[tracing.TRACE]["cached"] == 0  # the view's first loop program
    # k, done, the residuals and the carried ranks
    assert by_name[tracing.FETCH]["bytes"] == 4 + 1 + 4 * ITERS + 4 * n


def test_no_event_name_carries_its_arguments(recorded):
    _, events = recorded
    assert not [e[1] for e in events if "#" in e[1]]
