"""A session builds its device-loop solve once: one hoist of the plan, and
one ``lax.while_loop`` program per static configuration, reused by every
later solve with new per-solve vectors as operands."""
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
N = 96
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _session(seed=3):
    rng = np.random.default_rng(seed)
    b = np.where(rng.random((N, N)) < 0.06, rng.standard_normal((N, N)), 0.0)
    a = coo_from_dense((b @ b.T + N * np.eye(N)).astype(np.float32))
    return distribute(a, topology=Topology(1, 1), combo="NL-HC", exchange="replicated")


def _vec(seed):
    return np.random.default_rng(seed).random(N).astype(np.float32) + 0.1


def _solves(path):
    """Per ``sparse.solve`` in the trace, in order: ``(solver, {span: [args]})``
    of the spans nested in it."""
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("sparse."):
                    start = int(e.start_ns)
                    events.append((i, e.name, start, start + int(e.duration_ns), dict(e.stats)))
    out = []
    for s in sorted((e for e in events if e[1] == tracing.SOLVE), key=lambda e: e[2]):
        inner = {}
        for e in events:
            if e is not s and e[0] == s[0] and s[2] <= e[2] and e[3] <= s[3]:
                inner.setdefault(e[1], []).append(e[4])
        out.append((s[4]["solver"], inner))
    return out


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """On one session: a host-loop CG, a device-loop Jacobi, and two
    device-loop PageRanks with different teleports, traced."""
    sess = _session()
    log_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(log_dir):
        sess.solve("cg", iters=ITERS, b=_vec(1))
        sess.solve("jacobi", iters=ITERS, b=_vec(2), device_loop=True)
        sess.solve("pagerank", iters=ITERS, device_loop=True)
        sess.solve("pagerank", iters=ITERS, seeds=_vec(4), device_loop=True)
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    return _solves(path)


def test_a_second_device_loop_solve_reuses_the_hoist_and_the_program(recorded):
    assert [solver for solver, _ in recorded] == ["cg", "jacobi", "pagerank", "pagerank"]
    (_, first), (_, second) = recorded[2:]
    # the |A| view is a session of its own: its first solve hoists it
    assert len(first[tracing.HOIST]) == 1
    assert [a["cached"] for a in first[tracing.TRACE]] == [0]
    assert tracing.HOIST not in second
    assert [a["cached"] for a in second[tracing.TRACE]] == [1]
    # the per-solve vectors still go to the device, and the ranks come back
    assert second[tracing.PUT] == first[tracing.PUT]
    assert second[tracing.FETCH] == first[tracing.FETCH]


def test_a_host_loop_and_a_device_loop_on_one_session_share_one_hoist(recorded):
    (_, cg), (_, jac) = recorded[:2]
    assert len(cg[tracing.HOIST]) == 1
    assert tracing.HOIST not in jac
    assert [a["cached"] for a in jac[tracing.TRACE]] == [0]
    # one hoist for the session, one for its |A| view, in four solves
    assert sum(len(inner.get(tracing.HOIST, ())) for _, inner in recorded) == 2


def test_the_session_keeps_one_copy_of_its_plan():
    sess = _session()
    ops, _ = sess._hoisted()
    sess.spmv(_vec(1))
    sess.solve("jacobi", iters=ITERS, device_loop=True)
    assert sess._hoisted()[0] is ops
    assert sess.device_spmm() is sess.device_spmm()
    assert sess.with_executor("reference")._hoisted()[0] is ops
    assert sess.with_value_map(np.abs)._hoisted()[0] is not ops


SOLVES = {
    "pagerank": lambda v: {"seeds": v},
    "pagerank_batched": lambda v: {"seeds": np.stack([v, v[::-1]])},
    "jacobi": lambda v: {"b": v},
}


@pytest.mark.parametrize("case", sorted(SOLVES))
def test_a_reused_program_takes_the_new_vectors(case):
    """A per-solve vector baked into the program as a constant would
    return the first solve's answer again."""
    solver, kw = case.split("_")[0], SOLVES[case]
    reused = _session()
    first = reused.solve(solver, iters=ITERS, device_loop=True, **kw(_vec(5)))
    again = reused.solve(solver, iters=ITERS, device_loop=True, **kw(_vec(6)))
    fresh = _session().solve(solver, iters=ITERS, device_loop=True, **kw(_vec(6)))
    assert not np.array_equal(again.x, first.x)
    assert np.array_equal(again.x, fresh.x)
    assert again.residuals == fresh.residuals
    assert again.iters_run == fresh.iters_run == ITERS


class _Tally:
    """The jaxpr traces and backend compiles JAX reports while entered."""

    def __enter__(self):
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._record)
        return self

    def _record(self, event, duration_secs, **_):
        self.events.append(event)

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._record)

    def count(self, event):
        return self.events.count(event)


def test_a_reused_pagerank_solve_traces_and_compiles_nothing():
    sess = _session()
    sess.solve("pagerank", iters=ITERS, device_loop=True)
    with _Tally() as tally:
        sess.solve("pagerank", iters=ITERS, seeds=_vec(7), device_loop=True)
    assert tally.count(TRACE_EVENT) == 0
    assert tally.count(COMPILE_EVENT) == 0


@pytest.mark.parametrize(
    "change", [{"iters": ITERS + 1}, {"damping": 0.5}, {"tol": 1e-3}, {"seeds": np.ones((2, N))}]
)
def test_a_changed_static_configuration_builds_a_new_program(change):
    sess = _session()
    sess.solve("pagerank", iters=ITERS, device_loop=True)
    kw = {"iters": ITERS, **change}
    with _Tally() as tally:
        res = sess.solve("pagerank", device_loop=True, **kw)
    assert tally.count(TRACE_EVENT) > 0
    fresh = _session().solve("pagerank", device_loop=True, **kw)
    assert np.array_equal(res.x, fresh.x)
    assert res.iters_run == fresh.iters_run
