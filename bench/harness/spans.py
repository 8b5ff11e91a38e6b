"""The device's idle time split by the program's own spans.

The program records host spans named ``sparse.*`` (``src/repro/tracing.py``)
into the profiler's trace: ``sparse.solve`` around each solve, and inside
it ``sparse.hoist``, ``sparse.trace``, ``sparse.put``, ``sparse.fetch`` and
``sparse.update``. A span's self time is its interval less the intervals of
the ``sparse.*`` spans nested in it on the same thread. :func:`idle_by_span`
gives each span name the device idle time (the complement of the union of
``XLA Ops`` intervals) that lies in its self time inside the window. Self
intervals on one thread are disjoint, so an idle nanosecond goes to one
name, and no runtime event nested in a span can take it from the span.

The readers' context carries no trace path, so the trace read is the newest
``.xplane.pb`` under ``<repo>/bench/.trace/``; its ``bench.window`` span has
to last as long as the window the context was reduced from, or the read
fails, so that another run's trace is never read.

A window with no ``sparse.solve`` span reads ``None``, and the harness then
fails a run of a cell that lists the metric: the program's spans are gone
or renamed. A program that predates its spans (no ``repro.tracing``, as at
the parent of the change that added them) reads 0.0 instead, so that the
parent's traced run, which lists the same metrics, does not fail.
"""
from __future__ import annotations

import glob
import importlib.util
import os

from harness import trace
from harness.files import ROOT

PREFIX = "sparse."
SOLVE = "sparse.solve"
TRACE_DIR = os.path.join(ROOT, "bench", ".trace")
_MATCH_NS = 1000  # the window's length, in the file and in the context, to 1 us

_cache: dict = {}


def newest_trace(trace_dir: str) -> str | None:
    """The most recently written ``.xplane.pb`` under ``trace_dir``, if any."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def _self_intervals(spans: list) -> list:
    """``(name, start, end)`` pieces of the self time of spans on one thread.

    Spans on a thread nest; a child that outlasts its parent is cut at the
    parent's end."""
    out = []
    stack: list = []  # [span, end, where the parent's self time resumes]

    def pop():
        sp, end, cursor = stack.pop()
        out.append((sp.name, cursor, end))
        if stack:
            stack[-1][2] = end

    for sp in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1][1] <= sp.start:
            pop()
        end = sp.end
        if stack:
            out.append((stack[-1][0].name, stack[-1][2], sp.start))
            end = min(end, stack[-1][1])
        stack.append([sp, end, sp.start])
    while stack:
        pop()
    return [p for p in out if p[2] > p[1]]


def _overlap(a: list, b: list) -> int:
    """Total length of the intersection of two sorted lists of disjoint intervals."""
    total = i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def split_idle(profile) -> tuple:
    """``(window_ns, {span name: idle seconds})`` of a recorded trace, the
    idle time a mean over the devices; ``None`` in place of the dict where
    the window holds no ``sparse.solve`` span."""
    window, threads, devices = None, [], []
    for plane in profile.planes:
        if trace._DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.append(list(trace._events(line)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                mine = []
                for ev in trace._events(line):
                    if ev.name == trace.WINDOW:
                        window = ev
                    elif ev.name.startswith(PREFIX):
                        mine.append(ev)
                if mine:
                    threads.append(mine)
    if window is None:
        raise ValueError(f"the trace holds no {trace.WINDOW!r} span")
    lo, hi = window.start, window.end
    pieces: dict = {}
    for spans in threads:
        for name, s, e in _self_intervals(spans):
            pieces.setdefault(name, []).append(trace.Span(name, s, e))
    if not any(s.end > lo and s.start < hi for s in pieces.get(SOLVE, ())):
        return hi - lo, None
    self_time = {name: trace._union(p, lo, hi) for name, p in pieces.items()}
    idle: dict = {name: 0 for name in self_time}
    for ops in devices:
        edges = [lo] + [x for iv in trace._union(ops, lo, hi) for x in iv] + [hi]
        gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        for name, iv in self_time.items():
            idle[name] += _overlap(gaps, iv)
    nd = max(len(devices), 1)
    return hi - lo, {name: v / nd / 1e9 for name, v in idle.items()}


def idle_by_span(ctx):
    """``{span name: device idle seconds in its self time}`` for the run
    that ``ctx`` was reduced from; ``None`` where its window holds no
    ``sparse.solve`` span (the program's spans are gone or renamed), and
    ``{}`` where no trace was recorded under :data:`TRACE_DIR` at all (a
    context built by hand)."""
    path = newest_trace(TRACE_DIR)
    if path is None:
        return {}
    key = (path, os.path.getmtime(path), os.path.getsize(path))
    if key not in _cache:
        _cache.clear()
        _cache[key] = split_idle(trace.load(path))
    window_ns, idle = _cache[key]
    if abs(window_ns - ctx.trace.window_s * 1e9) > _MATCH_NS:
        raise ValueError(
            f"{path}: its window lasts {window_ns / 1e9:.6f} s, the run's "
            f"{ctx.trace.window_s:.6f} s: not this run's trace"
        )
    return idle


def program_has_spans() -> bool:
    """Whether the program under test records its spans at all."""
    return importlib.util.find_spec("repro.tracing") is not None


def idle_share(ctx, names: tuple):
    """Percent of the window in which the device sat idle inside the self
    time of the spans ``names``: 0.0 where none of them ran, ``None`` where
    the window holds no ``sparse.solve`` span of a program that records one."""
    if ctx.trace is None or ctx.trace.devices == 0 or ctx.trace.window_s <= 0.0:
        return None
    idle = idle_by_span(ctx)
    if idle is None:
        return None if program_has_spans() else 0.0
    return 100.0 * sum(idle.get(n, 0.0) for n in names) / ctx.trace.window_s
