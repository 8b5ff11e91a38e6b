"""The profiler's trace of a window, and its reduction to device time.

The run wraps its measured window in a host span named ``WINDOW``; the
reduction reads, from the ``.xplane.pb`` that ``jax.profiler`` writes:

* every device's ``XLA Ops`` line — busy time is the union of the op
  intervals inside the window, averaged over the devices;
* every device's ``XLA Modules`` line — one event per program run, by
  which the metric readers find the programs that hold a kernel;
* the host's lines — each idle gap of the device is named by the host
  event that covers at least half of it and is the shortest such (the
  most specific thing the host was doing), else by the one that overlaps
  it most.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import os
import re
import shutil

WINDOW = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


@dataclasses.dataclass
class Span:
    name: str
    start: int  # ns, on the trace's clock
    end: int


@dataclasses.dataclass
class TraceSummary:
    """What the readers see of a traced window (times in seconds)."""

    window_s: float
    busy_s: float  # union of device op intervals, mean over devices
    devices: int
    modules: list  # [Span] of program runs on every device, clipped to the window
    device_ops: list  # [(name, seconds)], most time first, all of them
    idle_gaps: list  # [(name, seconds)], longest first, all of them

    def module_seconds(self, prefix: str) -> tuple:
        """``(runs, seconds)`` of the programs whose name starts with ``prefix``."""
        runs = [m for m in self.modules if base_name(m.name) == prefix]
        return len(runs), sum(m.end - m.start for m in runs) / 1e9

    def breakdown(self, top: int = 10) -> dict:
        return {
            "device_ops": [[k, v] for k, v in self.device_ops[:top]],
            "idle_gaps": [[k, v] for k, v in self.idle_gaps[:top]],
        }


def base_name(name: str) -> str:
    """A program's name without the run id XLA appends: ``jit_body(12)`` -> ``jit_body``."""
    return name.split("(", 1)[0].strip()


def start(log_dir: str) -> None:
    import jax

    shutil.rmtree(log_dir, ignore_errors=True)
    os.makedirs(log_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the Python tracer costs more than the window
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def load_path(log_dir: str) -> str:
    """The newest ``.xplane.pb`` the profiler wrote under ``log_dir``."""
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str):
    """A ``ProfileData`` from an ``.xplane.pb`` (or its ``.gz``) file or a log dir."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = load_path(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _events(line):
    for e in line.events:
        s = int(e.start_ns)
        yield Span(e.name, s, s + int(e.duration_ns))


def _union(spans, lo: int, hi: int) -> list:
    """Merged ``[start, end]`` intervals of ``spans`` clipped to ``[lo, hi]``."""
    merged: list = []
    for s, e in sorted((max(x.start, lo), min(x.end, hi)) for x in spans):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _name_gap(s: int, e: int, host: list) -> str:
    best, best_key = "host (no span)", None
    covering = []
    for h in host:
        ov = min(e, h.end) - max(s, h.start)
        if ov <= 0:
            continue
        if 2 * ov >= e - s:
            covering.append(h)
        key = (ov, -(h.end - h.start))
        if best_key is None or key > best_key:
            best, best_key = h.name, key
    if covering:
        return min(covering, key=lambda h: h.end - h.start).name
    return best


def summarize(profile) -> TraceSummary:
    """Reduce a traced run to the numbers the readers and the result use."""
    host, window = [], None
    devices = []
    for plane in profile.planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = {line.name: list(_events(line)) for line in plane.lines}
            devices.append((lines.get("XLA Ops", []), lines.get("XLA Modules", [])))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in _events(line):
                    if ev.name == WINDOW:
                        window = ev
                    else:
                        host.append(ev)
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    lo, hi = window.start, window.end
    busy, modules, op_time, gaps = 0, [], {}, {}
    for ops, mods in devices:
        merged = _union(ops, lo, hi)
        busy += sum(e - s for s, e in merged)
        inside = sorted((m for m in mods if m.end > lo and m.start < hi), key=lambda m: m.start)
        modules.extend(Span(m.name, max(m.start, lo), min(m.end, hi)) for m in inside)
        starts = [m.start for m in inside]
        for op in ops:
            s, e = max(op.start, lo), min(op.end, hi)
            if e <= s:
                continue
            i = bisect.bisect_right(starts, op.start) - 1
            owner = base_name(inside[i].name) if i >= 0 and inside[i].end >= op.end else "?"
            key = f"{owner}/{op.name.split(' = ', 1)[0]}"  # the HLO instruction's name
            op_time[key] = op_time.get(key, 0) + (e - s)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                name = _name_gap(s, e, host)
                gaps[name] = gaps.get(name, 0) + (e - s)
    nd = max(len(devices), 1)
    return TraceSummary(
        window_s=(hi - lo) / 1e9,
        busy_s=busy / nd / 1e9,
        devices=len(devices),
        modules=modules,
        device_ops=sorted(((k, v / nd / 1e9) for k, v in op_time.items()), key=lambda kv: -kv[1]),
        idle_gaps=sorted(((k, v / nd / 1e9) for k, v in gaps.items()), key=lambda kv: -kv[1]),
    )
