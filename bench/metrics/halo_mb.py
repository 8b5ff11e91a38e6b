"""Megabytes the exchange moves between the chips per product, as the
program counts them on its ``sparse.put`` spans (``src/repro/tracing.py``):
``halo_bytes``, the x blocks the selective all-to-all carries from the
units that own them to the units that need them, plus ``reduce_bytes``,
the partial y every unit adds into the ``psum``. The mean over the
window's products.

The trace read is the newest under ``bench/.trace/`` (as for the idle
split, ``harness.spans``), and its window has to last as long as the
run's. Nothing to read where no ``sparse.put`` span in the window counts
those bytes: a program that runs no exchange, or predates the counts.
"""
from harness import spans, trace

PUT = "sparse.put"


def exchange_bytes(profile) -> tuple:
    """``(window_ns, [halo_bytes + reduce_bytes of each put in the window])``."""
    window, found = None, []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == trace.WINDOW:
                    window = (int(e.start_ns), int(e.start_ns) + int(e.duration_ns))
                elif e.name == PUT:
                    args = dict(e.stats)
                    if "halo_bytes" in args and "reduce_bytes" in args:
                        found.append((int(e.start_ns), int(args["halo_bytes"]) + int(args["reduce_bytes"])))
    if window is None:
        raise ValueError(f"the trace holds no {trace.WINDOW!r} span")
    lo, hi = window
    return hi - lo, [b for start, b in found if lo <= start < hi]


def read(ctx):
    if ctx.trace is None:
        return None
    path = spans.newest_trace(spans.TRACE_DIR)
    if path is None:
        return None
    window_ns, moved = exchange_bytes(trace.load(path))
    if abs(window_ns - ctx.trace.window_s * 1e9) > spans._MATCH_NS:
        raise ValueError(f"{path}: its window lasts {window_ns / 1e9:.6f} s, not the run's {ctx.trace.window_s:.6f} s")
    return sum(moved) / len(moved) / 1e6 if moved else None
