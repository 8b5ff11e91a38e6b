"""Share of the HBM roofline that the programs holding the sparse product reach.

Bytes and operations are those the product needs, counted from the
matrix (``harness.counts``), times the products each program run holds;
the time is the device time of those programs in the trace. The
programs are those the cell's solver names (``spmm_programs`` in
``bench/solvers/``). Nothing to read without a trace, a peak for the
device, or a run of such a program; a run of a cell that lists this
metric then fails rather than leave it out.
"""
from harness.counts import roofline_seconds, spmm_bytes, spmm_flops


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    p = ctx.plan
    need = seconds = 0.0
    for program, (per_run, batch) in ctx.spmm.items():
        runs, secs = ctx.trace.module_seconds(program)
        b = spmm_bytes(p["nnz"], p["rows"], p["cols"], batch)
        f = spmm_flops(p["nnz"], batch)
        need += runs * per_run * roofline_seconds(b, f, ctx.peak)
        seconds += secs
    if seconds <= 0.0 or need <= 0.0:
        return None
    return 100.0 * need / seconds
