"""Share of the roofline of all the run's chips that the sharded product reaches.

The bytes and operations one product needs are counted from the matrix
(``harness.counts``), as for ``spmm_roofline``, but the least time is
taken against the aggregate peak of the ``D`` chips that share the
product, and the time is the mean device time per chip of the programs
that hold it: the jitted ``shard_map`` step of the plan's exchange
(``repro.pmvc.dist.make_pmvc_step``), one product per run on every
chip, with as many right-hand sides as the cell's solver gives its
programs (``spmm_programs``). With ``runs`` and ``seconds`` summed over
the chips' runs of the step, ``runs * roofline_s / (D * seconds)``,
where ``roofline_s`` is one chip's least time for one product. Nothing
to read without a trace, a peak for the device, or a run of the step.
"""
from harness.counts import roofline_seconds, spmm_bytes, spmm_flops

# The step's program under each exchange: replicated, selective, overlap.
STEPS = ("jit_step", "jit_step_selective", "jit_step_overlap")


def read(ctx):
    if ctx.trace is None or ctx.peak is None or ctx.trace.devices == 0:
        return None
    p = ctx.plan
    batch = max(b for _, b in ctx.spmm.values())
    need = seconds = 0.0
    for program in STEPS:
        runs, secs = ctx.trace.module_seconds(program)
        b = spmm_bytes(p["nnz"], p["rows"], p["cols"], batch)
        need += runs * roofline_seconds(b, spmm_flops(p["nnz"], batch), ctx.peak)
        seconds += secs
    if seconds <= 0.0 or need <= 0.0:
        return None
    return 100.0 * need / (ctx.trace.devices * seconds)
