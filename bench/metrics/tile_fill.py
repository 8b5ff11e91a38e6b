"""Share of the plan's stored tile entries that hold a non-zero of the matrix."""


def read(ctx):
    stored = ctx.plan["stored_entries"]
    return 100.0 * ctx.plan["nnz"] / stored if stored else None
