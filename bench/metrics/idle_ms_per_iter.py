"""Device idle milliseconds per solver iteration completed in the window:
the host's share of each iteration that the device waited for."""


def read(ctx):
    t = ctx.trace
    if t is None or t.devices == 0 or not ctx.solver_iters:
        return None
    return 1e3 * (t.window_s - t.busy_s) / ctx.solver_iters
