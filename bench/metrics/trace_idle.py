"""Share of the traced window in which the device sat idle while the host
traced, compiled or loaded from the compile cache, and dispatched a device
loop's ``while_loop`` (``sparse.trace``)."""
from harness.spans import idle_share


def read(ctx):
    return idle_share(ctx, ("sparse.trace",))
