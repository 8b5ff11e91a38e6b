"""Share of the traced window in which the device sat idle while the host
moved data to or from it: a plan's arrays (``sparse.hoist``), padded x
(``sparse.put``), and the wait for a result and its copy back
(``sparse.fetch``)."""
from harness.spans import idle_share


def read(ctx):
    return idle_share(ctx, ("sparse.put", "sparse.fetch", "sparse.hoist"))
