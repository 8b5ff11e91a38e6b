"""Share of the traced window in which the device sat idle while the host
did a solver's arithmetic between two products (``sparse.update``)."""
from harness.spans import idle_share


def read(ctx):
    return idle_share(ctx, ("sparse.update",))
