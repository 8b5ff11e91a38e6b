"""Share of the traced window in which the chips ran collective operations:
the exchange of x between chips and the sum of their partial y.

An operation of the device trace is a collective when its HLO
instruction is one by name: XLA's own names (``all-to-all``,
``all-reduce``, ``all-gather``, ``collective-permute``,
``reduce-scatter``, and their async ``-start``/``-done`` halves) or the
names JAX gives the instructions it emits for its collectives
(``all_to_all``, ``psum``, ``psum_invariant``, ``all_gather``,
``ppermute``, ``psum_scatter``). The time is their device time inside
the window, a mean over the chips (``TraceSummary.device_ops``). Nothing
to read where the window holds no collective, as on one chip.
"""
import re

COLLECTIVE = re.compile(
    r"^%?(all[-_]to[-_]all|all[-_]reduce|all[-_]gather|collective[-_]permute|"
    r"reduce[-_]scatter|psum(_invariant|_scatter)?|ppermute)(-start|-done)?(\.\d+)?$"
)


def is_collective(op: str) -> bool:
    """Whether ``op``, a ``program/instruction`` key of the trace's
    device operations, is a collective."""
    return bool(COLLECTIVE.match(op.rsplit("/", 1)[-1]))


def read(ctx):
    t = ctx.trace
    if t is None or t.devices == 0 or t.window_s <= 0.0:
        return None
    seconds = sum(s for op, s in t.device_ops if is_collective(op))
    return 100.0 * seconds / t.window_s if seconds > 0.0 else None
