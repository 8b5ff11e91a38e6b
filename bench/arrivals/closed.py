"""One client that sends its next request when the last one returns.

The client cycles through ``"distinct"`` inputs drawn from the seed. The
window closes with the first request to finish after ``seconds``: the
request in flight at the close is finished and counted, so the window
holds whole requests only. End to end: ``solve_s``, the window's length
over the requests completed in it.
"""
from __future__ import annotations

import time


def input_count(params: dict, seconds: float) -> int:
    return int(params["distinct"])


def warm_up(call, inputs: list) -> None:
    """One request: the path's programs compile and its data reach the device."""
    call(0)


def measure(call, inputs: list, seconds: float, span) -> dict:
    answers = []
    t0 = time.perf_counter()
    while True:
        i = len(answers) % len(inputs)
        with span("bench.request"):
            answers.append((i, call(i)))
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    return {
        "window_s": window_s,
        "requests": len(answers),
        "failed": 0,
        "answers": answers,
        "metrics": {"solve_s": window_s / len(answers)},
    }
