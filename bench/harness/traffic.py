"""The one general traffic generator: every mix is a data file it reads.

A mix (``bench/traffic/<name>.json``) names three parts, each a file of
its own that the generator finds by that name:

* ``"arrivals"`` — ``bench/arrivals/<name>.py``: how requests arrive in
  the measured window, and the end-to-end metrics that gives;
* ``"request"["input"]`` — ``bench/inputs/<name>.py``: how each
  request's input is drawn from the seed;
* ``"request"["solver"]`` — ``bench/solvers/<name>.py``: how the program
  is called for a request, and the plain float64 reference that checks
  its answer.

Every other key is a parameter those parts read. A new arrival shape,
input kind or solver is a new file; a new mix of known parts is data.
"""
from __future__ import annotations

import numpy as np

from harness.files import load_json, load_module, part


def rng(seed: int, tag: int) -> np.random.Generator:
    """An independent stream per purpose; any non-negative seed, however large."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), int(tag)]))


class Mix:
    """A traffic mix with the parts its names resolve to."""

    def __init__(self, name: str, root: str):
        self.name = name
        self.params = load_json(part(root, "traffic", name, ".json"))
        self.request = self.params["request"]
        self.arrivals = load_module(part(root, "arrivals", self.params["arrivals"]))
        self.inputs = load_module(part(root, "inputs", self.request["input"]))
        self.solver = load_module(part(root, "solvers", self.request["solver"]))

    def draw_inputs(self, matrix: dict, count: int, seed: int) -> list:
        """``count`` request inputs from the seed (``None`` where a request takes none)."""
        return self.inputs.draw(matrix, self.request, count, rng(seed, 1))
