"""Record the small chip traces that ``test_bench_trace.py`` reduces.

    python bench/tests/record_trace.py [--out DIR]   # on a TPU

Runs each cell at the tiny sizes of ``tiny.py`` for half a second with
the profiler on, and keeps each trace, gzipped, as
``<DIR>/<cell>.xplane.pb.gz`` (by default ``bench/tests/data/``), with
the seed and the plan's counts beside it in ``<cell>.json``.
"""
import argparse
import gzip
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

from tiny import tiny_cell  # noqa: E402

from harness import trace  # noqa: E402
from harness.cell import run  # noqa: E402
from harness.device import require_tpu  # noqa: E402

CELLS = ("hpcg104.cg-direct", "g500s15.pr-direct")
SEED = 7

ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
ap.add_argument("--out", default=os.path.join(HERE, "data"))
out = ap.parse_args().out
os.makedirs(out, exist_ok=True)
for name in CELLS:
    cell = tiny_cell(name)
    result = run(cell, SEED, 0.5, True, require_tpu(1), compile_cache=False)
    assert result["correct"], result["checks"]
    src = trace.load_path(os.path.join(cell.root, "bench", ".trace", name))
    with open(src, "rb") as f, gzip.open(os.path.join(out, name + ".xplane.pb.gz"), "wb") as g:
        shutil.copyfileobj(f, g)
    with open(os.path.join(out, name + ".json"), "w") as f:
        json.dump({"seed": SEED, "matrix": cell.config["matrix"], "result": result}, f, indent=1)
    print(name, os.path.getsize(src), result["device"], result["metrics"])
