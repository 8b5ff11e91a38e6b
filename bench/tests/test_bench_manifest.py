import json
import os
import re
import shutil

import pytest

from harness.cell import Cell, reader_path, run
from harness.files import BENCH, ROOT, load_module

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_top_level_keys_and_command():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["bench"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    for word in MANIFEST["command"]:
        assert not word.startswith("/") and ".." not in word
    assert os.path.exists(os.path.join(ROOT, MANIFEST["command"][1]))


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end", "per_layer"])
def test_names_and_units_use_the_allowed_characters(kind):
    names = [e["name"] for e in MANIFEST[kind]]
    assert len(set(names)) == len(names)
    for e in MANIFEST[kind]:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in ("config", "traffic"):
            if key in e:
                assert NAME.match(e[key])
        for key in e.get("reduced", []):
            assert NAME.match(key)
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] and "\t" not in e[key]


def test_bounds_and_sources():
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    names = {m["name"] for m in MANIFEST["end_to_end"]}
    assert "setup_s" in names and names <= {"solve_s", "served_p95_s", "served_rps", "setup_s"}
    for m in MANIFEST["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert "bound" not in m


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_what_its_layer_metrics_move(cell):
    c = Cell(cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e, (cell, m["name"])
        assert os.path.exists(reader_path(m["name"])), m["name"]
    # every metric that lists cells lists real ones
    for m in METRICS:
        assert set(m.get("workloads", [])) <= set(CELLS)


def test_configs_are_used_and_files_exist():
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert used == {c["name"] for c in MANIFEST["configs"]}
    for c in MANIFEST["configs"]:
        path = os.path.join(ROOT, c["file"])
        assert c["file"].startswith("bench/") and os.path.exists(path)
        with open(path) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert os.path.exists(os.path.join(BENCH, "matrices", cfg["matrix"]["generator"] + ".py"))
    for w in MANIFEST["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200


JACOBI = """# Jacobi: session.solve("jacobi", b=[N]) against float64 Jacobi.
import numpy as np
from harness.reference import Arith


def program_kwargs(request, inp):
    return {"iters": int(request["iters"]), "b": inp}


def spmm_programs(request):
    return {"jit_body": (1, 1)}


def reference(matrix, request, inputs, precision):
    ar = Arith(matrix, precision)
    d = np.zeros(matrix["shape"][0])
    on = matrix["row"] == matrix["col"]
    d[matrix["row"][on]] = matrix["val"][on]
    out = []
    for b in inputs:
        z = np.zeros_like(ar.r(b))
        for _ in range(int(request["iters"])):
            z = ar.r(z + (ar.r(b) - ar.mv(z)) / d)
        out.append(z)
    return out
"""

BURST = """# Requests in one burst of "burst", back to back, one input each.
import time


def input_count(params, seconds):
    return int(params["burst"])


def warm_up(call, inputs):
    call(0)


def measure(call, inputs, seconds, span):
    answers = [(i, call(i)) for i in range(len(inputs))]
    return {"window_s": 1.0, "requests": len(answers), "failed": 0, "answers": answers,
            "metrics": {"solve_s": 1.0 / len(answers)}}
"""

ONES = """# A right-hand side of ones, scaled by the seed's draw.


def draw(matrix, request, count, rng):
    import numpy as np
    return [np.full(matrix["shape"][0], 1.0 + rng.random(), np.float32) for _ in range(count)]
"""


def test_the_harness_finds_a_new_cell_by_its_names(tmp_path):
    """A later PR adds a configuration, a mix with a new arrival shape, input
    kind and solver, limits and a reader, as new files plus manifest
    entries; nothing else changes, and the new cell runs."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    manifest = json.loads(json.dumps(MANIFEST))
    (root / "bench" / "configs" / "tiny-grid.json").write_text(json.dumps(
        {"name": "tiny-grid", "matrix": {"generator": "stencil27", "nx": 3, "ny": 3, "nz": 3},
         "plan": {"topology": [1, 1], "combo": "NL-HC", "block": 16, "exchange": "replicated"},
         "reduced": []}))
    (root / "bench" / "traffic" / "jacobi-burst.json").write_text(json.dumps(
        {"arrivals": "burst", "burst": 3, "check": {"sample": 2},
         "request": {"solver": "jacobi", "iters": 30, "input": "ones"}}))
    (root / "bench" / "solvers" / "jacobi.py").write_text(JACOBI)
    (root / "bench" / "arrivals" / "burst.py").write_text(BURST)
    (root / "bench" / "inputs" / "ones.py").write_text(ONES)
    (root / "bench" / "limits" / "tiny.jacobi-burst.json").write_text(json.dumps({"limits": {"rel_err_l2": 1e-5}}))
    (root / "bench" / "metrics" / "nnz_per_row.py").write_text(
        "def read(ctx):\n    return ctx.plan['nnz'] / ctx.plan['rows']\n")
    manifest["configs"].append({"name": "tiny-grid", "source": "x", "file": "bench/configs/tiny-grid.json",
                                "reduced": [], "why": "x"})
    manifest["workloads"].append({"name": "tiny.jacobi-burst", "config": "tiny-grid", "traffic": "jacobi-burst",
                                  "chips": 1, "why": "x"})
    manifest["per_layer"].append({"name": "nnz_per_row.solve", "unit": "1", "better": "lower",
                                  "source": "program_counter", "layer": "tile format", "moves": "solve_s",
                                  "workloads": ["tiny.jacobi-burst"]})
    manifest["end_to_end"][0]["workloads"].append("tiny.jacobi-burst")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    cell = Cell("tiny.jacobi-burst", root=str(root))
    assert cell.mix.params["burst"] == 3 and cell.limits == {"rel_err_l2": 1e-5}
    assert [m["name"] for m in cell.end_to_end] == ["solve_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["nnz_per_row.solve"]
    assert cell.generate(seed=0)["val"].shape[0] == 343
    reader = load_module(reader_path("nnz_per_row.solve", str(root)))
    assert reader.read(type("Ctx", (), {"plan": {"nnz": 343, "rows": 27}})) == pytest.approx(343 / 27)
    result = run(cell, seed=2**32 + 5, seconds=0.1, traced=False, compile_cache=False)
    assert result["correct"], result["checks"]
    assert result["attempted"] == 3 and result["metrics"]["solve_s"]["value"] == pytest.approx(1 / 3)
    assert not run(cell, seed=3, seconds=0.1, traced=False, compile_cache=False, control=True)["correct"]
    with pytest.raises(KeyError):
        Cell("no.such-cell", root=str(root))
