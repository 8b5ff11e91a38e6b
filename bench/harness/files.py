"""Where the benchmark's parts live: each is a file found by its name."""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def part(root: str, kind: str, name: str, ext: str = ".py") -> str:
    """``<root>/bench/<kind>/<name><ext>``: a part of the given kind by its name."""
    return os.path.join(root, "bench", kind, name + ext)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import a file of the benchmark by its path (names may hold dots)."""
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    name = "bench_" + os.path.relpath(path, BENCH).replace(os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
