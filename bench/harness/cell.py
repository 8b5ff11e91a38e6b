"""One cell of the benchmark, found by its name in ``BENCHMARK.json``.

A cell joins files that are each found by a name:

* the configuration (``configs[].file``): the matrix generator
  (``bench/matrices/<generator>.py``) with its sizes, and how the program
  plans it (``"plan"``: topology, tile block, partitioner, exchange);
* the traffic mix (``bench/traffic/<traffic>.json``) with the arrival
  shape, input kind and solver it names (:mod:`harness.traffic`);
* the correctness limits (``bench/limits/<workload>.json``): each number
  the check compares, with its limit;
* one reader per per-layer metric (``bench/metrics/<name>.py`` for a
  metric named ``<name>`` or ``<name>.<suffix>``).

:func:`run` makes the inputs from the seed, plans and warms up (the
set-up), measures a window of ``seconds``, reads the device's peak
memory, checks the answers against the solver's float64 reference and
returns the result line.
"""
from __future__ import annotations

import os
import sys
import time
import types

import numpy as np

from harness import counts, trace
from harness.device import CompileTally, describe, memory_peak_bytes, stopwatch
from harness.files import ROOT, load_json, load_module, part
from harness.traffic import Mix, rng


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def reader_path(metric: str, root: str = ROOT) -> str:
    return part(root, "metrics", metric.split(".", 1)[0])


class Cell:
    """A workload of ``BENCHMARK.json`` with its files resolved."""

    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        manifest = load_json(os.path.join(root, "BENCHMARK.json"))
        by_name = {w["name"]: w for w in manifest["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r}; known: {', '.join(sorted(by_name))}")
        self.name = name
        self.workload = by_name[name]
        self.chips = int(self.workload["chips"])
        entry = {c["name"]: c for c in manifest["configs"]}[self.workload["config"]]
        self.config = load_json(os.path.join(root, entry["file"]))
        self.mix = Mix(self.workload["traffic"], root)
        self.limits = load_json(part(root, "limits", name, ".json"))["limits"]
        self.end_to_end = [m for m in manifest["end_to_end"] if self._applies(m)]
        moved = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m
            for m in manifest["per_layer"]
            if (name in m["workloads"] if "workloads" in m else m["moves"] in moved)
        ]

    def _applies(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def generate(self, seed: int) -> dict:
        spec = self.config["matrix"]
        return load_module(part(self.root, "matrices", spec["generator"])).generate(spec, seed)


# -- the program under test --------------------------------------------------


def plan(cell: Cell, matrix: dict):
    """``distribute()`` the generated matrix as the configuration says."""
    from repro.api import Topology, distribute
    from repro.sparse.formats import COO

    p = cell.config["plan"]
    a = COO(tuple(matrix["shape"]), matrix["row"], matrix["col"], matrix["val"])
    return distribute(
        a,
        topology=Topology(*p["topology"]),
        combo=p["combo"],
        exchange=p["exchange"],
        block=int(p["block"]),
    )


def direct(session, mix: Mix, inp) -> np.ndarray:
    """One request through ``session.solve``, as a user calls it."""
    return session.solve(mix.request["solver"], **mix.solver.program_kwargs(mix.request, inp)).x


# -- the comparison ------------------------------------------------------------


def rel_err(got, ref, order) -> float:
    ref = np.asarray(ref, np.float64)
    diff = np.asarray(got, np.float64) - ref
    return float(np.linalg.norm(diff, order) / np.linalg.norm(ref, order))


def compare(answers: list, refs: list) -> dict:
    """The widest relative gap of any answer to its reference: the 2-norm
    for a solve (``rel_err_l2``), the 1-norm for a probability vector
    (``rel_err_l1``)."""
    return {
        "rel_err_l2": max(rel_err(a, r, 2) for a, r in zip(answers, refs)),
        "rel_err_l1": max(rel_err(a, r, 1) for a, r in zip(answers, refs)),
    }


def checks_against(found: dict, limits: dict) -> dict:
    """``{name: {"value", "limit"}}`` for every limited number; a number the
    run could not produce reads ``inf``."""
    return {
        k: {"value": float(found.get(k, float("inf"))), "limit": float(lim)}
        for k, lim in limits.items()
    }


def sample(count: int, k: int, seed: int) -> list:
    """``k`` distinct indices below ``count`` drawn from the seed, sorted."""
    k = min(k, count)
    return sorted(int(i) for i in rng(seed, 5).choice(count, size=k, replace=False))


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


# -- a run ---------------------------------------------------------------------


def run(
    cell: Cell,
    seed: int,
    seconds: float,
    traced: bool,
    devices=None,
    *,
    control: bool = False,
    compile_cache: bool = True,
) -> dict:
    """One run of ``cell``: the result line as a dict (``correct`` and all).

    ``devices`` are the chips the run may use (``None``: whatever JAX
    has, for tests on the CPU). ``control`` puts the bfloat16 reference
    in the program's place: the check has to find it wrong.
    ``compile_cache=False`` leaves JAX's persistent cache off (tests)."""
    import jax

    devices = devices or jax.devices()[: cell.chips]
    if compile_cache:
        from repro.compile_cache import enable_compile_cache

        log(f"compile cache: {enable_compile_cache()}")
        # Every program the run compiles goes to the cache, however fast
        # it compiled, so that a second run in the checkout compiles nothing.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    mix = cell.mix
    info: dict = {}
    with CompileTally() as tally:
        t_setup = time.perf_counter()
        with stopwatch(info, "generate_s"):
            matrix = cell.generate(seed)
        with stopwatch(info, "plan_s"):
            session = plan(cell, matrix)
        dp = session.device_plan
        plan_counts = count_plan(matrix, session)
        log(
            f"setup: generated {plan_counts['nnz']} nnz in {info['generate_s']:.3f}s; "
            f"planned {dp.num_units} unit(s) x {dp.t} tiles of {dp.bm}x{dp.bn} "
            f"({dp.tiles.nbytes} bytes) in {info['plan_s']:.3f}s"
        )
        inputs = mix.draw_inputs(matrix, mix.arrivals.input_count(mix.params, seconds), seed)
        if control:  # the reference in bfloat16 answers each request

            def call(i):
                return mix.solver.reference(matrix, mix.request, [inputs[i]], "bfloat16")[0]

        else:

            def call(i):
                return direct(session, mix, inputs[i])

        mark = tally.mark()
        with stopwatch(info, "warm_s"):
            mix.arrivals.warm_up(call, inputs)
        warm = tally.since(mark)
        setup_s = time.perf_counter() - t_setup
        log(
            f"setup: first requests (device hoist, compile, cache loads) {info['warm_s']:.3f}s; "
            f"{tally.compiles} compiles, {tally.cache_loads} cache loads, "
            f"{tally.seconds:.3f}s compiling in set-up ({warm['compiles']} compiles in the warm-up); "
            f"setup_s {setup_s:.3f}"
        )

        trace_dir = os.path.join(cell.root, "bench", ".trace", cell.name)
        if traced:
            trace.start(trace_dir)
        mark = tally.mark()
        with _annotate(trace.WINDOW):
            out = mix.arrivals.measure(call, inputs, seconds, _annotate)
        wc = tally.since(mark)
        if traced:
            jax.block_until_ready(jax.device_put(0.0))
            trace.stop()
        peak = memory_peak_bytes(devices)
        log(
            f"window: {out['window_s']:.3f}s, {out['requests']} requests, {wc['compiles']} compiles, "
            f"{wc['cache_loads']} compile-cache loads, {wc['compile_s']:.3f}s in compile requests"
        )
        log(f"peak_bytes_in_use: {peak}")

    picks = sample(len(out["answers"]), int(mix.params["check"]["sample"]), seed)
    answers = [out["answers"][k][1] for k in picks]
    sample_inputs = [inputs[out["answers"][k][0]] for k in picks]
    layer_ctx = None
    if traced:
        layer_ctx = layer_context(
            cell, trace.summarize(trace.load(trace_dir)), plan_counts, describe(devices), out["requests"]
        )
    del session, dp  # free the program's state before the reference runs
    found = {}
    if answers:
        found = compare(answers, mix.solver.reference(matrix, mix.request, sample_inputs, "float64"))
    checks = checks_against(found, cell.limits)
    correct = bool(answers) and out["failed"] == 0 and all(
        c["value"] <= c["limit"] for c in checks.values()
    )
    metrics = {**out["metrics"], "setup_s": setup_s}
    result = {
        "correct": correct,
        "attempted": out["requests"],
        "failed": out["failed"],
        "metrics": {},
        "device": {**describe(devices), "memory_peak_bytes": peak},
    }
    if traced:
        result["metrics"] = read_layers(cell, layer_ctx)
        result["device"]["busy_s"] = layer_ctx.trace.busy_s
        result["device"]["window_s"] = layer_ctx.trace.window_s
        result["breakdown"] = layer_ctx.trace.breakdown()
    else:
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": float(metrics[m["name"]]), "unit": m["unit"]}
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    return result


def count_plan(matrix: dict, session) -> dict:
    """The matrix's counts and the plan's stored tiles, for the readers."""
    dp = session.device_plan
    return {
        "nnz": int(matrix["val"].shape[0]),
        "rows": int(matrix["shape"][0]),
        "cols": int(matrix["shape"][1]),
        "tiles": int(dp.num_units * dp.t),
        "stored_entries": int(dp.tiles.size),
    }


def layer_context(cell: Cell, summary, plan_counts: dict, device: dict, requests: int):
    """What the per-layer readers see of a traced run."""
    return types.SimpleNamespace(
        trace=summary,
        plan=plan_counts,
        peak=counts.peaks(device["kind"]) if device["platform"] == "tpu" else None,
        spmm=cell.mix.solver.spmm_programs(cell.mix.request),
        solver_iters=requests * int(cell.mix.request["iters"]),
    )


def read_layers(cell: Cell, ctx) -> dict:
    """The cell's per-layer metrics from their readers. A reader that finds
    nothing to read leaves its metric out; where the metric lists this cell
    among its ``workloads``, finding nothing is a fault of the benchmark
    (a program renamed, a kernel moved) and the run fails."""
    out = {}
    for m in cell.per_layer:
        value = load_module(reader_path(m["name"], cell.root)).read(ctx)
        if value is None:
            if cell.name in m.get("workloads", ()):
                raise RuntimeError(f"per-layer metric {m['name']!r} found nothing to read in {cell.name!r}")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
