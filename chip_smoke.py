"""Smoke run of the sparse-solve main path on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the shard_map path across four chips

One process drives the chip (a chip belongs to one process at a time).
Without a TPU it exits non-zero before printing any result.

One chip, in order — any failure raises and exits non-zero:

1. device check, then the persistent compile cache
   (:mod:`repro.compile_cache`);
2. plan a banded 60k x 60k / 1.2M-nnz operator on ``Topology(4, 4)``
   (``NL-HC``, selective exchange, 128 x 128 tiles) with ``distribute``;
3. ``spmv`` on ``[N]`` and ``[8, N]`` against the float64 ``reference``
   executor (relative error <= 1e-5), and each column of the batch
   bitwise equal to its batched-of-1 product;
4. ``pagerank`` with and without ``device_loop`` against the reference
   executor's solve;
5. a :class:`SparseServeEngine` with 8 slots under a :class:`ServeDriver`
   serving 16 requests over every registered stepper, each result
   bitwise equal to the direct ``solve`` / ``spmv`` call;
6. compile seconds per phase and the device's peak bytes in use.

``--chips 4`` runs only the four-chip phase: the same operator planned on
``Topology(2, 2)`` (one unit per chip), ``spmv`` through the
``shard_map`` executor for the replicated, selective and ``overlap:2``
exchanges, each checked against ``simulate`` on the same plan and
against ``reference``.

The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The times printed are set-up and first-call times of a smoke run, not
performance measurements.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

N, NNZ, SEED, BLOCK = 60_000, 1_200_000, 0, 128
REL_TOL = 1e-5  # f32 contraction against the float64 CSR reference
ITERS = 20
SLOTS = 8
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(np.asarray(got, np.float64) - ref) / np.linalg.norm(ref))


class CompileTally:
    """Counts XLA backend compiles (and their seconds) while entered."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def __call__(self, event: str, duration_secs: float, **_) -> None:
        if event == _COMPILE_EVENT:
            self.count += 1
            self.seconds += duration_secs

    def __enter__(self) -> "CompileTally":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self)

    @contextlib.contextmanager
    def phase(self, name: str):
        n0, s0, t0 = self.count, self.seconds, time.perf_counter()
        log(f"[{name}]")
        yield
        log(
            f"[{name}] ok in {time.perf_counter() - t0:.3f}s "
            f"({self.count - n0} compiles, {self.seconds - s0:.3f}s compiling)"
        )


def first_and_second(fn, *args, **kw):
    """Call ``fn`` twice; returns both results and both wall times (the
    first includes tracing and compilation)."""
    times, outs = [], []
    for _ in range(2):
        t0 = time.perf_counter()
        outs.append(fn(*args, **kw))
        times.append(time.perf_counter() - t0)
    return outs, times


def spd_variant(a):
    """``A + Aᵀ`` with a strictly dominant positive diagonal: SPD, so
    Jacobi and CG converge on it; same band as ``a``."""
    from repro.sparse.formats import COO

    n = a.shape[0]
    off = a.row != a.col
    row = np.concatenate([a.row[off], a.col[off]]).astype(np.int64)
    col = np.concatenate([a.col[off], a.row[off]]).astype(np.int64)
    val = np.concatenate([a.val[off], a.val[off]]).astype(np.float64)
    key, inv = np.unique(row * n + col, return_inverse=True)
    val = np.bincount(inv, weights=val)
    row, col = key // n, key % n
    diag = 1.0 + np.bincount(row, weights=np.abs(val), minlength=n)
    d = np.arange(n)
    row, col = np.concatenate([row, d]), np.concatenate([col, d])
    val = np.concatenate([val, diag])
    order = np.lexsort((col, row))
    return COO(
        (n, n),
        row[order].astype(np.int32),
        col[order].astype(np.int32),
        val[order].astype(np.float32),
    )


def plan(a, topology, exchange, block):
    from repro.api import distribute

    t0 = time.perf_counter()
    sess = distribute(a, topology=topology, combo="NL-HC", exchange=exchange, block=block)
    dp = sess.device_plan
    log(
        f"plan {exchange} on {topology}: {time.perf_counter() - t0:.3f}s, "
        f"{dp.num_units} units x {dp.t} tiles of {dp.bm}x{dp.bn}, "
        f"{dp.tiles.nbytes} tile bytes resident"
    )
    return sess


def check_spmv(sess, x, *, executor="simulate", label=""):
    (y, y2), (t1, t2) = first_and_second(sess.spmv, x, executor=executor)
    require(np.array_equal(y, y2), f"{label}: repeated spmv differs")
    require(np.isfinite(y).all(), f"{label}: non-finite output")
    err = rel_err(y, sess.spmv(x, executor="reference"))
    log(
        f"{label} {executor} spmv {list(x.shape)}: first call {t1:.3f}s, "
        f"second {t2:.3f}s, rel err vs reference {err:.3e}"
    )
    require(err <= REL_TOL, f"{label}: rel err {err:.3e} > {REL_TOL}")
    return y


def smoke_one_chip(tally, *, n=N, nnz=NNZ, block=BLOCK) -> None:
    from repro.api import STEPPERS, Topology
    from repro.serve import ServeDriver, SparseServeEngine, Status
    from repro.sparse.generate import banded_coo

    topo = Topology(4, 4)
    rng = np.random.default_rng(SEED)
    with tally.phase("plan"):
        a = banded_coo(n, nnz, seed=SEED)
        sess = plan(a, topo, "selective", block)

    with tally.phase("spmv accuracy"):
        check_spmv(sess, rng.standard_normal(n).astype(np.float32), label="[N]")
        xb = rng.standard_normal((SLOTS, n)).astype(np.float32)
        yb = check_spmv(sess, xb, label="[8, N]")
        for j in range(SLOTS):
            require(
                np.array_equal(yb[j], sess.spmv(xb[j : j + 1])[0]),
                f"column {j} of the [8, N] product differs from batched-of-1",
            )
        log("[8, N] columns bitwise equal to batched-of-1 products")

    with tally.phase("solves"):
        ref = sess.with_executor("reference").solve("pagerank", iters=ITERS)
        for device_loop in (False, True):
            t0 = time.perf_counter()
            res = sess.solve("pagerank", iters=ITERS, device_loop=device_loop)
            err = rel_err(res.x, ref.x)
            log(
                f"pagerank device_loop={device_loop}: {time.perf_counter() - t0:.3f}s "
                f"(compile included), {res.iters_run} iters, "
                f"rel err vs reference {err:.3e}"
            )
            require(res.iters_run == ITERS, "pagerank stopped early")
            require(err <= REL_TOL, f"pagerank rel err {err:.3e} > {REL_TOL}")

    with tally.phase("serving"):
        sessions = {"banded": sess, "banded-spd": plan(spd_variant(a), topo, "selective", block)}
        graph_of = {"pagerank": "banded", "spmv": "banded", "jacobi": "banded-spd", "cg": "banded-spd"}
        require(set(graph_of) == set(STEPPERS.names()), "a stepper has no smoke request")
        key_of = {"pagerank": "seeds", "spmv": "x", "jacobi": "b", "cg": "b"}
        requests = [
            (solver, {key_of[solver]: rng.random(n).astype(np.float32)})
            for _ in range(4)
            for solver in sorted(graph_of)
        ]
        eng = SparseServeEngine(batch_slots=SLOTS, max_queue=64, default_iters=ITERS)
        for name, s in sessions.items():
            eng.register_graph(name, s)
        t0 = time.perf_counter()
        with ServeDriver(eng):
            tickets = [
                eng.submit(graph_of[solver], solver, payload=payload, iters=ITERS)
                for solver, payload in requests
            ]
            for t in tickets:
                require(t.wait(timeout=900.0), f"ticket {t.tid} did not finish")
        log(f"served {len(tickets)} requests in {time.perf_counter() - t0:.3f}s (compile included)")
        for t, (solver, payload) in zip(tickets, requests):
            require(t.status is Status.DONE, f"ticket {t.tid} ({solver}): {t.status} {t.error}")
            s = sessions[graph_of[solver]]
            if solver == "spmv":
                direct = s.spmv(payload["x"][None])[0]
            else:
                batched1 = {k: v[None] for k, v in payload.items()}
                direct = s.solve(solver, iters=ITERS, tol=0.0, **batched1).x[0]
            require(np.isfinite(t.result.x).all(), f"ticket {t.tid} ({solver}): non-finite")
            require(
                np.array_equal(t.result.x, direct),
                f"ticket {t.tid} ({solver}): served result differs from direct call",
            )
        snap = eng.metrics.snapshot()
        log(
            f"served == direct bitwise for all {len(tickets)} requests; "
            f"{snap['lane_steps']} lane steps for {snap['slot_iters']} request-iterations"
        )


def smoke_four_chips(tally, *, n=N, nnz=NNZ, block=BLOCK) -> None:
    from repro.api import Topology
    from repro.sparse.generate import banded_coo

    rng = np.random.default_rng(SEED)
    a = banded_coo(n, nnz, seed=SEED)
    xs = (
        rng.standard_normal(n).astype(np.float32),
        rng.standard_normal((SLOTS, n)).astype(np.float32),
    )
    for exchange in ("replicated", "selective", "overlap:2"):
        with tally.phase(f"shard_map {exchange}"):
            sess = plan(a, Topology(2, 2), exchange, block)
            require(sess.topology.units == 4, "expected one unit per chip")
            for x in xs:
                label = f"{exchange} {list(x.shape)}"
                y = check_spmv(sess, x, executor="shard_map", label=label)
                y_sim = sess.spmv(x, executor="simulate")
                err = rel_err(y, y_sim)
                log(
                    f"{label}: rel err vs simulate {err:.3e}, "
                    f"bitwise equal: {bool(np.array_equal(y, y_sim))}"
                )
                require(err <= REL_TOL, f"{label}: rel err vs simulate {err:.3e}")


def device_check(chips: int):
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU, JAX found platform {dev.platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: need {chips} chips, found {len(devices)}")
    log(f"device: {dev.device_kind}, count {len(devices)}")
    return dev, len(devices)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    dev, count = device_check(args.chips)
    from repro.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    with CompileTally() as tally:
        if args.chips == 4:
            smoke_four_chips(tally)
        else:
            smoke_one_chip(tally)
        log(f"total: {tally.count} compiles, {tally.seconds:.3f}s compiling")
    stats = dev.memory_stats() or {}
    log(f"peak bytes in use: {stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps(
        {"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind, "count": count}}
    ))
    return 0


if __name__ == "__main__":
    sys.exit(main())
