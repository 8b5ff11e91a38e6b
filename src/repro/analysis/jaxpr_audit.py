"""Jaxpr collective auditor: pin the exchange schedule without devices.

:func:`repro.pmvc.dist.make_pmvc_step` promises an ordering the whole
overlap design rests on — *every* wave's ``all_to_all`` is issued
before the first contraction, so XLA's async collectives can hide wave
k+1's transfer behind wave k's FLOPs. Nothing at runtime checks this:
a refactor that accidentally interleaves a wave's collective after a
contraction still computes the right numbers, just without the
overlap. This module traces each stepper through an
:class:`jax.sharding.AbstractMesh` (no devices needed — one CPU host
can audit a 64-unit schedule), extracts the collective/contraction
sequence from the jaxpr, and compares it against golden pins:

======================  =======================================
mode                    schedule signature
======================  =======================================
replicated              ``dot psum``
selective               ``a2a dot psum``
overlap (K waves)       ``a2a``×K · ``dot``×(K+1) · ``psum``
======================  =======================================

On top of the schedule pin, :func:`audit_jaxpr` asserts hygiene
properties on any traced computation: no f64 promotion anywhere in the
graph (the contraction contract is float32), no host callbacks (a
callback inside a jitted step is a silent device→host sync), and no
recompile bait in loop carries (weak-typed avals — a python scalar
carried through ``lax.while_loop``/``scan`` retraces on the first
concrete call).
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, List, Optional, Tuple

import numpy as np

from repro.analysis.passes import Finding
from repro.pmvc.plan_device import DevicePlan, OverlapPlan, SelectivePlan

__all__ = [
    "AuditReport",
    "audit_jaxpr",
    "audit_plan",
    "audit_session",
    "golden_signature",
    "iter_eqns",
    "schedule_signature",
    "trace_pmvc_step",
]

# The unit contraction's multiply-then-reduce
# (:func:`repro.pmvc.dist._unit_spmm`) traces as one ``reduce_sum``.
_CONTRACTION_PRIMITIVE = "reduce_sum"

# Primitive names folded into the schedule signature, normalized. A psum
# whose operand varies over the mesh axis traces as "psum_invariant"
# inside shard_map; both spell the same reduction.
_SIGNATURE_TOKENS = {
    "all_to_all": "a2a",
    "all_gather": "all_gather",
    "ppermute": "ppermute",
    _CONTRACTION_PRIMITIVE: "dot",
    "psum": "psum",
    "psum_invariant": "psum",
}

# Host-callback primitives — none may appear inside a step (a silent
# device→host sync per call, and a tracing hazard under AbstractMesh).
_CALLBACK_PRIMITIVES = {
    "pure_callback",
    "io_callback",
    "debug_callback",
    "outside_call",
    "host_callback_call",
}


def _subjaxprs(v) -> List:
    """Jaxprs nested inside one eqn param value (Jaxpr, ClosedJaxpr, or
    lists thereof — shard_map/pjit/while/scan all differ here)."""
    if hasattr(v, "eqns"):
        return [v]
    if hasattr(v, "jaxpr"):
        return [v.jaxpr]
    if isinstance(v, (list, tuple)):
        out = []
        for x in v:
            out.extend(_subjaxprs(x))
        return out
    return []


def iter_eqns(jaxpr) -> Iterator:
    """Depth-first walk over every eqn, descending into shard_map /
    pjit / while / scan bodies — program order within each body."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from iter_eqns(sub)


def _closed_to_jaxpr(closed):
    return closed.jaxpr if hasattr(closed, "jaxpr") else closed


def schedule_signature(closed_jaxpr) -> str:
    """The collective/contraction sequence as a space-joined token
    string — ``"a2a a2a dot dot dot psum"`` for ``overlap:2``."""
    tokens = []
    for eqn in iter_eqns(_closed_to_jaxpr(closed_jaxpr)):
        tok = _SIGNATURE_TOKENS.get(eqn.primitive.name)
        if tok is not None:
            tokens.append(tok)
    return " ".join(tokens)


def golden_signature(exchange: Optional[str], waves: int = 1) -> str:
    """The pinned schedule for a stepper mode. ``exchange`` is
    ``None``/``"replicated"``, ``"selective"``, or ``"overlap"``
    (``waves`` = K)."""
    kind = exchange or "replicated"
    kind = kind.split(":", 1)[0]
    if kind == "replicated":
        return "dot psum"
    if kind == "selective":
        return "a2a dot psum"
    if kind == "overlap":
        return " ".join(["a2a"] * waves + ["dot"] * (waves + 1) + ["psum"])
    raise ValueError(f"unknown exchange kind {exchange!r}")


# ---------------------------------------------------------------------------
# hygiene audits


def _avals(eqn):
    for var in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(var, "aval", None)
        if aval is not None and hasattr(aval, "dtype"):
            yield aval


def audit_jaxpr(closed_jaxpr, *, expect_waves: Optional[int] = None) -> List[Finding]:
    """Hygiene audit over any traced computation.

    * no f64 avals anywhere (silent promotion breaks the f32 contract);
    * no host-callback primitives;
    * no weak-typed loop carries in ``while``/``scan`` (recompile bait:
      a python scalar in the carry retraces on first concrete call);
    * with ``expect_waves``: the overlap ordering property — every
      ``all_to_all`` precedes the first contraction, and there are
      exactly K of them.
    """
    findings: List[Finding] = []
    jaxpr = _closed_to_jaxpr(closed_jaxpr)
    a2a_before = 0
    saw_dot = False
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        for aval in _avals(eqn):
            if str(aval.dtype) == "float64":
                findings.append(
                    Finding(
                        "jaxpr/f64",
                        f"f64 aval on primitive {name!r} — silent double "
                        "promotion in the step graph",
                    )
                )
                break
        if name in _CALLBACK_PRIMITIVES:
            findings.append(
                Finding(
                    "jaxpr/callback",
                    f"host callback {name!r} inside the traced step",
                )
            )
        if name == "while":
            carries = list(eqn.params["body_jaxpr"].in_avals)
            for i, aval in enumerate(carries):
                if getattr(aval, "weak_type", False):
                    findings.append(
                        Finding(
                            "jaxpr/loop-carry",
                            f"while carry {i} is weak-typed "
                            f"({aval}) — python-scalar recompile bait",
                        )
                    )
        elif name == "scan":
            num_carry = eqn.params.get("num_carry", 0)
            carries = list(eqn.params["jaxpr"].in_avals)[
                eqn.params.get("num_consts", 0) :
            ][:num_carry]
            for i, aval in enumerate(carries):
                if getattr(aval, "weak_type", False):
                    findings.append(
                        Finding(
                            "jaxpr/loop-carry",
                            f"scan carry {i} is weak-typed "
                            f"({aval}) — python-scalar recompile bait",
                        )
                    )
        if name == "all_to_all" and not saw_dot:
            a2a_before += 1
        elif name == "all_to_all" and saw_dot:
            findings.append(
                Finding(
                    "jaxpr/collective-order",
                    "all_to_all issued AFTER a contraction — the wave "
                    "transfer can no longer hide behind earlier FLOPs",
                )
            )
        elif name == _CONTRACTION_PRIMITIVE:
            saw_dot = True
    if expect_waves is not None and a2a_before != expect_waves:
        findings.append(
            Finding(
                "jaxpr/collective-order",
                f"{a2a_before} all_to_all(s) before the first contraction, "
                f"expected all {expect_waves} waves issued up front",
            )
        )
    return findings


# ---------------------------------------------------------------------------
# tracing


def _abstract_mesh(num_units: int):
    from jax.sharding import AbstractMesh

    return AbstractMesh((num_units,), ("unit",))


def trace_pmvc_step(
    plan: DevicePlan,
    exchange_plan=None,
    *,
    batch: Optional[int] = None,
):
    """Trace :func:`make_pmvc_step` for ``plan`` under an AbstractMesh
    and return the ClosedJaxpr — no devices, no compilation, no FLOPs.

    ``exchange_plan`` follows the executor convention (``None`` ==
    replicated, :class:`SelectivePlan`, :class:`OverlapPlan`). The x
    operand is a single vector by default — pass ``batch`` to audit the
    SpMM path instead (the same contraction with a batch axis).
    """
    import jax

    from repro.pmvc.dist import make_pmvc_step

    mesh = _abstract_mesh(plan.num_units)
    bn = plan.bn
    tail: Tuple[int, ...] = () if batch is None else (batch,)
    step = make_pmvc_step(plan, mesh, selective=exchange_plan)
    if exchange_plan is None:
        x = np.zeros((plan.num_col_blocks, bn) + tail, np.float32)
        args = (plan.tiles, plan.tile_row, plan.tile_col, x)
    elif isinstance(exchange_plan, OverlapPlan):
        op = exchange_plan
        sel = op.selective
        x = np.zeros((sel.num_units, sel.blocks_per_unit, bn) + tail, np.float32)
        args = (
            op.local_tiles,
            op.local_row,
            op.local_slot,
            op.halo_tiles,
            op.halo_row,
            op.halo_slot,
            x,
            op.wave_send_idx,
            op.wave_recv_src,
            op.wave_recv_lane,
        )
    elif isinstance(exchange_plan, SelectivePlan):
        sel = exchange_plan
        x = np.zeros((sel.num_units, sel.blocks_per_unit, bn) + tail, np.float32)
        args = (
            plan.tiles,
            plan.tile_row,
            sel.tile_col_local,
            x,
            sel.send_idx,
            sel.recv_src,
            sel.recv_lane,
        )
    else:
        raise TypeError(f"unknown exchange plan type {type(exchange_plan)!r}")
    return jax.make_jaxpr(step)(*args)


# ---------------------------------------------------------------------------
# reports


@dataclasses.dataclass(frozen=True)
class AuditReport:
    """One stepper audit: the extracted signature, the pinned golden it
    was compared against, and any hygiene findings."""

    exchange: str
    waves: int
    signature: str
    golden: str
    findings: Tuple[Finding, ...]

    @property
    def ok(self) -> bool:
        return not self.findings and self.signature == self.golden

    def __str__(self) -> str:
        status = "OK" if self.ok else "FAIL"
        lines = [
            f"jaxpr audit [{self.exchange}, K={self.waves}]: {status} — "
            f"schedule {self.signature!r}"
            + ("" if self.signature == self.golden else f" != golden {self.golden!r}")
        ]
        lines += [f"  - {f}" for f in self.findings]
        return "\n".join(lines)


def audit_plan(plan: DevicePlan, exchange_plan=None) -> AuditReport:
    """Trace ``plan``'s stepper, extract its schedule, compare against
    the golden pin, and run the hygiene audits."""
    if isinstance(exchange_plan, OverlapPlan):
        exchange, waves = "overlap", exchange_plan.waves
    elif isinstance(exchange_plan, SelectivePlan):
        exchange, waves = "selective", 1
    else:
        exchange, waves = "replicated", 1
    closed = trace_pmvc_step(plan, exchange_plan)
    findings = audit_jaxpr(
        closed, expect_waves=waves if exchange == "overlap" else None
    )
    sig = schedule_signature(closed)
    golden = golden_signature(exchange, waves)
    if sig != golden:
        findings = findings + [
            Finding(
                "jaxpr/schedule",
                f"collective schedule {sig!r} diverges from golden {golden!r}",
            )
        ]
    return AuditReport(
        exchange=exchange,
        waves=waves,
        signature=sig,
        golden=golden,
        findings=tuple(findings),
    )


def audit_session(sess) -> AuditReport:
    """Audit a :class:`SparseSession`'s stepper (its device plan +
    exchange plan as the shard_map executor would run them)."""
    return audit_plan(sess.device_plan, sess.selective)
