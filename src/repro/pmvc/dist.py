"""Distributed PMVC executor — the paper's runtime, on a JAX mesh.

Phases mirror ch.4's measurement decomposition:

* **Scatter** (fan-out of A_k, X_k): A is placed once at setup (the
  iterative-solver steady state); x either replicated (``échange
  total``, all-gather) or moved by the **selective exchange** — a static
  all_to_all schedule carrying only the C_Xk blocks each unit needs
  (:class:`repro.pmvc.plan_device.SelectivePlan`).
* **Compute**: per-unit Block-ELL SpMM — one jnp contraction on every
  backend (:func:`_unit_spmm`).
* **Gather + construction of Y**: partial y vectors summed across units
  (column fragments overlap rows — the paper's fan-in with accumulation)
  via ``psum``; row-clean plans could concat instead (cheaper — the
  difference is visible in the collective roofline term).

Everything is **batch-first**: x may be one vector ``[N]`` or a stack
``[B, N]``; block-padded x carries the batch as a trailing axis
(``[NCB, bn, B]``) so each tile contribution is a ``(bm × bn) @
(bn × B)`` matmul and one exchange moves all B right-hand sides — the
paper's scatter/gather volumes amortize over the batch
(:func:`phase_costs` with ``batch=``).

A third regime **overlaps** the two phases (DESIGN.md §9): the plan-time
local/halo tile split (:class:`repro.pmvc.plan_device.OverlapPlan`) lets
the runtime issue the halo all_to_all first, contract the local tiles —
whose x blocks the unit already owns — while the collective is in
flight, then stream-accumulate the halo contribution from the delivered
workspace: ``T_iter ≈ max(T_comm, T_local) + T_halo`` instead of
``T_comm + T_comp`` (the FMM-over-runtime pipelining trick, Agullo et
al. 2012). :func:`phase_costs` carries the matching analytic model.

Entry points: ``pmvc_simulate`` / ``pmvc_simulate_selective`` /
``pmvc_simulate_overlap`` (vmap over a stacked unit axis — CPU tests and
the paper-reproduction benchmarks), ``make_simulate_fn`` (the same math
as hoisted plan arrays and a pure body over them; what the ``simulate``
executor and the device-resident solver loops build on), and ``make_pmvc_step`` (shard_map over a device mesh —
the production path and dry-run).
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.pmvc.plan_device import (
    DevicePlan,
    ExchangePlan,
    OverlapPlan,
    SelectivePlan,
)
from repro.sparse.bell import pad_x_blocks
from repro.tracing import HOIST, span

__all__ = [
    "pmvc_simulate",
    "pmvc_simulate_selective",
    "pmvc_simulate_overlap",
    "make_simulate_fn",
    "make_pmvc_step",
    "make_unit_mesh",
    "hoist_tiles",
    "hoist_plan",
    "phase_costs",
    "unblock_y",
    "pad_x",
    "scatter_x_owned",
    "MESSAGE_OVERHEAD_BYTES",
    "MODEL_LINK_BYTES_PER_S",
    "MODEL_UNIT_FLOPS_PER_S",
]

# α term of the exchange cost model: fixed per-message overhead (header +
# rendezvous), in byte-equivalents at the link's β. Amortized over the
# batch — the reason bytes-per-RHS shrinks as B grows (ch.4's
# startup-vs-payload decomposition).
MESSAGE_OVERHEAD_BYTES = 512

# β and peak terms of the analytic time model (DESIGN.md §9): a 10 GbE
# commodity link (the paper's cluster class) and one unit's sustained
# SpMM rate. Only *ratios* of the derived times are meaningful — the
# constants pin t_* terms so the overlap_efficiency projection and its
# golden tests are deterministic.
MODEL_LINK_BYTES_PER_S = 1.25e9
MODEL_UNIT_FLOPS_PER_S = 5.0e10


# Host ufuncs with a device twin: applying the twin *after* the host→
# device transfer keeps the value-view fast path copy-free on the host —
# np.abs on a jax array would bounce through host memory instead.
_DEVICE_UFUNC = {np.absolute: jnp.abs, abs: jnp.abs, np.sign: jnp.sign,
                 np.negative: jnp.negative, np.square: jnp.square}


def hoist_tiles(tiles: np.ndarray, transform=None, put=jnp.asarray) -> jax.Array:
    """Move a tile payload to device with ``put``, applying an optional
    elementwise value transform (a :meth:`SparseSession.with_value_map`
    view): known ufuncs run on device after the transfer, anything else
    is applied to the host array on the way in (one transient host copy,
    never a persistent one)."""
    if transform is None:
        return put(tiles)
    dev = _DEVICE_UFUNC.get(transform)
    if dev is not None:
        return dev(put(tiles))
    return put(np.asarray(transform(np.asarray(tiles)), np.float32))


def hoist_plan(arrays: tuple, transform=None, tiles=(0,), sharding=None) -> tuple:
    """Move a plan's arrays (each with a leading unit axis) to the device,
    in one ``sparse.hoist`` span: those at the positions ``tiles`` through
    :func:`hoist_tiles`, the others as they are.

    Without ``sharding`` every array lands whole on the default device.
    With a ``NamedSharding`` over the unit mesh (``P("unit")``), each
    unit's slice goes from the host straight to its own device and no
    device ever holds another unit's share. The span counts ``bytes``
    (all arrays), ``units`` (their leading axis) and ``bytes_per_device``
    (what the fullest device receives)."""
    total = sum(int(a.nbytes) for a in arrays)
    if sharding is None:
        put, per_device = jnp.asarray, total
    else:
        put = functools.partial(jax.device_put, device=sharding)
        per_device = sum(
            int(np.prod(sharding.shard_shape(a.shape))) * a.itemsize for a in arrays
        )
    with span(HOIST, bytes=total, units=int(arrays[0].shape[0]), bytes_per_device=per_device):
        return tuple(
            hoist_tiles(a, transform, put) if i in tiles else put(a)
            for i, a in enumerate(arrays)
        )


def pad_x(x: np.ndarray, ncb: int, bn: int) -> np.ndarray:
    """Block-pad x; alias of :func:`repro.sparse.bell.pad_x_blocks`."""
    return pad_x_blocks(x, ncb, bn)


def unblock_y(y, n: int) -> np.ndarray:
    """Undo the block layout: ``[NRB, bm] -> [n]`` or ``[NRB, bm, B] ->
    [B, n]`` (row-major batch, matching the ``[B, N]`` input layout)."""
    if y.ndim == 2:
        return np.asarray(y).reshape(-1)[:n]
    b = y.shape[-1]
    return np.asarray(y).reshape(-1, b).T[:, :n]


def scatter_x_owned(sp: SelectivePlan, xb: np.ndarray) -> np.ndarray:
    """Place padded x blocks into the block-col-sharded ``[U, per, bn]``
    (or ``[U, per, bn, B]``) layout the selective executors start from
    (unit u owns ``owned[u]``)."""
    x_owned = np.zeros(
        (sp.num_units, sp.blocks_per_unit) + xb.shape[1:], np.float32
    )
    valid = sp.owned >= 0
    x_owned[valid] = xb[sp.owned[valid]]
    return x_owned


def _unit_spmm(
    tiles: jax.Array, tile_row: jax.Array, xb_of_tile: jax.Array, nrb: int
) -> jax.Array:
    """One unit's padded-tile SpMM into a full-length partial y.

    ``xb_of_tile`` is ``[T, bn]`` (single vector, run as B = 1) or
    ``[T, bn, B]``. One lowering on every backend and executor: an f32
    broadcast multiply summed over the *minor* (bn) axis, with the batch
    as a major axis. Every output element is then reduced by the same
    code whatever B is, so column j of a B-wide SpMM is bitwise the B = 1
    product — the per-column stability that served ≡ direct rests on
    (a batch-minor reduction or an MXU einsum breaks it on TPU, and the
    default-precision einsum also misses the f32 accuracy bar)."""
    squeeze = xb_of_tile.ndim == 2
    xt = xb_of_tile[:, None] if squeeze else jnp.swapaxes(xb_of_tile, 1, 2)
    contribs = jnp.sum(tiles[:, None] * xt[:, :, None, :], axis=-1)  # [T, B, bm]
    contribs = contribs[:, 0] if squeeze else jnp.swapaxes(contribs, 1, 2)
    y = jnp.zeros((nrb,) + contribs.shape[1:], jnp.float32)
    return y.at[tile_row].add(contribs)


def _emulated_exchange(owned, send_idx, xb):
    """Device-side ownership scatter + emulated static all_to_all:
    ``recv[u, v, l] = send[v, u, l]`` — the exact routing of the
    shard_map executors (−1 slots masked to zero blocks), testable
    without a multi-device mesh. ``owned`` is ``[U, per]``, ``send_idx``
    ``[U, U, L]``, ``xb`` the padded global x ``[NCB, bn(, B)]``.
    Returns ``(x_owned, recv)``: the block-col-sharded x ``[U, per,
    bn(, B)]`` and the per-unit receive workspace ``[U(dst), U(src), L,
    bn(, B)]``."""
    omask = (owned >= 0).reshape(owned.shape + (1,) * (xb.ndim - 1))
    x_owned = jnp.where(omask, xb[jnp.maximum(owned, 0)], 0.0)
    smask = (send_idx >= 0).reshape(send_idx.shape + (1,) * (xb.ndim - 1))
    safe = jnp.maximum(send_idx, 0)
    units = jnp.arange(owned.shape[0])
    send = jnp.where(
        smask, x_owned[units[:, None, None], safe], 0.0
    )  # [U(src), U(dst), L, bn(, B)]
    return x_owned, jnp.swapaxes(send, 0, 1)


def _emulated_wave_exchange(owned, wave_send_idx, xb):
    """Wave variant of :func:`_emulated_exchange`: ``wave_send_idx`` is
    ``[U(src), K, U(dst), L]`` (one all_to_all schedule per halo wave).
    Returns ``(x_owned, recv)`` with ``recv`` ``[U(dst), K, U(src), L,
    bn(, B)]`` — the same swap on the src/dst axes, wave axis carried
    through."""
    omask = (owned >= 0).reshape(owned.shape + (1,) * (xb.ndim - 1))
    x_owned = jnp.where(omask, xb[jnp.maximum(owned, 0)], 0.0)
    smask = (wave_send_idx >= 0).reshape(wave_send_idx.shape + (1,) * (xb.ndim - 1))
    safe = jnp.maximum(wave_send_idx, 0)
    units = jnp.arange(owned.shape[0])
    send = jnp.where(
        smask, x_owned[units[:, None, None, None], safe], 0.0
    )  # [U(src), K, U(dst), L, bn(, B)]
    return x_owned, jnp.swapaxes(send, 0, 2)


def _send_all_to_all(x_local, send_idx):
    """shard_map-side counterpart of :func:`_emulated_exchange`: mask the
    unit's outgoing blocks (``send_idx`` ``[U, L]`` slots into the local
    shard, −1 = unused lane) and run the collective. Returns ``recv``
    ``[U, L, bn(, B)]`` — ``recv[v]`` = blocks v sent to me."""
    safe = jnp.maximum(send_idx, 0)
    mask = (send_idx >= 0).reshape(send_idx.shape + (1,) * (x_local.ndim - 1))
    my_send = jnp.where(mask, x_local[safe], 0.0)  # [U, L, bn(, B)]
    return jax.lax.all_to_all(
        my_send, "unit", split_axis=0, concat_axis=0, tiled=False
    )


def make_simulate_fn(
    plan: DevicePlan,
    selective: ExchangePlan = None,
    *,
    transform=None,
) -> Tuple[tuple, Callable[[tuple, jax.Array], jax.Array]]:
    """Hoist the plan to the device and return ``(ops, body)``:
    ``body(ops, xb) -> y_blocks`` is the vmap-over-units PMVC on padded
    x blocks (``[NCB, bn]`` or ``[NCB, bn, B]`` → ``[NRB, bm(, B)]``).

    ``selective`` picks the exchange regime: ``None`` (replicated),
    a :class:`SelectivePlan` (blocking selective all_to_all) or an
    :class:`OverlapPlan` (pipelined local/halo — local tiles contract
    from the owned x shard, halo tiles from the delivered workspace).

    Each call hoists the plan arrays again (one ``sparse.hoist``):
    :meth:`SparseSession._hoisted` makes the pair once per session and
    both the ``simulate`` executor (``jax.jit(body)``, the ``jit_body``
    program) and the ``device_loop`` solvers' :meth:`SparseSession.device_spmm`
    run on it. The plan arrays enter a program as arguments, never as
    closed-over constants: a constant is compiled into the program,
    which at serving sizes (GBs of tiles) costs minutes of compilation
    and tens of GiB of host memory. ``body`` is pure JAX, so it can be
    jitted with ``ops`` as an argument, and traced inside
    ``lax.while_loop`` solver bodies, which pass the closed-over ``ops``
    to the loop program as operands. ``transform`` is the optional
    value-view map applied to tile payloads at hoist time (see
    :func:`hoist_tiles`).
    """
    nrb = plan.num_row_blocks
    if isinstance(selective, OverlapPlan):
        ops, body = _simulate_overlap(plan, selective, transform)
    elif selective is None:
        ops = hoist_plan((plan.tiles, plan.tile_row, plan.tile_col), transform)

        def body(ops, xb: jax.Array) -> jax.Array:
            def one_unit(t, r, c):
                return _unit_spmm(t, r, xb[c], nrb)

            return jax.vmap(one_unit)(*ops).sum(axis=0)

    else:
        sp = selective
        ops = hoist_plan(
            (
                plan.tiles,
                plan.tile_row,
                sp.tile_col_local,
                sp.recv_src,
                sp.recv_lane,
                sp.owned,  # [U, per]
                sp.send_idx,  # [U, U, L]
            ),
            transform,
        )

        def body(ops, xb: jax.Array) -> jax.Array:
            tiles, tile_row, tile_col_local, recv_src, recv_lane, owned, send_idx = ops
            _, recv = _emulated_exchange(owned, send_idx, xb)

            def one_unit(t, r, tcl, recv_u, src, lane):
                ws = recv_u[src, lane]  # [W, bn(, B)] compact workspace
                return _unit_spmm(t, r, ws[tcl], nrb)

            partials = jax.vmap(one_unit)(
                tiles, tile_row, tile_col_local, recv, recv_src, recv_lane
            )
            return partials.sum(axis=0)

    return ops, body


def _simulate_overlap(plan: DevicePlan, op: OverlapPlan, transform):
    """Overlapped vmap path as ``(ops, body)``: local tiles contract
    straight from the owned x shard (no dependency on the emulated
    all_to_all), halo tiles — one wave at a time — from the delivered
    per-wave workspaces: the same dependency structure the shard_map
    step exposes to XLA's async collectives. The wave count K is static
    (baked into the plan array shapes), so the Python loop over waves
    unrolls at trace time."""
    nrb = plan.num_row_blocks
    nw = op.waves
    ops = hoist_plan(
        (
            op.local_tiles,
            op.local_row,
            op.local_slot,
            op.halo_tiles,  # [U, K, TH, bm, bn]
            op.halo_row,
            op.halo_slot,
            op.wave_recv_src,  # [U, K, W]
            op.wave_recv_lane,
            op.selective.owned,  # [U, per]
            op.wave_send_idx,  # [U, K, U, L]
        ),
        transform,
        tiles=(0, 3),
    )

    def body(ops, xb: jax.Array) -> jax.Array:
        *unit_ops, wave_recv_src, wave_recv_lane, owned, wave_send_idx = ops
        x_owned, recv = _emulated_wave_exchange(owned, wave_send_idx, xb)

        def one_unit(lt, lr, ls, ht, hr, hs, x_own_u, recv_u, src, lane):
            # Local partial first — depends only on x_own_u.
            y = _unit_spmm(lt, lr, x_own_u[ls], nrb)
            for k in range(nw):
                ws = recv_u[k][src[k], lane[k]]  # [W, bn(, B)] workspace
                y = y + _unit_spmm(ht[k], hr[k], ws[hs[k]], nrb)
            return y

        partials = jax.vmap(one_unit)(
            *unit_ops, x_owned, recv, wave_recv_src, wave_recv_lane
        )
        return partials.sum(axis=0)

    return ops, body


def _simulate(plan: DevicePlan, selective: ExchangePlan, xb: jax.Array) -> jax.Array:
    ops, body = make_simulate_fn(plan, selective)
    return body(ops, xb)


def pmvc_simulate(plan: DevicePlan, x: np.ndarray) -> np.ndarray:
    """vmap-over-units execution on a single host; ``x`` is ``[N]`` or a
    batch ``[B, N]``; returns y with the same leading shape."""
    xb = jnp.asarray(pad_x(np.asarray(x, np.float32), plan.num_col_blocks, plan.bn))
    return unblock_y(_simulate(plan, None, xb), plan.shape[0])


def pmvc_simulate_selective(
    plan: DevicePlan, sp: SelectivePlan, x: np.ndarray
) -> np.ndarray:
    """vmap execution of the *selective* exchange on a single host; one
    emulated all_to_all carries all B right-hand sides."""
    xb = jnp.asarray(pad_x(np.asarray(x, np.float32), plan.num_col_blocks, plan.bn))
    return unblock_y(_simulate(plan, sp, xb), plan.shape[0])


def pmvc_simulate_overlap(
    plan: DevicePlan, op: OverlapPlan, x: np.ndarray
) -> np.ndarray:
    """vmap execution of the *overlapped* local/halo exchange on a single
    host — the oracle for the pipelined shard_map step (DESIGN.md §9)."""
    xb = jnp.asarray(pad_x(np.asarray(x, np.float32), plan.num_col_blocks, plan.bn))
    return unblock_y(_simulate(plan, op, xb), plan.shape[0])


def make_unit_mesh(num_units: int) -> Mesh:
    """Flat mesh over all local devices; the (node, core) structure of the
    plan is metadata — hierarchical collectives are an optimization knob."""
    found = jax.devices()
    if len(found) < num_units:
        raise ValueError(
            f"the shard_map executor runs one unit per device: need "
            f"{num_units} devices, found {len(found)} on platform "
            f"{found[0].platform!r}"
        )
    devs = np.asarray(found[:num_units])
    return Mesh(devs, ("unit",))


def make_pmvc_step(
    plan: DevicePlan,
    mesh: Mesh,
    *,
    selective: ExchangePlan = None,
    overlap: Optional[bool] = None,
) -> Callable[..., jax.Array]:
    """Build the jitted distributed PMVC step.

    Replicated mode: ``step(tiles, tile_row, tile_col, x_blocks)``.
    Selective mode: ``step(tiles, tile_row, tile_col_local, x_owned,
    send_idx, recv_src, recv_lane)`` with x block-col-sharded.
    Overlap mode (``overlap=True``, or ``selective`` already an
    :class:`OverlapPlan`): ``step(local_tiles, local_row, local_slot,
    halo_tiles, halo_row, halo_slot, x_owned, wave_send_idx,
    wave_recv_src, wave_recv_lane)`` — the step *issues every wave's
    all_to_all first* (the wave count K is static, read off the traced
    array shapes), contracts the local tiles (which only read the unit's
    own x shard), then accumulates each wave's halo tiles from its
    delivered workspace, so XLA's async collectives can hide wave k+1's
    transfer behind wave k's contraction (DESIGN.md §9/§13). The step
    closes over shapes only — the caller supplies the
    :class:`OverlapPlan`'s arrays at call time (build one with
    :func:`repro.pmvc.plan_device.build_overlap_plan`). Passing
    ``overlap=False`` with an :class:`OverlapPlan` runs its embedded
    selective schedule blocking.

    x blocks may carry a trailing batch axis (``[NCB, bn, B]`` /
    ``[U, per, bn, B]``); one all_to_all then moves all B vectors.
    Returns replicated y blocks ``[NRB, bm(, B)]``. The jit cache keys
    on shape, so one step serves every batch size.
    """
    nrb = plan.num_row_blocks
    if overlap is None:
        overlap = isinstance(selective, OverlapPlan)
    if not overlap and isinstance(selective, OverlapPlan):
        selective = selective.selective
    if overlap:
        # The step closes over shapes only — the caller supplies the
        # OverlapPlan arrays (see repro.api.executors.shard_map_executor).

        def step_overlap(
            local_tiles,
            local_row,
            local_slot,
            halo_tiles,
            halo_row,
            halo_slot,
            x_owned,
            wave_send_idx,
            wave_recv_src,
            wave_recv_lane,
        ):
            # x_owned: [1, per, bn(, B)] local shard; *_tiles/*_row/*_slot
            # and the schedule arrays are [1, ...] local unit slices; the
            # wave axis (K, static) sits at position 1 after the slice.
            x_local = x_owned[0]
            nw = halo_tiles.shape[1]
            # Every wave's collective issued before any FLOP: nothing
            # below depends on recvs[k] until wave k's halo contraction,
            # so the local partial hides wave 0's transfer and each
            # wave's contraction hides the next wave's transfer.
            recvs = [
                _send_all_to_all(x_local, wave_send_idx[0, k]) for k in range(nw)
            ]
            y = _unit_spmm(
                local_tiles[0], local_row[0], x_local[local_slot[0]], nrb
            )
            for k in range(nw):
                ws = recvs[k][wave_recv_src[0, k], wave_recv_lane[0, k]]
                y = y + _unit_spmm(
                    halo_tiles[0, k], halo_row[0, k], ws[halo_slot[0, k]], nrb
                )
            return jax.lax.psum(y, "unit")

        return jax.jit(
            jax.shard_map(
                step_overlap,
                mesh=mesh,
                in_specs=(P("unit"),) * 10,
                out_specs=P(),
            )
        )

    if selective is None:

        def step(tiles, tile_row, tile_col, x_blocks):
            # tiles/tile_*: [1, ...] local unit slice; x replicated.
            y_part = _unit_spmm(tiles[0], tile_row[0], x_blocks[tile_col[0]], nrb)
            return jax.lax.psum(y_part, "unit")

        return jax.jit(
            jax.shard_map(
                step,
                mesh=mesh,
                in_specs=(P("unit"), P("unit"), P("unit"), P()),
                out_specs=P(),
            )
        )

    def step_selective(tiles, tile_row, tile_col_local, x_owned, send_idx, recv_src, recv_lane):
        # x_owned: [1, per, bn(, B)] local; send_idx: [1, U, L]; recv_*: [1, W].
        recv = _send_all_to_all(x_owned[0], send_idx[0])
        ws = recv[recv_src[0], recv_lane[0]]  # [W, bn(, B)] compact workspace
        y_part = _unit_spmm(tiles[0], tile_row[0], ws[tile_col_local[0]], nrb)
        return jax.lax.psum(y_part, "unit")

    return jax.jit(
        jax.shard_map(
            step_selective,
            mesh=mesh,
            in_specs=(
                P("unit"),
                P("unit"),
                P("unit"),
                P("unit"),
                P("unit"),
                P("unit"),
                P("unit"),
            ),
            out_specs=P(),
        )
    )


def _message_counts(plan: DevicePlan, selective: Optional[SelectivePlan]) -> int:
    """Point-to-point messages per exchange (the α-cost multiplier)."""
    u = plan.num_units
    if selective is None:
        return u * (u - 1)  # all-gather: every unit hears every other
    off_diag = (selective.send_idx >= 0).any(axis=-1)
    np.fill_diagonal(off_diag, False)
    return int(off_diag.sum())


def phase_costs(
    plan: DevicePlan,
    selective: ExchangePlan = None,
    bytes_per: int = 4,
    batch: int = 1,
    *,
    link_bytes_per_s: Optional[float] = None,
    unit_flops_per_s: Optional[float] = None,
) -> Dict[str, float]:
    """Analytic per-phase volumes and model times for the benchmark
    tables (paper ch.4; overlap model DESIGN.md §9/§13).

    ``batch`` is the SpMM width B: payload volumes scale with B while
    the per-message overhead (``MESSAGE_OVERHEAD_BYTES`` × messages) is
    paid once per exchange — so the ``*_per_rhs`` keys shrink as B
    grows, the amortization the batch-first refactor buys.

    Time terms (seconds under the α-β-peak constants; only ratios are
    meaningful): ``t_scatter`` / ``t_gather`` are the wire times,
    ``t_compute`` the padded per-unit contraction.
    ``link_bytes_per_s`` / ``unit_flops_per_s`` override the model's β
    and peak terms — :mod:`repro.benchmarks.bench_pmvc` calibrates them
    against measured rows so the model tracks the machine it runs on;
    ``None`` keeps the pinned ``MODEL_*`` defaults the golden tests
    assume.

    When ``selective`` is an :class:`OverlapPlan` the dict additionally
    carries the pipelined model — ``t_local`` / ``t_halo`` (the two
    contraction phases) and ``t_iter_overlap`` vs ``t_iter_blocking =
    t_scatter + t_compute + t_gather``. For a single halo wave
    ``t_iter_overlap = max(t_scatter, t_local) + t_halo + t_gather``;
    for K waves the K-stage pipeline recursion applies — wave k's
    transfer (its own α-β time from ``wave_wire_blocks[k]`` /
    ``wave_messages[k]``) lands behind the preceding contractions:

    .. code-block:: text

        comm_end[k] = comm_end[k-1] + t_wave_scatter[k]
        comp_end[k] = max(comp_end[k-1], comm_end[k]) + t_wave_halo
        t_iter_overlap = comp_end[K-1] + t_gather

    ``overlap_efficiency`` is the fraction of the total exchange time
    hidden behind contractions (``min(t_scatter, t_local) / t_scatter``
    at K=1) and ``overlap_speedup`` the projected blocking/overlap
    ratio.
    """
    link = float(link_bytes_per_s) if link_bytes_per_s else MODEL_LINK_BYTES_PER_S
    peak = float(unit_flops_per_s) if unit_flops_per_s else MODEL_UNIT_FLOPS_PER_S
    op = selective if isinstance(selective, OverlapPlan) else None
    sp = op.selective if op is not None else selective
    u = plan.num_units
    b = max(int(batch), 1)
    blk = plan.bm * plan.bn * bytes_per
    scatter_naive = (u - 1) * plan.num_col_blocks * plan.bn * bytes_per * b
    scatter = (
        sp.wire_blocks * plan.bn * bytes_per * b if sp is not None else scatter_naive
    )
    msgs = _message_counts(plan, sp)
    overhead = msgs * MESSAGE_OVERHEAD_BYTES
    flops = 2.0 * u * plan.t * plan.bm * plan.bn * b  # padded (realized) FLOPs
    useful = 2.0 * float(plan.real_tiles.sum()) * plan.bm * plan.bn * b
    gather = u * plan.num_row_blocks * plan.bm * bytes_per * b  # psum volume
    gather_overhead = u * MESSAGE_OVERHEAD_BYTES
    t_scatter = float(scatter + overhead) / link
    t_gather = float(gather + gather_overhead) / link
    # Units run the padded tile count in lockstep → per-unit time.
    t_compute = 2.0 * plan.t * plan.bm * plan.bn * b / peak
    out = {
        "batch": float(b),
        "scatter_bytes": float(scatter),
        "scatter_bytes_naive": float(scatter_naive),
        "scatter_messages": float(msgs),
        "scatter_overhead_bytes": float(overhead),
        "scatter_bytes_per_rhs": float(scatter + overhead) / b,
        "compute_flops": flops,
        "useful_flops": useful,
        "flop_efficiency": useful / flops if flops else 1.0,
        "gather_bytes": float(gather),
        "gather_bytes_per_rhs": float(gather + gather_overhead) / b,
        "tile_bytes_resident": float(u * plan.t * blk),
        "t_scatter": t_scatter,
        "t_gather": t_gather,
        "t_compute": t_compute,
        "t_iter_blocking": t_scatter + t_compute + t_gather,
    }
    if op is None:
        return out
    # Pipelined model: the halo payload is exactly the wire volume (the
    # self-routed owned blocks never leave the unit); local x bytes are
    # the owned-and-referenced blocks read straight from the shard.
    diag = np.arange(op.num_units)
    local_blocks = int((op.selective.send_idx[diag, diag] >= 0).sum())
    nw = op.waves
    t_local = 2.0 * op.t_local * plan.bm * plan.bn * b / peak
    t_halo = 2.0 * op.t_halo * plan.bm * plan.bn * b / peak
    if nw == 1:
        t_iter_overlap = max(t_scatter, t_local) + t_halo + t_gather
        hidden = min(t_scatter, t_local)
        efficiency = hidden / t_scatter if t_scatter > 0 else 1.0
    else:
        # K-stage pipeline: wave k's α-β transfer queues behind wave
        # k-1's on the link; its contraction starts once both the wave
        # landed and the previous contraction finished. Each wave pads
        # to the common t_halo tile count (lockstep units).
        wave_bytes = op.wave_wire_blocks * plan.bn * bytes_per * b
        wave_overhead = op.wave_messages * MESSAGE_OVERHEAD_BYTES
        t_wave_scatter = (wave_bytes + wave_overhead).astype(np.float64) / link
        comm_end = np.cumsum(t_wave_scatter)
        comp_end = t_local
        for k in range(nw):
            comp_end = max(comp_end, float(comm_end[k])) + t_halo
        t_iter_overlap = comp_end + t_gather
        total_comm = float(t_wave_scatter.sum())
        exposed = comp_end - (t_local + nw * t_halo)
        efficiency = (
            (total_comm - exposed) / total_comm if total_comm > 0 else 1.0
        )
    out.update(
        {
            "halo_bytes": float(scatter),
            "local_x_bytes": float(local_blocks * plan.bn * bytes_per * b),
            "local_tile_fraction": op.local_fraction,
            "waves": float(nw),
            "t_local": t_local,
            "t_halo": t_halo,
            "t_iter_overlap": t_iter_overlap,
            "overlap_efficiency": efficiency,
        }
    )
    out["overlap_speedup"] = out["t_iter_blocking"] / out["t_iter_overlap"]
    return out
