"""Mixture-of-Experts layer — the paper's technique as expert parallelism.

Token→expert assignment is a sparse matrix (tokens = rows, experts =
columns); distributing it is the PMVC column-distribution problem
(DESIGN.md §3). Concretely:

* **Placement**: ``repro.core.expert_placement`` runs NEZGT over expert
  load estimates (balance) and a co-activation hypergraph (communication)
  to produce the expert→rank permutation, applied statically by permuting
  the stacked expert weights.
* **Dispatch**: inside ``shard_map``, activations arrive replicated over
  the ``model`` axis (Megatron-style), each rank owns ``E/ranks`` experts
  and gathers only its own tokens into an ``[E_loc, C, D]`` buffer —
  capacity ``C`` realizes the paper's per-fragment load bound, and the
  token-drop fraction is the SPMD materialization of load imbalance.
* **Combine**: partial outputs are summed over the model axis (``psum``)
  — the paper's fan-in of partial Y vectors.

A pure-pjit fallback (``moe_ffn_dense``) computes the same math with
one-hot einsums for single-device smoke tests and as an oracle.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.config import ArchConfig
from repro.models.common import Params, dense_init

__all__ = ["init_moe", "moe_ffn", "moe_ffn_dense", "router_topk", "MeshCtx"]


class MeshCtx:
    """Mesh + axis-name context threaded through models.

    ``batch_axes`` shard the token batch; ``model_axis`` shards heads /
    ffn / experts. ``mesh=None`` disables shard_map paths (smoke tests).
    """

    def __init__(
        self,
        mesh: Optional[Mesh] = None,
        batch_axes: Tuple[str, ...] = ("data",),
        model_axis: str = "model",
    ):
        self.mesh = mesh
        self.batch_axes = tuple(batch_axes)
        self.model_axis = model_axis

    @property
    def model_ranks(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape[self.model_axis]


def init_moe(key: jax.Array, cfg: ArchConfig, dtype) -> Params:
    d, e, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, e), fan_in=d, dtype=jnp.float32),
        "w_gate": dense_init(ks[1], (e, d, f), fan_in=d, dtype=dtype),
        "w_up": dense_init(ks[2], (e, d, f), fan_in=d, dtype=dtype),
        "w_down": dense_init(ks[3], (e, f, d), fan_in=f, dtype=dtype),
    }


def router_topk(
    p: Params, x: jax.Array, cfg: ArchConfig
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (gates [B,S,k], expert ids [B,S,k], aux load-balance loss)."""
    logits = jnp.einsum(
        "bsd,de->bse", x.astype(jnp.float32), p["router"]
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gates, e_idx = jax.lax.top_k(probs, cfg.experts_per_token)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style aux loss: E * Σ_e (fraction_tokens_e * mean_prob_e) —
    # the differentiable surrogate of the paper's LB criterion.
    e = cfg.num_experts
    onehot = jax.nn.one_hot(e_idx[..., 0], e, dtype=jnp.float32)
    frac = onehot.mean(axis=(0, 1))
    mean_prob = probs.mean(axis=(0, 1))
    aux = e * jnp.sum(frac * mean_prob)
    return gates.astype(x.dtype), e_idx.astype(jnp.int32), aux


def _expert_mlp(x_e: jax.Array, wg, wu, wd) -> jax.Array:
    h = jnp.einsum("ecd,edf->ecf", x_e, wg)
    u = jnp.einsum("ecd,edf->ecf", x_e, wu)
    h = jax.nn.silu(h) * u
    return jnp.einsum("ecf,efd->ecd", h, wd)


def _dispatch_compute_combine(
    x: jax.Array,  # [B_loc, S, D] (replicated over model axis)
    gates: jax.Array,  # [B_loc, S, k]
    e_idx: jax.Array,  # [B_loc, S, k]
    wg,  # [E_loc, D, F]
    wu,
    wd,
    *,
    num_experts: int,
    capacity: int,
    model_axis: Optional[str],
    sort_dispatch: bool = False,
) -> jax.Array:
    b, s, k = e_idx.shape
    d = x.shape[-1]
    e_loc = wg.shape[0]
    rank = jax.lax.axis_index(model_axis) if model_axis else 0

    t = b * s
    xf = x.reshape(t, d)
    ef = e_idx.reshape(t * k)
    gf = gates.reshape(t * k)
    tok = jnp.arange(t * k, dtype=jnp.int32) // k

    if sort_dispatch:
        # §Perf `moe_sort`: rank-within-expert via stable sort +
        # searchsorted — O(Tk·logTk) work and O(Tk) memory instead of the
        # O(Tk·E) one-hot cumsum.
        order = jnp.argsort(ef, stable=True)
        sorted_e = ef[order]
        ranks_sorted = jnp.arange(t * k, dtype=jnp.int32) - jnp.searchsorted(
            sorted_e, sorted_e, side="left"
        ).astype(jnp.int32)
        pos_in_e = jnp.zeros(t * k, jnp.int32).at[order].set(ranks_sorted)
    else:
        # Rank-within-expert via one-hot cumsum (position in the queue).
        onehot = jax.nn.one_hot(ef, num_experts, dtype=jnp.int32)  # [T*k, E]
        pos_in_e = (jnp.cumsum(onehot, axis=0) - 1)[
            jnp.arange(t * k), ef
        ]  # [T*k]
    local_e = ef - rank * e_loc
    mine = (local_e >= 0) & (local_e < e_loc) & (pos_in_e < capacity)
    slot = jnp.where(mine, local_e * capacity + pos_in_e, e_loc * capacity)

    # Gather tokens into the expert buffer (extra padding row absorbs drops).
    buf = jnp.zeros((e_loc * capacity + 1, d), x.dtype)
    buf = buf.at[slot].set(xf[tok] * mine[:, None].astype(x.dtype))
    x_e = buf[:-1].reshape(e_loc, capacity, d)

    y_e = _expert_mlp(x_e, wg, wu, wd).reshape(e_loc * capacity, d)
    y_e = jnp.concatenate([y_e, jnp.zeros((1, d), y_e.dtype)], axis=0)

    yk = y_e[slot] * (gf * mine.astype(gf.dtype))[:, None]  # [T*k, D]
    y = yk.reshape(t, k, d).sum(axis=1).reshape(b, s, d)
    if model_axis:
        y = jax.lax.psum(y, model_axis)
    return y


def _capacity(t_loc: int, cfg: ArchConfig, decode: bool) -> int:
    """Per-expert slot budget. Decode is dropless (tiny buffers anyway);
    train/prefill uses the capacity factor — overflow drops realize the
    paper's load imbalance (DESIGN.md §3)."""
    k, e = cfg.experts_per_token, cfg.num_experts
    if decode:
        return max(1, t_loc * k)  # worst case: every token picks one expert
    return max(1, int(-(-t_loc * k // e) * cfg.moe_capacity_factor))


def _rank_within(ids: jax.Array, n: int, sort_based: bool) -> jax.Array:
    """Position of each element in its id's queue (stable)."""
    m = ids.shape[0]
    if sort_based:
        order = jnp.argsort(ids, stable=True)
        sorted_ids = ids[order]
        ranks_sorted = jnp.arange(m, dtype=jnp.int32) - jnp.searchsorted(
            sorted_ids, sorted_ids, side="left"
        ).astype(jnp.int32)
        return jnp.zeros(m, jnp.int32).at[order].set(ranks_sorted)
    onehot = jax.nn.one_hot(ids, n, dtype=jnp.int32)
    return (jnp.cumsum(onehot, axis=0) - 1)[jnp.arange(m), ids]


def _dispatch_a2a(
    x: jax.Array,  # [B_loc, S_loc, D] — tokens sharded over the model axis
    gates: jax.Array,  # [B_loc, S_loc, k]
    e_idx: jax.Array,  # [B_loc, S_loc, k]
    wg,  # [E_loc, D, F]
    wu,
    wd,
    *,
    num_experts: int,
    cap_route: int,  # per (src,dst)-rank route capacity
    cap_expert: int,  # per-expert buffer capacity on the owning rank
    model_axis: str,
    ranks: int,
    sort_dispatch: bool,
) -> jax.Array:
    """§Perf `moe_a2a`: DeepSeek-style expert parallelism.

    Tokens are sequence-sharded over the model axis; each token travels
    to the rank owning its expert via a static-capacity ``all_to_all``
    and its output returns the same way. Wire volume per rank is
    O(k · T_loc · D / ranks) instead of the replicated-activation psum's
    O(T_loc · D) — the paper's selective exchange (only send the x
    entries a fragment actually needs) applied to expert fragments.
    Route overflow drops tokens, so NEZGT expert placement (balance)
    directly bounds the drop rate.
    """
    b, s, k = e_idx.shape
    d = x.shape[-1]
    e_loc = wg.shape[0]
    me = jax.lax.axis_index(model_axis)
    t = b * s
    xf = x.reshape(t, d)
    ef = e_idx.reshape(t * k)
    gf = gates.reshape(t * k)
    tok = jnp.arange(t * k, dtype=jnp.int32) // k

    # --- route to destination ranks -----------------------------------
    dest = ef // e_loc  # owning rank per (token, slot)
    pos_r = _rank_within(dest, ranks, sort_dispatch)
    keep_r = pos_r < cap_route
    slot_r = jnp.where(keep_r, dest * cap_route + pos_r, ranks * cap_route)

    send_x = jnp.zeros((ranks * cap_route + 1, d), x.dtype)
    send_x = send_x.at[slot_r].set(xf[tok] * keep_r[:, None].astype(x.dtype))
    send_e = jnp.full((ranks * cap_route + 1,), -1, jnp.int32)
    send_e = send_e.at[slot_r].set(jnp.where(keep_r, ef, -1))

    recv_x = jax.lax.all_to_all(
        send_x[:-1].reshape(ranks, cap_route, d), model_axis, 0, 0
    ).reshape(ranks * cap_route, d)
    recv_e = jax.lax.all_to_all(
        send_e[:-1].reshape(ranks, cap_route, 1), model_axis, 0, 0
    ).reshape(ranks * cap_route)

    # --- local dispatch into my experts --------------------------------
    local_e = recv_e - me * e_loc
    valid = recv_e >= 0
    safe_e = jnp.where(valid, jnp.clip(local_e, 0, e_loc - 1), 0)
    pos_e = _rank_within(jnp.where(valid, safe_e, e_loc), e_loc + 1, sort_dispatch)
    keep_e = valid & (pos_e < cap_expert)
    slot_e = jnp.where(keep_e, safe_e * cap_expert + pos_e, e_loc * cap_expert)

    buf = jnp.zeros((e_loc * cap_expert + 1, d), x.dtype)
    buf = buf.at[slot_e].set(recv_x * keep_e[:, None].astype(x.dtype))
    x_e = buf[:-1].reshape(e_loc, cap_expert, d)
    y_e = _expert_mlp(x_e, wg, wu, wd).reshape(e_loc * cap_expert, d)
    y_e = jnp.concatenate([y_e, jnp.zeros((1, d), y_e.dtype)], axis=0)

    # --- return trip ----------------------------------------------------
    y_back = y_e[slot_e] * keep_e[:, None].astype(y_e.dtype)
    ret = jax.lax.all_to_all(
        y_back.reshape(ranks, cap_route, d), model_axis, 0, 0
    ).reshape(ranks * cap_route, d)
    ret = jnp.concatenate([ret, jnp.zeros((1, d), ret.dtype)], axis=0)
    yk = ret[slot_r] * (gf * keep_r.astype(gf.dtype))[:, None]
    return yk.reshape(t, k, d).sum(axis=1).reshape(b, s, d).astype(x.dtype)


def moe_ffn(
    p: Params,
    x: jax.Array,  # [B, S, D]
    cfg: ArchConfig,
    ctx: MeshCtx,
) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE FFN. Returns (out, aux_loss)."""
    gates, e_idx, aux = router_topk(p, x, cfg)
    e, k = cfg.num_experts, cfg.experts_per_token
    ranks = ctx.model_ranks
    decode = x.shape[1] == 1

    if ctx.mesh is None or ranks == 1:
        b, s, _ = x.shape
        cap = _capacity(b * s, cfg, decode)
        y = _dispatch_compute_combine(
            x, gates, e_idx, p["w_gate"], p["w_up"], p["w_down"],
            num_experts=e, capacity=cap, model_axis=None,
            sort_dispatch=cfg.moe_sort_dispatch,
        )
        return y, aux

    # Local token count per batch shard (model axis sees replicas).
    batch_shards = 1
    for a in ctx.batch_axes:
        batch_shards *= ctx.mesh.shape.get(a, 1)
    t_loc = (x.shape[0] // batch_shards) * x.shape[1]
    cap = _capacity(t_loc, cfg, decode)
    bs = ctx.batch_axes

    if cfg.moe_a2a and not decode and x.shape[1] % ranks == 0:
        # Sequence-sharded all_to_all expert parallelism (§Perf moe_a2a).
        t_m = t_loc // ranks  # tokens per model rank
        cap_route = max(1, int(-(-t_m * k // ranks) * cfg.moe_capacity_factor))
        fn = functools.partial(
            _dispatch_a2a,
            num_experts=e,
            cap_route=cap_route,
            cap_expert=cap,
            model_axis=ctx.model_axis,
            ranks=ranks,
            sort_dispatch=cfg.moe_sort_dispatch,
        )
        y = jax.shard_map(
            fn,
            mesh=ctx.mesh,
            in_specs=(
                P(bs, ctx.model_axis, None),
                P(bs, ctx.model_axis, None),
                P(bs, ctx.model_axis, None),
                P(ctx.model_axis, None, None),
                P(ctx.model_axis, None, None),
                P(ctx.model_axis, None, None),
            ),
            out_specs=P(bs, ctx.model_axis, None),
            check_vma=False,
        )(x, gates, e_idx, p["w_gate"], p["w_up"], p["w_down"])
        return y, aux

    fn = functools.partial(
        _dispatch_compute_combine,
        num_experts=e,
        capacity=cap,
        model_axis=ctx.model_axis,
        sort_dispatch=cfg.moe_sort_dispatch,
    )
    y = jax.shard_map(
        fn,
        mesh=ctx.mesh,
        in_specs=(
            P(bs, None, None),
            P(bs, None, None),
            P(bs, None, None),
            P(ctx.model_axis, None, None),
            P(ctx.model_axis, None, None),
            P(ctx.model_axis, None, None),
        ),
        out_specs=P(bs, None, None),
        check_vma=False,
    )(x, gates, e_idx, p["w_gate"], p["w_up"], p["w_down"])
    return y, aux


def moe_ffn_dense(
    p: Params, x: jax.Array, cfg: ArchConfig
) -> Tuple[jax.Array, jax.Array]:
    """Oracle: every expert applied to every token, masked by gates."""
    gates, e_idx, aux = router_topk(p, x, cfg)
    dense_gates = jnp.zeros(
        x.shape[:-1] + (cfg.num_experts,), jnp.float32
    )
    for j in range(cfg.experts_per_token):
        dense_gates = dense_gates + jax.nn.one_hot(
            e_idx[..., j], cfg.num_experts, dtype=jnp.float32
        ) * gates[..., j : j + 1].astype(jnp.float32)
    h = jnp.einsum("bsd,edf->bsef", x, p["w_gate"])
    u = jnp.einsum("bsd,edf->bsef", x, p["w_up"])
    y = jnp.einsum("bsef,efd->bsed", jax.nn.silu(h) * u, p["w_down"])
    out = jnp.einsum("bsed,bse->bsd", y, dense_gates.astype(y.dtype))
    return out, aux
