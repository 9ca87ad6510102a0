"""Mixture-of-Experts layer: top-k router + capacity-based einsum dispatch.

Dispatch is GShard-style one-hot einsum with a capacity factor — fully
GSPMD-partitionable: experts shard on the 'model' axis (expert parallelism),
tokens on ('pod','data').  The dispatch einsum's FLOPs are real overhead and
show up in the roofline's useful-FLOPs ratio; replacing it with sort-based
dispatch is one of the §Perf hillclimb levers.

Router jitter/aux-loss: load-balance auxiliary loss (Switch §2.2) is
returned so the trainer can add ``aux_weight * aux``.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .layers import init_linear

__all__ = ["held_moe_block", "init_moe", "moe_block"]

Params = Dict[str, jnp.ndarray]


def init_moe(
    key,
    d_model: int,
    d_ff: int,
    n_experts: int,
    shared_expert: bool = False,
    n_router: int = 0,
    shared_d_ff: int = 0,
) -> Params:
    """``n_experts`` expert weight sets; the router scores ``n_router``
    experts (default all of them: a layer that holds only its share of a
    layer's experts still routes over every expert)."""
    ks = jax.random.split(key, 5)
    p = {
        "router": init_linear(ks[0], d_model, n_router or n_experts),
        # stacked expert weights: [E, d_model, d_ff] / [E, d_ff, d_model]
        "wi": jax.random.truncated_normal(ks[1], -3, 3, (n_experts, d_model, d_ff), jnp.float32) * d_model ** -0.5,
        "wu": jax.random.truncated_normal(ks[2], -3, 3, (n_experts, d_model, d_ff), jnp.float32) * d_model ** -0.5,
        "wo": jax.random.truncated_normal(ks[3], -3, 3, (n_experts, d_ff, d_model), jnp.float32) * d_ff ** -0.5,
    }
    if shared_expert:
        kk = jax.random.split(ks[4], 3)
        sf = shared_d_ff or d_ff
        p["shared_wi"] = init_linear(kk[0], d_model, sf)
        p["shared_wu"] = init_linear(kk[1], d_model, sf)
        p["shared_wo"] = init_linear(kk[2], sf, d_model, scale=sf ** -0.5)
    return p


def _shared_expert(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    h = jax.nn.silu(x @ p["shared_wi"].astype(x.dtype)) * (x @ p["shared_wu"].astype(x.dtype))
    return h @ p["shared_wo"].astype(x.dtype)


def held_moe_block(
    p: Params,
    x: jnp.ndarray,  # [B, S, d_model]
    top_k: int,
    expert_offset: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """GraniteMoE routing over a held share of the experts.

    The router scores all ``E`` experts; each token takes the top-``k``
    logits and a softmax over those ``k`` (GraniteMoE's order: top-k, then
    softmax).  This layer holds experts ``expert_offset ..
    expert_offset + E_held - 1`` (``p['wi']`` has ``E_held`` rows) and adds
    only their part of the result, for every token routed to them: no
    capacity, so no token is dropped.  The held experts are computed densely
    (each one for every token, weighted by its gate, 0 where not routed).
    The shared expert, when present, is added in full.

    The router and its softmax run in float32, the router at full matmul
    precision (a TPU float32 dot otherwise rounds its inputs to bfloat16).

    Returns (output [B,S,D], aux_loss, counts [E_held] int32: tokens
    routed to each held expert).
    """
    B, S, D = x.shape
    E = p["router"].shape[1]
    n_held = p["wi"].shape[0]
    T = B * S
    xt = x.reshape(T, D)
    logits = jnp.dot(
        xt.astype(jnp.float32), p["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )  # [T, E]
    top, idx = jax.lax.top_k(logits, top_k)  # [T, k]
    gates = jax.nn.softmax(top, axis=-1)
    local = idx - expert_offset
    onehot = jax.nn.one_hot(local, n_held, dtype=jnp.int32)  # [T, k, E_held]; 0 off-share
    counts = onehot.sum(axis=(0, 1))
    comb = (onehot.astype(gates.dtype) * gates[..., None]).sum(axis=1)  # [T, E_held]

    h = jnp.einsum("td,edf->tef", xt, p["wi"].astype(x.dtype))
    u = jnp.einsum("td,edf->tef", xt, p["wu"].astype(x.dtype))
    eo = jnp.einsum("tef,efd->ted", jax.nn.silu(h) * u, p["wo"].astype(x.dtype))
    out = jnp.einsum("ted,te->td", eo, comb.astype(x.dtype))
    if "shared_wi" in p:
        out = out + _shared_expert(p, xt)

    # load-balance aux loss over the whole router: E · Σ_e f_e · P_e
    probs = jax.nn.softmax(logits, axis=-1)
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (T * top_k)
    aux = E * jnp.sum(probs.mean(axis=0) * ce)
    return out.reshape(B, S, D), aux, counts.astype(jnp.int32)


def moe_block(
    p: Params,
    x: jnp.ndarray,  # [B, S, d_model]
    top_k: int,
    capacity_factor: float = 1.25,
    dispatch: str = "einsum",  # 'einsum' (GShard) | 'dense' (compute-all)
    group_tokens: int = 4096,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (output [B,S,D], aux_loss scalar).

    GShard-style grouped dispatch: tokens are cut into groups of
    ``group_tokens`` and capacity is **per group** (C = gs·k·cf/E), so the
    dispatch tensor is [g, gs, E, C] — linear in total tokens.  The group
    axis inherits the batch sharding, so groups are device-local and the
    expert einsums become the EP all-to-all under GSPMD.
    """
    B, S, D = x.shape
    E = p["router"].shape[1]
    T = B * S
    xt = x.reshape(T, D)

    # ---- grouping ------------------------------------------------------- #
    gs = min(group_tokens, T)
    Gm = T // gs
    pad = Gm * gs < T
    if pad:
        Gm += 1
        xt = jnp.pad(xt, ((0, Gm * gs - T), (0, 0)))
    xg = xt.reshape(Gm, gs, D)

    logits = (xg @ p["router"].astype(x.dtype)).astype(jnp.float32)  # [g, gs, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, top_k)  # [g, gs, k]
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss: E · Σ_e f_e · P_e   (over real tokens only)
    me = probs.reshape(-1, E)[:T].mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32)
    ce = ce.at[idx.reshape(-1)[: T * top_k]].add(1.0) / (T * top_k)
    aux = E * jnp.sum(me * ce)

    if dispatch == "scatter":
        # Scatter/gather dispatch (beyond-paper §Perf lever): instead of the
        # GShard one-hot einsums — whose [gs, E, C] dispatch products dominate
        # HLO bytes — scatter token vectors straight into the expert buffers
        # and gather them back for the combine.  O(T·k·D) data movement.
        C = max(int(gs * top_k * capacity_factor / E), 1)
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)
        flat = onehot.reshape(Gm, gs * top_k, E)
        pos = jnp.cumsum(flat, axis=1) - flat
        pos = (pos * flat).sum(-1).reshape(Gm, gs, top_k)
        keep = pos < C
        cidx = jnp.where(keep, pos, C)  # C = overflow slot (dropped)
        gi = jnp.arange(Gm)[:, None, None]
        xe = jnp.zeros((Gm, E, C + 1, D), x.dtype)
        xe = xe.at[gi, idx, cidx].add(x.dtype.type(1) * xg[:, :, None, :])
        xe = xe[:, :, :C]
        h = jnp.einsum("gecd,edf->gecf", xe, p["wi"].astype(x.dtype))
        u = jnp.einsum("gecd,edf->gecf", xe, p["wu"].astype(x.dtype))
        eo = jnp.einsum("gecf,efd->gecd", jax.nn.silu(h) * u, p["wo"].astype(x.dtype))
        eo = jnp.pad(eo, ((0, 0), (0, 0), (0, 1), (0, 0)))  # overflow row = 0
        gathered = eo[gi, idx, cidx]  # [g, gs, k, D]
        gates = jnp.where(keep, gate_vals, 0.0).astype(x.dtype)
        out = (gathered * gates[..., None]).sum(axis=2)
    elif dispatch == "dense":
        # compute every expert for every token (upper-bound baseline)
        h = jnp.einsum("gsd,edf->gsef", xg, p["wi"].astype(x.dtype))
        u = jnp.einsum("gsd,edf->gsef", xg, p["wu"].astype(x.dtype))
        eo = jnp.einsum("gsef,efd->gsed", jax.nn.silu(h) * u, p["wo"].astype(x.dtype))
        comb = (
            jax.nn.one_hot(idx, E, dtype=x.dtype)
            * gate_vals.astype(x.dtype)[..., None]
        ).sum(2)  # [g, gs, E]
        out = jnp.einsum("gsed,gse->gsd", eo, comb)
    else:
        # GShard capacity dispatch, per group
        C = max(int(gs * top_k * capacity_factor / E), 1)
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)  # [g, gs, k, E]
        flat = onehot.reshape(Gm, gs * top_k, E)
        pos = jnp.cumsum(flat, axis=1) - flat  # entries before me (per group)
        pos = (pos * flat).sum(-1).reshape(Gm, gs, top_k)
        keep = pos < C
        slot = jax.nn.one_hot(jnp.where(keep, pos, C), C + 1, dtype=x.dtype)[..., :C]
        ek = jax.nn.one_hot(idx, E, dtype=x.dtype)  # [g, gs, k, E]
        disp = jnp.einsum("gske,gskc->gsec", ek, slot)  # [g, gs, E, C]
        xe = jnp.einsum("gsec,gsd->gecd", disp, xg)  # [g, E, C, D]
        h = jnp.einsum("gecd,edf->gecf", xe, p["wi"].astype(x.dtype))
        u = jnp.einsum("gecd,edf->gecf", xe, p["wu"].astype(x.dtype))
        eo = jnp.einsum("gecf,efd->gecd", jax.nn.silu(h) * u, p["wo"].astype(x.dtype))
        cw = jnp.einsum(
            "gske,gskc->gsec",
            ek * jnp.where(keep, gate_vals, 0.0).astype(x.dtype)[..., None],
            slot,
        )
        out = jnp.einsum("gsec,gecd->gsd", cw, eo)

    if "shared_wi" in p:
        out = out + _shared_expert(p, xg)

    out = out.reshape(Gm * gs, D)[:T]
    return out.reshape(B, S, D), aux
