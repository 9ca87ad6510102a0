"""Block assembly: per-family layer groups + scan-over-layers.

Every architecture is expressed as a stack of identical **groups** so the
whole depth is a single ``lax.scan`` over stacked parameters (one group
compiles once — essential for 88-layer models on a single-core build host,
and the idiomatic JAX structure for remat + pipeline-friendly HLO).

Group composition per family (cfg.group_spec()):

  dense   1 group  = [attn + mlp]                        × n_layers
  moe     1 group  = [attn+mlp] × (interleave−1) + [attn+moe]
  hybrid  1 group  = attn_every sublayers, one of them attention, the rest
          Mamba2; FFNs alternate dense/MoE (Jamba's 1:7 + MoE-every-2)
  granitemoehybrid = hybrid's period (attention mid-group), MoE with
          a shared expert in every sublayer, over a held share of the
          experts (moe.held_moe_block); Granite's residual multiplier
  ssm     1 group  = [mamba2]                             × n_layers
  vlm     = dense (M-RoPE positions)
  audio   = dense non-causal encoder (LN + GELU MLP)
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as attn_mod
from . import mamba2 as m2
from . import moe as moe_mod
from .layers import (
    dense_mlp,
    gated_mlp,
    init_dense_mlp,
    init_gated_mlp,
    layer_norm,
    rms_norm,
)

Params = Dict[str, Any]


# --------------------------------------------------------------------------- #
# group init
# --------------------------------------------------------------------------- #


def init_group(key, cfg) -> Params:
    """Parameters for ONE group (to be stacked over cfg.n_groups)."""
    p: Params = {}
    spec = cfg.group_spec()
    keys = jax.random.split(key, len(spec))
    for i, (mixer, ffn) in enumerate(spec):
        sk = jax.random.split(keys[i], 4)
        sub: Params = {}
        if cfg.norm == "ln":
            sub["norm1"] = {"g": jnp.ones((cfg.d_model,), jnp.float32), "b": jnp.zeros((cfg.d_model,), jnp.float32)}
        else:
            sub["norm1"] = jnp.ones((cfg.d_model,), jnp.float32)
        if mixer == "attn":
            sub["attn"] = attn_mod.init_attention(
                sk[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.qk_norm
            )
        elif mixer == "mamba":
            sub["mamba"] = m2.init_mamba2(
                sk[0], cfg.d_model, cfg.ssm_heads, cfg.ssm_d_head, cfg.ssm_state
            )
        else:
            raise ValueError(mixer)
        if ffn is not None:
            if cfg.norm == "ln":
                sub["norm2"] = {"g": jnp.ones((cfg.d_model,), jnp.float32), "b": jnp.zeros((cfg.d_model,), jnp.float32)}
            else:
                sub["norm2"] = jnp.ones((cfg.d_model,), jnp.float32)
            if ffn == "mlp":
                if cfg.norm == "ln" or not cfg.mlp_gated:  # plain GELU MLP
                    sub["mlp"] = init_dense_mlp(sk[1], cfg.d_model, cfg.d_ff)
                else:
                    sub["mlp"] = init_gated_mlp(sk[1], cfg.d_model, cfg.d_ff)
            elif ffn == "moe":
                sub["moe"] = moe_mod.init_moe(
                    sk[1], cfg.d_model, cfg.moe_d_ff or cfg.d_ff, cfg.n_held_experts,
                    shared_expert=cfg.shared_expert, n_router=cfg.n_experts,
                    shared_d_ff=cfg.shared_d_ff,
                )
            else:
                raise ValueError(ffn)
        p[f"sub{i}"] = sub
    return p


def _norm(cfg, x, np_):
    if cfg.norm == "ln":
        return layer_norm(x, np_["g"], np_["b"])
    return rms_norm(x, np_, cfg.norm_eps)


def _residual(cfg, x, branch):
    if cfg.residual_multiplier != 1.0:
        branch = branch * jnp.asarray(cfg.residual_multiplier, branch.dtype)
    return x + branch


def _attn_scale(cfg):
    return cfg.attention_multiplier or None  # None => d_head ** -0.5


def _ffn(cfg, sub, ffn, h, decode: bool):
    """One FFN sublayer: (out, aux, held-expert counts or None)."""
    if ffn == "mlp":
        out = (
            dense_mlp(sub["mlp"], h) if (cfg.norm == "ln" or not cfg.mlp_gated) else gated_mlp(sub["mlp"], h)
        )
        return out, jnp.zeros((), jnp.float32), None
    if cfg.family == "granitemoehybrid":
        return moe_mod.held_moe_block(sub["moe"], h, cfg.top_k, expert_offset=cfg.expert_offset)
    out, a = moe_mod.moe_block(
        sub["moe"], h, cfg.top_k,
        capacity_factor=cfg.decode_capacity_factor if decode else cfg.capacity_factor,
        dispatch=cfg.moe_dispatch, group_tokens=cfg.moe_group_tokens,
    )
    return out, a, None


# --------------------------------------------------------------------------- #
# group forward (train / prefill)
# --------------------------------------------------------------------------- #


def apply_group(
    p: Params,
    x: jnp.ndarray,  # [B, S, D]
    positions: jnp.ndarray,
    cfg,
    collect_cache: bool = False,
    cache_pad_to: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[Dict[str, Any]]]:
    """Returns (x, aux_loss, group_cache) for one group.

    ``group_cache`` (prefill only) is already in decode format:
      {'kv': {'k': [n_attn, B, Hk, Smax, D], 'v': ...},
       'ssm_conv': [n_mamba, B, K-1, di], 'ssm_state': [n_mamba, B, H, N, P]}
    K/V are padded on the sequence axis to ``cache_pad_to`` (decode budget).
    """
    aux = jnp.zeros((), jnp.float32)
    kv_k: List = []
    kv_v: List = []
    ssm_conv: List = []
    ssm_state: List = []
    for i, (mixer, ffn) in enumerate(cfg.group_spec()):
        sub = p[f"sub{i}"]
        h = _norm(cfg, x, sub["norm1"])
        if mixer == "attn":
            if collect_cache:
                # prefill: also materialize this sublayer's K/V for the cache
                B, S, _ = h.shape
                q, k, v = attn_mod._project_qkv(
                    sub["attn"], h, cfg.n_heads, cfg.n_kv_heads, cfg.d_head,
                    positions, cfg.rope_variant, cfg.qk_norm, cfg.rope_theta,
                )
                o = attn_mod.chunked_attention(
                    q, k, v, causal=cfg.causal, scale=_attn_scale(cfg),
                    block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
                    window=cfg.window,
                )
                o = o.transpose(0, 2, 1, 3).reshape(B, S, cfg.n_heads * cfg.d_head)
                mix = o @ sub["attn"]["wo"].astype(h.dtype)
                pad = (cache_pad_to or S) - S
                if pad > 0:
                    k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
                    v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
                kv_k.append(k.astype(cfg.cache_dtype))
                kv_v.append(v.astype(cfg.cache_dtype))
            else:
                mix = attn_mod.attention_block(
                    sub["attn"], h, positions,
                    cfg.n_heads, cfg.n_kv_heads, cfg.d_head,
                    causal=cfg.causal, rope_variant=cfg.rope_variant,
                    qk_norm=cfg.qk_norm, theta=cfg.rope_theta, window=cfg.window,
                    block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
                    scale=_attn_scale(cfg),
                )
        else:  # mamba
            if collect_cache:
                mix, mcache = m2.mamba2_prefill(
                    sub["mamba"], h, cfg.ssm_heads, cfg.ssm_d_head, cfg.ssm_state,
                    chunk=cfg.ssm_chunk, eps=cfg.norm_eps,
                )
                ssm_conv.append(mcache["conv"])
                ssm_state.append(mcache["ssm"])
            else:
                mix = m2.mamba2_block(
                    sub["mamba"], h, cfg.ssm_heads, cfg.ssm_d_head, cfg.ssm_state,
                    chunk=cfg.ssm_chunk, eps=cfg.norm_eps,
                )
        x = _residual(cfg, x, mix)
        if ffn is not None:
            h = _norm(cfg, x, sub["norm2"])
            out, a, _ = _ffn(cfg, sub, ffn, h, decode=False)
            if ffn == "moe":
                aux = aux + a
            x = _residual(cfg, x, out)
    cache = None
    if collect_cache:
        cache = {}
        if kv_k:
            cache["kv"] = {"k": jnp.stack(kv_k), "v": jnp.stack(kv_v)}
        if ssm_conv:
            cache["ssm_conv"] = jnp.stack(ssm_conv)
            cache["ssm_state"] = jnp.stack(ssm_state)
    return x, aux, cache


# --------------------------------------------------------------------------- #
# group decode (single token, cache update)
# --------------------------------------------------------------------------- #


def decode_group(
    p: Params,
    x: jnp.ndarray,  # [B, 1, D]
    positions: jnp.ndarray,
    cache: Dict[str, Any],  # this group's cache slice
    cache_len,
    cfg,
) -> Tuple[jnp.ndarray, Dict[str, Any], Optional[jnp.ndarray]]:
    """Returns (x, new_cache, counts): ``counts`` is the int32
    [group_size, n_held_experts] held-expert routing count of a
    granitemoehybrid group, else None."""
    new_cache: Dict[str, Any] = {}
    counts: List = []
    ai = 0
    mi = 0
    for i, (mixer, ffn) in enumerate(cfg.group_spec()):
        sub = p[f"sub{i}"]
        h = _norm(cfg, x, sub["norm1"])
        if mixer == "attn":
            kv = (cache["kv"]["k"][ai], cache["kv"]["v"][ai])
            mix, kv_new = attn_mod.decode_attention_block(
                sub["attn"], h, positions, kv, cache_len,
                cfg.n_heads, cfg.n_kv_heads, cfg.d_head,
                rope_variant=cfg.rope_variant, qk_norm=cfg.qk_norm,
                theta=cfg.rope_theta, window=cfg.window, scale=_attn_scale(cfg),
            )
            new_cache.setdefault("kv", {"k": [], "v": []})
            new_cache["kv"]["k"].append(kv_new[0])
            new_cache["kv"]["v"].append(kv_new[1])
            ai += 1
        else:
            mc = {"conv": cache["ssm_conv"][mi], "ssm": cache["ssm_state"][mi]}
            mix, mc_new = m2.mamba2_decode(
                sub["mamba"], h, mc, cfg.ssm_heads, cfg.ssm_d_head, cfg.ssm_state,
                eps=cfg.norm_eps,
            )
            new_cache.setdefault("ssm_conv", []).append(mc_new["conv"])
            new_cache.setdefault("ssm_state", []).append(mc_new["ssm"])
            mi += 1
        x = _residual(cfg, x, mix)
        if ffn is not None:
            h = _norm(cfg, x, sub["norm2"])
            out, _, c = _ffn(cfg, sub, ffn, h, decode=True)
            if c is not None:
                counts.append(c)
            x = _residual(cfg, x, out)
    # restack lists into arrays
    if "kv" in new_cache:
        new_cache["kv"] = {
            "k": jnp.stack(new_cache["kv"]["k"]),
            "v": jnp.stack(new_cache["kv"]["v"]),
        }
    if "ssm_conv" in new_cache:
        new_cache["ssm_conv"] = jnp.stack(new_cache["ssm_conv"])
        new_cache["ssm_state"] = jnp.stack(new_cache["ssm_state"])
    return x, new_cache, (jnp.stack(counts) if counts else None)


# --------------------------------------------------------------------------- #
# full stacks
# --------------------------------------------------------------------------- #


def init_stack(key, cfg) -> Params:
    """Stacked group params: every leaf gains a leading n_groups dim."""
    keys = jax.random.split(key, cfg.n_groups)
    if cfg.scan_layers:
        return jax.vmap(lambda k: init_group(k, cfg))(keys)
    return [init_group(k, cfg) for k in keys]


def apply_stack(
    stack: Params,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cfg,
    collect_cache: bool = False,
    cache_pad_to: Optional[int] = None,
    block_specs=None,
):
    """Scan over groups. Returns (x, aux, stacked_caches).

    ``block_specs``: optional PartitionSpec pytree for ONE group (TP-only,
    no data axis).  With cfg.fsdp_gather_at_layer the scan body casts the
    group's weights to cfg.dtype and constrains them to these specs — the
    explicit ZeRO-3 gather-at-use.
    """

    def maybe_gather(gp):
        if not (cfg.fsdp_gather_at_layer and block_specs is not None):
            return gp
        from repro.distributed.collectives import constrain

        def one(w, spec):
            w = w.astype(cfg.dtype) if w.ndim >= 2 else w
            return constrain(w, spec)

        return jax.tree.map(
            one, gp, block_specs,
            is_leaf=lambda v: not isinstance(v, dict),
        )

    def body(carry, gp):
        h, aux = carry
        h, a, cache = apply_group(
            maybe_gather(gp), h, positions, cfg,
            collect_cache=collect_cache, cache_pad_to=cache_pad_to,
        )
        return (h, aux + a), cache

    if cfg.remat:
        body = jax.checkpoint(body, policy=cfg.remat_policy)

    if cfg.scan_layers:
        (x, aux), caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), stack)
    else:
        aux = jnp.zeros((), jnp.float32)
        outs = []
        for gp in stack:
            (x, aux), c = body((x, aux), gp)
            outs.append(c)
        caches = (
            jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
            if outs and outs[0] is not None
            else None
        )
    return x, aux, caches


def decode_stack(stack: Params, x, positions, caches, cache_len, cfg):
    """Scan decode over groups with per-group cache slices.  Returns (x,
    new_caches, counts): ``counts`` is [n_groups, group_size,
    n_held_experts] for held-expert groups, else None."""

    def body(h, inp):
        gp, cache = inp
        h, new_cache, counts = decode_group(gp, h, positions, cache, cache_len, cfg)
        return h, (new_cache, counts)

    if cfg.scan_layers:
        x, (new_caches, counts) = jax.lax.scan(body, x, (stack, caches))
    else:
        new_list = []
        for i, gp in enumerate(stack):
            c = jax.tree.map(lambda a: a[i], caches)
            x, nc = body(x, (gp, c))
            new_list.append(nc)
        new_caches, counts = jax.tree.map(lambda *xs: jnp.stack(xs), *new_list)
    return x, new_caches, counts
