"""Memory-program synthesis: ModelConfig -> (RegionMap, [Phase]).

This is the allocation half of the Tracer: every logical tensor class of a
step is registered as a region (the eBPF range-map analogue), and each layer
group becomes a Phase with its byte-accurate access list.  The CXLMemSim
attach path then prices any placement policy / topology against the step.

Accounting (per group, per step):
  train:   fwd reads W, writes A; bwd reads W + A, writes G(=W bytes);
           optimizer reads G + M (2 moments) + P, writes M + P.
  prefill: reads W, writes A + KV.
  decode:  reads W + KV(cache_len·kv_bytes_per_tok) + states, writes 1 token KV.

Held-expert decode (``decode_program``, the granitemoehybrid family): one
epoch per layer plus embed and head, weights sized by layer kind, and each
step's routed-expert reads taken from that step's own router counts.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, List, Tuple

import numpy as np

from repro.core.events import RegionMap
from repro.core.tracer import Access, Phase

__all__ = ["build_regions_and_phases", "decode_program", "group_param_bytes", "layer_param_counts"]


def _bytes_of(n_params: float, dtype_bytes: int = 4) -> float:
    return n_params * dtype_bytes


def group_param_bytes(cfg) -> float:
    """Parameters of one group (from the analytic counts)."""
    counts = cfg.param_counts()
    # embed (+head) params
    embed = cfg.vocab_size * cfg.d_model * (1 if cfg.embed_inputs else 0)
    if not cfg.tie_embeddings or not cfg.embed_inputs:
        embed += cfg.d_model * cfg.vocab_size
    per_group = (counts["total"] - embed - cfg.d_model) / max(cfg.n_groups, 1)
    return max(per_group, 0.0)


def build_regions_and_phases(
    cfg,
    kind: str,  # 'train' | 'prefill' | 'decode'
    batch: int,
    seq: int,
    param_dtype_bytes: int = 4,
    act_dtype_bytes: int = 4,
    cache_len: int = 0,
) -> Tuple[RegionMap, List[Phase]]:
    regions = RegionMap()
    G = cfg.n_groups
    D = cfg.d_model
    tokens = batch * (seq if kind != "decode" else 1)

    pg = group_param_bytes(cfg) * param_dtype_bytes
    embed_bytes = cfg.vocab_size * D * param_dtype_bytes
    act_bytes = tokens * D * act_dtype_bytes  # residual stream per group
    kv_per_tok = (
        2 * cfg.n_kv_heads * cfg.d_head * cfg.attn_layers_per_group * act_dtype_bytes
    )

    if cfg.embed_inputs:
        regions.alloc("embed", int(embed_bytes), "param")
    for g in range(G):
        regions.alloc(f"block{g}.w", int(pg), "param")
        regions.alloc(f"block{g}.act", int(act_bytes), "activation")
        if kind == "train":
            regions.alloc(f"block{g}.grad", int(pg), "grad")
            regions.alloc(f"block{g}.opt", int(2 * pg), "opt_state")
        if kind in ("prefill", "decode") and kv_per_tok:
            cache_tokens = batch * max(seq, cache_len)
            regions.alloc(
                f"block{g}.kv", int(cache_tokens * kv_per_tok), "kvcache"
            )
    if kind == "train":
        regions.alloc("logits", int(tokens * cfg.vocab_size * act_dtype_bytes), "activation")

    # per-group model FLOPs (6·n·tokens train, 2·n·tokens inference)
    n_active_group = cfg.param_counts()["active"] / max(G, 1)
    mult = 6.0 if kind == "train" else 2.0
    flops_g = mult * n_active_group * tokens

    phases: List[Phase] = []
    if cfg.embed_inputs:
        phases.append(
            Phase(
                "embed",
                flops=2.0 * tokens * D,
                accesses=(
                    Access("embed", embed_bytes),
                    *(() if kind == "decode" else ()),
                ),
            )
        )
    for g in range(G):
        acc = [Access(f"block{g}.w", pg)]
        if kind == "train":
            acc += [
                Access(f"block{g}.act", act_bytes, is_write=True),
                Access(f"block{g}.act", act_bytes),  # bwd re-read
                Access(f"block{g}.grad", pg, is_write=True),
            ]
        elif kind == "prefill":
            acc += [
                Access(f"block{g}.act", act_bytes, is_write=True),
                Access(f"block{g}.kv", tokens * kv_per_tok, is_write=True),
            ]
        else:  # decode
            acc += [
                Access(f"block{g}.act", act_bytes, is_write=True),
                Access(f"block{g}.kv", batch * max(cache_len, seq) * kv_per_tok),
                Access(f"block{g}.kv", batch * kv_per_tok, is_write=True),
            ]
        phases.append(Phase(f"block{g}", flops=flops_g, accesses=tuple(acc)))

    if kind == "train":
        lb = tokens * cfg.vocab_size * act_dtype_bytes
        phases.append(
            Phase(
                "loss",
                flops=2.0 * tokens * D * cfg.vocab_size,
                accesses=(Access("logits", lb, is_write=True), Access("logits", lb)),
            )
        )
        opt_acc = []
        for g in range(G):
            opt_acc += [
                Access(f"block{g}.grad", pg),
                Access(f"block{g}.opt", 2 * pg),
                Access(f"block{g}.opt", 2 * pg, is_write=True),
                Access(f"block{g}.w", pg, is_write=True),
            ]
        phases.append(Phase("optimizer", flops=0.0, accesses=tuple(opt_acc)))
    return regions, phases


# --------------------------------------------------------------------------- #
# held-expert decode: a program that is a function of the step's routing
# --------------------------------------------------------------------------- #


def _n_params(tree) -> int:
    """Parameters of a subtree of scan-stacked shapes (leading group axis
    dropped)."""
    import jax

    return sum(math.prod(leaf.shape[1:]) for leaf in jax.tree.leaves(tree))


def layer_param_counts(cfg) -> List[Dict[str, int]]:
    """Per layer, from ``param_shapes()``: ``mixer`` (its norms and its
    attention or Mamba-2 mixer), ``router``, ``shared`` (the shared expert)
    and ``expert`` (one held expert's three matrices)."""
    blocks = cfg.param_shapes()["blocks"]
    per_sub = []
    for i in range(cfg.group_size):
        sub = blocks[f"sub{i}"]
        moe = sub["moe"]
        per_sub.append({
            "mixer": _n_params({k: v for k, v in sub.items() if k != "moe"}),
            "router": _n_params(moe["router"]),
            "shared": _n_params({k: v for k, v in moe.items() if k.startswith("shared_")}),
            "expert": _n_params([moe["wi"], moe["wu"], moe["wo"]]) // cfg.n_held_experts,
        })
    return [dict(per_sub[i]) for _ in range(cfg.n_groups) for i in range(cfg.group_size)]


def decode_program(
    cfg,
    batch: int,
    s_max: int,
    param_dtype_bytes: int = 2,
    kv_dtype_bytes: int = 2,
    state_dtype_bytes: int = 4,
) -> Tuple[RegionMap, Callable[[int, np.ndarray], List[Phase]]]:
    """Decode memory program of a held-expert family (granitemoehybrid).

    Regions: ``embed`` and ``final_norm`` (param); per layer ``L{l}.mixer``,
    ``L{l}.router``, ``L{l}.shared`` (param), one ``L{l}.expert{j}`` per
    held expert (class ``expert``), and ``L{l}.kv`` (``kvcache``, the whole
    ``s_max`` buffer) on an attention layer or ``L{l}.ssm`` (``ssm_state``:
    the SSM state and the conv tail) on a Mamba-2 layer.

    Returns ``(regions, program)``; ``program(cache_len, counts)`` builds one
    step's phases from ``counts`` (int ``[n_layers, n_held_experts]``,
    tokens routed to each held expert):

      embed    reads the batch's embedding rows;
      L{l}     reads mixer, router and shared-expert weights; a Mamba-2
               layer reads and writes its state; the attention layer reads
               ``cache_len`` tokens of KV and writes one; each held expert
               with a count >= 1 is read once, one with 0 is not touched;
      head     reads the final norm and the (tied) embedding.

    Bytes are exact at the given dtypes; FLOPs (for the tracer's roofline
    pacing) are 2 per weight per token that uses it, plus attention's two
    products over ``cache_len`` keys.
    """
    D = cfg.d_model
    pb = param_dtype_bytes
    counts_by_layer = layer_param_counts(cfg)
    kinds = [m for _ in range(cfg.n_groups) for m, _ in cfg.group_spec()]
    kv_tok = 2 * cfg.n_kv_heads * cfg.d_head * kv_dtype_bytes  # K and V, one token
    conv_dim = cfg.ssm_heads * cfg.ssm_d_head + 2 * cfg.ssm_state
    ssm_bytes = batch * (
        cfg.ssm_heads * cfg.ssm_state * cfg.ssm_d_head + 3 * conv_dim
    ) * state_dtype_bytes
    n_held = cfg.n_held_experts

    regions = RegionMap()
    regions.alloc("embed", cfg.padded_vocab * D * pb, "param")
    regions.alloc("final_norm", D * pb, "param")
    for layer, (kind, pc) in enumerate(zip(kinds, counts_by_layer)):
        regions.alloc(f"L{layer}.mixer", pc["mixer"] * pb, "param")
        regions.alloc(f"L{layer}.router", pc["router"] * pb, "param")
        regions.alloc(f"L{layer}.shared", pc["shared"] * pb, "param")
        for j in range(n_held):
            regions.alloc(f"L{layer}.expert{j}", pc["expert"] * pb, "expert")
        if kind == "attn":
            regions.alloc(f"L{layer}.kv", batch * s_max * kv_tok, "kvcache")
        else:
            regions.alloc(f"L{layer}.ssm", ssm_bytes, "ssm_state")

    def program(cache_len: int, counts) -> List[Phase]:
        counts = np.asarray(counts)
        if counts.shape != (len(kinds), n_held):
            raise ValueError(f"counts {counts.shape}, expected {(len(kinds), n_held)}")
        phases = [Phase("embed", 0.0, (Access("embed", float(batch * D * pb)),))]
        for layer, (kind, pc) in enumerate(zip(kinds, counts_by_layer)):
            dense = pc["mixer"] + pc["router"] + pc["shared"]
            flops = 2.0 * batch * dense
            acc = [
                Access(f"L{layer}.mixer", float(pc["mixer"] * pb)),
                Access(f"L{layer}.router", float(pc["router"] * pb)),
                Access(f"L{layer}.shared", float(pc["shared"] * pb)),
            ]
            if kind == "attn":
                flops += 4.0 * batch * cfg.n_heads * cfg.d_head * cache_len
                acc += [
                    Access(f"L{layer}.kv", float(batch * cache_len * kv_tok)),
                    Access(f"L{layer}.kv", float(batch * kv_tok), is_write=True),
                ]
            else:
                acc += [
                    Access(f"L{layer}.ssm", float(ssm_bytes)),
                    Access(f"L{layer}.ssm", float(ssm_bytes), is_write=True),
                ]
            for j in range(n_held):
                c = int(counts[layer, j])
                if c > 0:
                    flops += 2.0 * c * pc["expert"]
                    acc.append(Access(f"L{layer}.expert{j}", float(pc["expert"] * pb)))
            phases.append(Phase(f"L{layer}", flops, tuple(acc)))
        head = (Access("final_norm", float(D * pb)), Access("embed", float(cfg.padded_vocab * D * pb)))
        phases.append(Phase("head", 2.0 * batch * D * cfg.padded_vocab, head))
        return phases

    return regions, program
