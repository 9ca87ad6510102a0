"""Decode memory program of a held-expert hybrid model (granitemoehybrid):
the benchmark's own copy of the simulator's builder
(``repro.models.phases.decode_program``), so the traffic a cell prices
stays fixed while the program changes.  It imports nothing of the
program: regions and phases come back as plain tuples, as in
``tenants.py``.

Parameter counts follow the published layer equations at the
configuration's widths (its top-level Hugging Face keys):

  Mamba-2 layer  norms 2·D; in_proj D·(2·di + 2·N + H); conv (K + 1)·(di + 2·N)
                 (weights and bias); A_log, dt_bias, D: 3·H; gated norm di;
                 out_proj di·D   (di = H·P, one B/C group)
  attention      norms 2·D; q D·Hq·Dh; k, v D·Hk·Dh each; o Hq·Dh·D (no bias)
  router         D · router_experts (the published num_local_experts)
  shared expert  3·D·shared_intermediate_size
  one expert     3·D·intermediate_size

Per step: ``embed`` reads the batch's embedding rows; layer ``l`` reads its
mixer, router and shared-expert weights, a Mamba-2 layer reads and writes
its SSM state and conv tail, the attention layer reads ``cache_len``
tokens of K and V and writes one; every held expert with a count >= 1 is
read once; ``head`` reads the final norm and the tied embedding.  FLOPs
(for the tracer's pacing) are 2 per weight per token that uses it plus
attention's two products over ``cache_len`` keys.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

Region = Tuple[str, int, str]
Access = Tuple[str, float, bool]
Phase = Tuple[str, float, Tuple[Access, ...]]


def head_dim(m: Dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def router_experts(cfg: Dict) -> int:
    """Experts the router scores: the published count, held over
    ``chips_sharing_each_layer`` chips."""
    ep = cfg["expert_parallel"]
    return ep["experts_per_chip"] * ep["chips_sharing_each_layer"]


def layer_params(cfg: Dict, kind: str) -> Dict[str, int]:
    m = cfg
    D = m["hidden_size"]
    if kind == "mamba":
        H, P, N, K = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"], m["mamba_d_conv"]
        di = H * P
        mixer = 2 * D + D * (2 * di + 2 * N + H) + (K + 1) * (di + 2 * N) + 3 * H + di + di * D
    elif kind == "attention":
        Hq, Hk, Dh = m["num_attention_heads"], m["num_key_value_heads"], head_dim(m)
        mixer = 2 * D + 2 * D * Hq * Dh + 2 * D * Hk * Dh
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return {
        "mixer": mixer,
        "router": D * router_experts(cfg),
        "shared": 3 * D * m["shared_intermediate_size"],
        "expert": 3 * D * m["intermediate_size"],
    }


def build(cfg: Dict, batch: int, s_max: int):
    """``(regions, program)``; ``program(cache_len, counts)`` gives one
    step's phases from ``counts`` ([n_layers][n_held]: tokens routed to
    each held expert)."""
    m, t = cfg, cfg["tenant"]
    D, V = m["hidden_size"], m["vocab_size"]
    pb, kvb, sb = t["param_dtype_bytes"], t["kv_dtype_bytes"], t["state_dtype_bytes"]
    kinds = list(m["layer_types"])
    n_held = m["num_local_experts"]
    kv_tok = 2 * m["num_key_value_heads"] * head_dim(m) * kvb
    H, P, N, K = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"], m["mamba_d_conv"]
    ssm_bytes = batch * (H * N * P + (K - 1) * (H * P + 2 * N)) * sb
    params = [layer_params(cfg, k) for k in kinds]

    regions: List[Region] = [("embed", V * D * pb, "param"), ("final_norm", D * pb, "param")]
    for layer, (kind, pc) in enumerate(zip(kinds, params)):
        regions += [
            (f"L{layer}.mixer", pc["mixer"] * pb, "param"),
            (f"L{layer}.router", pc["router"] * pb, "param"),
            (f"L{layer}.shared", pc["shared"] * pb, "param"),
        ]
        regions += [(f"L{layer}.expert{j}", pc["expert"] * pb, "expert") for j in range(n_held)]
        if kind == "attention":
            regions.append((f"L{layer}.kv", batch * s_max * kv_tok, "kvcache"))
        else:
            regions.append((f"L{layer}.ssm", ssm_bytes, "ssm_state"))

    def program(cache_len: int, counts: Sequence[Sequence[int]]) -> List[Phase]:
        if len(counts) != len(kinds) or any(len(c) != n_held for c in counts):
            raise ValueError("counts must be [n_layers][n_held]")
        phases: List[Phase] = [("embed", 0.0, (("embed", float(batch * D * pb), False),))]
        for layer, (kind, pc) in enumerate(zip(kinds, params)):
            flops = 2.0 * batch * (pc["mixer"] + pc["router"] + pc["shared"])
            acc: List[Access] = [
                (f"L{layer}.mixer", float(pc["mixer"] * pb), False),
                (f"L{layer}.router", float(pc["router"] * pb), False),
                (f"L{layer}.shared", float(pc["shared"] * pb), False),
            ]
            if kind == "attention":
                flops += 4.0 * batch * m["num_attention_heads"] * head_dim(m) * cache_len
                acc += [(f"L{layer}.kv", float(batch * cache_len * kv_tok), False),
                        (f"L{layer}.kv", float(batch * kv_tok), True)]
            else:
                acc += [(f"L{layer}.ssm", float(ssm_bytes), False),
                        (f"L{layer}.ssm", float(ssm_bytes), True)]
            for j in range(n_held):
                c = int(counts[layer][j])
                if c > 0:
                    flops += 2.0 * c * pc["expert"]
                    acc.append((f"L{layer}.expert{j}", float(pc["expert"] * pb), False))
            phases.append((f"L{layer}", flops, tuple(acc)))
        phases.append(("head", 2.0 * batch * D * V,
                       (("final_norm", float(D * pb), False), ("embed", float(V * D * pb), False))))
        return phases

    return regions, program
