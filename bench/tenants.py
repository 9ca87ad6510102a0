"""Tenant memory programs: model configuration -> (regions, phases).

The benchmark's own copy of the simulator's phase builder, so that the
traffic a cell offers stays fixed while the program changes.  It imports
nothing of the program: regions and phases come back as plain tuples, and
the harness turns them into the program's input types.

Accounting per layer group and step (all bytes at ``param_dtype_bytes`` /
``act_dtype_bytes``):

  train:   fwd reads W, writes A; bwd reads A, writes G (= W bytes);
           the optimizer reads G + M (two moments) + P, writes M + P.
  decode:  reads W + KV (cache_len tokens), writes A and one token of KV.
           Where the model attends over a sliding window
           (``sliding_window`` in the configuration), the cache holds and
           each step reads at most that many tokens per sequence: the
           one departure from the simulator's builder, which reads the
           whole cache_len.

Parameter counts follow a dense decoder of the configuration's widths:
per layer Q (d·H·h), K and V (d·KV·h each), O (H·h·d), two norms (2·d),
optional Q/K norms (2·h), and a gated (3·d·F) or plain (2·d·F) MLP; the
embedding is tied (V·d) and the final norm adds d.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

Region = Tuple[str, int, str]  # (name, nbytes, tensor class)
Access = Tuple[str, float, bool]  # (region, bytes, is_write)
Phase = Tuple[str, float, Tuple[Access, ...]]  # (name, flops, accesses)


def layer_params(m: Dict) -> int:
    d, h = m["hidden_size"], m["head_dim"]
    nh, nkv, f = m["num_attention_heads"], m["num_key_value_heads"], m["intermediate_size"]
    attn = d * nh * h + 2 * d * nkv * h + nh * h * d
    norms = 2 * d + (2 * h if m["qk_norm"] else 0)
    mlp = (3 if m["mlp_gated"] else 2) * d * f
    return attn + norms + mlp


def total_params(m: Dict) -> int:
    embed = m["vocab_size"] * m["hidden_size"] * (1 if m["tie_word_embeddings"] else 2)
    return m["num_hidden_layers"] * layer_params(m) + embed + m["hidden_size"]


def build(
    m: Dict,
    kind: str,
    batch: int,
    seq: int,
    cache_len: int = 0,
    param_dtype_bytes: int = 4,
    act_dtype_bytes: int = 4,
) -> Tuple[List[Region], List[Phase]]:
    """``m`` is the ``model`` block of a configuration file."""
    if kind not in ("train", "decode"):
        raise ValueError(f"unknown tenant kind {kind!r}")
    regions: List[Region] = []
    G = m["num_hidden_layers"]
    D = m["hidden_size"]
    V = m["vocab_size"]
    tokens = batch * (seq if kind != "decode" else 1)
    pg = float(layer_params(m) * param_dtype_bytes)
    embed_bytes = V * D * param_dtype_bytes
    act_bytes = tokens * D * act_dtype_bytes
    kv_per_tok = 2 * m["num_key_value_heads"] * m["head_dim"] * act_dtype_bytes
    kv_len = max(seq, cache_len)
    if m.get("sliding_window"):
        kv_len = min(kv_len, m["sliding_window"])

    regions.append(("embed", int(embed_bytes), "param"))
    for g in range(G):
        regions.append((f"block{g}.w", int(pg), "param"))
        regions.append((f"block{g}.act", int(act_bytes), "activation"))
        if kind == "train":
            regions.append((f"block{g}.grad", int(pg), "grad"))
            regions.append((f"block{g}.opt", int(2 * pg), "opt_state"))
        else:
            regions.append((f"block{g}.kv", int(batch * kv_len * kv_per_tok), "kvcache"))
    if kind == "train":
        regions.append(("logits", int(tokens * V * act_dtype_bytes), "activation"))

    mult = 6.0 if kind == "train" else 2.0
    flops_g = mult * (total_params(m) / G) * tokens

    phases: List[Phase] = [("embed", 2.0 * tokens * D, (("embed", float(embed_bytes), False),))]
    for g in range(G):
        acc: List[Access] = [(f"block{g}.w", pg, False)]
        if kind == "train":
            acc += [
                (f"block{g}.act", float(act_bytes), True),
                (f"block{g}.act", float(act_bytes), False),
                (f"block{g}.grad", pg, True),
            ]
        else:
            acc += [
                (f"block{g}.act", float(act_bytes), True),
                (f"block{g}.kv", float(batch * kv_len * kv_per_tok), False),
                (f"block{g}.kv", float(batch * kv_per_tok), True),
            ]
        phases.append((f"block{g}", flops_g, tuple(acc)))
    if kind == "train":
        lb = float(tokens * V * act_dtype_bytes)
        phases.append(("loss", 2.0 * tokens * D * V, (("logits", lb, True), ("logits", lb, False))))
        opt: List[Access] = []
        for g in range(G):
            opt += [
                (f"block{g}.grad", pg, False),
                (f"block{g}.opt", 2 * pg, False),
                (f"block{g}.opt", 2 * pg, True),
                (f"block{g}.w", pg, True),
            ]
        phases.append(("optimizer", 0.0, tuple(opt)))
    return regions, phases


def seed_words(seed: int) -> List[int]:
    """A seed of any size as 32-bit words, low first."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("seeds are non-negative")
    words = [seed & 0xFFFFFFFF]
    seed >>= 32
    while seed:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
    return words


def draw(traffic: Dict, seed: int) -> List[Dict]:
    """The mix's tenants with their sizes fixed: a ``[lo, hi]`` pair is
    drawn uniformly from the seed, tenant by tenant in file order."""
    rng = np.random.default_rng(seed_words(seed))
    out = []
    for spec in traffic["tenants"]:
        t = {}
        for k, v in spec.items():
            t[k] = int(rng.integers(v[0], v[1] + 1)) if isinstance(v, list) else v
        out.append(t)
    return out


def programs(cfg: Dict, mix: Sequence[Dict]) -> List[Tuple[List[Region], List[Phase]]]:
    """Every tenant's memory program under a configuration's model."""
    t = cfg["tenant"]
    return [
        build(
            cfg["model"], spec["kind"], spec["batch"], spec.get("seq", 1),
            cache_len=spec.get("cache_len", 0),
            param_dtype_bytes=t["param_dtype_bytes"], act_dtype_bytes=t["act_dtype_bytes"],
        )
        for spec in mix
    ]
