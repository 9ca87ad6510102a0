"""The least HBM bytes of one decode step of a held-expert hybrid model
(granitemoehybrid), counted from the configuration's widths.

A step has to read, once: every weight the chip holds (mixers, routers,
shared experts, every held expert, since the held-expert layer computes
each of them for the batch; the final norm and the tied embedding as the
output head), the batch's embedding rows, ``cache_len`` tokens of K and V
per sequence; it writes one token of K and V per sequence, and reads and
writes every Mamba-2 layer's SSM state and conv tail.  Activations (a few
hundred KB a layer at batch 8) and the logits are left out.  The
roofline is those bytes at the chip's peak HBM bandwidth over the step's
time: a decode step at batch 8 does 16 FLOPs per bf16 weight (8 per
byte read), far below the v5e's 240 FLOP/byte ridge, so the bytes bound
it.
"""

from __future__ import annotations

from typing import Dict

import decode_program

# counter into which the decode entry sums each traced window step's least
# bytes (at that step's cache length)
LEAST_BYTES = "bench.decode.least_bytes"


def step_least_bytes(cfg: Dict, batch: int, cache_len: int) -> float:
    kinds = cfg["layer_types"]
    n_held = cfg["num_local_experts"]
    _, build = decode_program.build(cfg, batch, cache_len + 1)
    every_expert = [[1] * n_held for _ in kinds]  # read them all, once
    return float(sum(b for _, _, acc in build(cache_len, every_expert) for _, b, _ in acc))
