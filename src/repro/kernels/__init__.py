"""Pallas TPU kernels for the perf-critical compute paths.

  congestion.py       — the paper's Timing-Analyzer hot loop (serial-queue scan)
  flash_attention.py  — blockwise causal GQA attention (VMEM-tiled)
  ssd_scan.py         — Mamba2 SSD chunked scan (sequential-grid state carry)
  ops.py              — jit'd wrappers with pallas/interpret/ref dispatch
  ref.py              — pure-jnp oracles (the correctness contract)
"""

from . import ops, ref
from .congestion import congestion_cascade, congestion_scan
from .flash_attention import flash_attention
from .ssd_scan import ssd_scan

__all__ = [
    "congestion_cascade",
    "congestion_scan",
    "flash_attention",
    "ops",
    "ref",
    "ssd_scan",
]
