"""Jit'd public wrappers for the Pallas kernels with implementation dispatch.

Three implementations per op:

  * ``'pallas'``           — compiled TPU kernel,
  * ``'pallas_interpret'`` — same kernel body executed by the Pallas
                             interpreter (CPU-correctness path; used by tests),
  * ``'ref'``              — pure-jnp oracle (GSPMD-partitionable, and what
                             the analyzer's ``impl='inline'`` runs).

Default: ``'ref'`` on every platform — a kernel runs only where a caller
asks for it, per call with ``impl=`` or globally with
:func:`set_implementation`.  A kernel that does not compile for the TPU
(:func:`ssd`) raises when ``'pallas'`` selects it; nothing falls back.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from ..analysis.annotations import axes
from . import ref
from .congestion import congestion_cascade as _cascade_pallas
from .congestion import congestion_scan as _congestion_pallas
from .congestion import qos_congestion_cascade as _qos_cascade_pallas
from .flash_attention import flash_attention as _flash_pallas
from .ssd_scan import ssd_scan as _ssd_pallas

__all__ = [
    "attention",
    "chain_cascade",
    "congestion_cascade",
    "congestion_queue",
    "get_implementation",
    "qos_congestion_cascade",
    "set_implementation",
    "ssd",
    "staging_sort",
    "two_run_merge",
]

_VALID = ("pallas", "pallas_interpret", "ref")
_IMPL = "ref"


def get_implementation() -> str:
    return _IMPL


def set_implementation(impl: str) -> None:
    if impl not in _VALID:
        raise ValueError(f"impl must be one of {_VALID}")
    global _IMPL
    _IMPL = impl


def _resolve(impl: Optional[str]) -> str:
    if impl is not None:
        if impl not in _VALID:
            raise ValueError(f"impl must be one of {_VALID}")
        return impl
    return get_implementation()


# --------------------------------------------------------------------------- #


@axes("B,H,Sq,D", k="B,Hk,Sk,D", v="B,Hk,Sk,D")
def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_offset: int = 0,
    causal: bool = True,
    scale: Optional[float] = None,
    impl: Optional[str] = None,
    block_q: int = 256,
    block_k: int = 512,
) -> jnp.ndarray:
    """GQA attention: q [B,H,Sq,D] × kv [B,Hk,Sk,D] -> [B,H,Sq,D]."""
    i = _resolve(impl)
    if i == "ref":
        return ref.mha_attention(q, k, v, causal=causal, scale=scale, q_offset=q_offset)
    return _flash_pallas(
        q, k, v,
        q_offset=q_offset, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k,
        interpret=(i == "pallas_interpret"),
    )


@axes("B,L,H,P", dt="B,L,H", A="H", Bm="B,L,N", Cm="B,L,N")
def ssd(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    A: jnp.ndarray,
    Bm: jnp.ndarray,
    Cm: jnp.ndarray,
    chunk: int = 128,
    impl: Optional[str] = None,
) -> jnp.ndarray:
    """Mamba2 SSD mixer: x [B,L,H,P] -> y [B,L,H,P]."""
    i = _resolve(impl)
    if i == "ref":
        return ref.ssd_chunked(x, dt, A, Bm, Cm, chunk=min(chunk, x.shape[1]))
    if i == "pallas":
        raise NotImplementedError(
            "ssd: the Pallas SSD kernel does not compile for TPU (its "
            "(1, chunk, 1, P) blocks break Mosaic's tiling, and Mosaic has no "
            "in-kernel cumsum); use impl='ref'"
        )
    return _ssd_pallas(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)


@axes("N", mask="N")
def congestion_queue(
    t_sorted: jnp.ndarray,
    mask: jnp.ndarray,
    stt,
    impl: Optional[str] = None,
    block: int = 2048,
):
    """Serial-queue scan for one switch; returns (start_times, delays)."""
    i = _resolve(impl)
    if i == "ref":
        start = ref.serial_queue(t_sorted, mask, stt)
        return start, jnp.where(mask, start - t_sorted, 0.0)
    return _congestion_pallas(
        t_sorted, mask, stt, block=block, interpret=(i == "pallas_interpret")
    )


@axes("N", route_bits="N", stts="S", hosts="N")
def congestion_cascade(
    t_sorted: jnp.ndarray,
    route_bits: jnp.ndarray,
    stts: jnp.ndarray,
    impl: Optional[str] = None,
    block: int = 2048,
    merge_plan=None,
    hosts: Optional[jnp.ndarray] = None,
    n_hosts: int = 1,
):
    """Fused S-stage congestion cascade over one time-sorted epoch.

    Returns ``(t_final, slot_idx, per_stage_delay)``; see
    :func:`repro.kernels.ref.serial_queue_cascade` for the semantics.
    ``merge_plan`` (static, from :func:`repro.core.analyzer.plan_cascade`)
    prunes inter-stage merges on every path.  With ``hosts`` (per-event host
    ids, same sorted order as ``t_sorted``), ``per_stage_delay`` becomes
    host-segmented ``[S, n_hosts]``.
    """
    i = _resolve(impl)
    if i == "ref":
        return ref.serial_queue_cascade(
            t_sorted, route_bits, stts, merge_plan, hosts=hosts, n_hosts=n_hosts
        )
    return _cascade_pallas(
        t_sorted, route_bits, stts, merge_plan=merge_plan, hosts=hosts,
        n_hosts=n_hosts, block=block, interpret=(i == "pallas_interpret"),
    )


@axes(
    "N", route_bits="N", stts="S", qos="N", disc_code="S",
    class_weights="S,C", hosts="N",
)
def qos_congestion_cascade(
    t_sorted: jnp.ndarray,
    route_bits: jnp.ndarray,
    stts: jnp.ndarray,
    qos: jnp.ndarray,
    disc_code: jnp.ndarray,
    class_weights: jnp.ndarray,
    impl: Optional[str] = None,
    block: int = 2048,
    hosts: Optional[jnp.ndarray] = None,
    n_hosts: int = 1,
):
    """QoS-arbitrated congestion cascade (priority / WFQ / FIFO per switch).

    Data-driven form: ``disc_code`` ([S] i32, :data:`repro.kernels.ref.DISC_FIFO`
    etc.) and ``class_weights`` ([S, C] f32) are runtime arrays, so one
    lowering serves every discipline/weight mix.  Returns ``(t_final,
    slot_idx, per_stage_delay[S, n_hosts, C])``; see
    :func:`repro.kernels.ref.qos_cascade_dyn` for the semantics.
    """
    i = _resolve(impl)
    if i == "ref":
        return ref.qos_cascade_dyn(
            t_sorted, route_bits, stts, qos, disc_code, class_weights,
            hosts=hosts, n_hosts=n_hosts,
        )
    return _qos_cascade_pallas(
        t_sorted, route_bits, qos, stts, disc_code, class_weights,
        hosts=hosts, n_hosts=n_hosts, block=block,
        interpret=(i == "pallas_interpret"),
    )


@axes("N")
def two_run_merge(x, split, *payloads):
    """Stable merge of two adjacent sorted runs ``x[:split]`` and
    ``x[split:]`` (``split`` static) by ranking only the shorter run.

    XLA only: a short-run count, a scatter as wide as the short run, one
    ``cumsum`` and a gather per payload; Mosaic lowers neither 1-D gathers
    nor scatters.
    """
    return ref.two_run_merge(x, split, *payloads)


@axes("N")
def staging_sort(x, run_caps, *payloads):
    """On-device stable sort of concatenated sorted runs (merge tree of
    :func:`two_run_merge` rounds); bitwise-equal to a host stable argsort of
    the run-major concatenation.  XLA only, as for :func:`two_run_merge`."""
    return ref.staging_sort(x, run_caps, *payloads)


@axes("W", idx_pack="W", stts="D")
def chain_cascade(t_pack, idx_pack, stts, seg_caps):
    """Compact suffix cascade over per-stage packed sorted runs — the
    device-resident pipeline's fused merge+scan.  XLA only, as for
    :func:`two_run_merge`."""
    return ref.chain_cascade(t_pack, idx_pack, stts, seg_caps)
