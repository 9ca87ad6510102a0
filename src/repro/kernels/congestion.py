"""Pallas TPU kernel for the congestion serial-queue scan (paper §3, delay 2).

The Timing Analyzer's hot loop is, per switch, the FIFO queue
``out_i = max(arr_i, out_{i-1} + STT)`` over the time-sorted events that
traverse the switch.  The closed form

    out_i = cummax(arr_i − STT·rank_i) + STT·rank_i,   rank = cumsum(mask) − 1

turns it into two prefix scans (a cumsum over the mask and a cummax over the
shifted arrivals), which map onto the TPU VPU as log₂(B) lane-rotate steps
per block plus a scalar carry between sequential grid steps.

One kernel body (:func:`_stage_kernel`) runs one switch stage over one
time-sorted epoch:

  * ``C`` masked scans per block, one per QoS read class — class ``c``'s
    subsequence is ``lo_c <= q <= c`` with service time ``stt_c`` (strict
    priority: ``lo_c = 0``; WFQ and FIFO: ``lo_c = c``, WFQ inflating
    ``stt_c``); the plain FIFO queue is the ``C = 1`` instance and reads no
    class row at all;
  * per-segment delay sums (host, or host × class) accumulated in a
    whole-array SMEM output, so delay attribution never leaves the kernel.

Wrappers:

  * :func:`congestion_scan` — one switch's FIFO queue over a pre-sorted epoch.
  * :func:`congestion_cascade` — the S-stage cascade (optionally
    host-segmented) and :func:`qos_congestion_cascade` — the QoS-arbitrated
    cascade.  Each launches the stage kernel once per stage, and restores the
    sorted-by-current-time invariant between launches in XLA, inside the
    same jit, with exactly the merges of the inline path
    (:func:`repro.kernels.ref.serial_queue_cascade` /
    :func:`repro.kernels.ref.qos_cascade_dyn` drive the stage loop; only the
    scan is swapped).  The merges are 1-D gathers and scatters, which Mosaic
    does not lower, so they stay outside the kernel.

TPU adaptation notes (vs the paper's sequential C++ loop):
  * events live in HBM as (1, N) rows; each grid step pulls a (1, B) tile
    into VMEM, B = 2048 lanes; scalars (service times, class bounds) live in
    SMEM;
  * Mosaic implements neither ``cumsum`` nor ``cummax``, so both prefix
    scans are Hillis–Steele ladders of ``pltpu.roll`` lane rotations under
    an iota mask (log₂ B steps; the rank sum is over 0/1 floats and stays
    exact), and each block's carry comes from a reduction (``max(g)`` is
    ``cummax(g)[-1]``) — a ``[-1]`` element read lowers to an unsupported
    dynamic slice;
  * the inter-block carry is kept in SMEM, exploiting the sequential TPU
    grid — the idiomatic replacement for a GPU decoupled-lookback scan;
  * compiled for a described v5e in ``tests/test_tpu_compile.py`` and run
    against ``impl='inline'`` on a v5e chip by ``chip_smoke.py``;
  * the scan is **latency-agnostic**: it queues arrival times only.
    Device-cache mode (:mod:`repro.core.cache`) reshapes the per-event
    *latency* through a per-(host, pool) scale vector applied outside the
    kernel, in :func:`repro.core.analyzer._analyze_jax` — so this one kernel
    body serves cache-enabled and cache-free analyses alike.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..analysis.annotations import axes
from . import ref as _ref

__all__ = [
    "congestion_cascade",
    "congestion_scan",
    "qos_congestion_cascade",
    "stage_scan",
    "DEFAULT_BLOCK",
]

DEFAULT_BLOCK = 2048
_NEG = -1e30  # sentinel "minus infinity" safely inside f32


def _prefix(x, op, identity):
    """Inclusive prefix scan of ``op`` along the lanes of a (1, B) tile."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    k = 1
    while k < x.shape[1]:
        x = op(x, jnp.where(lane >= k, pltpu.roll(x, k, 1), identity))
        k *= 2
    return x


def _stage_kernel(n_classes, n_seg, *refs):
    """One (1, B) block of one switch stage.

    Ref layout (inputs, outputs, scratch):
      t_ref     (1, B) time-sorted arrival tile
      m_ref     (1, B) i32 1 where the event traverses this stage
      q_ref     (1, B) i32 read class                    [n_classes > 1 only]
      seg_ref   (1, B) i32 delay-attribution segment     [n_seg > 1 only]
      stt_ref   SMEM f32[C] per-class service time
      lo_ref    SMEM i32[C] lowest class in class c's scan  [n_classes > 1 only]
      out_ref   (1, B) start times (arrival where unmasked)
      dsum_ref  SMEM f32[n_seg] per-segment delay sums (whole array)
      carry_ref SMEM f32[2C]: [c] = class c's running max of g,
                [C + c] = class c's masked events in prior blocks
    """
    it = iter(refs)
    t_ref, m_ref = next(it), next(it)
    q_ref = next(it) if n_classes > 1 else None
    seg_ref = next(it) if n_seg > 1 else None
    stt_ref = next(it)
    lo_ref = next(it) if n_classes > 1 else None
    out_ref, dsum_ref, carry_ref = next(it), next(it), next(it)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        for c in range(n_classes):
            carry_ref[c] = _NEG
            carry_ref[n_classes + c] = 0.0
        for k in range(n_seg):
            dsum_ref[k] = 0.0

    t = t_ref[...]
    m = m_ref[...] != 0
    start = t
    for c in range(n_classes):
        if q_ref is None:
            sel = own = m
        else:
            q = q_ref[...]
            sel = m & (q <= c) & (q >= lo_ref[c])
            own = m & (q == c)
        stt = stt_ref[c]
        mf = sel.astype(t.dtype)
        rank = (_prefix(mf, jnp.add, 0.0) - 1.0) + carry_ref[n_classes + c]
        g = jnp.where(sel, t - stt * rank, _NEG)
        f = jnp.maximum(_prefix(g, jnp.maximum, _NEG), carry_ref[c])
        start = jnp.where(own, f + stt * rank, start)
        carry_ref[c] = jnp.maximum(carry_ref[c], jnp.max(g))
        carry_ref[n_classes + c] = carry_ref[n_classes + c] + jnp.sum(mf)

    out_ref[...] = start
    d = jnp.where(m, start - t, 0.0)
    if seg_ref is None:
        dsum_ref[0] = dsum_ref[0] + jnp.sum(d)
    else:
        seg = seg_ref[...]
        for k in range(n_seg):
            dsum_ref[k] = dsum_ref[k] + jnp.sum(jnp.where(seg == k, d, 0.0))


@functools.partial(jax.jit, static_argnames=("n_seg", "block", "interpret"))
def stage_scan(
    t: jnp.ndarray,  # [N] f32, sorted along each scanned subsequence
    mask: jnp.ndarray,  # [N] bool, events traversing this stage
    stt: jnp.ndarray,  # [C] f32 per-class service time
    q: jnp.ndarray = None,  # [N] i32 read class (required when C > 1)
    lo: jnp.ndarray = None,  # [C] i32 lowest class of each class's scan
    seg: jnp.ndarray = None,  # [N] i32 attribution segment (n_seg > 1)
    n_seg: int = 1,
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
):
    """One switch stage: returns ``(start[N], seg_delay[n_seg])``.

    ``start`` is the queue's start time for masked events and the arrival
    for the rest; ``seg_delay[k]`` sums the masked events' waiting over
    segment ``k`` of ``seg`` (the whole stage when ``n_seg == 1``).
    """
    n = t.shape[0]
    n_classes = int(stt.shape[0])
    pad = -n % block
    rows = [
        jnp.pad(t, (0, pad), constant_values=jnp.finfo(t.dtype).max / 8),
        jnp.pad(mask.astype(jnp.int32), (0, pad)),
    ]
    if n_classes > 1:
        rows.append(jnp.pad(q.astype(jnp.int32), (0, pad)))
    if n_seg > 1:
        rows.append(jnp.pad(seg.astype(jnp.int32), (0, pad)))
    npad = n + pad
    rows = [r.reshape(1, npad) for r in rows]
    scalars = [jnp.asarray(stt, t.dtype)]
    if n_classes > 1:
        scalars.append(jnp.asarray(lo, jnp.int32))
    tile = pl.BlockSpec((1, block), lambda i: (0, i))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    start, dsum = pl.pallas_call(
        functools.partial(_stage_kernel, n_classes, n_seg),
        grid=(npad // block,),
        in_specs=[tile] * len(rows) + [smem] * len(scalars),
        out_specs=[tile, smem],
        out_shape=[
            jax.ShapeDtypeStruct((1, npad), t.dtype),
            jax.ShapeDtypeStruct((n_seg,), t.dtype),
        ],
        scratch_shapes=[pltpu.SMEM((2 * n_classes,), t.dtype)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*rows, *scalars)
    return start[0, :n], dsum


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def congestion_scan(
    t_sorted: jnp.ndarray,  # [N] f32, time-sorted arrivals
    mask: jnp.ndarray,  # [N] bool, events traversing this switch
    stt,  # scalar f32
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
):
    """Returns ``(start_times[N], delays[N])`` for one switch's queue."""
    start, _ = stage_scan(
        t_sorted, mask, jnp.reshape(jnp.asarray(stt, t_sorted.dtype), (1,)),
        block=block, interpret=interpret,
    )
    return start, jnp.where(mask, start - t_sorted, 0.0)


# --------------------------------------------------------------------------- #
# Cascades: one stage launch per switch, merges in XLA between launches
# --------------------------------------------------------------------------- #


@functools.partial(
    jax.jit, static_argnames=("merge_plan", "n_hosts", "block", "interpret")
)
@axes("N", route_bits="N", stts="S", hosts="N")
def congestion_cascade(
    t_sorted: jnp.ndarray,  # [N] f32, globally time-sorted arrivals
    route_bits: jnp.ndarray,  # [N] i32, bit s set iff event traverses stage s
    stts: jnp.ndarray,  # [S] f32, service times in stage order
    merge_plan=None,  # static merge schedule (None: conservative)
    hosts: jnp.ndarray = None,  # [N] i32 host ids, same sorted order
    n_hosts: int = 1,
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
):
    """S-stage congestion cascade with the stage scan as a Pallas kernel.

    Returns ``(t_final[N], slot_idx[N], per_stage_delay)`` with exactly the
    semantics of :func:`repro.kernels.ref.serial_queue_cascade` (same merge
    schedule): ``per_stage_delay`` is ``[S]``, or ``[S, n_hosts]`` when
    ``hosts`` is given.
    """

    def scan(ts, m, stt, seg, n_seg):
        return stage_scan(
            ts, m, stt[None], seg=seg, n_seg=n_seg, block=block,
            interpret=interpret,
        )

    return _ref.serial_queue_cascade(
        t_sorted, route_bits, stts, merge_plan, hosts=hosts, n_hosts=n_hosts,
        scan=scan,
    )


@functools.partial(jax.jit, static_argnames=("n_hosts", "block", "interpret"))
@axes(
    "N", route_bits="N", qos="N", stts="S", disc_code="S",
    class_weights="S,C", hosts="N",
)
def qos_congestion_cascade(
    t_sorted: jnp.ndarray,  # [N] f32, globally time-sorted arrivals
    route_bits: jnp.ndarray,  # [N] i32, bit s set iff event traverses stage s
    qos: jnp.ndarray,  # [N] i32 QoS class ids, same sorted order
    stts: jnp.ndarray,  # [S] f32, service times in stage order
    disc_code: jnp.ndarray,  # [S] i32 discipline codes (ref.DISC_*)
    class_weights: jnp.ndarray,  # [S, C] f32 per-stage class weights
    hosts: jnp.ndarray = None,  # [N] i32 host ids, same sorted order
    n_hosts: int = 1,
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
):
    """QoS-arbitrated cascade with the stage scans as a Pallas kernel.

    Returns ``(t_final[N], slot_idx[N], per_stage_delay[S, H, C])`` with the
    semantics of :func:`repro.kernels.ref.qos_cascade_dyn` (``H`` is
    ``n_hosts``, 1 without ``hosts``): per stage, ``C`` per-class FIFO
    scans replace the inline path's one max-plus scan — the same DES
    horizons — and the stable multi-run fold between stages is shared.
    """
    n_classes = int(class_weights.shape[1])
    lv = jnp.arange(n_classes, dtype=jnp.int32)

    def stage(ts, m, q_cur, disc, stt, w_row, seg, n_seg):
        q_eff = jnp.where(disc == _ref.DISC_FIFO, 0, q_cur)
        stt_c = jnp.where(disc == _ref.DISC_WFQ, stt * w_row.sum() / w_row, stt)
        lo = jnp.where(disc == _ref.DISC_PRIORITY, 0, lv)
        return stage_scan(
            ts, m, stt_c, q=q_eff, lo=lo, seg=seg, n_seg=n_seg, block=block,
            interpret=interpret,
        )

    return _ref.qos_cascade_dyn(
        t_sorted, route_bits, stts, qos, disc_code, class_weights,
        hosts=hosts, n_hosts=n_hosts, stage=stage,
    )
