"""The Timing Analyzer — the paper's core contribution (§3, component 3).

Given one epoch's memory-event trace and a flattened topology, compute the
three delays the paper defines:

  1. **latency delay**    Σ_events (total latency of target pool − local DRAM
                          latency).  Pure gather + segment-sum.
  2. **congestion delay** per switch, events traversing the same switch must
                          be ≥ STT apart; later events are pushed back and the
                          push cascades through the path (leaf switch → RC).
  3. **bandwidth delay**  per switch, windows whose traffic exceeds BW × window
                          are stretched to bytes/BW ("observed bandwidth after
                          latency and congestion delays are added exceeds the
                          bandwidth of the switch").

Three implementations, in increasing speed order:

  * :class:`FineGrainedSimulator` — event-by-event discrete-event simulation
    walking every transaction through its switch path individually.  This is
    our stand-in for the cycle-level baseline the paper compares against
    (Gem5): exact, Python, deliberately per-event.
  * :func:`analyze_ref` — vectorized numpy epoch analyzer, float64.  The
    correctness oracle for the JAX/Pallas paths.
  * :class:`EpochAnalyzer` — jitted JAX analyzer with bucketed padding so
    repeated epochs hit the compile cache.  This is the production path.

The serial queue ``out_i = max(arr_i, out_{i-1} + STT)`` is solved in closed
form with a cumulative max:  let ``f_i = cummax(arr_i − STT·rank_i)``; then
``out_i = f_i + STT·rank_i``.  That turns the per-switch queue into a sort +
scan, which is what makes the epoch analyzer vectorizable (and, in
:mod:`repro.kernels.congestion`, a Pallas kernel).

Every jitted surface prices an epoch the same way: the two traced helpers
:func:`_price_latency` and :func:`_price_bandwidth` own the latency and
windowed-bandwidth formulas, and each surface differs only in how it
queues (the congestion cascade) and what it stacks.  From one epoch up:

  * :func:`_analyze_jax` — one epoch on the full padded plane.  Events
    arrive time-sorted (the :class:`~repro.core.events.EventStager`
    contract: the host sorts only traces that are not sorted already), the
    fused cascade (:func:`repro.kernels.ref.serial_queue_cascade` / the
    Pallas stage kernel) runs every switch stage's serial queue in one
    pass, re-merging the queued and untouched sorted runs with rank
    arithmetic instead of re-sorting, and bandwidth windows are summed on
    the post-congestion times;
  * :func:`_analyze_batch_jax` — B epochs vmapped and summed on device,
    and :func:`_analyze_words_jax`, the same on one staged int32 word
    plane (one host→device transfer per dispatch);
  * :func:`_analyze_pipeline_jax` — the chain path: single-host chain
    fabrics (:func:`plan_chain`) run a compact suffix cascade on packed
    per-stage runs, sorted on device;
  * :func:`_analyze_stack_jax` — K batches stacked ``[K, B, N]`` with
    per-row numeric topology leaves: coalesced engine sessions (one row
    broadcast to K) and fleet racks (one row each);
  * :func:`_analyze_sweep_jax` — K scenarios over U deduplicated cascades.

Each dispatch returns the ten :class:`DelayBreakdown` leaves, reduced on
device, in one device→host copy.

Choosing ``impl``:

  * ``'inline'`` — fused cascade as pure XLA ops; fastest on CPU/GPU, the
    default, and the recommended production path everywhere.
  * ``'pallas'`` — the cascade's stage scan as a compiled TPU kernel (one
    launch per switch stage; the inter-stage merges stay in XLA, shared
    with ``'inline'``).  It compiles for a described v5e
    (``tests/test_tpu_compile.py``) and matches ``'inline'`` on a v5e chip
    (``chip_smoke.py``); which of the two is faster is not measured yet.
    Single-epoch and ``analyze_batch`` dispatches only.
  * ``'pallas_interpret'`` — same kernel body via the Pallas interpreter;
    slow, used by tests to validate the kernel on CPU.
  * ``'ref'`` (``analyze_ref``) — numpy float64; the oracle, not jitted.

``fused=False`` keeps the per-switch argsort loop for congestion (latency
and bandwidth still go through the shared helpers).  It is the fallback
for fabrics of more than 31 cascade stages, whose masks do not fit the
fused cascade's 31-bit route word: :class:`EpochAnalyzer` switches to it
on its own there.
"""

from __future__ import annotations

import dataclasses
import heapq
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.annotations import axes
from . import spans
from .aot import AotDispatchCache
from .events import EventStager, MemEvents, PlaneLayout, plane_layout
from .topology import FlatTopology

# f32 contractions at full precision: a TPU's default f32 dot rounds its
# operands to bf16 (2^-9 relative), far outside the oracle tolerances
_HI = jax.lax.Precision.HIGHEST

__all__ = [
    "ChainPlan",
    "DelayBreakdown",
    "DispatchStats",
    "EpochAnalyzer",
    "FineGrainedSimulator",
    "PendingBatch",
    "analyze_any",
    "analyze_ref",
    "bucket_pow2",
    "collect_dispatch",
    "count_dispatch",
    "enqueue_dispatch",
    "plan_cascade",
    "plan_chain",
    "serial_queue_ref",
]


@dataclasses.dataclass(frozen=True)
class DispatchStats:
    """Observability record for the most recent stacked dispatch.

    ``devices_used`` is 1 whenever sharding did not engage; ``shard_rows``
    is the per-device slice of the (padded) leading axis, 0 when unsharded;
    ``padded_fraction`` is the fraction of leading-axis rows that were
    bucket/alignment padding — wasted compute the caller can act on.

    The timing split of the dispatch's wall clock, each part measured by
    the :mod:`~repro.core.spans` span of the same name: ``stage_s`` host
    staging (pack/fill plus the scale and window buffers), ``transfer_s``
    H2D placement, ``compile_s`` compiles (an AOT miss, or backend compile
    seconds seen inside the call — steady state is 0), ``enqueue_s`` the
    call of the executable, ``wait_s`` the block on its outputs and
    ``d2h_s`` their copy to the host.  ``compute_s`` is ``enqueue_s +
    wait_s + d2h_s``: under the engine's overlapped dispatcher only the
    *exposed* compute, the part staging and H2D of the next batch could
    not hide.  ``slots`` counts the dispatched plane's slots (B × N, × K
    where stacked) and ``events`` the real events among them.
    ``donated`` records whether the dispatch reused the staged device
    buffers in place; ``aot_cache_hit`` whether it ran a pre-compiled
    executable.

    ``qos_classes`` is the number of QoS classes the dispatched graph
    decomposed congestion over (1 = the plain FIFO fabric).
    """

    devices_used: int = 1
    shard_rows: int = 0
    rows: int = 0
    padded_fraction: float = 0.0
    stage_s: float = 0.0
    transfer_s: float = 0.0
    compile_s: float = 0.0
    enqueue_s: float = 0.0
    wait_s: float = 0.0
    d2h_s: float = 0.0
    slots: int = 0
    events: int = 0
    donated: bool = False
    aot_cache_hit: bool = False
    qos_classes: int = 1

    @property
    def compute_s(self) -> float:
        return self.enqueue_s + self.wait_s + self.d2h_s


def enqueue_dispatch(fn, *args, **kwargs) -> Tuple[Any, float, float]:
    """Call an executable or jitted function under the ``cxlsim.enqueue``
    span; returns ``(outputs, enqueue_s, compile_s)``.  Backend compile
    seconds seen on this thread during the call (a cold jit) are moved
    out of ``enqueue_s`` into ``compile_s``."""
    c0 = spans.compile_seconds()
    with spans.span("cxlsim.enqueue") as sp:
        out = fn(*args, **kwargs)
    compile_s = spans.compile_seconds() - c0
    return out, sp.seconds - compile_s, compile_s


def collect_dispatch(out) -> Tuple[Any, float, float]:
    """Block on a dispatch's outputs (``cxlsim.wait``), then copy them to
    the host (``cxlsim.d2h``); returns ``(host_outputs, wait_s, d2h_s)``."""
    with spans.span("cxlsim.wait") as wait:
        jax.block_until_ready(out)
    with spans.span("cxlsim.d2h") as d2h:
        host = jax.device_get(out)
    return host, wait.seconds, d2h.seconds


def count_dispatch(slots: int, events: int) -> None:
    """The per-dispatch slot and event counters of :mod:`spans`."""
    spans.count("cxlsim.slots", slots)
    spans.count("cxlsim.events", events)


def _opt_add(a: Optional[np.ndarray], b: Optional[np.ndarray]) -> Optional[np.ndarray]:
    if a is None:
        return None if b is None else np.array(b, copy=True)
    if b is None:
        return np.array(a, copy=True)
    return a + b


@dataclasses.dataclass(frozen=True)
class DelayBreakdown:
    """Per-epoch simulated delays (ns), plus per-component decomposition.

    ``per_pool_latency_ns`` stays indexed by *physical* pool (summed over
    hosts); the optional ``per_host_*`` arrays carry the host-segmented
    decomposition of each delay class for multi-host fabric analyses.  Each
    per-host array sums (within analyzer tolerance) to its fabric total.
    ``per_class_congestion_ns`` decomposes queueing delay by QoS class
    (length ``n_qos_classes``; ``[congestion_ns]`` on plain FIFO fabrics,
    ``None`` when the producing path predates the QoS axis).
    """

    latency_ns: float
    congestion_ns: float
    bandwidth_ns: float
    per_pool_latency_ns: np.ndarray  # [P]
    per_switch_congestion_ns: np.ndarray  # [S]
    per_switch_bandwidth_ns: np.ndarray  # [S]
    per_host_latency_ns: Optional[np.ndarray] = None  # [H]
    per_host_congestion_ns: Optional[np.ndarray] = None  # [H]
    per_host_bandwidth_ns: Optional[np.ndarray] = None  # [H]
    per_class_congestion_ns: Optional[np.ndarray] = None  # [C]

    @property
    def total_ns(self) -> float:
        return self.latency_ns + self.congestion_ns + self.bandwidth_ns

    @property
    def per_host_total_ns(self) -> Optional[np.ndarray]:
        """[H] total delay per host (None when host decomposition is absent)."""
        if self.per_host_latency_ns is None:
            return None
        return (
            self.per_host_latency_ns
            + self.per_host_congestion_ns
            + self.per_host_bandwidth_ns
        )

    def __add__(self, other: "DelayBreakdown") -> "DelayBreakdown":
        return DelayBreakdown(
            self.latency_ns + other.latency_ns,
            self.congestion_ns + other.congestion_ns,
            self.bandwidth_ns + other.bandwidth_ns,
            self.per_pool_latency_ns + other.per_pool_latency_ns,
            self.per_switch_congestion_ns + other.per_switch_congestion_ns,
            self.per_switch_bandwidth_ns + other.per_switch_bandwidth_ns,
            _opt_add(self.per_host_latency_ns, other.per_host_latency_ns),
            _opt_add(self.per_host_congestion_ns, other.per_host_congestion_ns),
            _opt_add(self.per_host_bandwidth_ns, other.per_host_bandwidth_ns),
            _opt_add(
                self.per_class_congestion_ns, other.per_class_congestion_ns
            ),
        )

    @staticmethod
    def zero(n_pools: int, n_switches: int, n_hosts: int = 1) -> "DelayBreakdown":
        return DelayBreakdown(
            0.0,
            0.0,
            0.0,
            np.zeros((n_pools,)),
            np.zeros((n_switches,)),
            np.zeros((n_switches,)),
            np.zeros((n_hosts,)),
            np.zeros((n_hosts,)),
            np.zeros((n_hosts,)),
        )

    @staticmethod
    def from_leaves(leaves: Sequence[Any], k: Optional[int] = None) -> "DelayBreakdown":
        """The breakdown of a dispatch's ten host-side output leaves (the
        field order above), or of row ``k`` of stacked ``[K, ...]`` ones."""
        if k is not None:
            leaves = [x[k] for x in leaves]
        lat, cong, bw, *arrays = leaves
        return DelayBreakdown(
            float(lat), float(cong), float(bw),
            *(np.array(a, np.float64) for a in arrays),
        )


# --------------------------------------------------------------------------- #
# Closed-form serial queue
# --------------------------------------------------------------------------- #


def bucket_pow2(n: int, floor: int = 16) -> int:
    """Next power-of-two bucket >= n (>= floor) — the shared padding rule
    of the epoch analyzer and the scenario suite, so their staged shapes
    land in the same jit compile-cache entries."""
    b = floor
    while b < n:
        b <<= 1
    return b


def serial_queue_ref(arrival_sorted: np.ndarray, stt: float) -> np.ndarray:
    """Start times of a FIFO queue with constant service time ``stt``.

    out_i = max(arrival_i, out_{i-1} + stt), solved as
    out_i = cummax(arrival_i - i*stt) + i*stt.
    """
    if len(arrival_sorted) == 0:
        return arrival_sorted
    idx = np.arange(len(arrival_sorted), dtype=np.float64)
    return np.maximum.accumulate(arrival_sorted - idx * stt) + idx * stt


def _check_reachable(flat: FlatTopology, events: MemEvents) -> None:
    """Reject events whose (host, pool) pair has no row on this fabric.

    Out-of-range host ids would be silently clamped by the jitted gather
    (routing the event through the wrong virtual-pool row and dropping it
    from the host decomposition), and traffic to a pool the issuing host's
    ports exclude has no fabric route — analyzing it would charge latency
    with zero switch traversal.  Both are attach-time mistakes, so both
    raise.
    """
    if events.n == 0:
        return
    hmax = int(events.host.max())
    if hmax >= flat.n_hosts or int(events.host.min()) < 0:
        raise ValueError(
            f"trace carries host id {hmax} but the topology declares "
            f"{flat.n_hosts} host(s) — flatten a Topology(n_hosts=...) that "
            "covers every merged host"
        )
    reach = flat.host_reachable
    if reach is None or reach.all():
        return
    bad = ~reach[events.host, events.pool]
    if bad.any():
        i = int(np.argmax(bad))
        raise ValueError(
            f"event targets pool {flat.pool_names[events.pool[i]]!r} which "
            f"host {int(events.host[i])}'s ports cannot reach "
            f"({int(bad.sum())} such events)"
        )


# --------------------------------------------------------------------------- #
# Reference (numpy, float64) epoch analyzer
# --------------------------------------------------------------------------- #


def analyze_ref(
    flat: FlatTopology,
    events: MemEvents,
    bw_window_ns: float = 10_000.0,
    lat_scale: Optional[np.ndarray] = None,
    n_windows: Optional[int] = None,
    presorted: bool = False,
) -> DelayBreakdown:
    """Vectorized numpy implementation of the three-delay model (oracle).

    Multi-host fabrics: each event is routed through its virtual pool
    ``vp = host * n_pools + pool`` (shared switch rows, private RC rows);
    every delay class additionally comes back host-segmented.  With
    ``n_hosts == 1`` this is numerically identical to the historical
    single-host oracle (``vp == pool`` and the host segment is the total).

    ``lat_scale`` (``[H*P]``, from
    :meth:`~repro.core.cache.DeviceCacheModel.latency_scale`) multiplies
    each event's added latency — the device-cache epoch summary.  Hits
    still traverse the fabric, so congestion/bandwidth are deliberately
    unscaled; an all-ones vector is bitwise identical to passing None.

    ``n_windows`` pins the bandwidth-window count, with overflow clamped
    into the last window — the jitted analyzers' static-window semantics
    (they cannot grow window counts with the post-congestion span).  Pass
    the analyzer's ``n_windows`` together with its effective per-epoch
    ``bw_window_ns`` to compare against the batched/scenario paths at
    float tolerance instead of window-discretization tolerance.  Default
    (None) keeps the historical behavior: enough windows to cover the
    shifted span.

    ``presorted=True`` promises ``events.t_ns`` is already non-decreasing
    (:func:`~repro.core.events.merge_host_traces` output, staged epochs),
    letting the first cascade stage skip its stable argsort — the
    permutation would be the identity.  Later stages re-sort only after a
    stage actually rewrote times.
    """
    P, S, H = flat.n_pools, flat.n_switches, flat.n_hosts
    if events.n == 0:
        return DelayBreakdown.zero(P, S, H)
    _check_reachable(flat, events)

    t = events.t_ns.astype(np.float64).copy()
    pool = events.pool.astype(np.int64)
    host = events.host.astype(np.int64)
    vp = host * P + pool
    nbytes = events.bytes_.astype(np.float64)

    # -- 1. latency delay ------------------------------------------------- #
    per_event_lat = flat.pool_latency_ns[vp] - flat.local_latency_ns
    per_event_lat = np.maximum(per_event_lat, 0.0)
    if lat_scale is not None:
        per_event_lat = per_event_lat * np.asarray(lat_scale, np.float64)[vp]
    per_event_lat = per_event_lat * events.weight
    per_pool_lat = np.bincount(pool, weights=per_event_lat, minlength=P)[:P]
    per_host_lat = np.bincount(host, weights=per_event_lat, minlength=H)[:H]
    latency_ns = float(per_event_lat.sum())

    # -- 2. congestion delay (cascaded serial queues, deepest switch first) - #
    # QoS fabrics (per-switch priority/WFQ disciplines) replace the single
    # FIFO scan with per-level / per-class scans over the same sorted
    # subsequence; plain FIFO fabrics take the historical path bitwise.
    C = int(flat.n_qos_classes)
    qos_on = flat.has_qos
    qcls = np.clip(events.qos.astype(np.int64), 0, C - 1)
    w_table = flat.class_weight_table().astype(np.float64)
    per_switch_cong = np.zeros((S,), np.float64)
    per_host_cong = np.zeros((H,), np.float64)
    per_class_cong = np.zeros((C,), np.float64)
    sorted_now = bool(presorted)
    for s in flat.stage_order():
        stt = float(flat.switch_stt_ns[s])
        mask = flat.route[vp, s] > 0
        if stt <= 0 or not mask.any():
            continue
        if sorted_now:
            sub = np.nonzero(mask)[0]
        else:
            order = np.argsort(t, kind="stable")
            m_sorted = mask[order]
            sub = order[m_sorted]
        disc = (
            flat.switch_discipline[s]
            if qos_on and flat.switch_discipline
            else "fifo"
        )
        if disc == "fifo":
            start = serial_queue_ref(t[sub], stt)
        elif disc == "priority":
            # event of class c takes its start from the FIFO scan over the
            # subsequence of classes <= c (strict priority, FIFO in class)
            q_sub = qcls[sub]
            start = np.empty((len(sub),), np.float64)
            for lvl in range(C):
                lv = q_sub <= lvl
                st_l = serial_queue_ref(t[sub[lv]], stt)
                start[q_sub == lvl] = st_l[q_sub[lv] == lvl]
        else:  # wfq: per-class virtual time with inflated service stt*W/w_c
            q_sub = qcls[sub]
            w_row = w_table[s]
            w_total = float(w_row.sum())
            start = np.empty((len(sub),), np.float64)
            for c in range(C):
                cm = q_sub == c
                start[cm] = serial_queue_ref(
                    t[sub[cm]], stt * w_total / float(w_row[c])
                )
        delay = start - t[sub]
        t[sub] = start
        sorted_now = False  # this stage rewrote times
        per_switch_cong[s] = delay.sum()
        per_host_cong += np.bincount(host[sub], weights=delay, minlength=H)[:H]
        per_class_cong += np.bincount(qcls[sub], weights=delay, minlength=C)[:C]
    congestion_ns = float(per_switch_cong.sum())

    # -- 3. bandwidth delay (windowed, after latency+congestion shifts) ---- #
    # Paper: observed bandwidth is measured after the earlier delays are
    # applied, so windows are computed on the shifted times plus the latency
    # component of each event's pool.
    t_obs = t + per_event_lat
    if n_windows is None:
        span = max(float(t_obs.max()) + 1.0, bw_window_ns)
        n_win = int(np.ceil(span / bw_window_ns))
    else:
        n_win = int(n_windows)
    win = np.minimum((t_obs / bw_window_ns).astype(np.int64), n_win - 1)
    per_switch_bw = np.zeros((S,), np.float64)
    per_host_bw = np.zeros((H,), np.float64)
    for s in range(S):
        bw = float(flat.switch_bandwidth_gbps[s])  # GB/s == bytes/ns
        if bw <= 0:
            continue
        mask = flat.route[vp, s] > 0
        if not mask.any():
            continue
        # per-(window, host) bytes through this switch; the window stretch is
        # attributed to hosts proportionally to their byte share in it
        key = win[mask] * H + host[mask]
        wb_h = np.bincount(key, weights=nbytes[mask], minlength=n_win * H)
        wb_h = wb_h.reshape(n_win, H)
        wbytes = wb_h.sum(axis=1)
        stretch = np.maximum(wbytes / bw - bw_window_ns, 0.0)
        per_switch_bw[s] = stretch.sum()
        share = np.divide(
            wb_h,
            wbytes[:, None],
            out=np.zeros_like(wb_h),
            where=wbytes[:, None] > 0,
        )
        per_host_bw += (stretch[:, None] * share).sum(axis=0)
    bandwidth_ns = float(per_switch_bw.sum())

    return DelayBreakdown(
        latency_ns,
        congestion_ns,
        bandwidth_ns,
        per_pool_lat,
        per_switch_cong,
        per_switch_bw,
        per_host_lat,
        per_host_cong,
        per_host_bw,
        per_class_cong,
    )


# --------------------------------------------------------------------------- #
# JAX epoch analyzer (production path)
# --------------------------------------------------------------------------- #


def plan_cascade(flat: FlatTopology):
    """Derive the fused cascade's static route bits and merge plan.

    The cascade keeps the event array sorted by current time.  A stage's
    scan only needs *its own masked events* to appear in non-decreasing
    order — and a subsequence of a sorted run is sorted.  Simulating the run
    partition of the array (runs split as stages rewrite their events) tells
    us, per stage, which previously-independent sorted runs its mask spans;
    only those need merging, piecewise, before the scan.  Chains (every pool
    behind the deepest switch) need zero merges; the paper's Figure 1 needs
    exactly one.  Falls back to the conservative merge-every-stage plan when
    the needed masks exceed the 31 bits of an int32 route word.

    Returns ``(bits_pool [V] int32, merge_plan | None, stage_order tuple)``
    where bit ``k`` of an event's route word marks membership in the pool
    set ``k`` (the first ``S`` bits are the stage masks, in stage order).
    Rows are **virtual pools** — one per (host, pool) pair — so a shared
    switch's stage mask spans every host that routes through it while each
    host's RC stage covers only that host's rows; with ``n_hosts == 1``
    virtual and physical pools coincide.
    """
    route = np.asarray(flat.route)
    P = route.shape[0]  # virtual (host, pool) rows
    stage_order = tuple(int(s) for s in flat.stage_order())
    masks = [
        frozenset(int(p) for p in np.nonzero(route[:, s] > 0)[0]) for s in stage_order
    ]
    # pool index P is a pseudo-pool for padded/invalid events: routed nowhere
    all_ids = frozenset(range(P + 1))

    sets: List[frozenset] = list(masks)  # bit k <-> sets[k]; first S are stages

    def bit_of(pool_set: frozenset) -> int:
        for k, existing in enumerate(sets):
            if existing == pool_set:
                return k
        sets.append(pool_set)
        return len(sets) - 1

    runs = [all_ids]
    plan: List[Tuple[Tuple[int, Optional[int]], ...]] = []
    for mask in masks:
        hits = [r & mask for r in runs if r & mask]
        ops: List[Tuple[int, Optional[int]]] = []
        if len(hits) > 1:
            # fold the runs the mask spans into one sorted subsequence; the
            # local pool and the padding pseudo-pool are never routed, so a
            # whole-array (within=None) merge can't arise here — it belongs
            # to the conservative fallback plan only
            acc = hits[0]
            for piece in hits[1:]:
                within = acc | piece
                ops.append((bit_of(piece), bit_of(within)))
                acc = within
            runs = [mask] + [r - mask for r in runs if r - mask]
        else:
            runs = [p for r in runs for p in (r & mask, r - mask) if p]
        plan.append(tuple(ops))

    if len(sets) > 31:  # int32 route word exhausted: conservative plan
        sets = list(masks)
        merge_plan = None
    else:
        merge_plan = tuple(plan)
    if len(sets) > 31:
        raise ValueError(
            f"{len(sets)} cascade stages exceed the 31-bit route word "
            f"(every switch plus one RC pseudo-switch per host is a stage; "
            f"this topology has {flat.n_hosts} hosts) — use "
            f"EpochAnalyzer, which falls back to the unfused path here"
        )
    bits_pool = np.zeros((P,), np.int32)
    for k, pool_set in enumerate(sets):
        for p in pool_set:
            if p < P:
                bits_pool[p] |= np.int32(1) << k
    return bits_pool, merge_plan, stage_order


@dataclasses.dataclass(frozen=True)
class ChainPlan:
    """Static routing data for the device-resident pipeline dispatch.

    ``enter_stage[v]`` is the cascade stage position at which events of
    virtual pool ``v`` first enter the fabric (-1 = local, never routed).
    Valid only for *chain* topologies: single host, and every stage mask a
    subset of the next in stage order (deepest-first) — then an event
    entering at position ``p`` traverses exactly stages ``p..S-1``, which
    is what lets :func:`repro.kernels.ref.chain_cascade` process a compact
    growing suffix instead of the full padded plane.
    """

    enter_stage: np.ndarray  # [V] int32
    stage_order: Tuple[int, ...]


def plan_chain(flat: FlatTopology) -> Optional[ChainPlan]:
    """Chain-eligibility check; None when the compact cascade cannot apply.

    Eligible: ``n_hosts == 1`` and nested stage masks (``M_p ⊆ M_{p+1}``
    in stage order).  Every linear expander chain — the paper's Figure 1
    shape, two-tier trees with one leaf switch per level on the path, and
    the deep ``chained_topology`` — qualifies; sibling switches at the
    same depth (disjoint masks) do not, and those dispatches fall back to
    the AOT-compiled full-plane path.
    """
    if flat.n_hosts != 1:
        return None
    route = np.asarray(flat.route)
    stage_order = tuple(int(s) for s in flat.stage_order())
    masks = [route[:, s] > 0 for s in stage_order]
    for p in range(len(masks) - 1):
        if np.any(masks[p] & ~masks[p + 1]):
            return None
    enter = np.full((route.shape[0],), -1, np.int32)
    for p in range(len(masks) - 1, -1, -1):
        enter[masks[p]] = p
    return ChainPlan(enter_stage=enter, stage_order=stage_order)


def unpack_plane(words: jnp.ndarray, layout: PlaneLayout) -> Dict[str, jnp.ndarray]:
    """The staged columns of one word plane, restored on device: each is
    a static slice at its :func:`~repro.core.events.plane_layout` offset,
    float columns bitcast back from their int32 words (a transposed one
    transposed back) and ``valid`` compared with 0 — the bits the host
    staged, unchanged."""
    cols = {}
    for name, off, shape, kind in layout:
        w = jax.lax.slice(words, (off,), (off + math.prod(shape),))
        if kind == "t":
            w = jax.lax.bitcast_convert_type(w.reshape(shape[::-1]), jnp.float32).T
        elif kind == "f":
            w = jax.lax.bitcast_convert_type(w.reshape(shape), jnp.float32)
        elif kind == "b":
            w = w.reshape(shape) != 0
        else:
            w = w.reshape(shape)
        cols[name] = w
    return cols


def _price_latency(
    vp: jnp.ndarray,  # [N] i32 virtual pool (host * P + pool)
    pool: jnp.ndarray,  # [N] i32 physical pool
    host: Optional[jnp.ndarray],  # [N] i32 (unused when n_hosts == 1)
    weight: jnp.ndarray,  # [N] statistical multiplicity
    valid: jnp.ndarray,  # [N] bool
    lat_scale: jnp.ndarray,  # [V] device-cache latency scale (ones: no cache)
    pool_latency_ns: jnp.ndarray,  # [V]
    local_latency_ns: jnp.ndarray,  # []
    n_hosts: int,  # static
):
    """One epoch's latency delay: ``(per_event_lat [N], latency [],
    per_pool_lat [P], per_host_lat [H])``.  Each event pays its pool's
    latency above local DRAM, times its device-cache scale (hits are
    charged at device-DRAM latency, :mod:`~repro.core.cache`; ones give
    the no-cache graph bitwise) and its weight."""
    P = pool_latency_ns.shape[0] // n_hosts
    per_event_lat = (
        jnp.maximum(pool_latency_ns[vp] - local_latency_ns, 0.0)
        * lat_scale[vp]
        * weight
    )
    per_event_lat = jnp.where(valid, per_event_lat, 0.0)
    f32 = per_event_lat.dtype
    # one-hot contraction: XLA CPU scatter-add (segment_sum) costs ~10x
    # more than an [N, P] einsum at pool counts this small
    pool_onehot = (pool[:, None] == jnp.arange(P, dtype=pool.dtype)).astype(f32)
    per_pool_lat = jnp.einsum("n,np->p", per_event_lat, pool_onehot, precision=_HI)
    latency = per_event_lat.sum()
    if n_hosts == 1:
        per_host_lat = latency[None]
    else:
        host_onehot = (host[:, None] == jnp.arange(n_hosts, dtype=host.dtype)).astype(f32)
        per_host_lat = jnp.einsum("n,nh->h", per_event_lat, host_onehot, precision=_HI)
    return per_event_lat, latency, per_pool_lat, per_host_lat


def _price_bandwidth(
    t_fin: jnp.ndarray,  # [N] post-queue times
    lat_e: jnp.ndarray,  # [N] per-event latency
    vp_e: jnp.ndarray,  # [N] i32 virtual pool
    nbytes_e: jnp.ndarray,  # [N] payload bytes
    valid_e: jnp.ndarray,  # [N] bool
    bw_window_ns: jnp.ndarray,  # [] window length
    route: jnp.ndarray,  # [V, S]
    switch_bw: jnp.ndarray,  # [S] bytes/ns
    n_windows: int,  # static
    n_hosts: int,  # static
):
    """One epoch's bandwidth delay: ``(bandwidth [], per_switch_bw [S],
    per_host_bw [H])``.  The five event arrays may come in any order (slot
    order after a cascade, input order, or a compact suffix): each event
    lands in the window of its observed time (post-queue time plus
    latency, overflow clamped into the last window), one segment sum over
    (window, virtual pool) and a ``[W, V] @ [V, S]`` product give each
    switch's bytes per window, and a window whose bytes exceed bandwidth
    × window is stretched to bytes / bandwidth.  A host's share of a
    window's stretch is its share of the window's bytes."""
    V, S = route.shape
    P = V // n_hosts
    t_obs = jnp.where(valid_e, t_fin + lat_e, 0.0)
    win = jnp.minimum((t_obs / bw_window_ns).astype(jnp.int32), n_windows - 1)
    win = jnp.where(valid_e, win, n_windows - 1)
    key = win * V + vp_e
    wp = jax.ops.segment_sum(
        jnp.where(valid_e, nbytes_e, 0.0), key, num_segments=n_windows * V
    ).reshape(n_windows, V)
    if n_hosts == 1:
        wbytes = jnp.matmul(wp, route, precision=_HI)  # [W, S]
    else:
        wph = wp.reshape(n_windows, n_hosts, P)
        route_h = route.reshape(n_hosts, P, S)
        wbytes_h = jnp.einsum(
            "whp,hps->whs", wph, route_h, precision=_HI,
        )  # [W, H, S]
        wbytes = wbytes_h.sum(axis=1)
    # bw <= 0 means an unconstrained component (analyze_ref skips it);
    # unguarded 0/0 windows would poison totals with NaN
    bw_safe = jnp.where(switch_bw > 0, switch_bw, 1.0)
    stretch = jnp.maximum(wbytes / bw_safe[None, :] - bw_window_ns, 0.0)
    stretch = jnp.where(switch_bw[None, :] > 0, stretch, 0.0)
    per_switch_bw = stretch.sum(axis=0)
    bandwidth = per_switch_bw.sum()
    if n_hosts == 1:
        per_host_bw = bandwidth[None]
    else:
        denom = jnp.maximum(wbytes, jnp.asarray(1e-30, wbytes.dtype))
        per_host_bw = jnp.einsum("ws,whs->h", stretch / denom, wbytes_h, precision=_HI)
    return bandwidth, per_switch_bw, per_host_bw


def _congestion_leaves(psd, stage_arr, n_switches: int, n_hosts: int, qos_on: bool):
    """A cascade's per-stage queueing delays as breakdown leaves:
    ``(congestion [], per_switch_cong [S], per_host_cong [H],
    per_class_cong [C])``.  ``psd`` is ``[stages, H, C]`` from the QoS
    cascade, else ``[stages, H]`` (``[stages]`` on one host); a FIFO
    fabric's per-class leaf is the degenerate ``[congestion]``."""
    zeros = jnp.zeros((n_switches,), psd.dtype)
    if qos_on:
        per_switch_cong = zeros.at[stage_arr].set(psd.sum(axis=(1, 2)))
        per_class_cong = psd.sum(axis=(0, 1))
        congestion = per_switch_cong.sum()
        per_host_cong = congestion[None] if n_hosts == 1 else psd.sum(axis=(0, 2))
        return congestion, per_switch_cong, per_host_cong, per_class_cong
    if n_hosts == 1:
        per_switch_cong = zeros.at[stage_arr].set(psd)
        congestion = per_switch_cong.sum()
        per_host_cong = congestion[None]
    else:
        per_switch_cong = zeros.at[stage_arr].set(psd.sum(axis=1))
        per_host_cong = psd.sum(axis=0)
        congestion = per_switch_cong.sum()
    return congestion, per_switch_cong, per_host_cong, congestion[None]


@axes("P", "V", "", "V,S", "S", "S")
def _analyze_pipeline_jax(
    words: jnp.ndarray,  # [P] i32 staged word plane (chain layout) — DONATED
    pool_latency_ns: jnp.ndarray,  # [V]
    local_latency_ns: jnp.ndarray,  # []
    route: jnp.ndarray,  # [V, S]
    switch_stt_ns: jnp.ndarray,  # [S]
    switch_bw: jnp.ndarray,  # [S]
    layout: PlaneLayout,  # static: the plane's columns
    stage_order: Tuple[int, ...],  # static
    seg_caps: Tuple[int, ...],  # static packed segment capacities
    n_windows: int,  # static
):
    """Device-resident single-host chain dispatch (the pipeline hot path).

    ``words`` carries every staged column in one transfer: the per-stage
    packed sorted runs ``t_pack`` (+inf pads) and ``idx_pack`` (positions
    into the staged row, -1 pads), the full-plane ``pool``, ``bytes``,
    ``weight`` and ``valid``, then ``bw_window`` and ``scale``
    (:func:`unpack_plane`).  The merge of per-stage sorted runs into one
    fabric timeline and every serial-queue scan happen **inside this
    graph** (:func:`repro.kernels.ref.chain_cascade` over a compact suffix
    that only ever holds routed events), so staging performed zero host
    argsorts.  Bandwidth windows come straight off the compact array:
    local-DRAM route rows are all zero, so unrouted events could only ever
    contribute zero bytes to every switch — skipping them is exact, and
    ``W`` (sum of per-stage capacity buckets) is typically much smaller
    than padded ``N``.  Latency stays a full-plane gather (it needs no
    times).  Returns the ten breakdown leaves of :func:`_analyze_jax`
    (this path is FIFO-only, so the per-class leaf is the degenerate
    ``[congestion]``) plus the plane with the final ``(t, idx)`` runs
    written over the packed ones — shaped and typed like the donated
    input, so XLA serves it from the donated buffer and steady-state
    dispatch allocates nothing on device.
    """
    c = unpack_plane(words, layout)
    t_pack, idx_pack = c["t_pack"], c["idx_pack"]
    S = switch_stt_ns.shape[0]
    stage_arr = jnp.asarray(stage_order, jnp.int32)
    stts = switch_stt_ns[stage_arr]

    def one(tp1, ip1, pool1, nbytes1, weight1, valid1, bww1, scale1):
        per_event_lat, latency, per_pool_lat, _ = _price_latency(
            pool1, pool1, None, weight1, valid1, scale1, pool_latency_ns,
            local_latency_ns, n_hosts=1,
        )

        # congestion: compact suffix cascade (merge + scan fused)
        from repro.kernels import ops as kops  # deferred: avoid cycles

        t_fin, idx_fin, dsums = kops.chain_cascade(tp1, ip1, stts, seg_caps)
        congestion, per_switch_cong, _, _ = _congestion_leaves(
            dsums, stage_arr, S, n_hosts=1, qos_on=False
        )

        # bandwidth from the compact array: payloads gathered through the
        # staged-row positions the cascade carried along
        real = idx_fin >= 0
        safe = jnp.maximum(idx_fin, 0)
        lat_e = jnp.take(per_event_lat, safe)
        vp_e = jnp.take(pool1, safe)
        nbytes_e = jnp.take(nbytes1, safe)
        bandwidth, per_switch_bw, _ = _price_bandwidth(
            t_fin, lat_e, vp_e, nbytes_e, real, bww1, route, switch_bw,
            n_windows, n_hosts=1,
        )

        return (
            latency, congestion, bandwidth,
            per_pool_lat, per_switch_cong, per_switch_bw,
            latency[None], congestion[None], bandwidth[None],
            congestion[None],
            t_fin, idx_fin,
        )

    outs = jax.vmap(one)(
        t_pack, idx_pack, c["pool"], c["bytes"], c["weight"], c["valid"],
        c["bw_window"], c["scale"],
    )
    summed = tuple(x.sum(axis=0) for x in outs[:10])
    off = {name: o for name, o, _, _ in layout}
    t_fin = jax.lax.bitcast_convert_type(outs[10], jnp.int32).ravel()
    words = jax.lax.dynamic_update_slice(words, t_fin, (off["t_pack"],))
    words = jax.lax.dynamic_update_slice(words, outs[11].ravel(), (off["idx_pack"],))
    return summed + (words,)


@axes(
    "N", "N", "N", "N", "N", "N", "N", "V", "V", "V", "",
    "V,S", "S", "S", "S", "S,C",
    bw_window_ns="",
)
def _analyze_jax(
    t: jnp.ndarray,  # [N] f32 epoch-relative ns, TIME-SORTED (padded: 0, last)
    pool: jnp.ndarray,  # [N] i32 (padded entries: 0)
    nbytes: jnp.ndarray,  # [N] f32 (padded entries: 0)
    weight: jnp.ndarray,  # [N] f32 statistical multiplicity
    host: jnp.ndarray,  # [N] i32 attached-host index (padded entries: 0)
    qos: jnp.ndarray,  # [N] i32 QoS class ids (padded entries: 0)
    valid: jnp.ndarray,  # [N] bool
    lat_scale: jnp.ndarray,  # [V] device-cache latency scale (ones: no cache)
    bits_table: jnp.ndarray,  # [V] i32 per-virtual-pool route word (plan_cascade)
    pool_latency_ns: jnp.ndarray,  # [V] (V = n_hosts * n_pools)
    local_latency_ns: jnp.ndarray,  # []
    route: jnp.ndarray,  # [V, S]
    switch_stt_ns: jnp.ndarray,  # [S]
    switch_bw: jnp.ndarray,  # [S] bytes/ns
    disc_code: jnp.ndarray,  # [S] i32 per-switch discipline codes
    class_weights: jnp.ndarray,  # [S, C] f32 per-switch class weights
    stage_order: Tuple[int, ...],  # static
    n_windows: int,  # static
    n_hosts: int,  # static
    bw_window_ns: jnp.ndarray,  # []
    impl: str = "inline",  # 'inline' | 'pallas' | 'pallas_interpret'
    fused: bool = True,  # False: per-stage argsort loop (beyond 31 stages)
    merge_plan=None,  # static merge schedule from plan_cascade (fused only)
    qos_on: bool = False,  # static: route congestion through the QoS cascade
):
    """One epoch's three-delay analysis; the fused path (default) assumes
    the events were staged time-sorted with padding at the tail (the
    :class:`~repro.core.events.EventStager` contract — the epoch's one
    stable sort happens host-side during staging, and only when the trace
    isn't already sorted).

    Multi-host fabrics (``n_hosts > 1``, a static branch): every lookup is
    keyed by the virtual pool ``vp = host * P + pool`` so shared switches
    see the merged timeline while per-host RCs stay private, and each delay
    class is additionally host-segmented on device.  The ``n_hosts == 1``
    graph is exactly the historical single-host one.

    ``qos_on`` (static) swaps the FIFO cascade for the data-driven QoS
    cascade (:func:`repro.kernels.ref.qos_cascade_dyn`): per-switch
    disciplines/weights become runtime operands and a tenth output leaf
    decomposes congestion by QoS class.  ``qos_on=False`` leaves the
    congestion graph bitwise identical to the historical one (``qos``,
    ``disc_code`` and ``class_weights`` go unused) with the degenerate
    ``[congestion]`` tenth leaf.
    """
    V = pool_latency_ns.shape[0]
    P = V // n_hosts  # physical pools
    S = switch_stt_ns.shape[0]
    f32 = t.dtype
    vp = pool if n_hosts == 1 else host * P + pool
    if qos_on and not fused:
        raise ValueError(
            "QoS disciplines require the fused cascade (fused=True)"
        )

    per_event_lat, latency, per_pool_lat, per_host_lat = _price_latency(
        vp, pool, host, weight, valid, lat_scale, pool_latency_ns,
        local_latency_ns, n_hosts,
    )

    big = jnp.asarray(jnp.finfo(f32).max / 4, f32)
    t_cur = jnp.where(valid, t, big)
    # bandwidth sees the events in the cascade's final slot order: input
    # order unless the cascade merged runs (no merges: skip the gathers)
    lat_e, vp_e, nbytes_e, valid_e = per_event_lat, vp, nbytes, valid

    if fused:
        # -- congestion: fused single-sort cascade -------------------------- #
        from repro.kernels import ops as kops  # deferred: avoid cycles

        stage_arr = jnp.asarray(stage_order, jnp.int32)
        ev_bits = jnp.where(valid, bits_table[vp], 0)
        if qos_on:
            qos_e = jnp.where(valid, qos, 0)
            t_fin, slot_idx, psd = kops.qos_congestion_cascade(
                t_cur,
                ev_bits,
                switch_stt_ns[stage_arr],
                qos_e,
                disc_code[stage_arr],
                class_weights[stage_arr],
                impl="ref" if impl == "inline" else impl,
                hosts=None if n_hosts == 1 else host,
                n_hosts=n_hosts,
            )
            # the QoS cascade's fold is data-driven (always runs), so slot
            # order never matches input order
            has_merges = True
        else:
            t_fin, slot_idx, psd = kops.congestion_cascade(
                t_cur,
                ev_bits,
                switch_stt_ns[stage_arr],
                impl="ref" if impl == "inline" else impl,
                merge_plan=merge_plan,
                hosts=None if n_hosts == 1 else host,
                n_hosts=n_hosts,
            )
            has_merges = merge_plan is None or any(len(ops) for ops in merge_plan)
        congestion, per_switch_cong, per_host_cong, per_class_cong = (
            _congestion_leaves(psd, stage_arr, S, n_hosts, qos_on)
        )
        if has_merges:
            # gather payloads through the cascade's permutation (slot k
            # held input event slot_idx[k])
            lat_e = per_event_lat[slot_idx]
            vp_e, nbytes_e = vp[slot_idx], nbytes[slot_idx]
            valid_e = valid[slot_idx]
    else:
        # -- congestion: per-stage argsort loop (beyond 31 stages) ---------- #
        per_switch_list = [jnp.zeros((), f32)] * S
        per_host_cong = jnp.zeros((n_hosts,), f32)
        for s in stage_order:
            stt = switch_stt_ns[s]
            mask = (route[vp, s] > 0) & valid
            order = jnp.argsort(t_cur, stable=True)
            t_sorted = t_cur[order]
            m_sorted = mask[order]
            if impl == "inline":
                rank = jnp.cumsum(m_sorted.astype(jnp.int32)) - 1
                rankf = rank.astype(f32)
                g = jnp.where(m_sorted, t_sorted - stt * rankf, -big)
                f = jax.lax.cummax(g)
                start = jnp.where(m_sorted, f + stt * rankf, t_sorted)
                delay = jnp.where(m_sorted, start - t_sorted, 0.0)
            else:
                from repro.kernels import ops as kops  # deferred: avoid cycles

                start, delay = kops.congestion_queue(t_sorted, m_sorted, stt, impl=impl)
            t_cur = t_cur.at[order].set(jnp.where(m_sorted, start, t_sorted))
            per_switch_list[s] = delay.sum()
            if n_hosts > 1:
                per_host_cong = per_host_cong + jax.ops.segment_sum(
                    delay, host[order], num_segments=n_hosts
                )
        per_switch_cong = jnp.stack(per_switch_list)
        congestion = per_switch_cong.sum()
        per_class_cong = congestion[None]
        if n_hosts == 1:
            per_host_cong = congestion[None]
        t_fin = t_cur

    bandwidth, per_switch_bw, per_host_bw = _price_bandwidth(
        t_fin, lat_e, vp_e, nbytes_e, valid_e, bw_window_ns, route, switch_bw,
        n_windows, n_hosts,
    )
    return (
        latency, congestion, bandwidth,
        per_pool_lat, per_switch_cong, per_switch_bw,
        per_host_lat, per_host_cong, per_host_bw,
        per_class_cong,
    )


@axes(
    "B,N", "B,N", "B,N", "B,N", "B,N", "B,N", "B,N", "B", "B,V",
    "V", "V", "", "V,S", "S", "S", "S", "S,C",
)
def _analyze_batch_jax(
    t: jnp.ndarray,  # [B, N]
    pool: jnp.ndarray,  # [B, N]
    nbytes: jnp.ndarray,  # [B, N]
    weight: jnp.ndarray,  # [B, N]
    host: jnp.ndarray,  # [B, N]
    qos: jnp.ndarray,  # [B, N]
    valid: jnp.ndarray,  # [B, N]
    bw_window_ns: jnp.ndarray,  # [B] per-epoch window length
    lat_scale: jnp.ndarray,  # [B, V] per-epoch device-cache latency scale
    bits_table: jnp.ndarray,  # [V]
    pool_latency_ns: jnp.ndarray,
    local_latency_ns: jnp.ndarray,
    route: jnp.ndarray,
    switch_stt_ns: jnp.ndarray,
    switch_bw: jnp.ndarray,
    disc_code: jnp.ndarray,  # [S]
    class_weights: jnp.ndarray,  # [S, C]
    stage_order: Tuple[int, ...],
    n_windows: int,
    n_hosts: int,
    impl: str = "inline",
    fused: bool = True,
    merge_plan=None,
    qos_on: bool = False,
):
    """B stacked epochs -> breakdown totals, accumulated on device.

    The inline path vmaps the whole per-epoch analysis (one batched
    cascade); the Pallas kernel runs epochs sequentially inside one traced
    ``lax.map`` dispatch.  Either way the host sees a single call and a
    single small transfer per batch.
    """

    def one(t1, pool1, nbytes1, weight1, host1, qos1, valid1, bww1, scale1):
        return _analyze_jax(
            t1, pool1, nbytes1, weight1, host1, qos1, valid1, scale1, bits_table,
            pool_latency_ns, local_latency_ns, route, switch_stt_ns, switch_bw,
            disc_code, class_weights,
            stage_order=stage_order, n_windows=n_windows, n_hosts=n_hosts,
            bw_window_ns=bww1, impl=impl, fused=fused, merge_plan=merge_plan,
            qos_on=qos_on,
        )

    xs = (t, pool, nbytes, weight, host, qos, valid, bw_window_ns, lat_scale)
    if impl in ("pallas", "pallas_interpret"):
        outs = jax.lax.map(lambda args: one(*args), xs)
    else:
        outs = jax.vmap(one)(*xs)
    return jax.tree.map(lambda x: x.sum(axis=0), outs)


@axes("P", "V", "V", "", "V,S", "S", "S", "S", "S,C")
def _analyze_words_jax(
    words: jnp.ndarray,  # [P] i32 staged word plane (full-plane layout)
    bits_table: jnp.ndarray,  # [V]
    pool_latency_ns: jnp.ndarray,
    local_latency_ns: jnp.ndarray,
    route: jnp.ndarray,
    switch_stt_ns: jnp.ndarray,
    switch_bw: jnp.ndarray,
    disc_code: jnp.ndarray,  # [S]
    class_weights: jnp.ndarray,  # [S, C]
    layout: PlaneLayout,  # static: the plane's columns
    stage_order: Tuple[int, ...],
    n_windows: int,
    n_hosts: int,
    impl: str = "inline",
    fused: bool = True,
    merge_plan=None,
    qos_on: bool = False,
):
    """:func:`_analyze_batch_jax` on one staged word plane: the dispatch
    of :meth:`EpochAnalyzer.launch_batch` off the chain path, whose nine
    columns cross to the device in one transfer (:func:`unpack_plane`)."""
    c = unpack_plane(words, layout)
    return _analyze_batch_jax(
        c["t"], c["pool"], c["bytes"], c["weight"], c["host"], c["qos"],
        c["valid"], c["bw_window"], c["scale"], bits_table, pool_latency_ns,
        local_latency_ns, route, switch_stt_ns, switch_bw, disc_code,
        class_weights, stage_order=stage_order, n_windows=n_windows,
        n_hosts=n_hosts, impl=impl, fused=fused, merge_plan=merge_plan,
        qos_on=qos_on,
    )


@axes(
    "K,B,N", "K,B,N", "K,B,N", "K,B,N", "K,B,N", "K,B,N", "K,B,N",
    "K,B", "K,B,V", "V", "K,V", "K", "V,S", "K,S", "K,S", "K,S", "K,S,C",
)
def _analyze_stack_jax(
    t: jnp.ndarray,  # [K, B, N] K stacked epoch batches
    pool: jnp.ndarray,  # [K, B, N]
    nbytes: jnp.ndarray,  # [K, B, N]
    weight: jnp.ndarray,  # [K, B, N]
    host: jnp.ndarray,  # [K, B, N]
    qos: jnp.ndarray,  # [K, B, N]
    valid: jnp.ndarray,  # [K, B, N]
    bw_window_ns: jnp.ndarray,  # [K, B]
    lat_scale: jnp.ndarray,  # [K, B, V]
    bits_table: jnp.ndarray,  # [V] shared (one fabric structure)
    pool_latency_ns: jnp.ndarray,  # [K, V] per-row numeric leaves
    local_latency_ns: jnp.ndarray,  # [K]
    route: jnp.ndarray,  # [V, S] shared (structure)
    switch_stt_ns: jnp.ndarray,  # [K, S]
    switch_bw: jnp.ndarray,  # [K, S]
    disc_code: jnp.ndarray,  # [K, S] per-row QoS policies (numeric leaves)
    class_weights: jnp.ndarray,  # [K, S, C]
    stage_order: Tuple[int, ...],
    n_windows: int,
    n_hosts: int,
    impl: str = "inline",
    fused: bool = True,
    merge_plan=None,
    qos_on: bool = False,
):
    """K epoch batches × B epochs in one dispatch, per-row totals on device.

    The stacked form of :func:`_analyze_batch_jax`: the leading axis is a
    coalesced engine session (:meth:`EpochAnalyzer.analyze_batch_multi`,
    its analyzer's one row of numeric leaves broadcast to K) or a fleet
    rack (:class:`~repro.core.fleet.FleetSim`, one row of
    :class:`~repro.core.topology.FlatTopologyStack` leaves each).  Rows
    share one structure, so the route matrix, route-word table and
    cascade merge plan stay static and every K compiles once; each row's
    epochs are reduced on device, and sharding the leading axis over a
    ``('data',)`` mesh keeps the host transfer at one ``[K, ...]`` tuple.
    """

    def one(t1, pool1, nbytes1, weight1, host1, qos1, valid1, bww1, scale1,
            plat1, llat1, stt1, sbw1, disc1, cw1):
        return _analyze_batch_jax(
            t1, pool1, nbytes1, weight1, host1, qos1, valid1, bww1, scale1,
            bits_table, plat1, llat1, route, stt1, sbw1, disc1, cw1,
            stage_order=stage_order, n_windows=n_windows, n_hosts=n_hosts,
            impl=impl, fused=fused, merge_plan=merge_plan, qos_on=qos_on,
        )

    return jax.vmap(one)(
        t, pool, nbytes, weight, host, qos, valid, bw_window_ns, lat_scale,
        pool_latency_ns, local_latency_ns, switch_stt_ns, switch_bw,
        disc_code, class_weights,
    )


@axes(
    "G,B,N", "G,B,N", "G,B,N", "G,B,N", "G,B,N", "G,B,N", "G,B",
    "U", "U,R", "U,S", "U,S", "U,S,C", "R", "K", "K", "K,R", "K,B,V",
    "K,V", "K", "K,S", "V", "V,S",
)
def _analyze_sweep_jax(
    t: jnp.ndarray,  # [G, B, N] f32 sorted epoch times per granularity group
    nbytes: jnp.ndarray,  # [G, B, N]
    weight: jnp.ndarray,  # [G, B, N]
    host: jnp.ndarray,  # [G, B, N]
    valid: jnp.ndarray,  # [G, B, N]
    region: jnp.ndarray,  # [G, B, N] i32 region ids (skeleton payload)
    bw_window: jnp.ndarray,  # [G, B] per-epoch window lengths
    cas_group: jnp.ndarray,  # [U] i32 cascade -> skeleton group
    cas_assign: jnp.ndarray,  # [U, R] i32 placement rows of unique cascades
    cas_stt: jnp.ndarray,  # [U, S] stt rows of unique cascades
    cas_disc: jnp.ndarray,  # [U, S] i32 discipline rows of unique cascades
    cas_weights: jnp.ndarray,  # [U, S, C] class-weight rows of unique cascades
    qos_of_region: jnp.ndarray,  # [R] i32 QoS class per workload region
    group_of: jnp.ndarray,  # [K] i32 scenario -> skeleton group
    cascade_of: jnp.ndarray,  # [K] i32 scenario -> unique cascade
    assign: jnp.ndarray,  # [K, R] i32 placement matrix
    lat_scale: jnp.ndarray,  # [K, B, V] per-scenario device-cache scales
    pool_latency_ns: jnp.ndarray,  # [K, V] stacked topology leaves
    local_latency_ns: jnp.ndarray,  # [K]
    switch_bw: jnp.ndarray,  # [K, S]
    bits_table: jnp.ndarray,  # [V] shared (structure)
    route: jnp.ndarray,  # [V, S] shared (structure)
    stage_order: Tuple[int, ...],  # static
    n_windows: int,  # static
    n_hosts: int,  # static
    merge_plan=None,  # static
    qos_on: bool = False,  # static: arbitrate cascades by QoS discipline
):
    """K scenarios × B epochs in ONE dispatch, per-scenario totals on device.

    Two phases, both inside the same jitted graph:

    1. **U unique cascades.**  Congestion — and the post-queue times the
       bandwidth windows are computed on — depends only on (trace skeleton,
       per-event route bits, per-stage STT), i.e. on the scenario's
       granularity group, placement row and STT row.  Latency and
       bandwidth-capacity overrides, cache configs, and policy duplicates
       all collapse onto the same cascade, so the expensive fused scan
       (and its inter-stage merges) runs once per *unique* triple: a
       256-scenario latency×policy sweep typically runs a handful of
       cascades.  The host computes the dedup (``cascade_of``); worst case
       ``U == K`` and nothing is lost.
    2. **K scenario reductions.**  Each scenario gathers its cascade's
       slot-ordered outputs, derives per-event pools **on device** from its
       row of the placement matrix (the cheap pool-gather), and prices
       each epoch's latency and bandwidth (:func:`_price_latency`,
       :func:`_price_bandwidth`) against its row of the stacked topology
       leaves (+ cache scale) on the shared post-congestion times.  Cheap
       elementwise/gather/segment-sum work only — no sorts, no scans.

    Structure — route matrix, route-word table, stage order, merge plan —
    is shared by construction (:class:`~repro.core.topology.
    FlatTopologyStack`), so the whole stack compiles once regardless of K,
    and per-scenario breakdowns are reduced over epochs on device: the
    host sees one ``[K, ...]`` transfer for the entire sweep.
    """
    from repro.kernels import ops as kops  # deferred: avoid cycles

    f32 = t.dtype
    V = pool_latency_ns.shape[1]
    P = V // n_hosts
    S = switch_bw.shape[1]
    stage_arr = jnp.asarray(stage_order, jnp.int32)
    big = jnp.asarray(jnp.finfo(f32).max / 4, f32)
    # the QoS cascade's inter-stage fold is data-driven (always runs)
    has_merges = qos_on or merge_plan is None or any(
        len(ops) for ops in merge_plan
    )

    # -- phase 1: the U unique congestion cascades -------------------------- #
    def one_cascade(g, assign_u, stt_u, disc_u, cw_u):
        tg, vg, rg, hg = t[g], valid[g], region[g], host[g]
        pool_u = jnp.where(vg, assign_u[rg], 0)
        vp_u = pool_u if n_hosts == 1 else hg * P + pool_u
        bits_u = jnp.where(vg, bits_table[vp_u], 0)
        # QoS class rides the region skeleton: derived on device per event
        qg = jnp.where(vg, qos_of_region[rg], 0)

        def per_epoch(t1, bits1, v1, h1, q1):
            t_cur = jnp.where(v1, t1, big)
            if qos_on:
                return kops.qos_congestion_cascade(
                    t_cur, bits1, stt_u[stage_arr], q1,
                    disc_u[stage_arr], cw_u[stage_arr], impl="ref",
                    hosts=None if n_hosts == 1 else h1, n_hosts=n_hosts,
                )
            return kops.congestion_cascade(
                t_cur, bits1, stt_u[stage_arr], impl="ref",
                merge_plan=merge_plan,
                hosts=None if n_hosts == 1 else h1, n_hosts=n_hosts,
            )

        t_fin, slot_idx, psd = jax.vmap(per_epoch)(tg, bits_u, vg, hg, qg)
        if has_merges:
            # slot-order payloads, gathered once per cascade (not per
            # scenario): slot k of epoch b held input event slot_idx[b, k]
            ga = lambda x: jnp.take_along_axis(x, slot_idx, axis=1)
            region_e = ga(rg)
            nbytes_e, weight_e = ga(nbytes[g]), ga(weight[g])
            valid_e, host_e = ga(vg), ga(hg)
        else:  # no merges scheduled: slot order == input order
            region_e, nbytes_e, weight_e = rg, nbytes[g], weight[g]
            valid_e, host_e = vg, hg
        return t_fin, psd, region_e, nbytes_e, weight_e, valid_e, host_e

    cas = jax.vmap(one_cascade)(
        cas_group, cas_assign, cas_stt, cas_disc, cas_weights
    )
    (t_fin_u, psd_u, region_u, nbytes_u, weight_u, valid_u, host_u) = cas

    # -- phase 2: per-scenario latency/bandwidth reductions ----------------- #
    def per_scenario(u, g, assign_k, scale_k, plat_k, llat_k, sbw_k):
        t_fin, region_e = t_fin_u[u], region_u[u]
        nbytes_e, weight_e = nbytes_u[u], weight_u[u]
        valid_e, host_e = valid_u[u], host_u[u]

        pool_e = jnp.where(valid_e, assign_k[region_e], 0)
        vp_e = pool_e if n_hosts == 1 else host_e * P + pool_e

        # latency and bandwidth per epoch, summed over the scenario's B:
        # windows on the shared post-congestion times plus this scenario's
        # latency, priced against its own leaves (+ cache scale)
        def price_epoch(t1, vp1, pool1, host1, w1, b1, v1, scale1, bww1):
            lat1, latency1, ppl1, phl1 = _price_latency(
                vp1, pool1, host1, w1, v1, scale1, plat_k, llat_k, n_hosts
            )
            bw1 = _price_bandwidth(
                t1, lat1, vp1, b1, v1, bww1, route, sbw_k, n_windows, n_hosts
            )
            return (latency1, ppl1, phl1) + bw1

        (latency, per_pool_lat, per_host_lat,
         bandwidth, per_switch_bw, per_host_bw) = (
            x.sum(axis=0)
            for x in jax.vmap(price_epoch)(
                t_fin, vp_e, pool_e, host_e, weight_e, nbytes_e, valid_e,
                scale_k, bw_window[g],
            )
        )

        # congestion: shared with every scenario of the same cascade
        congestion, per_switch_cong, per_host_cong, per_class_cong = (
            _congestion_leaves(psd_u[u].sum(axis=0), stage_arr, S, n_hosts, qos_on)
        )

        return (
            latency, congestion, bandwidth,
            per_pool_lat, per_switch_cong, per_switch_bw,
            per_host_lat, per_host_cong, per_host_bw,
            per_class_cong,
        )

    return jax.vmap(per_scenario)(
        cascade_of, group_of, assign, lat_scale, pool_latency_ns,
        local_latency_ns, switch_bw,
    )


@dataclasses.dataclass
class PendingBatch:
    """An in-flight epoch dispatch: staged, transferred and launched, but
    not yet resolved.  :meth:`finish` blocks on the device result and
    returns the :class:`DelayBreakdown`; until then the caller is free to
    stage and launch the *next* batch — the engine's overlapped dispatcher
    does exactly that, so batch k+1's staging and H2D run while batch k
    computes.  ``stats.wait_s`` and ``stats.d2h_s`` are filled at finish
    time with the exposed device wait and the copy back."""

    analyzer: "EpochAnalyzer"
    out: Optional[tuple]
    stats: DispatchStats

    def finish(self) -> DelayBreakdown:
        a = self.analyzer
        P, S, H = a.flat.n_pools, a.flat.n_switches, a.flat.n_hosts
        if self.out is None:
            a.last_dispatch = self.stats
            return DelayBreakdown.zero(P, S, H)
        # the single host-boundary crossing for the whole batch; the
        # chain dispatch's trailing plane stays on device and is simply
        # dropped
        host, wait_s, d2h_s = collect_dispatch(self.out[:10])
        stats = dataclasses.replace(self.stats, wait_s=wait_s, d2h_s=d2h_s)
        a.last_dispatch = stats
        self.stats = stats
        self.out = None
        return DelayBreakdown.from_leaves(host)


class EpochAnalyzer:
    """Jitted epoch analyzer with bucketed padding and epoch batching.

    Event counts vary per epoch; traces are padded up to the next power-of-two
    bucket (via reusable :class:`~repro.core.events.EventStager` buffers, no
    per-epoch allocation) so repeated calls reuse the compile cache.

    :meth:`analyze_batch` stacks B bucketed epochs into ``[B, N]`` arrays and
    runs a single jitted, vmapped dispatch whose per-epoch breakdowns are
    summed **on device** — one host round-trip per batch instead of one per
    epoch.  :meth:`analyze` is the B=1 special case, and
    :meth:`analyze_batch_multi` stacks K such batches in one dispatch.
    See the module docstring for the surfaces and the ``impl`` / ``fused``
    knobs.
    """

    def __init__(
        self,
        flat: FlatTopology,
        bw_window_ns: float = 10_000.0,
        n_windows: int = 128,
        dtype=jnp.float32,
        impl: str = "inline",
        fused: bool = True,
        mesh=None,
        pipeline: bool = False,
        aot: Optional[AotDispatchCache] = None,
    ):
        """``pipeline=True`` enables the device-resident dispatch path:
        chain-eligible topologies (:func:`plan_chain`) run the packed
        compact cascade with on-device sorting and donated staging
        buffers; everything else runs the standard full-plane graph, but
        still through the AOT executable cache (``aot``, private by
        default) with the stage/transfer/compile/compute breakdown in
        :attr:`last_dispatch`.  Requires ``impl='inline'``."""
        self.flat = flat
        self.mesh = mesh
        self.last_dispatch = DispatchStats()
        self.sharded_dispatches = 0
        self.bw_window_ns = float(bw_window_ns)
        self.n_windows = int(n_windows)
        self.dtype = dtype
        self._pool_lat = jnp.asarray(flat.pool_latency_ns, dtype)
        self._local_lat = jnp.asarray(flat.local_latency_ns, dtype)
        self._route = jnp.asarray(flat.route, dtype)
        self._stt = jnp.asarray(flat.switch_stt_ns, dtype)
        self._bw = jnp.asarray(flat.switch_bandwidth_gbps, dtype)
        self._disc = jnp.asarray(flat.discipline_codes(), jnp.int32)
        self._weights = jnp.asarray(flat.class_weight_table(), dtype)
        self.qos_on = bool(flat.has_qos)
        self.impl = impl
        self.fused = bool(fused)
        if self.fused and flat.n_switches > 31:
            # the fused cascade encodes one stage per switch (incl. per-host
            # RCs) in a 31-bit route word; very wide fabrics fall back to
            # the per-stage argsort loop — slower, but any host count works
            self.fused = False
        if self.qos_on and not self.fused:
            raise ValueError(
                "QoS disciplines require the fused cascade: pass fused=True "
                "and keep the fabric within the 31-switch route-word budget"
            )
        if self.fused:
            bits_pool, self._merge_plan, self._stage_order = plan_cascade(flat)
        else:
            bits_pool = np.zeros((flat.route.shape[0],), np.int32)
            self._merge_plan = None
            self._stage_order = tuple(int(s) for s in flat.stage_order())
        self._bits_table = jnp.asarray(bits_pool)
        self._stager = EventStager(np.dtype(jnp.dtype(dtype).name))
        _static = (
            "stage_order", "n_windows", "n_hosts", "impl", "fused",
            "merge_plan", "qos_on",
        )
        self._batch_fn = jax.jit(
            _analyze_words_jax, static_argnames=_static + ("layout",)
        )
        self._stack_fn = jax.jit(_analyze_stack_jax, static_argnames=_static)
        self.pipeline = bool(pipeline)
        self._chain_plan: Optional[ChainPlan] = None
        self._aot: Optional[AotDispatchCache] = None
        if self.pipeline:
            if impl != "inline":
                raise ValueError(
                    "pipeline=True requires impl='inline' — the device-"
                    "resident dispatch is a pure-XLA graph"
                )
            self._aot = aot if aot is not None else AotDispatchCache()
            # the packed compact cascade is FIFO-only: QoS fabrics run the
            # full-plane graph (still AOT-cached) instead
            self._chain_plan = None if self.qos_on else plan_chain(flat)

    _bucket = staticmethod(bucket_pow2)

    def analyze(
        self, events: MemEvents, lat_scale: Optional[np.ndarray] = None
    ) -> DelayBreakdown:
        return self.analyze_batch(
            [events], None if lat_scale is None else [lat_scale]
        )

    def _clean_pairs(
        self,
        traces: Sequence[MemEvents],
        lat_scales: Optional[Sequence[Optional[np.ndarray]]],
    ) -> List[Tuple[MemEvents, Optional[np.ndarray]]]:
        """Pair epochs with their scales, drop empties, validate routes."""
        if lat_scales is None:
            lat_scales = [None] * len(traces)
        elif len(lat_scales) != len(traces):
            raise ValueError(
                f"{len(lat_scales)} lat_scales for {len(traces)} traces — "
                "pass one (possibly None) per epoch"
            )
        pairs = [(tr, sc) for tr, sc in zip(traces, lat_scales) if tr.n]
        for tr, _ in pairs:
            _check_reachable(self.flat, tr)
        return pairs

    def _aot_build(
        self, chain: Optional[ChainPlan], caps, b_bucket, n_bucket, layout, words
    ):
        """(cache key, build thunk) for this dispatch's AOT executable.

        The key carries what varies *within* one analyzer: the dispatch
        kind and the bucketed shapes (chain-path segment capacities
        included — they are static operands of the compact cascade, and
        with the shapes they fix the plane's ``layout``).  The topology
        fingerprint and mesh are fixed per analyzer and its private cache,
        so they need no key bits here; the engine's ``dispatch_key``
        separates analyzers."""
        words_s = jax.ShapeDtypeStruct(words.shape, words.dtype)
        topo = (self._pool_lat, self._local_lat, self._route, self._stt, self._bw)
        topo_s = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in topo)
        if chain is not None:
            key = ("chain", b_bucket, n_bucket, caps)

            def build():
                jitted = jax.jit(
                    _analyze_pipeline_jax,
                    static_argnames=("layout", "stage_order", "seg_caps", "n_windows"),
                    donate_argnums=(0,),
                )
                return jitted.lower(
                    words_s, *topo_s,
                    layout=layout,
                    stage_order=chain.stage_order,
                    seg_caps=caps,
                    n_windows=self.n_windows,
                ).compile()

        else:
            bits_s = jax.ShapeDtypeStruct(
                self._bits_table.shape, self._bits_table.dtype
            )
            topo_b = topo + (self._disc, self._weights)
            topo_bs = tuple(
                jax.ShapeDtypeStruct(a.shape, a.dtype) for a in topo_b
            )
            key = ("batch", b_bucket, n_bucket)

            def build():
                jitted = jax.jit(
                    _analyze_words_jax,
                    static_argnames=(
                        "layout", "stage_order", "n_windows", "n_hosts", "impl",
                        "fused", "merge_plan", "qos_on",
                    ),
                )
                return jitted.lower(
                    words_s, bits_s, *topo_bs,
                    layout=layout,
                    stage_order=self._stage_order,
                    n_windows=self.n_windows,
                    n_hosts=self.flat.n_hosts,
                    impl=self.impl,
                    fused=self.fused,
                    merge_plan=self._merge_plan,
                    qos_on=self.qos_on,
                ).compile()

        return key, build

    def launch_batch(
        self,
        traces: Sequence[MemEvents],
        lat_scales: Optional[Sequence[Optional[np.ndarray]]] = None,
        stager: Optional[EventStager] = None,
    ) -> PendingBatch:
        """Stage, transfer and launch one epoch batch without blocking.

        The non-blocking half of :meth:`analyze_batch` (same arguments,
        same semantics once the returned :class:`PendingBatch` is
        finished).  Pipeline analyzers on chain-eligible topologies run
        the device-resident packed dispatch — on-device sort, donated
        staging buffers, AOT executable; other pipeline dispatches run
        the full-plane graph through the AOT cache; non-pipeline
        analyzers launch the classic jitted path.  All three place one
        staged word plane (one host→device transfer) and record the
        stage/transfer/compile/compute split in the pending stats.
        """
        H = self.flat.n_hosts
        V = H * self.flat.n_pools
        pairs = self._clean_pairs(traces, lat_scales)
        if not pairs:
            return PendingBatch(self, None, DispatchStats(rows=0))
        traces = [tr for tr, _ in pairs]
        with spans.span("cxlsim.stage") as stage:
            n_bucket = self._bucket(max(tr.n for tr in traces))
            b_bucket = self._bucket(len(traces), floor=1)
            st = stager if stager is not None else self._stager
            chain = self._chain_plan
            caps = None
            if chain is not None:
                buf, caps = st.stage_packed(
                    traces, b_bucket, n_bucket, chain.enter_stage,
                    len(chain.stage_order), scale_width=V,
                )
            else:
                buf = st.stage(traces, b_bucket, n_bucket, scale_width=V)
            scale = buf["scale"]
            scale.fill(1.0)
            for row, (_, sc) in enumerate(pairs):
                if sc is not None:
                    scale[row] = sc
            span = np.maximum(buf["span"], self.bw_window_ns)
            buf["bw_window"][:] = np.maximum(span / self.n_windows, 1.0)
            layout = plane_layout(b_bucket, n_bucket, V, sum(caps) if caps else 0)

        from repro.distributed.sharding import timed_device_put

        with spans.span("cxlsim.h2d") as h2d:
            words = timed_device_put(buf["words"])
        spans.count(spans.H2D_BUFFERS, 1)

        compile_s = 0.0
        aot_hit = False
        donated = False
        if self.pipeline:
            key, build = self._aot_build(
                chain, caps, b_bucket, n_bucket, layout, words
            )
            built: List[float] = []

            def build_in_span():
                with spans.span("cxlsim.compile") as c:
                    exe = build()
                built.append(c.seconds)
                return exe

            exe, aot_hit = self._aot.get(key, build_in_span)
            compile_s = sum(built)
            if chain is not None:
                out, enqueue_s, jit_compile_s = enqueue_dispatch(
                    exe, words, self._pool_lat, self._local_lat, self._route,
                    self._stt, self._bw,
                )
                donated = bool(words.is_deleted())
            else:
                out, enqueue_s, jit_compile_s = enqueue_dispatch(
                    exe, words, self._bits_table, self._pool_lat,
                    self._local_lat, self._route, self._stt, self._bw,
                    self._disc, self._weights,
                )
        else:
            out, enqueue_s, jit_compile_s = enqueue_dispatch(
                self._batch_fn,
                words, self._bits_table, self._pool_lat, self._local_lat,
                self._route, self._stt, self._bw, self._disc, self._weights,
                layout=layout,
                stage_order=self._stage_order,
                n_windows=self.n_windows,
                n_hosts=H,
                impl=self.impl,
                fused=self.fused,
                merge_plan=self._merge_plan,
                qos_on=self.qos_on,
            )
        slots = b_bucket * n_bucket
        events = sum(tr.n for tr in traces)
        count_dispatch(slots, events)
        stats = DispatchStats(
            devices_used=1,
            shard_rows=0,
            rows=len(traces),
            padded_fraction=float(b_bucket - len(traces)) / b_bucket,
            stage_s=stage.seconds,
            transfer_s=h2d.seconds,
            compile_s=compile_s + jit_compile_s,
            enqueue_s=enqueue_s,
            slots=slots,
            events=events,
            donated=donated,
            aot_cache_hit=aot_hit,
            qos_classes=self.flat.n_qos_classes,
        )
        self.last_dispatch = stats
        return PendingBatch(self, tuple(out), stats)

    def warmup(
        self,
        traces: Sequence[MemEvents],
        lat_scales: Optional[Sequence[Optional[np.ndarray]]] = None,
    ) -> bool:
        """Populate the AOT cache for the executable this batch shape would
        dispatch (one throwaway dispatch), so the first *real* dispatch of
        a serving loop finds it compiled.  Returns True if a lowering
        actually happened (False: already warm, empty batch, or a
        non-pipeline analyzer — the jit path warms itself on first call).
        """
        if not self.pipeline:
            return False
        before = self._aot.lowerings
        self.launch_batch(traces, lat_scales).finish()
        return self._aot.lowerings > before

    def analyze_batch(
        self,
        traces: Sequence[MemEvents],
        lat_scales: Optional[Sequence[Optional[np.ndarray]]] = None,
        stager: Optional[EventStager] = None,
    ) -> DelayBreakdown:
        """Analyze B epochs in one device dispatch; returns summed totals.

        ``lat_scales`` optionally pairs each epoch with a ``[H*P]``
        device-cache latency-scale vector
        (:meth:`~repro.core.cache.DeviceCacheModel.latency_scale`); ``None``
        entries (and padded rows) analyze with the exact no-cache ones
        vector.  ``stager`` substitutes the caller's staging buffers for
        the analyzer's own — the shared engine passes its per-engine stager
        so its dispatcher thread never shares mutable buffers with callers
        analyzing synchronously on this analyzer.
        """
        # the synchronous special case of the overlapped dispatch: launch,
        # then immediately block
        return self.launch_batch(traces, lat_scales, stager=stager).finish()

    def analyze_batch_multi(
        self,
        groups: Sequence[Sequence[MemEvents]],
        lat_scale_groups: Optional[Sequence[Optional[Sequence]]] = None,
        stager: Optional[EventStager] = None,
        mesh=None,
    ) -> List[DelayBreakdown]:
        """K sessions' epoch batches → K summed breakdowns, ONE dispatch.

        The multi-session stacked entry point the shared engine coalesces
        through: ``groups[k]`` is session k's epoch list and comes back as
        its own :class:`DelayBreakdown`, all from a single ``[K, B, N]``
        dispatch (:meth:`_dispatch_stack`, with this analyzer's numeric
        leaves broadcast to K).  Every session must share this analyzer's
        topology and window config (the engine's dispatch key guarantees
        it).  Sessions with no events come back zero; with one live
        session this is :meth:`analyze_batch`.

        ``mesh`` (defaulting to the analyzer's own) shards the session axis
        with ``NamedSharding`` over ``('data',)``: the K leading axis is
        padded to a multiple of the device count so shards stay uniform,
        stacked inputs are placed pre-sharded, the structure replicates,
        and per-shard epoch reduction still happens on device — the host
        transfer stays one ``[K, ...]`` vector regardless of how many
        devices participate.  With one device (or K == 1) the path is
        bitwise identical to the unsharded dispatch.

        Restricted to ``impl='inline'``: the session axis vmaps the fused
        cascade, and only the pure-XLA path is validated under that second
        vmap (mirroring the scenario suite's restriction).
        """
        if self.impl != "inline":
            raise ValueError(
                "cross-session stacking requires impl='inline' (the Pallas "
                "epoch loop is not validated under a session vmap)"
            )
        P, S = self.flat.n_pools, self.flat.n_switches
        H = self.flat.n_hosts
        K = len(groups)
        if lat_scale_groups is None:
            lat_scale_groups = [None] * K
        elif len(lat_scale_groups) != K:
            raise ValueError(
                f"{len(lat_scale_groups)} lat_scale_groups for {K} groups"
            )
        cleaned = [
            self._clean_pairs(traces, scales)
            for traces, scales in zip(groups, lat_scale_groups)
        ]
        out = [DelayBreakdown.zero(P, S, H) for _ in range(K)]
        rows = [i for i, p in enumerate(cleaned) if p]
        if not rows:
            return out
        if len(rows) == 1:  # degenerate stack: the plain batched path
            i = rows[0]
            out[i] = self.analyze_batch(
                [tr for tr, _ in cleaned[i]],
                [sc for _, sc in cleaned[i]],
                stager=stager,
            )
            return out
        # the one row of numeric leaves, broadcast to the K sessions
        leaves = tuple(
            np.broadcast_to(np.asarray(a), (len(rows),) + a.shape)
            for a in (
                self._pool_lat, self._local_lat, self._stt, self._bw,
                self._disc, self._weights,
            )
        )
        bds = self._dispatch_stack(
            [[tr for tr, _ in cleaned[i]] for i in rows],
            leaves,
            scale_groups=[[sc for _, sc in cleaned[i]] for i in rows],
            stager=stager,
            mesh=mesh,
        )
        for i, bd in zip(rows, bds):
            out[i] = bd
        return out

    def _dispatch_stack(
        self,
        groups: Sequence[Sequence[MemEvents]],
        leaves: Sequence[np.ndarray],
        scale_groups: Optional[Sequence[Sequence[Optional[np.ndarray]]]] = None,
        stager: Optional[EventStager] = None,
        mesh=None,
        qos_on: Optional[bool] = None,
    ) -> List[DelayBreakdown]:
        """ONE ``[K, B, N]`` dispatch of K epoch batches on this analyzer's
        fabric structure (:func:`_analyze_stack_jax`) → K breakdowns; the
        dispatch's :class:`DispatchStats` lands in :attr:`last_dispatch`.

        ``leaves`` are the numeric topology leaves with one row per batch:
        ``pool_latency_ns [K, V]``, ``local_latency_ns [K]``,
        ``switch_stt_ns [K, S]``, ``switch_bw [K, S]``, discipline codes
        ``[K, S]`` (int32) and class weights ``[K, S, C]``, floats in the
        analyzer's dtype.  ``scale_groups`` pairs each epoch with a device-
        cache latency scale (None: ones); ``qos_on`` overrides the
        analyzer's own (a fleet arbitrates by its racks' policies).  Every
        axis is bucketed by :func:`bucket_pow2` and K is then padded to
        the mesh's shard count; padded rows are all-invalid planes with
        row 0's leaves."""
        from repro.distributed.sharding import (
            pad_to_multiple, replicated, resolve_data_mesh, shard_rows,
        )

        K = len(groups)
        mesh, n_shards = resolve_data_mesh(
            mesh if mesh is not None else self.mesh, K, what="stacked dispatch"
        )
        with spans.span("cxlsim.stage") as stage:
            n_bucket = self._bucket(max((tr.n for g in groups for tr in g), default=0))
            b_bucket = self._bucket(max(len(g) for g in groups), floor=1)
            k_bucket = pad_to_multiple(self._bucket(K, floor=1), n_shards)
            st = stager if stager is not None else self._stager
            buf = st.stage_stack(groups, k_bucket, b_bucket, n_bucket)
            np_dtype = np.dtype(jnp.dtype(self.dtype).name)
            scale = np.ones((k_bucket, b_bucket, self.flat.route.shape[0]), np_dtype)
            for k, scales in enumerate(scale_groups or ()):
                for row, sc in enumerate(scales):
                    if sc is not None:
                        scale[k, row] = sc
            span = np.maximum(buf["span"], self.bw_window_ns)
            bw_window = np.maximum(span / self.n_windows, 1.0).astype(np_dtype)
            pad = np.where(np.arange(k_bucket) < K, np.arange(k_bucket), 0)
        if mesh is not None:
            self.sharded_dispatches += 1
        put_k = lambda a: shard_rows(mesh, jnp.asarray(a))
        with spans.span("cxlsim.h2d") as h2d:
            planes = [
                put_k(buf[f])
                for f in ("t", "pool", "bytes", "weight", "host", "qos", "valid")
            ]
            plat, llat, stt, sbw, disc, cw = (put_k(a[pad]) for a in leaves)
            bits, route = (replicated(mesh, a) for a in (self._bits_table, self._route))
        res, enqueue_s, compile_s = enqueue_dispatch(
            self._stack_fn,
            *planes, put_k(bw_window), put_k(scale),
            bits, plat, llat, route, stt, sbw, disc, cw,
            stage_order=self._stage_order,
            n_windows=self.n_windows,
            n_hosts=self.flat.n_hosts,
            impl=self.impl,
            fused=self.fused,
            merge_plan=self._merge_plan,
            qos_on=self.qos_on if qos_on is None else qos_on,
        )
        # one [K, ...] transfer for every stacked batch
        host, wait_s, d2h_s = collect_dispatch(res)
        slots = k_bucket * b_bucket * n_bucket
        events = sum(tr.n for g in groups for tr in g)
        count_dispatch(slots, events)
        self.last_dispatch = DispatchStats(
            devices_used=n_shards,
            shard_rows=k_bucket // n_shards if mesh is not None else 0,
            rows=K,
            padded_fraction=float(k_bucket - K) / k_bucket,
            stage_s=stage.seconds,
            transfer_s=h2d.seconds,
            compile_s=compile_s,
            enqueue_s=enqueue_s,
            wait_s=wait_s,
            d2h_s=d2h_s,
            slots=slots,
            events=events,
            qos_classes=leaves[-1].shape[-1],
        )
        return [DelayBreakdown.from_leaves(host, k) for k in range(K)]


def analyze_any(
    analyzer,
    traces: Sequence[MemEvents],
    lat_scales: Optional[Sequence] = None,
    stager: Optional[EventStager] = None,
) -> DelayBreakdown:
    """Run one epoch batch through whichever analyzer a session carries:
    an :class:`EpochAnalyzer` batches on device; DES-style analyzers
    (anything with ``.flat`` and ``.simulate``) run per epoch and sum.
    The single dispatch point shared by the synchronous attach path and
    the engine's solo-submission path."""
    if isinstance(analyzer, EpochAnalyzer):
        return analyzer.analyze_batch(traces, lat_scales, stager=stager)
    flat = analyzer.flat
    bd = DelayBreakdown.zero(flat.n_pools, flat.n_switches, flat.n_hosts)
    for i, tr in enumerate(traces):
        bd = bd + analyzer.simulate(
            tr, None if lat_scales is None else lat_scales[i]
        )
    return bd


# --------------------------------------------------------------------------- #
# Fine-grained discrete-event baseline (the "Gem5" of our Table 1)
# --------------------------------------------------------------------------- #


class FineGrainedSimulator:
    """Event-by-event DES through the switch hierarchy.

    Every transaction is walked individually through its pool's switch path
    (deepest switch -> RC) with per-switch FIFO occupancy.  ``bandwidth_mode``:

      * ``'stt'``      service time = STT only (matches the epoch analyzer's
                       congestion model exactly; used for oracle agreement).
      * ``'per_txn'``  service time = max(STT, bytes/BW): fine-grained
                       bandwidth modelling the epoch analyzer approximates
                       with windows (used for the accuracy benchmark).
    """

    def __init__(self, flat: FlatTopology, bandwidth_mode: str = "per_txn"):
        if bandwidth_mode not in ("stt", "per_txn"):
            raise ValueError(bandwidth_mode)
        self.flat = flat
        self.bandwidth_mode = bandwidth_mode
        # per-(host, pool) switch path, deepest first (the analyzer's stage
        # order); shared switches appear in several hosts' paths, private RCs
        # in exactly one — the same contention structure the epoch analyzer
        # derives from the virtual-pool route matrix
        order = list(flat.stage_order())
        self._paths: List[List[int]] = []
        for v in range(flat.route.shape[0]):
            self._paths.append([s for s in order if flat.route[v, s] > 0])

    def simulate(
        self,
        events: MemEvents,
        lat_scale: Optional[np.ndarray] = None,
        presorted: bool = False,
    ) -> DelayBreakdown:
        bd, _ = self._run(events, lat_scale, presorted)
        return bd

    def final_times(
        self, events: MemEvents, presorted: bool = False
    ) -> np.ndarray:
        """Per-event post-cascade times (the DES decision oracle the
        vectorized QoS cascades are gated against): ``out[i]`` is event
        ``i``'s departure time from its last switch — its service *start*
        under ``bandwidth_mode='stt'``, matching the kernels' final-time
        semantics exactly.  Times align with the simulated (time-sorted)
        event order; pass ``presorted=True`` on an already-sorted trace to
        keep input order."""
        _, t_out = self._run(events, None, presorted)
        return t_out

    def _run(
        self,
        events: MemEvents,
        lat_scale: Optional[np.ndarray],
        presorted: bool,
    ) -> Tuple[DelayBreakdown, np.ndarray]:
        flat = self.flat
        P, S, H = flat.n_pools, flat.n_switches, flat.n_hosts
        C = int(getattr(flat, "n_qos_classes", 1))
        if events.n == 0:
            return DelayBreakdown.zero(P, S, H), np.zeros((0,), np.float64)
        _check_reachable(flat, events)
        # presorted: the caller promises a non-decreasing timeline (e.g.
        # merge_host_traces output), skipping even the monotone check
        ev = events if presorted else events.sorted_by_time()
        pool = ev.pool.astype(np.int64)
        hostv = ev.host.astype(np.int64)
        qcls = np.clip(ev.qos.astype(np.int64), 0, C - 1)
        vpool = hostv * P + pool
        per_event_lat = np.maximum(
            flat.pool_latency_ns[vpool] - flat.local_latency_ns, 0.0
        )
        if lat_scale is not None:
            # device-cache epoch summary, same contract as analyze_ref
            per_event_lat = per_event_lat * np.asarray(lat_scale, np.float64)[vpool]
        per_event_lat = per_event_lat * ev.weight
        per_pool_lat = np.bincount(pool, weights=per_event_lat, minlength=P)[:P]
        per_host_lat = np.bincount(hostv, weights=per_event_lat, minlength=H)[:H]

        # per-(switch, class) horizons: FIFO switches use column 0 (one
        # shared queue), strict-priority ones carve per-level horizons a
        # high-class arrival pushes forward, WFQ ones advance class-private
        # virtual time by the weight-inflated service
        discs = (
            list(flat.switch_discipline)
            if getattr(flat, "switch_discipline", None)
            else ["fifo"] * S
        )
        w_table = flat.class_weight_table().astype(np.float64)
        w_total = w_table.sum(axis=1)
        fin = np.zeros((S, C), np.float64)
        per_switch_cong = np.zeros((S,), np.float64)
        per_switch_bw = np.zeros((S,), np.float64)
        per_host_cong = np.zeros((H,), np.float64)
        per_host_bw = np.zeros((H,), np.float64)
        per_class_cong = np.zeros((C,), np.float64)
        t_out = np.zeros((ev.n,), np.float64)
        # priority queue of (time, seq, event_idx, stage_pos); ``ev`` is
        # time-sorted, so the seed list already satisfies the heap invariant
        # — one O(n) pass instead of n heappushes.
        heap: List[Tuple[float, int, int, int]] = [
            (float(ev.t_ns[i]), i, i, 0) for i in range(ev.n)
        ]
        seq = ev.n
        while heap:
            t_arr, _, i, stage = heapq.heappop(heap)
            path = self._paths[vpool[i]]
            if stage >= len(path):
                t_out[i] = t_arr
                continue
            s = path[stage]
            stt = float(flat.switch_stt_ns[s])
            if self.bandwidth_mode == "per_txn":
                bw = float(flat.switch_bandwidth_gbps[s])
                service = max(stt, float(ev.bytes_[i]) / bw if bw > 0 else stt)
            else:
                service = stt
            disc = discs[s]
            c = int(qcls[i])
            if disc == "priority":
                start = max(t_arr, fin[s, c])
                for lvl in range(c, C):
                    fin[s, lvl] = max(t_arr, fin[s, lvl]) + service
            elif disc == "wfq":
                start = max(t_arr, fin[s, c])
                fin[s, c] = start + service * w_total[s] / w_table[s, c]
            else:  # fifo: one shared horizon
                start = max(t_arr, fin[s, 0])
                fin[s, 0] = start + service
            per_switch_cong[s] += start - t_arr  # queueing delay
            per_host_cong[hostv[i]] += start - t_arr
            per_class_cong[c] += start - t_arr
            if self.bandwidth_mode == "per_txn" and service > stt:
                per_switch_bw[s] += service - stt
                per_host_bw[hostv[i]] += service - stt
            heapq.heappush(heap, (start + service if self.bandwidth_mode == "per_txn" else start, seq, i, stage + 1))
            seq += 1

        return DelayBreakdown(
            float(per_event_lat.sum()),
            float(per_switch_cong.sum()),
            float(per_switch_bw.sum()),
            per_pool_lat,
            per_switch_cong,
            per_switch_bw,
            per_host_lat,
            per_host_cong,
            per_host_bw,
            per_class_cong,
        ), t_out
