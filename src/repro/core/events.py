"""Memory-event traces and the region->pool allocation map.

The paper's Tracer has two halves:

  1. an *allocation* tracer (eBPF probes on mmap/sbrk/brk) that maintains a
     map from address ranges to memory pools, and
  2. an *event* tracer (PEBS) that samples memory operations.

Our JAX-native analogue: every logical tensor region of a step function
(weights, activations, KV cache, optimizer state, MoE experts, ...) is
registered with a :class:`RegionMap`; a placement policy assigns each region
to a pool.  Event traces are dense struct-of-arrays so the timing analyzer
can be fully vectorized.

Times inside a trace are **epoch-relative nanoseconds** (float).  Keeping
them epoch-relative bounds their magnitude (epochs are ms-scale), so float32
retains sub-ns resolution inside jitted analyzer code; totals are accumulated
host-side in float64.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "CACHELINE_BYTES",
    "PAGE_BYTES",
    "EventStager",
    "MemEvents",
    "PlaneLayout",
    "Region",
    "RegionMap",
    "concat_events",
    "merge_host_traces",
    "plane_layout",
    "plane_words",
    "split_by_host",
    "synthetic_trace",
]

CACHELINE_BYTES = 64
PAGE_BYTES = 4096
# synthetic-trace burst width as a *fraction* of the epoch (dimensionless
# tuning knob, not a ns conversion)
_BURST_SPREAD_FRAC = 1e-3


@dataclasses.dataclass(frozen=True)
class MemEvents:
    """A struct-of-arrays trace of memory events within one epoch.

    Attributes:
      t_ns:    [N] issue time, ns, relative to epoch start, non-decreasing
               not required (the analyzer sorts).
      pool:    [N] int32 pool index into the FlatTopology.
      bytes_:  [N] bytes moved by the event (a transaction may cover many
               cachelines; granularity is the policy's choice).
      is_write:[N] bool (writes may cost differently; coherency uses this).
      region:  [N] int32 region id (for migration/hotness accounting).
      weight:  [N] statistical multiplicity (1.0 exact; 1/rate under PEBS-style
               sampling so count-proportional delays stay unbiased).
      host:    [N] int32 attached-host index (0 for single-host simulation).
               In a shared-fabric session events from several hosts are merged
               onto one timeline; the analyzer routes each event through its
               (host, pool) pair so contention appears only at shared
               components.
      qos:     [N] int32 QoS class (0 = default / highest priority).  Switch
               arbiters running 'priority' or 'wfq' disciplines order their
               queues by this class; FIFO switches ignore it.
    """

    t_ns: np.ndarray
    pool: np.ndarray
    bytes_: np.ndarray
    is_write: np.ndarray
    region: np.ndarray
    weight: np.ndarray = None  # type: ignore[assignment]
    host: np.ndarray = None  # type: ignore[assignment]
    qos: np.ndarray = None  # type: ignore[assignment]

    def __post_init__(self) -> None:
        if self.weight is None:
            object.__setattr__(self, "weight", np.ones((len(self.t_ns),), np.float64))
        if self.host is None:
            object.__setattr__(self, "host", np.zeros((len(self.t_ns),), np.int32))
        if self.qos is None:
            object.__setattr__(self, "qos", np.zeros((len(self.t_ns),), np.int32))
        n = len(self.t_ns)
        for f in ("pool", "bytes_", "is_write", "region", "weight", "host", "qos"):
            if len(getattr(self, f)) != n:
                raise ValueError(f"field {f} length mismatch")

    @property
    def n(self) -> int:
        return int(len(self.t_ns))

    @property
    def total_bytes(self) -> float:
        return float(self.bytes_.sum())

    def sorted_by_time(self) -> "MemEvents":
        # Monotone fast path: a stable argsort of a non-decreasing key is the
        # identity permutation, so an already-sorted trace (the tracer's
        # common case, and everything downstream of merge_host_traces) costs
        # one O(N) check instead of an argsort plus seven gathers.
        if self.n <= 1 or bool(np.all(self.t_ns[1:] >= self.t_ns[:-1])):
            return self
        order = np.argsort(self.t_ns, kind="stable")
        return self.take(order)

    def take(self, idx: np.ndarray) -> "MemEvents":
        return MemEvents(
            t_ns=self.t_ns[idx],
            pool=self.pool[idx],
            bytes_=self.bytes_[idx],
            is_write=self.is_write[idx],
            region=self.region[idx],
            weight=self.weight[idx],
            host=self.host[idx],
            qos=self.qos[idx],
        )

    def with_host(self, host: int) -> "MemEvents":
        """Copy with every event tagged as issued by ``host``."""
        return dataclasses.replace(
            self, host=np.full((self.n,), int(host), np.int32)
        )

    def with_qos(self, qos) -> "MemEvents":
        """Copy with events tagged as QoS class ``qos`` — a scalar (a
        tenant's whole trace usually shares one class) or a per-event
        array."""
        q = np.asarray(qos, np.int32)
        if q.ndim == 0:
            q = np.full((self.n,), int(q), np.int32)
        elif q.shape != (self.n,):
            raise ValueError(f"qos shape {q.shape} != ({self.n},)")
        return dataclasses.replace(self, qos=q)

    def sample(self, rate: float, seed: int = 0) -> "MemEvents":
        """PEBS-style sampling: keep each event with probability ``rate`` and
        scale bytes by 1/rate so aggregate traffic is preserved in expectation.
        """
        if not (0.0 < rate <= 1.0):
            raise ValueError("rate must be in (0, 1]")
        if rate == 1.0:
            return self
        rng = np.random.default_rng(seed)
        keep = rng.random(self.n) < rate
        out = self.take(np.nonzero(keep)[0])
        return dataclasses.replace(
            out, bytes_=out.bytes_ / rate, weight=out.weight / rate
        )

    @staticmethod
    def empty() -> "MemEvents":
        z = np.zeros((0,))
        return MemEvents(
            t_ns=z.astype(np.float64),
            pool=z.astype(np.int32),
            bytes_=z.astype(np.float64),
            is_write=z.astype(bool),
            region=z.astype(np.int32),
        )

    @staticmethod
    def build(
        t_ns: Iterable[float],
        pool: Iterable[int],
        bytes_: Iterable[float],
        is_write: Optional[Iterable[bool]] = None,
        region: Optional[Iterable[int]] = None,
        host: Optional[Iterable[int]] = None,
        qos: Optional[Iterable[int]] = None,
    ) -> "MemEvents":
        t = _as_column(t_ns, np.float64)
        p = _as_column(pool, np.int32)
        b = _as_column(bytes_, np.float64)
        w = (
            _as_column(is_write, bool)
            if is_write is not None
            else np.zeros(len(t), bool)
        )
        r = (
            _as_column(region, np.int32)
            if region is not None
            else np.zeros(len(t), np.int32)
        )
        h = (
            _as_column(host, np.int32)
            if host is not None
            else np.zeros(len(t), np.int32)
        )
        q = (
            _as_column(qos, np.int32)
            if qos is not None
            else np.zeros(len(t), np.int32)
        )
        return MemEvents(t, p, b, w, r, host=h, qos=q)


def _as_column(x, dtype) -> np.ndarray:
    """Coerce a build() input to a 1-D array without the list round-trip.

    ndarrays and plain sequences go straight to ``np.asarray`` (an O(copy)
    conversion, or free when dtype already matches); only true generators are
    materialized first.
    """
    if not isinstance(x, (np.ndarray, list, tuple)):
        x = list(x)
    return np.asarray(x, dtype)


def concat_events(traces: Sequence[MemEvents]) -> MemEvents:
    traces = [t for t in traces if t.n]
    if not traces:
        return MemEvents.empty()
    return MemEvents(
        t_ns=np.concatenate([t.t_ns for t in traces]),
        pool=np.concatenate([t.pool for t in traces]),
        bytes_=np.concatenate([t.bytes_ for t in traces]),
        is_write=np.concatenate([t.is_write for t in traces]),
        region=np.concatenate([t.region for t in traces]),
        weight=np.concatenate([t.weight for t in traces]),
        host=np.concatenate([t.host for t in traces]),
        qos=np.concatenate([t.qos for t in traces]),
    )


def merge_host_traces(
    traces: Sequence[MemEvents],
    hosts: Optional[Sequence[int]] = None,
) -> MemEvents:
    """Merge per-host epoch traces onto one shared fabric timeline.

    ``traces[i]`` is tagged with host ``hosts[i]`` (default: index ``i``) and
    the union is returned time-sorted, which is exactly the analyzer's staging
    contract: co-scheduled epochs start at the same fabric instant, so their
    epoch-relative times are directly comparable.
    """
    if hosts is None:
        hosts = range(len(traces))
    tagged = [tr.with_host(h) for tr, h in zip(traces, hosts)]
    return concat_events(tagged).sorted_by_time()


def split_by_host(trace: MemEvents, n_hosts: int) -> List[MemEvents]:
    """Inverse of :func:`merge_host_traces`: per-host sub-traces, order kept."""
    return [
        trace.take(np.nonzero(trace.host == h)[0]) for h in range(int(n_hosts))
    ]


# --------------------------------------------------------------------------- #
# Batched staging buffers — the analyzer's host-side feed path
# --------------------------------------------------------------------------- #


def _bucket_pow2(n: int, floor: int) -> int:
    v = max(int(floor), 1)
    while v < n:
        v *= 2
    return v


# Columns of the staged word plane, in plane order: the full-plane
# dispatch's seven [B, N] event columns, or the chain dispatch's packed
# [B, W] runs and the four [B, N] columns it reads; both end with the [B]
# window lengths and the [B, V] latency scales.  "f" columns hold float32
# bit patterns, "b" columns 0/1 words, and "t" columns float32 bits
# stored transposed: the per-event scale gather reads the scales
# batch-minor, the layout XLA gives them on a TPU, and a row-major slice
# of the plane doubled that gather's device time on a TPU v5e (PERF.md §6).
_FULL_COLUMNS = (
    ("t", "f"), ("pool", "i"), ("bytes", "f"), ("weight", "f"),
    ("host", "i"), ("qos", "i"), ("valid", "b"),
)
_CHAIN_COLUMNS = (
    ("t_pack", "f"), ("idx_pack", "i"), ("pool", "i"), ("bytes", "f"),
    ("weight", "f"), ("valid", "b"),
)

PlaneLayout = Tuple[Tuple[str, int, Tuple[int, ...], str], ...]


@functools.lru_cache(maxsize=256)
def plane_layout(
    b_bucket: int, n_bucket: int, scale_width: int, width: int = 0
) -> PlaneLayout:
    """``(name, word offset, shape, kind)`` of every column of one staged
    word plane (kind ``"f"`` float32 bits, ``"i"`` int32, ``"b"`` 0/1,
    ``"t"`` float32 bits stored transposed).  ``width`` > 0 lays out the
    chain dispatch (packed runs of that width), 0 the full-plane
    dispatch.  The host views of :class:`EventStager` and the device-side
    unpacking read the same offsets, so one transfer carries every column
    of a dispatch."""
    entries = [
        (name, (b_bucket, width if name.endswith("_pack") else n_bucket), kind)
        for name, kind in (_CHAIN_COLUMNS if width else _FULL_COLUMNS)
    ] + [
        ("bw_window", (b_bucket,), "f"),
        ("scale", (b_bucket, scale_width), "t"),
    ]
    out = []
    off = 0
    for name, shape, kind in entries:
        out.append((name, off, shape, kind))
        off += int(np.prod(shape))
    return tuple(out)


def plane_words(layout: PlaneLayout) -> int:
    """Total 32-bit words of a plane laid out by :func:`plane_layout`."""
    _, off, shape, _ = layout[-1]
    return off + int(np.prod(shape))


class EventStager:
    """Reusable host staging buffers for bucketed, batched epoch analysis.

    The epoch analyzer pads traces up to power-of-two buckets so repeated
    calls hit the jit compile cache.  Doing that with ``np.pad`` allocates
    five fresh float64 arrays per epoch; at analyzer rates (thousands of
    epochs per second) the allocator churn dominates.  The stager instead
    owns one buffer set per ``(batch, length)`` bucket and refills it in
    place — steady-state staging performs zero host allocations, and the
    float64 -> analyzer-dtype conversion happens once, during the fill.

    Each buffer set is one int32 word plane (``"words"``, laid out by
    :func:`plane_layout`) and views into it: every column a dispatch reads
    is a reshaped slice at a static offset, float columns seen through
    ``.view(time_dtype)`` (``scale`` through a transposed one) and
    ``valid`` as 0/1 words.  Filling the views
    fills the plane, so the analyzer places a dispatch with one host to
    device transfer; columns the chain dispatch does not read (``t``,
    ``host``, ``qos``) live beside the plane.  The plane holds 32-bit
    words, so ``time_dtype`` must be a 32-bit float for :meth:`stage`
    and :meth:`stage_packed`; :meth:`stage_stack` keeps plain planes.

    Not thread-safe: every thread that stages must own its stager.  The
    shared :class:`~repro.core.engine.AnalysisEngine` owns one stager set
    per engine (all staging happens on its single dispatcher thread);
    each :class:`~repro.core.analyzer.EpochAnalyzer` keeps a private
    stager for callers analyzing synchronously on their own thread —
    the two never share buffers.
    """

    _FIELDS = ("t", "pool", "bytes", "weight", "host", "qos", "valid")

    # dispatches a bucket's natural caps must sit at (or below) half the
    # sticky high-water mark before the sticky caps shrink to the recent
    # peak — a transient burst stops pinning peak-size staging planes (and
    # their AOT executables) after this many consecutive idle calls
    CAP_DECAY_CALLS = 8

    def __init__(self, time_dtype: object = np.float32, slots: int = 1) -> None:
        self.time_dtype = np.dtype(time_dtype)
        # ``slots`` > 1 turns each bucket's buffer set into a ring: every
        # stage() call rotates to the next slot before filling, so a caller
        # overlapping H2D/compute of dispatch k with the staging of k+1
        # (the engine's double-buffered pipeline) never overwrites host
        # planes an in-flight transfer may still be reading.
        self.slots = max(1, int(slots))
        self._bufs: Dict[Tuple[int, ...], Dict[str, np.ndarray]] = {}
        self._turn: Dict[Tuple[int, int], int] = {}
        self._stack_bufs: Dict[Tuple[int, int, int], Dict[str, np.ndarray]] = {}
        self._stack_filled: Dict[Tuple[int, int, int], int] = {}
        self._cap_hwm: Dict[Tuple[int, int, int], Tuple[int, ...]] = {}
        # idle-decay state per cap key: consecutive calls whose natural caps
        # sat at <= half the sticky high-water mark, and the elementwise peak
        # of the natural caps observed during that streak
        self._cap_slack: Dict[Tuple[int, int, int], int] = {}
        self._cap_peak: Dict[Tuple[int, int, int], Tuple[int, ...]] = {}

    def rotate(self, b_bucket: int, n_bucket: int) -> int:
        """Advance this bucket's ring and return the now-current slot."""
        key = (b_bucket, n_bucket)
        slot = (self._turn.get(key, self.slots - 1) + 1) % self.slots
        self._turn[key] = slot
        return slot

    def buffers(
        self, b_bucket: int, n_bucket: int, scale_width: int = 0, width: int = 0
    ) -> Dict[str, np.ndarray]:
        """The current slot's buffer set: the word plane of
        ``plane_layout(b_bucket, n_bucket, scale_width, width)``, its
        column views, the host-only columns and ``span``."""
        key = (
            b_bucket, n_bucket, scale_width, width,
            self._turn.get((b_bucket, n_bucket), 0),
        )
        buf = self._bufs.get(key)
        if buf is None:
            if self.time_dtype.itemsize != 4:
                raise ValueError(
                    f"the staged word plane holds 32-bit columns; time dtype "
                    f"{self.time_dtype} does not fit"
                )
            layout = plane_layout(b_bucket, n_bucket, scale_width, width)
            words = np.zeros((plane_words(layout),), np.int32)
            buf = {"words": words}
            for name, off, shape, kind in layout:
                col = words[off : off + int(np.prod(shape))]
                col = col.reshape(shape[::-1]).T if kind == "t" else col.reshape(shape)
                buf[name] = col.view(self.time_dtype) if kind in ("f", "t") else col
            td, i32 = self.time_dtype, np.int32
            for name, dt in (("t", td), ("host", i32), ("qos", i32)):
                if name not in buf:  # the chain dispatch does not read it
                    buf[name] = np.zeros((b_bucket, n_bucket), dt)
            buf["span"] = np.zeros((b_bucket,), np.float64)
            self._bufs[key] = buf
        return buf

    def stage(
        self,
        traces: Sequence["MemEvents"],
        b_bucket: int,
        n_bucket: int,
        scale_width: int = 0,
    ) -> Dict[str, np.ndarray]:
        """Fill (in place) and return the buffer set for this bucket.

        Every row is delivered **time-sorted** — the analyzer's one stable
        sort per epoch happens here, on the host, and only when a trace is
        not already monotone (the tracer emits sorted epochs, so the common
        case is a 30 µs check plus plain copies).  Rows beyond
        ``len(traces)`` — and the tail of every row beyond its trace's
        event count — are marked invalid; ``span`` holds each epoch's max
        issue time + 1 (0 for empty rows).  ``bw_window`` and the
        ``[B, scale_width]`` ``scale`` columns are the caller's to fill.
        """
        if len(traces) > b_bucket:
            raise ValueError(f"{len(traces)} traces exceed batch bucket {b_bucket}")
        self.rotate(b_bucket, n_bucket)
        buf = self.buffers(b_bucket, n_bucket, scale_width)
        self._fill_rows(buf, traces, b_bucket)
        return buf

    def stage_packed(
        self,
        traces: Sequence["MemEvents"],
        b_bucket: int,
        n_bucket: int,
        enter_stage: np.ndarray,
        n_stages: int,
        cap_floor: int = 16,
        scale_width: int = 0,
    ) -> Tuple[Dict[str, np.ndarray], Tuple[int, ...]]:
        """Pipeline staging: the columns of :meth:`stage` plus per-stage
        packed ``t_pack``/``idx_pack`` runs feeding the device-resident
        chain cascade; returns ``(buffers, caps)``.

        ``enter_stage[pool]`` gives the cascade stage position at which an
        event routed to ``pool`` first enters the fabric (-1 = local, never
        routed).  Because every staged row is time-sorted and extracting a
        per-stage subsequence preserves that order, each packed segment is
        already a sorted run — the merge into one fabric timeline happens on
        device, with **zero host argsort** beyond the monotone check of
        :meth:`_fill_rows`.  Segment ``p`` occupies ``caps[p]`` slots (a
        power-of-two bucket of the batch-max count, shared across rows so
        the packed width is static per dispatch); pad slots carry
        ``t=+inf, idx=-1`` and sort harmlessly to every merge's tail.
        ``idx`` values are positions into the staged (sorted) full row.
        """
        if len(traces) > b_bucket:
            raise ValueError(f"{len(traces)} traces exceed batch bucket {b_bucket}")
        self.rotate(b_bucket, n_bucket)
        enter = np.asarray(enter_stage, np.int32)
        n_stages = int(n_stages)
        # the caps size the plane, so they come from the traces before the
        # fill; per-stage counts do not depend on the order of a row
        counts = np.zeros((max(len(traces), 1), n_stages), np.int64)
        depth_rows: List[np.ndarray] = []
        for row, ev in enumerate(traces):
            d = enter[ev.pool]
            depth_rows.append(d)
            routed = d >= 0
            if routed.any():
                counts[row, :] = np.bincount(d[routed], minlength=n_stages)
        caps = tuple(
            _bucket_pow2(int(counts[:, p].max()), cap_floor)
            for p in range(n_stages)
        )
        # sticky caps: hold the high-water mark within a (batch, length)
        # bucket, so the packed width — and with it the AOT executable key —
        # stabilizes after the first few dispatches instead of flapping with
        # each epoch's depth distribution (zero steady-state recompiles).
        # Idle decay: once CAP_DECAY_CALLS consecutive calls need at most
        # half the held caps, shrink to the peak demand of that streak —
        # a one-off burst stops pinning peak-size planes forever, while a
        # workload oscillating around the mark never shrinks (each touch of
        # the high caps resets the streak, so decay costs at most one
        # recompile per genuine regime change.)
        cap_key = (b_bucket, n_bucket, n_stages)
        natural = caps
        prev = self._cap_hwm.get(cap_key)
        if prev is not None:
            # a stage held at the floor cannot shrink: it counts as idle
            # while its demand still fits, never once the demand outgrows it
            idle = all(
                n <= p // 2 or (p <= cap_floor and n <= p)
                for n, p in zip(natural, prev)
            )
            if idle:
                peak = self._cap_peak.get(cap_key, natural)
                peak = tuple(max(a, b) for a, b in zip(peak, natural))
                streak = self._cap_slack.get(cap_key, 0) + 1
                if streak >= self.CAP_DECAY_CALLS:
                    caps = tuple(max(c, cap_floor) for c in peak)
                    self._cap_slack[cap_key] = 0
                    self._cap_peak.pop(cap_key, None)
                else:
                    caps = prev
                    self._cap_slack[cap_key] = streak
                    self._cap_peak[cap_key] = peak
            else:
                caps = tuple(max(c, p) for c, p in zip(natural, prev))
                self._cap_slack[cap_key] = 0
                self._cap_peak.pop(cap_key, None)
        self._cap_hwm[cap_key] = caps
        buf = self.buffers(b_bucket, n_bucket, scale_width, int(sum(caps)))
        orders = self._fill_rows(buf, traces, b_bucket)
        t_pack, idx_pack = buf["t_pack"], buf["idx_pack"]
        t_pack.fill(np.inf)
        idx_pack.fill(-1)
        for row, d in enumerate(depth_rows):
            if orders[row] is not None:
                d = d[orders[row]]  # the row was staged in time order
            off = 0
            for p in range(n_stages):
                sel = np.flatnonzero(d == p)
                m = sel.shape[0]
                t_pack[row, off : off + m] = buf["t"][row, sel]
                idx_pack[row, off : off + m] = sel
                off += caps[p]
        return buf, caps

    @staticmethod
    def _fill_rows(
        buf: Dict[str, np.ndarray], traces: Sequence["MemEvents"], b_bucket: int
    ) -> List[Optional[np.ndarray]]:
        """Fill one ``[B, N]`` buffer view (shared by :meth:`stage` and the
        per-session planes of :meth:`stage_stack`).  Returns, per trace,
        the stable sort it was staged in, or None where it was monotone."""
        orders: List[Optional[np.ndarray]] = [None] * len(traces)
        for row in range(b_bucket):
            ev = traces[row] if row < len(traces) else None
            n = ev.n if ev is not None else 0
            if n:
                if np.all(ev.t_ns[1:] >= ev.t_ns[:-1]):
                    t, pool, nbytes, weight, host, qos = (
                        ev.t_ns, ev.pool, ev.bytes_, ev.weight, ev.host, ev.qos
                    )
                else:
                    order = np.argsort(ev.t_ns, kind="stable")
                    orders[row] = order
                    t, pool, nbytes, weight, host, qos = (
                        ev.t_ns[order], ev.pool[order], ev.bytes_[order],
                        ev.weight[order], ev.host[order], ev.qos[order],
                    )
                buf["t"][row, :n] = t
                buf["pool"][row, :n] = pool
                buf["bytes"][row, :n] = nbytes
                buf["weight"][row, :n] = weight
                buf["host"][row, :n] = host
                buf["qos"][row, :n] = qos
                buf["valid"][row, :n] = True
                buf["span"][row] = float(t[-1]) + 1.0
            else:
                buf["span"][row] = 0.0
            buf["t"][row, n:] = 0.0
            buf["pool"][row, n:] = 0
            buf["bytes"][row, n:] = 0.0
            buf["weight"][row, n:] = 0.0
            buf["host"][row, n:] = 0
            buf["qos"][row, n:] = 0
            buf["valid"][row, n:] = False
        return orders

    def stack_buffers(
        self, k_bucket: int, b_bucket: int, n_bucket: int
    ) -> Dict[str, np.ndarray]:
        key = (k_bucket, b_bucket, n_bucket)
        buf = self._stack_bufs.get(key)
        if buf is None:
            td, i32 = self.time_dtype, np.int32
            dtypes = (td, i32, td, td, i32, i32, bool)
            buf = {
                f: np.zeros((k_bucket, b_bucket, n_bucket), dt)
                for f, dt in zip(self._FIELDS, dtypes)
            }
            buf["span"] = np.zeros((k_bucket, b_bucket), np.float64)
            self._stack_bufs[key] = buf
        return buf

    def stage_stack(
        self,
        groups: Sequence[Sequence["MemEvents"]],
        k_bucket: int,
        b_bucket: int,
        n_bucket: int,
    ) -> Dict[str, np.ndarray]:
        """Fill (in place) and return ``[K, B, N]`` buffers: one plane per
        epoch batch, each staged under the exact :meth:`stage` contract —
        the shared engine's cross-session coalescing path.  Planes beyond
        ``len(groups)`` are all-invalid; only planes a previous (larger)
        fill dirtied are re-cleared, and clearing touches just the masks
        the analyzer reads (``valid``/``span``) — stale payload values
        under an invalid mask are never observable."""
        if len(groups) > k_bucket:
            raise ValueError(f"{len(groups)} groups exceed stack bucket {k_bucket}")
        for g in groups:
            if len(g) > b_bucket:
                raise ValueError(f"{len(g)} traces exceed batch bucket {b_bucket}")
        key = (k_bucket, b_bucket, n_bucket)
        buf = self.stack_buffers(*key)
        for k, traces in enumerate(groups):
            plane = {f: buf[f][k] for f in self._FIELDS + ("span",)}
            self._fill_rows(plane, traces, b_bucket)
        for k in range(len(groups), self._stack_filled.get(key, 0)):
            buf["valid"][k] = False
            buf["span"][k] = 0.0
        self._stack_filled[key] = len(groups)
        return buf


# --------------------------------------------------------------------------- #
# Region map — the eBPF allocation-trace analogue
# --------------------------------------------------------------------------- #


@dataclasses.dataclass
class Region:
    """A logical allocation (tensor class or individual buffer)."""

    rid: int
    name: str
    nbytes: int
    tensor_class: str  # 'param' | 'grad' | 'opt_state' | 'activation' | 'kvcache' | 'ssm_state' | 'expert' | 'input' | 'other'
    pool: int = 0  # pool index; set by a placement policy
    access_count: float = 0.0  # running hotness statistic (per epoch window)


class RegionMap:
    """Maps logical regions to pools — the software analogue of the paper's
    eBPF-maintained address-range map.

    ``alloc`` corresponds to tracing mmap/sbrk/brk; ``free`` to munmap.
    Placement policies (:mod:`repro.core.policy`) mutate ``Region.pool``.
    """

    def __init__(self) -> None:
        self._regions: List[Region] = []
        self._by_name: Dict[str, Region] = {}

    def alloc(self, name: str, nbytes: int, tensor_class: str = "other", pool: int = 0) -> Region:
        if name in self._by_name:
            raise KeyError(f"region {name!r} already allocated")
        r = Region(rid=len(self._regions), name=name, nbytes=int(nbytes), tensor_class=tensor_class, pool=pool)
        self._regions.append(r)
        self._by_name[name] = r
        return r

    def free(self, name: str) -> None:
        r = self._by_name.pop(name)
        # keep rid slot (traces may still reference it); mark empty
        r.nbytes = 0

    def __getitem__(self, name: str) -> Region:
        return self._by_name[name]

    def __contains__(self, name: str) -> bool:
        return name in self._by_name

    def __iter__(self) -> Iterator[Region]:
        return iter(self._regions)

    def __len__(self) -> int:
        return len(self._regions)

    @property
    def regions(self) -> List[Region]:
        return list(self._regions)

    def by_class(self, tensor_class: str) -> List[Region]:
        return [r for r in self._regions if r.tensor_class == tensor_class]

    def pool_of(self, name: str) -> int:
        return self._by_name[name].pool

    def pool_vector(self) -> np.ndarray:
        """[n_regions] int32: region id -> pool id (dense lookup table)."""
        out = np.zeros((len(self._regions),), np.int32)
        for r in self._regions:
            out[r.rid] = r.pool
        return out

    def bytes_per_pool(self, n_pools: int) -> np.ndarray:
        out = np.zeros((n_pools,), np.float64)
        for r in self._regions:
            out[r.pool] += r.nbytes
        return out

    def total_bytes(self) -> int:
        return sum(r.nbytes for r in self._regions)


# --------------------------------------------------------------------------- #
# Synthetic traces (tests / microbenchmarks)
# --------------------------------------------------------------------------- #


def synthetic_trace(
    n_events: int,
    n_pools: int,
    epoch_ns: float = 1e6,
    granule_bytes: float = CACHELINE_BYTES,
    pool_probs: Optional[Sequence[float]] = None,
    write_frac: float = 0.3,
    seed: int = 0,
    burstiness: float = 0.0,
    n_qos_classes: int = 1,
    qos_probs: Optional[Sequence[float]] = None,
) -> MemEvents:
    """Random trace generator used by tests and the microbenchmark suite.

    ``burstiness`` in [0, 1): 0 => uniform issue times; near 1 => events
    clustered into bursts (stress for congestion/bandwidth modelling).
    ``n_qos_classes`` > 1 tags events with random QoS classes
    (``qos_probs`` weights the draw; uniform by default).
    """
    rng = np.random.default_rng(seed)
    if pool_probs is None:
        pool_probs = np.full((n_pools,), 1.0 / n_pools)
    pool_probs = np.asarray(pool_probs, np.float64)
    pool_probs = pool_probs / pool_probs.sum()
    if burstiness > 0:
        n_bursts = max(1, int(n_events * (1 - burstiness) / 16) + 1)
        centers = rng.uniform(0, epoch_ns, size=n_bursts)
        t = rng.choice(centers, size=n_events) + rng.exponential(
            scale=max(epoch_ns * (1 - burstiness) * _BURST_SPREAD_FRAC, 1.0),
            size=n_events
        )
        t = np.clip(t, 0, epoch_ns)
    else:
        t = rng.uniform(0, epoch_ns, size=n_events)
    if n_qos_classes > 1:
        qp = (
            np.asarray(qos_probs, np.float64)
            if qos_probs is not None
            else np.full((n_qos_classes,), 1.0 / n_qos_classes)
        )
        qos = rng.choice(n_qos_classes, size=n_events, p=qp / qp.sum())
        qos = qos.astype(np.int32)
    else:
        qos = np.zeros((n_events,), np.int32)
    return MemEvents(
        t_ns=np.sort(t),
        pool=rng.choice(n_pools, size=n_events, p=pool_probs).astype(np.int32),
        bytes_=np.full((n_events,), float(granule_bytes)),
        is_write=rng.random(n_events) < write_frac,
        region=np.zeros((n_events,), np.int32),
        qos=qos,
    )
