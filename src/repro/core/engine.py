"""Shared async analysis engine — one dispatcher for every attached session.

The paper's central claim is *low-overhead attach*: the Timing Analyzer must
hide behind the attached program's own execution.  Historically each
``CXLMemSim.attach`` owned a private worker thread (one parked thread per
attach) while ``FabricSession`` analyzed synchronously on the critical path.
:class:`AnalysisEngine` replaces both with one process-wide dispatcher:

  * **Sessions register** (:meth:`AnalysisEngine.register`) and get an
    :class:`EngineHandle`; ``handle.submit(traces, scales, fold=...)``
    enqueues one epoch batch and returns a
    :class:`concurrent.futures.Future` resolving to the batch's
    :class:`~repro.core.analyzer.DelayBreakdown`.
  * **Backpressure**: each handle allows ``max_inflight`` outstanding
    batches (default 2 — the historical double-buffered queue depth);
    ``submit`` blocks past that, so a runaway producer cannot grow the
    queue unboundedly.
  * **Cross-session coalescing**: while the dispatcher is busy, submissions
    from *different* sessions accumulate; same-topology sessions (equal
    :func:`dispatch_key` — route matrix, merge plan, numeric leaves,
    window config) are coalesced into one stacked ``[K, B, N]`` jitted
    dispatch (:meth:`~repro.core.analyzer.EpochAnalyzer.analyze_batch_multi`,
    the cross-session analogue of the scenario suite's ``[K, B, N]``
    stacking) with per-session totals.  Two batches of the *same* session
    are never coalesced — each handle's submissions are processed FIFO,
    one dispatch each, so a solo session's async results stay bit-identical
    to its synchronous path.
  * **Thread-safe folding**: the optional ``fold(breakdown, analyzer_s)``
    callback runs on the dispatcher thread after analysis; sessions fold
    into their reports under their own report lock.
  * **Dropped-batch accounting**: a failing batch is *recorded* —
    ``handle.dropped_batches`` / ``dropped_epochs`` — before the error is
    re-raised (once) from ``handle.flush()``.  Truncated report totals are
    therefore always detectable; see ``SimReport.dropped_epochs``.
  * **Lifecycle**: ``handle.close()`` drains and releases a session;
    ``engine.close()`` (or the engine's context manager) drains everything
    and joins the dispatcher thread.  The lazily-created process-default
    engine (:meth:`AnalysisEngine.default`) keeps one daemon dispatcher
    for the whole process — closing handles never leaks a thread per
    attach the way the old per-program pipeline did.

Staging buffers: the engine owns its :class:`~repro.core.events.EventStager`
set (one per analyzer time-dtype), so host staging never shares mutable
buffers with a session's own synchronous analyzer calls on other threads.
"""

from __future__ import annotations

import dataclasses
import threading
from collections import deque
from concurrent.futures import Future, InvalidStateError
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..analysis.annotations import guarded_by, single_threaded
from .analyzer import (
    DelayBreakdown, DispatchStats, EpochAnalyzer, PendingBatch, analyze_any,
)
from .events import EventStager, MemEvents
from .spans import span

__all__ = [
    "AnalysisEngine",
    "EngineClient",
    "EngineHandle",
    "dispatch_key",
    "fold_dispatch_stats",
]


def dispatch_key(analyzer) -> Optional[Tuple]:
    """Coalescing signature: submissions from handles with equal keys may
    share one stacked dispatch.  ``None`` means "never coalesce" (non-epoch
    analyzers, and the Pallas impls whose ``lax.map`` epoch loop is not
    validated under a session vmap).  The key hashes the topology's numeric
    leaves, not object identity, so distinct sessions on equal topologies
    batch together — the same structural-sharing requirement the scenario
    suite's stacked dispatch imposes."""
    if not isinstance(analyzer, EpochAnalyzer) or analyzer.impl != "inline":
        return None
    flat = analyzer.flat
    return (
        bool(analyzer.pipeline),
        bool(analyzer.fused),
        int(analyzer.n_windows),
        jnp.dtype(analyzer.dtype).name,
        float(analyzer.bw_window_ns),
        analyzer._stage_order,
        analyzer._merge_plan,
        int(flat.n_hosts),
        np.asarray(flat.route).tobytes(),
        np.asarray(flat.pool_latency_ns).tobytes(),
        float(flat.local_latency_ns),
        np.asarray(flat.switch_stt_ns).tobytes(),
        np.asarray(flat.switch_bandwidth_gbps).tobytes(),
    )


def fold_dispatch_stats(report, stats, group_size: int) -> None:
    """Fold one dispatch's sharding observability into a report.

    ``report`` is any object with ``devices_used`` / ``shard_rows`` /
    ``padded_waste`` / ``coalesced_group_size`` fields (SimReport,
    FabricReport).  Device counts, shard widths and group sizes keep their
    maxima (did sharding/coalescing ever engage, and how wide); padded
    waste keeps the worst fraction seen.  The timing split
    (``stage_s``/``transfer_s``/``compile_s``/``enqueue_s``/``wait_s``/
    ``d2h_s``, and ``compute_s``, their last three summed) and the
    ``slots``/``events`` counts accumulate across dispatches, and
    ``donated_dispatches``/``aot_cache_hits`` count how often donation and
    the AOT cache engaged.  A coalesced dispatch's timing and counts are
    carried by its first session only (the others see them zeroed), so
    cross-session sharing never double-counts.  Callers hold their report
    lock.
    """
    if stats is not None:
        report.devices_used = max(report.devices_used, stats.devices_used)
        report.shard_rows = max(report.shard_rows, stats.shard_rows)
        report.padded_waste = max(report.padded_waste, stats.padded_fraction)
        report.stage_s += stats.stage_s
        report.transfer_s += stats.transfer_s
        report.compile_s += stats.compile_s
        report.compute_s += stats.compute_s
        report.enqueue_s += stats.enqueue_s
        report.wait_s += stats.wait_s
        report.d2h_s += stats.d2h_s
        report.slots += stats.slots
        report.events += stats.events
        if stats.donated:
            report.donated_dispatches += 1
        if stats.aot_cache_hit:
            report.aot_cache_hits += 1
    if group_size:
        report.coalesced_group_size = max(
            report.coalesced_group_size, int(group_size)
        )


def _peer_view(stats: DispatchStats) -> DispatchStats:
    """A coalesced peer's copy of a dispatch's stats: the sharding facts,
    without the timing and counts the group's first session carries."""
    return dataclasses.replace(
        stats, stage_s=0.0, transfer_s=0.0, compile_s=0.0, enqueue_s=0.0,
        wait_s=0.0, d2h_s=0.0, slots=0, events=0,
    )


@dataclasses.dataclass
class _Submission:
    handle: "EngineHandle"
    traces: List[MemEvents]
    scales: Optional[List]
    fold: Optional[Callable[[DelayBreakdown, float], None]]
    future: Future


@dataclasses.dataclass
class _Launched:
    """One launched-but-unresolved dispatch in the worker's depth-1
    pipeline.  Exactly one of ``pending`` (overlapped solo launch) or
    ``bds`` (synchronously computed results) is set when ``error`` is
    None."""

    group: List[_Submission]
    live: List[_Submission]
    pending: Optional[PendingBatch]
    bds: Optional[List[DelayBreakdown]]
    launch_s: float
    error: Optional[BaseException]


class EngineHandle:
    """One session's port into the engine; created by
    :meth:`AnalysisEngine.register`.  Not constructed directly."""

    # handle state is shared between the submitting thread and the
    # dispatcher; everything mutable rides under the engine's one lock
    _simlint_guards = guarded_by(
        "_cv",
        "_inflight",
        "_error",
        "_closed",
        "dropped_batches",
        "dropped_epochs",
        "_pending",
        "_broken",
    )

    def __init__(
        self,
        engine: "AnalysisEngine",
        analyzer,
        key: Optional[Tuple],
        max_inflight: int,
    ):
        self.engine = engine
        self.analyzer = analyzer
        self.key = key
        if int(max_inflight) < 1:
            raise ValueError(
                f"max_inflight must be >= 1, got {max_inflight} — a 0-depth "
                "handle could never admit a submission"
            )
        self.max_inflight = int(max_inflight)
        self._inflight = 0  # guarded by engine._cv
        self._error: Optional[BaseException] = None
        self._closed = False
        self.dropped_batches = 0
        self.dropped_epochs = 0
        # dispatch observability, written by the dispatcher thread before
        # fold callbacks run (sessions copy these into their reports)
        self.last_dispatch = None  # Optional[DispatchStats]
        self.last_group_size = 0

    # -- session-facing API -------------------------------------------------- #

    def submit(
        self,
        traces: Sequence[MemEvents],
        scales: Optional[Sequence] = None,
        fold: Optional[Callable[[DelayBreakdown, float], None]] = None,
    ) -> Future:
        """Enqueue one epoch batch; returns a Future of its breakdown.

        Blocks while ``max_inflight`` batches of this handle are already in
        flight (backpressure).  ``fold(breakdown, analyzer_s)`` runs on the
        dispatcher thread after analysis, before the future resolves;
        ``analyzer_s`` is this batch's share of the dispatch's compute
        seconds (attributed by epoch count when coalesced)."""
        eng = self.engine
        with eng._cv:
            self._check_open_locked()
            eng._ensure_thread_locked()
            if self._inflight >= self.max_inflight:
                with span("cxlsim.submit_wait"):
                    while self._inflight >= self.max_inflight:
                        self._check_open_locked()
                        eng._cv.wait(1.0)
            self._check_open_locked()
            self._inflight += 1
            fut: Future = Future()
            eng._pending.append(
                _Submission(self, list(traces), None if scales is None else list(scales), fold, fut)
            )
            eng._cv.notify_all()
        return fut

    def flush(self) -> None:
        """Block until every submitted batch of this handle is folded, then
        re-raise the first recorded error (once).  Dropped-batch counters
        persist — the raised error announces the truncation, the counters
        let later readers detect it."""
        eng = self.engine
        with eng._cv:
            while self._inflight > 0:
                if eng._broken:
                    raise RuntimeError("analysis engine dispatcher died")
                eng._cv.wait(1.0)
            err, self._error = self._error, None
        if err is not None:
            raise err

    def close(self) -> None:
        """Drain and release the handle (idempotent).  The engine — and its
        dispatcher thread — stays up for other sessions; closing a handle
        only forbids further submissions on it."""
        try:
            with self.engine._cv:
                closed = self._closed
            if not closed:
                self.flush()
        finally:
            with self.engine._cv:
                self._closed = True
                self.engine._cv.notify_all()

    # -- dispatcher-side helpers -------------------------------------------- #

    def _check_open_locked(self) -> None:
        if self._closed:
            raise RuntimeError(
                "engine handle is closed — submit() after close() would "
                "enqueue work no dispatcher will ever drain"
            )
        if self.engine._closed:
            raise RuntimeError("analysis engine is closed")
        if self.engine._broken:
            raise RuntimeError("analysis engine dispatcher died")

    def _analyze(self, traces, scales, stager) -> DelayBreakdown:
        """Solo analysis of one batch (coalesced groups go through
        :meth:`EpochAnalyzer.analyze_batch_multi` instead)."""
        return analyze_any(self.analyzer, traces, scales, stager=stager)

    def _record_error_locked(self, err: BaseException, n_epochs: int) -> None:
        self.dropped_batches += 1
        self.dropped_epochs += int(n_epochs)
        if self._error is None:
            self._error = err


class EngineClient:
    """Handle-lifecycle plumbing shared by every session type that folds
    through the engine (``AttachedProgram``, ``FabricSession``).

    Subclasses provide ``_handle`` (an :class:`EngineHandle` or ``None``
    for synchronous sessions), ``_report_lock`` and ``_report`` (any
    object with ``dropped_batches`` / ``dropped_epochs`` fields)."""

    _handle: Optional[EngineHandle] = None
    # the report belongs to the session's lock; the handle's drop counters
    # belong to the engine's — _sync_dropped bridges them (never nested)
    _simlint_guards = guarded_by("_report_lock", "_report") | guarded_by(
        "_cv", "_handle.dropped_batches", "_handle.dropped_epochs"
    )

    def flush(self) -> None:
        """Block until every submitted batch has been analyzed and folded.

        Re-raises the first analyzer failure (once); the failed batch's
        epochs stay recorded as ``report.dropped_batches`` /
        ``dropped_epochs`` so truncated totals remain detectable."""
        if self._handle is None:
            return
        try:
            self._handle.flush()
        finally:
            self._sync_dropped()

    def close(self) -> None:
        """Flush and release the engine handle (idempotent).  The shared
        engine's dispatcher thread stays up for other sessions — closing a
        session never parks or leaks a thread."""
        if self._handle is None:
            return
        try:
            self._handle.close()
        finally:
            self._sync_dropped()

    def _sync_dropped(self) -> None:
        # the drop counters are dispatcher-written under the *engine's*
        # lock; snapshot them there, then publish under the report lock
        # (two disjoint critical sections — no nesting, no lock-order edge)
        with self._handle.engine._cv:
            dropped_batches = self._handle.dropped_batches
            dropped_epochs = self._handle.dropped_epochs
        with self._report_lock:
            self._report.dropped_batches = dropped_batches
            self._report.dropped_epochs = dropped_epochs

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class AnalysisEngine:
    """One dispatcher thread serving any number of attached sessions; see
    the module docstring.  ``coalesce=False`` disables cross-session
    stacking (every batch dispatches solo) — a debugging/bisection knob."""

    _simlint_guards = guarded_by(
        "_cv",
        "_pending",
        "_thread",
        "_closed",
        "_broken",
        "_active",
        "_stagers",
        "dispatches",
        "coalesced_dispatches",
        "max_coalesced_sessions",
        "_inflight",
    ) | guarded_by("_default_lock", "_default")

    def __init__(
        self,
        name: str = "cxlmemsim-engine",
        coalesce: bool = True,
        mesh=None,
    ):
        self.name = name
        self.coalesce = bool(coalesce)
        # a ('data',) mesh shards every coalesced dispatch's session axis
        # across devices (repro.launch.mesh.make_data_mesh); None = the
        # analyzer's own mesh (if any), i.e. single-device by default
        self.mesh = mesh
        self._cv = threading.Condition(threading.Lock())
        self._pending: Deque[_Submission] = deque()
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        self._broken = False
        self._active = 0  # dispatches currently executing (guarded by _cv)
        self._stagers: Dict[np.dtype, EventStager] = {}
        # observability (read-only; updated under _cv)
        self.dispatches = 0
        self.coalesced_dispatches = 0
        self.max_coalesced_sessions = 1

    # -- lifecycle ----------------------------------------------------------- #

    _default_lock = threading.Lock()
    _default: Optional["AnalysisEngine"] = None

    @classmethod
    def default(cls) -> "AnalysisEngine":
        """The lazily-created process-wide engine: one daemon dispatcher
        shared by every session that doesn't bring its own engine.  A
        closed — or crashed — default engine is replaced, so one
        dispatcher death never disables async analysis for the rest of
        the process (already-registered handles keep raising; new
        sessions get a fresh engine)."""
        with cls._default_lock:
            d = cls._default
            # reading another engine's _closed/_broken without ITS _cv is a
            # benign race: a stale value only defers replacement by one call
            if d is None or d._closed or d._broken:  # simlint: ignore[lock-discipline] -- benign race: stale _closed/_broken only delays replacing the default engine one call
                cls._default = cls()
            return cls._default

    def register(self, analyzer, max_inflight: int = 2) -> EngineHandle:
        """Attach a session's analyzer; returns its :class:`EngineHandle`.

        ``analyzer`` is an :class:`~repro.core.analyzer.EpochAnalyzer`
        (coalescible when ``impl='inline'``) or any object with ``.flat``
        and ``.simulate`` (dispatched solo)."""
        with self._cv:
            if self._closed:
                raise RuntimeError("analysis engine is closed")
        return EngineHandle(self, analyzer, dispatch_key(analyzer), max_inflight)

    def flush(self) -> None:
        """Block until the queue is empty and no dispatch is running.
        Per-handle errors stay with their handles (``handle.flush``)."""
        with self._cv:
            while self._pending or self._active:
                if self._broken:
                    raise RuntimeError("analysis engine dispatcher died")
                self._cv.wait(1.0)

    def close(self) -> None:
        """Drain outstanding work, stop the dispatcher, join it (idempotent)."""
        with self._cv:
            if self._closed:
                return
            self._closed = True
            self._cv.notify_all()
            thread = self._thread
        if (
            thread is not None
            and thread.is_alive()
            and thread is not threading.current_thread()
        ):
            thread.join()

    def __enter__(self) -> "AnalysisEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- dispatcher ---------------------------------------------------------- #

    def _ensure_thread_locked(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._worker, name=self.name, daemon=True
            )
            self._thread.start()

    @single_threaded("dispatcher-thread only: called from _launch, and the "
                     "engine runs exactly one dispatcher")
    def _stager_for(self, analyzer) -> Optional[EventStager]:
        if not isinstance(analyzer, EpochAnalyzer):
            return None
        dt = np.dtype(jnp.dtype(analyzer.dtype).name)
        st = self._stagers.get(dt)
        if st is None:
            # slots=2: the dispatcher overlaps batch k+1's staging/H2D with
            # batch k's compute, so staging must rotate to a fresh buffer
            # slot while the previous slot's planes may still back an
            # in-flight transfer
            st = self._stagers[dt] = EventStager(dt, slots=2)
        return st

    def _pop_group_locked(self) -> List[_Submission]:
        """FIFO head plus, when coalescing, the first pending submission of
        every *other* same-key handle.  Same-handle batches never share a
        dispatch (bit-stability of the solo path; per-handle FIFO order)."""
        first = self._pending.popleft()
        group = [first]
        if self.coalesce and first.handle.key is not None:
            taken = {id(first.handle)}
            kept: Deque[_Submission] = deque()
            while self._pending:
                sub = self._pending.popleft()
                if sub.handle.key == first.handle.key and id(sub.handle) not in taken:
                    taken.add(id(sub.handle))
                    group.append(sub)
                else:
                    kept.append(sub)
            self._pending = kept
        return group

    def _worker(self) -> None:
        # Depth-1 software pipeline: after launching a dispatch, the worker
        # does NOT block on its result — it first pops and launches the next
        # group (staging + H2D + async device dispatch), so batch k+1's host
        # work overlaps batch k's device compute.  The previous dispatch is
        # finished (device_get, folds, future resolution) only once the next
        # one is in flight, or immediately when the queue drains, so a lone
        # submission never waits on a successor.
        pend: Optional[_Launched] = None
        try:
            while True:
                group = None
                with self._cv:
                    if pend is None:
                        while not self._pending and not self._closed:
                            self._cv.wait(1.0)
                    if self._pending:
                        group = self._pop_group_locked()
                        self._active += 1
                    elif pend is None and self._closed:
                        return  # closed and drained
                if group is not None:
                    launched = self._launch(group)
                    if pend is not None:
                        self._finish(pend)
                    pend = launched
                else:
                    self._finish(pend)
                    pend = None
        except BaseException:
            with self._cv:
                self._broken = True
                self._cv.notify_all()
            raise

    def _launch(self, group: List[_Submission]) -> "_Launched":
        """Stage, transfer and launch one group without blocking on results.

        Solo :class:`EpochAnalyzer` submissions launch asynchronously
        (:meth:`EpochAnalyzer.launch_batch`); DES analyzers and coalesced
        stacks compute synchronously here and carry finished breakdowns.
        Never raises — a launch failure is carried in the returned record
        and surfaced by :meth:`_finish`."""
        stager = self._stager_for(group[0].handle.analyzer)
        live = group
        pending: Optional[PendingBatch] = None
        bds: Optional[List[DelayBreakdown]] = None
        error: Optional[BaseException] = None
        with span("cxlsim.launch") as launch:
            try:
                if len(group) > 1:
                    # per-session validation BEFORE stacking: one session's
                    # bad trace (unreachable route, scales mismatch) must
                    # drop only that session's batch, never its peers'
                    live = []
                    for sub in group:
                        try:
                            sub.handle.analyzer._clean_pairs(sub.traces, sub.scales)
                        except BaseException as e:
                            with self._cv:
                                sub.handle._record_error_locked(e, len(sub.traces))
                            self._resolve(sub.future, error=e)
                        else:
                            live.append(sub)
                if not live:
                    bds = []
                elif (
                    len(live) == 1
                    and isinstance(live[0].handle.analyzer, EpochAnalyzer)
                    and type(live[0].handle.analyzer).analyze_batch
                    is EpochAnalyzer.analyze_batch
                ):
                    # the overlapped fast path talks to launch_batch
                    # directly; subclasses that override analyze_batch
                    # (tests inject failures there) keep the classic
                    # synchronous route
                    sub = live[0]
                    pending = sub.handle.analyzer.launch_batch(
                        sub.traces, sub.scales, stager=stager
                    )
                elif len(live) == 1:
                    sub = live[0]
                    bds = [sub.handle._analyze(sub.traces, sub.scales, stager)]
                else:
                    bds = live[0].handle.analyzer.analyze_batch_multi(
                        [s.traces for s in live],
                        [s.scales for s in live],
                        stager=stager,
                        mesh=self.mesh,
                    )
            except BaseException as e:
                pending, bds, error = None, None, e
        return _Launched(group, live, pending, bds, launch.seconds, error)

    def _finish(self, launched: "_Launched") -> None:
        """Resolve one launched group: block on the device result if it was
        an overlapped launch, run folds, resolve futures, release inflight
        slots."""
        group, live = launched.group, launched.live
        with span("cxlsim.finish"):
            try:
                if launched.error is not None:
                    raise launched.error
                # launch work + exposed finish wait; the overlap gap (spent
                # launching the NEXT group) is deliberately excluded
                elapsed = launched.launch_s
                if launched.pending is not None:
                    bds: List[DelayBreakdown] = [launched.pending.finish()]
                    elapsed += launched.pending.stats.wait_s + launched.pending.stats.d2h_s
                else:
                    bds = launched.bds
                if live:
                    # written before the fold loop so fold callbacks (and any
                    # reader after the future resolves) see this dispatch's
                    # sharding stats on their own handle, even when a peer's
                    # analyzer ran the stacked dispatch
                    stats = getattr(live[0].handle.analyzer, "last_dispatch", None)
                    for j, sub in enumerate(live):
                        sub.handle.last_dispatch = (
                            stats if j == 0 or stats is None else _peer_view(stats)
                        )
                        sub.handle.last_group_size = len(live)
                total_epochs = sum(len(s.traces) for s in live)
                with self._cv:
                    if live:
                        self.dispatches += 1
                    if len(live) > 1:
                        self.coalesced_dispatches += 1
                        self.max_coalesced_sessions = max(
                            self.max_coalesced_sessions, len(live)
                        )
                with span("cxlsim.fold"):
                    for sub, bd in zip(live, bds):
                        # the dispatch's compute seconds are attributed across
                        # the coalesced group by epoch share (evenly when all
                        # batches are empty) so summed analyzer_s never
                        # exceeds real cost
                        if len(live) == 1:
                            share = elapsed
                        elif total_epochs:
                            share = elapsed * len(sub.traces) / total_epochs
                        else:
                            share = elapsed / len(live)
                        try:
                            if sub.fold is not None:
                                sub.fold(bd, share)
                            self._resolve(sub.future, result=bd)
                        except BaseException as e:  # analyzed, not folded: dropped
                            with self._cv:
                                sub.handle._record_error_locked(e, len(sub.traces))
                            self._resolve(sub.future, error=e)
            except BaseException as e:  # whole dispatch failed: every live
                with self._cv:  # batch dropped (validation failures already recorded)
                    for sub in live:
                        sub.handle._record_error_locked(e, len(sub.traces))
                for sub in live:
                    self._resolve(sub.future, error=e)
            finally:
                with self._cv:
                    self._active -= 1
                    for sub in group:
                        sub.handle._inflight -= 1
                    self._cv.notify_all()

    @staticmethod
    def _resolve(fut: Future, result=None, error=None) -> None:
        """Resolve a submission future, tolerating callers that cancelled
        it while pending — an externally-cancelled future must not take
        down the dispatcher (report folding already happened or the drop
        was already recorded; the future is only a notification)."""
        try:
            if error is None:
                fut.set_result(result)
            else:
                fut.set_exception(error)
        except InvalidStateError:
            pass

    def stats(self) -> Dict[str, int]:
        with self._cv:
            return {
                "dispatches": self.dispatches,
                "coalesced_dispatches": self.coalesced_dispatches,
                "max_coalesced_sessions": self.max_coalesced_sessions,
                "pending": len(self._pending),
            }
