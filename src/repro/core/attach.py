"""CXLMemSim.attach — the user-facing simulator (paper Figure 2, assembled).

Wraps any jitted step function.  Per step:

  1. cut the step's structural trace into epochs (Timer), apply migration
     remapping, inject coherency traffic, and run the device-cache tag
     simulation (stateful, main thread) — the cache's per-epoch hit
     fractions become latency-scale vectors shipped with the batch;
  2. submit the step's epoch batch to the Timing Analyzer — by default
     **asynchronously** through the shared
     :class:`~repro.core.engine.AnalysisEngine`: one process-wide
     dispatcher thread serves every attached session (depth-2 backpressure
     per session, cross-session coalescing into stacked dispatches), so
     the analyzer's device work overlaps the next step's native execution
     (the paper's low-overhead attach model);
  3. dispatch the real step and measure native wall time (the paper's
     "execution of the attached program");
  4. optionally ``time.sleep`` the computed delay — the paper's delay
     injection, making the host observe simulated-topology speed (this
     forces synchronous analysis: the delay must exist before it can be
     injected).

All epochs of a step go through :meth:`EpochAnalyzer.analyze_batch` as one
device dispatch; results cross the host boundary once per step, not once
per epoch.  Reading :attr:`AttachedProgram.report` flushes any in-flight
async work first, so observed totals are always consistent.  A batch lost
to an analyzer failure is *accounted*: the error is re-raised once from
``flush()`` and the report's ``dropped_batches`` / ``dropped_epochs``
record the truncation permanently.

Two clocks are reported:

  * ``native_s``    — measured host execution time,
  * ``simulated_s`` — native + Σ delays (what the topology would impose),

plus the per-component delay decomposition, per-pool/switch, per-epoch.
``analyzer_s`` stays the analyzer's own compute seconds (the paper's
overhead accounting) whether or not it overlapped native execution.

A memory program may also be a function of the step's outputs (a decode
step whose routed-expert reads follow that step's own router counts).
Such a step runs natively first; its program is then built (span
``cxlsim.program``) and its epoch batch submitted, so the batch is priced
while the next step runs.  Counters ``cxlsim.experts.held`` /
``cxlsim.experts.touched`` (expert-class regions, and those the step read),
``cxlsim.bytes.expert`` and ``cxlsim.bytes.priced`` (the step program's
expert-class and total bytes) record what each step priced.  A program
that is the same for every step keeps the order above.

``AttachedProgram`` is a context manager; ``with sim.attach(...) as prog``
(or an explicit ``prog.close()``) releases its engine handle.  The shared
engine keeps exactly one dispatcher thread for the whole process — attach
cycles no longer park one worker thread each.

This module attaches **one** program to a private topology.  To co-attach
several programs on one shared fabric — cross-host contention at shared
switches, trace-driven coherency — use
:class:`repro.core.fabric.FabricSession`, which composes the same tracer /
timer / analyzer stack over a merged multi-host timeline (and overlaps its
rounds through the same shared engine).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from ..analysis.annotations import guarded_by
from .analyzer import DelayBreakdown, EpochAnalyzer, FineGrainedSimulator, analyze_any
from .cache import DeviceCacheConfig, DeviceCacheModel
from .coherency import CoherencyModel
from .engine import AnalysisEngine, EngineClient, EngineHandle, fold_dispatch_stats
from .events import MemEvents, RegionMap
from .migration import MigrationSimulator
from .policy import PlacementPolicy, capacity_check
from .spans import count, span
from .timer import EpochSchedule
from .topology import Topology
from .tracer import HardwareModel, Phase, TPU_V5E, class_traffic, synthesize_step_trace
from .units import ns_to_s

__all__ = ["CXLMemSim", "AttachedProgram", "SimReport"]


@dataclasses.dataclass
class SimReport:
    steps: int = 0
    epochs: int = 0
    native_s: float = 0.0
    simulated_s: float = 0.0
    latency_s: float = 0.0
    congestion_s: float = 0.0
    bandwidth_s: float = 0.0
    coherency_s: float = 0.0
    injected_sleep_s: float = 0.0
    analyzer_s: float = 0.0  # simulator's own cost (overhead accounting)
    per_pool_latency_ns: Optional[np.ndarray] = None
    per_switch_congestion_ns: Optional[np.ndarray] = None
    per_switch_bandwidth_ns: Optional[np.ndarray] = None
    qos_classes: int = 1  # arbitration classes of the attached fabric
    per_class_congestion_ns: Optional[np.ndarray] = None  # [qos_classes]
    migration_moved_bytes: float = 0.0
    cache_hit_fraction: float = float("nan")  # device-cache running hit rate
    dropped_batches: int = 0  # analysis batches lost to analyzer failures
    dropped_epochs: int = 0  # their epochs: totals exclude exactly these
    # sharded-dispatch observability (maxima over this session's dispatches)
    devices_used: int = 1  # devices the stacked dispatch sharded over
    shard_rows: int = 0  # per-device rows of the padded leading axis (0=unsharded)
    padded_waste: float = 0.0  # worst padding fraction of the leading axis
    coalesced_group_size: int = 1  # sessions stacked into one dispatch
    # pipeline-phase timing (sums over this session's dispatches)
    stage_s: float = 0.0  # host staging-plane pack time
    transfer_s: float = 0.0  # explicit H2D device_put time
    compile_s: float = 0.0  # AOT lowering time (first dispatch per shape only)
    compute_s: float = 0.0  # exposed device compute (post-overlap)
    enqueue_s: float = 0.0  # the executable calls (part of compute_s)
    wait_s: float = 0.0  # blocked on device outputs (part of compute_s)
    d2h_s: float = 0.0  # outputs copied to the host (part of compute_s)
    slots: int = 0  # dispatched plane slots (B x N per dispatch)
    events: int = 0  # real events among them
    donated_dispatches: int = 0  # dispatches whose input planes were donated
    aot_cache_hits: int = 0  # dispatches served from the AOT executable cache

    @property
    def slowdown(self) -> float:
        """Simulated time / native time — the paper's headline metric."""
        return self.simulated_s / self.native_s if self.native_s > 0 else float("nan")

    @property
    def overhead(self) -> float:
        """(native + analyzer + injected) / native: host-side cost of simulating."""
        if self.native_s <= 0:
            return float("nan")
        return (self.native_s + self.analyzer_s + self.injected_sleep_s) / self.native_s

    def qos_delay_shares(self) -> List[float]:
        """Fraction of switch queueing delay charged to each QoS class."""
        pcc = self.per_class_congestion_ns
        if pcc is None:
            return [1.0]
        total = float(pcc.sum())
        if total <= 0.0:
            return [0.0] * len(pcc)
        return [float(x) / total for x in pcc]

    def summary(self) -> Dict[str, float]:
        """The full report contract — every scalar a benchmark JSON consumer
        needs, key set locked by ``tests/test_engine.py``."""
        return {
            "steps": self.steps,
            "epochs": self.epochs,
            "native_s": self.native_s,
            "simulated_s": self.simulated_s,
            "slowdown": self.slowdown,
            "latency_s": self.latency_s,
            "congestion_s": self.congestion_s,
            "bandwidth_s": self.bandwidth_s,
            "coherency_s": self.coherency_s,
            "injected_sleep_s": self.injected_sleep_s,
            "analyzer_s": self.analyzer_s,
            "overhead": self.overhead,
            "migration_moved_bytes": self.migration_moved_bytes,
            "cache_hit_fraction": self.cache_hit_fraction,
            "dropped_batches": self.dropped_batches,
            "dropped_epochs": self.dropped_epochs,
            "devices_used": self.devices_used,
            "shard_rows": self.shard_rows,
            "padded_waste": self.padded_waste,
            "coalesced_group_size": self.coalesced_group_size,
            "stage_s": self.stage_s,
            "transfer_s": self.transfer_s,
            "compile_s": self.compile_s,
            "compute_s": self.compute_s,
            "enqueue_s": self.enqueue_s,
            "wait_s": self.wait_s,
            "d2h_s": self.d2h_s,
            "slots": self.slots,
            "events": self.events,
            "donated_dispatches": self.donated_dispatches,
            "aot_cache_hits": self.aot_cache_hits,
            "qos_classes": self.qos_classes,
            "qos_delay_shares": self.qos_delay_shares(),
        }


class CXLMemSim:
    """Configure once, attach to any number of step functions."""

    def __init__(
        self,
        topology: Topology,
        policy: PlacementPolicy,
        epoch: EpochSchedule = EpochSchedule("step"),
        hw: HardwareModel = TPU_V5E,
        inject_delays: bool = False,
        sample_rate: float = 1.0,
        migration: Optional[MigrationSimulator] = None,
        cache: Optional[DeviceCacheConfig] = None,
        coherency: Optional[CoherencyModel] = None,
        analyzer: str = "epoch",  # 'epoch' (paper) | 'fine' (Gem5-like baseline)
        n_windows: int = 128,
        check_capacity: bool = True,
        max_events_per_access: int = 64,  # trace fidelity (higher = finer)
        async_analysis: Optional[bool] = None,  # None: auto (see below)
        engine: Optional[AnalysisEngine] = None,  # None: the shared default
        pipeline: bool = False,  # device-resident epoch pipeline (AOT + donation)
        warmup: bool = False,  # pre-compile the pipeline executable at attach
    ):
        self.topology = topology
        self.flat = topology.flatten()
        self.policy = policy
        self.epoch = epoch
        self.hw = hw
        self.inject_delays = inject_delays
        self.sample_rate = sample_rate
        self.migration = migration
        self.cache = cache
        self.coherency = coherency
        self.analyzer_kind = analyzer
        self.n_windows = n_windows
        self.check_capacity = check_capacity
        self.max_events_per_access = max_events_per_access
        self.engine = engine
        self.pipeline = pipeline
        self.warmup = warmup
        # async analysis overlaps analyzer work with native execution; delay
        # injection needs the delay before the step returns, so it forces
        # the synchronous path
        if async_analysis is None:
            async_analysis = analyzer == "epoch" and not inject_delays
        self.async_analysis = bool(async_analysis) and not inject_delays

    def attach(
        self,
        step_fn: Callable[..., Any],
        phases: Union[Sequence[Phase], Callable[[Any], Sequence[Phase]]],
        regions: RegionMap,
        calibration: float = 1.0,
        warm_programs: Sequence[Sequence[Phase]] = (),
    ) -> "AttachedProgram":
        """``phases`` is the step's memory program: one phase list for every
        step, or a function of each step's outputs that returns that step's
        phases.  ``warm_programs`` are phase lists of such a function whose
        dispatch shapes ``warmup`` compiles at attach (every plane bucket
        the steps can reach)."""
        self.policy.place(regions, self.flat)
        if self.check_capacity:
            capacity_check(regions, self.flat)
        return AttachedProgram(self, step_fn, phases, regions, calibration, warm_programs)


class AttachedProgram(EngineClient):
    # the report is folded from the engine's dispatcher thread while the
    # submitting thread accumulates native clocks — every touch locks
    _simlint_guards = guarded_by("_report_lock", "_report")

    def __init__(
        self,
        sim: CXLMemSim,
        step_fn: Callable[..., Any],
        phases: Union[Sequence[Phase], Callable[[Any], Sequence[Phase]]],
        regions: RegionMap,
        calibration: float,
        warm_programs: Sequence[Sequence[Phase]] = (),
    ):
        self.sim = sim
        self.step_fn = step_fn
        # a routing-driven program is built from each step's outputs
        self.program = phases if callable(phases) else None
        self.phases = None if callable(phases) else list(phases)
        self.regions = regions
        self._n_experts_held = sum(1 for r in regions if r.tensor_class == "expert")
        self.calibration = calibration
        if sim.analyzer_kind == "epoch":
            self._analyzer = EpochAnalyzer(
                sim.flat, n_windows=sim.n_windows, pipeline=sim.pipeline
            )
        else:
            self._analyzer = FineGrainedSimulator(sim.flat, bandwidth_mode="per_txn")
        self._cache = (
            DeviceCacheModel(sim.cache, sim.flat, [regions])
            if sim.cache is not None
            else None
        )
        self._report = SimReport(
            per_pool_latency_ns=np.zeros((sim.flat.n_pools,)),
            per_switch_congestion_ns=np.zeros((sim.flat.n_switches,)),
            per_switch_bandwidth_ns=np.zeros((sim.flat.n_switches,)),
            qos_classes=sim.flat.n_qos_classes,
            per_class_congestion_ns=np.zeros((sim.flat.n_qos_classes,)),
        )
        self._report_lock = threading.Lock()
        self._trace_cache: Optional[tuple] = None
        if sim.async_analysis:
            eng = sim.engine if sim.engine is not None else AnalysisEngine.default()
            self._handle: Optional[EngineHandle] = eng.register(self._analyzer)
        else:
            self._handle = None
        if sim.warmup and isinstance(self._analyzer, EpochAnalyzer):
            # pre-compile the pipeline executable on this step's trace shapes
            # so the first real dispatch is a pure AOT-cache hit
            if self.program is None:
                traces, _, _ = self._traces()
                self._analyzer.warmup(traces)
            for warm in warm_programs:
                self._analyzer.warmup(self._traces(list(warm))[0])

    # ------------------------------------------------------------------ #

    @property
    def report(self) -> SimReport:
        """The accumulated report; flushes in-flight async analysis first
        (``flush``/``close``/context-manager semantics come from
        :class:`~repro.core.engine.EngineClient`)."""
        self.flush()
        return self._report  # simlint: ignore[lock-discipline] -- post-flush read: no in-flight fold can race the caller's view

    # ------------------------------------------------------------------ #

    def _traces(self, phases: Optional[List[Phase]] = None):
        """Structural traces of a static program are shape-static per step:
        cached across steps, but recomputed when migration has changed
        residency.  ``phases`` (one step of a routing-driven program) are
        synthesized afresh."""
        if phases is not None:
            return self._synthesize(phases)
        if self._trace_cache is None or self.sim.migration is not None:
            self._trace_cache = self._synthesize(self.phases)
        return self._trace_cache

    def _synthesize(self, phases: List[Phase]):
        mode = "layer" if self.sim.epoch.mode == "layer" else "step"
        traces, native_ns, names = synthesize_step_trace(
            phases,
            self.regions,
            hw=self.sim.hw,
            granularity_bytes=self.sim.policy.granularity_bytes,
            max_events_per_access=self.sim.max_events_per_access,
            calibration=self.calibration,
            epoch_mode=mode,
        )
        if self.sim.epoch.mode == "quantum":
            cut: List[MemEvents] = []
            for tr in traces:
                cut.extend(self.sim.epoch.slices(tr))
            traces = cut
            native_ns = [self.sim.epoch.quantum_ns] * len(traces)
            names = [f"q{i}" for i in range(len(traces))]
        if self.sim.sample_rate < 1.0:
            traces = [t.sample(self.sim.sample_rate, seed=i) for i, t in enumerate(traces)]
        return traces, native_ns, names

    def _epoch_batch(
        self, phases: Optional[List[Phase]] = None
    ) -> Tuple[List[MemEvents], float, Optional[List]]:
        """One step's epoch traces with migration/coherency/cache applied.

        Stateful transforms run on the submitting thread so their epoch
        order is deterministic; only the (pure) analysis is offloaded.
        The device cache observes the *final* per-epoch stream (including
        injected migration and BI traffic, which warms and pollutes it like
        any other access) and returns per-epoch latency-scale vectors."""
        traces, _, _ = self._traces(phases)
        from .events import concat_events  # local import to avoid cycle

        batch: List[MemEvents] = []
        scales: Optional[List] = [] if self._cache is not None else None
        coh_ns_total = 0.0
        for tr in traces:
            if self.sim.migration is not None:
                tr, extra = self.sim.migration.observe_and_migrate(tr)
                if extra.n:
                    tr = concat_events([tr, extra])
            if self.sim.coherency is not None:
                bi, coh_ns = self.sim.coherency.epoch_traffic(tr)
                coh_ns_total += coh_ns
                if bi.n:
                    tr = concat_events([tr, bi])
            if self._cache is not None:
                scales.append(self._cache.observe_scale(tr))
            batch.append(tr)
        if self.sim.migration is not None or self._cache is not None:
            # running-statistic snapshots; written under the report lock —
            # the async dispatcher folds breakdowns under the same lock
            with self._report_lock:
                if self.sim.migration is not None:
                    self._report.migration_moved_bytes = (
                        self.sim.migration.moved_bytes_total
                    )
                if self._cache is not None:
                    self._report.cache_hit_fraction = self._cache.hit_fraction
        return batch, coh_ns_total, scales

    def _fold(
        self, bd: DelayBreakdown, coh_ns: float, analyzer_s: float, n_epochs: int
    ) -> float:
        """Fold one analyzed batch into the report (any thread; locks).

        Returns the batch's total delay in ns.  ``analyzer_s`` accumulates
        the analyzer's own compute time regardless of overlap."""
        delay_ns = bd.total_ns + coh_ns
        with self._report_lock:
            r = self._report
            r.epochs += n_epochs
            r.latency_s += ns_to_s(bd.latency_ns)
            r.congestion_s += ns_to_s(bd.congestion_ns)
            r.bandwidth_s += ns_to_s(bd.bandwidth_ns)
            r.coherency_s += ns_to_s(coh_ns)
            r.per_pool_latency_ns += bd.per_pool_latency_ns
            r.per_switch_congestion_ns += bd.per_switch_congestion_ns
            r.per_switch_bandwidth_ns += bd.per_switch_bandwidth_ns
            if bd.per_class_congestion_ns is not None:
                pcc = np.asarray(bd.per_class_congestion_ns, np.float64)
                if len(pcc) == len(r.per_class_congestion_ns):
                    r.per_class_congestion_ns += pcc
                else:  # qos-off breakdown on a multi-class fabric: all class 0
                    r.per_class_congestion_ns[0] += float(pcc.sum())
            r.simulated_s += ns_to_s(delay_ns)
            r.analyzer_s += analyzer_s
            if self._handle is not None:
                fold_dispatch_stats(
                    r, self._handle.last_dispatch, self._handle.last_group_size
                )
            else:
                fold_dispatch_stats(
                    r, getattr(self._analyzer, "last_dispatch", None), 1
                )
        return delay_ns

    def _analyze_and_accumulate(
        self, batch: List[MemEvents], coh_ns: float, scales: Optional[List] = None
    ) -> float:
        """Synchronous path: analyze one step's epoch batch inline and fold
        it; returns the step's total delay in ns.  A failed batch is
        recorded as dropped before the error propagates, mirroring the
        async engine's accounting."""
        a0 = time.perf_counter()
        try:
            bd = analyze_any(self._analyzer, batch, scales)
        except BaseException:
            with self._report_lock:
                self._report.dropped_batches += 1
                self._report.dropped_epochs += len(batch)
            raise
        elapsed = time.perf_counter() - a0
        return self._fold(bd, coh_ns, elapsed, len(batch))

    def step(self, *args, **kwargs):
        """Run one real step under simulation; returns the step's outputs.

        In async mode the step's epoch batch is submitted *before* the
        native dispatch, so the analyzer works while the step executes;
        totals become visible via :attr:`report` (which flushes).  A
        routing-driven program's batch is submitted *after* its step, and
        is priced while the next step runs."""
        if self.program is None:
            with span("cxlsim.epoch_batch"):
                batch = self._epoch_batch()
            self._submit(batch)

        with span("cxlsim.native") as native:
            out = self.step_fn(*args, **kwargs)
            jax.block_until_ready(out)
        with self._report_lock:
            self._report.native_s += native.seconds
            self._report.simulated_s += native.seconds
            self._report.steps += 1

        if self.program is not None:
            with span("cxlsim.program"):
                phases = list(self.program(out))
                total, expert, touched = class_traffic(phases, self.regions, "expert")
            count("cxlsim.experts.held", self._n_experts_held)
            count("cxlsim.experts.touched", touched)
            count("cxlsim.bytes.expert", int(expert))
            count("cxlsim.bytes.priced", int(total))
            with span("cxlsim.epoch_batch"):
                batch = self._epoch_batch(phases)
            self._submit(batch)

        if self._handle is None:
            delay_ns = self._analyze_and_accumulate(*batch)
            if self.sim.inject_delays and delay_ns > 0:
                # the paper's delay injection: the host program observes the
                # simulated-topology execution speed
                time.sleep(ns_to_s(delay_ns))
                with self._report_lock:
                    self._report.injected_sleep_s += ns_to_s(delay_ns)
        return out

    def _submit(self, batch) -> None:
        """Hand a step's ``(epochs, coh_ns, scales)`` to the async engine."""
        if self._handle is None:
            return
        traces, coh_ns, scales = batch
        n_epochs = len(traces)
        self._handle.submit(
            traces,
            scales,
            fold=lambda bd, elapsed: self._fold(bd, coh_ns, elapsed, n_epochs),
        )

    def run(self, n_steps: int, *args, **kwargs) -> SimReport:
        for _ in range(n_steps):
            self.step(*args, **kwargs)
        self.flush()
        return self._report  # simlint: ignore[lock-discipline] -- post-flush read: no in-flight fold can race the caller's view
