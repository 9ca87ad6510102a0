"""Batched scenario-sweep engine: K (topology × policy × cache × granularity)
configurations in one stacked on-device dispatch.

The paper's headline use case is *exploration* — "experimentation with
memory pooling configurations, scheduling policies, data migration
strategies, and caching techniques that were previously infeasible to
evaluate at scale".  The historical sweep surfaces evaluated one scenario
per Python iteration: re-place regions with a per-``Region`` loop,
re-synthesize the trace, one analyzer dispatch — a 500-point sweep paid
500 dispatches.  :class:`ScenarioSuite` folds the whole sweep into one
``[K, B, N]``-stacked jitted dispatch through the existing fused cascade:

  * **Placement** is a ``[K, R]`` matrix (:func:`~repro.core.policy.
    assign_batch` over the vectorized policy ``assign`` paths); per-event
    pools are gathered on device.
  * **Traces** share one structural skeleton per management granule
    (:func:`~repro.core.tracer.synthesize_skeleton`): times/bytes/region
    ids are placement-independent, so K scenarios pay one synthesis + one
    sort, not K.
  * **Topologies** are numeric variants of one structure
    (:class:`~repro.core.topology.TopologyOverride`), lowered to stacked
    ``[K, ...]`` leaves by :func:`~repro.core.topology.flatten_stack`; the
    route matrix and the cascade's static merge plan are shared, so the
    stack compiles once regardless of K.
  * **Caches** lower to per-scenario latency-scale vectors
    (:meth:`~repro.core.cache.DeviceCacheModel.latency_scale`).

One host transfer returns per-scenario latency/congestion/bandwidth totals
(each matching the sequential ``analyze_ref`` oracle; locked at 1e-4
relative in ``tests/test_scenario.py`` and ``benchmarks/scenario_sweep.py``).
:class:`SweepResult` is the frontier API: best config under capacity /
latency constraints, plus :meth:`ScenarioSuite.successive_halving` for
hillclimb-style refinement sweeps.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Callable, Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import spans
from .analyzer import (
    DelayBreakdown,
    DispatchStats,
    _analyze_sweep_jax,
    bucket_pow2,
    collect_dispatch,
    count_dispatch,
    enqueue_dispatch,
    plan_cascade,
)
from .cache import DeviceCacheConfig, DeviceCacheModel
from .events import RegionMap
from .policy import PlacementPolicy, RegionArrays, assign_batch, bytes_per_pool_batch
from .topology import QosSpec, Topology, TopologyOverride, flatten_stack
from .tracer import (
    HardwareModel,
    Phase,
    TPU_V5E,
    TraceSkeleton,
    skeleton_to_events,
    synthesize_skeleton,
)
from .units import bytes_to_gib, bytes_to_mib, ns_to_ms

__all__ = ["Scenario", "ScenarioSuite", "SweepResult"]


def _class_shares(b: DelayBreakdown) -> List[float]:
    """Per-QoS-class share of a breakdown's congestion delay."""
    pcc = b.per_class_congestion_ns
    if pcc is None:
        return [1.0]
    total = float(pcc.sum())
    if total <= 0.0:
        return [0.0] * len(pcc)
    return [float(x) / total for x in pcc]


@dataclasses.dataclass(frozen=True)
class Scenario:
    """One point of a sweep: placement policy × topology numeric variant ×
    device-cache config.  The management granularity rides on the policy
    (``policy.granularity_bytes``; see
    :meth:`~repro.core.policy.PlacementPolicy.with_granularity`)."""

    policy: PlacementPolicy
    topology: Optional[TopologyOverride] = None
    cache: Optional[DeviceCacheConfig] = None
    qos: Optional[QosSpec] = None
    name: str = ""

    def label(self) -> str:
        if self.name:
            return self.name
        parts = [self.policy.describe()]
        parts.append(self.topology.describe() if self.topology else "base")
        if self.cache is not None:
            parts.append(f"cache={bytes_to_mib(self.cache.capacity_bytes):g}MiB")
        if self.qos is not None:
            parts.append(self.qos.describe())
        return "|".join(parts)


@dataclasses.dataclass
class SweepResult:
    """Per-scenario outcome of one :meth:`ScenarioSuite.run` dispatch."""

    scenarios: List[Scenario]
    breakdowns: List[DelayBreakdown]
    native_ns: float  # roofline-paced native step time (shared: one workload)
    feasible: np.ndarray  # [K] bool: every pool within capacity
    utilization: np.ndarray  # [K, P] bytes placed / capacity
    # sharded-dispatch observability for THIS run's single dispatch
    devices_used: int = 1  # devices the scenario axis sharded over
    shard_rows: int = 0  # scenarios per device after padding (0 = unsharded)
    padded_fraction: float = 0.0  # padded scenario rows / dispatched rows
    # phase timing of this run's dispatch (host pack / H2D / device compute)
    stage_s: float = 0.0
    transfer_s: float = 0.0
    compile_s: float = 0.0  # compiles inside the dispatch (a cold call)
    compute_s: float = 0.0
    qos_classes: int = 1  # QoS class count of this run's dispatch

    @property
    def k(self) -> int:
        return len(self.scenarios)

    def totals_ns(self) -> np.ndarray:
        return np.asarray([b.total_ns for b in self.breakdowns], np.float64)

    def slowdowns(self) -> np.ndarray:
        """Simulated step time over native step time, per scenario."""
        return (self.native_ns + self.totals_ns()) / self.native_ns

    def order(self, require_feasible: bool = True) -> np.ndarray:
        """Scenario indices sorted best-first (lowest total simulated delay);
        infeasible scenarios sort last when ``require_feasible``."""
        key = self.totals_ns().copy()
        if require_feasible:
            key[~self.feasible] = np.inf
        return np.argsort(key, kind="stable")

    def top(self, n: int, require_feasible: bool = True) -> List[int]:
        return [int(i) for i in self.order(require_feasible)[: max(int(n), 1)]]

    def best(
        self,
        max_total_ns: Optional[float] = None,
        max_slowdown: Optional[float] = None,
        require_feasible: bool = True,
    ) -> Optional[int]:
        """Index of the best scenario under the given constraints.

        ``require_feasible`` enforces the capacity constraint (every pool's
        placed bytes within its capacity); ``max_total_ns``/``max_slowdown``
        bound the simulated delay.  Returns None when nothing qualifies.
        """
        totals = self.totals_ns()
        ok = np.ones((self.k,), bool)
        if require_feasible:
            ok &= self.feasible
        if max_total_ns is not None:
            ok &= totals <= max_total_ns
        if max_slowdown is not None:
            ok &= self.slowdowns() <= max_slowdown
        if not ok.any():
            return None
        key = np.where(ok, totals, np.inf)
        return int(np.argmin(key))

    def table(self) -> List[Dict]:
        """One row per scenario — the purchasing-decision table."""
        slow = self.slowdowns()
        return [
            {
                "scenario": s.label(),
                "latency_ms": ns_to_ms(b.latency_ns),
                "congestion_ms": ns_to_ms(b.congestion_ns),
                "bandwidth_ms": ns_to_ms(b.bandwidth_ns),
                "total_ms": ns_to_ms(b.total_ns),
                "slowdown": float(slow[i]),
                "feasible": bool(self.feasible[i]),
                "devices_used": self.devices_used,
                "shard_rows": self.shard_rows,
                "padded_fraction": self.padded_fraction,
                "stage_s": self.stage_s,
                "transfer_s": self.transfer_s,
                "compute_s": self.compute_s,
                "qos_classes": self.qos_classes,
                "qos_delay_shares": _class_shares(b),
            }
            for i, (s, b) in enumerate(zip(self.scenarios, self.breakdowns))
        ]


class ScenarioSuite:
    """Evaluate K scenarios against one workload in one stacked dispatch.

    The workload (``regions`` + ``phases``, e.g. from
    :func:`repro.models.phases.build_regions_and_phases`) and the base
    topology *structure* are fixed per suite; scenarios vary placement,
    numeric topology parameters, device caching and granularity.  Repeated
    :meth:`run` calls at the same ``(K, N)`` bucket reuse the compile cache
    (shapes are bucketed to powers of two like the epoch analyzer's).

    Restricted to the ``'inline'`` analyzer implementation: the scenario
    axis vmaps the fused cascade, and only the pure-XLA path is known to
    vmap on every backend (the Pallas kernel runs epochs via ``lax.map``
    and is still single-topology).
    """

    def __init__(
        self,
        topology: Topology,
        regions: RegionMap,
        phases: Sequence[Phase],
        hw: HardwareModel = TPU_V5E,
        max_events_per_access: int = 64,
        calibration: float = 1.0,
        epoch_mode: str = "step",
        bw_window_ns: float = 10_000.0,
        n_windows: int = 128,
        dtype=jnp.float32,
        mesh=None,
        region_qos: Optional[Mapping[str, int]] = None,
    ):
        """``region_qos`` maps region names to QoS class ids (absent
        regions default to class 0); with it — or a QoS-bearing topology,
        or any scenario carrying a :class:`~repro.core.topology.QosSpec` —
        the sweep routes congestion through the vectorized QoS arbitration
        cascade and reports per-class delay shares."""
        self.topology = topology
        # a ('data',) mesh shards the scenario axis of every run() dispatch
        # (repro.launch.mesh.make_data_mesh); overridable per run
        self.mesh = mesh
        self.regions = regions
        self.phases = list(phases)
        self.hw = hw
        self.max_events_per_access = int(max_events_per_access)
        self.calibration = float(calibration)
        if epoch_mode not in ("step", "layer"):
            raise ValueError(epoch_mode)
        self.epoch_mode = epoch_mode
        self.bw_window_ns = float(bw_window_ns)
        self.n_windows = int(n_windows)
        self.dtype = dtype
        self._np_dtype = np.dtype(jnp.dtype(dtype).name)

        self.base_flat = topology.flatten()
        if self.base_flat.n_switches > 31:
            raise ValueError(
                "scenario sweeps require the fused cascade (<= 31 stages)"
            )
        bits_pool, self._merge_plan, self._stage_order = plan_cascade(self.base_flat)
        self._bits_table = jnp.asarray(bits_pool)
        self._route = jnp.asarray(self.base_flat.route, dtype)
        self.region_arrays = RegionArrays.from_regions(regions)
        self._region_qos = {str(k): int(v) for k, v in (region_qos or {}).items()}
        self._qos_of_region = np.asarray(
            [self._region_qos.get(name, 0) for name in self.region_arrays.names],
            np.int32,
        )
        if (self._qos_of_region < 0).any():
            raise ValueError("region_qos classes must be >= 0")
        self._skeletons: Dict[float, TraceSkeleton] = {}
        self._staged: Dict[Tuple[float, int], Dict[str, np.ndarray]] = {}
        self._sweep_jit = jax.jit(
            _analyze_sweep_jax,
            static_argnames=(
                "stage_order", "n_windows", "n_hosts", "merge_plan", "qos_on",
            ),
        )
        # count at the callable itself so EVERY sweep-kernel dispatch is
        # counted, whatever code path issues it (tests assert 1 per run)
        self.dispatch_count = 0

        def _counted(*args, **kwargs):
            self.dispatch_count += 1
            return self._sweep_jit(*args, **kwargs)

        self._sweep_fn = _counted
        self.last_unique_cascades = 0  # U of the latest run (dedup visibility)
        self.last_dispatch = DispatchStats()  # sharding stats of latest run

    def compile_cache_size(self) -> int:
        """Compiled-graph count of the sweep kernel.  Process-global for
        the underlying function (jit wrappers share caches), so only the
        *delta* across runs is meaningful: a stable value means repeated
        sweeps re-dispatch the same executable — no per-scenario traces
        or compiles."""
        return int(self._sweep_jit._cache_size())

    # ------------------------------------------------------------------ #
    # scenario construction helpers
    # ------------------------------------------------------------------ #

    @staticmethod
    def cartesian(
        policies: Mapping[str, PlacementPolicy],
        overrides: Optional[Mapping[str, Optional[TopologyOverride]]] = None,
        caches: Optional[Mapping[str, Optional[DeviceCacheConfig]]] = None,
        granularities: Optional[Sequence[int]] = None,
    ) -> List[Scenario]:
        """Cartesian scenario grid; names are ``topo/policy[/gN][/cache]``.

        ``granularities`` multiplies every policy by
        :meth:`~repro.core.policy.PlacementPolicy.with_granularity` copies.
        """
        overrides = overrides or {"base": None}
        caches = caches or {"nocache": None}
        pol_items: List[Tuple[str, PlacementPolicy]] = []
        for pname, pol in policies.items():
            if granularities is None:
                pol_items.append((pname, pol))
            else:
                pol_items += [
                    (f"{pname}/g{g}", pol.with_granularity(g)) for g in granularities
                ]
        out = []
        for (tname, ov), (pname, pol), (cname, cache) in itertools.product(
            overrides.items(), pol_items, caches.items()
        ):
            out.append(
                Scenario(
                    policy=pol, topology=ov, cache=cache,
                    name=f"{tname}/{pname}/{cname}",
                )
            )
        return out

    # ------------------------------------------------------------------ #
    # skeleton staging
    # ------------------------------------------------------------------ #

    _bucket = staticmethod(bucket_pow2)

    def skeleton_for(self, granularity_bytes: float) -> TraceSkeleton:
        g = float(granularity_bytes)
        skel = self._skeletons.get(g)
        if skel is None:
            skel = synthesize_skeleton(
                self.phases,
                self.regions,
                self.hw,
                granularity_bytes=g,
                max_events_per_access=self.max_events_per_access,
                calibration=self.calibration,
                epoch_mode=self.epoch_mode,
            )
            self._skeletons[g] = skel
        return skel

    def _staged_group(self, granularity_bytes: float, n_bucket: int):
        """Sorted, padded ``[B, n_bucket]`` arrays for one skeleton —
        built once per (granule, bucket) and reused across runs.

        Deliberately not :class:`~repro.core.events.EventStager`: the
        stager refills mutable per-call buffers from finished
        ``MemEvents`` (pool already resolved), while this stages the
        placement-independent *skeleton* — region ids instead of pools —
        into an immutable cache that whole sweeps alias.  The padding
        contract (bucketing, tail-invalid, span = max t + 1) is shared
        via :func:`~repro.core.analyzer.bucket_pow2` and locked by the
        sweep-vs-``analyze_ref`` oracle tests.
        """
        key = (float(granularity_bytes), int(n_bucket))
        buf = self._staged.get(key)
        if buf is not None:
            return buf
        skel = self.skeleton_for(granularity_bytes)
        B = skel.n_epochs
        fd = self._np_dtype
        buf = {
            "t": np.zeros((B, n_bucket), fd),
            "bytes": np.zeros((B, n_bucket), fd),
            "weight": np.zeros((B, n_bucket), fd),
            "host": np.zeros((B, n_bucket), np.int32),
            "valid": np.zeros((B, n_bucket), bool),
            "region": np.zeros((B, n_bucket), np.int32),
            "span": np.zeros((B,), np.float64),
        }
        for e in range(B):
            lo, hi = int(skel.epoch_ptr[e]), int(skel.epoch_ptr[e + 1])
            n = hi - lo
            if n == 0:
                continue
            t = skel.t_ns[lo:hi]
            if np.all(t[1:] >= t[:-1]):  # single-access epochs stage as-is
                order = slice(None)
            else:
                order = np.argsort(t, kind="stable")  # the group's ONE sort
            buf["t"][e, :n] = t[order]
            buf["bytes"][e, :n] = skel.bytes_[lo:hi][order]
            buf["region"][e, :n] = skel.region[lo:hi][order]
            buf["weight"][e, :n] = 1.0
            buf["valid"][e, :n] = True
            buf["span"][e] = float(buf["t"][e, n - 1]) + 1.0
        self._staged[key] = buf
        return buf

    # ------------------------------------------------------------------ #
    # the stacked dispatch
    # ------------------------------------------------------------------ #

    def run(
        self,
        scenarios: Sequence[Scenario],
        on_overflow: str = "mark",
        mesh=None,
    ) -> SweepResult:
        """Evaluate every scenario in ONE jitted, stacked device dispatch.

        ``on_overflow``: ``'mark'`` records capacity violations in
        ``SweepResult.feasible`` (the frontier API filters on it);
        ``'raise'`` fails fast like :func:`~repro.core.policy.capacity_check`.

        ``mesh`` (defaulting to the suite's) shards the scenario axis over
        the mesh's 'data' devices: K is padded (scenario 0 repeated) to a
        multiple of the device count so shards stay uniform, the K-leading
        arrays are placed pre-sharded, and the skeleton stacks plus the U
        unique cascades replicate — every device runs the (deduped) phase-1
        cascades, then reduces only its own scenario slice, so the host
        transfer stays one ``[K, ...]`` vector.  Padded rows are dropped
        before results are built.  Unsharded runs are bitwise unchanged.
        """
        if on_overflow not in ("mark", "raise"):
            raise ValueError(on_overflow)
        from repro.distributed.sharding import (
            pad_to_multiple, replicated, resolve_data_mesh, shard_rows,
        )
        scenarios = list(scenarios)
        if not scenarios:
            raise ValueError("empty scenario list")
        K = len(scenarios)
        flat = self.base_flat
        P, S, H = flat.n_pools, flat.n_switches, flat.n_hosts
        V = H * P
        ra = self.region_arrays

        # 1. [K, R] placement matrix (vectorized; repeated policies dedup'd)
        assign = assign_batch([s.policy for s in scenarios], ra, flat)
        util_bytes = bytes_per_pool_batch(assign, ra.nbytes, P)
        cap = np.asarray(flat.pool_capacity, np.float64)
        with np.errstate(divide="ignore", invalid="ignore"):
            utilization = np.where(cap[None, :] > 0, util_bytes / cap[None, :], 0.0)
        feasible = (util_bytes <= cap[None, :]).all(axis=1)
        if on_overflow == "raise" and not feasible.all():
            k = int(np.argmin(feasible))
            over = int(np.argmax(util_bytes[k] - cap))
            raise ValueError(
                f"scenario {scenarios[k].label()!r}: pool "
                f"{flat.pool_names[over]} over capacity "
                f"({bytes_to_gib(util_bytes[k, over]):.1f} GiB placed, "
                f"{bytes_to_gib(cap[over]):.1f} GiB available)"
            )
        if flat.host_reachable is not None and not flat.host_reachable.all():
            bad = ~flat.host_reachable[0, assign]
            if bad.any():
                k, r = np.argwhere(bad)[0]
                raise ValueError(
                    f"scenario {scenarios[k].label()!r} places region "
                    f"{ra.names[r]!r} on a pool host 0 cannot reach"
                )

        # 2. granularity groups share one skeleton + one sort each
        grans = sorted({float(s.policy.granularity_bytes) for s in scenarios})
        group_of = np.asarray(
            [grans.index(float(s.policy.granularity_bytes)) for s in scenarios],
            np.int32,
        )
        skels = [self.skeleton_for(g) for g in grans]
        B = skels[0].n_epochs
        n_bucket = self._bucket(
            max(
                (int(np.diff(sk.epoch_ptr).max()) if sk.n else 1)
                for sk in skels
            )
        )
        groups = [self._staged_group(g, n_bucket) for g in grans]
        stack_np = lambda f: np.stack([gr[f] for gr in groups])
        span = np.maximum(stack_np("span"), self.bw_window_ns)  # [G, B]
        bw_window = np.maximum(span / self.n_windows, 1.0)

        # 3. stacked topology leaves (structure shared -> one compiled graph)
        topo_stack = flatten_stack(self.topology, [s.topology for s in scenarios])

        # 3a. the qos axis: per-scenario discipline/weight rows.  Disciplines
        # are numeric data under the vectorized QoS cascade, so K
        # discipline×weight mixes still compile ONE graph; qos_on itself is
        # the only static bit, and all-FIFO suites keep the historical path.
        qos_specs = [s.qos for s in scenarios]
        qos_on = bool(
            flat.has_qos
            or self._qos_of_region.any()
            or any(sp is not None for sp in qos_specs)
        )
        C = int(flat.n_qos_classes)
        if qos_on:
            C = max(
                C,
                int(self._qos_of_region.max(initial=0)) + 1,
                max((sp.n_classes() for sp in qos_specs if sp), default=1),
            )
        disc_base = flat.discipline_codes()  # [S] i32
        w_base = np.ones((S, C), self._np_dtype)
        w_base[:, : flat.n_qos_classes] = flat.class_weight_table()
        disc_np = np.tile(disc_base, (K, 1))
        w_np = np.tile(w_base, (K, 1, 1))
        for k, sp in enumerate(qos_specs):
            if sp is not None:
                sp.apply(disc_np[k], w_np[k], flat.switch_names)

        # 3b. cascade dedup: congestion (and the post-queue times bandwidth
        # windows see) depends only on (granularity group, placement row,
        # STT row — plus the discipline/weight rows when QoS is on) —
        # scenarios differing only in latency/bandwidth/cache share one
        # cascade on device
        stt_np = topo_stack.switch_stt_ns.astype(self._np_dtype)
        cas_index: Dict[Tuple, int] = {}
        cascade_of = np.empty((K,), np.int32)
        cas_rows: List[int] = []
        for k in range(K):
            ck = (int(group_of[k]), assign[k].tobytes(), stt_np[k].tobytes())
            if qos_on:
                ck += (disc_np[k].tobytes(), w_np[k].tobytes())
            u = cas_index.get(ck)
            if u is None:
                u = len(cas_rows)
                cas_index[ck] = u
                cas_rows.append(k)
            cascade_of[k] = u
        cas_rows_np = np.asarray(cas_rows, np.int64)
        cas_group = group_of[cas_rows_np]
        cas_assign = assign[cas_rows_np]
        cas_stt = stt_np[cas_rows_np]
        cas_disc = disc_np[cas_rows_np]
        cas_weights = w_np[cas_rows_np]
        self.last_unique_cascades = len(cas_rows)

        # 4. per-scenario device-cache latency scales (host-side tag model),
        # dedup'd like the cascades: the scale depends only on (granularity
        # group, placement row, cache config, scenario latency leaves), so
        # bandwidth/STT variants share one tag simulation
        lat_scale = np.ones((K, B, V), self._np_dtype)
        scale_cache: Dict[Tuple, np.ndarray] = {}
        for k, s in enumerate(scenarios):
            if s.cache is None:
                continue
            sk = (
                int(group_of[k]),
                assign[k].tobytes(),
                s.cache,
                topo_stack.pool_latency_ns[k].tobytes(),
                topo_stack.pool_media_latency_ns[k].tobytes(),
                float(topo_stack.local_latency_ns[k]),
            )
            rows = scale_cache.get(sk)
            if rows is None:
                model = DeviceCacheModel(s.cache, topo_stack.member(k), [self.regions])
                epochs = skeleton_to_events(
                    self.skeleton_for(s.policy.granularity_bytes), assign[k]
                )
                rows = np.ones((B, V), self._np_dtype)
                for e, tr in enumerate(epochs):
                    sc = model.observe_scale(tr)
                    if sc is not None:
                        rows[e] = sc
                scale_cache[sk] = rows
            lat_scale[k] = rows

        # 5. ONE stacked dispatch; per-scenario totals come back together.
        # With a mesh, the scenario axis is padded to a device multiple
        # (repeating scenario 0 — its cascade/group indices stay valid) and
        # sharded over 'data'; everything per-cascade or structural
        # replicates.
        mesh, n_shards = resolve_data_mesh(
            mesh if mesh is not None else self.mesh, K, what="scenario sweep"
        )
        Kp = pad_to_multiple(K, n_shards)

        def pad_k(a: np.ndarray) -> np.ndarray:
            if Kp == a.shape[0]:
                return a
            return np.concatenate(
                [a, np.repeat(a[:1], Kp - a.shape[0], axis=0)], axis=0
            )

        put_k = lambda a: shard_rows(mesh, jnp.asarray(pad_k(np.asarray(a))))
        put_r = lambda a: replicated(mesh, a)
        fd = self.dtype
        # host staging (pack), H2D transfer, then the dispatch proper — the
        # same phase split DispatchStats reports for the epoch pipeline
        with spans.span("cxlsim.stage") as stage:
            host_r = [
                stack_np("t"), stack_np("bytes"), stack_np("weight"),
                stack_np("host"), stack_np("valid"), stack_np("region"),
                np.asarray(bw_window, self._np_dtype),
            ]
            host_k = [
                group_of, cascade_of, assign, lat_scale,
                np.asarray(topo_stack.pool_latency_ns, self._np_dtype),
                np.asarray(topo_stack.local_latency_ns, self._np_dtype),
                np.asarray(topo_stack.switch_bandwidth_gbps, self._np_dtype),
            ]
        with spans.span("cxlsim.h2d") as h2d:
            dev_r = [put_r(jnp.asarray(a, fd) if a.dtype.kind == "f" else jnp.asarray(a)) for a in host_r]
            dev_cas = [
                put_r(jnp.asarray(cas_group)), put_r(jnp.asarray(cas_assign)),
                put_r(jnp.asarray(cas_stt)), put_r(jnp.asarray(cas_disc)),
                put_r(jnp.asarray(cas_weights)),
                put_r(jnp.asarray(self._qos_of_region)),
            ]
            dev_k = [put_k(a) for a in host_k]
            dev_s = [put_r(self._bits_table), put_r(self._route)]
        out, enqueue_s, compile_s = enqueue_dispatch(
            self._sweep_fn,
            *dev_r,
            *dev_cas,
            *dev_k,
            *dev_s,
            stage_order=self._stage_order,
            n_windows=self.n_windows,
            n_hosts=H,
            merge_plan=self._merge_plan,
            qos_on=qos_on,
        )
        host, wait_s, d2h_s = collect_dispatch(out)
        lat, cong, bw, ppl, psc, psb, phl, phc, phb, pcc = host
        # every scenario row prices its granularity group's [B, N] plane
        valid = host_r[4]
        slots = Kp * int(np.prod(valid.shape[1:]))
        events = int(valid.reshape(len(valid), -1).sum(axis=1)[group_of].sum())
        count_dispatch(slots, events)
        self.last_dispatch = DispatchStats(
            devices_used=n_shards,
            shard_rows=Kp // n_shards if mesh is not None else 0,
            rows=K,
            padded_fraction=float(Kp - K) / Kp,
            stage_s=stage.seconds,
            transfer_s=h2d.seconds,
            compile_s=compile_s,
            enqueue_s=enqueue_s,
            wait_s=wait_s,
            d2h_s=d2h_s,
            slots=slots,
            events=events,
            qos_classes=C,
        )
        breakdowns = [
            DelayBreakdown(
                float(lat[k]), float(cong[k]), float(bw[k]),
                ppl[k].astype(np.float64),
                psc[k].astype(np.float64),
                psb[k].astype(np.float64),
                phl[k].astype(np.float64),
                phc[k].astype(np.float64),
                phb[k].astype(np.float64),
                pcc[k].astype(np.float64),
            )
            for k in range(K)
        ]
        native = float(sum(skels[0].native_ns))
        return SweepResult(
            scenarios=scenarios,
            breakdowns=breakdowns,
            native_ns=native,
            feasible=feasible,
            utilization=utilization,
            devices_used=self.last_dispatch.devices_used,
            shard_rows=self.last_dispatch.shard_rows,
            padded_fraction=self.last_dispatch.padded_fraction,
            stage_s=self.last_dispatch.stage_s,
            transfer_s=self.last_dispatch.transfer_s,
            compile_s=self.last_dispatch.compile_s,
            compute_s=self.last_dispatch.compute_s,
            qos_classes=C,
        )

    # ------------------------------------------------------------------ #
    # hillclimb-style refinement
    # ------------------------------------------------------------------ #

    def successive_halving(
        self,
        scenarios: Sequence[Scenario],
        refine: Callable[[Scenario, int], Iterable[Scenario]],
        rounds: int = 2,
        keep: float = 0.5,
        on_overflow: str = "mark",
    ) -> Tuple[SweepResult, int]:
        """Batched hillclimb: evaluate, keep the best ``keep`` fraction,
        expand survivors via ``refine(scenario, round)``, repeat.

        Every round is one stacked dispatch, so a whole search costs
        ``rounds + 1`` dispatches regardless of population size.  Returns
        the final round's :class:`SweepResult` and its best index.

        Capacity-infeasible scenarios never survive a round while at
        least one feasible scenario exists (``top`` pads with infeasible
        entries only to fill its quota — they are filtered here, so
        refinement budget is not spent expanding capacity violations).
        If the *entire* final population is infeasible the returned index
        is the lowest-delay infeasible scenario; check
        ``result.feasible[index]`` before acting on it.
        """
        pop = list(scenarios)
        res = self.run(pop, on_overflow=on_overflow)
        for r in range(int(rounds)):
            n_keep = int(np.ceil(len(pop) * keep))
            survivors = [
                pop[i] for i in res.top(n_keep) if res.feasible[i]
            ] or [pop[i] for i in res.top(n_keep)]
            children, seen = [], {s.label() for s in survivors}
            for s in survivors:
                for c in refine(s, r):
                    if c.label() not in seen:
                        seen.add(c.label())
                        children.append(c)
            pop = survivors + children
            res = self.run(pop, on_overflow=on_overflow)
        best = res.best()
        if best is None:  # nothing feasible anywhere: least-bad, flagged
            best = int(res.order(require_feasible=False)[0])
        return res, int(best)
