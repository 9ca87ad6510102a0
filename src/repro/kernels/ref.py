"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

These are the semantics the kernels must match bit-for-bit (up to fp
accumulation order).  Tests sweep shapes/dtypes and assert_allclose against
these functions with the kernels run in interpret mode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..analysis.annotations import axes

__all__ = [
    "chain_cascade",
    "merge_sorted_runs",
    "qos_cascade_dyn",
    "qos_serial_queue_cascade",
    "serial_queue",
    "serial_queue_cascade",
    "staging_sort",
    "two_run_merge",
    "mha_attention",
    "ssd_naive",
    "ssd_chunked",
]

# queue-discipline codes shared with ``topology.DISCIPLINE_CODES`` (kernels
# do not import core; the mapping is part of the kernel ABI)
DISC_FIFO, DISC_PRIORITY, DISC_WFQ = 0, 1, 2


# --------------------------------------------------------------------------- #
# congestion kernel oracle
# --------------------------------------------------------------------------- #


def serial_queue(t_sorted: jnp.ndarray, mask: jnp.ndarray, stt) -> jnp.ndarray:
    """Start times of a FIFO queue with constant service time over the masked
    subsequence of a time-sorted event stream; unmasked events pass through.

    out_i = max(arr_i, out_{i-1} + stt) over masked events, closed form
    out_i = cummax(arr_i − stt·rank_i) + stt·rank_i.
    """
    f32 = t_sorted.dtype
    stt = jnp.asarray(stt, f32)
    big = jnp.asarray(jnp.finfo(f32).max / 4, f32)
    rank = jnp.cumsum(mask.astype(jnp.int32)) - 1
    rankf = rank.astype(f32)
    g = jnp.where(mask, t_sorted - stt * rankf, -big)
    f = jax.lax.cummax(g)
    return jnp.where(mask, f + stt * rankf, t_sorted)


def merge_sorted_runs(
    x: jnp.ndarray,
    changed: jnp.ndarray,
    *payloads: jnp.ndarray,
    within: jnp.ndarray = None,
):
    """Restore sortedness of ``x`` after a masked serial-queue update.

    ``x`` interleaves two individually-sorted runs: the ``changed`` events
    (whose values a queue just rewrote — FIFO start times are non-decreasing
    along the array) and the rest (still in the previously-sorted order).
    Merging two sorted runs needs no sort: each element's merged position is
    its rank within its own run plus a ``searchsorted`` count against the
    other run.  Ties place changed-run elements first.

    With ``within`` (a superset of ``changed``), only the ``within``
    subsequence is merged — its elements are redistributed over the
    ``within`` positions, everything else stays put.  This is how the
    cascade stitches several sorted runs back together piecewise when a
    topology's stage masks overlap only partially.

    Returns ``(x, *payloads)`` permuted into the merged order.
    """
    n = x.shape[0]
    inf = jnp.asarray(jnp.inf, x.dtype)
    w = jnp.ones_like(changed) if within is None else within
    a = changed
    b = w & ~changed
    idx_a = jnp.cumsum(a.astype(jnp.int32)) - 1
    idx_b = jnp.cumsum(b.astype(jnp.int32)) - 1
    drop = jnp.int32(n)  # out-of-bounds index: dropped by scatter mode='drop'
    a_run = jnp.full((n,), inf, x.dtype).at[jnp.where(a, idx_a, drop)].set(
        x, mode="drop"
    )
    b_run = jnp.full((n,), inf, x.dtype).at[jnp.where(b, idx_b, drop)].set(
        x, mode="drop"
    )
    rank = jnp.where(
        a,
        idx_a + jnp.searchsorted(b_run, x, side="left"),
        idx_b + jnp.searchsorted(a_run, x, side="right"),
    )
    iota = jnp.arange(n, dtype=jnp.int32)
    if within is None:
        pos = rank
    else:
        idx_w = jnp.cumsum(w.astype(jnp.int32)) - 1
        w_pos = jnp.full((n,), drop, jnp.int32).at[jnp.where(w, idx_w, drop)].set(
            iota, mode="drop"
        )
        pos = jnp.where(w, jnp.take(w_pos, rank, mode="clip"), iota)
    return tuple(jnp.zeros_like(p).at[pos].set(p) for p in (x,) + payloads)


# two_run_merge counts with one compare of the short run against the whole
# long run while short x long is at most this many pairs, else with a binary
# search.  On a TPU v5e the compare was never slower at any shape measured, up
# to 2**30 pairs (PERF.md §6, PR 14); past that its work grows as the product.
_COMPARE_ALL_MAX_PAIRS = 1 << 30


@axes("N")
def two_run_merge(x: jnp.ndarray, split, *payloads: jnp.ndarray):
    """Stable merge of two adjacent sorted runs ``[A | B]`` by ranking the
    shorter one.

    ``x[:split]`` (``A``) and ``x[split:]`` (``B``) are individually sorted;
    ``split`` is static.  Ties place ``A`` first, so the result is
    **bitwise identical** to a host stable argsort of ``x``.  Only the
    shorter run is ranked against the longer: ``b_j`` lands at
    ``j + #(A <= b_j)``, ``a_i`` at ``i + #(B < a_i)``.  A scatter as wide
    as the short run marks those slots and one ``cumsum`` over the
    unmarked ones places the long run in order.  The count compares the
    short run against the whole long run at once (no loop) up to
    ``_COMPARE_ALL_MAX_PAIRS`` pairs, else binary-searches per short-run
    entry.

    Padding contract (the device pipeline's): entries keyed ``+inf`` in
    either run sort to the tail, ``A``'s pads before ``B``'s, and never
    perturb the ranks of finite entries.

    Returns ``(x, *payloads)`` permuted into merged order.
    """
    n = x.shape[0]
    w0 = int(split)
    if not 0 <= w0 <= n:
        raise ValueError(f"split {w0} outside [0, {n}]")
    w1 = n - w0
    a, b = x[:w0], x[w0:]
    ws = min(w0, w1)
    method = "compare_all" if w0 * w1 <= _COMPARE_ALL_MAX_PAIRS else "scan"
    if w1 <= w0:  # B short: A first on ties, so count A at-or-below
        cnt = jnp.searchsorted(a, b, side="right", method=method)
        s_off, l_off = w0, 0
    else:  # A short: count B strictly below
        cnt = jnp.searchsorted(b, a, side="left", method=method)
        s_off, l_off = 0, w0
    j = jnp.arange(ws, dtype=jnp.int32)
    # slot -> 1 + source index of the short-run entry placed there, else 0
    mark = (
        jnp.zeros((n,), jnp.int32)
        .at[j + cnt]
        .set(s_off + 1 + j, unique_indices=True, mode="promise_in_bounds")
    )
    short = mark > 0
    src = jnp.where(
        short, mark - 1, l_off - 1 + jnp.cumsum((~short).astype(jnp.int32))
    )
    return tuple(jnp.take(p, src) for p in (x,) + payloads)


@axes("N")
def staging_sort(x: jnp.ndarray, run_caps, *payloads: jnp.ndarray):
    """Sort R concatenated time-sorted runs fully on device.

    ``x`` is the concatenation of ``len(run_caps)`` individually-sorted
    runs, run ``r`` occupying the static slice of width ``run_caps[r]``
    (pad entries keyed ``+inf`` at each run's tail).  A ``ceil(log2 R)``
    round tree of :func:`two_run_merge` calls over adjacent run pairs, each
    split at the static width of its left run, produces the fully-sorted
    order; ties keep the lower run first, so the result is **bitwise
    identical** to a host stable argsort of the run-major concatenation
    (all pads land at the global tail).

    This is the device half of the staging contract: the host packs runs
    (a stable partition, O(copy), zero argsort) and the merge tree replaces
    the per-epoch host ``np.argsort``.

    Returns ``(x, *payloads)`` fully sorted.
    """
    caps = [int(c) for c in run_caps]
    if sum(caps) != x.shape[0]:
        raise ValueError(f"run_caps {caps} do not tile length {x.shape[0]}")
    arrs = (x,) + payloads
    runs = []
    off = 0
    for c in caps:
        if c:
            runs.append((off, c))
        off += c
    while len(runs) > 1:
        nxt = []
        pieces = [[] for _ in arrs]
        cursor = 0

        def flush_gap(lo, hi):
            if hi > lo:
                for j, p in enumerate(arrs):
                    pieces[j].append(p[lo:hi])

        for i in range(0, len(runs) - 1, 2):
            (s0, w0), (s1, w1) = runs[i], runs[i + 1]
            flush_gap(cursor, s0)
            merged = two_run_merge(
                arrs[0][s0 : s1 + w1], w0, *(p[s0 : s1 + w1] for p in arrs[1:])
            )
            for j, m in enumerate(merged):
                pieces[j].append(m)
            nxt.append((s0, w0 + w1))
            cursor = s1 + w1
        if len(runs) % 2:
            nxt.append(runs[-1])
        flush_gap(cursor, x.shape[0])
        arrs = tuple(jnp.concatenate(ps) for ps in pieces)
        runs = nxt
    return arrs


@axes("W", idx_pack="W", stts="D")
def chain_cascade(
    t_pack: jnp.ndarray,  # [W] f32 depth-packed times (+inf pads per segment)
    idx_pack: jnp.ndarray,  # [W] i32 original slot of each event (-1 pads)
    stts: jnp.ndarray,  # [D] f32 service times in stage order
    seg_caps,  # static: per-stage entry-segment capacities, sum == W
):
    """Compact suffix cascade for nested-mask (chained) topologies.

    Eligibility (checked by ``plan_chain``): in deepest-first stage order
    every stage's route mask is a subset of the next stage's — the CXL
    multi-level-switching shape, where an event entering the fabric at
    depth ``d`` traverses every shallower switch on its way to the RC.
    Under that nesting the cascade never needs full-width merges: the
    working array ``A`` holds exactly the events that traverse the current
    stage, each stage folds in the (time-sorted) segment of events whose
    *deepest* switch it is with one :func:`two_run_merge` split at ``A``'s
    static width, and the stage scan runs **unmasked** — its output start
    times are non-decreasing, so ``A`` stays sorted and never splits back
    into runs.  A merge ranks only the shorter run, so a narrow entry
    segment is ranked at the cost of its own width (placing ``A`` is one
    full-width pass), and local-DRAM traffic (no routes) never enters at
    all.

    Per-event final times are bitwise identical to
    :func:`serial_queue_cascade` on tie-free inputs: a compact segment is
    the same subsequence the full-width masked scan sees, with identical
    ranks and the identical ``f + stt*rank`` float chain.  (Exact-time ties
    *across* entry depths may resolve in a different — equally valid FIFO —
    order; per-stage delay sums then still agree.)

    Pads ride along keyed ``+inf`` with ``idx < 0``: merges keep them at
    the tail, the unmasked scan maps them ``+inf -> +inf``, and delay sums
    mask them out.

    Returns ``(t_fin [W], idx [W], per_stage_delay [D])``.
    """
    f32 = t_pack.dtype
    caps = [int(c) for c in seg_caps]
    if sum(caps) != t_pack.shape[0]:
        raise ValueError(f"seg_caps {caps} do not tile length {t_pack.shape[0]}")
    a_t = t_pack[:0]
    a_i = idx_pack[:0]
    per_stage = []
    off = 0
    for p, cap in enumerate(caps):
        if cap:
            seg_t = t_pack[off : off + cap]
            seg_i = idx_pack[off : off + cap]
            if a_t.shape[0] == 0:
                a_t, a_i = seg_t, seg_i
            else:
                a_t, a_i = two_run_merge(
                    jnp.concatenate([a_t, seg_t]),
                    a_t.shape[0],
                    jnp.concatenate([a_i, seg_i]),
                )
            off += cap
        if a_t.shape[0] == 0:
            per_stage.append(jnp.zeros((), f32))
            continue
        stt = stts[p]
        rankf = jnp.arange(a_t.shape[0], dtype=f32)
        g = a_t - stt * rankf
        f = jax.lax.cummax(g)
        start = f + stt * rankf
        real = a_i >= 0
        d = jnp.where(real, start - a_t, 0.0)
        per_stage.append(d.sum())
        a_t = jnp.where(real, start, a_t)
    return a_t, a_i, jnp.stack(per_stage)


def serial_queue_cascade(
    t_sorted: jnp.ndarray,  # [N] f32, globally time-sorted arrivals
    route_bits: jnp.ndarray,  # [N] i32, bit s set iff event traverses stage s
    stts: jnp.ndarray,  # [S] f32, service times in stage order
    merge_plan=None,  # static: per-stage tuple of (changed_bit, within_bit|None)
    hosts: jnp.ndarray = None,  # [N] i32 host ids in sorted order (optional)
    n_hosts: int = 1,  # static; only used when hosts is given
    scan=None,  # stage scan (ts, m, stt, seg, n_seg) -> (start, seg_delay)
):
    """Fused S-stage congestion cascade over one time-sorted epoch.

    Runs every switch's serial queue (deepest stage first, encoded by the
    caller's stage order) over the same array with **one** initial sort: the
    array is kept physically sorted (per stage mask) by *current* time
    throughout, so each stage's scan sees true arrival order.  This
    reproduces the per-stage re-sort of ``analyze_ref`` exactly (up to tie
    attribution at identical float times) without ever re-sorting.

    ``merge_plan`` (static) lists, per stage, the :func:`merge_sorted_runs`
    ops to run *before* that stage's scan: each op names the route-bit of
    the sorted run to fold in and the route-bit of the subsequence to merge
    within (``None`` = whole array).  ``None`` for the whole plan selects
    the conservative schedule — a full two-run merge before every stage,
    folding in the previous stage's events — which is always valid.  The
    epoch analyzer derives a minimal plan from the topology's route matrix
    (nested or disjoint stage masks need no merge at all: a subsequence of
    a sorted run is sorted).  All merges are skipped at runtime while no
    stage has accumulated any delay.

    Returns ``(t_final, slot_idx, per_stage_delay)`` where ``t_final[k]`` is
    the post-congestion time of the event originally at sorted position
    ``slot_idx[k]``, and ``per_stage_delay[s]`` is the summed queueing delay
    at stage ``s``.

    With ``hosts`` (per-event host ids in the same sorted order as
    ``t_sorted``), ``per_stage_delay`` is host-segmented to shape ``[S,
    n_hosts]`` — the shared-fabric decomposition: a stage's queueing delay
    is charged to the host whose event waited.  Hosts are recovered through
    the cascade's live permutation (``hosts[idx]``), so merges need no extra
    payload.

    ``scan`` swaps the per-stage queue (default :func:`_fifo_stage`, pure
    jnp) — the Pallas cascade passes its compiled stage kernel, so both
    paths share this loop and its merges.

    The cascade never sees latencies: device-cache latency scaling
    (:mod:`repro.core.cache`) happens on the caller's side, which is what
    keeps this oracle — and the Pallas kernel it specifies — identical
    across cache-enabled and cache-free analyses.
    """
    f32 = t_sorted.dtype
    n = t_sorted.shape[0]
    s_stages = stts.shape[0]
    if merge_plan is None:
        merge_plan = tuple(((s - 1, None),) if s else () for s in range(s_stages))
    if scan is None:
        scan = _fifo_stage
    ts = t_sorted
    bits = route_bits.astype(jnp.int32)
    idx = jnp.arange(n, dtype=jnp.int32)
    dirty = jnp.zeros((), f32)  # total delay so far; 0 => nothing ever moved
    per_stage = []
    for s in range(s_stages):
        for changed_bit, within_bit in merge_plan[s]:
            changed = (jnp.right_shift(bits, changed_bit) & 1) == 1
            if within_bit is None:
                args = (ts, bits, idx, changed)
                merge = lambda a: merge_sorted_runs(a[0], a[3], a[1], a[2])
            else:
                within = (jnp.right_shift(bits, within_bit) & 1) == 1
                args = (ts, bits, idx, changed, within)
                merge = lambda a: merge_sorted_runs(
                    a[0], a[3], a[1], a[2], within=a[4]
                )
            ts, bits, idx = jax.lax.cond(
                dirty > 0, merge, lambda a: (a[0], a[1], a[2]), args
            )
        m = (jnp.right_shift(bits, s) & 1) == 1
        if hosts is None:
            ts, seg_d = scan(ts, m, stts[s], None, 1)
            per_stage.append(seg_d[0])
        else:
            ts, seg_d = scan(ts, m, stts[s], hosts[idx], n_hosts)
            per_stage.append(seg_d)
        dirty = dirty + seg_d.sum()
    return ts, idx, jnp.stack(per_stage)


def _fifo_stage(ts, m, stt, seg, n_seg):
    """One FIFO stage of :func:`serial_queue_cascade`: ``(start, delay
    summed per segment of seg)`` — the whole stage when ``seg`` is None."""
    big = jnp.asarray(jnp.finfo(ts.dtype).max / 4, ts.dtype)
    start = jnp.where(m, _class_scan(ts, m, stt, big), ts)
    d = jnp.where(m, start - ts, 0.0)
    if seg is None:
        return start, d.sum()[None]
    return start, jax.ops.segment_sum(d, seg, num_segments=n_seg)


# --------------------------------------------------------------------------- #
# QoS arbitration cascades
# --------------------------------------------------------------------------- #


def _class_scan(ts, M, stt_c, big):
    """Serial-queue start times over the ``M`` subsequence with service time
    ``stt_c`` — the shared primitive of every discipline's per-class scan.
    Values are only meaningful at ``M`` positions."""
    f32 = ts.dtype
    rankf = (jnp.cumsum(M.astype(jnp.int32)) - 1).astype(f32)
    g = jnp.where(M, ts - stt_c * rankf, -big)
    f = jax.lax.cummax(g)
    return f + stt_c * rankf


def _qos_fold(ts, bits, idx, qos, s, n_classes, dirty, fifo_like):
    """Restore sortedness after stage ``s``'s per-class scans.

    A discipline's per-class scans leave up to ``C + 1`` interleaved sorted
    runs: each class's start times are non-decreasing along its own
    subsequence (a serial queue never reorders its arrivals), and the
    unmasked events keep their previous order.  ``C`` sequential
    :func:`merge_sorted_runs` calls fold the runs back together — step ``c``
    merges class ``c``'s run *within* the subsequence that excludes the
    not-yet-folded classes ``> c``, so every step is a true two-sorted-run
    merge.  Masks are recomputed from the live permutation after each step.

    ``fifo_like`` (static, or per-stage data under ``jnp.where`` in the
    dynamic path) collapses the fold to the single conservative full merge
    of :func:`serial_queue_cascade`: with every masked event in class 0 the
    first step is the full two-run merge and the rest are identity
    permutations.
    """
    for c in range(n_classes):
        m_cur = (jnp.right_shift(bits, s) & 1) == 1
        q_cur = jnp.take(qos, idx)
        if fifo_like:
            q_cur = jnp.zeros_like(q_cur)
        changed = m_cur & (q_cur == c)
        within = ~(m_cur & (q_cur > c))
        args = (ts, bits, idx, changed, within)
        ts, bits, idx = jax.lax.cond(
            dirty > 0,
            lambda a: merge_sorted_runs(a[0], a[3], a[1], a[2], within=a[4]),
            lambda a: (a[0], a[1], a[2]),
            args,
        )
    return ts, bits, idx


def qos_serial_queue_cascade(
    t_sorted: jnp.ndarray,  # [N] f32, globally time-sorted arrivals
    route_bits: jnp.ndarray,  # [N] i32, bit s set iff event traverses stage s
    stts: jnp.ndarray,  # [S] f32, service times in stage order
    qos: jnp.ndarray,  # [N] i32 QoS class per event, in sorted order
    class_weights: jnp.ndarray,  # [S, C] f32 per-stage WFQ class weights
    disciplines,  # static: tuple of "fifo" | "priority" | "wfq", one per stage
    merge_plan=None,  # static: forwarded to the FIFO fast path
    hosts: jnp.ndarray = None,  # [N] i32 host ids in sorted order (optional)
    n_hosts: int = 1,  # static; only used when hosts is given
):
    """QoS-arbitrated S-stage congestion cascade (static disciplines).

    Extends :func:`serial_queue_cascade` with per-switch queue disciplines:

    * ``fifo`` — the plain serial queue.
    * ``priority`` — strict priority with FIFO within class (class 0
      highest): an event of class ``c`` takes its start time from the FIFO
      scan over the subsequence of classes ``<= c``, i.e. it waits behind
      every earlier higher-or-equal-priority arrival but is invisible to
      them.
    * ``wfq`` — weighted-fair queueing in virtual-time form: class ``c``
      is served as its own FIFO queue with inflated service time
      ``stt * W / w_c`` (``W`` the stage's total weight), the fluid-limit
      GPS approximation where each class owns a ``w_c / W`` bandwidth
      share.

    When every stage is ``fifo`` this function takes *exactly* the
    :func:`serial_queue_cascade` path — same merge schedule, same scan
    arithmetic — so final times and ``idx`` are bitwise identical; the QoS
    class only affects delay attribution.  Mixed disciplines replace the
    caller's ``merge_plan`` with the always-valid per-class fold of
    :func:`_qos_fold` after every stage but the last.

    Returns ``(t_final, slot_idx, per_stage_delay)`` with ``per_stage_delay``
    shaped ``[S, C]`` (no hosts) or ``[S, n_hosts, C]`` (host-segmented):
    stage delay charged to the (host, class) whose event waited.
    """
    f32 = t_sorted.dtype
    n = t_sorted.shape[0]
    s_stages = stts.shape[0]
    n_classes = class_weights.shape[1]
    disciplines = tuple(disciplines)
    if len(disciplines) != s_stages:
        raise ValueError(
            f"{len(disciplines)} disciplines for {s_stages} stages"
        )
    all_fifo = all(d == "fifo" for d in disciplines)
    if merge_plan is None:
        merge_plan = tuple(
            ((s - 1, None),) if s else () for s in range(s_stages)
        )
    big = jnp.asarray(jnp.finfo(f32).max / 4, f32)
    ts = t_sorted
    bits = route_bits.astype(jnp.int32)
    qos = jnp.clip(qos.astype(jnp.int32), 0, n_classes - 1)
    idx = jnp.arange(n, dtype=jnp.int32)
    dirty = jnp.zeros((), f32)
    per_stage = []
    for s in range(s_stages):
        if all_fifo:
            # bitwise serial_queue_cascade merge schedule
            for changed_bit, within_bit in merge_plan[s]:
                changed = (jnp.right_shift(bits, changed_bit) & 1) == 1
                if within_bit is None:
                    args = (ts, bits, idx, changed)
                    merge = lambda a: merge_sorted_runs(a[0], a[3], a[1], a[2])
                else:
                    within = (jnp.right_shift(bits, within_bit) & 1) == 1
                    args = (ts, bits, idx, changed, within)
                    merge = lambda a: merge_sorted_runs(
                        a[0], a[3], a[1], a[2], within=a[4]
                    )
                ts, bits, idx = jax.lax.cond(
                    dirty > 0, merge, lambda a: (a[0], a[1], a[2]), args
                )
        m = (jnp.right_shift(bits, s) & 1) == 1
        stt = stts[s]
        disc = disciplines[s]
        q_cur = jnp.take(qos, idx)
        if disc == "fifo":
            start = jnp.where(m, _class_scan(ts, m, stt, big), ts)
        elif disc == "priority":
            start = ts
            for lvl in range(n_classes):
                sc = _class_scan(ts, m & (q_cur <= lvl), stt, big)
                start = jnp.where(m & (q_cur == lvl), sc, start)
        elif disc == "wfq":
            w_row = class_weights[s]
            w_total = w_row.sum()
            start = ts
            for c in range(n_classes):
                M = m & (q_cur == c)
                sc = _class_scan(ts, M, stt * w_total / w_row[c], big)
                start = jnp.where(M, sc, start)
        else:
            raise ValueError(f"unknown discipline {disc!r}")
        d = jnp.where(m, start - ts, 0.0)
        dsum = d.sum()
        if hosts is None:
            if n_classes == 1:
                per_stage.append(dsum[None])  # bitwise squeeze to FIFO
            else:
                per_stage.append(
                    jax.ops.segment_sum(d, q_cur, num_segments=n_classes)
                )
        else:
            hs = jnp.take(hosts, idx)
            if n_classes == 1:
                per_stage.append(
                    jax.ops.segment_sum(d, hs, num_segments=n_hosts)[:, None]
                )
            else:
                per_stage.append(
                    jax.ops.segment_sum(
                        d, hs * n_classes + q_cur,
                        num_segments=n_hosts * n_classes,
                    ).reshape(n_hosts, n_classes)
                )
        dirty = dirty + dsum
        ts = jnp.where(m, start, ts)
        if not all_fifo and s < s_stages - 1:
            ts, bits, idx = _qos_fold(
                ts, bits, idx, qos, s, n_classes, dirty,
                fifo_like=(disc == "fifo"),
            )
    return ts, idx, jnp.stack(per_stage)


def _f32_sort_key(ts: jnp.ndarray) -> jnp.ndarray:
    """Order-preserving int32 image of an f32 array (IEEE-754 trick: for
    non-negative floats the bit pattern is already monotone; negatives have
    their magnitude bits flipped so more-negative sorts lower)."""
    x = jax.lax.bitcast_convert_type(ts, jnp.int32)
    return jnp.where(x >= 0, x, x ^ jnp.int32(0x7FFFFFFF))


def _qos_rank_fold(ts, bits, idx, run_id, n_runs):
    """Restore global time order after a stage by ONE stable multi-run merge.

    The array interleaves ``n_runs`` individually-sorted runs (per-class
    start-time runs plus the untouched events).  Each element's merged
    position is its rank within its own run plus, per other run ``j``, the
    count of run-``j`` elements that precede it — read off ``searchsorted``
    against run ``j``'s cummax *key envelope* (no scatter compaction:
    within a run, keys are non-decreasing along array positions, so the
    envelope at position ``p`` IS the last run-``j`` key at ``<= p``).

    The merge is **stable**: equal-key elements keep their current array
    order.  This is load-bearing for DES parity — the oracle's heap breaks
    time ties by push sequence, which is exactly the previous stage's
    processing order, i.e. the pre-fold array order.  Stability per run
    ``j`` is three monotone counts clamped together: with ``a`` = #run-j
    strictly below the key, ``a2`` = #run-j at-or-below, and ``pc`` =
    #run-j at earlier array positions, the stable contribution is
    ``clip(pc, a, a2)`` — the run-j elements below count fully, those
    above not at all, and the tied ones exactly when they sit earlier in
    the array (run-j keys are non-decreasing along positions, so its
    first ``pc`` elements are precisely those at earlier positions).

    The per-position counts are one batched scan + cumsum; the result is a
    strict total order, so the final inverse-permutation scatter never
    collides.  Cost: ``2·n_runs`` searchsorteds, two [N, R] scans and ONE
    scatter, versus the ``C`` sequential :func:`merge_sorted_runs` (each
    with its own scatter compactions and payload scatters) this replaces.
    """
    n = ts.shape[0]
    key = _f32_sort_key(ts)
    neg = jnp.iinfo(jnp.int32).min
    iota = jnp.arange(n, dtype=jnp.int32)
    mj = run_id[:, None] == jnp.arange(n_runs, dtype=run_id.dtype)[None, :]
    env = jax.lax.associative_scan(
        jnp.maximum, jnp.where(mj, key[:, None], neg), axis=0
    )  # [N, R]
    pc = jnp.cumsum(mj.astype(jnp.int32), axis=0)  # [N, R] inclusive
    pos = jnp.zeros((n,), jnp.int32)
    for j in range(n_runs):
        p_lo = jnp.searchsorted(env[:, j], key, side="left")
        p_hi = jnp.searchsorted(env[:, j], key, side="right")
        pcj = pc[:, j]
        a = jnp.where(p_lo > 0, jnp.take(pcj, jnp.maximum(p_lo - 1, 0)), 0)
        a2 = jnp.where(p_hi > 0, jnp.take(pcj, jnp.maximum(p_hi - 1, 0)), 0)
        stable = jnp.clip(pcj, a, a2)
        pos = pos + jnp.where(mj[:, j], pcj - 1, stable)
    inv = jnp.zeros((n,), jnp.int32).at[pos].set(iota, unique_indices=True)
    return jnp.take(ts, inv), jnp.take(bits, inv), jnp.take(idx, inv)


def _tropical_stage(ts, m, q_cur, disc, stt, w_row):
    """Start times for one arbitration stage — ONE max-plus associative scan.

    The DES horizon recurrence for every discipline is a tropical affine
    map per class coordinate ``l``: an event of class ``c`` applies
    ``fin[l] -> max(fin[l], t) + s_l = max(fin[l] + s_l, t + s_l)`` to the
    coordinates it updates (priority: ``l >= c``; WFQ: ``l == c`` with the
    weight-inflated service; FIFO: every ``l`` with ``c_eff = 0``).  Maps of
    the form ``f -> max(f + a, b)`` compose coordinate-wise as
    ``(a1, b1) . (a2, b2) = (a1 + a2, max(b1 + a2, b2))`` — associative, so
    the whole stage is one ``associative_scan`` over an ``[N, C]`` pair
    instead of ``C`` per-class cummax scans.  The event's start is
    ``max(t, fin_prefix[c_read])`` with the *exclusive* prefix (shift by
    one), exactly the event-by-event oracle, vectorized.
    """
    f32 = ts.dtype
    n_classes = w_row.shape[0]
    lv = jnp.arange(n_classes, dtype=q_cur.dtype)
    neg = jnp.asarray(-jnp.inf, f32)
    s_l = jnp.where(disc == DISC_WFQ, stt * w_row.sum() / w_row, stt)  # [C]
    q_eff = jnp.where(disc == DISC_FIFO, 0, q_cur)  # [N] read coordinate
    upd = jnp.where(
        disc == DISC_WFQ,
        lv[None, :] == q_eff[:, None],
        lv[None, :] >= q_eff[:, None],
    ) & m[:, None]  # [N, C] coordinates this event pushes forward
    a = jnp.where(upd, s_l[None, :], jnp.asarray(0.0, f32))
    b = jnp.where(upd, ts[:, None] + s_l[None, :], neg)

    def compose(x, y):
        return (x[0] + y[0], jnp.maximum(x[1] + y[0], y[1]))

    acc_a, acc_b = jax.lax.associative_scan(compose, (a, b), axis=0)
    fin = jnp.maximum(acc_a, acc_b)  # applied to the all-zero initial state
    # exclusive prefix: event i sees the horizons BEFORE itself (row 0 sees
    # the all-zero initial state; t >= 0 makes max(t, 0) = t)
    fin = jnp.concatenate([jnp.zeros((1, n_classes), f32), fin[:-1]], axis=0)
    fin_c = jnp.take_along_axis(fin, q_eff[:, None], axis=1)[:, 0]
    return jnp.maximum(ts, fin_c)


def _tropical_dyn_stage(ts, m, q_cur, disc, stt, w_row, seg, n_seg):
    """One stage of :func:`qos_cascade_dyn`: the max-plus scan, then the
    stage's waiting summed per (host, class) segment."""
    start = _tropical_stage(ts, m, q_cur, disc, stt, w_row)
    d = jnp.where(m, start - ts, 0.0)
    if n_seg <= 32:
        # one-hot matmul: far cheaper than a scatter-based segment_sum at
        # small segment counts (a single fused reduction per column)
        oh = seg[:, None] == jnp.arange(n_seg, dtype=jnp.int32)[None, :]
        return start, jnp.dot(
            d, oh.astype(ts.dtype), precision=jax.lax.Precision.HIGHEST
        )
    return start, jax.ops.segment_sum(d, seg, num_segments=n_seg)


@axes(
    "N", route_bits="N", stts="S", qos="N", disc_code="S",
    class_weights="S,C", hosts="N",
)
def qos_cascade_dyn(
    t_sorted: jnp.ndarray,  # [N] f32, globally time-sorted arrivals
    route_bits: jnp.ndarray,  # [N] i32, bit s set iff event traverses stage s
    stts: jnp.ndarray,  # [S] f32, service times in stage order
    qos: jnp.ndarray,  # [N] i32 QoS class per event, in sorted order
    disc_code: jnp.ndarray,  # [S] i32 DISC_* code per stage (traced)
    class_weights: jnp.ndarray,  # [S, C] f32 per-stage class weights (traced)
    hosts: jnp.ndarray = None,  # [N] i32 host ids in sorted order (optional)
    n_hosts: int = 1,  # static; attribution rows (1 when hosts is None)
    stage=None,  # (ts, m, q, disc, stt, w_row, seg, n_seg) -> (start, seg_delay)
):
    """Data-driven QoS cascade: disciplines and weights are *runtime* arrays.

    Same semantics as :func:`qos_serial_queue_cascade`, reformulated so one
    lowering serves every discipline/weight mix — the property that lets a
    ``K``-scenario QoS sweep ride a single vmapped dispatch with zero
    steady-state recompiles.  Two structural optimizations over the static
    spec (identical results on tie-free traces; f32-coincident cross-class
    ties may re-attribute tie-order-ambiguous waiting without changing
    totals):

    * each stage is ONE max-plus associative scan (:func:`_tropical_stage`)
      — the DES horizon recurrence in closed composition form — instead of
      ``C`` per-class cummax scans;
    * the inter-stage fold is ONE *stable* multi-run rank merge
      (:func:`_qos_rank_fold`) instead of ``C`` sequential two-run merges —
      stability reproduces the DES heap's push-sequence tie rule — and is
      *elided* (runtime branch, one lowering) when the NEXT stage is WFQ
      over the same event mask: WFQ events read/update only their own class
      coordinate, and every stage leaves each class subsequence
      non-decreasing in array order, so class-local DES order survives
      without a global re-sort.  The predicate is local and inductive —
      skipped states keep runs = {mask∩class} ∪ {untouched}, exactly what
      the eventual fold's ``run_id`` labels.

    ``stage`` swaps one stage's arbitration and its delay attribution
    (default :func:`_tropical_dyn_stage`); the Pallas cascade passes its
    compiled per-class scans and shares the folds.

    Returns ``(t_final, slot_idx, per_stage_delay[S, H, C])`` where ``H`` is
    ``n_hosts`` (1 when ``hosts`` is None).
    """
    f32 = t_sorted.dtype
    n = t_sorted.shape[0]
    s_stages = stts.shape[0]
    n_classes = class_weights.shape[1]
    ts = t_sorted
    bits = route_bits.astype(jnp.int32)
    qos = jnp.clip(qos.astype(jnp.int32), 0, n_classes - 1)
    idx = jnp.arange(n, dtype=jnp.int32)
    if hosts is None:
        hosts = jnp.zeros((n,), jnp.int32)
        n_hosts = 1
    disc_code = disc_code.astype(jnp.int32)
    if stage is None:
        stage = _tropical_dyn_stage
    n_seg = n_hosts * n_classes
    dirty = jnp.zeros((), f32)
    per_stage = []
    for s in range(s_stages):
        m = (jnp.right_shift(bits, s) & 1) == 1
        q_cur = jnp.take(qos, idx)
        seg = jnp.take(hosts, idx) * n_classes + q_cur
        # a zero-service stage is a DES identity (processed in time order,
        # the horizon never exceeds the current arrival, so start == t and
        # delay == 0 for every discipline) — skip its scan entirely
        start, seg_d = jax.lax.cond(
            stts[s] > 0,
            lambda a: stage(
                a[0], a[1], a[2], disc_code[s], stts[s], class_weights[s],
                a[3], n_seg,
            ),
            lambda a: (a[0], jnp.zeros((n_seg,), f32)),
            (ts, m, q_cur, seg),
        )
        per_stage.append(seg_d.reshape(n_hosts, n_classes))
        dirty = dirty + seg_d.sum()
        ts = jnp.where(m, start, ts)
        if s < s_stages - 1:
            # Elide the fold when the NEXT stage is WFQ over the SAME event
            # mask (traced check — one lowering serves every mix).  WFQ
            # reads/updates only its own class coordinate and every stage
            # leaves each class subsequence non-decreasing in array order,
            # so the class-local DES order (time, then previous-stage
            # processing order) is already the array order.  Inductively the
            # skipped state keeps runs = {mask∩class} ∪ {untouched}, which
            # is exactly what ``run_id`` labels at the eventual fold.
            next_bit = (jnp.right_shift(bits, s + 1) & 1) == 1
            skip = (disc_code[s + 1] == DISC_WFQ) & jnp.all(next_bit == m)
            if s + 1 == s_stages - 1:
                # a trailing zero-service stage is an identity (see above),
                # so it never needs its input re-sorted either
                skip = skip | (stts[s + 1] == 0.0)
            do_fold = (dirty > 0) & jnp.logical_not(skip)
            run_id = jnp.where(
                m, jnp.where(disc_code[s] == DISC_FIFO, 0, q_cur), n_classes
            )
            ts, bits, idx = jax.lax.cond(
                do_fold,
                lambda a: _qos_rank_fold(a[0], a[1], a[2], a[3], n_classes + 1),
                lambda a: (a[0], a[1], a[2]),
                (ts, bits, idx, run_id),
            )
    return ts, idx, jnp.stack(per_stage)


# --------------------------------------------------------------------------- #
# flash-attention oracle
# --------------------------------------------------------------------------- #


def mha_attention(
    q: jnp.ndarray,  # [B, H, Sq, D]
    k: jnp.ndarray,  # [B, Hk, Sk, D]
    v: jnp.ndarray,  # [B, Hk, Sk, D]
    causal: bool = True,
    scale: float | None = None,
    q_offset: int = 0,
) -> jnp.ndarray:
    """Full-matrix GQA attention in f32 (the flash kernel oracle).

    ``q_offset``: absolute position of q[0] (for decode: Sq=1, offset=cache
    length) so causality is computed on absolute positions.
    """
    B, H, Sq, D = q.shape
    Hk = k.shape[1]
    assert H % Hk == 0
    g = H // Hk
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), kk.astype(jnp.float32)
    ) * scale
    if causal:
        Sk = k.shape[2]
        qpos = jnp.arange(Sq) + q_offset
        kpos = jnp.arange(Sk)
        mask = qpos[:, None] >= kpos[None, :]
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", w, vv.astype(jnp.float32))
    return out.astype(q.dtype)


# --------------------------------------------------------------------------- #
# Mamba2 SSD oracles
# --------------------------------------------------------------------------- #


def ssd_naive(
    x: jnp.ndarray,  # [B, L, H, P]   (P = head dim)
    dt: jnp.ndarray,  # [B, L, H]      (softplus-activated step)
    A: jnp.ndarray,  # [H]            (negative; per-head scalar decay rate)
    Bm: jnp.ndarray,  # [B, L, N]      (input projection onto state, 1 group)
    Cm: jnp.ndarray,  # [B, L, N]      (state readout, 1 group)
) -> jnp.ndarray:
    """Sequential state-space recurrence (the exact semantics):

        h_t = exp(A·dt_t) ⊙ h_{t−1} + dt_t · B_t ⊗ x_t        h ∈ [N, P]
        y_t = C_t · h_t
    """
    Bsz, L, H, P = x.shape
    N = Bm.shape[-1]
    f32 = jnp.float32

    def one_head(xh, dth, Ah, Bmh, Cmh):
        # xh [L,P], dth [L], Bmh/Cmh [L,N]
        decay = jnp.exp(Ah * dth)  # [L]

        def step(h, inp):
            xt, dt_t, dec, bt, ct = inp
            h = dec * h + dt_t * (bt[:, None] * xt[None, :])  # [N,P]
            y = ct @ h  # [P]
            return h, y

        h0 = jnp.zeros((N, P), f32)
        _, ys = jax.lax.scan(step, h0, (xh, dth, decay, Bmh, Cmh))
        return ys  # [L,P]

    out = jax.vmap(  # over batch
        jax.vmap(  # over heads
            one_head, in_axes=(1, 1, 0, None, None), out_axes=1
        ),
        in_axes=(0, 0, None, 0, 0),
        out_axes=0,
    )(x.astype(f32), dt.astype(f32), A.astype(f32), Bm.astype(f32), Cm.astype(f32))
    return out.astype(x.dtype)  # [B, L, H, P]


def ssd_chunked(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    A: jnp.ndarray,
    Bm: jnp.ndarray,
    Cm: jnp.ndarray,
    chunk: int = 64,
) -> jnp.ndarray:
    """Chunked SSD (state-space duality) — the blocked algorithm the Pallas
    kernel implements: quadratic attention-like math within chunks, linear
    state passing between chunks.  Must agree with :func:`ssd_naive`.
    """
    Bsz, L, H, P = x.shape
    N = Bm.shape[-1]
    assert L % chunk == 0, "sequence must be divisible by chunk"
    C = L // chunk
    f32 = jnp.float32

    x_ = x.astype(f32).reshape(Bsz, C, chunk, H, P)
    dt_ = dt.astype(f32).reshape(Bsz, C, chunk, H)
    B_ = Bm.astype(f32).reshape(Bsz, C, chunk, N)
    C_ = Cm.astype(f32).reshape(Bsz, C, chunk, N)
    A_ = A.astype(f32)

    # per-position log decay a_t = A·dt_t ; cumulative within chunk
    a = A_[None, None, None, :] * dt_[..., :]  # [B,C,c,H]
    acum = jnp.cumsum(a, axis=2)  # inclusive cumsum within chunk

    # ---- intra-chunk (quadratic, like masked attention) ------------------- #
    # y_intra[t] = Σ_{s≤t} C_t·B_s dt_s exp(acum_t − acum_s) x_s
    seg = acum[:, :, :, None, :] - acum[:, :, None, :, :]  # [B,C,t,s,H]
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    seg = jnp.where(tri[None, None, :, :, None], seg, -jnp.inf)
    G = jnp.einsum("bctn,bcsn->bcts", C_, B_)  # [B,C,t,s]
    W = G[..., None] * jnp.exp(seg) * dt_[:, :, None, :, :]  # [B,C,t,s,H]
    y_intra = jnp.einsum("bctsh,bcshp->bcthp", W, x_)

    # ---- chunk states ------------------------------------------------------ #
    # state_c = Σ_s B_s dt_s exp(acum_last − acum_s) x_s   ∈ [N,P]
    decay_to_end = jnp.exp(acum[:, :, -1:, :] - acum)  # [B,C,c,H]
    S = jnp.einsum(
        "bcsn,bcsh,bcshp->bchnp", B_, dt_ * decay_to_end, x_
    )  # [B,C,H,N,P]
    chunk_decay = jnp.exp(acum[:, :, -1, :])  # [B,C,H]

    # ---- inter-chunk scan --------------------------------------------------- #
    def scan_fn(h, inp):
        S_c, dec_c = inp  # [B,H,N,P], [B,H]
        h_out = h  # state BEFORE this chunk
        h = dec_c[..., None, None] * h + S_c
        return h, h_out

    h0 = jnp.zeros((Bsz, H, N, P), f32)
    _, h_prev = jax.lax.scan(
        scan_fn,
        h0,
        (jnp.moveaxis(S, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    h_prev = jnp.moveaxis(h_prev, 0, 1)  # [B,C,H,N,P] state entering chunk

    # ---- inter-chunk contribution ------------------------------------------ #
    # y_inter[t] = C_t · (exp(acum_t) ⊙ h_prev)
    y_inter = jnp.einsum(
        "bctn,bcth,bchnp->bcthp", C_, jnp.exp(acum), h_prev
    )

    y = (y_intra + y_inter).reshape(Bsz, L, H, P)
    return y.astype(x.dtype)
