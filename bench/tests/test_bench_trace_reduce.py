"""trace_reduce on hand-made traces: busy union, idle gaps, module time."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import trace_reduce  # noqa: E402


def _trace():
    # window 0..1000 ns; chip 0 runs two overlapping ops and one late op,
    # chip 1 one op that starts before the window
    return {
        "devices": [
            {"name": "/device:TPU:0", "lines": {
                "XLA Ops": [["fusion.1", 100, 200], ["fusion.2", 250, 150], ["copy.3", 700, 100]],
                "XLA Modules": [["jit__analyze_batch_jax(7)", 100, 300], ["jit_train_step(3)", 700, 100]],
            }},
            {"name": "/device:TPU:1", "lines": {
                "XLA Ops": [["fusion.1", -100, 300]],
                "XLA Modules": [["jit__analyze_batch_jax(9)", -100, 300]],
            }},
        ],
        "spans": [
            ["bench.window", 0, 1000],
            ["bench.round", 0, 600],
            ["bench.flush", 600, 400],
        ],
    }


def test_busy_union_and_window():
    red = trace_reduce.reduce(_trace())
    assert red["window_s"] == pytest.approx(1000e-9)
    # chip 0: [100, 400) + [700, 800) = 400 ns; chip 1: [0, 200) = 200 ns
    assert red["busy_s"] == pytest.approx(300e-9)
    assert red["chips"] == 2


def test_module_time_drops_program_ids_and_clips_to_window():
    red = trace_reduce.reduce(_trace())
    assert red["modules"]["jit__analyze_batch_jax"] == pytest.approx(500e-9)
    assert red["modules"]["jit_train_step"] == pytest.approx(100e-9)
    assert trace_reduce.module_seconds(red, "_analyze_") == pytest.approx(500e-9)
    assert trace_reduce.module_seconds(red, "no_such") is None


def test_idle_gaps_are_labelled_by_the_open_span():
    red = trace_reduce.reduce(_trace())
    # chip 0 idle: [0, 100) round, [400, 700) gap midpoint 550 -> round,
    # [800, 1000) flush
    assert red["idle_gaps"] == [
        ["bench.round", pytest.approx(300e-9)],
        ["bench.flush", pytest.approx(200e-9)],
        ["bench.round", pytest.approx(100e-9)],
    ]
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx(400e-9)


def test_a_trace_without_the_window_span_is_refused():
    tr = _trace()
    tr["spans"] = tr["spans"][1:]
    with pytest.raises(RuntimeError):
        trace_reduce.reduce(tr)


def test_only_tpu_core_planes_count_as_devices():
    assert trace_reduce.is_device_plane("/device:TPU:3")
    assert not trace_reduce.is_device_plane("/device:TPU:0 SparseCore")
    assert not trace_reduce.is_device_plane("/host:CPU")



def test_extract_reads_the_benchmark_spans_from_a_recorded_trace(tmp_path):
    # recorded here on the CPU: host spans only, no TPU plane
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2.0).sum())
    f(jnp.ones((64,))).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.round"):
            f(jnp.ones((64,))).block_until_ready()
    jax.profiler.stop_trace()
    ex = trace_reduce.extract(trace_reduce.find_xplane(str(tmp_path)))
    names = [n for n, _, _ in ex["spans"]]
    assert names.count("bench.window") == 1 and "bench.round" in names
    lo, hi = trace_reduce.window_of(ex)
    assert hi > lo
    assert ex["devices"] == []
    red = trace_reduce.reduce(ex)
    assert red["chips"] == 0 and red["busy_s"] == 0.0


# --------------------------------------------------------------------------- #
# a trace recorded on one TPU v5e (bench.window around 0.3 s of the serve
# cell's fabric rounds), as ``extract`` keeps it
# --------------------------------------------------------------------------- #

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                       "v5e_fabric_rounds.json.gz")


def _chip_trace():
    import gzip
    import json

    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


def _sweep_busy(events, lo, hi):
    """Busy time by an endpoint sweep: a different algorithm from union()."""
    points = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            points += [(a, 1), (b, -1)]
    points.sort(key=lambda p: (p[0], -p[1]))
    busy, depth, last = 0.0, 0, None
    for t, step in points:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_chip_trace_busy_union_matches_a_sweep():
    tr = _chip_trace()
    red = trace_reduce.reduce(tr)
    lo, hi = trace_reduce.window_of(tr)
    per_chip = [_sweep_busy(d["lines"]["XLA Ops"], lo, hi) for d in tr["devices"]
                if d["lines"].get("XLA Ops")]
    assert red["chips"] == len(per_chip) >= 1
    assert red["busy_s"] == pytest.approx(sum(per_chip) / len(per_chip) * 1e-9, rel=1e-12)
    assert 0 < red["busy_s"] < red["window_s"]


def test_chip_trace_gaps_fill_the_window_with_the_busy_time():
    red = trace_reduce.reduce(_chip_trace(), top=10**9)
    idle = sum(s for _, s in red["idle_gaps"])
    assert idle + red["busy_s"] == pytest.approx(red["window_s"], rel=1e-9)
    assert all(n.startswith("bench.") for n, _ in red["idle_gaps"])


def test_chip_trace_analyzer_modules_are_found():
    tr = _chip_trace()
    red = trace_reduce.reduce(tr)
    lo, hi = trace_reduce.window_of(tr)
    want = sum(min(s + d, hi) - max(s, lo) for dev in tr["devices"]
               for n, s, d in dev["lines"].get("XLA Modules", [])
               if "_analyze_" in n and s + d > lo and s < hi) * 1e-9
    got = trace_reduce.module_seconds(red, "_analyze_")
    assert got == pytest.approx(want, rel=1e-12)
    assert got > 0
