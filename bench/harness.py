"""The benchmark's window loop, metric readers and result line.

Everything a cell needs is found by name: ``workloads/<cell>.json`` names
its configuration (``configs/<config>.json``) and its entry kind
(``entries/<kind>.py``); ``BENCHMARK.json`` lists the metrics, and each
metric is read by ``metrics/<metric>.py`` (or, for a suffixed name such
as ``device_idle.step``, by ``metrics/device_idle.py``).  Nothing here
knows a cell.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from contextlib import contextmanager
from types import SimpleNamespace
from typing import Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(*parts: str) -> Dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def manifest() -> Dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def entry_module(kind: str):
    return load_module(os.path.join(BENCH, "entries", f"{kind}.py"), f"bench_entry_{kind}")


def reader(metric: str):
    """``metrics/<metric>.py``, else the reader of the quantity the name
    splits: ``device_idle.step`` and ``device_idle.events`` are both read
    by ``metrics/device_idle.py`` (the suffix names what the metric moves)."""
    path = os.path.join(BENCH, "metrics", f"{metric}.py")
    if not os.path.exists(path) and "." in metric:
        path = os.path.join(BENCH, "metrics", f"{metric.rsplit('.', 1)[0]}.py")
    return load_module(path, f"bench_metric_{metric}")


def cell_metrics(man: Dict, cell: str, trace: bool) -> List[Dict]:
    """The metrics a cell reports: its end-to-end metrics in a timed run,
    its per-layer metrics in a traced one (an entry with a ``workloads``
    list applies to the cells listed there, else to every cell)."""
    group = man["per_layer"] if trace else man["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


@contextmanager
def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


def require_devices(chips: int):
    """The devices a cell runs on; refuses anything but enough TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips; JAX found {len(devs)}")
    return devs


def device_record(devs) -> Dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": peak,
    }


def run_window(entry, seconds: float, tracer=None) -> SimpleNamespace:
    """Whole client calls in a closed loop: the window opens at the first
    call and closes once the first call that ends after ``seconds`` has had
    its pricing folded (the flush is inside the window)."""
    from repro.core.aot import AotDispatchCache

    snap0 = entry.snapshot()
    low0 = AotDispatchCache.total_lowerings()
    calls: List[float] = []
    if tracer is not None:
        tracer.start()
    with span("bench.window"):
        t0 = time.perf_counter()
        while True:
            c0 = time.perf_counter()
            with span(entry.CALL_SPAN):
                entry.call()
            c1 = time.perf_counter()
            calls.append(c1 - c0)
            if c1 - t0 >= seconds:
                break
        with span("bench.flush"):
            entry.flush()
        t1 = time.perf_counter()
    if tracer is not None:
        tracer.stop()
    lowerings = AotDispatchCache.total_lowerings() - low0
    snap1 = entry.snapshot()
    return SimpleNamespace(
        window_s=t1 - t0,
        call_s=calls,
        n_calls=len(calls),
        lowerings=lowerings,
        snap0=snap0,
        snap1=snap1,
    )


def read_metrics(metrics: List[Dict], ctx) -> Dict:
    out = {}
    for m in metrics:
        value = reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def emit(result: Dict, checks: Dict) -> None:
    """Checks last on stderr and last in the result line, which is the last
    line of stdout."""
    result = dict(result)
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} value={c['value']!r} limit={c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
