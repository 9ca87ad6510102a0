"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights, compiles from the persistent cache, warm calls) counts as
``setup_s``; then whole client calls run in a closed loop for ``--seconds``
and the window closes once the last call's pricing has folded.  After the
window the f64 reference in ``bench/reference`` re-prices the same epochs
and every compared number is printed beside its limit.  ``--trace 1``
records a profiler trace of the window and reports the per-layer metrics;
``--trace 0`` reports the end-to-end ones.  The last line of stdout is the
result as one JSON object.  Without enough TPUs it exits non-zero and
prints no result.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import compare  # noqa: E402
import harness  # noqa: E402
import trace_reduce  # noqa: E402


class Tracer:
    """A profiler trace of the window, kept under TMPDIR until reduced.
    The Python tracer stays off: it would record every Python call of the
    host loop, slowing it and swelling the trace; the benchmark's own
    spans come from the host tracer."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")

    def start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        import jax

        jax.profiler.stop_trace()

    def reduce(self):
        try:
            return trace_reduce.reduce(trace_reduce.extract(trace_reduce.find_xplane(self.dir)))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, devices=harness.require_devices) -> int:
    args = parse(argv)
    man = harness.manifest()
    cells = {w["name"]: w for w in man["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"bench: no cell {args.workload!r} in BENCHMARK.json")
    cell = cells[args.workload]
    wl = harness.load_json("workloads", args.workload + ".json")
    cfg = harness.load_json("configs", wl["config"] + ".json")
    if wl["chips"] != cell["chips"] or wl["config"] != cell["config"] or wl["traffic"] != cell["traffic"]:
        raise SystemExit(f"bench: workloads/{args.workload}.json disagrees with BENCHMARK.json")
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise SystemExit("bench: the program under test (src/repro) is not in this checkout")

    from repro.core.aot import install_persistent_cache

    install_persistent_cache()
    devs = devices(wl["chips"])
    peaks = harness.load_json("peaks.json").get(devs[0].device_kind)
    if peaks is None:
        raise SystemExit(f"bench: no peaks for device kind {devs[0].device_kind!r}")

    entry = harness.entry_module(wl["entry"]).Entry(cfg, wl, args.seed)
    tracer = Tracer() if args.trace else None
    setup_s = time.perf_counter() - T0
    win = harness.run_window(entry, args.seconds, tracer)
    device = harness.device_record(devs)
    red = tracer.reduce() if tracer is not None else None
    entry.close()

    ref = entry.expected()
    checks = compare.checks(entry.readings(win, ref), wl["limits"])
    d = lambda f: (  # noqa: E731
        None if win.snap0.get(f) is None else win.snap1[f] - win.snap0[f]
    )
    ctx = SimpleNamespace(
        entry=wl["entry"], setup_s=setup_s, window_s=win.window_s, n_calls=win.n_calls,
        call_s=win.call_s, events=win.n_calls * entry.events_per_call,
        delta={f: d(f) for f in ("native_s", "stage_s", "transfer_s", "compile_s", "compute_s")},
        filled=entry.FILLED, lowerings=win.lowerings, trace=red, peaks=peaks,
        hosts=entry.hosts, qos_on=entry.qos_on,
    )
    metrics = harness.read_metrics(harness.cell_metrics(man, args.workload, bool(args.trace)), ctx)
    result = {
        "correct": compare.all_within(checks),
        "attempted": win.n_calls,
        "failed": int(win.snap1["dropped"] - win.snap0["dropped"]),
        "metrics": metrics,
        "device": device,
    }
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
