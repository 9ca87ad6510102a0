"""The control of a cell: the reference put in the program's place and
carried in bfloat16, the precision below the float32 the simulator
states, read by the same comparison that decides ``correct``.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

Prints, per seed, each compared number of the control beside the cell's
limit; every seed has to fail at least one.  It needs no chip: what is
compared is the reference's own float64 pricing against its bfloat16
copy, on the cell's full-size epochs.  The benchmark's own runs never run
it.
"""

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import compare  # noqa: E402
import harness  # noqa: E402
from reference import oracle  # noqa: E402


def control_readings(entry_mod, cfg, wl, seed):
    """The control's compared numbers for one call; a reference that prices
    several rows per call (the fleet) reads the worst row."""
    ref = entry_mod.reference(cfg, wl, seed)
    exact, ctrl = ref.expected(), ref.expected(oracle.round_bf16)
    if "latency" in exact:
        return compare.class_gaps(ctrl, exact)
    out = None
    for row in exact:
        out = compare.worst(compare.class_gaps(ctrl[row], exact[row]), out)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Read a cell's bfloat16 control.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    args = ap.parse_args(argv)
    wl = harness.load_json("workloads", args.workload + ".json")
    cfg = harness.load_json("configs", wl["config"] + ".json")
    mod = harness.entry_module(wl["entry"])
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        readings = control_readings(mod, cfg, wl, seed)
        checks = compare.checks(readings, wl["limits"])
        fails = not compare.all_within(checks)
        failed_all &= fails
        print(json.dumps({"workload": args.workload, "seed": seed, "control_fails": fails,
                          "seconds": time.perf_counter() - t0, "checks": checks}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
