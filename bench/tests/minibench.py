"""A copy of the benchmark at test size, run on the CPU in a subprocess.

``make_tree`` copies ``bench/`` into a temporary checkout, links the
program's ``src/`` beside it, and adds one tiny configuration per entry
kind, a CPU row in the peaks table and a manifest of tiny cells.  ``run``
drives ``run.main`` there with the chip check replaced by the CPU devices,
optionally after a snippet that breaks the program underneath, and
returns the result line and the process's stderr.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import textwrap

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

TINY_MODEL = {
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "vocab_size": 512, "tie_word_embeddings": True, "rope_theta": 10000,
    "qk_norm": True, "mlp_gated": True,
}

LIMITS = {"latency_gap": 1e-4, "congestion_gap": 1e-4, "bandwidth_gap": 1e-4, "epochs_gap": 0}


def _config(name: str, base: str) -> dict:
    with open(os.path.join(BENCH, "configs", base + ".json")) as f:
        cfg = json.load(f)
    cfg["name"] = name
    cfg["model"] = dict(TINY_MODEL)
    return cfg


def make_tree(tmp: str) -> str:
    root = os.path.join(tmp, "checkout")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    b = os.path.join(root, "bench")

    def dump(obj, *parts):
        with open(os.path.join(b, *parts), "w") as f:
            json.dump(obj, f)

    dump(_config("tiny-fig1", "fig1-qwen3-0.6b"), "configs", "tiny-fig1.json")
    dump(_config("tiny-pool4", "pool4-starcoder2-3b"), "configs", "tiny-pool4.json")
    dump({"tenants": [{"kind": "train", "batch": 2, "seq": 16}]}, "traffic", "tiny-train.json")
    dump({"tenants": [
        {"kind": "train", "batch": 2, "seq": 16},
        {"kind": "decode", "batch": [2, 4], "cache_len": [32, 64]},
        {"kind": "decode", "batch": [2, 4], "cache_len": [32, 64]},
        {"kind": "train", "batch": 2, "seq": 16},
    ]}, "traffic", "tiny-mixed.json")
    dump({"racks": 3, "hosts_per_rack": 4, "offload_fractions": [0.0, 1.0], "sampled_rows": 2,
          "rack_tenants": [{"kind": "train", "batch": 2, "seq": 16},
                           {"kind": "decode", "batch": [2, 4], "cache_len": [32, 64]}] * 2},
         "traffic", "tiny-fleet.json")
    cells = [
        ("tiny.attach", "tiny-fig1", "tiny-train", "attach", 1),
        ("tiny.fabric", "tiny-pool4", "tiny-mixed", "fabric", 1),
        ("tiny.fleet", "tiny-pool4", "tiny-fleet", "fleet", 4),
    ]
    for name, cfg, traffic, entry, chips in cells:
        limits = dict(LIMITS)
        if entry == "fleet":
            limits["rows_gap"] = limits.pop("epochs_gap")
        dump({"config": cfg, "traffic": traffic, "entry": entry, "chips": chips,
              "warm_calls": 1, "limits": limits}, "workloads", name + ".json")
    with open(os.path.join(b, "peaks.json")) as f:
        peaks = json.load(f)
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="test row: the v5e's numbers")
    dump(peaks, "peaks.json")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    man["workloads"] = [
        {"name": n, "config": c, "traffic": t, "chips": k, "why": "test"}
        for n, c, t, _, k in cells
    ]
    for m in man["end_to_end"] + man["per_layer"]:
        m.pop("workloads", None)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root


DRIVER = """
import sys
sys.argv = ["run.py"] + {argv!r}
sys.path.insert(0, "bench")
sys.path.insert(0, "src")
import jax
{fault}
import run
sys.exit(run.main(sys.argv[1:], devices=lambda chips: jax.devices()[:chips]))
"""


def run(root: str, cell: str, seed: int = 7, seconds: float = 1.0, trace: int = 0,
        fault: str = "", timeout: float = 600):
    argv = ["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
            "--trace", str(trace)]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    code = DRIVER.format(argv=argv, fault=textwrap.dedent(fault))
    p = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                       capture_output=True, text=True, timeout=timeout)
    last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
    result = json.loads(last) if last.startswith("{") else None
    return p.returncode, result, p.stderr
