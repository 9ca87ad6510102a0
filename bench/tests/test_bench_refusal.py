"""The benchmark refuses to give a result where it cannot measure: on a
CPU, and in a directory that holds only the benchmark without the program
under test."""

import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "pool4.serve.fifo", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def _no_result(p):
    lines = p.stdout.strip().splitlines()
    return not lines or not lines[-1].lstrip().startswith("{")


def test_cpu_run_exits_nonzero_without_a_result():
    p = _run(ROOT, {"PYTHONPATH": os.path.join(ROOT, "src")})
    assert p.returncode != 0
    assert _no_result(p)
    assert "needs a TPU" in p.stderr


def test_benchmark_alone_exits_nonzero_without_a_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    p = _run(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert _no_result(p)
