"""Without a TPU the benchmark fails and prints no result, and in a
directory that holds only the benchmark's own files it fails too."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from bench_testkit import ROOT


def _run(cwd, cell="unsw.replay"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         "2147483648", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    for line in proc.stdout.splitlines():
        try:
            out = json.loads(line)
        except ValueError:
            continue
        assert "metrics" not in out and "device" not in out, line


def test_cpu_only_fails_without_a_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    _no_result(proc)
    assert "not 'tpu'" in proc.stderr


def test_benchmark_files_alone_fail(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "unsw.silo",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    _no_result(proc)
