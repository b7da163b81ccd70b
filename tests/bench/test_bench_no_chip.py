"""Without a TPU, or without the program beside it, a run exits non-zero
and prints no result."""

import json
import os
import shutil
import subprocess
import sys

from bench_helpers import ROOT

CELL = "smollm-135m.inhibitor.offline"


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed",
         str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def _no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert "metrics" not in obj and "correct" not in obj


def test_run_without_a_tpu_fails_and_prints_no_result(tmp_path):
    # a copy, so the run cannot leave a compile cache in the checkout
    for p in ("BENCHMARK.json", "bench", "src"):
        src = ROOT / p
        (shutil.copytree if src.is_dir() else shutil.copy)(src, tmp_path / p)
    proc = _run(tmp_path)
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_run_with_only_the_benchmark_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    _no_result(_run(tmp_path))
