"""Without a TPU, or without the program beside it, a run exits non-zero
and prints no result."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

from conftest import CHECKOUT

ARGS = ["--workload", "mamba2-370m.train-seq2k", "--seed", "0",
        "--seconds", "10", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(root / "bench" / "run.py"),
                           *ARGS], cwd=root, env=env, capture_output=True,
                          text=True, timeout=300)


def test_cpu_only_exits_nonzero_without_a_result():
    r = _run(CHECKOUT)
    assert r.returncode != 0
    assert "metrics" not in r.stdout
    assert "no TPU" in r.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHECKOUT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert "metrics" not in r.stdout
