"""``bench/run.py`` finds the TPU or fails: no CPU fallback, no result."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_tiny import ROOT


def _run(root, workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         str(2 ** 31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)


@pytest.mark.parametrize("workload", [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]])
def test_no_tpu_exits_nonzero_before_running(workload):
    p = _run(ROOT, workload)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
    # it stopped at the device check, before making any data
    assert "] data " not in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, "lj-retrieve-b1024")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
