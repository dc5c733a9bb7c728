"""Tiny copies of the benchmark's cells for CPU tests.

``tiny_root`` builds a checkout-like directory: ``BENCHMARK.json`` with
the real metrics and one cell, ``lj``, on the real configuration cut to
a size the Pallas interpreter runs in seconds, and the real ``bench/``
tree beside it.
"""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

SECONDS = 0.3


def tiny_root(tmp: Path) -> Path:
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfgs = tmp / "bench" / "configs"
    lj = json.loads((cfgs / "lj-social.json").read_text())
    lj.update(name="lj-tiny", vertices=6000, edges=60000, max_degree=600)
    mixes = tmp / "bench" / "traffic"
    b64 = json.loads((mixes / "uniform-b1024.json").read_text())
    b64["ops"][0]["args"]["ids"]["count"] = 64
    b64.update(cover=50, warmup=2, settle=1)
    files = {cfgs / "lj-tiny.json": lj, mixes / "tiny-b64.json": b64}
    for path, obj in files.items():
        path.write_text(json.dumps(obj))
    spec["configs"] = [
        {"name": "lj-tiny", "source": "test", "file": "bench/configs/lj-tiny.json",
         "reduced": ["vertices", "edges", "max_degree"], "why": "test"}]
    spec["workloads"] = [
        {"name": "lj", "config": "lj-tiny", "traffic": "tiny-b64", "chips": 1,
         "why": "test"}]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def run(root: Path, cell: str, seed: int = 3, **kw) -> dict:
    """One run of a tiny cell on the CPU, the chip check skipped."""
    return harness.run_cell(root, cell, seed, SECONDS, False,
                            time.perf_counter(), check_device=False, **kw)
