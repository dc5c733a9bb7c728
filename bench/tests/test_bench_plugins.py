"""A cell, a traffic mix and a per-layer metric added as new files are
found by their names, with no edit to a file the harness already has."""
from __future__ import annotations

import json
import types

import numpy as np

from bench_tiny import harness, run, tiny_root


def test_new_files_are_found_by_name(tmp_path):
    root = tiny_root(tmp_path)
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "lj-tiny.json").read_text())
    cfg.update(name="lj-tiny-dense", vertices=4000, edges=80000)
    (bench / "configs" / "lj-tiny-dense.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "tiny-b32.json").write_text(json.dumps({
        "ops": [{"op": "retrieve", "share": 1, "args": {"ids": {
            "draw": "uniform_distinct", "count": 32, "over": "vertices"}}}],
        "cover": 20, "warmup": 2, "settle": 1}))
    (bench / "metrics" / "answers_per_req.py").write_text(
        "def read(run):\n"
        "    return run.completed and run.answers / run.completed\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "lj-tiny-dense", "source": "test",
                            "file": "bench/configs/lj-tiny-dense.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "lj-dense", "config": "lj-tiny-dense",
                              "traffic": "tiny-b32", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "answers_per_req", "unit": "ids/req",
                              "better": "higher", "source": "host_clock",
                              "layer": "entry and query",
                              "moves": "requests_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.Cell(root, "lj-dense")
    assert cell.cfg["vertices"] == 4000
    assert cell.mix["ops"][0]["args"]["ids"]["count"] == 32
    readers = {m["name"]: r for m, r in cell.per_layer}
    assert "answers_per_req" in readers and "device_idle_pct" in readers
    assert readers["answers_per_req"].read(
        types.SimpleNamespace(completed=4, answers=10)) == 2.5

    out = run(root, "lj-dense")
    assert out["correct"], out
    assert out["attempted"] > 0


def test_readers_return_nothing_without_a_trace():
    cell_metrics = json.loads(
        (harness.BENCH.parent / "BENCHMARK.json").read_text())["per_layer"]
    run = types.SimpleNamespace(summary=None, completed=0,
                                latency_s=np.zeros(0), counters_before={},
                                counters_after={})
    for m in cell_metrics:
        reader = harness.load_module(harness.BENCH / "metrics" /
                                     f"{m['name']}.py")
        assert reader.read(run) is None, m["name"]
