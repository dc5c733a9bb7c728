"""The reduction from a profiler trace to the per-layer numbers."""
from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest
from jax.profiler import ProfileData

from bench_tiny import ROOT  # noqa: F401  (puts the repo on sys.path)

from bench import tracing

DATA = Path(__file__).resolve().parent / "data"
SPANS = [tracing.WINDOW_SPAN, tracing.REQUEST_SPAN, "entry"]


def _events(meta: dict, events) -> str:
    ids = {name: i + 1 for i, name in enumerate(meta)}
    body = "".join(
        f"events {{ metadata_id: {ids[n]} offset_ps: {int(s * 1000)} "
        f"duration_ps: {int(d * 1000)} }}\n" for n, s, d in events)
    return body, "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
        for n, i in ids.items())


def _plane(pid, name, lines):
    meta = sorted({n for _, evs in lines for n, _, _ in evs})
    out = f'planes {{ id: {pid} name: "{name}"\n'
    metadata = ""
    for lid, (lname, evs) in enumerate(lines):
        body, metadata = _events(meta, evs)
        out += (f'lines {{ id: {lid + 1} name: "{lname}" timestamp_ns: 0\n'
                f"{body}}}\n")
    return out + metadata + "}\n"


def synthetic() -> tracing.Trace:
    """Window [100, 1100); two requests; ops overlapping and outside."""
    text = (_plane(1, "/device:TPU:0", [
        ("XLA Modules", [("jit_a", 150, 100), ("jit_b", 400, 300),
                         ("jit_a", 50, 20)]),
        ("XLA Ops", [("fusion", 150, 60), ("fusion", 180, 60),
                     ("sort", 400, 100), ("gather", 600, 100),
                     ("fusion", 50, 20), ("gather", 1050, 100)])])
        + _plane(2, "/host:CPU", [
            ("python", [(tracing.WINDOW_SPAN, 100, 1000),
                        (tracing.REQUEST_SPAN, 100, 400),
                        (tracing.REQUEST_SPAN, 500, 600),
                        ("entry", 120, 200), ("other", 130, 5)])]))
    return tracing.from_profile(ProfileData.from_text_proto(text), SPANS)


def test_interval_arithmetic():
    u = tracing.union(np.array([[5, 7], [0, 2], [1, 3], [6, 9], [10, 11]],
                               float))
    assert u.tolist() == [[0, 3], [5, 9], [10, 11]]
    assert tracing.covered(u, np.array([0, 2, 8]),
                           np.array([11, 6, 10.5])).tolist() == [8, 2, 1.5]
    assert tracing.clip(u, 1, 6).tolist() == [[1, 3], [5, 6]]
    assert tracing.union(np.zeros((0, 2))).shape == (0, 2)


def test_summary_of_a_synthetic_trace():
    t = synthetic()
    assert set(t.spans["entry"].ravel()) == {120, 320}
    s = tracing.summarize(t)
    # busy: [150, 240) + [400, 500) + [600, 700) + [1050, 1100)
    assert s.window_ns == 1000 and s.devices == 1
    assert s.busy_ns == 90 + 100 + 100 + 50
    assert s.launches == 2   # the module at 50 started before the window
    assert s.request_ns.tolist() == [400, 600]
    assert s.request_busy_ns.tolist() == [90 + 100, 100 + 50]
    # each op is labelled with the program that last started before it
    assert s.op_ns == {"jit_a/fusion": 120, "jit_b/sort": 100,
                       "jit_b/gather": 150}
    # idle: [120,150) and [240,320) under "entry"; [100,120) and
    # [320,400) under the first request, [500,600) and [700,1050) under
    # the second
    assert s.idle_ns == {"entry": 30 + 80,
                         tracing.REQUEST_SPAN: 20 + 80 + 100 + 350}
    assert tracing.top(s.idle_ns, 1) == [[tracing.REQUEST_SPAN, 550e-9]]


def test_a_trace_without_window_or_device_is_refused():
    t = synthetic()
    with pytest.raises(RuntimeError):
        tracing.summarize(tracing.Trace(ops={}, modules={}, spans=t.spans))
    with pytest.raises(RuntimeError):
        tracing.summarize(tracing.Trace(ops=t.ops, modules=t.modules,
                                        spans={}))


def _loop_union(iv):
    out = []
    for a, b in sorted(map(tuple, iv)):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def test_summary_of_a_recorded_trace():
    """Seven requests of an IS-3 / IC-8 query mix recorded on the chip;
    the expected numbers come from plain loops over the same events."""
    text = (DATA / "snb_trace.textproto").read_text()
    spans = [tracing.WINDOW_SPAN, tracing.REQUEST_SPAN, "is3_graphar",
             "ic8_graphar"]
    t = tracing.from_profile(ProfileData.from_text_proto(text), spans)
    s = tracing.summarize(t)
    (lo, hi), = t.spans[tracing.WINDOW_SPAN].tolist()
    (names, ops), = t.ops.values()
    busy = _loop_union([(max(a, lo), min(b, hi)) for a, b in ops.tolist()
                        if min(b, hi) > max(a, lo)])
    assert s.devices == 1 and s.window_ns == hi - lo
    assert s.busy_ns == pytest.approx(sum(b - a for a, b in busy))
    (mods,) = t.modules.values()
    assert s.launches == sum(lo <= a < hi for a, _ in mods.tolist()) == 13
    reqs = t.spans[tracing.REQUEST_SPAN].tolist()
    assert len(s.request_ns) == len(reqs) == 7
    for (ra, rb), got in zip(sorted(reqs), s.request_busy_ns):
        want = sum(max(0.0, min(b, rb) - max(a, ra)) for a, b in busy)
        assert got == pytest.approx(want)
    idle = hi - lo - sum(b - a for a, b in busy)
    assert sum(s.idle_ns.values()) == pytest.approx(idle)
    assert sum(s.op_ns.values()) >= s.busy_ns
    # one IC-8 among the seven: its two-hop program holds the device most
    top_op = tracing.top(s.op_ns, 1)[0][0]
    assert top_op.startswith("jit_two_hop_ref/%fusion.") and "[800000]" in top_op
    assert tracing.top(s.idle_ns, 1)[0][0] == "is3_graphar"


def test_op_label():
    hlo = ("%fusion.10 = u32[2096128]{0:T(1024)S(1)} fusion(u32[1024,2048]"
           "{1,0:T(8,128)S(1)} %fusion.6), kind=kCustom")
    assert (tracing.op_label("jit_fused(1684537)", hlo)
            == "jit_fused/%fusion.10 u32[2096128]")


def test_traced_run_reports_the_per_layer_metrics(tmp_path, monkeypatch):
    """A ``--trace 1`` run on a tiny cell, the profiler replaced by the
    recorded trace: the line carries every per-layer metric, the
    device's busy and window seconds, and the breakdown."""
    import contextlib
    import json

    from bench_tiny import SECONDS, harness, tiny_root

    recorded = tracing.from_profile(ProfileData.from_text_proto(
        (DATA / "snb_trace.textproto").read_text()),
        [tracing.WINDOW_SPAN, tracing.REQUEST_SPAN, "is3_graphar",
         "ic8_graphar"])
    monkeypatch.setattr(tracing, "record",
                        lambda log_dir: contextlib.nullcontext())
    monkeypatch.setattr(tracing, "load", lambda log_dir, names: recorded)
    root = tiny_root(tmp_path)
    out = harness.run_cell(root, "lj", 3, SECONDS, True, 0.0,
                           check_device=False)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    assert set(out["metrics"]) == {m["name"] for m in spec["per_layer"]}
    assert out["metrics"]["retraces_in_window"]["value"] == 0
    assert 0 < out["device"]["busy_s"] < out["device"]["window_s"]
    assert 0 < out["metrics"]["device_idle_pct"]["value"] < 100
    for key in ("device_ops", "idle_gaps"):
        assert 0 < len(out["breakdown"][key]) <= 10
    assert list(out)[-1] == "checks" and out["correct"]
