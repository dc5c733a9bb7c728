"""The benchmark harness: one run of one cell, driven by data.

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration and a
traffic mix.  Everything else is found by name:

* the configuration's file (``configs[].file``) says its ``kind``; the
  store kind is ``bench/kinds/<kind>.py`` (data from the seed, the
  program's build, the ops, the shape of a request) and its plain
  reference is
  ``bench/kinds/<kind>_ref.py``;
* the traffic mix is ``bench/traffic/<traffic>.json``, read by
  ``bench/traffic.py``;
* each per-layer metric is read by ``bench/metrics/<name>.py``.

A run: data and store from the seed, warm-up, then a closed loop with
one client for ``seconds``; after the window, the program's answers to
every request it issued are compared with the reference's.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import sys
import time
import types
from pathlib import Path
from itertools import islice
from typing import Callable, Dict, Iterator, List, Optional

import numpy as np

from bench import tracing, traffic

BENCH = Path(__file__).resolve().parent

#: the numbers compared with the reference, each with its limit: an
#: exact comparison, so any mismatch or failed request makes a run wrong
LIMITS = {"mismatched": 0, "failed": 0}


class NoDevice(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"bench_plugin_{path.parent.name}_{path.stem}".replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    def __init__(self, root: Path, name: str):
        spec = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"known: {sorted(cells)}")
        self.workload = cells[name]
        self.name = name
        self.chips = int(self.workload["chips"])
        configs = {c["name"]: c for c in spec["configs"]}
        self.cfg = json.loads(
            (root / configs[self.workload["config"]]["file"]).read_text())
        bench = root / "bench"
        self.mix = json.loads(
            (bench / "traffic" / f"{self.workload['traffic']}.json")
            .read_text())
        self.kind = load_module(bench / "kinds" / f"{self.cfg['kind']}.py")
        self.ref = load_module(bench / "kinds" / f"{self.cfg['kind']}_ref.py")
        self.end_to_end = spec["end_to_end"]
        self.per_layer = [(m, load_module(bench / "metrics" /
                                          f"{m['name']}.py"))
                          for m in spec["per_layer"]]


def device_info(chips: int) -> dict:
    """The chips as JAX reports them; no TPU, or too few, is an error."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX's platform is {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoDevice(f"{chips} chips asked for, JAX sees {len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak(chips: int) -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def program_counters() -> Dict[str, int]:
    from repro.kernels._pad import trace_count
    return {"traces": trace_count()}


def same(got, want) -> bool:
    if isinstance(want, tuple):
        return (isinstance(got, tuple) and len(got) == len(want)
                and all(same(g, w) for g, w in zip(got, want)))
    return np.array_equal(np.asarray(got), np.asarray(want))


def warm_up(store, ops, reqs: Iterator, mix: dict, shape: Callable,
            counters: Callable[[], dict]) -> int:
    """Warm every shape the traffic reaches; returns how many requests
    ran.

    Of the first ``cover`` requests of ``reqs``, the first of each
    ``shape`` runs, so a shape too rare to come up in a few hundred
    requests is compiled before the window and not inside it.  Then at
    least ``warmup`` more run, and on until ``settle`` in a row add no
    trace (``4 * warmup`` at most)."""
    ran, seen = 0, set()
    for op, args in islice(reqs, int(mix["cover"])):
        key = shape(op, args)
        if key not in seen:
            seen.add(key)
            ops[op](store, **args)
            ran += 1
    minimum, settle = int(mix["warmup"]), int(mix["settle"])
    quiet, last = 0, counters()
    for i, (op, args) in enumerate(islice(reqs, 4 * minimum)):
        ops[op](store, **args)
        now = counters()
        quiet = quiet + 1 if now == last else 0
        last = now
        if i + 1 >= minimum and quiet >= settle:
            return ran + i + 1
    log(f"warm-up: {4 * minimum} requests without {settle} quiet in a row")
    return ran + 4 * minimum


def window(store, ops, reqs: Iterator, seconds: float) -> dict:
    """Closed loop, one client: the next request, drawn from ``reqs``, is
    issued when the previous returns, until ``seconds`` have passed.  A
    request's latency runs from the call to its answer."""
    from jax.profiler import TraceAnnotation
    lat: List[float] = []
    issued, answers, errors = [], [], []
    with TraceAnnotation(tracing.WINDOW_SPAN):
        t0 = time.perf_counter()
        t_end = t0 + seconds
        t2 = t0
        while t2 < t_end:
            op, args = next(reqs)
            issued.append((op, args))
            t1 = time.perf_counter()
            try:
                with TraceAnnotation(tracing.REQUEST_SPAN):
                    out = ops[op](store, **args)
            except Exception as e:  # a failed request is counted, not fatal
                out = None
                errors.append(f"{op}: {type(e).__name__}: {e}")
            t2 = time.perf_counter()
            lat.append(t2 - t1 if out is not None else math.inf)
            answers.append(out)
    return {"issued": issued, "answers": answers, "errors": errors,
            "latency_s": np.array(lat), "window_s": t2 - t0}


def compare(cell: Cell, data, w: dict) -> dict:
    """The reference's answer to every issued request, against the
    program's."""
    ref = cell.ref.prepare(cell.cfg, data, w["issued"])
    mismatched = 0
    for (op, args), got in zip(w["issued"], w["answers"]):
        if got is None:
            continue
        want = cell.ref.OPS[op](ref, **args)
        if not same(got, want):
            mismatched += 1
    return {"mismatched": mismatched, "failed": len(w["errors"])}


def end_to_end(name: str, w: dict, setup_s: float) -> Optional[float]:
    lat = w["latency_s"]
    done = int(np.isfinite(lat).sum())
    if name == "setup_s":
        return setup_s
    if name == "requests_per_s":
        return done / w["window_s"] if w["window_s"] > 0 else None
    pct = {"p50_ms": 50, "p95_ms": 95}.get(name)
    if pct is None:
        raise KeyError(f"no end-to-end metric {name!r}")
    if not lat.size:
        return None
    with np.errstate(invalid="ignore"):  # failed requests are inf
        v = float(np.percentile(lat, pct)) * 1e3
    return v if math.isfinite(v) else None


def run_cell(root: Path, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, check_device: bool = True,
             build: Optional[Callable] = None,
             ops: Optional[Dict[str, Callable]] = None,
             counters: Callable[[], dict] = program_counters) -> dict:
    """One run of cell ``name``; returns the result line's object.

    ``build`` and ``ops`` put something else in the program's place (the
    control, or a deliberately broken program in a test); by default the
    cell's store kind supplies both.
    """
    cell = Cell(root, name)
    device = device_info(cell.chips) if check_device else \
        {"platform": "none", "kind": "none", "count": 0}
    log(f"[{name}] seed={seed} seconds={seconds} trace={int(trace)} "
        f"device={device}")
    t = time.perf_counter()
    data = cell.kind.make_data(cell.cfg, seed)
    log(f"[{name}] data {time.perf_counter() - t:.3f}s")
    mix = cell.mix
    t = time.perf_counter()
    store = (build or cell.kind.build)(cell.cfg, data)
    log(f"[{name}] build {time.perf_counter() - t:.3f}s")
    ops = ops or cell.kind.OPS
    t = time.perf_counter()
    warmed = warm_up(store, ops,
                     traffic.stream(mix, data.domains, seed, traffic.WARMUP),
                     mix, lambda op, args: cell.kind.shape(data, op, **args),
                     counters)
    log(f"[{name}] warm-up {warmed} requests {time.perf_counter() - t:.3f}s")
    setup_s = time.perf_counter() - t_start

    reqs = traffic.stream(mix, data.domains, seed, traffic.WINDOW)
    before = counters()
    trace_dir = str(root / ".bench_run" / "trace")
    if trace:
        with tracing.record(trace_dir):
            w = window(store, ops, reqs, seconds)
    else:
        w = window(store, ops, reqs, seconds)
    after = counters()
    if check_device:
        device["memory_peak_bytes"] = memory_peak(cell.chips)
    del store
    gc.collect()

    attempted = len(w["issued"])
    failed = len(w["errors"])
    log(f"[{name}] window {w['window_s']:.3f}s attempted={attempted} "
        f"failed={failed} traces={after.get('traces', 0) - before.get('traces', 0)}")
    for e in w["errors"][:5]:
        log(f"[{name}] failed request: {e}")

    t = time.perf_counter()
    checks = compare(cell, data, w)
    log(f"[{name}] reference {time.perf_counter() - t:.3f}s over "
        f"{attempted - failed} answers")
    correct = attempted > failed and all(checks[k] <= LIMITS[k]
                                         for k in LIMITS)

    if trace:
        summary = tracing.summarize(tracing.load(
            trace_dir, [tracing.WINDOW_SPAN, tracing.REQUEST_SPAN,
                        *cell.kind.SPANS]))
        run = types.SimpleNamespace(
            summary=summary, completed=attempted - failed,
            latency_s=w["latency_s"], counters_before=before,
            counters_after=after)
        values = [(m, reader.read(run)) for m, reader in cell.per_layer]
        device.update(busy_s=summary.busy_ns / 1e9,
                      window_s=summary.window_ns / 1e9)
    else:
        values = [(m, end_to_end(m["name"], w, setup_s))
                  for m in cell.end_to_end]
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": {m["name"]: {"value": v, "unit": m["unit"]}
                       for m, v in values if v is not None},
           "device": device}
    if trace:
        out["breakdown"] = {"device_ops": tracing.top(summary.op_ns),
                            "idle_gaps": tracing.top(summary.idle_ns)}
    out["checks"] = {k: {"value": checks[k], "limit": LIMITS[k]}
                     for k in LIMITS}
    for k in LIMITS:
        log(f"check {k}={checks[k]} limit={LIMITS[k]}")
    return out
