"""Profiler trace of the measured window, and its reduction to numbers.

``record`` wraps the window in a JAX profiler session (host TraceMe
spans on, the Python function tracer off, since it would trace every
call of the program).  ``load`` reads the ``.xplane.pb`` it wrote into a
:class:`Trace`: per device, the intervals of its XLA ops and of its
program executions, and the host spans the harness and the store kinds
annotate.  ``summarize`` reduces a trace to the numbers the per-layer
metrics read.  Both steps are plain code kept with the benchmark, so
every run computes the same numbers the same way.

All times are nanoseconds on the trace's clock.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import shutil
from typing import Dict, List, Sequence, Tuple

import numpy as np

#: the span around the whole measured window and around each request
WINDOW_SPAN = "bench_window"
REQUEST_SPAN = "bench_request"

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"

Intervals = np.ndarray  # float64[k, 2] of [start, end)


@contextlib.contextmanager
def record(log_dir: str):
    """Profile the enclosed block into ``log_dir`` (emptied first)."""
    import jax
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@dataclasses.dataclass
class Trace:
    #: per device plane: its ops as (names, intervals) and its program
    #: executions as intervals
    ops: Dict[str, Tuple[List[str], Intervals]]
    modules: Dict[str, Intervals]
    #: host spans by name
    spans: Dict[str, Intervals]


def _intervals(events) -> Intervals:
    a = np.array([(e.start_ns, e.start_ns + e.duration_ns) for e in events],
                 np.float64)
    return a.reshape(-1, 2)


def op_label(module: str, hlo: str) -> str:
    """``program/op shape`` from a module event's name and an op event's
    HLO text, e.g. ``jit_f/%fusion.10 u32[2096128]``."""
    name, _, rest = hlo.partition(" = ")
    shape = re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0])
    return f"{module.split('(', 1)[0]}/{name} {shape}".strip()


def _label_ops(mod_names: List[str], mods: Intervals, hlos: List[str],
               ops: Intervals) -> List[str]:
    """Each op labelled with the program execution it falls in (the last
    one to start before it: one device runs one program at a time)."""
    order = np.argsort(mods[:, 0], kind="stable")
    k = np.searchsorted(mods[order, 0], ops[:, 0], "right") - 1
    return [op_label(mod_names[order[i]] if i >= 0 else "", hlo)
            for i, hlo in zip(k, hlos)]


def from_profile(data, span_names: Sequence[str]) -> Trace:
    """A :class:`Trace` from a ``jax.profiler.ProfileData``."""
    ops, modules = {}, {}
    spans: Dict[str, list] = {n: [] for n in span_names}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = lines.get(MODULES_LINE, [])
            modules[plane.name] = _intervals(mods)
            evs = lines.get(OPS_LINE, [])
            ops[plane.name] = (_label_ops([m.name for m in mods],
                                          modules[plane.name],
                                          [e.name for e in evs],
                                          _intervals(evs)),
                               _intervals(evs))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name in spans:
                        spans[e.name].append(e)
    return Trace(ops=ops, modules=modules,
                 spans={n: _intervals(evs) for n, evs in spans.items()})


def load(log_dir: str, span_names: Sequence[str]) -> Trace:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file under {log_dir}, "
                           f"found {len(files)}")
    return from_profile(ProfileData.from_file(files[0]), span_names)


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------

def union(iv: Intervals) -> Intervals:
    """Disjoint sorted union of intervals."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.flatnonzero(new)
    stops = ends[np.append(last[1:] - 1, len(iv) - 1)]
    return np.stack([starts, stops], axis=1)


def clip(iv: Intervals, lo: float, hi: float) -> Intervals:
    out = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)],
                   axis=1).reshape(-1, 2)
    return out[out[:, 1] > out[:, 0]]


def covered(u: Intervals, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Time of the disjoint sorted union ``u`` inside each ``[a, b)``."""
    cum = np.concatenate([[0.0], np.cumsum(u[:, 1] - u[:, 0])])

    def upto(t):
        k = np.searchsorted(u[:, 0], t, "right") - 1
        kk = np.maximum(k, 0)
        inside = np.clip(t - u[kk, 0], 0.0, u[kk, 1] - u[kk, 0])
        return np.where(k >= 0, cum[kk] + inside, 0.0)

    if len(u) == 0:
        return np.zeros(np.shape(a))
    return upto(np.asarray(b, np.float64)) - upto(np.asarray(a, np.float64))


# --------------------------------------------------------------------------
# reduction
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Summary:
    window_ns: float
    devices: int
    #: device busy time in the window, averaged over the devices
    busy_ns: float
    #: program executions in the window, averaged over the devices
    launches: float
    #: per request: its span and the device busy time inside it
    request_ns: np.ndarray
    request_busy_ns: np.ndarray
    #: op name -> device time in the window, averaged over the devices
    op_ns: Dict[str, float]
    #: innermost host span -> device idle time under it in the window
    idle_ns: Dict[str, float]


def _innermost(spans: Dict[str, Intervals], t: np.ndarray) -> List[str]:
    """Name of the shortest host span that holds each time in ``t``."""
    best = np.full(len(t), np.inf)
    names = np.full(len(t), "no host span", dtype=object)
    for name, iv in spans.items():
        if len(iv) == 0:
            continue
        iv = iv[np.argsort(iv[:, 0], kind="stable")]
        k = np.searchsorted(iv[:, 0], t, "right") - 1
        kk = np.maximum(k, 0)
        inside = (k >= 0) & (t < iv[kk, 1])
        length = iv[kk, 1] - iv[kk, 0]
        take = inside & (length < best)
        best[take] = length[take]
        names[take] = name
    return list(names)


def _idle_pieces(u: Intervals, lo: float, hi: float,
                 spans: Dict[str, Intervals]):
    """The gaps of busy union ``u`` in ``[lo, hi)``, cut at every host
    span boundary, each with the innermost span that holds it."""
    bounds = np.concatenate([[lo], u.ravel(), [hi]]).reshape(-1, 2)
    gaps = bounds[bounds[:, 1] > bounds[:, 0]]
    cuts = [iv.ravel() for iv in spans.values()]
    pts = np.unique(np.concatenate([gaps.ravel(), *cuts]))
    pieces = np.stack([pts[:-1], pts[1:]], axis=1)
    mids = pieces.mean(axis=1)
    k = np.searchsorted(gaps[:, 0], mids, "right") - 1
    idle = (k >= 0) & (mids < gaps[np.maximum(k, 0), 1])
    pieces = pieces[idle]
    return _innermost(spans, pieces.mean(axis=1)), pieces


def summarize(trace: Trace) -> Summary:
    win = trace.spans.get(WINDOW_SPAN)
    if win is None or len(win) != 1:
        raise RuntimeError(f"the trace holds no single {WINDOW_SPAN!r} span")
    lo, hi = win[0]
    if not trace.ops:
        raise RuntimeError("the trace holds no device ops")
    reqs = clip(trace.spans.get(REQUEST_SPAN, np.zeros((0, 2))), lo, hi)
    inner = {n: clip(iv, lo, hi) for n, iv in trace.spans.items()
             if n != WINDOW_SPAN}
    busy, launches, req_busy = [], [], []
    op_ns: Dict[str, float] = {}
    idle_ns: Dict[str, float] = {}
    ndev = len(trace.ops)
    for plane, (names, iv) in trace.ops.items():
        a, b = np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)
        keep = b > a
        inwin = np.stack([a[keep], b[keep]], axis=1)
        for name, t in zip(np.asarray(names, object)[keep],
                           b[keep] - a[keep]):
            op_ns[name] = op_ns.get(name, 0.0) + t / ndev
        u = union(inwin)
        busy.append(float((u[:, 1] - u[:, 0]).sum()))
        req_busy.append(covered(u, reqs[:, 0], reqs[:, 1]))
        mods = trace.modules.get(plane, np.zeros((0, 2)))
        launches.append(int(((mods[:, 0] >= lo) & (mods[:, 0] < hi)).sum()))
        for name, (a, b) in zip(*_idle_pieces(u, lo, hi, inner)):
            idle_ns[name] = idle_ns.get(name, 0.0) + (b - a) / ndev
    return Summary(window_ns=float(hi - lo), devices=ndev,
                   busy_ns=float(np.mean(busy)),
                   launches=float(np.mean(launches)),
                   request_ns=reqs[:, 1] - reqs[:, 0],
                   request_busy_ns=np.mean(req_busy, axis=0),
                   op_ns=op_ns, idle_ns=idle_ns)


def top(d: Dict[str, float], k: int = 10) -> List[List]:
    """The ``k`` largest entries as ``[name, seconds]``."""
    items = sorted(d.items(), key=lambda kv: -kv[1])[:k]
    return [[name, float(ns) / 1e9] for name, ns in items]
