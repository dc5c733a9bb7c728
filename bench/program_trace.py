"""What the program records about itself in a traced window.

``bench/tracing.py`` reduces the profiler trace of a ``--trace 1`` run
to the harness's per-layer numbers, with the host spans of the harness
and the store kinds.  This module reads the same ``.xplane.pb`` for
what the program records itself (:mod:`repro.obs`): its ``graphar.*``
host spans, and each device op's name stack (the ``jax.named_scope``
path it was traced under, the ``tf_op`` stat of the op's metadata).
It splits the window by the program's phases:

    python3 -m bench.program_trace [--trace-dir .bench_run/trace]

from the root of a checkout, after a ``--trace 1`` run, prints one JSON
object: per span its time, self time and the device's busy time inside
it; the device's idle time by the innermost span; the device's time by
scope; and four numbers a request (:func:`phases`).  The program's
counters are read in the run itself (:func:`program_counters`).

The trace's device clock runs early against the host's by a part of a
millisecond, so device time meets a host span only after
:func:`aligned` has put it on the host's clock.  All times are
nanoseconds on the trace's clock.
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import sys
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import tracing

ROOT = Path(__file__).resolve().parents[1]
#: the stat of a device op's event metadata that holds its name stack,
#: e.g. ``jit(f)/decode/gather_words/jit(take_along_axis)/gather:``
STACK_STAT = "tf_op"
#: the program's span around each kernel call (``repro.obs.LAUNCH``)
LAUNCH_SPAN = "graphar.launch"
#: the spans whose self time is the host's preparation of a dispatch
PREP_SPANS = ("graphar.edge_ranges", "graphar.plan", "graphar.upload",
              LAUNCH_SPAN)
PULL_SPAN = "graphar.pull"
#: the host's build of the answer: PAC from the bitmap, ids from the PAC
ASSEMBLE_SPANS = ("graphar.assemble", "graphar.to_ids")
#: the fused retrieval's gathers: page rows of the resident plan, and
#: the per-delta packed-word gather inside the decode
GATHER_SCOPES = ("gather_rows", "gather_words")


def program_spans() -> tuple:
    """The host spans the program opens; none from a program without
    :mod:`repro.obs`."""
    try:
        from repro import obs
    except ImportError:
        return ()
    return obs.SPANS


def program_counters(run) -> Optional[Dict[str, int]]:
    """The program's counters (:mod:`repro.obs`) at the end of a traced
    run: totals over every request the process ran, warm-up included.
    None for an untraced run or a program without them."""
    if getattr(run, "summary", None) is None:
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.counters()


# --------------------------------------------------------------------------
# loading
# --------------------------------------------------------------------------

def _xspace_names():
    """A message class for the part of the profiler's ``XSpace`` proto
    that ``ProfileData`` does not expose: each plane's event metadata
    with its stats.  Fields are numbered as in ``xplane.proto``; the
    rest of the message is skipped."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    fdp = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(
        name="bench_xspace_names.proto", package="bench_xspace",
        syntax="proto3")
    scalar = {"int64": fdp.TYPE_INT64, "string": fdp.TYPE_STRING}

    def message(name, *fields):
        """``fields``: (name, number, type), ``*type`` for repeated."""
        m = f.message_type.add(name=name)
        for field, number, kind in fields:
            label = fdp.LABEL_REPEATED if kind[0] == "*" \
                else fdp.LABEL_OPTIONAL
            kind = kind.lstrip("*")
            if kind in scalar:
                m.field.add(name=field, number=number, label=label,
                            type=scalar[kind])
            else:
                m.field.add(name=field, number=number, label=label,
                            type=fdp.TYPE_MESSAGE,
                            type_name=f".bench_xspace.{kind}")

    message("Stat", ("metadata_id", 1, "int64"), ("str_value", 5, "string"))
    message("StatMetadata", ("name", 2, "string"))
    message("EventMetadata", ("name", 2, "string"), ("stats", 5, "*Stat"))
    # a proto map is a repeated (key, value) entry on the wire
    message("EventMetadataEntry", ("key", 1, "int64"),
            ("value", 2, "EventMetadata"))
    message("StatMetadataEntry", ("key", 1, "int64"),
            ("value", 2, "StatMetadata"))
    message("Plane", ("name", 2, "string"),
            ("event_metadata", 4, "*EventMetadataEntry"),
            ("stat_metadata", 5, "*StatMetadataEntry"))
    message("Space", ("planes", 1, "*Plane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xspace.Space"))


def op_stacks(serialized: bytes) -> Dict[str, Dict[str, str]]:
    """Per device plane: op event name -> its name stack, from the
    ``tf_op`` stat of the event's metadata in a serialized ``XSpace``.
    (Op names carry the HLO text, so two ops of one plane share a name
    only when they are the same instruction.)"""
    space = _xspace_names()()
    space.ParseFromString(serialized)
    out = {}
    for plane in space.planes:
        if not tracing.DEVICE_PLANE.match(plane.name):
            continue
        stat = {e.value.name: e.key for e in plane.stat_metadata}
        sid = stat.get(STACK_STAT)
        out[plane.name] = {
            e.value.name: s.str_value for e in plane.event_metadata
            for s in e.value.stats if s.metadata_id == sid}
    return out


@dataclasses.dataclass
class ProgramTrace:
    #: the trace as ``bench/tracing.py`` reads it, with the program's
    #: spans among its host spans
    trace: tracing.Trace
    #: per device plane: each op's name stack, in the order of
    #: ``trace.ops`` ("" where the trace holds none)
    stacks: Dict[str, List[str]]


def from_serialized(serialized: bytes) -> ProgramTrace:
    """A :class:`ProgramTrace` of a serialized ``XSpace``, with the
    harness's window and request spans and the program's spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_serialized_xspace(serialized)
    trace = tracing.from_profile(data, [tracing.WINDOW_SPAN,
                                        tracing.REQUEST_SPAN,
                                        *program_spans()])
    by_name = op_stacks(serialized)
    stacks = {}
    for plane in data.planes:
        if plane.name in trace.ops:
            ops = [e.name for line in plane.lines
                   if line.name == tracing.OPS_LINE for e in line.events]
            known = by_name.get(plane.name, {})
            stacks[plane.name] = [known.get(n, "") for n in ops]
    return ProgramTrace(trace=trace, stacks=stacks)


def from_text_proto(text: str) -> ProgramTrace:
    from jax.profiler import ProfileData
    return from_serialized(ProfileData.text_proto_to_serialized_xspace(text))


def load(log_dir: str) -> ProgramTrace:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file under {log_dir}, "
                           f"found {len(files)}")
    with open(files[0], "rb") as f:
        return from_serialized(f.read())


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------

def _total(u: tracing.Intervals) -> float:
    return float((u[:, 1] - u[:, 0]).sum())


def intersect(a: tracing.Intervals,
              b: tracing.Intervals) -> tracing.Intervals:
    """Intersection of two disjoint sorted unions, as sorted pieces."""
    if len(a) == 0 or len(b) == 0:
        return np.zeros((0, 2))
    pts = np.unique(np.concatenate([a.ravel(), b.ravel()]))
    pieces = np.stack([pts[:-1], pts[1:]], axis=1)
    mids = pieces.mean(axis=1)

    def inside(u):
        k = np.searchsorted(u[:, 0], mids, "right") - 1
        return (k >= 0) & (mids < u[np.maximum(k, 0), 1])

    return pieces[inside(a) & inside(b)]


def self_time(spans: Dict[str, tracing.Intervals], name: str) -> float:
    """Time in span ``name`` that no other span it contains covers."""
    u = tracing.union(spans[name])
    kids = [np.zeros((0, 2))]
    for other, iv in spans.items():
        if other == name or len(iv) == 0:
            continue
        k = np.searchsorted(u[:, 0], iv[:, 0], "right") - 1
        kk = np.maximum(k, 0)
        kids.append(iv[(k >= 0) & (iv[:, 1] <= u[kk, 1])])
    inside = tracing.covered(tracing.union(np.concatenate(kids)),
                             u[:, 0], u[:, 1])
    return _total(u) - float(np.sum(inside))


def aligned(ops: tracing.Intervals, mods: tracing.Intervals,
            launches: tracing.Intervals) -> tracing.Intervals:
    """``ops`` put on the host's clock, where the device's runs early.

    Each program execution in ``mods`` is matched to the launch span
    that opens nearest its start.  A program that starts before its
    launch opened, by less than its own length, moves later with its
    ops, so that it starts as that span opens; programs of one launch
    keep their order, each moved as much as the first.  With no launch
    span nothing moves."""
    if len(ops) == 0 or len(mods) == 0 or len(launches) == 0:
        return ops
    mods = mods[np.argsort(mods[:, 0], kind="stable")]
    opens = np.sort(launches[:, 0])
    j = np.searchsorted(opens, mods[:, 0])
    before, after = np.maximum(j - 1, 0), np.minimum(j, len(opens) - 1)
    near = np.where(opens[after] - mods[:, 0] < mods[:, 0] - opens[before],
                    after, before)
    early = opens[near] - mods[:, 0]
    early[(early <= 0) | (early >= mods[:, 1] - mods[:, 0])] = 0.0
    per_launch = np.zeros(len(opens))
    np.maximum.at(per_launch, near, early)
    k = np.searchsorted(mods[:, 0], ops[:, 0], "right") - 1
    shift = np.where(k >= 0, per_launch[near[np.maximum(k, 0)]], 0.0)
    return ops + shift[:, None]


def scopes(stack: str) -> set:
    """The scopes of a name stack: its ``/``-separated parts."""
    return {part.rstrip(":") for part in stack.split("/") if part}


# --------------------------------------------------------------------------
# reduction
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Split:
    #: request spans in the window
    requests: int
    #: host span -> time in the window: the union of its intervals, its
    #: self time (minus the spans it contains), the device busy time
    #: inside it (averaged over the devices); spans that never opened
    #: in the window are left out
    span_ns: Dict[str, float]
    span_self_ns: Dict[str, float]
    span_busy_ns: Dict[str, float]
    #: host span -> per request span: the device idle time inside the
    #: host span within that request, averaged over the devices
    span_idle_req_ns: Dict[str, np.ndarray]
    #: innermost host span -> device idle time under it in the window
    idle_ns: Dict[str, float]
    #: name-stack scope -> device time of the ops traced under it in
    #: the window, averaged over the devices
    scope_ns: Dict[str, float]


def split(pt: ProgramTrace) -> Split:
    """The window split by host span and by device scope; device time
    meets the host spans on the host's clock (:func:`aligned`)."""
    trace = pt.trace
    win = trace.spans.get(tracing.WINDOW_SPAN)
    if win is None or len(win) != 1:
        raise RuntimeError(
            f"the trace holds no single {tracing.WINDOW_SPAN!r} span")
    lo, hi = win[0]
    if not trace.ops:
        raise RuntimeError("the trace holds no device ops")
    reqs = tracing.clip(trace.spans.get(tracing.REQUEST_SPAN,
                                        np.zeros((0, 2))), lo, hi)
    inner = {n: tracing.clip(iv, lo, hi) for n, iv in trace.spans.items()
             if n != tracing.WINDOW_SPAN}
    opened = {n: tracing.union(iv) for n, iv in inner.items() if len(iv)}
    launch = trace.spans.get(LAUNCH_SPAN, np.zeros((0, 2)))
    span_busy_ns = dict.fromkeys(opened, 0.0)
    span_idle_req_ns = {n: np.zeros(len(reqs)) for n in opened}
    idle_ns: Dict[str, float] = {}
    scope_ns: Dict[str, float] = {}
    ndev = len(trace.ops)
    for plane, (names, iv) in trace.ops.items():
        a, b = np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)
        keep = b > a
        stacks = pt.stacks.get(plane) or [""] * len(names)
        for stack, t in zip(np.asarray(stacks, object)[keep],
                            b[keep] - a[keep]):
            for scope in scopes(stack):
                scope_ns[scope] = scope_ns.get(scope, 0.0) + float(t) / ndev
        mods = trace.modules.get(plane, np.zeros((0, 2)))
        on_host = tracing.union(tracing.clip(aligned(iv, mods, launch),
                                             lo, hi))
        for name, su in opened.items():
            span_busy_ns[name] += float(
                tracing.covered(on_host, su[:, 0], su[:, 1]).sum()) / ndev
            span_idle_req_ns[name] += (
                tracing.covered(su, reqs[:, 0], reqs[:, 1])
                - tracing.covered(intersect(su, on_host),
                                  reqs[:, 0], reqs[:, 1])) / ndev
        for name, (a, b) in zip(*tracing._idle_pieces(on_host, lo, hi,
                                                      inner)):
            idle_ns[name] = idle_ns.get(name, 0.0) + (b - a) / ndev
    return Split(requests=len(reqs),
                 span_ns={n: _total(u) for n, u in opened.items()},
                 span_self_ns={n: self_time(opened, n) for n in opened},
                 span_busy_ns=span_busy_ns,
                 span_idle_req_ns=span_idle_req_ns, idle_ns=idle_ns,
                 scope_ns=scope_ns)


def phases(s: Split) -> Dict[str, Optional[float]]:
    """Four numbers a request, in ms; None where the trace holds none of
    the spans or scopes a number reads.

    * ``prep_ms_per_req``: self time of the spans that prepare a
      dispatch (:data:`PREP_SPANS`);
    * ``pull_ms_per_req``: time in ``graphar.pull`` with the device not
      busy, the median over the requests (a few pulls a window stall
      for about 100 ms on the host and would swing a mean);
    * ``assemble_ms_per_req``: time in :data:`ASSEMBLE_SPANS`;
    * ``gather_ms_per_req``: device time of the ops under a scope of
      :data:`GATHER_SCOPES`.
    """
    n = s.requests

    def per_req(d, keys):
        got = [d[k] for k in keys if k in d]
        return sum(got) / 1e6 / n if got and n else None

    pull = s.span_idle_req_ns.get(PULL_SPAN)
    return {"prep_ms_per_req": per_req(s.span_self_ns, PREP_SPANS),
            "pull_ms_per_req": (float(np.median(pull)) / 1e6
                                if pull is not None and len(pull) else None),
            "assemble_ms_per_req": per_req(s.span_ns, ASSEMBLE_SPANS),
            "gather_ms_per_req": per_req(s.scope_ns, GATHER_SCOPES)}


def report(s: Split) -> dict:
    """The split as one JSON-ready object; times in seconds."""
    return {"requests": s.requests,
            "phases": phases(s),
            "spans": {n: {"s": s.span_ns[n] / 1e9,
                          "self_s": s.span_self_ns[n] / 1e9,
                          "busy_s": s.span_busy_ns[n] / 1e9}
                      for n in s.span_ns},
            "idle_gaps": tracing.top(s.idle_ns),
            "scopes": tracing.top(s.scope_ns)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--trace-dir", default=str(ROOT / ".bench_run" / "trace"))
    args = ap.parse_args(argv)
    print(json.dumps(report(split(load(args.trace_dir)))))
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src")]
    sys.exit(main())
