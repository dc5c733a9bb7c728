"""What the program records about itself in a trace: its spans, the
device ops' name stacks, its counters, and the readers of each."""
from __future__ import annotations

import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest
from jax.profiler import ProfileData

from bench_tiny import ROOT  # noqa: F401  (puts the repo on sys.path)

from bench import harness, program_trace as pt, tracing
from repro import obs

DATA = Path(__file__).resolve().parent / "data"
SNB_SPANS = [tracing.WINDOW_SPAN, tracing.REQUEST_SPAN, "is3_graphar",
             "ic8_graphar"]
LJ_SPANS = [tracing.WINDOW_SPAN, tracing.REQUEST_SPAN,
            "retrieve_neighbors_batch", "to_ids"]
#: the per-layer metrics that read no program span, name stack or
#: ``repro.obs`` counter
FIRST_FIVE = ("device_idle_pct", "device_ms_per_req", "launches_per_req",
              "host_ms_per_req", "retraces_in_window")
COUNTER_READERS = ("h2d_bytes_per_req", "d2h_bytes_per_req",
                   "decode_used_pct")


def _iv(*pairs):
    return np.array(pairs, np.float64).reshape(-1, 2)


def _reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


def _lj() -> pt.ProgramTrace:
    return pt.from_text_proto((DATA / "lj_trace.textproto").read_text())


def hand_built(spans=None) -> pt.ProgramTrace:
    """Window [0, 100); one request [0, 90) holding a root span [10, 80)
    with two children, one of them twice; a sibling [80, 90); ops with
    name stacks, one outside the window."""
    return pt.ProgramTrace(
        trace=tracing.Trace(
            ops={"/device:TPU:0": (["a", "b", "c", "d"],
                                   _iv((30, 40), (35, 50), (60, 70),
                                       (95, 110)))},
            modules={"/device:TPU:0": _iv((30, 50), (60, 70), (95, 110))},
            spans=spans or {tracing.WINDOW_SPAN: _iv((0, 100)),
                            tracing.REQUEST_SPAN: _iv((0, 90)),
                            "root": _iv((10, 80)),
                            "child": _iv((20, 30), (40, 55)),
                            "leaf": _iv((60, 75)),
                            "sibling": _iv((80, 90)),
                            "never": _iv()}),
        stacks={"/device:TPU:0": ["jit(f)/gather_rows/jit(_take)/gather:",
                                  "jit(f)/decode/gather_words/gather:",
                                  "jit(f)/decode/pallas_call:",
                                  "jit(f)/gather_rows/gather:"]})


def test_intersect():
    u = _iv((0, 3), (5, 9), (10, 11))
    b = _iv((1, 6), (8, 20))
    assert tracing.union(pt.intersect(u, b)).tolist() == \
        [[1, 3], [5, 6], [8, 9], [10, 11]]
    assert pt.intersect(u, _iv()).shape == (0, 2)


def test_span_union_self_time_and_busy_inside():
    s = pt.split(hand_built())
    assert "never" not in s.span_ns and s.requests == 1
    assert s.span_ns == {tracing.REQUEST_SPAN: 90, "root": 70,
                         "child": 25, "leaf": 15, "sibling": 10}
    # root minus its children [20, 30) + [40, 55) + [60, 75)
    assert s.span_self_ns["root"] == 70 - 10 - 15 - 15
    # the request minus root and sibling, which it holds
    assert s.span_self_ns[tracing.REQUEST_SPAN] == 90 - 70 - 10
    assert s.span_self_ns["child"] == 25 and s.span_self_ns["leaf"] == 15
    # busy [30, 50) + [60, 70) in the window
    assert s.span_busy_ns == {tracing.REQUEST_SPAN: 30, "root": 30,
                              "child": 10, "leaf": 10, "sibling": 0}
    # the op at 95 counts up to the window's end
    assert s.scope_ns == {"jit(f)": 10 + 15 + 10 + 5, "gather_rows": 10 + 5,
                          "jit(_take)": 10, "gather": 10 + 15 + 5,
                          "decode": 15 + 10, "gather_words": 15,
                          "pallas_call": 10}
    # idle [0, 30) [50, 60) [70, 95) by the innermost span
    assert s.idle_ns == {tracing.REQUEST_SPAN: 10, "root": 10 + 5 + 5,
                         "child": 10 + 5, "leaf": 5, "sibling": 10,
                         "no host span": 5}


def test_self_time_ignores_spans_that_only_overlap():
    spans = {"a": _iv((0, 10)), "b": _iv((5, 15)), "c": _iv((2, 4))}
    assert pt.self_time(spans, "a") == 10 - 2
    assert pt.self_time(spans, "b") == 10


def test_aligned_moves_early_programs_to_their_launch():
    mods = _iv((10, 30), (31, 35), (60, 80))
    ops = _iv((5, 8), (10, 20), (20, 30), (31, 35), (60, 80))
    launches = _iv((12, 13), (65, 66))
    # both programs of the first launch move as much as the first (2),
    # the third moves to its own launch (5); an op before every program
    # stays
    np.testing.assert_array_equal(
        pt.aligned(ops, mods, launches),
        _iv((5, 8), (12, 22), (22, 32), (33, 37), (65, 85)))
    # with no launch span, or a program that ends before its launch
    # opens, nothing moves
    np.testing.assert_array_equal(pt.aligned(ops, mods, _iv()), ops)
    np.testing.assert_array_equal(
        pt.aligned(_iv((10, 11)), _iv((10, 11)), _iv((20, 21))),
        _iv((10, 11)))


def test_busy_inside_spans_is_read_on_the_host_clock():
    """A program that the trace puts 5 ns before its launch span opened
    is counted from the launch on, in the spans' busy and idle time."""
    trace = tracing.Trace(
        ops={"/device:TPU:0": (["a"], _iv((20, 40)))},
        modules={"/device:TPU:0": _iv((20, 40))},
        spans={tracing.WINDOW_SPAN: _iv((0, 100)),
               tracing.REQUEST_SPAN: _iv((0, 90)),
               "graphar.plan": _iv((10, 25)),
               pt.LAUNCH_SPAN: _iv((25, 27)),
               "graphar.pull": _iv((27, 50))})
    s = pt.split(pt.ProgramTrace(trace, {}))
    assert s.span_busy_ns == {tracing.REQUEST_SPAN: 20, "graphar.plan": 0,
                              pt.LAUNCH_SPAN: 2, "graphar.pull": 18}
    assert s.idle_ns["graphar.plan"] == 15 and s.idle_ns["graphar.pull"] == 5
    assert list(s.span_idle_req_ns["graphar.pull"]) == [5]
    assert list(s.span_idle_req_ns["graphar.plan"]) == [15]
    # the harness's own reduction reads the times as recorded
    assert tracing.summarize(trace).idle_ns["graphar.plan"] == 10


def test_phases_on_a_hand_built_trace():
    s = pt.split(hand_built({
        tracing.WINDOW_SPAN: _iv((0, 100)),
        tracing.REQUEST_SPAN: _iv((0, 50), (50, 100)),
        "graphar.edge_ranges": _iv((0, 10)),
        "graphar.plan": _iv((10, 20), (50, 60)),
        "graphar.upload": _iv((20, 25)),
        "graphar.launch": _iv((25, 30)),
        "graphar.pull": _iv((30, 50), (60, 98)),
        "graphar.assemble": _iv((98, 99)),
        "graphar.to_ids": _iv((99, 100))}))
    got = pt.phases(s)
    assert got["prep_ms_per_req"] == pytest.approx((10 + 20 + 5 + 5) / 2e6)
    assert got["assemble_ms_per_req"] == pytest.approx(2 / 2e6)
    assert got["gather_ms_per_req"] == pytest.approx((10 + 5 + 15) / 2e6)
    # the pull's idle time in each request: [30, 50) less busy [30, 50);
    # [60, 98) less busy [60, 70) and [95, 98): the median of 0 and 25
    assert list(s.span_idle_req_ns["graphar.pull"]) == [0, 25]
    assert got["pull_ms_per_req"] == pytest.approx(12.5 / 1e6)


def test_phases_without_the_program_spans():
    """The recorded SNB trace holds no program span and no name stack."""
    s = pt.split(pt.from_text_proto(
        (DATA / "snb_trace.textproto").read_text()))
    assert s.requests > 0 and not any(n in s.span_ns for n in obs.SPANS)
    assert pt.phases(s) == dict.fromkeys(
        ["prep_ms_per_req", "pull_ms_per_req", "assemble_ms_per_req",
         "gather_ms_per_req"])


def test_recorded_trace_names_the_gathers_by_their_scopes():
    """Three requests of ``lj-retrieve-b1024`` recorded on the chip: the
    name stacks come from the ``tf_op`` stat of each op's metadata, and
    the word gather, whatever XLA numbers its fusion, is the
    ``gather_words`` scope; the page-row gather is ``gather_rows``."""
    p = _lj()
    (names, _), = p.trace.ops.values()
    (stacks,) = p.stacks.values()
    # ops XLA adds itself (copies, slices, loops) carry no name stack
    assert len(stacks) == len(names) and any(stacks)
    word = {st for n, st in zip(names, stacks) if "%fusion.10 u32" in n}
    assert word == {"jit(fused_gather_decode_bitmap_batch)/decode/"
                    "gather_words/jit(take_along_axis)/gather:"}
    s = pt.split(p)
    busy = tracing.summarize(p.trace).busy_ns
    assert 0 < s.scope_ns["gather_rows"] < s.scope_ns["gather_words"]
    assert s.scope_ns["gather_words"] > 0.95 * busy
    assert {"decode", "bitmap_tail"} <= set(s.scope_ns)
    got = pt.phases(s)
    assert got["gather_ms_per_req"] == pytest.approx(
        (s.scope_ns["gather_rows"] + s.scope_ns["gather_words"]) / 1e6 / 3)
    # every span of the program opened once a request, plan twice
    assert {n: len(p.trace.spans[n]) for n in obs.SPANS} == {
        **dict.fromkeys(obs.SPANS, 3), obs.PLAN: 6}
    # the program's spans hold nearly all the idle time
    program = sum(v for k, v in s.idle_ns.items() if k in obs.SPANS)
    assert program > 0.9 * sum(s.idle_ns.values())
    assert tracing.top(s.idle_ns, 1)[0][0] == obs.TO_IDS
    assert all(v > 0 for v in got.values())


def test_recorded_trace_is_read_on_the_host_clock():
    """On the chip's trace each request's program starts 0.78-0.89 ms
    before the ``graphar.launch`` span that enqueued it, before its
    inputs were even uploaded.  Put on the host's clock, no device time
    falls inside the planning or upload, and the pull's idle time loses
    that offset: 4.240 ms over three requests; the median request's,
    1.357 ms."""
    p = _lj()
    (mods,) = p.trace.modules.values()
    early = p.trace.spans[obs.LAUNCH][:, 0] - np.sort(mods[:, 0])
    assert np.all((0.7e6 < early) & (early < 0.9e6))
    s = pt.split(p)
    assert s.span_busy_ns[obs.PLAN] == s.span_busy_ns[obs.UPLOAD] == 0
    assert s.span_busy_ns[obs.EDGE_RANGES] == 0
    idle_pull = s.span_ns[obs.PULL] - s.span_busy_ns[obs.PULL]
    assert idle_pull == pytest.approx(4.240112e6)
    np.testing.assert_allclose(s.span_idle_req_ns[obs.PULL],
                               [1.357113e6, 1.530543e6, 1.352456e6])
    assert pt.phases(s)["pull_ms_per_req"] == pytest.approx(1.357113)


def test_command_line_splits_a_recorded_trace(tmp_path, capsys):
    xplane = tmp_path / "plugins" / "profile" / "run" / "host.xplane.pb"
    xplane.parent.mkdir(parents=True)
    xplane.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        (DATA / "lj_trace.textproto").read_text()))
    assert pt.main(["--trace-dir", str(tmp_path)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["requests"] == 3
    assert out["phases"] == pytest.approx(pt.phases(pt.split(_lj())))
    assert out["idle_gaps"][0][0] == obs.TO_IDS
    assert {s[0] for s in out["scopes"]} >= {"gather_rows", "gather_words"}
    assert set(out["spans"]) == {tracing.REQUEST_SPAN, *obs.SPANS}


@pytest.mark.parametrize("fixture,spans", [("snb_trace", SNB_SPANS),
                                           ("lj_trace", LJ_SPANS)])
def test_first_five_metrics_ignore_the_program_spans(fixture, spans):
    """Loading the program's spans changes none of the metrics that
    read no program span."""
    data = ProfileData.from_text_proto(
        (DATA / f"{fixture}.textproto").read_text())
    values = []
    for names in (spans, spans + list(obs.SPANS)):
        run = types.SimpleNamespace(
            summary=tracing.summarize(tracing.from_profile(data, names)),
            completed=3, counters_before={"traces": 1},
            counters_after={"traces": 1})
        values.append([_reader(m).read(run) for m in FIRST_FIVE])
    assert values[0] == values[1] and None not in values[0]


def _traced_run():
    return types.SimpleNamespace(summary=object(), completed=4)


def test_counter_readers(monkeypatch):
    monkeypatch.setattr(obs, "counters", lambda: {
        "retrieve.requests": 4, "retrieve.rows": 50,
        "retrieve.lanes_decoded": 5000, "transfer.h2d_bytes": 400,
        "transfer.d2h_bytes": 800, "traces/x": 3})
    assert _reader("h2d_bytes_per_req").read(_traced_run()) == 100
    assert _reader("d2h_bytes_per_req").read(_traced_run()) == 200
    assert _reader("decode_used_pct").read(_traced_run()) == 1.0


@pytest.mark.parametrize("name", COUNTER_READERS)
def test_counter_readers_without_their_counters(name, monkeypatch):
    reader = _reader(name)
    # an untraced run
    assert reader.read(types.SimpleNamespace(summary=None)) is None
    # no retrieval ran
    monkeypatch.setattr(obs, "counters", lambda: {"traces/x": 1})
    assert reader.read(_traced_run()) is None
    # a program without repro.obs
    import repro
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert pt.program_spans() == ()
    assert reader.read(_traced_run()) is None


def test_warm_up_settles_while_the_program_counters_move():
    """Warm-up watches traces only: the program's own counters, which
    move on every request, never keep it from settling."""
    def op(store, x):
        obs.count("retrieve.requests")

    before = obs.counters().get("retrieve.requests", 0)
    reqs = iter([("op", {"x": x}) for x in range(100)])
    n = harness.warm_up(None, {"op": op}, reqs,
                        {"cover": 0, "warmup": 4, "settle": 3},
                        lambda op, args: None, harness.program_counters)
    assert n == 4
    assert obs.counters()["retrieve.requests"] - before == 4
