"""The label-filter cell on a tiny copy: the program's filtered answers
are ``correct`` against the plain reference for every predicate, each
fault a filtered cell can have is not, the label plane's readers read
what they should, and the traffic holds its predicates 1:2:1:2.

The chip check is skipped; everything else of a run is driven on the
CPU.
"""
from __future__ import annotations

import json
import types

import numpy as np
import pytest

from bench_tiny import harness, run, tiny_root

from bench import program_trace, traffic
from bench.control import control_program
from bench.kinds import powerlaw_filter_ref, powerlaw_ref

CELL = "lj-filter"
READERS = ("filter_host_ms_per_req", "filter_device_ms_per_req",
           "filter_planes_per_req")


def filter_root(tmp):
    """``tiny_root`` with a tiny copy of ``lj-filter-b1024`` beside its
    ``lj`` cell: the real label configuration at ``lj-tiny``'s size, the
    real mix at 64 ids a request."""
    root = tiny_root(tmp)
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "lj-social-labels.json")
                     .read_text())
    cfg.update(name="lj-labels-tiny", vertices=6000, edges=60000,
               max_degree=600, label_run_scale=64)
    (bench / "configs" / "lj-labels-tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "label-filter-b1024.json")
                     .read_text())
    for op in mix["ops"]:
        op["args"]["ids"]["count"] = 64
    mix.update(cover=50, warmup=2, settle=1)
    (bench / "traffic" / "tiny-filter-b64.json").write_text(json.dumps(mix))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append(
        {"name": "lj-labels-tiny", "source": "test",
         "file": "bench/configs/lj-labels-tiny.json",
         "reduced": ["vertices", "edges", "max_degree", "label_run_scale"],
         "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "lj-labels-tiny",
                              "traffic": "tiny-filter-b64", "chips": 1,
                              "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return filter_root(tmp_path_factory.mktemp("tiny_filter"))


@pytest.fixture(scope="module")
def cell(root):
    return harness.Cell(root, CELL)


@pytest.fixture(scope="module")
def world(cell):
    """The cell's data, the program's store and the reference, for one
    seed."""
    data = cell.kind.make_data(cell.cfg, 5)
    store = cell.kind.build(cell.cfg, data)
    ref = cell.ref.prepare(cell.cfg, data, None)
    return data, store, ref


@pytest.mark.parametrize("i", range(4))
def test_each_predicate_matches_the_reference(cell, world, i):
    data, store, ref = world
    op = f"filter{i}"
    rng = np.random.default_rng(i)
    kept = []
    for _ in range(3):
        ids = rng.choice(data.n, 64, replace=False)
        got = cell.kind.OPS[op](store, ids=ids)
        want = cell.ref.OPS[op](ref, ids=ids)
        np.testing.assert_array_equal(got, want)
        unfiltered = powerlaw_ref.retrieve(ref, ids)
        assert set(want) <= set(unfiltered)
        kept.append(len(want) / max(len(unfiltered), 1))
    # every predicate keeps some neighbors and drops others
    assert 0 < np.mean(kept) < 1


def test_program_is_correct_for_every_predicate(root):
    seen = set()

    def record(name, op):
        def f(store, **args):
            seen.add(name)
            return op(store, **args)
        return f

    ops = {name: record(name, op)
           for name, op in harness.Cell(root, CELL).kind.OPS.items()}
    out = run(root, CELL, ops=ops)
    assert out["correct"], out
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"] == {"mismatched": {"value": 0, "limit": 0},
                             "failed": {"value": 0, "limit": 0}}
    assert seen == {"filter0", "filter1", "filter2", "filter3"}


def stale(cell):
    """Each request gets the previous request's answer."""
    last = []

    def wrap(op):
        def f(store, **args):
            last.append(op(store, **args))
            return last[-2] if len(last) > 1 else last[-1]
        return f
    return {name: wrap(op) for name, op in cell.kind.OPS.items()}


def altered(cell):
    """One value of each answer changed."""
    def wrap(op):
        def f(store, **args):
            out = np.array(op(store, **args), copy=True)
            if out.size == 0:
                return np.array([7], np.int64)
            out[0] += 1
            return out
        return f
    return {name: wrap(op) for name, op in cell.kind.OPS.items()}


def unfiltered(cell):
    """The predicate ignored: every neighbor of the batch."""
    def f(store, ids):
        from repro.core import retrieve_neighbors_batch
        return retrieve_neighbors_batch(
            store.adj, ids, store.tps, engine=store.engine,
            resident=store.resident).to_ids()
    return {name: f for name in cell.kind.OPS}


def swapped(cell):
    """Each op answers with the next op's predicate."""
    names = sorted(cell.kind.OPS)
    return {name: cell.kind.OPS[names[(k + 1) % len(names)]]
            for k, name in enumerate(names)}


@pytest.mark.parametrize("fault", [stale, altered, unfiltered, swapped])
def test_fault_is_not_correct(root, cell, fault):
    out = run(root, CELL, ops=fault(cell))
    assert not out["correct"], out
    assert out["checks"]["mismatched"]["value"] > 0


def test_control_is_not_correct(root, cell):
    build, ops = control_program(cell, fanout=2)
    out = run(root, CELL, build=build, ops=ops, counters=dict)
    assert not out["correct"], out
    assert out["checks"]["mismatched"]["value"] > 0


def _conds():
    from repro.core import L
    return [L("l0"), (L("l0") & ~L("l1")) | L("l2"), L("l3") & L("l4"),
            ~(L("l5") | L("l6")) | L("l7")]


@pytest.mark.parametrize("i", range(4))
def test_predicate_text_builds_the_same_condition(cell, i):
    from repro.core import L
    text = cell.cfg["predicates"][i]
    assert repr(powerlaw_filter_ref.predicate(text, L)) == repr(_conds()[i])


@pytest.mark.parametrize("text", [
    '__import__("os")', 'L("l0") + L("l1")', 'L(l0)', 'L("a", "b")',
    'M("l0")'])
def test_predicate_text_refuses_anything_else(text):
    with pytest.raises(ValueError):
        powerlaw_filter_ref.predicate(text, lambda name: name)


def _reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


def _traced(idle_ns, op_ns, completed=4):
    summary = types.SimpleNamespace(idle_ns=idle_ns, op_ns=op_ns)
    return types.SimpleNamespace(summary=summary, completed=completed)


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_without_a_trace(name):
    run_ = types.SimpleNamespace(summary=None, completed=3,
                                 counters_before={}, counters_after={})
    assert _reader(name).read(run_) is None


def test_readers_read_a_filtered_run(monkeypatch):
    monkeypatch.setattr(program_trace, "program_counters", lambda run: {
        "filter.requests": 40, "filter.planes": 40, "traces": 9})
    run_ = _traced({"graphar.filter": 6e6, "graphar.launch": 1e6},
                   {"jit_cond_bitmap_pallas/%fusion.3 s32[3,32,151488]": 2e6,
                    "jit_cond_bitmap_pallas/%custom-call s32[1,151488]": 2e6,
                    "jit_fused_gather_decode_filter_bitmap_batch/%x": 9e6})
    got = {name: _reader(name).read(run_) for name in READERS}
    assert got == {"filter_host_ms_per_req": 1.5,
                   "filter_device_ms_per_req": 1.0,
                   "filter_planes_per_req": 1.0}


def test_readers_read_zero_where_no_request_carries_a_filter(monkeypatch):
    """The unfiltered cell (``lj``): no label-plane span opens, no plane
    program runs, no counter moves."""
    monkeypatch.setattr(program_trace, "program_counters", lambda run: {
        "retrieve.requests": 40, "traces": 9})
    run_ = _traced({"graphar.to_ids": 6e6},
                   {"jit_fused_gather_decode_bitmap_batch/%x": 9e6})
    assert {name: _reader(name).read(run_) for name in READERS} == \
        dict.fromkeys(READERS, 0.0)


def test_readers_read_nothing_on_a_program_without_the_label_span(
        monkeypatch):
    """A program older than the label plane's span and counters."""
    from repro import obs
    monkeypatch.delattr(obs, "ALL_SPANS")
    monkeypatch.setattr(program_trace, "program_counters",
                        lambda run: {"retrieve.requests": 40})
    run_ = _traced({"graphar.to_ids": 6e6}, {})
    assert _reader("filter_host_ms_per_req").read(run_) is None
    assert _reader("filter_planes_per_req").read(run_) is None


def test_traffic_is_seeded_and_balanced_per_block():
    mix = json.loads((harness.BENCH / "traffic" / "label-filter-b1024.json")
                     .read_text())
    domains = {"vertices": 4847571}

    def take(seed, n=60):
        reqs = traffic.stream(mix, domains, seed, traffic.WINDOW)
        return [next(reqs) for _ in range(n)]

    a, b, c = take(2616000001), take(2616000001), take(2616000002)
    assert [op for op, _ in a] == [op for op, _ in b]
    assert all(np.array_equal(x["ids"], y["ids"])
               for (_, x), (_, y) in zip(a, b))
    assert [op for op, _ in a] != [op for op, _ in c]
    for k in range(0, len(a), 6):
        block = [op for op, _ in a[k:k + 6]]
        assert sorted(block) == ["filter0", "filter1", "filter1",
                                 "filter2", "filter3", "filter3"]
    for _, args in a:
        assert len(np.unique(args["ids"])) == 1024
        assert 0 <= args["ids"].min() and args["ids"].max() < 4847571


def test_every_seed_places_the_same_label_runs(cell):
    """Each label keeps its run count, run lengths and density from seed
    to seed; only where the runs lie changes."""
    def runs(col):
        edges = np.flatnonzero(np.diff(col.astype(np.int8))) + 1
        return np.diff(np.r_[0, edges, len(col)]), bool(col[0])

    a, b = (cell.kind.make_data(cell.cfg, s).labels for s in (5, 2616000001))
    assert list(a) == [f"l{i}" for i in range(cell.cfg["labels"])]
    moved = 0
    for name in a:
        (la, fa), (lb, fb) = runs(a[name]), runs(b[name])
        assert fa == fb and len(la) == len(lb)
        assert sorted(la[0::2]) == sorted(lb[0::2])
        assert sorted(la[1::2]) == sorted(lb[1::2])
        assert a[name].sum() == b[name].sum()
        assert not a[name].flags.writeable
        moved += not np.array_equal(a[name], b[name])
    assert moved == len(a)
