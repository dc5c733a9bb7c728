"""The traffic generator and the plain references, at a tiny size."""
from __future__ import annotations

import json
from collections import Counter
from itertools import islice

import numpy as np
import pytest

from bench_tiny import ROOT

from bench import gen, traffic
from bench.kinds import powerlaw, powerlaw_ref

MIXES = sorted((ROOT / "bench" / "traffic").glob("*.json"))
DOMAINS = {"vertices": 5000}


def take(stream, n):
    return list(islice(stream, n))


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_mix_is_seeded_and_in_range(path):
    mix = json.loads(path.read_text())
    big = 2 ** 31 + 12345
    a = take(traffic.stream(mix, DOMAINS, big, traffic.WINDOW), 50)
    b = take(traffic.stream(mix, DOMAINS, big, traffic.WINDOW), 50)
    c = take(traffic.stream(mix, DOMAINS, big + 1, traffic.WINDOW), 50)
    w = take(traffic.stream(mix, DOMAINS, big, traffic.WARMUP), 50)

    def key(reqs):
        return [(op, {k: np.asarray(v).tolist() for k, v in args.items()})
                for op, args in reqs]

    assert key(a) == key(b)
    assert key(a) != key(c) and key(a) != key(w)
    block = sum(int(op["share"]) for op in mix["ops"])
    want = Counter({op["op"]: int(op["share"]) for op in mix["ops"]})
    for reqs in (a, c):
        for i in range(0, 50 - block + 1, block):
            assert Counter(op for op, _ in reqs[i:i + block]) == want
    specs = {op["op"]: op["args"] for op in mix["ops"]}
    for op, args in a:
        for name, v in args.items():
            spec = specs[op][name]
            v = np.atleast_1d(v)
            assert v.min() >= 0 and v.max() < DOMAINS[spec["over"]]
            if spec["draw"] == "uniform_distinct":
                assert v.size == spec["count"] == np.unique(v).size


def test_powerlaw_reference_is_the_union_of_neighbor_sets():
    n = 3000
    src, dst = gen.powerlaw_graph(n, 24000, 0.9, 2.1, 300, seed=5)
    data = type("D", (), {"n": n, "src": src, "dst": dst})
    reqs = take(traffic.stream(
        {"ops": [{"op": "retrieve", "share": 1, "args": {"ids": {
            "draw": "uniform_distinct", "count": 40, "over": "vertices"}}}]},
        {"vertices": n}, 9, traffic.WINDOW), 6)
    ref = powerlaw_ref.prepare({}, data, reqs)
    adj = {}
    for s, d in zip(src.tolist(), dst.tolist()):
        adj.setdefault(s, []).append(d)
    for _, args in reqs:
        want = sorted(set().union(*(adj.get(v, []) for v in args["ids"])))
        got = powerlaw_ref.retrieve(ref, **args)
        assert got.tolist() == want
        cut = {v: sorted(adj.get(v, []))[:3] for v in args["ids"]}
        sampled = powerlaw_ref.retrieve(ref, **args, fanout=3)
        assert sampled.tolist() == sorted(set().union(*cut.values()))


def test_degree_sequence_is_a_seedless_power_law():
    n, m, top = 20000, 280000, 2000
    deg = gen.degree_sequence(n, m, 2.1, top)
    assert deg.sum() == m and deg.max() <= top and deg.min() >= 1
    assert np.all(np.diff(deg) <= 1)  # by rank, highest first
    # heavy tail: the top 1% of vertices hold far more than 1% of edges,
    # and the count above d falls about as d**-(alpha - 1)
    assert deg[:n // 100].sum() > 0.1 * m
    hi, lo = (deg >= 200).sum(), (deg >= 20).sum()
    assert 0.05 < hi / lo < 0.12  # 10**-1.1 = 0.079


def test_powerlaw_graph_has_hubs_on_seeded_vertices():
    n, m, top = 20000, 280000, 2000
    want = np.sort(gen.degree_sequence(n, m, 2.1, top))
    hubs = []
    for seed in (1, 2 ** 31 + 5):
        src, dst = gen.powerlaw_graph(n, m, 0.9, 2.1, top, seed=seed)
        assert np.all(src != dst) and m - len(src) < 100
        out = np.bincount(src, minlength=n)
        # the same degrees on every seed, up to the dropped self-loops
        assert np.abs(np.sort(out) - want).sum() == m - len(src)
        hubs.append(int(out.argmax()))
        assert want[-1] - 5 <= out.max() <= want[-1] <= top
    assert hubs[0] != hubs[1]


def test_shape_counts_pages_touched_and_rows_read():
    n, m, ps = 3000, 40000, 64
    src, dst = gen.powerlaw_graph(n, m, 0.9, 2.1, 2000, seed=8)
    data = powerlaw.make_data({"vertices": n, "edges": m, "locality": 0.9,
                               "alpha": 2.1, "max_degree": 2000,
                               "labels": 1, "label_density": 0.3,
                               "label_run_scale": 64, "page_size": ps}, 8)
    assert np.array_equal(data.src, src)
    rng = np.random.default_rng(2)
    for k in (1, 5, 40, 300):
        ids = rng.choice(n, k, replace=False)
        rows = [r for v in ids for r in range(data.offsets[v],
                                              data.offsets[v + 1])]
        pages = len({r // ps for r in rows})
        want = ("retrieve", 1 << max(pages - 1, 0).bit_length(),
                1 << max(len(rows) - 1, 0).bit_length())
        assert powerlaw.shape(data, "retrieve", ids) == want


def test_warm_up_runs_each_shape_before_settling():
    from bench.harness import warm_up
    ran = []
    reqs = iter([("op", {"x": x}) for x in [3, 1, 3, 2, 1, 5, 6, 7, 8, 9]])
    ops = {"op": lambda store, x: ran.append(x)}
    n = warm_up(None, ops, reqs, {"cover": 5, "warmup": 2, "settle": 1},
                lambda op, args: args["x"] % 3, dict)
    # of the first five, the first of each x % 3: 3, 1, 2; then two more
    assert ran == [3, 1, 2, 5, 6] and n == 5
