"""The program's spans and counters (``repro.obs``) on the retrieval path.

A fused retrieval opens the span of each layer boundary in a fixed
nesting, advances each counter by exactly what its dispatch moved, and
returns the same ids it returns untraced.  A filtered one opens the
label plane's span where it works, and counts each predicate plane it
has the device evaluate.  The kernel layer's trace
counter keeps its functions and values on top of the registry.
"""
import contextlib
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from _engines import engines
from repro import obs
from repro.core import (BY_SRC, ENC_GRAPHAR, L, LabelFilter, build_adjacency,
                        partition_column, retrieve_neighbors_batch)
from repro.core.schema import VertexTypeSchema
from repro.core.vertex import VertexTable
from repro.data.synthetic import clustered_labels, powerlaw_graph
from repro.kernels import _pad
from repro.kernels.pac_decode import ops as pdo

SRC = str(Path(__file__).resolve().parents[1] / "src")
N = 2000
PAGE = 256
TPS = 512
N_WORDS = -(-N // 32)

#: the spans one fused resident retrieval opens, then ``to_ids``
RESIDENT = [(obs.RETRIEVE, [(obs.EDGE_RANGES, []), (obs.PLAN, []),
                            (obs.PLAN, []), (obs.UPLOAD, []),
                            (obs.LAUNCH, []), (obs.PULL, []),
                            (obs.ASSEMBLE, [])]),
            (obs.TO_IDS, [])]
#: the same, with a fresh label filter: the filter's kernel inputs, its
#: qualifying hull in the page planning, its predicate plane at launch
FILTERED = [(obs.RETRIEVE, [(obs.EDGE_RANGES, []), (obs.FILTER, []),
                            (obs.PLAN, []), (obs.PLAN, [(obs.FILTER, [])]),
                            (obs.UPLOAD, []),
                            (obs.LAUNCH, [(obs.FILTER, [])]),
                            (obs.PULL, []), (obs.ASSEMBLE, [])]),
            (obs.TO_IDS, [])]


def _adj():
    src, dst = powerlaw_graph(N, 6, seed=13)
    return build_adjacency(src, dst, N, N, BY_SRC, ENC_GRAPHAR,
                           page_size=PAGE)


@pytest.fixture(scope="module")
def adj():
    return _adj()


@pytest.fixture(scope="module")
def batch():
    return np.random.default_rng(17).choice(N, 64, replace=False)


@pytest.fixture(scope="module")
def labels():
    return clustered_labels(N, ["A", "B"], density=0.3, run_scale=64,
                            seed=5)


@pytest.fixture(scope="module")
def vt(labels):
    return VertexTable.build(VertexTypeSchema("v", [], labels=list(labels),
                                              page_size=PAGE),
                             {}, labels, num_vertices=N)


@pytest.fixture
def spans(monkeypatch):
    """The spans the program opens, as a tree of (name, children)."""
    root = []
    stack = [root]

    @contextlib.contextmanager
    def record(name):
        node = (name, [])
        stack[-1].append(node)
        stack.append(node[1])
        try:
            yield
        finally:
            stack.pop()

    monkeypatch.setattr(obs, "span", record)
    return root


def _retrieve(adj, batch, engine, **kw):
    before = obs.counters()
    ids = retrieve_neighbors_batch(adj, batch, TPS, engine=engine,
                                   fused=True, **kw).to_ids()
    after = obs.counters()
    moved = {k: v - before.get(k, 0) for k, v in after.items()
             if k.startswith(("retrieve.", "transfer."))}
    return ids, moved


def _expected_ids(adj, batch):
    return retrieve_neighbors_batch(adj, batch, TPS,
                                    engine="numpy").to_ids()


def _shape(adj, batch):
    """(rows asked for, pages touched) of a batch."""
    los, his = adj.edge_ranges_batch(np.asarray(batch, np.int64))
    pages, _ = pdo.page_set_for_ranges(los, his, PAGE)
    return int((his - los).sum()), len(pages)


@pytest.mark.parametrize("engine", engines(kernel_only=True))
def test_resident_retrieval_opens_each_span_in_its_nesting(adj, batch,
                                                           engine, spans):
    ids, _ = _retrieve(adj, batch, engine, resident=True)
    assert spans == RESIDENT
    assert {name for name, _ in RESIDENT} | \
        {name for _, kids in RESIDENT for name, _ in kids} == set(obs.SPANS)
    np.testing.assert_array_equal(ids, _expected_ids(adj, batch))


def _filter_counts(adj, batch, engine, filt, resident=True):
    """Ids of one filtered retrieval, and how far it moved the label
    plane's counters."""
    before = obs.counters()
    ids = retrieve_neighbors_batch(adj, batch, TPS, engine=engine,
                                   fused=True, resident=resident,
                                   filter=filt).to_ids()
    after = obs.counters()
    return ids, {k: after.get(k, 0) - before.get(k, 0)
                 for k in ("filter.requests", "filter.planes")}


def _filtered_ids(adj, batch, labels, keep):
    """The numpy oracle's ids where ``keep`` of the label columns
    holds."""
    want = _expected_ids(adj, batch)
    return want[keep(labels)[want]]


#: label conditions, each with its numpy twin over the label columns
CONDS = [(L("A"), lambda c: c["A"]),
         ((L("A") & ~L("B")) | L("B"), lambda c: (c["A"] & ~c["B"]) | c["B"])]
COND_IDS = [repr(cond) for cond, _ in CONDS]


@pytest.mark.parametrize("engine", engines(kernel_only=True))
@pytest.mark.parametrize("cond,keep", CONDS, ids=COND_IDS)
def test_filtered_retrieval_opens_the_filter_span_in_its_nesting(
        adj, batch, vt, labels, engine, cond, keep, spans):
    ids, _ = _filter_counts(adj, batch, engine, LabelFilter(vt, cond))
    assert spans == FILTERED
    assert {name for name, _ in FILTERED} | \
        {name for _, kids in FILTERED for name, _ in kids} == \
        set(obs.ALL_SPANS)
    np.testing.assert_array_equal(ids,
                                  _filtered_ids(adj, batch, labels, keep))


@pytest.mark.parametrize("engine", engines(kernel_only=True))
@pytest.mark.parametrize("cond,keep", CONDS, ids=COND_IDS)
def test_filtered_retrieval_counts_each_plane_it_builds(adj, batch, vt,
                                                        labels, engine,
                                                        cond, keep):
    """A fresh filter builds its plane on the device; a reused one
    ANDs the plane it already has."""
    want = _filtered_ids(adj, batch, labels, keep)
    filt = LabelFilter(vt, cond)
    for planes in (1, 0):
        ids, moved = _filter_counts(adj, batch, engine, filt)
        assert moved == {"filter.requests": 1, "filter.planes": planes}
        np.testing.assert_array_equal(ids, want)
    _, moved = _filter_counts(adj, batch, engine, LabelFilter(vt, cond))
    assert moved == {"filter.requests": 1, "filter.planes": 1}


@pytest.mark.parametrize("engine", engines(kernel_only=True))
def test_per_dispatch_pack_path_counts_a_plane_per_dispatch(adj, batch, vt,
                                                            labels, engine):
    """The per-dispatch pack path evaluates the plane inside each
    dispatch, reused filter or not."""
    cond, keep = CONDS[1]
    filt = LabelFilter(vt, cond)
    for _ in range(2):
        ids, moved = _filter_counts(adj, batch, engine, filt, resident=False)
        assert moved == {"filter.requests": 1, "filter.planes": 1}
        np.testing.assert_array_equal(
            ids, _filtered_ids(adj, batch, labels, keep))


@pytest.mark.parametrize("engine", engines(kernel_only=True))
def test_resident_retrieval_counts_what_it_moved(adj, batch, engine):
    ids, moved = _retrieve(adj, batch, engine, resident=True)
    rows, n_pages = _shape(adj, batch)
    p_pad = pdo._page_class(n_pages, len(adj.table["<dst>"].encoded.pages))
    # the staged vector [idx | gidx | total], int32
    staged = 4 * (p_pad + _pad.size_class(rows, pdo.RANGE_CLASS_MIN) + 1)
    assert moved == {"retrieve.requests": 1, "retrieve.rows": rows,
                     "retrieve.lanes_decoded": p_pad * (PAGE - 1),
                     "transfer.h2d_bytes": staged,
                     "transfer.d2h_bytes": 4 * N_WORDS}
    np.testing.assert_array_equal(ids, _expected_ids(adj, batch))


@pytest.mark.parametrize("engine", engines(kernel_only=True))
def test_per_dispatch_pack_path_counts_what_it_moved(adj, batch, engine,
                                                     spans):
    ids, moved = _retrieve(adj, batch, engine, resident=False)
    rows, n_pages = _shape(adj, batch)
    m_pad = _pad.next_pow2(n_pages)   # no cache: every page is a miss
    packed = pdo.pack_page_list(adj.table["<dst>"].encoded, [0])
    shipped = (m_pad * sum(a.nbytes for a in packed)   # packed miss pages
               + 4 * PAGE                              # one empty cached row
               + 4 * _pad.size_class(rows, pdo.RANGE_CLASS_MIN)  # gidx
               + 4)                                    # total
    assert moved == {"retrieve.requests": 1, "retrieve.rows": rows,
                     "retrieve.lanes_decoded": m_pad * (PAGE - 1),
                     "transfer.h2d_bytes": shipped,
                     "transfer.d2h_bytes": 4 * N_WORDS}
    (root, kids), to_ids = spans
    assert root == obs.RETRIEVE and to_ids == (obs.TO_IDS, [])
    # six packed arrays, the cached rows, the row positions and the total
    assert [k for k, _ in kids] == [obs.EDGE_RANGES] + [obs.PLAN] * 3 \
        + [obs.UPLOAD] * 9 + [obs.LAUNCH, obs.PULL, obs.ASSEMBLE]
    np.testing.assert_array_equal(ids, _expected_ids(adj, batch))


@pytest.mark.parametrize("engine", engines(kernel_only=True))
def test_partitioned_retrieval_opens_the_same_spans(batch, engine, spans):
    adj = _adj()
    partition_column(adj.table["<dst>"].encoded, 2)
    ids, moved = _retrieve(adj, batch, engine, resident=True)
    rows, _ = _shape(adj, batch)
    assert moved["retrieve.requests"] == 1
    assert moved["retrieve.rows"] == rows
    assert moved["transfer.d2h_bytes"] % (4 * N_WORDS) == 0
    assert moved["transfer.h2d_bytes"] > 0
    (root, kids), to_ids = spans
    assert root == obs.RETRIEVE and to_ids == (obs.TO_IDS, [])
    assert {k for k, _ in kids} == {obs.EDGE_RANGES, obs.PLAN, obs.UPLOAD,
                                    obs.LAUNCH, obs.PULL, obs.ASSEMBLE}
    np.testing.assert_array_equal(ids, _expected_ids(adj, batch))


@pytest.mark.parametrize("engine,page,partitions,kernel", [
    ("pallas", 1024, None, True),     # slab plan: the Mosaic kernel
    ("pallas", 1024, 2, True),        # partition plane, single-shard tail
    ("pallas", PAGE, None, False),    # no whole slab: the XLA gathers
    ("jax", 1024, None, False),       # the jnp engine's row plan
])
def test_kernel_pages_count_each_slab_dispatch(batch, engine, page,
                                               partitions, kernel):
    """``decode.kernel_pages`` advances by each dispatch's ``p_pad``
    where the slab kernel decodes, and stays put on the XLA path."""
    src, dst = powerlaw_graph(N, 6, seed=13)
    adj = build_adjacency(src, dst, N, N, BY_SRC, ENC_GRAPHAR,
                          page_size=page)
    col = adj.table["<dst>"].encoded
    cap = len(col.pages)    # the page class's cap: the plan's rows
    if partitions:
        cap = partition_column(col, partitions).stack_rows
    want = retrieve_neighbors_batch(adj, batch, TPS, engine="numpy").to_ids()
    for sub in (batch[:16], batch):
        los, his = adj.edge_ranges_batch(np.asarray(sub, np.int64))
        n_pages = len(pdo.page_set_for_ranges(los, his, page)[0])
        before = obs.counters().get("decode.kernel_pages", 0)
        ids = retrieve_neighbors_batch(adj, sub, TPS, engine=engine,
                                       fused=True, resident=True).to_ids()
        moved = obs.counters().get("decode.kernel_pages", 0) - before
        assert moved == (pdo._page_class(n_pages, cap) if kernel else 0)
        np.testing.assert_array_equal(ids, retrieve_neighbors_batch(
            adj, sub, TPS, engine="numpy").to_ids())
    np.testing.assert_array_equal(ids, want)


def test_counters_are_copies_and_reset_by_prefix():
    obs.count("test_obs.a")
    obs.count("test_obs.a", 4)
    obs.count("test_obs.b", 2)
    got = obs.counters()
    assert got["test_obs.a"] == 5 and got["test_obs.b"] == 2
    got["test_obs.a"] = 0
    assert obs.counters()["test_obs.a"] == 5
    obs.reset("test_obs.")
    assert not any(k.startswith("test_obs.") for k in obs.counters())


def test_trace_counts_read_the_same_on_the_registry():
    _pad.reset_trace_counts()
    assert _pad.trace_count() == 0 and _pad.trace_counts() == {}
    _pad.note_trace("entry_a")
    _pad.note_trace("entry_a")
    _pad.note_trace("entry_b")
    obs.count("retrieve.requests")   # other counters are not traces
    assert _pad.trace_counts() == {"entry_a": 2, "entry_b": 1}
    assert _pad.trace_count() == 3
    assert _pad.trace_count("entry_a") == 2
    assert obs.counters()["traces/entry_a"] == 2
    _pad.reset_trace_counts()
    assert _pad.trace_count() == 0
    assert "retrieve.requests" in obs.counters()


def test_the_storage_plane_imports_no_jax():
    """``repro.obs`` binds JAX on a span's first use, so importing the
    numpy storage plane, which opens spans, loads no JAX."""
    code = ("import sys, repro.core, repro.obs; "
            "print(sorted(m for m in sys.modules if m.split('.')[0] == 'jax'))")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True,
                         env={**os.environ, "PYTHONPATH": SRC})
    assert out.stdout.strip() == "[]"
