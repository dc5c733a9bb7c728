"""Partition plane: explicit graph partitions + sharded batched retrieval.

The invariant under test everywhere: partitioning is *invisible* except
in placement, pruning counters, and wall time.  Sharded retrieval over
any partition count must return bit-identical ids and IOMeter accounting
to the single-device resident path (and the numpy oracle), the
1-partition case must reduce to the monolithic PR 4 path outright, and
statistics pruning may only ever *remove* charged I/O while leaving ids
untouched.

Runs on any device count: under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the multi-device
CI job) the SPMD tail executes across a real mesh; on one device the
degenerate single-shard tail covers the same interfaces.  Forced-SPMD
tests pin ``SHARD_MIN_PAGES`` to 0 so the ``shard_map`` path runs even
for small dispatches.
"""
import numpy as np
import pytest

from _engines import engines
from _hypothesis_shim import given, settings, st
from repro.core import (BY_SRC, ENC_GRAPHAR, IOMeter, L, LabelFilter,
                        attach_page_cache, build_adjacency, k_hop,
                        live_partitions, pack_column, partition_bounds,
                        partition_column, retrieve_neighbors_batch)
from repro.core.encoding import delta_encode_column, delta_encode_page
from repro.core.page_cache import DecodedPageCache
from repro.core.schema import VertexTypeSchema
from repro.core.vertex import VertexTable
from repro.data.synthetic import clustered_labels, powerlaw_graph
from repro.kernels import _pad
from repro.kernels.pac_decode import ops as pdo

N = 2000
PAGE = 256
TPS = 512
PART_COUNTS = (1, 2, 3, 8)


def _graph():
    return powerlaw_graph(N, 6, seed=13)


@pytest.fixture(scope="module")
def adj_pair():
    """(monolithic, partition-ready) adjacencies over the same edges."""
    src, dst = _graph()
    mono = build_adjacency(src, dst, N, N, BY_SRC, ENC_GRAPHAR,
                           page_size=PAGE)
    part = build_adjacency(src, dst, N, N, BY_SRC, ENC_GRAPHAR,
                           page_size=PAGE)
    return mono, part


@pytest.fixture(scope="module")
def vt():
    labels = clustered_labels(N, ["A", "B"], density=0.3, run_scale=64,
                              seed=7)
    return VertexTable.build(VertexTypeSchema("v", [], labels=["A", "B"]),
                             {}, labels, num_vertices=N)


@pytest.fixture
def forced_spmd(monkeypatch):
    """Force the shard_map tail regardless of dispatch size."""
    monkeypatch.setattr(pdo, "SHARD_MIN_PAGES", 0)


def _set_parts(adj, n):
    partition_column(adj.table["<dst>"].encoded, n)


# ------------------------------- construction ------------------------------

def test_partition_bounds_even_split():
    np.testing.assert_array_equal(partition_bounds(10, 4), [0, 3, 6, 9, 10])
    np.testing.assert_array_equal(partition_bounds(8, 2), [0, 4, 8])
    b = partition_bounds(3, 8)              # more partitions than pages
    assert b[-1] == 3 and b[0] == 0 and np.all(np.diff(b) >= 0)


def test_partitions_cover_column_and_record_stats():
    vals = np.sort(np.random.default_rng(0).integers(0, 1 << 20,
                                                     5 * PAGE + 37))
    col = delta_encode_column(vals, PAGE)
    parts = partition_column(col, 3)
    assert parts.n_parts == 3
    assert int(parts.bounds[-1]) == len(col.pages)
    covered = 0
    for p in parts.parts:
        assert p.packed.n_pages == p.page_hi - p.page_lo
        covered += p.n_pages
        # per-partition value hull matches the decoded slice
        lo, hi = p.row_lo, min(p.row_hi, len(vals))
        if hi > lo:
            assert p.vmin == int(vals[lo:hi].min())
            assert p.vmax == int(vals[lo:hi].max())
    assert covered == len(col.pages)


def test_pack_column_records_page_minmax():
    vals = np.sort(np.random.default_rng(1).integers(0, 1 << 20,
                                                     3 * PAGE + 11))
    col = delta_encode_column(vals, PAGE)
    packed = pack_column(col)
    for i, pg in enumerate(col.pages):
        s, e = i * PAGE, min((i + 1) * PAGE, len(vals))
        assert packed.page_min[i] == int(vals[s:e].min())
        assert packed.page_max[i] == int(vals[s:e].max())


def test_single_partition_detaches_to_monolithic():
    vals = np.sort(np.random.default_rng(2).integers(0, 1 << 20, 2 * PAGE))
    col = delta_encode_column(vals, PAGE)
    partition_column(col, 4)
    assert live_partitions(col) is not None
    assert partition_column(col, 1) is None     # the PR 4 path IS 1 partition
    assert live_partitions(col) is None and col.partitions == 0


def test_partition_cache_rebuilds_on_version_bump():
    vals = np.sort(np.random.default_rng(3).integers(0, 1 << 20,
                                                     3 * PAGE + 17))
    col = delta_encode_column(vals, PAGE)
    parts = partition_column(col, 3)
    new_tail = np.sort(np.random.default_rng(4).integers(0, 1 << 20, 17))
    col.set_page(len(col.pages) - 1, delta_encode_page(new_tail))
    fresh = live_partitions(col)
    assert fresh is not parts                   # keyed on the write counter
    assert fresh.version == col.version
    last = len(col.pages) - 1
    k = int(fresh.part_of_pages(np.array([last]))[0])
    local = last - int(fresh.bounds[k])
    assert fresh.parts[k].packed.page_min[local] == int(new_tail.min())


def test_mesh_size_is_largest_divisor():
    vals = np.sort(np.random.default_rng(5).integers(0, 1 << 20, 8 * PAGE))
    col = delta_encode_column(vals, PAGE)
    parts = partition_column(col, 6)
    assert parts.mesh_size(1) == 1
    assert parts.mesh_size(2) == 2
    assert parts.mesh_size(4) == 3              # largest divisor of 6 <= 4
    assert parts.mesh_size(8) == 6
    assert parts.stack_rows == 6 * parts.pmax


# ----------------- sharded == single-device resident == oracle -------------

@pytest.mark.parametrize("engine", engines())
@pytest.mark.parametrize("n_parts", PART_COUNTS)
def test_sharded_bit_identical_to_resident(adj_pair, engine, n_parts):
    mono, part = adj_pair
    _set_parts(part, n_parts)
    vs = np.random.default_rng(17).integers(0, N, 64)
    kw = {} if engine == "numpy" else dict(fused=True, resident=True)
    m_mono, m_part = IOMeter(), IOMeter()
    want = retrieve_neighbors_batch(mono, vs, TPS, m_mono, engine=engine,
                                    **kw)
    got = retrieve_neighbors_batch(part, vs, TPS, m_part, engine=engine,
                                   **kw)
    assert got == want
    np.testing.assert_array_equal(got.to_ids(), want.to_ids())
    assert (m_part.nbytes, m_part.nrequests) == (m_mono.nbytes,
                                                 m_mono.nrequests)


@pytest.mark.parametrize("engine", engines(kernel_only=True))
@given(seed=st.integers(0, 2**32 - 1),
       n_parts=st.sampled_from(PART_COUNTS),
       size=st.integers(1, 96))
@settings(max_examples=12, deadline=None)
def test_sharded_property_random_batches(adj_pair, engine, seed, n_parts,
                                         size):
    """Satellite: hypothesis property -- sharded retrieval over random
    partition counts and random batches is bit-identical (ids + IOMeter)
    to the single-device resident path.  The shard_map tail is forced
    per example (hypothesis refuses function-scoped fixtures)."""
    mono, part = adj_pair
    rng = np.random.default_rng(seed)
    vs = rng.integers(0, N, size)
    _set_parts(part, n_parts)
    m_mono, m_part = IOMeter(), IOMeter()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pdo, "SHARD_MIN_PAGES", 0)
        want = retrieve_neighbors_batch(mono, vs, TPS, m_mono,
                                        engine=engine, fused=True,
                                        resident=True)
        got = retrieve_neighbors_batch(part, vs, TPS, m_part,
                                       engine=engine, fused=True,
                                       resident=True)
    assert got == want
    assert (m_part.nbytes, m_part.nrequests) == (m_mono.nbytes,
                                                 m_mono.nrequests)


@pytest.mark.parametrize("engine", engines(kernel_only=True))
@pytest.mark.parametrize("n_parts", (2, 8))
def test_sharded_filtered_bit_identical(adj_pair, vt, engine, n_parts,
                                        forced_spmd):
    mono, part = adj_pair
    _set_parts(part, n_parts)
    vs = np.random.default_rng(23).integers(0, N, 64)
    cond = L("A") | ~L("B")
    m_mono, m_part = IOMeter(), IOMeter()
    want = retrieve_neighbors_batch(mono, vs, TPS, m_mono, engine=engine,
                                    fused=True, resident=True,
                                    filter=LabelFilter(vt, cond))
    got = retrieve_neighbors_batch(part, vs, TPS, m_part, engine=engine,
                                   fused=True, resident=True,
                                   filter=LabelFilter(vt, cond))
    assert got == want
    # ~L("B") qualifies ids across the whole range, so the hull prunes
    # nothing and the meters stay bit-identical
    assert (m_part.nbytes, m_part.nrequests) == (m_mono.nbytes,
                                                 m_mono.nrequests)


@pytest.mark.parametrize("engine", engines(kernel_only=True))
def test_khop_routes_through_partitions(adj_pair, engine, forced_spmd):
    mono, part = adj_pair
    _set_parts(part, 3)
    seeds = np.random.default_rng(29).integers(0, N, 8)
    np.testing.assert_array_equal(k_hop(mono, seeds, 2, engine=engine),
                                  k_hop(part, seeds, 2, engine=engine))
    parts = live_partitions(part.table["<dst>"].encoded)
    assert parts.dispatches > 0                 # decode went through the plane


# ------------------------------ decoded-page LRU ---------------------------

@pytest.mark.parametrize("engine", engines(kernel_only=True))
def test_warm_lru_charges_nothing_and_keys_by_partition(adj_pair, engine,
                                                        forced_spmd):
    _, part = adj_pair
    _set_parts(part, 2)
    col = part.table["<dst>"]
    cache = attach_page_cache(col, 4096)
    try:
        cache.clear()
        vs = np.random.default_rng(31).integers(0, N, 64)
        p1 = retrieve_neighbors_batch(part, vs, TPS, engine=engine,
                                      fused=True, resident=True)
        m_warm = IOMeter()
        p2 = retrieve_neighbors_batch(part, vs, TPS, m_warm, engine=engine,
                                      fused=True, resident=True)
        assert p1 == p2
        m_off = IOMeter()
        part.edge_ranges_batch(vs, m_off)
        assert (m_warm.nbytes, m_warm.nrequests) == (m_off.nbytes,
                                                     m_off.nrequests)
        # entries are namespaced (partition, page)
        keys = list(cache._pages)
        assert keys and all(isinstance(k, tuple) and len(k) == 2
                            for k in keys)
        parts = live_partitions(col.encoded)
        for k, p in keys:
            assert parts.bounds[k] <= p < parts.bounds[k + 1]
    finally:
        col.encoded.page_cache = None


def test_page_cache_partition_namespace_isolated():
    cache = DecodedPageCache(8)
    cache.put(3, np.array([1]), part=0)
    cache.put(3, np.array([2]), part=1)
    cache.put(3, np.array([3]))
    assert cache.get(3, part=0)[0] == 1
    assert cache.get(3, part=1)[0] == 2
    assert cache.get(3)[0] == 3


# --------------------------- statistics pushdown ---------------------------

def _local_ring(n):
    """Perfectly local graph: partition value hulls track src ranges."""
    src = np.repeat(np.arange(n), 2)
    dst = np.stack([np.arange(n), (np.arange(n) + 1) % n], 1).ravel()
    return src, dst


@pytest.mark.parametrize("engine", engines(kernel_only=True))
def test_stats_pruning_skips_partitions_and_reduces_io(engine):
    n = 2048
    src, dst = _local_ring(n)
    labels = {"A": np.arange(n) < n // 4}
    lvt = VertexTable.build(VertexTypeSchema("v", [], labels=["A"]), {},
                            labels, num_vertices=n)
    mono = build_adjacency(src, dst, n, n, BY_SRC, ENC_GRAPHAR,
                           page_size=PAGE)
    part = build_adjacency(src, dst, n, n, BY_SRC, ENC_GRAPHAR,
                           page_size=PAGE)
    _set_parts(part, 8)
    vs = np.arange(0, n, 7)
    m_none, m_mono, m_part = IOMeter(), IOMeter(), IOMeter()
    # unpruned baseline: same retrieval, no predicate pushed down
    retrieve_neighbors_batch(mono, vs, TPS, m_none, engine=engine,
                             fused=True, resident=True)
    want = retrieve_neighbors_batch(mono, vs, TPS, m_mono, engine=engine,
                                    fused=True, resident=True,
                                    filter=LabelFilter(lvt, L("A")))
    got = retrieve_neighbors_batch(part, vs, TPS, m_part, engine=engine,
                                   fused=True, resident=True,
                                   filter=LabelFilter(lvt, L("A")))
    assert got == want                          # pruning never changes ids
    parts = live_partitions(part.table["<dst>"].encoded)
    assert parts.stats_pruned > 0
    # page-granular zone maps refine the partition hulls to the *same*
    # final page set on both layouts (partition-pruned pages are a subset
    # of page-pruned ones), so the filtered meters agree -- and both beat
    # the unpruned baseline
    assert m_part.nbytes == m_mono.nbytes
    assert m_mono.nbytes < m_none.nbytes
    assert mono.table["<dst>"].encoded.prune_stats.pages_pruned > 0


def test_filter_qual_range_matches_host_intervals(vt):
    filt = LabelFilter(vt, L("A") | ~L("B"))
    starts, ends = filt.intervals("numpy")
    lo, hi = filt.qual_range()
    assert (lo, hi) == (int(starts[0]), int(ends[-1]))


def test_stats_pruning_everything_yields_empty_pac(vt):
    n = 2048
    src, dst = _local_ring(n)
    labels = {"Z": np.zeros(n, bool)}           # nothing qualifies
    lvt = VertexTable.build(VertexTypeSchema("v", [], labels=["Z"]), {},
                            labels, num_vertices=n)
    part = build_adjacency(src, dst, n, n, BY_SRC, ENC_GRAPHAR,
                           page_size=PAGE)
    _set_parts(part, 4)
    got = retrieve_neighbors_batch(part, np.arange(0, n, 9), TPS,
                                   engine="jax", fused=True, resident=True,
                                   filter=LabelFilter(lvt, L("Z")))
    assert got.count() == 0


def test_page_stats_survive_serialization(tmp_path):
    from repro.core.storage import read_table, write_table
    n = 2048
    src, dst = _local_ring(n)
    adj = build_adjacency(src, dst, n, n, BY_SRC, ENC_GRAPHAR,
                          page_size=PAGE)
    path = str(tmp_path / "edges.gar")
    write_table(adj.table, path)
    rt = read_table(path)
    col = rt["<dst>"].encoded
    for orig, back in zip(adj.table["<dst>"].encoded.pages, col.pages):
        assert (back.vmin, back.vmax) == (orig.vmin, orig.vmax)
    parts = partition_column(col, 4)
    assert all(p.stats_known for p in parts.parts)


def test_unknown_page_stats_never_prune():
    """A column whose pages carry no value stats (e.g. deserialized from
    a pre-stats file) must disable hull pruning, not prune everything."""
    n = 2048
    src, dst = _local_ring(n)
    labels = {"A": np.arange(n) < n // 4}
    lvt = VertexTable.build(VertexTypeSchema("v", [], labels=["A"]), {},
                            labels, num_vertices=n)
    mono = build_adjacency(src, dst, n, n, BY_SRC, ENC_GRAPHAR,
                           page_size=PAGE)
    part = build_adjacency(src, dst, n, n, BY_SRC, ENC_GRAPHAR,
                           page_size=PAGE)
    for pg in part.table["<dst>"].encoded.pages:
        pg.vmin, pg.vmax = 0, -1            # simulate a pre-stats file
    parts = partition_column(part.table["<dst>"].encoded, 8)
    assert not any(p.stats_known for p in parts.parts)
    vs = np.arange(0, n, 7)
    want = retrieve_neighbors_batch(mono, vs, TPS, engine="jax",
                                    fused=True, resident=True,
                                    filter=LabelFilter(lvt, L("A")))
    got = retrieve_neighbors_batch(part, vs, TPS, engine="jax",
                                   fused=True, resident=True,
                                   filter=LabelFilter(lvt, L("A")))
    assert got == want
    assert parts.stats_pruned == 0


# --------------------------- dispatch-cost plane ---------------------------

@pytest.mark.parametrize("engine", engines(kernel_only=True))
def test_sharded_steady_state_does_not_retrace(adj_pair, engine,
                                               forced_spmd):
    _, part = adj_pair
    _set_parts(part, 2)
    rng = np.random.default_rng(37)
    batches = [rng.integers(0, N, s) for s in rng.integers(40, 64, size=8)]
    for vs in batches:                          # warm every size class
        retrieve_neighbors_batch(part, vs, TPS, engine=engine, fused=True,
                                 resident=True)
    before = _pad.trace_count()
    for vs in batches:
        retrieve_neighbors_batch(part, vs, TPS, engine=engine, fused=True,
                                 resident=True)
    assert _pad.trace_count() == before


def test_device_plan_placed_once_per_engine():
    vals = np.sort(np.random.default_rng(41).integers(0, 1 << 20, 4 * PAGE))
    col = delta_encode_column(vals, PAGE)
    parts = partition_column(col, 2)
    t0 = parts.device_transfers
    plan1 = parts.device_plan("jax")
    assert parts.device_plan("jax") is plan1    # exactly once
    assert parts.device_transfers == t0 + 1
    single = parts.device_plan_single("jax")
    assert parts.device_plan_single("jax") is single
    # a degenerate one-device mesh reuses the sharded placement outright
    # (same bytes, same device); a real mesh places a second copy
    expected = t0 + 1 if plan1[0].devices.size == 1 else t0 + 2
    assert parts.device_transfers == expected
    assert all(p.device is not None for p in parts.parts)


def test_page_class_caps_at_stack():
    assert pdo._page_class(53, 160) == 64       # pow2 ladder below the cap
    assert pdo._page_class(157, 160) == 160     # capped: 256 would be waste
    assert pdo._page_class(3, 160) == 8         # floor intact


# ------------------------------ serving stats ------------------------------

def test_retriever_surfaces_partition_counters():
    from repro.core import EdgeTypeSchema, GraphArBuilder, PropertySchema
    from repro.data.synthetic import document_graph
    from repro.serve.retrieval import GraphRetriever
    lake = document_graph(num_docs=300, vocab=256, mean_len=8, seed=3)
    b = GraphArBuilder("docs")
    b.add_vertices(
        VertexTypeSchema("doc", [PropertySchema("tokens", "tokens")],
                         labels=list(lake.labels), page_size=128),
        {"tokens": lake.tokens}, lake.labels)
    b.add_edges(EdgeTypeSchema("doc", "links", "doc", page_size=128),
                lake.links_src, lake.links_dst)
    g = b.build()
    adj = g.adjacency("doc-links-doc", BY_SRC)
    retr = GraphRetriever(adj, g.vertex("doc").table["tokens"],
                          engine="jax", partitions=4)
    retr(np.arange(12))
    s = retr.stats()
    assert s["partitions"]["n_parts"] == 4
    assert "partitions_pruned" in s["partitions"]
    assert s["partitions"]["dispatches"] >= 1


def test_env_default_partitions(monkeypatch):
    import repro.core.partition as cpart
    src, dst = _graph()
    adj = build_adjacency(src, dst, N, N, BY_SRC, ENC_GRAPHAR,
                          page_size=PAGE)
    monkeypatch.setattr(cpart, "DEFAULT_PARTITIONS", 2)
    retrieve_neighbors_batch(adj, np.arange(16), TPS, engine="jax",
                             fused=True, resident=True)
    parts = live_partitions(adj.table["<dst>"].encoded)
    assert parts is not None and parts.n_parts == 2
