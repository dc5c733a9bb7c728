"""Fused batched decode->bitmap path vs the numpy oracle.

The fused kernel turns a deduplicated page list + per-row range masks
into target bitmap planes in one dispatch; these tests pin its PAC
output to the host path (decode + ``PAC.from_ids``) across engines,
including empty ranges, duplicate vertices, and cache interplay.
"""
import numpy as np
import pytest

from _engines import engines
from repro.core import (BY_SRC, ENC_GRAPHAR, IOMeter, PAC,
                        attach_page_cache, build_adjacency,
                        retrieve_neighbors, retrieve_neighbors_batch)
from repro.core.encoding import delta_encode_column
from repro.core.pac import words_per_page
from repro.data.synthetic import powerlaw_graph
from repro.kernels.pac_decode import ops as pdo

N = 2000
PAGE = 256


@pytest.fixture(scope="module")
def adj():
    src, dst = powerlaw_graph(N, 6, seed=13)
    return build_adjacency(src, dst, N + 8, N, BY_SRC, ENC_GRAPHAR,
                           page_size=PAGE)


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(17)
    vs = rng.integers(0, N, 48)
    # duplicates + guaranteed-empty adjacency vertices in the batch
    return np.concatenate([vs, vs[:9], np.arange(N, N + 8)])


def test_adjacency_knows_value_side_size(adj):
    assert adj.num_value_vertices == N


@pytest.mark.parametrize("engine", engines(kernel_only=True))
def test_fused_matches_numpy_oracle(adj, batch, engine):
    got = retrieve_neighbors_batch(adj, batch, 512, engine=engine,
                                   fused=True)
    want = PAC.union_all(
        [retrieve_neighbors(adj, int(v), 512) for v in batch], 512)
    assert got == want
    np.testing.assert_array_equal(got.to_ids(), want.to_ids())


@pytest.mark.parametrize("engine", engines(kernel_only=True))
def test_fused_matches_host_path(adj, batch, engine):
    fused = retrieve_neighbors_batch(adj, batch, 512, engine=engine,
                                     fused=True)
    host = retrieve_neighbors_batch(adj, batch, 512, engine=engine,
                                    fused=False)
    assert fused == host


@pytest.mark.parametrize("engine", engines(kernel_only=True))
def test_fused_meter_identical_to_numpy(adj, batch, engine):
    m_f, m_np = IOMeter(), IOMeter()
    retrieve_neighbors_batch(adj, batch, 512, m_f, engine=engine,
                             fused=True)
    retrieve_neighbors_batch(adj, batch, 512, m_np, engine="numpy")
    assert (m_f.nbytes, m_f.nrequests) == (m_np.nbytes, m_np.nrequests)


@pytest.mark.parametrize("engine", engines(kernel_only=True))
def test_fused_empty_ranges_and_empty_batch(adj, engine):
    # batch of only empty-adjacency vertices
    pac = retrieve_neighbors_batch(adj, np.arange(N, N + 8), 512,
                                   engine=engine, fused=True)
    assert pac.count() == 0 and len(pac) == 0
    # empty batch short-circuits before the kernel
    assert retrieve_neighbors_batch(adj, np.zeros(0, np.int64), 512,
                                    engine=engine, fused=True).count() == 0


@pytest.mark.parametrize("engine", engines(kernel_only=True))
def test_fused_unsorted_duplicated_page_rows(engine):
    # adjacency-like column whose pages interleave many vertices' sorted
    # neighbor runs: ids within one page are neither sorted nor unique
    rng = np.random.default_rng(23)
    vals = rng.integers(0, 1500, size=4096).astype(np.int64)
    col = delta_encode_column(vals, 512)
    los = np.array([0, 10, 700, 700, 4000, 9, 0])
    his = np.array([10, 300, 1400, 1400, 4096, 9, 0])
    for tps in (512, 2048):
        got = pdo.retrieve_pac_batch(col, los, his, tps, engine=engine,
                                     num_targets=1500, fused=True)
        ids = pdo.decode_row_ranges(col, los, his, engine="numpy")
        want = PAC.from_ids(np.unique(ids), tps)
        assert got == want


@pytest.mark.parametrize("engine", engines(kernel_only=True))
def test_fused_target_boundary_ids(engine):
    # ids at the very edge of a non-word-multiple target space
    num_targets = 1000  # not a multiple of 32
    vals = np.array([0, 1, 31, 32, 998, 999] * 10, np.int64)
    col = delta_encode_column(vals, 32)
    got = pdo.retrieve_pac_batch(col, np.array([0]), np.array([60]), 256,
                                 engine=engine, num_targets=num_targets,
                                 fused=True)
    ids = pdo.decode_row_ranges(col, np.array([0]), np.array([60]),
                                engine="numpy")
    assert got == PAC.from_ids(np.unique(ids), 256)


@pytest.mark.parametrize("engine", engines(kernel_only=True))
def test_fused_with_warm_cache_charges_nothing(adj, batch, engine):
    col = adj.table["<dst>"]
    cache = attach_page_cache(col, 4096)
    try:
        cache.clear()
        cache.reset_stats()
        m_cold, m_warm = IOMeter(), IOMeter()
        p1 = retrieve_neighbors_batch(adj, batch, 512, m_cold,
                                      engine=engine, fused=True)
        p2 = retrieve_neighbors_batch(adj, batch, 512, m_warm,
                                      engine=engine, fused=True)
        assert p1 == p2
        # warm tick pays only the (uncached) <offset> index gather; the
        # value-column decode is fully served from the LRU
        m_off = IOMeter()
        adj.edge_ranges_batch(batch, m_off)
        assert m_cold.nbytes > m_off.nbytes
        assert (m_warm.nbytes, m_warm.nrequests) == (m_off.nbytes,
                                                     m_off.nrequests)
        assert cache.hits > 0
    finally:
        col.encoded.page_cache = None


@pytest.mark.parametrize("engine", engines(kernel_only=True))
def test_fused_resident_toggle_bit_identical(adj, batch, engine):
    """The device-resident mirror is a transfer optimization only: the
    fused PAC and IOMeter must be identical with the mirror on and off."""
    m_on, m_off = IOMeter(), IOMeter()
    on = retrieve_neighbors_batch(adj, batch, 512, m_on, engine=engine,
                                  fused=True, resident=True)
    off = retrieve_neighbors_batch(adj, batch, 512, m_off, engine=engine,
                                   fused=True, resident=False)
    assert on == off
    np.testing.assert_array_equal(on.to_ids(), off.to_ids())
    assert (m_on.nbytes, m_on.nrequests) == (m_off.nbytes, m_off.nrequests)


@pytest.mark.parametrize("engine", engines(kernel_only=True))
def test_fused_resident_unsorted_duplicated_page_rows(engine):
    rng = np.random.default_rng(23)
    vals = rng.integers(0, 1500, size=4096).astype(np.int64)
    col = delta_encode_column(vals, 512)
    los = np.array([0, 10, 700, 700, 4000, 9, 0])
    his = np.array([10, 300, 1400, 1400, 4096, 9, 0])
    ids = pdo.decode_row_ranges(col, los, his, engine="numpy")
    want = PAC.from_ids(np.unique(ids), 512)
    for resident in (True, False):
        got = pdo.retrieve_pac_batch(col, los, his, 512, engine=engine,
                                     num_targets=1500, fused=True,
                                     resident=resident)
        assert got == want


def test_pac_from_bitmap_planes_roundtrip():
    wpp = words_per_page(512)
    planes = np.zeros((4, wpp), np.uint32)
    planes[0, 0] = 0b101          # ids 0, 2
    planes[2, 3] = 1 << 7         # id 2*512 + 3*32 + 7
    pac = PAC.from_bitmap_planes(planes, 512)
    assert pac.pages() == [0, 2]  # empty planes dropped
    np.testing.assert_array_equal(pac.to_ids(), [0, 2, 2 * 512 + 103])
    # explicit page indices
    pac2 = PAC.from_bitmap_planes(planes[[0, 2]], 512,
                                  pages=np.array([5, 9]))
    assert pac2.pages() == [5, 9]
    np.testing.assert_array_equal(
        pac2.to_ids(), [5 * 512, 5 * 512 + 2, 9 * 512 + 103])
    with pytest.raises(ValueError):
        PAC.from_bitmap_planes(np.zeros((2, wpp + 1), np.uint32), 512)


def test_pac_from_dense_bitmap_pads_tail():
    words = np.zeros(3, np.uint32)   # 96 ids < one 128-id page
    words[2] = 1 << 5                # id 69
    pac = PAC.from_dense_bitmap(words, 128)
    np.testing.assert_array_equal(pac.to_ids(), [69])
    assert pac.pages() == [0]
    with pytest.raises(ValueError):
        PAC.from_dense_bitmap(words, 100)   # page_size not word-aligned


@pytest.mark.parametrize("n_words,t,filtered", [
    (63, 64, False),        # one partial tile, one chunk
    (1100, 1500, False),    # three tiles (last partial), two chunks
    (3000, 5000, True),     # chunks straddling tiles, predicate AND
    (5000, 64, False),      # mostly empty tiles
])
def test_bitmap_tail_kernel_matches_scatter_reference(n_words, t, filtered):
    """The tiled bitmap tail (sorted ids, scalar-prefetched tile/chunk
    work list, one-hot counts) equals the jnp scatter reference under
    duplicates, unsorted rows, tile-boundary ids and masked padding."""
    import jax.numpy as jnp
    from repro.kernels.pac_decode import kernel as K
    rng = np.random.default_rng(n_words + t)
    n_slots = n_words * 32
    ids = rng.integers(0, n_slots, (16, 64)).astype(np.int32)
    ids[0, :8] = [0, 31, 32, 16383, 16384, 16385, n_slots - 1, 0]
    gidx = rng.integers(0, ids.size, t).astype(np.int32)
    gidx[:8] = np.arange(8)
    gcount = t - 3
    fwords = rng.integers(0, 2 ** 32, n_words, dtype=np.uint64) \
        .astype(np.uint32) if filtered else None
    want = np.asarray(K._bitmap_scatter(jnp.asarray(ids), jnp.asarray(gidx),
                                        gcount, n_words))
    if filtered:
        want = want & fwords
    got = K._bitmap_tail_pallas(
        jnp.asarray(ids), jnp.asarray(gidx), jnp.int32(gcount), n_words,
        True, fwords=None if fwords is None else jnp.asarray(fwords))
    np.testing.assert_array_equal(np.asarray(got), want)
    assert want.any()
