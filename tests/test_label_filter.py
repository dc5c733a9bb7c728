"""The filtering plane: compiled Cond programs vs the legacy oracle.

Deterministic tests pin the compiled stack-machine plane (numpy run-merge
engine + jax/pallas bitmap kernels) to the legacy per-node ``evaluate(env)``
recursion, including IOMeter identity across engines.  The hypothesis
tests assert the Cond algebra -- De Morgan, double negation, and-or
distribution -- holds between the compiled kernel plane and the oracle for
randomly generated label columns and condition trees.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from _engines import engines
from _hypothesis_shim import given, settings, st

from repro import obs
from repro.core import (IOMeter, L, LabelFilter, bitmap_to_intervals,
                        compile_cond, complex_filter_intervals, eval_program,
                        evaluate_filter_intervals, filter_rle_interval,
                        intervals_to_bitmap, intervals_to_ids,
                        rle_encode_bool)
from repro.core.schema import VertexTypeSchema
from repro.core.vertex import VertexTable
from repro.kernels._pad import next_multiple
from repro.kernels.label_filter import kernel as lf_kernel
from repro.kernels.label_filter import ops as lf_ops
from repro.kernels.label_filter import ref as lf_ref

NAMES = ("A", "B", "C")
N = 4000


def make_vt(n=N, seed=0, run=64, page_size=256):
    rng = np.random.default_rng(seed)
    cols = {m: np.repeat(rng.random(n // run + 1) < 0.4, run)[:n]
            for m in NAMES}
    return VertexTable.build(
        VertexTypeSchema("v", [], labels=list(NAMES), page_size=page_size),
        {}, cols, num_vertices=n)


@pytest.fixture(scope="module")
def vt():
    return make_vt()


CONDS = [
    L("A"),
    ~L("B"),
    L("A") & L("B"),
    L("A") | ~L("C"),
    (L("A") & ~L("B")) | L("C"),
    ~(L("A") | L("B")) & L("C"),
    ~~L("C") | (L("A") & L("A")),
]


def _random_cond(rng, depth=3):
    if depth == 0 or rng.random() < 0.3:
        return L(NAMES[int(rng.integers(len(NAMES)))])
    k = int(rng.integers(3))
    if k == 0:
        return ~_random_cond(rng, depth - 1)
    a = _random_cond(rng, depth - 1)
    b = _random_cond(rng, depth - 1)
    return (a & b) if k == 1 else (a | b)


# ----------------------------- compilation --------------------------------

def test_compile_dedups_labels_and_is_postfix():
    prog = compile_cond((L("A") & ~L("B")) | (L("A") & L("C")))
    assert prog.labels == ("A", "B", "C")      # first-use order, deduped
    # postfix evaluation over plain numpy bool planes
    out = eval_program(prog.ops, [np.array([1, 0, 0], bool),
                                  np.array([0, 0, 0], bool),
                                  np.array([0, 1, 0], bool)])
    np.testing.assert_array_equal(out, [True, False, False])


def test_compile_rejects_foreign_nodes():
    with pytest.raises(TypeError):
        compile_cond("not a cond")


def test_eval_program_rejects_malformed():
    with pytest.raises(ValueError):
        eval_program((("leaf", 0), ("leaf", 0)), [np.ones(2, bool)])


@pytest.mark.parametrize("cond", CONDS, ids=[repr(c) for c in CONDS])
def test_compiled_plane_matches_legacy_oracle(vt, cond):
    got = complex_filter_intervals(vt, cond)
    want = evaluate_filter_intervals(vt, cond)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


# ----------------------------- engine dispatch ----------------------------

@pytest.mark.parametrize("engine", engines())
@pytest.mark.parametrize("cond", CONDS[:5], ids=[repr(c) for c in CONDS[:5]])
def test_engine_bitmap_matches_oracle(vt, cond, engine):
    want = intervals_to_bitmap(evaluate_filter_intervals(vt, cond),
                               vt.num_vertices)
    got = lf_ops.label_filter_bitmap(vt, cond, engine=engine)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("engine", engines())
def test_engine_intervals_and_meter_identical(vt, engine):
    cond = (L("A") & ~L("B")) | L("C")
    m_np, m_e = IOMeter(), IOMeter()
    want = filter_rle_interval(vt, cond, m_np, engine="numpy")
    got = filter_rle_interval(vt, cond, m_e, engine=engine)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    assert (m_e.nbytes, m_e.nrequests) == (m_np.nbytes, m_np.nrequests)


@pytest.mark.parametrize("engine", engines())
def test_simple_condition_engine_paths_agree(vt, engine):
    for cond in (L("B"), ~L("A")):
        got = filter_rle_interval(vt, cond, engine=engine)
        want = filter_rle_interval(vt, cond, engine="numpy")
        np.testing.assert_array_equal(intervals_to_ids(got),
                                      intervals_to_ids(want))


def test_label_filter_caches_bitmap_and_masks(vt):
    f = LabelFilter(vt, L("A") | L("B"))
    w1 = f.bitmap()
    assert f.bitmap() is w1                     # cached per engine
    ids = intervals_to_ids(evaluate_filter_intervals(vt, f.cond))
    np.testing.assert_array_equal(
        np.flatnonzero(f.mask_ids(np.arange(vt.num_vertices))), ids)


# ----------------------------- the cond kernel's word tiles ---------------

T = lf_kernel.COND_TILE * 32    # lanes per word tile of the cond kernel


def _flips(n, at, first=False):
    """Bool column over ``[0, n)`` whose value flips at each of ``at``."""
    marks = np.zeros(n, np.int64)
    marks[np.asarray(list(at), np.int64)] = 1
    return (np.cumsum(marks) & 1).astype(bool) ^ first


def _scattered(n, k, seed):
    rng = np.random.default_rng(seed)
    return rng.choice(np.arange(1, n), k, replace=False)


#: layout -> (row count, leaf columns over [0, count), condition)
TILE_LAYOUTS = {
    # run starts on a tile's first lane and one either side of it
    "tile_edges": (2 * T + 64,
                   lambda n: [_flips(n, [T - 1, T, T + 1, 2 * T - 1]),
                              _flips(n, [2 * T - 1, 2 * T + 1])],
                   L("A") & ~L("B")),
    # the middle tile holds no boundary: it is one run throughout
    "tile_without_boundary": (3 * T,
                              lambda n: [_flips(n, [5, T - 3, 2 * T + 7])],
                              L("A")),
    # every lane of the second tile starts a run of length 1
    "tile_of_unit_runs": (2 * T,
                          lambda n: [_flips(n, range(T, 2 * T)),
                                     _flips(n, [100, 200])],
                          L("A") | L("B")),
    # run lists of 2, 3,001 and 1 positions, padded to one length
    "unequal_run_lists": (T + 5000,
                          lambda n: [_flips(n, [3]),
                                     _flips(n, _scattered(n, 3000, 1)),
                                     _flips(n, [T + 100])],
                          (L("A") & ~L("B")) | L("C")),
    # NOT sets every bit, up to a count that ends inside a word and tile
    "ragged_count_under_not": (2 * T + 37,
                               lambda n: [_flips(n, [77, T + 5])],
                               ~L("A")),
    "four_leaves": (T + 999,
                    lambda n: [_flips(n, _scattered(n, 40, s), first=s % 2)
                               for s in range(4)],
                    ((L("A") | L("B")) & ~L("C")) | L("D")),
    # fewer words than one tile, and not a multiple of it
    "under_one_tile": (1000,
                       lambda n: [_flips(n, [10, 500, 501]),
                                  _flips(n, [999], first=True)],
                       ~(L("A") & L("B"))),
}


@pytest.mark.parametrize("layout", sorted(TILE_LAYOUTS))
def test_cond_kernel_tiles_match_search_and_interval_oracles(layout):
    """The kernel's tile walk over run boundaries equals the reference's
    per-lane binary search, and both equal the numpy interval plane of
    the columns the runs were encoded from."""
    n, columns, cond = TILE_LAYOUTS[layout]
    cols = columns(n)
    names = [chr(ord("A") + i) for i in range(len(cols))]
    vt = VertexTable.build(VertexTypeSchema("v", [], labels=names),
                           {}, dict(zip(names, cols)), num_vertices=n)
    plan = lf_ops.make_plan(vt, cond)
    assert plan.program.labels == tuple(names)
    n_words = next_multiple(plan.n_words, lf_ops.K.WORD_TILE)
    args = (jnp.asarray(plan.pos), jnp.asarray(plan.meta))
    got = np.asarray(lf_kernel.cond_bitmap_pallas(
        *args, n_words=n_words, ops=plan.program.ops))
    ref = np.asarray(lf_ref.cond_bitmap_ref(
        *args, n_words=n_words, ops=plan.program.ops))
    keep = np.asarray(eval_program(plan.program.ops, cols), bool)
    want = intervals_to_bitmap(rle_encode_bool(keep).interval_starts(True),
                               n)
    np.testing.assert_array_equal(got, ref)
    np.testing.assert_array_equal(got[:plan.n_words], want)
    assert not got[plan.n_words:].any()


@pytest.mark.parametrize("engine", engines(kernel_only=True))
def test_device_bitmap_counts_the_plans_unpadded_runs(vt, engine):
    cond = (L("A") & ~L("B")) | L("C")
    plan = lf_ops.make_plan(vt, cond)
    runs = sum(vt.label_rle(n).n_runs for n in plan.program.labels)
    assert runs < plan.pos.size                # the plan is padded
    before = obs.counters()
    for _ in range(2):                         # the second is cached
        plan.device_bitmap(engine, plan.n_words)
    after = obs.counters()
    moved = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("filter.planes", "filter.plane_runs")}
    assert moved == {"filter.planes": 1, "filter.plane_runs": runs}


# ----------------------------- plane conversions --------------------------

@pytest.mark.parametrize("n", [0, 1, 31, 32, 1000, N])
def test_interval_bitmap_roundtrip(n):
    rng = np.random.default_rng(n)
    cut = np.unique(rng.integers(0, max(n, 1), 12))
    starts, ends = cut[:-1:2], cut[1::2]
    k = min(len(starts), len(ends))
    iv = (starts[:k].astype(np.int64), ends[:k].astype(np.int64))
    words = intervals_to_bitmap(iv, n)
    assert words.size == -(-n // 32)
    back = bitmap_to_intervals(words, n)
    np.testing.assert_array_equal(intervals_to_ids(back),
                                  intervals_to_ids(iv))


# ----------------------------- Cond algebra (hypothesis) ------------------

def _assert_equiv(vt, lhs, rhs, engine):
    """lhs and rhs must produce identical planes, both equal to the legacy
    oracle of lhs."""
    a = lf_ops.label_filter_bitmap(vt, lhs, engine=engine)
    b = lf_ops.label_filter_bitmap(vt, rhs, engine=engine)
    want = intervals_to_bitmap(evaluate_filter_intervals(vt, lhs),
                               vt.num_vertices)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, want)


@given(seed=st.integers(0, 2**32 - 1))
@settings(max_examples=8, deadline=None)
def test_algebra_de_morgan(seed):
    rng = np.random.default_rng(seed)
    vt = make_vt(n=int(rng.integers(64, 1500)), seed=seed, run=16)
    a, b = _random_cond(rng, 2), _random_cond(rng, 2)
    for engine in engines():
        _assert_equiv(vt, ~(a & b), ~a | ~b, engine)
        _assert_equiv(vt, ~(a | b), ~a & ~b, engine)


@given(seed=st.integers(0, 2**32 - 1))
@settings(max_examples=8, deadline=None)
def test_algebra_double_negation(seed):
    rng = np.random.default_rng(seed)
    vt = make_vt(n=int(rng.integers(64, 1500)), seed=seed, run=16)
    a = _random_cond(rng, 3)
    for engine in engines():
        _assert_equiv(vt, ~~a, a, engine)


@given(seed=st.integers(0, 2**32 - 1))
@settings(max_examples=8, deadline=None)
def test_algebra_and_or_distribution(seed):
    rng = np.random.default_rng(seed)
    vt = make_vt(n=int(rng.integers(64, 1500)), seed=seed, run=16)
    a, b, c = (_random_cond(rng, 1) for _ in range(3))
    for engine in engines():
        _assert_equiv(vt, a & (b | c), (a & b) | (a & c), engine)


@given(seed=st.integers(0, 2**32 - 1))
@settings(max_examples=10, deadline=None)
def test_compiled_matches_oracle_fuzz(seed):
    rng = np.random.default_rng(seed)
    vt = make_vt(n=int(rng.integers(33, 2000)), seed=seed, run=8)
    cond = _random_cond(rng, 4)
    got = complex_filter_intervals(vt, cond)
    want = evaluate_filter_intervals(vt, cond)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
