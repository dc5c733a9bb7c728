"""Jit'd wrappers + storage-plane integration for pac_decode kernels.

Two granularities:

* single-range (``retrieve_pac``): the original Definition-2 path for one
  vertex's edge rows;
* batched (``decode_row_ranges`` / ``retrieve_pac_batch``): an arbitrary
  set of row ranges decoded through **one** kernel dispatch over the
  page-deduplicated page set -- the unit of work of the batched
  neighbor-retrieval plane (whole-frontier expansion, IC-8/BI-2 multi-hop,
  per-tick serving retrieval).

Both paths read pages through the cached column-wide packed representation
(:func:`repro.core.encoding.pack_column`), so the VMEM-layout batch arrays
are materialized once per column instead of once per query.

Two cross-cutting performance layers (PR 2):

* **decoded-page LRU** -- when a :class:`repro.core.page_cache.DecodedPageCache`
  is attached to the column, every decode path splits its page set into
  hits and misses, decodes and IOMeter-charges the **misses only**, and
  inserts the fresh decodes back (see ``decode_page_list``);
* **fused batched decode->bitmap** -- ``retrieve_pac_batch`` on the
  jax/pallas engines runs page-pack -> multi-range decode -> target-bitmap
  scatter in one kernel dispatch and builds the merged PAC straight from
  the returned bitmap planes (``PAC.from_dense_bitmap``), never
  materializing the concatenated per-range id list on the host.
"""
from __future__ import annotations

import os
from collections import deque
from typing import Dict, Optional, Sequence, Tuple

import numpy as np
import jax.numpy as jnp

from repro.core.encoding import (DeltaColumn, delta_decode_page, pack_column,
                                 prune_page_list)
from repro.core.labels import intervals_to_ids
from repro.core.pac import PAC
from repro.core.page_cache import live_cache, miss_runs
from repro.core.partition import live_partitions
from repro.kernels._pad import next_multiple, next_pow2, size_class

from . import kernel as K
from . import ref as R

ENGINES = ("numpy", "jax", "pallas")

#: auto-fused threshold: below this many ranges the host path's
#: O(neighbors) post-processing beats the fused tail's O(num_targets)
#: bitmap pass (crossover measured in bench_batch_scaling; the win
#: criterion is batch >= 64, so the default of 16 leaves comfortable
#: margin both ways).  Overridable via ``REPRO_FUSED_MIN_RANGES`` for
#: bench sweeps of the crossover.
FUSED_MIN_RANGES = int(os.environ.get("REPRO_FUSED_MIN_RANGES", "16"))

#: device-resident packed column plane (``PackedPages.device``): kernel
#: engines gather pages on-device by index instead of row-gathering on
#: the host and re-shipping packed bytes per dispatch.  On by default;
#: ``REPRO_DEVICE_RESIDENT=0`` restores the per-dispatch pack path
#: everywhere (the ``resident=`` arguments override per call).
DEVICE_RESIDENT = os.environ.get("REPRO_DEVICE_RESIDENT", "1") \
    .strip().lower() not in ("0", "false", "no", "off")

#: pow2 size-class floors for the per-dispatch index/position vectors --
#: small frontiers share one bucket instead of retracing per shape.
PAGE_CLASS_MIN = 8
RANGE_CLASS_MIN = 64

#: adaptive sharding threshold for the partition plane: the SPMD
#: (``shard_map``) dispatch pays a fixed multi-executable launch cost per
#: call, so partitioned columns shard across the device mesh only when
#: the busiest device gets at least this many pages to decode; below it
#: the plane takes its **degenerate single-shard dispatch** -- the
#: monolithic resident kernels over the stacked partition plan on one
#: device -- which costs what the unpartitioned path costs.  Results,
#: meters, and pruning are identical either way.  ``REPRO_SHARD_MIN_PAGES=0``
#: forces SPMD everywhere (the multi-device CI job does, so the sharded
#: path is validated without real accelerators).
SHARD_MIN_PAGES = int(os.environ.get("REPRO_SHARD_MIN_PAGES", "48"))

#: (engine, n_words) -> ring of the two most recent dispatches' bitmap
#: planes, handed back to the resident kernel as its aliased output
#: buffer so steady-state serving ticks reuse device allocations instead
#: of growing one per dispatch.  The ring is **double-buffered**: a
#: dispatch donates the *older* of the two pooled buffers, never the
#: most recent output -- so with pipelined serving (retrieval issued
#: asynchronously in the decode's shadow, host copy-out deferred until
#: the result is consumed) two in-flight dispatches can never alias one
#: plane.  Steady state settles at exactly two buffers per class.
_WORDS_POOL: Dict[Tuple[str, int], "deque"] = {}


def _words_buffer(engine: str, n_words: int):
    ring = _WORDS_POOL.get((engine, n_words))
    if ring is not None and len(ring) >= 2:
        # oldest pooled plane: its dispatch is two behind, its host copy
        # long consumed -- safe to donate even with one still in flight
        return ring.popleft()
    return jnp.zeros(n_words, jnp.uint32)


def _pool_words(engine: str, n_words: int, buf) -> None:
    ring = _WORDS_POOL.setdefault((engine, n_words), deque())
    ring.append(buf)
    while len(ring) > 2:
        ring.popleft()


def reset_dispatch_pools() -> None:
    """Drop pooled device buffers (tests / bench isolation)."""
    _WORDS_POOL.clear()


def pack_pages(col: DeltaColumn, p0: int, p1: int
               ) -> Tuple[np.ndarray, ...]:
    """Views of pages [p0, p1) of the cached packed representation.

    Kept for API compatibility; the batch arrays are no longer rebuilt per
    call -- they are zero-copy slices of :func:`pack_column`'s cache.
    """
    return pack_column(col).slice(p0, p1)


def pack_page_list(col: DeltaColumn, pages: Sequence[int]
                   ) -> Tuple[np.ndarray, ...]:
    """Row-gather of an arbitrary (sorted, deduplicated) page list."""
    return pack_column(col).gather(pages)


def decode_pages(col: DeltaColumn, p0: int, p1: int,
                 use_pallas: bool = True) -> np.ndarray:
    """Decode pages [p0, p1) via the kernel (or jnp ref); returns flat ids."""
    ps = col.page_size
    args = pack_pages(col, p0, p1)
    if use_pallas:
        ids = K.delta_decode_pallas(*[jnp.asarray(a) for a in args],
                                    page_size=ps)
    else:
        ids = R.decode_pages_ref(*[jnp.asarray(a) for a in args],
                                 page_size=ps)
    ids = np.asarray(ids)
    counts = args[5][:, 0]
    return np.concatenate([ids[i, :counts[i]] for i in range(len(counts))])


def _charge_pages(col: DeltaColumn, pages: Sequence[int], meter) -> None:
    """IOMeter charge for a (sorted) page list: each page's bytes once,
    requests per contiguous run (what a real ranged reader would issue)."""
    if meter is None or not len(pages):
        return
    meter.record(sum(col.pages[int(p)].nbytes() for p in pages),
                 miss_runs(pages))


def _page_index_vector(pages: Sequence[int], total_pages: int) -> np.ndarray:
    """int32 page-index vector padded to a shared pow2 size class (the
    only thing the host ships for a resident-column decode), capped at
    the (rounded) whole column -- a gather cannot name more distinct
    rows than the column has, so padding past it is pure wasted decode
    (the stacked-plan ladder cap of the sharded path, backported)."""
    idx = np.zeros(_page_class(len(pages), total_pages), np.int32)
    idx[:len(pages)] = pages
    return idx


def _stack_index(parts, pages: np.ndarray,
                 owner: np.ndarray) -> np.ndarray:
    """Flat row of each global page in the partition-major stacked plan
    (``owner * pmax + offset within partition``) -- the index space every
    partitioned gather consumes.  A device shard's block-local index is
    this minus the block's first row."""
    return (owner * parts.pmax
            + (pages - parts.bounds[owner])).astype(np.int32)


def _page_class(n: int, stack_rows: int) -> int:
    """Page-padding class for a resident dispatch: the shared pow2
    ladder, capped at the (PAGE_CLASS_MIN-rounded) whole plan --
    ``stack_rows`` is the stacked partition plan's row count on the
    sharded paths and the column's page count on the monolithic ones.
    The plan bounds how many distinct rows a gather can name, so padding
    past it is pure wasted decode -- at large page counts the uncapped
    pow2 ladder over-decodes by up to ~2x (e.g. 157 touched pages pad
    to 256 uncapped, 160 capped).  The cap adds at most one extra jit
    size class per column."""
    return min(size_class(n, PAGE_CLASS_MIN),
               next_multiple(stack_rows, PAGE_CLASS_MIN))


_N_DEVICES: "int | None" = None


def _n_devices() -> int:
    """Device count, resolved once (the PjRt device list is fixed for
    the process lifetime; ``jax.devices()`` is not free on the dispatch
    hot path)."""
    global _N_DEVICES
    if _N_DEVICES is None:
        import jax
        _N_DEVICES = len(jax.devices())
    return _N_DEVICES


def _shard_width(parts, owner: np.ndarray
                 ) -> Tuple[int, int, "np.ndarray | None",
                            "np.ndarray | None"]:
    """Adaptive mesh width for one dispatch.

    Returns ``(g, ppd, dev_of_page, per_dev)``; ``g == 1`` selects the
    degenerate single-shard dispatch (one-device host, or no device's
    page bucket reaches ``SHARD_MIN_PAGES`` -- the SPMD launch cost
    would not amortize), in which case the bucketing outputs are None.
    The one home for the policy: the fused and non-fused paths must
    shard under identical conditions.
    """
    g = parts.mesh_size(_n_devices())
    if g <= 1:
        return 1, 1, None, None
    ppd = parts.n_parts // g
    dev_of_page = owner // ppd
    per_dev = np.bincount(dev_of_page, minlength=g)
    if per_dev.max() < SHARD_MIN_PAGES:
        return 1, 1, None, None
    return g, ppd, dev_of_page, per_dev


def _sharded_decode_matrix(col: DeltaColumn, parts, pages: Sequence[int],
                           engine: str) -> np.ndarray:
    """Partitioned page-matrix decode (the non-fused batched path).

    Pages are re-addressed into the stacked partition plan; above the
    sharding threshold they are bucketed per device and decoded through
    one ``shard_map`` dispatch over the partition mesh, below it through
    the monolithic resident gather over the single-device stacked plan.
    Same contract as the monolithic resident decode --
    int64[len(pages), page_size], tails zeroed by the caller."""
    ps = col.page_size
    pages_arr = np.asarray(pages, np.int64)
    owner, _ = parts.prune(pages_arr)  # dispatch/pruning counters only
    stack_idx = _stack_index(parts, pages_arr, owner)
    g, ppd, dev_of_page, per_dev = _shard_width(parts, owner)
    if g == 1:
        arrays, _ = parts.device_plan_single(engine)
        idx = np.zeros(_page_class(len(pages_arr), parts.stack_rows),
                       np.int32)
        idx[:len(pages_arr)] = stack_idx
        fn = K.gather_decode_pallas if engine == "pallas" \
            else R.gather_decode_ref
        ids = fn(*arrays, jnp.asarray(idx), page_size=ps)
        return np.asarray(ids[:len(pages_arr)], np.int64)
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.kernels.shard import sharded_decode_entry
    mesh, plan, pmax = parts.device_plan(engine)
    block0 = dev_of_page * (ppd * pmax)      # first stacked row per block
    local_idx = (stack_idx - block0).astype(np.int32)
    p_pad = _page_class(int(per_dev.max()), ppd * pmax)
    idxmat = np.zeros((g, p_pad), np.int32)
    for i in range(g):
        sel = local_idx[dev_of_page == i]
        idxmat[i, :len(sel)] = sel
    jidx = jax.device_put(idxmat,
                          NamedSharding(mesh, PartitionSpec("part", None)))
    fn = sharded_decode_entry(mesh, engine, ps, p_pad)
    mat = np.asarray(fn(*plan, jidx), np.int64)  # [g, p_pad, ps]
    # row of page i = its appearance order within its device's bucket --
    # the same masks that filled idxmat, so correct for any page order
    within = np.empty(len(pages_arr), np.int64)
    for i in range(g):
        m = dev_of_page == i
        within[m] = np.arange(int(m.sum()))
    return mat[dev_of_page, within]


def _decode_page_matrix(col: DeltaColumn, pages: Sequence[int],
                        engine: str) -> np.ndarray:
    """Engine dispatch only -- no cache, no metering (see decode_page_list).

    Kernel engines follow the ``REPRO_DEVICE_RESIDENT`` default (the
    per-call ``resident=`` override exists on the fused entry points
    only).  Columns with a partition plane attached
    (:func:`repro.core.partition.partition_column`) decode through the
    sharded entry -- pages bucketed per partition, one dispatch across
    the device mesh -- with bit-identical output.
    """
    ps = col.page_size
    n = len(pages)
    parts = live_partitions(col)
    if engine == "numpy":
        if parts is not None:
            parts.prune(np.asarray(pages, np.int64))  # accounting only
        out = np.zeros((n, ps), np.int64)
        for i, p in enumerate(pages):
            d = delta_decode_page(col.pages[p])
            out[i, :len(d)] = d
        return out
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; want one of {ENGINES}")
    if parts is not None and DEVICE_RESIDENT:
        ids = _sharded_decode_matrix(col, parts, pages, engine)
        counts = np.asarray([col.pages[int(p)].count for p in pages],
                            np.int64)
        cols = np.arange(ps)[None, :]
        return np.where(cols < counts[:, None], ids, 0)
    if DEVICE_RESIDENT:
        # device-resident path: the unpack plan crossed the PCIe once;
        # the dispatch ships the int32 page-index vector and gathers +
        # decodes rows on device
        packed = pack_column(col)
        plan = packed.device_plan(engine)
        idx = _page_index_vector(pages, len(col.pages))
        if engine == "pallas":
            ids = K.gather_decode_pallas(*plan, jnp.asarray(idx),
                                         page_size=ps)
        else:
            ids = R.gather_decode_ref(*plan, jnp.asarray(idx),
                                      page_size=ps)
        counts = packed.counts[np.asarray(pages, np.int64), 0]
    else:
        args = pack_page_list(col, pages)
        pad = next_pow2(n) - n
        if pad:
            args = tuple(np.concatenate(
                [a, np.zeros((pad,) + a.shape[1:], a.dtype)]) for a in args)
        jargs = [jnp.asarray(a) for a in args]
        if engine == "pallas":
            ids = K.delta_decode_pallas(*jargs, page_size=ps)
        else:
            ids = R.decode_pages_ref(*jargs, page_size=ps)
        counts = args[5][:n, 0]
    ids = np.asarray(ids[:n], np.int64)
    # zero out the padded tail of each page so all engines agree bit-exactly
    cols = np.arange(ps)[None, :]
    return np.where(cols < counts[:, None], ids, 0)


def decode_page_list(col: DeltaColumn, pages: Sequence[int],
                     engine: str = "pallas", meter=None) -> np.ndarray:
    """Decode an arbitrary (sorted, deduplicated) page list, one dispatch.

    Returns ``int64[len(pages), page_size]``; rows are zero-padded past
    each page's count (callers only index positions < count).  The page
    batch is padded to a power of two before the jax/pallas dispatch so
    the jitted kernels retrace O(log n) times, not once per distinct
    frontier size.

    When the column carries a decoded-page LRU (``col.page_cache``,
    consulted through :func:`~repro.core.page_cache.live_cache` so a
    version-bumped column drops stale decodes first), only the cache-miss
    pages are decoded and IOMeter-charged; hit rows are assembled from
    the cache and cost no lake I/O.  Without a cache every page is a miss
    (the pre-LRU accounting, unchanged).

    On a partitioned column, cache entries live in the ``(partition,
    page)`` namespace (the same keying the sharded fused path uses), so
    fused and non-fused dispatches against one column share warm pages.
    """
    ps = col.page_size
    n = len(pages)
    if n == 0:
        return np.zeros((0, ps), np.int64)
    cache = live_cache(col)
    parts = live_partitions(col)
    pages_arr = np.asarray(pages, np.int64)
    owner = parts.part_of_pages(pages_arr) if parts is not None else None
    if cache is None:
        _charge_pages(col, pages, meter)
        return _decode_page_matrix(col, pages, engine)
    hits, miss = cache.split(pages, owner=owner)
    _charge_pages(col, miss, meter)
    out = np.zeros((n, ps), np.int64)
    if miss:
        mat = _decode_page_matrix(col, miss, engine)
        # miss preserves the sorted page order, so one fancy-index scatter
        # places every miss row (no per-row dict lookups)
        is_miss = np.isin(pages_arr, np.asarray(miss, np.int64))
        miss_idx = np.flatnonzero(is_miss)
        out[miss_idx] = mat
        for i, p in enumerate(miss):
            cache.put(p, mat[i, :col.pages[p].count].copy(),
                      part=None if owner is None
                      else int(owner[miss_idx[i]]))
        hit_idx = np.flatnonzero(~is_miss)
    else:
        hit_idx = np.arange(n)
    if hit_idx.size:
        rows = [hits[int(pages_arr[i])] for i in hit_idx]
        lens = np.fromiter((len(r) for r in rows), np.int64, len(rows))
        full = lens == ps
        if full.any():   # full-width hits stack into one scatter
            out[hit_idx[full]] = [rows[j] for j in np.flatnonzero(full)]
        for j in np.flatnonzero(~full):  # at most the last partial page
            out[hit_idx[j], :lens[j]] = rows[j]
    return out


# --------------------------------------------------------------------------
# batched multi-range decode (the batched retrieval plane's kernel entry)
# --------------------------------------------------------------------------

def page_set_for_ranges(los: np.ndarray, his: np.ndarray, page_size: int
                        ) -> Tuple[np.ndarray, int]:
    """(sorted unique pages, contiguous-run count) touched by the ranges.

    The run count models the read requests a real reader would issue:
    consecutive pages coalesce into one ranged GET.
    """
    los = np.asarray(los, np.int64)
    his = np.asarray(his, np.int64)
    keep = his > los
    if not keep.any():
        return np.zeros(0, np.int64), 0
    p0 = los[keep] // page_size
    p1 = his[keep] // page_size + ((his[keep] % page_size) != 0)
    pages = np.unique(intervals_to_ids((p0, p1)))
    return pages, miss_runs(pages)


def decode_row_ranges(col: DeltaColumn, los, his, meter=None,
                      engine: str = "pallas", qual=None) -> np.ndarray:
    """Concatenated rows over many [lo, hi) ranges, one decode dispatch.

    The deduplicated page set is decoded **once** (numpy / jnp ref /
    Pallas kernel -- same IOMeter accounting for all three: each
    cache-miss page's bytes charged once, requests counted per contiguous
    miss run), then every output element is gathered from the decoded
    page matrix.

    ``qual`` -- a predicate's half-open qualifying ``[lo, hi)`` id hull
    -- drops pages whose zone map cannot intersect it **before** the
    cache split and the decode (:func:`~repro.core.encoding
    .prune_page_list`): pruned pages are never gathered, decoded, or
    charged, and the rows they held are dropped from the output (every
    one of them provably fails the predicate, so callers that filter by
    ``qual``'s predicate see bit-identical ids).
    """
    los = np.asarray(los, np.int64)
    his = np.asarray(his, np.int64)
    lengths = np.maximum(his - los, 0)
    total = int(lengths.sum())
    if total == 0:
        return np.zeros(0, np.int64)
    ps = col.page_size
    pages, _ = page_set_for_ranges(los, his, ps)
    pages, pmask = prune_page_list(col, pages, qual)
    if len(pages) == 0:
        return np.zeros(0, np.int64)
    mat = decode_page_list(col, pages, engine, meter=meter)
    # absolute row index of every output element
    rows = intervals_to_ids((los, his))
    page_of = rows // ps
    pidx = np.searchsorted(pages, page_of)
    if pmask is not None:
        # rows addressed at a pruned page cannot pass the predicate
        ok = pidx < len(pages)
        ok &= pages[np.minimum(pidx, len(pages) - 1)] == page_of
        rows, page_of, pidx = rows[ok], page_of[ok], pidx[ok]
    return mat[pidx, rows - page_of * ps]


def _gather_positions(pages: np.ndarray, base_of_page: np.ndarray,
                      los: np.ndarray, his: np.ndarray,
                      page_size: int, pruned: bool = False
                      ) -> Tuple[np.ndarray, int]:
    """Flat (row * page_size + offset) position of every requested row,
    zero-padded to a power of two.

    These are row *positions* (derivable from the <offset> index alone),
    not decoded ids -- the host addresses the requested rows inside the
    kernel's [miss | cached] row order (``base_of_page[i]`` is the matrix
    row holding sorted page ``pages[i]``) without ever materializing the
    concatenated id list.  Returns ``(int32[t], total)``.

    ``pruned`` marks a statistics-pruned ``pages`` list: rows whose page
    was dropped are dropped with it (they cannot pass the predicate that
    derived the pruning hull).
    """
    rows = intervals_to_ids((los, his))
    n_rows = len(rows)
    page_of = rows // page_size
    pidx = np.searchsorted(pages, page_of)
    if pruned:
        ok = pidx < len(pages)
        ok &= pages[np.minimum(pidx, len(pages) - 1)] == page_of
        if not ok.all():
            rows, page_of, pidx = rows[ok], page_of[ok], pidx[ok]
    total = len(rows)
    gidx = (base_of_page[pidx] * page_size + (rows - page_of * page_size)) \
        .astype(np.int32)
    # pad to the *unpruned* request's size class: pruning must never mint
    # a new staged shape (the dropped rows ride out as masked padding
    # lanes under ``total``), so the jit-cache footprint is exactly the
    # unpruned path's
    pad = size_class(n_rows, RANGE_CLASS_MIN) - total
    if pad:
        gidx = np.concatenate([gidx, np.zeros(pad, np.int32)])
    return gidx, total


def _retrieve_pac_batch_sharded(col: DeltaColumn, parts, los, his, pages,
                                target_page_size: int, num_targets: int,
                                meter, engine: str, filter_plan=None) -> PAC:
    """Partition-sharded fused path: one ``shard_map`` dispatch across the
    partition mesh, per-partition bitmap planes OR-merged into one PAC.

    The host buckets the batch per partition: the deduplicated page set
    and the requested-row positions are split at partition boundaries
    (partitions are page-aligned, so a range spanning a boundary simply
    contributes rows to both sides), re-addressed into each device's
    block-local index space, and shipped as one ``staged`` matrix (row
    ``i`` = device ``i``'s ``[idx | gidx | total]`` vector).  Each shard
    gathers and decodes its partitions' pages from the sharded stacked
    plan and scatters its rows into a full target bitmap plane; the ``g``
    planes OR together on the host (a target id may be a neighbor via
    several partitions).

    Pruning happens before anything is charged or shipped: partitions
    holding none of the batch's pages are skipped (meter-neutral -- they
    had nothing to charge), and with a pushed-down filter, partitions
    whose min/max id hull cannot intersect the predicate's qualifying
    range are skipped too -- their neighbors would be ANDed away inside
    the kernel, so ids are unchanged while their page I/O is genuinely
    saved (statistics pushdown; the meter records the smaller read).

    Accounting is otherwise the monolithic resident path's, verbatim:
    the decoded-page LRU (entries namespaced ``(partition, page)``) is
    split over the global page set, misses are charged once with
    requests per contiguous global run, and the decode matrix backfills
    the cache only when there are misses to backfill.

    Dispatch is adaptive (``SHARD_MIN_PAGES``): above the threshold the
    SPMD tail runs, below it the **degenerate single-shard tail** --
    the monolithic resident kernels over the single-device stacked plan,
    with the cross-tick bitmap buffer pool and ``want_ids`` elision
    intact -- so small dispatches never pay the multi-executable launch
    cost.  Both tails produce identical planes.

    ``pages`` is the caller's already-deduplicated page set (the fused
    entry computes it for its empty-batch check; recomputing it here was
    a measurable per-dispatch cost).
    """
    ps = col.page_size
    qual = filter_plan.qual_range() if filter_plan is not None else None
    owner, mask = parts.prune(pages, qual)
    if mask is not None:
        pages = pages[mask]
        if pages.size == 0:  # every partition statistics-pruned
            return PAC(target_page_size)
    # page-granular zone maps inside the surviving partitions: a finer
    # sieve over the same hull (partition-pruned pages are a subset of
    # page-pruned ones, so the final page set -- and the meter -- equals
    # the monolithic path's at any partition count)
    kept, pmask = prune_page_list(col, pages, qual)
    if pmask is not None:
        pages, owner = kept, owner[pmask]
        if pages.size == 0:  # every page statistics-pruned
            return PAC(target_page_size)
    pruned = mask is not None or pmask is not None
    stack_idx = _stack_index(parts, pages, owner)
    cache = live_cache(col)
    if cache is None:
        hits, miss = {}, [int(p) for p in pages]
    else:
        hits, miss = cache.split(pages, owner=owner)
    _charge_pages(col, miss, meter)
    n_words = -(-num_targets // 32)
    want_ids = cache is not None and bool(miss)
    # requested rows: with statistics pruning, rows whose page was
    # dropped cannot pass the predicate and are dropped with it
    rows = intervals_to_ids((los, his))
    n_rows = len(rows)
    page_of = rows // ps
    pidx = np.searchsorted(pages, page_of)
    if pruned:
        ok = pidx < len(pages)
        ok &= pages[np.minimum(pidx, len(pages) - 1)] == page_of
        if not ok.all():
            rows, page_of, pidx = rows[ok], page_of[ok], pidx[ok]
    g, ppd, dev_of_page, per_dev = _shard_width(parts, owner)
    if g == 1:
        # single-shard tail: exactly the monolithic resident dispatch,
        # addressed through the stacked partition plan
        arrays, _ = parts.device_plan_single(engine)
        gidx = (pidx * ps + (rows - page_of * ps)).astype(np.int32)
        total = len(gidx)
        # pad to the unpruned request's class -- pruning never mints a
        # new staged shape (see _gather_positions)
        pad = size_class(n_rows, RANGE_CLASS_MIN) - total
        if pad:
            gidx = np.concatenate([gidx, np.zeros(pad, np.int32)])
        p_pad = _page_class(len(pages), parts.stack_rows)
        staged = np.zeros(p_pad + len(gidx) + 1, np.int32)
        staged[:len(pages)] = stack_idx
        staged[p_pad:-1] = gidx
        staged[-1] = total
        jargs = arrays + (jnp.asarray(staged),)
        if filter_plan is None:
            fn = (K.fused_gather_decode_bitmap_batch if engine == "pallas"
                  else R.fused_gather_batch_ref)
            out = fn(*jargs, _words_buffer(engine, n_words),
                     page_size=ps, n_words=n_words, p_pad=p_pad,
                     want_ids=want_ids)
        else:
            from repro.kernels.label_filter import kernel as LK
            from repro.kernels.label_filter import ref as LR
            fwords = filter_plan.device_bitmap(engine, n_words)
            fn = (LK.fused_gather_decode_filter_bitmap_batch
                  if engine == "pallas" else LR.fused_gather_filter_batch_ref)
            out = fn(*jargs, fwords, _words_buffer(engine, n_words),
                     page_size=ps, n_words=n_words, p_pad=p_pad,
                     want_ids=want_ids)
        if want_ids:
            words, ids = out
            mat = np.asarray(ids, np.int64)
            pos_of = {int(p): i for i, p in enumerate(pages)}
            for p in miss:
                i = pos_of[p]
                cache.put(p, mat[i, :col.pages[p].count].copy(),
                          part=int(owner[i]))
        else:
            words = out
        host_words = np.asarray(words)
        _pool_words(engine, n_words, words)  # reuse 2 dispatches later
        return PAC.from_dense_bitmap(host_words, target_page_size)
    # SPMD tail: bucket per device and dispatch across the mesh
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.kernels.shard import sharded_fused_entry
    mesh, plan, pmax = parts.device_plan(engine)
    block0 = dev_of_page * (ppd * pmax)
    local_idx = (stack_idx - block0).astype(np.int32)
    # pidx already maps each row to its page's slot; gather its device
    # from there instead of a second searchsorted over all rows
    dev_of_row = dev_of_page[pidx]
    dev_page_start = np.searchsorted(dev_of_page, np.arange(g))
    base_local = pidx - dev_page_start[dev_of_row]
    gidx = (base_local * ps + (rows - page_of * ps)).astype(np.int32)
    row_lists = [gidx[dev_of_row == i] for i in range(g)]
    p_pad = _page_class(int(per_dev.max()), ppd * pmax)
    t_pad = size_class(max(len(x) for x in row_lists), RANGE_CLASS_MIN)
    staged = np.zeros((g, p_pad + t_pad + 1), np.int32)
    for i in range(g):
        sel = local_idx[dev_of_page == i]
        staged[i, :len(sel)] = sel
        staged[i, p_pad:p_pad + len(row_lists[i])] = row_lists[i]
        staged[i, -1] = len(row_lists[i])
    jstaged = jax.device_put(
        staged, NamedSharding(mesh, PartitionSpec("part", None)))
    fargs = ()
    if filter_plan is not None:
        fargs = (filter_plan.device_bitmap_sharded(engine, n_words, mesh),)
    fn = sharded_fused_entry(mesh, engine, ps, n_words, p_pad, want_ids,
                             filter_plan is not None)
    out = fn(*plan, jstaged, *fargs)
    if want_ids:
        planes, ids = out
        mat = np.asarray(ids, np.int64)  # [g, p_pad, ps]
        pos = {int(p): (int(dev_of_page[i]),
                        i - int(dev_page_start[dev_of_page[i]]),
                        int(owner[i]))
               for i, p in enumerate(pages)}
        for p in miss:
            d, s, k = pos[p]
            cache.put(p, mat[d, s, :col.pages[p].count].copy(), part=k)
    else:
        planes = out
    merged = np.bitwise_or.reduce(np.asarray(planes, np.uint32), axis=0)
    return PAC.from_dense_bitmap(merged, target_page_size)


def _retrieve_pac_batch_fused(col: DeltaColumn, los, his,
                              target_page_size: int, num_targets: int,
                              meter, engine: str, filter_plan=None,
                              resident: Optional[bool] = None) -> PAC:
    """Fused path: one dispatch from packed pages to target bitmap planes.

    The decoded ids stay on the device; the host receives only the dense
    bitmap (``PAC.from_dense_bitmap`` keeps the non-empty planes).  With a
    decoded-page LRU attached, the IOMeter charges the **miss** pages only
    (hits are RAM/device-resident, no lake I/O) and the kernel's
    by-product decode matrix backfills the cache (the one case where the
    matrix is pulled to the host).  With ``filter_plan`` (a
    :class:`repro.kernels.label_filter.ops.FilterPlan` over the target
    vertex table) the label-predicate bitmap is ANDed into the bitmap
    tail inside the same dispatch.

    Two transfer regimes, identical results and accounting:

    * **device-resident** (default): the packed column's device mirror is
      populated once (``PackedPages.device``); the dispatch ships only the
      int32 page-index vector + range positions, pages are gathered and
      decoded on device (LRU hits re-decode there rather than shipping
      their decoded rows across PCIe), and with a filter the predicate
      plane comes from the plan's device-cached bitmap -- no label bytes
      move either.  The bitmap output buffer is reused across dispatches
      (aliased into the kernel).
    * **per-dispatch pack** (``resident=False`` /
      ``REPRO_DEVICE_RESIDENT=0``): the PR 3 path -- miss pages are
      row-gathered on the host and shipped packed each dispatch, LRU-hit
      rows are fed in pre-decoded via the ``cached`` input.
    """
    ps = col.page_size
    pages, _ = page_set_for_ranges(los, his, ps)
    if pages.size == 0:
        return PAC(target_page_size)
    if engine not in ("jax", "pallas"):
        raise ValueError(f"fused path requires a kernel engine, not "
                         f"{engine!r}")
    if resident is None:
        resident = DEVICE_RESIDENT
    parts = live_partitions(col)
    if parts is not None and resident:
        # partition plane attached: shard the fused dispatch across the
        # device mesh (the monolithic resident path is its 1-partition
        # degenerate case; resident=False keeps the per-dispatch pack
        # baseline below as the single-device oracle)
        return _retrieve_pac_batch_sharded(col, parts, los, his, pages,
                                           target_page_size, num_targets,
                                           meter, engine, filter_plan)
    # page-granular statistics pushdown: with a predicate pushed down,
    # pages whose zone map cannot intersect its qualifying hull drop out
    # *before* the cache split and the staging -- never gathered onto
    # the device, never decoded, never charged (the sharded path above
    # applies the same sieve after its partition-level prune)
    qual = filter_plan.qual_range() if filter_plan is not None else None
    pages, pmask = prune_page_list(col, pages, qual)
    if pages.size == 0:  # every page statistics-pruned
        return PAC(target_page_size)
    cache = live_cache(col)
    part_of = {}
    if cache is None:
        hits, miss = {}, [int(p) for p in pages]
    else:
        # a partitioned column's LRU entries live in the (partition,
        # page) namespace on every path -- the non-resident oracle must
        # probe/fill the same keys the sharded dispatches use, or one
        # column's cache splits into two disjoint namespaces
        # (double-charging warm pages)
        owner = parts.part_of_pages(pages) if parts is not None else None
        if owner is not None:
            part_of = {int(p): int(o) for p, o in zip(pages, owner)}
        hits, miss = cache.split(pages, owner=owner)
    _charge_pages(col, miss, meter)
    n_words = -(-num_targets // 32)
    if resident:
        # rows are in sorted-page order: base_of_page[i] == i
        gidx, total = _gather_positions(pages, np.arange(len(pages)),
                                        los, his, ps,
                                        pruned=pmask is not None)
        plan = pack_column(col).device_plan(engine)
        # one staging vector [idx | gidx | total] = one device put per
        # dispatch (three separate puts were a measurable fixed cost);
        # page padding capped at the whole column (sharded-path ladder
        # cap, backported to the monolithic resident dispatch)
        p_pad = _page_class(len(pages), len(col.pages))
        staged = np.zeros(p_pad + len(gidx) + 1, np.int32)
        staged[:len(pages)] = pages
        staged[p_pad:-1] = gidx
        staged[-1] = total
        jargs = plan + (jnp.asarray(staged),)
        # the decode matrix only exists to backfill the LRU: with no
        # cache -- or a warm one (zero misses) -- the ids never leave
        # the kernel, skipping the dominant output materialization
        want_ids = cache is not None and bool(miss)
        if filter_plan is None:
            fn = (K.fused_gather_decode_bitmap_batch if engine == "pallas"
                  else R.fused_gather_batch_ref)
            out = fn(*jargs, _words_buffer(engine, n_words),
                     page_size=ps, n_words=n_words, p_pad=p_pad,
                     want_ids=want_ids)
        else:
            from repro.kernels.label_filter import kernel as LK
            from repro.kernels.label_filter import ref as LR
            fwords = filter_plan.device_bitmap(engine, n_words)
            fn = (LK.fused_gather_decode_filter_bitmap_batch
                  if engine == "pallas" else LR.fused_gather_filter_batch_ref)
            out = fn(*jargs, fwords, _words_buffer(engine, n_words),
                     page_size=ps, n_words=n_words, p_pad=p_pad,
                     want_ids=want_ids)
        if want_ids:
            words, ids = out
            mat = np.asarray(ids, np.int64)
            pos_of = {int(p): i for i, p in enumerate(pages)}
            for p in miss:
                cache.put(p, mat[pos_of[p], :col.pages[p].count].copy(),
                          part=part_of.get(p))
        else:
            words = out
        host_words = np.asarray(words)
        _pool_words(engine, n_words, words)  # reuse 2 dispatches later
        return PAC.from_dense_bitmap(host_words, target_page_size)
    m = len(miss)
    m_pad = next_pow2(m)
    args = pack_page_list(col, miss)
    if m_pad - m:
        args = tuple(np.concatenate(
            [a, np.zeros((m_pad - m,) + a.shape[1:], a.dtype)])
            for a in args)
    hit_list = [int(p) for p in pages if int(p) in hits]
    cached = np.zeros((next_pow2(len(hit_list)), ps), np.int32)
    for i, p in enumerate(hit_list):
        d = hits[p]
        cached[i, :len(d)] = d
    # matrix row of each sorted page: misses first, then cached rows
    miss_set = set(miss)
    is_miss = np.fromiter((int(p) in miss_set for p in pages), bool,
                          len(pages))
    base_of_page = np.where(is_miss, np.cumsum(is_miss) - 1,
                            m_pad + np.cumsum(~is_miss) - 1)
    gidx, total = _gather_positions(pages, base_of_page, los, his, ps,
                                    pruned=pmask is not None)
    jargs = [jnp.asarray(a) for a in args] \
        + [jnp.asarray(cached), jnp.asarray(gidx),
           jnp.full((1, 1), total, np.int32)]
    if filter_plan is None:
        if engine == "pallas":
            words, ids = K.fused_decode_bitmap_batch(*jargs, page_size=ps,
                                                     n_words=n_words)
        else:
            words, ids = R.fused_batch_ref(*jargs, page_size=ps,
                                           n_words=n_words)
    else:
        from repro.kernels.label_filter import kernel as LK
        from repro.kernels.label_filter import ref as LR
        fargs = [jnp.asarray(filter_plan.pos), jnp.asarray(filter_plan.meta)]
        fn = (LK.fused_decode_filter_bitmap_batch if engine == "pallas"
              else LR.fused_filter_batch_ref)
        words, ids = fn(*jargs, *fargs, page_size=ps, n_words=n_words,
                        ops=filter_plan.program.ops)
    if cache is not None and miss:
        mat = np.asarray(ids, np.int64)
        for i, p in enumerate(miss):
            cache.put(p, mat[i, :col.pages[p].count].copy(),
                      part=part_of.get(p))
    return PAC.from_dense_bitmap(np.asarray(words), target_page_size)


def retrieve_pac_batch(col: DeltaColumn, los, his, target_page_size: int,
                       meter=None, engine: str = "pallas",
                       num_targets: Optional[int] = None,
                       fused: Optional[bool] = None,
                       label_filter=None,
                       resident: Optional[bool] = None,
                       delta_ids=None) -> PAC:
    """Batched Definition 2: many row ranges -> one merged (unioned) PAC.

    Kernel engines take the fused decode->bitmap path whenever the target
    id space is known (``num_targets``), the target page size is
    word-aligned, and the batch is large enough to amortize the fused
    tail's O(num_targets) bitmap pass (small batches keep the host path,
    which is O(neighbors) and faster there -- see bench_batch_scaling);
    ``fused`` forces the choice either way (the host path -- decode +
    ``PAC.from_ids`` -- is kept as the oracle and numpy route).

    ``label_filter`` (:class:`repro.core.labels.LabelFilter` over the
    target vertex table) pushes a label predicate down: the fused path
    ANDs the predicate bitmap inside the kernel dispatch; the host path
    intersects with the host-evaluated filter PAC (the oracle).  Label
    metadata I/O is the caller's to charge (see
    ``neighbor.retrieve_neighbors_batch``), keeping accounting identical
    on every path.

    ``resident`` picks the fused path's transfer regime (see
    :func:`_retrieve_pac_batch_fused`); None follows the
    ``REPRO_DEVICE_RESIDENT`` default.  Residency is purely a transfer
    optimization -- ids, PAC, and IOMeter are bit-identical either way.

    ``delta_ids`` -- the batch's pending neighbor ids from the mutable
    plane (already predicate-filtered by the caller) -- are unioned into
    the returned PAC after the base dispatch: the memtable rows are
    RAM-resident, so they cost no lake I/O and never touch the kernel.
    """
    los = np.asarray(los, np.int64)
    his = np.asarray(his, np.int64)
    if fused is None:
        fused = (engine != "numpy" and num_targets is not None
                 and target_page_size % 32 == 0
                 and len(los) >= FUSED_MIN_RANGES)
    if fused:
        if num_targets is None:
            raise ValueError("fused=True requires num_targets")
        plan = None
        if label_filter is not None:
            plan = label_filter.plan()
            if plan.count != int(num_targets):
                raise ValueError(
                    f"filter covers {plan.count} vertices but the target "
                    f"id space has {num_targets}")
        pac = _retrieve_pac_batch_fused(col, los, his, target_page_size,
                                        int(num_targets), meter, engine,
                                        plan, resident=resident)
    else:
        # non-fused oracle: the same page-granular pruning hull applies
        # (pruned pages hold no qualifying ids, and the intersect below
        # removes exactly those ids on the unpruned path), so meters
        # agree with the fused dispatches bit for bit
        qual = label_filter.qual_range() if label_filter is not None else None
        ids = decode_row_ranges(col, los, his, meter, engine, qual=qual)
        pac = PAC.from_ids(np.unique(ids), target_page_size) if ids.size \
            else PAC(target_page_size)
        if label_filter is not None:
            pac = pac.intersect(label_filter.pac(target_page_size))
    if delta_ids is not None and len(delta_ids):
        pac = pac.union(PAC.from_ids(np.asarray(delta_ids, np.int64),
                                     target_page_size))
    return pac


def retrieve_pac(col: DeltaColumn, lo: int, hi: int, target_page_size: int,
                 meter=None, use_pallas: bool = True) -> PAC:
    """Kernel-engine neighbor retrieval: rows [lo, hi) -> PAC.

    Charges the same page bytes as the numpy path (the I/O plane is
    identical; only the decode compute engine differs).
    """
    return retrieve_pac_batch(col, np.array([lo]), np.array([hi]),
                              target_page_size, meter,
                              engine=("pallas" if use_pallas else "jax"))


def decode_range_to_bitmap(col: DeltaColumn, lo: int, hi: int,
                           base: int, n_words: int,
                           use_pallas: bool = True) -> np.ndarray:
    """Fused path: delta rows [lo, hi) -> one uint32 bitmap over
    [base, base + 32 * n_words). ``base`` must be 32-aligned.

    The row mask is applied by decoding whole pages but marking rows
    outside [lo, hi) invalid via count clamping per page boundary -- for
    simplicity, rows outside the range are zeroed host-side by id slicing
    in the non-fused path; the fused path requires page-aligned [lo, hi)
    (the common case: whole-column label/bitmap scans).
    """
    assert base % 32 == 0
    ps = col.page_size
    assert lo % ps == 0 and (hi % ps == 0 or hi == col.count), \
        "fused path requires page-aligned ranges"
    p0, p1 = lo // ps, -(-hi // ps)
    args = [jnp.asarray(a) for a in pack_pages(col, p0, p1)]
    words_out = next_multiple(n_words, K.WORD_TILE)
    if use_pallas:
        bm = K.fused_decode_bitmap(*args, jnp.int32(base), page_size=ps,
                                   words_out=words_out)
    else:
        bm = R.fused_ref(*args, jnp.int32(base), page_size=ps,
                         words_out=words_out)
    return np.asarray(bm)[:n_words]


def ids_to_bitmap(ids: np.ndarray, base: int, n_words: int,
                  use_pallas: bool = True) -> np.ndarray:
    """Standalone bitmap construction from sorted ids (32-aligned base)."""
    assert base % 32 == 0
    n = next_multiple(max(len(ids), 1), K.ID_TILE)
    padded = np.zeros(n, np.int32)
    padded[:len(ids)] = ids
    words_out = next_multiple(n_words, K.WORD_TILE)
    if use_pallas:
        bm = K.bitmap_pallas(jnp.asarray(padded), jnp.int32(len(ids)),
                             jnp.int32(base), n_words=words_out)
    else:
        bm = R.bitmap_ref(jnp.asarray(padded), jnp.int32(len(ids)),
                          jnp.int32(base), words_out)
    return np.asarray(bm)[:n_words]
