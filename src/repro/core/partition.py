"""Explicit graph partitions over delta columns (GraphAr chunk style).

GraphAr's layout is fundamentally partitioned: vertex chunks and edge
chunks are keyed by contiguous source-vertex ranges, and because edges
are sorted by source vertex, a source range maps to a contiguous edge-row
range -- i.e. to a contiguous **page range** of the edge value column.
This module makes that unit explicit: a :class:`Partition` is a
page-aligned contiguous slice of a :class:`~repro.core.encoding.DeltaColumn`
with its own packed-page batch arrays
(:func:`~repro.core.encoding.build_packed` over the slice) and value
statistics, and a :class:`PartitionedColumn` is the ordered list of
partitions covering the whole column.

The partition is the unit of device placement: the sharded retrieval
plane (``kernels/pac_decode/ops``) stacks the partitions' unpack plans
into one array sharded across a 1-D device mesh (partition ``k`` lives on
device ``k * g // n_parts``), buckets each dispatch's page-index and
row-position vectors per partition on the host, runs the fused
decode->bitmap kernels under ``shard_map``, and OR-merges the
per-partition bitmap planes into one PAC.  The monolithic PR 4 path is
exactly the degenerate 1-partition case (``partition_column(col, 1)``
routes straight back to it).

Partition pruning:

* **range pruning** -- partitions containing none of a dispatch's pages
  are skipped outright (their edge-row range cannot intersect the
  batch).  This is meter-neutral by construction: a pruned partition had
  nothing to charge.
* **statistics pruning** -- each partition (and page) records the
  min/max id hull of its values at pack time; with a label filter pushed
  down, partitions whose hull cannot intersect the predicate's
  qualifying id range are skipped too (their neighbors would be ANDed
  away inside the kernel).  This *reduces* I/O charged relative to the
  unpartitioned path -- the first step of the ROADMAP's
  statistics-pushdown item -- and is therefore observable in the meter
  (ids stay bit-identical).

Both kinds are counted in :attr:`PartitionedColumn.partitions_pruned`
(and ``stats_pruned`` for the second), surfaced through
``GraphRetriever.stats()`` / ``ServeEngine.stats()``.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .encoding import (DeltaColumn, PackedPages, build_packed, engine_plan,
                       hull_intersects)

#: sharded-retrieval default: ``REPRO_PARTITIONS=N`` partitions every
#: column the retrieval plane packs (0 / unset keeps the monolithic
#: column; explicit ``partition_column`` / ``partitions=`` override).
DEFAULT_PARTITIONS = int(os.environ.get("REPRO_PARTITIONS", "0") or 0)


@dataclasses.dataclass
class Partition:
    """One page-aligned contiguous slice of a column.

    ``page_lo``/``page_hi`` are global page indices (half-open);
    ``row_lo``/``row_hi`` the covered rows; ``vmin``/``vmax`` the value
    hull over the slice's pages (empty hull = (0, -1)).  ``packed`` holds
    the slice's own batch arrays with **local** page numbering
    (0 .. page_hi - page_lo), the unit a device shard consumes.
    """

    index: int
    page_lo: int
    page_hi: int
    row_lo: int
    row_hi: int
    vmin: int
    vmax: int
    packed: PackedPages
    #: False when any non-empty page in the slice carries the empty-hull
    #: sentinel -- e.g. a column deserialized from a pre-stats ``.gar``
    #: file.  Unknown statistics must never prune: the hull then claims
    #: to intersect everything.
    stats_known: bool = True
    #: device this partition's plan shard lands on (set when the stacked
    #: device plan is placed; informational).
    device: "object | None" = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def n_pages(self) -> int:
        return self.page_hi - self.page_lo

    def intersects_range(self, lo: int, hi: int) -> bool:
        """Whether the value hull can intersect half-open ``[lo, hi)``.

        An unknown hull (``stats_known=False``) conservatively intersects
        everything -- pruning is an optimization and may only ever fire
        on hard evidence.  The intersection predicate itself is the
        shared :func:`repro.core.encoding.hull_intersects` (one
        definition across partition, page, and delta-segment
        granularities)."""
        if not self.stats_known:
            return True
        return hull_intersects(self.vmin, self.vmax, lo, hi)


def partition_bounds(n_pages: int, n_parts: int) -> np.ndarray:
    """Even page split: ``n_parts + 1`` boundaries over ``[0, n_pages]``.

    Mirrors GraphAr's fixed-size chunking: every partition gets
    ``ceil(n_pages / n_parts)`` pages except a short tail.  With fewer
    pages than partitions the trailing partitions are empty (degenerate
    but legal -- they never receive work).
    """
    span = -(-max(n_pages, 1) // n_parts)
    b = np.minimum(np.arange(n_parts + 1, dtype=np.int64) * span, n_pages)
    return b


@dataclasses.dataclass
class PartitionedColumn:
    """A delta column as an ordered list of page-aligned partitions.

    Built once per ``(column version, n_parts)`` by
    :func:`partition_column` and cached on the column.  Holds the
    per-partition :class:`~repro.core.encoding.PackedPages` (+ their
    unpack plans), the aggregate value statistics, the pruning/dispatch
    counters, and the engine-keyed **stacked device plan**: all
    partitions' unpack plans padded to a common page count and placed as
    one array sharded across a 1-D device mesh, so each device holds
    exactly its partitions' pages (the multi-device generalization of
    ``PackedPages.device_plan``).
    """

    col: DeltaColumn
    bounds: np.ndarray              # int64 [n_parts + 1], page units
    parts: List[Partition]
    version: int = 0
    # -- dispatch counters (reset via reset_stats) --------------------------
    dispatches: int = dataclasses.field(default=0, compare=False)
    partitions_pruned: int = dataclasses.field(default=0, compare=False)
    stats_pruned: int = dataclasses.field(default=0, compare=False)
    #: engine -> (mesh, stacked device plan, pmax); one placement each.
    _device_plans: Dict[str, Tuple] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    #: host->device placements performed (one per engine populated).
    device_transfers: int = dataclasses.field(
        default=0, repr=False, compare=False)

    @property
    def n_parts(self) -> int:
        return len(self.parts)

    @property
    def page_size(self) -> int:
        return self.col.page_size

    @property
    def pmax(self) -> int:
        """Pages per partition slot in the stacked plan (the padding
        target: the largest partition's page count)."""
        return max((p.n_pages for p in self.parts), default=0) or 1

    @property
    def stack_rows(self) -> int:
        """Rows of the stacked plan (``n_parts * pmax``) -- the natural
        upper bound for any dispatch's page-padding class: padding a
        gather past the whole stack is pure wasted decode."""
        return self.n_parts * self.pmax

    # -- page bookkeeping ---------------------------------------------------
    def part_of_pages(self, pages: np.ndarray) -> np.ndarray:
        """Partition index of each global page (vectorized)."""
        pages = np.asarray(pages, np.int64)
        return np.searchsorted(self.bounds, pages, side="right") - 1

    def prune(self, pages: np.ndarray,
              qual_range: Optional[Tuple[int, int]] = None,
              owner: Optional[np.ndarray] = None
              ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """One dispatch's partition pruning (and counters).

        Returns ``(owner, mask)``: each kept page's partition index plus
        a kept-page mask, or ``mask=None`` when every page survives (the
        overwhelmingly common case, kept allocation-free -- this runs on
        the per-dispatch hot path).  Partitions holding none of ``pages``
        are range-pruned (counted only; their absence costs nothing);
        with ``qual_range`` (a predicate's qualifying id hull, half-open)
        partitions whose value hull cannot intersect it are
        statistics-pruned and their pages drop out of the mask -- they
        are neither decoded nor charged.
        """
        self.dispatches += 1
        if owner is None:
            owner = self.part_of_pages(pages)
        present = np.unique(owner)
        if qual_range is not None:
            lo, hi = qual_range
            keep = np.asarray([self.parts[int(k)].intersects_range(lo, hi)
                               for k in present], bool)
            self.stats_pruned += int((~keep).sum())
            live = present[keep]
            self.partitions_pruned += self.n_parts - int(live.size)
            if live.size < present.size:
                mask = np.isin(owner, live)
                return owner[mask], mask
            return owner, None
        self.partitions_pruned += self.n_parts - int(present.size)
        return owner, None

    # -- device plane -------------------------------------------------------
    _mesh_sizes: Dict[int, int] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    def mesh_size(self, n_devices: int) -> int:
        """Mesh width for this partition count: the largest divisor ``g``
        of ``n_parts`` with ``g <= n_devices``, so every device owns
        exactly ``n_parts / g`` partitions.  One device (the degenerate
        mesh) is always legal.  Memoized -- this sits on the dispatch hot
        path."""
        g = self._mesh_sizes.get(n_devices)
        if g is None:
            n = self.n_parts
            g = max(d for d in range(1, n_devices + 1) if n % d == 0)
            self._mesh_sizes[n_devices] = g
        return g

    def mesh_devices(self, devices: Sequence) -> List:
        """Devices of the 1-D partition mesh (see :meth:`mesh_size`)."""
        return list(devices[:self.mesh_size(len(devices))])

    def stacked_plan_host(self) -> Tuple[np.ndarray, ...]:
        """All partitions' unpack plans stacked partition-major.

        Row ``k * pmax + j`` is partition ``k``'s plan row ``j`` (zero
        rows pad partitions shorter than ``pmax``); sharding this axis
        across the mesh gives each device exactly its partitions' pages.
        """
        pmax = self.pmax
        plans = [p.packed.unpack_plan() for p in self.parts]
        out = []
        for a_idx in range(4):  # (first, pos, mind, packed)
            ref = plans[0][a_idx]
            stack = np.zeros((self.n_parts * pmax,) + ref.shape[1:],
                             ref.dtype)
            for k, pl in enumerate(plans):
                stack[k * pmax: k * pmax + pl[a_idx].shape[0]] = pl[a_idx]
            out.append(stack)
        return tuple(out)

    def _engine_plan_host(self, engine: str) -> Tuple[np.ndarray, ...]:
        """The stacked plan in the engine's layout (``engine_plan``)."""
        return engine_plan(self.stacked_plan_host(), engine, self.page_size)

    def device_plan(self, engine: str) -> Tuple:
        """Engine-keyed sharded device plan: ``(mesh, arrays, pmax)``.

        Placed once per (column build, engine): the stacked plan crosses
        the host->device boundary a single time, sharded so partition
        ``k`` lives on mesh device ``k // (n_parts / g)``; every
        subsequent dispatch ships only the per-device staged index
        vectors.  Records each partition's device for observability.
        """
        plan = self._device_plans.get(engine)
        if plan is None:
            import jax
            from jax.sharding import Mesh, NamedSharding, PartitionSpec
            devs = self.mesh_devices(jax.devices())
            mesh = Mesh(np.array(devs), ("part",))
            arrays = tuple(
                jax.device_put(a, NamedSharding(
                    mesh, PartitionSpec("part", *(None,) * (a.ndim - 1))))
                for a in self._engine_plan_host(engine))
            ppd = self.n_parts // len(devs)
            for p in self.parts:
                p.device = devs[p.index // ppd]
            plan = (mesh, arrays, self.pmax)
            self._device_plans[engine] = plan
            self.device_transfers += 1
        return plan

    def device_plan_single(self, engine: str) -> Tuple:
        """The stacked plan on one (the default) device.

        The degenerate single-shard dispatch: below the sharding
        threshold -- or on a one-device host -- the partition plane
        dispatches the monolithic resident kernels directly over this
        placement with block-local page indices, paying no ``shard_map``
        launch overhead.  Placed once per engine.  When the sharded
        placement already exists on a one-device mesh it is reused
        outright (same bytes, same device); with a real multi-device
        mesh the two placements are distinct, so a workload whose
        dispatch sizes straddle ``SHARD_MIN_PAGES`` keeps both copies
        resident -- 2x the column's device footprint, a deliberate
        wall-time-for-memory trade (pin the threshold to 0 or huge to
        hold one copy)."""
        key = ("single", engine)
        plan = self._device_plans.get(key)
        if plan is None:
            sharded = self._device_plans.get(engine)
            if sharded is not None and sharded[0].devices.size == 1:
                plan = (sharded[1], sharded[2])  # same device, same bytes
            else:
                import jax.numpy as jnp
                arrays = tuple(jnp.asarray(a)
                               for a in self._engine_plan_host(engine))
                plan = (arrays, self.pmax)
                self.device_transfers += 1
            if self.parts and self.parts[0].device is None:
                import jax
                for p in self.parts:
                    p.device = jax.devices()[0]
            self._device_plans[key] = plan
        return plan

    # -- observability ------------------------------------------------------
    def reset_stats(self) -> None:
        self.dispatches = 0
        self.partitions_pruned = 0
        self.stats_pruned = 0

    def stats(self) -> Dict[str, object]:
        return {
            "n_parts": self.n_parts,
            "dispatches": self.dispatches,
            "partitions_pruned": self.partitions_pruned,
            "stats_pruned": self.stats_pruned,
            "devices": sorted({str(p.device) for p in self.parts
                               if p.device is not None}),
            "transfers": self.device_transfers,
            "version": self.version,
        }


def partition_column(col: DeltaColumn, n_parts: int) -> "PartitionedColumn | None":
    """Partition ``col`` into ``n_parts`` page-aligned slices (cached).

    Sets the column's requested partition count and builds (or returns)
    the cached :class:`PartitionedColumn` for the current version.
    ``n_parts <= 1`` detaches the partition plane -- the monolithic
    PR 4 path *is* the 1-partition case, so the retrieval plane routes
    straight to it -- and returns None.
    """
    if n_parts <= 1:
        col.partitions = 0
        col.partition_cache = None
        return None
    col.partitions = int(n_parts)
    return live_partitions(col)


def ensure_default_partitions(col: DeltaColumn) -> None:
    """Attach the ``REPRO_PARTITIONS`` environment default to a column
    with no explicit partitioning (an explicit :func:`partition_column`
    count wins)."""
    if DEFAULT_PARTITIONS > 1 and not getattr(col, "partitions", 0):
        partition_column(col, DEFAULT_PARTITIONS)


def live_partitions(col: DeltaColumn) -> "PartitionedColumn | None":
    """The column's partition plane, coherent with its current version.

    Rebuilds lazily after a version bump (writers only touch the column;
    derived partition packs follow), mirroring ``pack_column`` /
    ``live_cache`` keying.  Returns None when partitioning is off.
    """
    n_parts = getattr(col, "partitions", 0)
    if n_parts <= 1:
        return None
    cached = col.partition_cache
    if cached is not None and cached.version == col.version \
            and cached.n_parts == n_parts:
        return cached
    n_pages = len(col.pages)
    bounds = partition_bounds(n_pages, n_parts)
    ps = col.page_size
    parts: List[Partition] = []
    for k in range(n_parts):
        p0, p1 = int(bounds[k]), int(bounds[k + 1])
        pages = col.pages[p0:p1]
        packed = build_packed(pages, ps, version=col.version)
        nonempty = [p for p in pages if p.count]
        vmin = min((p.vmin for p in nonempty), default=0)
        vmax = max((p.vmax for p in nonempty), default=-1)
        # a non-empty page with the empty-hull sentinel means its stats
        # were never recorded (pre-stats serialized file): pruning on
        # such a partition would be guesswork, so mark the hull unknown
        known = all(p.vmax >= p.vmin for p in nonempty)
        row_hi = p1 * ps if p1 < n_pages else col.count
        parts.append(Partition(k, p0, p1, p0 * ps, row_hi, vmin, vmax,
                               packed, stats_known=known))
    col.partition_cache = PartitionedColumn(col, bounds, parts,
                                            version=col.version)
    return col.partition_cache
