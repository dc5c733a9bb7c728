"""Jit'd wrappers + storage-plane integration for the filtering plane.

Engine-dispatched label filtering over RLE label columns: a compiled
:class:`~repro.core.labels.CondProgram` evaluates

* on the ``numpy`` engine as the vectorized run-boundary merge
  (:func:`repro.core.labels.program_filter_intervals` -- the host oracle),
* on the ``jax``/``pallas`` engines as an on-device bitmap kernel
  (:mod:`.kernel` / :mod:`.ref`) over the interval position lists.

All engines charge the same I/O -- the referenced labels' RLE metadata --
through :func:`repro.core.labels.charge_label_metadata`, so meters agree
bit-for-bit regardless of where the predicate evaluates.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple, Union

import numpy as np
import jax.numpy as jnp

from repro import obs
from repro.core.labels import (Cond, CondProgram, Intervals,
                               bitmap_to_intervals, charge_label_metadata,
                               compile_cond, interval_hull,
                               intervals_to_bitmap,
                               program_filter_intervals)
from repro.core.pac import PAC
from repro.core.vertex import VertexTable

from repro.kernels._pad import next_multiple

from . import kernel as K
from . import ref as R

ENGINES = ("numpy", "jax", "pallas")



@dataclasses.dataclass
class FilterPlan:
    """Padded kernel inputs for one (vertex table, program) pair.

    ``pos`` stacks every leaf label's interval position list, padded with
    ``count`` (the sentinel past every run); ``meta[i] = (first_value,
    count)``.  Built once per filter and reused across dispatches (the
    arrays are a few KB -- the whole point of the RLE interval lists).

    Label columns are immutable, so the plan also owns the filtering
    plane's **device residency**: :meth:`device` mirrors the RLE run
    arrays on device once per engine (filter dispatches ship no label
    bytes), and :meth:`device_bitmap` caches the fully evaluated
    predicate bitmap plane on device per (engine, n_words) -- the
    resident fused retrieval path ANDs that plane instead of rebuilding
    it every dispatch.
    """

    program: CondProgram
    pos: np.ndarray    # int32 [k, n_pos]
    meta: np.ndarray   # int32 [k, 2]
    count: int         # number of rows (vertices)
    #: vertex table the plan was built over (for the lazy qualifying-hull
    #: evaluation; label columns are immutable).
    vt: "VertexTable | None" = dataclasses.field(
        default=None, repr=False, compare=False)
    #: lazily evaluated qualifying hull (see :meth:`qual_range`).
    _qual: "Tuple[int, int] | None" = dataclasses.field(
        default=None, repr=False, compare=False)
    #: engine -> (device pos, device meta); populated lazily, once each.
    _device: Dict[str, Tuple] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    #: (engine, n_words[, mesh]) -> device uint32[n_words] predicate
    #: plane (the 3-tuple keys are the mesh-replicated copies consumed by
    #: the sharded dispatches).
    _device_bitmaps: Dict[Tuple, object] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    @property
    def n_words(self) -> int:
        return -(-self.count // 32)

    def count_plane(self) -> None:
        """Count one plane evaluated on the device (``filter.planes``)
        and the run positions it walks, padding left out
        (``filter.plane_runs``)."""
        obs.count("filter.planes")
        obs.count("filter.plane_runs",
                  np.count_nonzero(self.pos < self.count))

    def qual_range(self) -> Tuple[int, int]:
        """Half-open hull ``[lo, hi)`` of the qualifying ids.

        The partition plane's statistics pushdown compares partitions'
        min/max id hulls against it: a partition whose values cannot
        land inside the hull contributes nothing after the AND and is
        skipped.  Evaluated on the host (``program_filter_intervals``)
        **lazily, on first use**, and cached for the plan's lifetime --
        only the partition plane consumes it, so the one-shot kernel
        entries (``label_filter_bitmap`` et al.) never pay the host
        merge evaluation.  ``(0, 0)`` when nothing qualifies (every
        partition prunes -- correct: no id can pass the predicate).
        """
        if self._qual is None:
            self._qual = interval_hull(
                *program_filter_intervals(self.vt, self.program))
        return self._qual

    def device(self, engine: str) -> Tuple:
        """Device mirror of the RLE run arrays (once per engine)."""
        arrs = self._device.get(engine)
        if arrs is None:
            arrs = (jnp.asarray(self.pos), jnp.asarray(self.meta))
            obs.count("transfer.h2d_bytes", self.pos.nbytes + self.meta.nbytes)
            self._device[engine] = arrs
        return arrs

    def device_bitmap(self, engine: str, n_words: int):
        """Device-resident predicate bitmap over ``[0, 32 * n_words)``.

        Evaluated once per (engine, n_words) by the cond kernel over the
        device-mirrored run arrays (tile-padded, then sliced); lanes past
        ``count`` are zero, matching the per-dispatch evaluation of the
        non-resident fused kernel bit for bit.  Each evaluation counts
        one ``filter.planes`` and the plan's ``filter.plane_runs``
        (:meth:`count_plane`).
        """
        key = (engine, n_words)
        words = self._device_bitmaps.get(key)
        if words is None:
            self.count_plane()
            pos, meta = self.device(engine)
            padded = next_multiple(max(n_words, 1), K.WORD_TILE)
            fn = K.cond_bitmap_pallas if engine == "pallas" \
                else R.cond_bitmap_ref
            words = fn(pos, meta, n_words=padded,
                       ops=self.program.ops)[:n_words]
            self._device_bitmaps[key] = words
        return words

    def device_bitmap_sharded(self, engine: str, n_words: int, mesh):
        """The predicate plane replicated across a partition mesh.

        Keyed per (engine, n_words, mesh) so the replication crosses the
        host->device boundary once: every shard of the sharded fused
        dispatch ANDs its local copy, and filtered sharded dispatches
        ship no label bytes -- the multi-device analogue of
        :meth:`device_bitmap`'s single-device residency.
        """
        key = (engine, n_words, mesh)
        words = self._device_bitmaps.get(key)
        if words is None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec
            words = jax.device_put(
                self.device_bitmap(engine, n_words),
                NamedSharding(mesh, PartitionSpec()))
            self._device_bitmaps[key] = words
        return words


def make_plan(vt: VertexTable, cond: Union[Cond, CondProgram]) -> FilterPlan:
    program = compile_cond(cond)
    if not program.labels:
        raise ValueError("condition references no labels")
    rles = [vt.label_rle(n) for n in program.labels]
    n = vt.num_vertices
    n_pos = next_multiple(max(r.positions.size for r in rles), 128)
    pos = np.full((len(rles), n_pos), n, np.int32)
    meta = np.zeros((len(rles), 2), np.int32)
    for i, r in enumerate(rles):
        pos[i, :r.positions.size] = r.positions
        meta[i] = (int(r.first_value), n)
    return FilterPlan(program, pos, meta, n, vt=vt)


def label_filter_bitmap(vt: VertexTable, cond: Union[Cond, CondProgram],
                        meter=None, engine: str = "pallas") -> np.ndarray:
    """Whole-table predicate bitmap: uint32 words over [0, num_vertices)."""
    program = compile_cond(cond)
    charge_label_metadata(vt, program.labels, meter)
    if engine == "numpy":
        return intervals_to_bitmap(program_filter_intervals(vt, program),
                                   vt.num_vertices)
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; want one of {ENGINES}")
    plan = make_plan(vt, program)
    n_words = next_multiple(plan.n_words or 1, K.WORD_TILE)
    plan.count_plane()
    if engine == "pallas":
        words = K.cond_bitmap_pallas(jnp.asarray(plan.pos),
                                     jnp.asarray(plan.meta),
                                     n_words=n_words, ops=program.ops)
    else:
        words = R.cond_bitmap_ref(jnp.asarray(plan.pos),
                                  jnp.asarray(plan.meta),
                                  n_words=n_words, ops=program.ops)
    return np.asarray(words)[:plan.n_words]


def label_filter_intervals(vt: VertexTable, cond: Union[Cond, CondProgram],
                           meter=None, engine: str = "pallas") -> Intervals:
    """Qualifying half-open intervals; engine-dispatched, same accounting."""
    program = compile_cond(cond)
    if engine == "numpy":
        charge_label_metadata(vt, program.labels, meter)
        return program_filter_intervals(vt, program)
    return bitmap_to_intervals(
        label_filter_bitmap(vt, program, meter, engine), vt.num_vertices)


def label_filter_pac(vt: VertexTable, cond: Union[Cond, CondProgram],
                     page_size: int, meter=None,
                     engine: str = "pallas") -> PAC:
    """Qualifying ids as a PAC over ``page_size`` pages (bitmap planes on
    kernel engines -- no host-side id materialization).  One-shot wrapper
    around :meth:`repro.core.labels.LabelFilter.pac`, which owns the
    plane-selection logic (and the memoization for long-lived filters)."""
    from repro.core.labels import LabelFilter
    f = LabelFilter(vt, cond)
    f.charge(meter)
    return f.pac(page_size, engine)
