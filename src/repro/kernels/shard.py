"""Sharded dispatch entries for the partition plane.

The device-resident fused kernels of ``pac_decode`` / ``label_filter``
run unchanged on every shard of a 1-D ``("part",)`` device mesh: the
partitioned column's stacked unpack plan is sharded partition-major
across the mesh (``PartitionedColumn.device_plan``), the host buckets
each dispatch's page-index / row-position vectors per device into one
``staged`` matrix (row ``i`` = device ``i``'s ``[idx | gidx | total]``
vector, the same one-put layout as the monolithic resident path --
under a pushed-down predicate those vectors arrive already statistics-
pruned: partition hulls first, then per-page zone maps, so pruned
pages never appear in any shard's staged block), and
``shard_map`` runs the per-shard body -- gather, decode, bitmap tail,
optional resident-filter AND -- on every device concurrently.
Each shard emits a full ``[n_words]`` bitmap plane over the target id
space; the host OR-merges the ``g`` planes into one PAC (partitions may
contribute the same target id, so the merge is OR, not concat).

Entries are built once per static configuration and memoized
(``lru_cache`` keyed on mesh + shapes), so steady-state serving
dispatches hit the jit cache exactly like the monolithic path --
``note_trace`` fires only on a (re)trace.

``check_vma=False`` is required: pallas_call has no varying-manual-axes
rule under ``jax.shard_map`` (the kernels never cross shards, so the
check has nothing to verify anyway).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels._pad import note_trace

_PART = P("part")
_REPL = P()


@functools.lru_cache(maxsize=None)
def sharded_fused_entry(mesh, engine: str, page_size: int, n_words: int,
                        p_pad: int, want_ids: bool, filtered: bool):
    """Jitted sharded fused decode->bitmap entry (memoized per config).

    Returns a callable ``(first, pos, mind, packed, staged[, fwords]) ->
    words [g, n_words]`` (plus ``ids [g, p_pad, page_size]`` under
    ``want_ids``).  The four plan arrays are the partition-major stacked
    device plan (sharded ``P('part')``); ``staged`` is ``int32[g, L]``
    with device ``i``'s block-local ``[idx | gidx | total]`` vector in
    row ``i``; ``fwords`` (``filtered`` only) is the predicate's
    device-resident bitmap plane, replicated across the mesh so every
    shard ANDs it locally -- no label bytes move per dispatch.
    """
    from repro.kernels.label_filter import kernel as LK
    from repro.kernels.label_filter import ref as LR
    from repro.kernels.pac_decode import kernel as K
    from repro.kernels.pac_decode import ref as R

    if filtered:
        inner = (LK.fused_gather_decode_filter_bitmap_batch
                 if engine == "pallas" else LR.fused_gather_filter_batch_ref)
    else:
        inner = (K.fused_gather_decode_bitmap_batch
                 if engine == "pallas" else R.fused_gather_batch_ref)

    def body(first, pos, mind, packed, staged, *fwords):
        note_trace("sharded_fused")
        winit = jnp.zeros((n_words,), jnp.uint32)
        out = inner(first, pos, mind, packed, staged[0], *fwords, winit,
                    page_size=page_size, n_words=n_words, p_pad=p_pad,
                    want_ids=want_ids)
        if want_ids:
            words, ids = out
            return words[None], ids[None]
        return out[None]

    in_specs = (_PART,) * 5 + ((_REPL,) if filtered else ())
    out_specs = (_PART, _PART) if want_ids else _PART
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


@functools.lru_cache(maxsize=None)
def sharded_khop_entry(mesh, engine: str, n_out: int):
    """Jitted sharded fused k-hop (memoized per mesh + id space).

    ``(skey_sorted, svoff, seed_ids, filt_words) -> (visited [g, n_out],
    hop_planes [g, hops, n_out], hop_sizes [g, hops])``: the traversal
    plan's per-partition rank layouts are stacked partition-major and
    sharded ``P('part')`` (``TraversalPlan.sharded_arrays``); seed ids
    and the per-hop predicate words are replicated.  Each hop every
    shard rank-expands its partitions' rows into a full-size frontier
    plane (padding keys select nothing), an all-reduce ``pmax`` merges
    the planes across the mesh (a vertex may be reached via several
    partitions), and the filter-AND / visited-ANDNOT / scan step run
    replicated -- so the hop-to-hop frontier never leaves the device
    mesh.  Every shard returns identical planes; the host takes row 0.
    """
    from repro.kernels.traversal import kernel as TK
    from repro.kernels.traversal import ref as TR

    def body(skey_sorted, svoff, seed_ids, filt_words):
        note_trace("sharded_khop")
        f0 = TR._seed_plane(seed_ids, n_out)

        def hop(carry, fw):
            frontier, visited = carry
            if engine == "pallas":
                plane = TK._expand_pallas(skey_sorted, svoff, frontier,
                                          n_out=n_out)
            else:
                plane = TR.expand_plane_ref(skey_sorted, svoff, frontier)
            plane = jax.lax.pmax(plane, "part")
            nxt = plane * TR._filter_bits(fw, n_out) * (1 - visited)
            return (nxt, visited + nxt), nxt

        # unrolled like khop_scan_ref (compile time on the TPU)
        (_, visited), planes = jax.lax.scan(hop, (f0, f0), filt_words,
                                             unroll=True)
        return visited[None], planes[None], planes.sum(axis=1)[None]

    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(_PART, _PART, _REPL, _REPL),
                                 out_specs=(_PART, _PART, _PART),
                                 check_vma=False))


@functools.lru_cache(maxsize=None)
def sharded_decode_entry(mesh, engine: str, page_size: int, p_pad: int):
    """Jitted sharded page-matrix decode (the non-fused batched path).

    ``(first, pos, mind, packed, idx [g, p_pad]) ->
    ids [g, p_pad, page_size]``: each shard gathers its block-local page
    indices from its partitions' plan rows and decodes them; the host
    reassembles the global page matrix from the per-device slices.
    """
    from repro.kernels.pac_decode import kernel as K
    from repro.kernels.pac_decode import ref as R

    inner = (K.gather_decode_pallas if engine == "pallas"
             else R.gather_decode_ref)

    def body(first, pos, mind, packed, idx):
        note_trace("sharded_decode")
        return inner(first, pos, mind, packed, idx[0],
                     page_size=page_size)[None]

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(_PART,) * 5,
                                 out_specs=_PART, check_vma=False))
