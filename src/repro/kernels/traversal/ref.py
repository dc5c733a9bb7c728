"""jnp reference implementations of the fused traversal entries.

Shared representation (all entries):

* the **resident expansion plan** -- ``key_sorted`` int32[rows_pad] (the
  CSR key of every edge row, re-ordered so rows group by *value* id and
  padded to a word multiple with the key-space size) and ``voff``
  int32[n_value + 1] (each value id's row segment in that order) -- lives
  on device across dispatches
  (:class:`repro.kernels.traversal.ops.TraversalPlan`);
* frontiers are dense int32 0/1 **planes** over the vertex id space,
  built on device from padded seed-id vectors (``mode="drop"`` discards
  the out-of-range padding), so a dispatch ships O(seeds) ids, never a
  plane;
* per-hop predicates arrive as uint32 **bitmap words** (the
  label-filter plane's convention, ~n/32 ints per hop) and are expanded
  and ANDed in place inside the hop body.

One hop is: gather each edge row's frontier bit through ``key_sorted``,
pack the bits to uint32 words, take a word-level popcount prefix, and
read each value id's count as a **rank difference** at its segment
bounds -- then AND the hop's predicate bits, ANDNOT the visited plane,
and fold the survivors into ``visited``.  ``lax.scan`` steps the hop
``k`` times inside one jitted dispatch.

The rank formulation is the load-bearing trick: the obvious
``.at[vals].max(sel)`` scatter-OR is exact but serializes on CPU XLA
(~45x slower than a gather of the same width); gathers + a short
word-level prefix sum vectorize, and double as the multiplicity-exact
counting expansion (BI-2) since the rank difference *is* the segment's
edge count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels._pad import note_trace
from repro.kernels.pac_decode.kernel import _row_cumsum


def _shifts():
    # built in-trace (an iota) rather than captured as a module-level
    # device constant: pallas kernel bodies cannot close over arrays
    return jnp.arange(32, dtype=jnp.uint32)


def _cumsum(x):
    """1-D inclusive prefix sum as a blocked scan: over millions of
    words a flat ``jnp.cumsum`` takes the TPU compiler ~20x longer to
    compile than 1024-wide blocks plus a carry scan."""
    return _row_cumsum(x[None, :], chunk=1024)[0]


def _seed_plane(seed_ids, n: int):
    """Padded seed ids -> dense 0/1 plane (padding == n drops out)."""
    return jnp.zeros((n,), jnp.int32).at[seed_ids].set(1, mode="drop")


def _filter_bits(words, n: int):
    """uint32 bitmap words -> dense 0/1 plane over [0, n)."""
    ids = jnp.arange(n, dtype=jnp.int32)
    return ((jnp.take(words, ids >> 5)
             >> (ids & 31).astype(jnp.uint32)) & 1).astype(jnp.int32)


def expand_counts(key_sorted, voff, frontier):
    """Per-value-id count of frontier-selected in-rows (scatter-free).

    ``key_sorted`` groups edge rows by value id (padding keys >= the key
    space size select nothing); ``voff[v]:voff[v+1]`` is value ``v``'s
    segment.  The gathered 0/1 row selection is bit-packed to uint32
    words, a popcount prefix runs over the words, and each segment's
    count is the rank difference at its bounds.
    """
    nk = frontier.shape[0]
    sel = (jnp.take(frontier, jnp.minimum(key_sorted, nk - 1))
           * (key_sorted < nk))
    words = (sel.reshape(-1, 32).astype(jnp.uint32)
             << _shifts()[None, :]).sum(axis=1, dtype=jnp.uint32)
    csw = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         _cumsum(jax.lax.population_count(words).astype(jnp.int32))])

    def rank(i):
        w = i >> 5
        part = (jnp.take(words, w, mode="clip")
                & ((jnp.uint32(1) << (i & 31).astype(jnp.uint32)) - 1))
        return (jnp.take(csw, w)
                + jax.lax.population_count(part).astype(jnp.int32))

    return rank(voff[1:]) - rank(voff[:-1])


def expand_plane_ref(key_sorted, voff, frontier):
    """One frontier expansion: 0/1 plane of every value id reachable by
    an edge whose key is on the frontier (count > 0 == OR)."""
    return (expand_counts(key_sorted, voff, frontier) > 0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_out",))
def khop_scan_ref(key_sorted, voff, seed_ids, filt_words, *, n_out: int):
    """Fused k-hop: ``filt_words`` uint32[hops, n_words] steps the scan.

    Returns ``(visited, hop_planes, hop_sizes)``: the final visited 0/1
    plane (seeds included), each hop's newly-discovered plane
    int32[hops, n_out], and per-hop frontier sizes int32[hops].
    """
    note_trace("khop_ref")
    f0 = _seed_plane(seed_ids, n_out)

    def hop(carry, fw):
        frontier, visited = carry
        plane = expand_plane_ref(key_sorted, voff, frontier)
        nxt = plane * _filter_bits(fw, n_out) * (1 - visited)
        return (nxt, visited + nxt), nxt

    # unrolled: hops are few, and with the edge gather inside a while
    # loop the v5e compiler took ~155 s at LiveJournal size (~4 s
    # unrolled, compile-only)
    (_, visited), planes = jax.lax.scan(hop, (f0, f0), filt_words,
                                         unroll=True)
    return visited, planes, planes.sum(axis=1)


def _pack_words(plane, n_words: int):
    """Dense 0/1 plane -> uint32 bitmap words (on device)."""
    padded = jnp.zeros((n_words * 32,), jnp.int32).at[: plane.shape[0]] \
        .set(plane)
    return (padded.reshape(n_words, 32).astype(jnp.uint32)
            << _shifts()[None, :]).sum(axis=1, dtype=jnp.uint32)


@functools.partial(jax.jit,
                   static_argnames=("n_key", "n_mid", "n_out", "n_words"))
def two_hop_ref(ks_a, voff_a, ks_b, voff_b, seed_ids, filt_words, *,
                n_key: int, n_mid: int, n_out: int, n_words: int):
    """Heterogeneous two-hop chain (IC-8's shape): seeds in adjacency
    A's key space expand to a mid plane, which expands through adjacency
    B; the predicate words AND the result in place.  Returns
    ``(mid_plane, out_words)`` -- the output already packed to uint32
    bitmap words for ``PAC.from_dense_bitmap``.
    """
    note_trace("twohop_ref")
    f0 = _seed_plane(seed_ids, n_key)
    mid = expand_plane_ref(ks_a, voff_a, f0)
    out = expand_plane_ref(ks_b, voff_b, mid)
    return mid, _pack_words(out, n_words) & filt_words


@functools.partial(jax.jit, static_argnames=("n_key", "n_out"))
def count_hop_ref(key_sorted, voff, starts, ends, *,
                  n_key: int, n_out: int):
    """Counting expansion (BI-2's shape): the frontier arrives as sorted
    disjoint id intervals over the key space (padding index ``n_key + 1``
    drops); the rank difference at each target's segment bounds *is* its
    edge count, so multiplicity survives.  Returns int32[n_out] counts."""
    note_trace("counthop_ref")
    delta = jnp.zeros((n_key + 1,), jnp.int32) \
        .at[starts].add(1, mode="drop").at[ends].add(-1, mode="drop")
    plane = (_cumsum(delta)[:n_key] > 0).astype(jnp.int32)
    return expand_counts(key_sorted, voff, plane)
