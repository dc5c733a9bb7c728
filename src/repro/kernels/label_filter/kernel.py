"""Compiled label-predicate -> bitmap Pallas kernels (paper §5, pushed down).

The filtering plane's device entry points.  A :class:`~repro.core.labels.
CondProgram` is a static (hashable) postfix program over ``k`` RLE label
columns; the kernels specialize on it, so the whole And/Or/Not tree is
unrolled into straight-line word ops at trace time -- no recursion, no
interpretive dispatch on device.

* ``cond_bitmap_pallas`` -- evaluate the program over the label columns'
  interval position lists: each bit position finds its run per leaf by a
  binary search (O(log |P|) per lane, lane-parallel; XLA work), then a
  kernel combines the leaf bit planes by the unrolled program a word tile
  at a time and packs bits to uint32 words.
  The O(|P|) storage advantage of the RLE interval lists is preserved; the
  dense per-vertex boolean column is never materialized.

* ``fused_decode_filter_bitmap_batch`` -- the filtering plane fused with
  the batched retrieval plane: miss-page delta decode (+ host-fed cached
  rows for LRU hits, which skip the on-device unpack entirely) -> neighbor
  bitmap tail -> AND with the predicate bitmap, all in ONE dispatch.
  "Neighbors of batch B having label L" leaves the kernel as bitmap
  planes; neither the decoded ids nor the unfiltered bitmap ever reach the
  host.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.labels import eval_program
from repro.kernels._pad import note_trace, pallas_interpret
from repro.kernels.pac_decode.kernel import (_bitmap_tail_pallas,
                                             _page_deltas, _scan_rows_pallas,
                                             fused_gather_bitmap)

WORD_TILE = 64    # callers pad the predicate plane to this many words
COND_TILE = 512   # output words per grid step of the cond kernel


def _leaf_planes(pos, meta, lanes):
    """Each label's bit plane at ``lanes``: the run holding a lane is
    found by binary search (O(log |P|) per lane, lane-parallel), and its
    parity against the first run's value gives the bit."""
    leaves = []
    for i in range(pos.shape[0]):
        run = jnp.searchsorted(pos[i], lanes,
                               side="right").astype(jnp.int32) - 1
        leaves.append((meta[i, 0] ^ (run & 1)).astype(jnp.int32) == 1)
    return leaves


def eval_cond_bits(pos, meta, lanes, ops: Tuple[Tuple, ...]):
    """Compiled program over leaf bit planes, statically unrolled.

    ``pos`` int32[k, n_pos] -- each label's interval position list, padded
    with ``count`` so out-of-range lanes land in the last run; ``meta``
    int32[k, 2] = (first_value, count); ``lanes`` int32[t] -- absolute bit
    positions.  Each label's plane is looked up once
    (:func:`_leaf_planes`); the op stream then runs through the one
    shared stack machine (:func:`repro.core.labels.eval_program`) over
    traced jnp planes.  Returns bool[t]; lanes >= count are forced False
    so NOT never sets bits past the row count.
    """
    return eval_program(ops, _leaf_planes(pos, meta, lanes)) \
        & (lanes < meta[0, 1])


def pack_bits(bits):
    """bool[n_words * 32] -> uint32[n_words] (sum of distinct powers == OR)."""
    b = bits.reshape(-1, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, :]
    return (b << shifts).sum(axis=1, dtype=jnp.uint32)


def _cond_kernel(count_ref, leaf_ref, out_ref, *, ops):
    """One word tile: leaf planes laid out ``[bit, word]`` -> program ->
    words, packed by a sublane sum of distinct powers of two (== OR)."""
    words = out_ref.shape[1]
    bit = jax.lax.broadcasted_iota(jnp.int32, (32, words), 0)
    word = pl.program_id(0) * words \
        + jax.lax.broadcasted_iota(jnp.int32, (32, words), 1)
    leaves = [leaf_ref[i] == 1 for i in range(leaf_ref.shape[0])]
    bits = eval_program(ops, leaves) & (word * 32 + bit < count_ref[0])
    out_ref[...] = jnp.sum(jnp.where(bits, jnp.left_shift(1, bit), 0),
                           axis=0, keepdims=True)


def _cond_words(pos, meta, n_words: int, ops: Tuple[Tuple, ...],
                interpret: Optional[bool]):
    """Predicate plane -> ``uint32[n_words]`` (see cond_bitmap_pallas)."""
    k = pos.shape[0]
    lanes = (jnp.arange(n_words, dtype=jnp.int32)[None, :] * 32
             + jnp.arange(32, dtype=jnp.int32)[:, None])
    leaves = jnp.stack(_leaf_planes(pos, meta, lanes)).astype(jnp.int32)
    words = min(COND_TILE, n_words)
    out = pl.pallas_call(
        functools.partial(_cond_kernel, ops=ops),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(-(-n_words // words),),
            in_specs=[pl.BlockSpec((k, 32, words),
                                   lambda w, count: (0, 0, w))],
            out_specs=pl.BlockSpec((1, words), lambda w, count: (0, w))),
        out_shape=jax.ShapeDtypeStruct((1, n_words), jnp.int32),
        interpret=pallas_interpret(interpret),
    )(meta[0, 1:], leaves)
    return jax.lax.bitcast_convert_type(out.reshape(n_words), jnp.uint32)


@functools.partial(jax.jit, static_argnames=("n_words", "ops", "interpret"))
def cond_bitmap_pallas(pos, meta, n_words: int, ops: Tuple[Tuple, ...],
                       interpret: Optional[bool] = None):
    """pos int32[k, n_pos] (padded with count), meta int32[k, 2] =
    (first_value, count), ``ops`` the static postfix program.  Returns
    uint32[n_words].

    The per-lane binary searches run in XLA (Mosaic lowers no
    searchsorted) into ``[k, 32, n_words]`` leaf planes, bit-major so
    that the kernel packs each word along sublanes; the kernel evaluates
    the program a word tile at a time.
    """
    note_trace("cond_bitmap_pallas")
    return _cond_words(pos, meta, n_words, ops, interpret)


# --------------------------------------------------------------------------
# fused: miss-page decode + cached rows -> neighbor bitmap AND label bitmap
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("page_size", "n_words", "ops",
                                             "interpret"))
def fused_decode_filter_bitmap_batch(first, min_deltas, bit_widths,
                                     word_offsets, packed, counts, cached,
                                     gidx, gcount, fpos, fmeta,
                                     page_size: int, n_words: int,
                                     ops: Tuple[Tuple, ...],
                                     interpret: Optional[bool] = None):
    """Predicate-pushdown batched retrieval, one dispatch.

    Same contract as ``pac_decode.kernel.fused_decode_bitmap_batch`` (miss
    pages packed, LRU-hit rows pre-decoded in ``cached``, requested-row
    positions in ``gidx`` over the [miss | cached] row order), plus the
    filter inputs of :func:`cond_bitmap_pallas`; the returned ``words``
    are the neighbor bitmap ANDed with the label-predicate bitmap.
    Returns ``(words, ids)`` with ``ids`` the decoded miss-page matrix
    (LRU backfill by-product).
    """
    note_trace("fused_decode_filter_bitmap_batch")
    ids = _scan_rows_pallas(
        first, _page_deltas(min_deltas, bit_widths, word_offsets, packed,
                            counts, page_size), interpret)
    full = jnp.concatenate([ids, cached], axis=0)
    fwords = _cond_words(fpos, fmeta, n_words, ops, interpret)
    words = _bitmap_tail_pallas(full, gidx, gcount[0, 0], n_words,
                                interpret, fwords=fwords)
    return words, ids


# --------------------------------------------------------------------------
# device-resident fused filter: page indices + resident predicate plane
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("page_size", "n_words", "p_pad",
                                             "want_ids", "interpret"))
def fused_gather_decode_filter_bitmap_batch(first, pos, mind, packed, staged,
                                            fwords, words_init,
                                            page_size: int, n_words: int,
                                            p_pad: int,
                                            want_ids: bool = True,
                                            interpret: Optional[bool] = None):
    """Device-resident predicate-pushdown retrieval, one dispatch.

    Same contract as ``pac_decode.kernel.fused_gather_decode_bitmap_batch``
    (whole-column unpack plan + on-device page gather driven by the
    one-put ``staged`` index vector, decode matrix emitted only under
    ``want_ids`` for LRU backfill), with the
    label plane equally resident: ``fwords`` is the predicate's
    **device-cached bitmap plane** (``FilterPlan.device_bitmap`` -- built
    once per (engine, n_words) from the RLE interval lists, label columns
    are immutable), so the dispatch ships no label bytes and re-evaluates
    no per-lane binary searches -- the bitmap tail ANDs each word tile of
    the resident plane into the neighbor words.  ``words_init`` is
    accepted for signature parity and not read.  Returns ``(words,
    ids)`` (``ids`` in ``idx`` order), or ``words`` alone without
    ``want_ids``.
    """
    note_trace("fused_gather_decode_filter_bitmap_batch")
    del words_init, page_size
    return fused_gather_bitmap(first, pos, mind, packed, staged, p_pad,
                               n_words, want_ids, interpret, fwords=fwords)
