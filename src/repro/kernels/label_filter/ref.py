"""Pure-jnp oracle for label_filter (same padded inputs, same programs).

The predicate plane here finds each lane's run by binary search, independent
of the kernel's walk over the run boundaries of each word tile.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.labels import eval_program
from repro.kernels._pad import note_trace
from repro.kernels.pac_decode.kernel import (_bitmap_scatter,
                                             _decode_plan_rows, _gather_rows)
from repro.kernels.pac_decode.ref import decode_pages_ref


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


@functools.partial(jax.jit, static_argnames=("n_words", "ops"))
def cond_bitmap_ref(pos, meta, n_words: int, ops: Tuple[Tuple, ...]):
    """jnp reference of ``cond_bitmap_pallas`` (whole bitmap in one pass)."""
    note_trace("cond_bitmap_ref")
    lanes = jnp.arange(n_words * 32, dtype=jnp.int32)
    return pack_bits(eval_cond_bits(pos, meta, lanes, ops))


@functools.partial(jax.jit, static_argnames=("page_size", "n_words", "ops"))
def fused_filter_batch_ref(first, min_deltas, bit_widths, word_offsets,
                           packed, counts, cached, gidx, gcount, fpos, fmeta,
                           page_size: int, n_words: int,
                           ops: Tuple[Tuple, ...]):
    """jnp reference of ``fused_decode_filter_bitmap_batch``."""
    note_trace("fused_filter_batch_ref")
    ids = decode_pages_ref(first, min_deltas, bit_widths, word_offsets,
                           packed, counts, page_size).astype(jnp.int32)
    full = jnp.concatenate([ids, cached], axis=0)
    nbr = _bitmap_scatter(full, gidx, gcount[0, 0], n_words)
    lanes = jnp.arange(n_words * 32, dtype=jnp.int32)
    words = nbr & pack_bits(eval_cond_bits(fpos, fmeta, lanes, ops))
    return words, ids


@functools.partial(jax.jit, static_argnames=("page_size", "n_words", "p_pad",
                                             "want_ids"))
def fused_gather_filter_batch_ref(first, pos, mind, packed, staged, fwords,
                                  words_init, page_size: int, n_words: int,
                                  p_pad: int, want_ids: bool = True):
    """jnp reference of ``fused_gather_decode_filter_bitmap_batch``.

    ``words_init`` is accepted for signature parity with the pallas
    entry's aliased output buffer and ignored (XLA allocates here).
    Without ``want_ids`` only the bitmap is returned.
    """
    from repro.kernels.pac_decode.kernel import _split_staged
    note_trace("fused_gather_filter_batch_ref")
    del words_init, page_size
    idx, gidx, gcount = _split_staged(staged, p_pad)
    g = _gather_rows(idx, first, pos, mind, packed)
    ids = _decode_plan_rows(*g)
    nbr = _bitmap_scatter(ids, gidx, gcount[0, 0], n_words)
    return (nbr & fwords, ids) if want_ids else nbr & fwords
