"""Compiled label-predicate -> bitmap Pallas kernels (paper §5, pushed down).

The filtering plane's device entry points.  A :class:`~repro.core.labels.
CondProgram` is a static (hashable) postfix program over ``k`` RLE label
columns; the kernels specialize on it, so the whole And/Or/Not tree is
unrolled into straight-line word ops at trace time -- no recursion, no
interpretive dispatch on device.

* ``cond_bitmap_pallas`` -- evaluate the program over the label columns'
  interval position lists in one Mosaic kernel, a word tile at a time:
  each leaf enters a tile with the parity of the run boundaries before
  it and flips the bits at or past each boundary inside it, in packed
  words; the program then combines the leaves' words.  A plane costs
  O(n_words + runs) -- no lane searches for its run, and the dense
  per-vertex boolean column is never materialized.

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
from repro.kernels._pad import (next_multiple, note_trace,
                                pallas_interpret)
from repro.kernels.pac_decode.kernel import (_bitmap_tail_pallas,
                                             _page_deltas, _scan_rows_pallas,
                                             fused_gather_bitmap)

WORD_TILE = 64    # callers pad the predicate plane to this many words
COND_ROWS = 32    # the cond kernel's output block: (COND_ROWS, 128) words
COND_TILE = COND_ROWS * 128   # output words per grid step
RUN_CHUNK = 1024  # run positions of one leaf held in SMEM at a time


def _toggle_words(acc, widx, rel):
    """XOR-toggle every bit at or past tile-relative bit ``rel``: whole
    words past its word, the high bits of its own word."""
    w, b = rel >> 5, rel & 31
    return acc ^ jnp.where(widx > w, -1,
                           jnp.where(widx == w, jnp.left_shift(-1, b), 0))


def _cond_kernel(meta_ref, pos_hbm, out_ref, buf, cursor, sem, *, ops,
                 n_pos):
    """One word tile of the predicate plane, from the run boundaries
    inside it.

    Grid steps walk the tiles in order; each leaf keeps in SMEM a cursor
    into its run positions (the number already passed, whose parity
    gives the leaf's bit at the tile's first lane) and the
    ``RUN_CHUNK`` positions around it, DMAed in as the cursor crosses
    into them, so run lists of any length fit.  A position ``p`` in the
    tile flips every bit at or past ``p`` -- the parity of ``#{pos <=
    lane}``, which is what ``searchsorted(side="right") - 1`` gives.
    The leaves are packed words from the start, so the unrolled program
    combines whole words and no per-bit plane exists.  The next
    position rides in the loop's carry: its condition reads no ref,
    which the interpreter would not see change.
    """
    t = pl.program_id(0)
    k = meta_ref.shape[0] // 2
    count = meta_ref[1]
    span = out_ref.shape[0] * out_ref.shape[1] * 32
    base = t * span
    lim = jnp.minimum(base + span, count)
    widx = (jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
            * out_ref.shape[1]
            + jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1))

    def load(i, j):
        """Bring leaf ``i``'s chunk holding position ``j`` into SMEM."""
        copy = pltpu.make_async_copy(
            pos_hbm.at[pl.ds(pl.multiple_of(i * n_pos + j, RUN_CHUNK),
                           RUN_CHUNK)],
            buf.at[pl.ds(i * RUN_CHUNK, RUN_CHUNK)], sem)
        copy.start()
        copy.wait()

    @pl.when(t == 0)
    def _init():
        for i in range(k):
            cursor[i] = 0
            load(i, 0)

    leaves = []
    for i in range(k):
        def run_pos(j, i=i):
            return buf[i * RUN_CHUNK + j % RUN_CHUNK]

        def step(carry, i=i):
            j, p, acc = carry
            acc = _toggle_words(acc, widx, p - base)

            @pl.when((j + 1) % RUN_CHUNK == 0)
            def _next():
                load(i, j + 1)
            return j + 1, run_pos(j + 1), acc

        j0 = cursor[i]
        fill = -(meta_ref[2 * i] ^ 1 ^ (j0 & 1))
        j1, _, acc = jax.lax.while_loop(
            lambda c: c[1] < lim, step,
            (j0, run_pos(j0), jnp.full(out_ref.shape, fill, jnp.int32)))
        cursor[i] = j1
        leaves.append(acc)
    rem = jnp.clip(count - (t * (span // 32) + widx) * 32, 0, 32)
    valid = jnp.where(rem == 32, -1, jnp.left_shift(1, rem & 31) - 1)
    out_ref[...] = eval_program(ops, leaves) & valid


def _cond_words(pos, meta, n_words: int, ops: Tuple[Tuple, ...],
                interpret: Optional[bool]):
    """Predicate plane -> ``uint32[n_words]`` (see cond_bitmap_pallas)."""
    k, n_pos = pos.shape
    n_pad = next_multiple(n_pos + 1, RUN_CHUNK)   # >= 1 sentinel a leaf
    flat = jnp.pad(pos, ((0, 0), (0, n_pad - n_pos)),
                   constant_values=meta[0, 1]).reshape(-1)
    n_tiles = -(-n_words // COND_TILE)
    out = pl.pallas_call(
        functools.partial(_cond_kernel, ops=ops, n_pos=n_pad),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((COND_ROWS, 128), lambda t, meta: (t, 0)),
            scratch_shapes=[pltpu.SMEM((k * RUN_CHUNK,), jnp.int32),
                            pltpu.SMEM((k,), jnp.int32),
                            pltpu.SemaphoreType.DMA]),
        out_shape=jax.ShapeDtypeStruct((n_tiles * COND_ROWS, 128),
                                       jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(interpret),
    )(meta.reshape(-1), flat)
    return jax.lax.bitcast_convert_type(out.reshape(-1)[:n_words],
                                        jnp.uint32)


@functools.partial(jax.jit, static_argnames=("n_words", "ops", "interpret"))
def cond_bitmap_pallas(pos, meta, n_words: int, ops: Tuple[Tuple, ...],
                       interpret: Optional[bool] = None):
    """pos int32[k, n_pos] (sorted, padded with count), meta int32[k, 2]
    = (first_value, count), ``ops`` the static postfix program.  Returns
    uint32[n_words].

    One Mosaic kernel: each word tile reads only the run boundaries that
    fall inside it (:func:`_cond_kernel`), so a plane costs O(n_words +
    runs); bits at or past ``count`` are zero.
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
    are immutable), so the dispatch ships no label bytes and rebuilds no
    plane -- the bitmap tail ANDs each word tile of the resident plane
    into the neighbor words.  ``words_init`` is
    accepted for signature parity and not read.  Returns ``(words,
    ids)`` (``ids`` in ``idx`` order), or ``words`` alone without
    ``want_ids``.
    """
    note_trace("fused_gather_decode_filter_bitmap_batch")
    del words_init, page_size
    return fused_gather_bitmap(first, pos, mind, packed, staged, p_pad,
                               n_words, want_ids, interpret, fwords=fwords)
