"""Pure-jnp oracle for the pac_decode kernels (same padded inputs)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.encoding import MINIBLOCK
from repro.kernels._pad import note_trace


def decode_pages_ref(first, min_deltas, bit_widths, word_offsets, packed,
                     counts, page_size: int):
    """jnp reference of delta_decode_pallas (vmapped over pages)."""

    def one(first1, mind, bw_arr, woff, pk, count):
        n_deltas = page_size - 1
        idx = jnp.arange(n_deltas, dtype=jnp.int32)
        mini = idx // MINIBLOCK
        within = idx % MINIBLOCK
        bw = jnp.take(bw_arr, mini).astype(jnp.int32)
        bit_pos = within * bw
        word_idx = jnp.take(woff, mini) + bit_pos // 32
        shift = (bit_pos % 32).astype(jnp.uint32)
        words = jnp.take(pk, word_idx)
        mask = jnp.where(bw >= 32, jnp.uint32(0xFFFFFFFF),
                         (jnp.uint32(1) << bw.astype(jnp.uint32)) - 1)
        resid = ((words >> shift) & mask).astype(jnp.int32)
        resid = jnp.where(bw == 0, 0, resid)
        deltas = resid + jnp.take(mind, mini)
        deltas = jnp.where(idx < count - 1, deltas, 0)
        return first1 + jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(deltas)])

    return jax.vmap(one)(first[:, 0], min_deltas, bit_widths, word_offsets,
                         packed, counts[:, 0])


def bitmap_ref(ids, count, base, n_words: int):
    """jnp reference of bitmap_pallas."""
    ids = ids.astype(jnp.int32)
    n = ids.shape[0]
    gidx = jnp.arange(n, dtype=jnp.int32)
    valid = gidx < count
    prev = jnp.concatenate([ids[:1] - 1, ids[:-1]])
    valid = valid & ((ids != prev) | (gidx == 0))
    rel = ids - base
    word = rel >> 5
    bit = jnp.uint32(1) << (rel & 31).astype(jnp.uint32)
    in_range = (rel >= 0) & (word < n_words) & valid
    out = jnp.zeros(n_words, jnp.uint32)
    contrib = jnp.where(in_range, bit, 0)
    return out.at[jnp.where(in_range, word, 0)].add(
        contrib, mode="drop").astype(jnp.uint32)


@functools.partial(jax.jit, static_argnames=("page_size", "n_words"))
def fused_batch_ref(first, min_deltas, bit_widths, word_offsets, packed,
                    counts, cached, gidx, gcount, page_size: int,
                    n_words: int):
    """jnp reference of ``fused_decode_bitmap_batch`` (same outputs).

    Decode goes through the vmapped per-page oracle (miss pages only --
    LRU-hit rows arrive pre-decoded in ``cached``); the bitmap tail is
    the jnp sorted scatter shared with the resident reference.
    """
    from .kernel import _bitmap_scatter
    note_trace("fused_batch_ref")
    ids = decode_pages_ref(first, min_deltas, bit_widths, word_offsets,
                           packed, counts, page_size)
    ids = ids.astype(jnp.int32)
    full = jnp.concatenate([ids, cached], axis=0)
    words = _bitmap_scatter(full, gidx, gcount[0, 0], n_words)
    return words, ids


@functools.partial(jax.jit, static_argnames=("page_size",))
def gather_decode_ref(first, pos, mind, packed, idx, page_size: int):
    """jnp reference of ``gather_decode_pallas`` (resident-plan gather)."""
    from .kernel import _decode_plan_rows, _gather_rows
    note_trace("gather_decode_ref")
    del page_size  # implied by the plan's per-delta shape
    g = _gather_rows(idx, first, pos, mind, packed)
    return _decode_plan_rows(*g)


@functools.partial(jax.jit, static_argnames=("page_size", "n_words", "p_pad",
                                             "want_ids"))
def fused_gather_batch_ref(first, pos, mind, packed, staged, words_init,
                           page_size: int, n_words: int, p_pad: int,
                           want_ids: bool = True):
    """jnp reference of ``fused_gather_decode_bitmap_batch``.

    ``words_init`` is accepted for signature parity with the pallas
    entry's aliased output buffer and ignored (XLA allocates here).
    Without ``want_ids`` only the bitmap is returned (and XLA never
    materializes the full decode matrix).
    """
    from .kernel import (_bitmap_scatter, _decode_plan_rows, _gather_rows,
                         _split_staged)
    note_trace("fused_gather_batch_ref")
    del words_init, page_size
    idx, gidx, gcount = _split_staged(staged, p_pad)
    g = _gather_rows(idx, first, pos, mind, packed)
    ids = _decode_plan_rows(*g)
    words = _bitmap_scatter(ids, gidx, gcount[0, 0], n_words)
    return (words, ids) if want_ids else words


def fused_ref(first, min_deltas, bit_widths, word_offsets, packed, counts,
              base, page_size: int, words_out: int):
    ids = decode_pages_ref(first, min_deltas, bit_widths, word_offsets,
                           packed, counts, page_size)
    acc = jnp.zeros(words_out, jnp.uint32)
    for p in range(ids.shape[0]):
        acc |= bitmap_ref(ids[p], counts[p, 0], base, words_out)
    return acc
