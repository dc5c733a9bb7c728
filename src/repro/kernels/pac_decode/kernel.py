"""Delta-unpack -> prefix-scan -> page-bitmap kernels (Pallas / Mosaic).

TPU adaptation of the paper's BMI/SIMD decoding strategy (§4.3).  The CPU
version breaks the serial delta dependency with PEXT-compacted bit-shift
encodings; the TPU version breaks it with a **lane-parallel prefix scan**
in VMEM, and builds PAC bitmaps by counting (bit, word) hits of the
requested ids a word tile at a time instead of serial bit appends.

Power-of-two miniblock bit widths guarantee no packed value straddles a
32-bit word, so the unpack is a single gather + variable shift per lane --
the same alignment argument the paper uses for its SIMD path.  The
kernels:

  * ``_decode_slabs_pallas`` -- the resident decode.  Each staged page
                              index is scalar-prefetched and drives the
                              DMA of that page's slab of the plan
                              (``repro.core.encoding.slab_plan``) into
                              VMEM; there each delta's packed word is a
                              lane ``dynamic_gather`` within 128-lane
                              rows plus a select over the word rows,
                              then shift, mask, ``mind`` and the page's
                              row-major scan (``pltpu.roll``).
  * ``_scan_rows_pallas``  -- first id + inclusive row scan of the deltas
                              (Hillis-Steele over ``pltpu.roll``), gridded
                              over blocks of page rows.
  * ``_bitmap_tail_pallas`` -- sorted requested ids -> target bitmap
                              words, a 512-word tile at a time, each tile
                              streaming only the id chunks that can hit it
                              (scalar-prefetched work list) and counting
                              hits with a one-hot matmul on the MXU;
                              exact under duplicates, optionally ANDed
                              with a predicate plane.

XLA, inside the same jit, gathers the requested ids for the bitmap tail
and, where a plan is in the row layout (the jnp engine's, and a page too
small to make whole (8, 128) slabs), gathers page rows and per-delta
words and unpacks them for ``_scan_rows_pallas``; so do the per-dispatch
packed-page entries below.

Entries: ``gather_decode_pallas`` / ``fused_gather_decode_bitmap_batch``
(device-resident unpack plan, the served path), ``delta_decode_pallas`` /
``fused_decode_bitmap_batch`` (per-dispatch packed pages).  The older
single-range ``bitmap_pallas`` and ``fused_decode_bitmap`` use (1, n)
blocks the TPU refuses: they interpret on the CPU and raise
:class:`repro.kernels._pad.KernelNotLowered` elsewhere.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.encoding import (DEFAULT_PAGE_SIZE, MINIBLOCK, POS_BW_MASK,
                                 POS_SHIFT_SHIFT, POS_WIDX_SHIFT, SLAB_LANES)
from repro.kernels._pad import (next_multiple, note_trace, pallas_interpret,
                                refuse_off_cpu)

#: what the v5e compiler said of the entries that still use (1, n)
#: blocks; quoted by :func:`refuse_off_cpu` off the CPU.
_BLOCK_REFUSAL = (
    "ValueError: The Pallas TPU lowering currently requires that the "
    "last two dimensions of your block shape are divisible by 8 and "
    "128 respectively, or be equal to the respective dimensions of "
    "the overall array.")


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def delta_decode_pallas(first, min_deltas, bit_widths, word_offsets, packed,
                        counts, page_size: int = DEFAULT_PAGE_SIZE,
                        interpret: Optional[bool] = None):
    """Decode a batch of pages.

    Shapes: first/counts int32[n,1]; min_deltas/bit_widths/word_offsets
    int32[n, n_mini]; packed uint32[n, max_words].  Returns int32[n, page_size].
    """
    note_trace("delta_decode_pallas")
    return _scan_rows_pallas(
        first, _page_deltas(min_deltas, bit_widths, word_offsets, packed,
                            counts, page_size), interpret)


# --------------------------------------------------------------------------
# bitmap construction: sorted ids -> OR-accumulated bitmap words
# --------------------------------------------------------------------------

ID_TILE = 512     # ids per grid step
WORD_TILE = 64    # uint32 words per grid step (= 2048 bits = one page)


def _bitmap_tile(ids, valid, word_base):
    """Bitmap words for one (id tile x word tile): lane-parallel compare.

    ``sum`` of distinct powers of two == OR because ids are sorted and
    de-duplicated by ``valid`` -- each (word, bit) contributes once.
    """
    rel_word = (ids >> 5) - word_base                       # [ID_TILE]
    bit = (jnp.uint32(1) << (ids & 31).astype(jnp.uint32))  # [ID_TILE]
    cols = jnp.arange(WORD_TILE, dtype=jnp.int32)           # [WORD_TILE]
    hit = (rel_word[:, None] == cols[None, :]) & valid[:, None]
    contrib = jnp.where(hit, bit[:, None], jnp.uint32(0))
    return contrib.sum(axis=0, dtype=jnp.uint32)


def _bitmap_kernel(ids_ref, count_ref, base_ref, out_ref):
    it = pl.program_id(0)       # id-tile index (accumulation axis)
    wt = pl.program_id(1)       # word-tile index

    @pl.when(it == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[0]
    count = count_ref[0, 0]
    base = base_ref[0, 0]
    gidx = it * ID_TILE + jnp.arange(ID_TILE, dtype=jnp.int32)
    valid = gidx < count
    # sorted input: drop duplicates so sum == OR
    prev = jnp.concatenate([ids[:1] - 1, ids[:-1]])
    valid = valid & ((ids != prev) | (gidx == 0))
    word_base = base // 32 + wt * WORD_TILE
    out_ref[0] |= _bitmap_tile(ids, valid, word_base)


@functools.partial(jax.jit, static_argnames=("n_words", "interpret"))
def bitmap_pallas(ids, count, base, n_words: int,
                  interpret: Optional[bool] = None):
    """Sorted int32 ids -> uint32[n_words] bitmap for range starting at
    ``base`` (bit j of word w <=> id == base + 32*w + j).

    ``ids`` is padded to a multiple of ID_TILE; ``n_words`` to WORD_TILE.
    """
    note_trace("bitmap_pallas")
    n_ids = ids.shape[0]
    assert n_ids % ID_TILE == 0 and n_words % WORD_TILE == 0
    grid = (n_ids // ID_TILE, n_words // WORD_TILE)
    return pl.pallas_call(
        _bitmap_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ID_TILE), lambda it, wt: (0, it)),
            pl.BlockSpec((1, 1), lambda it, wt: (0, 0)),
            pl.BlockSpec((1, 1), lambda it, wt: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, WORD_TILE), lambda it, wt: (0, wt)),
        out_shape=jax.ShapeDtypeStruct((1, n_words), jnp.uint32),
        interpret=refuse_off_cpu("bitmap_pallas", _BLOCK_REFUSAL,
                                 interpret),
    )(ids.reshape(1, -1), count.reshape(1, 1), base.reshape(1, 1))[0]


# --------------------------------------------------------------------------
# fused: delta pages -> bitmap, IDs never leave VMEM
# --------------------------------------------------------------------------

def _fused_kernel(first_ref, mind_ref, bw_ref, woff_ref, packed_ref,
                  count_ref, base_ref, out_ref, *, page_size, words_out):
    pt = pl.program_id(0)   # page index (accumulation axis)

    @pl.when(pt == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = first_ref[0, 0] + jnp.cumsum(_page_deltas(
        mind_ref[...], bw_ref[...], woff_ref[...], packed_ref[...],
        count_ref[...], page_size)[0])
    count = count_ref[0, 0]
    gidx = jnp.arange(page_size, dtype=jnp.int32)
    valid = gidx < count
    prev = jnp.concatenate([ids[:1] - 1, ids[:-1]])
    valid = valid & ((ids != prev) | (gidx == 0))
    base = base_ref[0, 0]
    word_base = base // 32
    rel_word = (ids >> 5) - word_base
    bit = (jnp.uint32(1) << (ids & 31).astype(jnp.uint32))
    cols = jnp.arange(words_out, dtype=jnp.int32)
    hit = (rel_word[:, None] == cols[None, :]) & valid[:, None]
    contrib = jnp.where(hit, bit[:, None], jnp.uint32(0))
    out_ref[0] |= contrib.sum(axis=0, dtype=jnp.uint32)


def _page_deltas(min_deltas, bit_widths, word_offsets, packed, counts,
                 page_size):
    """All pages' packed miniblocks -> per-position deltas, one shot.

    Every step is an elementwise op or a row-gather, run in XLA.
    Column 0 is a
    zero delta and deltas past ``count - 1`` are zeroed, so an inclusive
    row scan plus ``first`` yields ``ids[n_pages, page_size]`` with
    positions >= count holding the running last id.
    """
    n = min_deltas.shape[0]
    n_deltas = page_size - 1
    idx = jnp.arange(n_deltas, dtype=jnp.int32)
    mini = idx // MINIBLOCK
    within = idx % MINIBLOCK
    bw = jnp.take(bit_widths, mini, axis=1).astype(jnp.int32)     # [n, D]
    woff = jnp.take(word_offsets, mini, axis=1)                   # [n, D]
    bit_pos = within[None, :] * bw
    word_idx = woff + bit_pos // 32
    shift = (bit_pos % 32).astype(jnp.uint32)
    words = jnp.take_along_axis(packed, word_idx, axis=1,
                                mode="clip")
    mask = jnp.where(bw >= 32, jnp.uint32(0xFFFFFFFF),
                     (jnp.uint32(1) << bw.astype(jnp.uint32)) - 1)
    resid = ((words >> shift) & mask).astype(jnp.int32)
    resid = jnp.where(bw == 0, 0, resid)
    deltas = resid + jnp.take(min_deltas, mini, axis=1)
    deltas = jnp.where(idx[None, :] < counts - 1, deltas, 0)
    return jnp.concatenate([jnp.zeros((n, 1), jnp.int32), deltas], axis=1)


@functools.partial(jax.jit, static_argnames=("page_size", "n_words",
                                             "interpret"))
def fused_decode_bitmap_batch(first, min_deltas, bit_widths, word_offsets,
                              packed, counts, cached, gidx, gcount,
                              page_size: int, n_words: int,
                              interpret: Optional[bool] = None):
    """Deduplicated page list + requested-row positions -> target bitmap.

    One dispatch for the whole batch: batched unpack->scan decode of the
    LRU-**miss** pages (the only pages shipped packed), then bitmap
    construction over the target id space [0, 32 * n_words) from the
    ``gcount`` requested rows addressed by ``gidx`` (int32[t], flat
    ``row * page_size + offset`` positions into the [miss | cached] row
    order, zero-padded).  ``cached`` (int32[c, page_size]) carries the
    decoded rows of the LRU-hit pages straight from the host cache --
    hits skip the on-device unpack entirely instead of being re-decoded.
    Returns ``(words, ids)``: ``uint32[n_words]`` plus the decoded
    miss-page matrix ``int32[n, page_size]`` (a by-product of the decode
    -- callers feed it to the decoded-page LRU without a second dispatch;
    they simply skip the host transfer when no cache is attached).
    """
    note_trace("fused_decode_bitmap_batch")
    ids = _scan_rows_pallas(
        first, _page_deltas(min_deltas, bit_widths, word_offsets, packed,
                            counts, page_size), interpret)
    full = jnp.concatenate([ids, cached], axis=0)
    words = _bitmap_tail_pallas(full, gidx, gcount[0, 0], n_words,
                                interpret)
    return words, ids


# --------------------------------------------------------------------------
# device-resident entries: whole-column unpack plan + on-device gather
# --------------------------------------------------------------------------

def _gather_rows(idx, *arrays):
    """On-device row gather of resident column arrays by page index.

    ``idx`` is int32[p_pad] (pow2 size-classed, clip-padded with 0); the
    arrays stay on device across dispatches, so the host ships the index
    vector, never page bytes.  A row plan's decode starts here; a slab
    plan's kernel DMAs the pages by index itself.
    """
    return tuple(jnp.take(a, idx, axis=0, mode="clip") for a in arrays)


def _row_cumsum(a, chunk=128):
    """Row-wise inclusive prefix sum as a two-level blocked scan.

    ``jnp.cumsum`` lowers to an O(log d)-pass associative scan over the
    full row; scanning ``chunk``-wide blocks and then the per-block
    carries touches the data ~half as many times (measurably ~2x faster
    on the CPU backend at the decode plane's [pages, page_size] shapes).
    """
    n, d = a.shape
    pad = (-d) % chunk
    ap = jnp.pad(a, ((0, 0), (0, pad))).reshape(n, -1, chunk)
    within = jnp.cumsum(ap, axis=2)
    carry = jnp.cumsum(within[:, :, -1], axis=1)
    carry = jnp.concatenate(
        [jnp.zeros((n, 1), a.dtype), carry[:, :-1]], axis=1)
    return (within + carry[:, :, None]).reshape(n, -1)[:, :d]


def _plan_deltas(pos, mind, packed):
    """Gathered unpack-plan rows (``PackedPages.unpack_plan``) -> the
    per-position deltas of each page, ``int32[n, page_size]``.

    The per-delta expansion folded every query-independent decision
    (miniblock lookup, zero-width handling, count clamping) into the
    plan at column-build time: ``pos`` packs word index / shift /
    effective bit width into one int32 lane, so this is one gather + a
    few elementwise ops.  Column 0 is a zero delta (the page's first id
    is ``first``), so an inclusive row scan yields the ids directly.
    This is the jnp engine's decode and the Pallas engine's for a row
    plan: the word gather runs in XLA, fused with the page-row gather,
    and only the scan is a kernel.  A slab plan decodes the same deltas
    in VMEM instead (:func:`_decode_slab`).
    """
    word_idx = pos >> POS_WIDX_SHIFT
    shift = ((pos >> POS_SHIFT_SHIFT) & 31).astype(jnp.uint32)
    bw = (pos & POS_BW_MASK).astype(jnp.uint32)
    mask = jnp.where(bw >= 32, jnp.uint32(0xFFFFFFFF),
                     (jnp.uint32(1) << bw) - 1)
    # the word gather roots the decode's costliest fusion, and XLA
    # labels a fusion with its root's scope: this one names it
    with jax.named_scope("gather_words"):
        words = jnp.take_along_axis(packed, word_idx, axis=1, mode="clip")
    resid = ((words >> shift) & mask).astype(jnp.int32)
    return jnp.concatenate(
        [jnp.zeros((pos.shape[0], 1), jnp.int32), resid + mind], axis=1)


def _decode_plan_rows(first, pos, mind, packed):
    """Decode gathered unpack-plan rows with jnp (the reference engine).

    Positions >= count hold the running last id, exactly like the
    per-dispatch pack path's decode.
    """
    return first + _row_cumsum(_plan_deltas(pos, mind, packed))


def _scan_kernel(first_ref, d_ref, out_ref):
    """Inclusive row prefix sum + page base, lane-parallel.

    A Hillis-Steele scan over the lane axis: ``log2(width)`` steps of a
    lane rotation (``pltpu.roll``) and a masked add, all in VMEM.
    """
    x = d_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    s = 1
    while s < x.shape[1]:
        x = x + jnp.where(lane >= s, pltpu.roll(x, s, 1), 0)
        s *= 2
    out_ref[...] = x + first_ref[...]


def _scan_rows_pallas(first, deltas, interpret):
    """``first + inclusive_cumsum(deltas, axis=1)`` as a Mosaic kernel,
    gridded over blocks of page rows (lanes padded to a multiple of 128
    with zero deltas, which leave every prefix unchanged)."""
    n, d = deltas.shape
    w = next_multiple(d, 128)
    if w != d:
        deltas = jnp.pad(deltas, ((0, 0), (0, w - d)))
    rows = next((r for r in (64, 32, 16, 8) if n % r == 0), n)
    ids = pl.pallas_call(
        _scan_kernel,
        grid=(n // rows,),
        in_specs=[pl.BlockSpec((rows, 1), lambda i: (i, 0)),
                  pl.BlockSpec((rows, w), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, w), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=pallas_interpret(interpret),
    )(first, deltas)
    return ids[:, :d] if w != d else ids


def _bitmap_scatter(ids, gidx, gcount, n_words):
    """Reference bitmap tail: requested rows -> bitmap, O(t log t).

    Sorts the ``gcount`` requested ids (padding sorts past the range
    sentinel), drops duplicates via the sorted-neighbor compare, and
    scatter-ORs one bit per distinct id (``sum`` of distinct powers of
    two == OR).  The jnp engine's tail; the Pallas entries build the
    same words with :func:`_bitmap_tail_pallas`.
    """
    n_slots = n_words * 32
    flat = jnp.take(ids.reshape(-1), gidx, mode="clip")
    k = jnp.arange(gidx.shape[0], dtype=jnp.int32)
    s = jnp.sort(jnp.where(k < gcount, flat, n_slots))
    prev = jnp.concatenate([s[:1] - 1, s[:-1]])
    valid = (s != prev) & (s >= 0) & (s < n_slots)
    word = s >> 5
    bit = jnp.uint32(1) << (s & 31).astype(jnp.uint32)
    out = jnp.zeros(n_words, jnp.uint32)
    return out.at[jnp.where(valid, word, 0)].add(
        jnp.where(valid, bit, jnp.uint32(0)), mode="drop")


# --------------------------------------------------------------------------
# bitmap tail: sorted requested ids -> target bitmap words, a word tile
# at a time
# --------------------------------------------------------------------------

TAIL_WORDS = 512            # output words per tile (16384 target ids)
TAIL_CHUNK = 1024           # sorted ids per step, as one (8, 128) block
_FIRST, _LAST, _VALID = 1, 2, 4


def _tail_work(s, n_tiles, tile_slots):
    """The tail kernel's work list, built in XLA from the sorted ids.

    Tile ``w`` owns the sorted ids ``s[seg[w]:seg[w+1]]`` and so the id
    chunks ``c0[w]..c1[w]`` (at least one, so every tile is written).
    Listing each tile's chunks in tile order gives at most
    ``n_tiles + n_chunks`` (tile, chunk) steps -- a static grid whose
    output block changes only between tiles, so each tile's words stay
    in VMEM while its chunks stream in.  Returns the scalar-prefetch
    arrays ``(seg, tile << 3 | flags, chunk)``; steps past the real
    list are marked invalid and do nothing.
    """
    n_chunks = s.shape[0] // TAIL_CHUNK
    bounds = jnp.arange(n_tiles + 1, dtype=jnp.int32) * tile_slots
    seg = jnp.searchsorted(s, bounds, side="left").astype(jnp.int32)
    lo, hi = seg[:-1], seg[1:]
    c0 = jnp.minimum(lo // TAIL_CHUNK, n_chunks - 1)
    c1 = jnp.where(hi > lo, (hi - 1) // TAIL_CHUNK, c0)
    cnt = c1 - c0 + 1
    end = jnp.cumsum(cnt)
    start = end - cnt
    step = jnp.arange(n_tiles + n_chunks, dtype=jnp.int32)
    tile = jnp.minimum(jnp.searchsorted(end, step, side="right"),
                       n_tiles - 1).astype(jnp.int32)
    valid = step < end[-1]
    chunk = jnp.where(valid, c0[tile] + step - start[tile], c1[-1])
    flags = (jnp.where(valid & (step == start[tile]), _FIRST, 0)
             | jnp.where(valid & (step == end[tile] - 1), _LAST, 0)
             | jnp.where(valid, _VALID, 0))
    return seg, (tile << 3) | flags, chunk


def _tail_kernel(seg_ref, tf_ref, ch_ref, s_ref, *refs, n_words, filtered):
    """One (tile, chunk) step: count each (bit, word) pair of the tile
    that the chunk's ids hit, then fold the counts into words.

    The counts are a one-hot matmul on the MXU -- ``A[bit, id] @
    W[word, id]^T`` -- so duplicates and any id order are exact (a word
    bit is set iff its count is > 0), and ids outside the tile match no
    word row.  Rows of the chunk outside the tile's id segment are
    skipped on scalar bounds.
    """
    if filtered:
        f_ref, out_ref, acc_ref = refs
    else:
        out_ref, acc_ref = refs
    words = out_ref.shape[1]
    i = pl.program_id(0)
    tf = tf_ref[i]
    tile = tf >> 3

    @pl.when((tf & _FIRST) != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((tf & _VALID) != 0)
    def _count():
        base = tile * (words * 32)
        lo, hi = seg_ref[tile], seg_ref[tile + 1]
        c = ch_ref[i]
        bit_row = jax.lax.broadcasted_iota(jnp.int32, (32, 128), 0)
        word_row = jax.lax.broadcasted_iota(jnp.int32, (words, 128), 0)
        for r in range(TAIL_CHUNK // 128):
            g0 = c * TAIL_CHUNK + r * 128

            @pl.when((g0 < hi) & (g0 + 128 > lo))
            def _row():
                rel = s_ref[r:r + 1, :] - base                # [1, 128]
                a = (bit_row == (rel & 31)).astype(jnp.bfloat16)
                w = (word_row == (rel >> 5)).astype(jnp.bfloat16)
                acc_ref[...] += jax.lax.dot_general(
                    a, w, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)

    @pl.when((tf & _LAST) != 0)
    def _emit():
        shifts = jax.lax.broadcasted_iota(jnp.int32, (32, words), 0)
        bits = jnp.where(acc_ref[...] > 0, jnp.left_shift(1, shifts), 0)
        out = jnp.sum(bits, axis=0, keepdims=True)  # distinct bits: == OR
        out_ref[...] = out & f_ref[...] if filtered else out


def _bitmap_tail_pallas(ids, gidx, gcount, n_words, interpret,
                        fwords=None):
    """Pallas bitmap tail: the same words as :func:`_bitmap_scatter`
    (ANDed with ``fwords`` when given), without a scatter.

    XLA gathers the requested ids, sorts them (padding becomes a
    sentinel past every tile) and builds the work list; the kernel then
    walks the word tiles, streaming in only the id chunks that can hit
    each tile.
    """
    flat = jnp.take(ids.reshape(-1), gidx, mode="clip")
    t = gidx.shape[0]
    t_pad = next_multiple(t, TAIL_CHUNK)
    k = jnp.arange(t_pad, dtype=jnp.int32)
    flat = jnp.pad(flat, (0, t_pad - t))
    words = min(TAIL_WORDS, n_words)
    n_tiles = -(-n_words // words)
    sentinel = n_tiles * words * 32
    s = jnp.sort(jnp.where(k < gcount, flat, sentinel))
    seg, tf, chunk = _tail_work(s, n_tiles, words * 32)
    tile_spec = pl.BlockSpec((1, words), lambda i, seg, tf, ch: (0, tf[i] >> 3))
    in_specs = [pl.BlockSpec((8, 128), lambda i, seg, tf, ch: (ch[i], 0))]
    args = [s.reshape(-1, 128)]
    if fwords is not None:
        in_specs.append(tile_spec)
        args.append(jax.lax.bitcast_convert_type(fwords, jnp.int32)
                    .reshape(1, n_words))
    kern = functools.partial(_tail_kernel, n_words=n_words,
                             filtered=fwords is not None)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tf.shape[0],),
            in_specs=in_specs,
            out_specs=tile_spec,
            scratch_shapes=[pltpu.VMEM((32, words), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((1, n_words), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(interpret),
    )(seg, tf, chunk, *args)
    return jax.lax.bitcast_convert_type(out.reshape(n_words), jnp.uint32)


# --------------------------------------------------------------------------
# resident decode: each staged page DMAed by its index, decoded in VMEM
# --------------------------------------------------------------------------

#: staged pages per grid step, each brought in by a DMA of its own
PAGES_PER_STEP = 8


def _decode_slab(pos, mind, packed):
    """One page's slab (``[page_size // 128, 128]``, lane ``j`` of row
    ``t`` is position ``128 t + j``) -> its ids, in VMEM.

    Each lane's packed word is a lane ``dynamic_gather`` from every word
    row, kept where the lane's word index falls in that row.  Lane 0
    carries the page's first id as its delta (width 0), so the row-major
    inclusive scan -- a Hillis-Steele scan over the lanes of each row
    (``pltpu.roll``), then one over the rows' totals -- is the ids.
    """
    word = _slab_words(pos >> POS_WIDX_SHIFT, packed)
    shift = ((pos >> POS_SHIFT_SHIFT) & 31).astype(jnp.uint32)
    bw = (pos & POS_BW_MASK).astype(jnp.uint32)
    mask = jnp.where(bw >= 32, jnp.uint32(0xFFFFFFFF),
                     (jnp.uint32(1) << bw) - 1)
    x = ((word >> shift) & mask).astype(jnp.int32) + mind
    lanes = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    s = 1
    while s < x.shape[1]:
        x = x + jnp.where(lanes >= s, pltpu.roll(x, s, 1), 0)
        s *= 2
    total = jnp.broadcast_to(x[:, -1:], x.shape)
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    carry = total
    s = 1
    while s < x.shape[0]:
        carry = carry + jnp.where(rows >= s, pltpu.roll(carry, s, 0), 0)
        s *= 2
    return x + carry - total


def _slab_words(widx, packed):
    """The packed word at each lane's word index ``widx``: a lane gather
    from every word row, kept where ``widx`` falls in that row."""
    lane = widx & (SLAB_LANES - 1)
    row_of = widx >> (SLAB_LANES.bit_length() - 1)
    word = jnp.zeros(widx.shape, packed.dtype)
    for r in range(widx.shape[0]):
        row = jnp.broadcast_to(packed[r:r + 1, :], widx.shape)
        got = jnp.take_along_axis(row, lane, axis=1,
                                  mode="promise_in_bounds")
        word = jnp.where(row_of == r, got, word)
    return word


def _slab_kernel(idx_ref, *refs):
    """Decode the step's pages: refs are ``PAGES_PER_STEP``-or-fewer
    page blocks of ``pos``, then of ``mind``, then of ``packed``, then
    the step's output block."""
    *ins, out_ref = refs
    b = len(ins) // 3
    for k in range(b):
        out_ref[k] = _decode_slab(ins[k][0], ins[b + k][0],
                                  ins[2 * b + k][0])


def _decode_slabs_pallas(idx, pos, mind, packed, interpret):
    """Slab plan (``repro.core.encoding.slab_plan``) + page indices ->
    ``int32[len(idx), page_size]`` ids, one Mosaic kernel.

    ``idx`` is scalar-prefetched; each page's block index map reads its
    entry, so the pipeline DMAs exactly the staged pages from HBM, a few
    per grid step, and nothing else of the plan.  Indices are clipped to
    the plan, as the row path's gather clips them."""
    idx = jnp.clip(idx, 0, pos.shape[0] - 1)
    p = idx.shape[0]
    _, rows, lanes = pos.shape
    b = next(k for k in (PAGES_PER_STEP, 4, 2, 1) if p % k == 0)

    def page_spec(k):
        return pl.BlockSpec((1, rows, lanes),
                            lambda i, ix: (ix[i * b + k], 0, 0))

    specs = [page_spec(k) for k in range(b)]
    ids = pl.pallas_call(
        _slab_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(p // b,),
            in_specs=specs * 3,
            out_specs=pl.BlockSpec((b, rows, lanes),
                                   lambda i, ix: (i, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((p, rows, lanes), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=pallas_interpret(interpret),
    )(idx, *[pos] * b, *[mind] * b, *[packed] * b)
    return ids.reshape(p, rows * lanes)


def decodes_in_kernel(plan) -> bool:
    """Whether a resident plan is decoded by the slab kernel: a plan in
    the slab layout (``pos`` page-leading 3-D) is; a row plan -- the
    jnp engine's, or a page size that makes no whole slab -- takes the
    XLA gathers and the row scan."""
    return plan[1].ndim == 3


def _decode_resident(idx, plan, interpret):
    """Resident plan + page indices -> ``int32[len(idx), page_size]``."""
    if decodes_in_kernel(plan):
        with jax.named_scope("gather_words"):
            return _decode_slabs_pallas(idx, *plan[1:], interpret)
    with jax.named_scope("gather_rows"):
        first, pos, mind, packed = _gather_rows(idx, *plan)
    return _scan_rows_pallas(first, _plan_deltas(pos, mind, packed),
                             interpret)


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def gather_decode_pallas(first, pos, mind, packed, idx,
                         page_size: int = DEFAULT_PAGE_SIZE,
                         interpret: Optional[bool] = None):
    """Decode an arbitrary page subset of a device-resident column.

    Inputs are the column's device unpack plan
    (``PackedPages.device_plan`` -- whole-column arrays, constant shapes
    across dispatches; a slab plan decodes in one kernel, a row plan
    through the XLA gathers); ``idx`` selects the pages.  Returns
    ``int32[p_pad, page_size]`` in ``idx`` order (clip-padded rows decode
    page 0 and are sliced off by the caller).
    """
    note_trace("gather_decode")
    del page_size  # implied by the plan's shape
    return _decode_resident(idx, (first, pos, mind, packed), interpret)


def _split_staged(staged, p_pad):
    """Split the one-put staging vector ``[idx | gidx | total]`` on
    device: three host->device transfers per dispatch become one."""
    idx = staged[:p_pad]
    gidx = staged[p_pad:-1]
    gcount = staged[-1:].reshape(1, 1)
    return idx, gidx, gcount


def fused_gather_bitmap(first, pos, mind, packed, staged, p_pad, n_words,
                        want_ids, interpret, fwords=None):
    """Shared body of the resident fused entries (plain and filtered):
    gather + decode the staged pages, then the bitmap tail, ANDed with
    the resident predicate plane ``fwords`` when given."""
    idx, gidx, gcount = _split_staged(staged, p_pad)
    # named scopes label each phase's device ops in the profiler trace
    # under a name that survives XLA's renumbering of its fusions
    with jax.named_scope("decode"):
        ids = _decode_resident(idx, (first, pos, mind, packed), interpret)
    with jax.named_scope("bitmap_tail"):
        words = _bitmap_tail_pallas(ids, gidx, gcount[0, 0], n_words,
                                    interpret, fwords=fwords)
    return (words, ids) if want_ids else words


@functools.partial(jax.jit, static_argnames=("page_size", "n_words", "p_pad",
                                             "want_ids", "interpret"))
def fused_gather_decode_bitmap_batch(first, pos, mind, packed, staged,
                                     words_init,
                                     page_size: int, n_words: int,
                                     p_pad: int,
                                     want_ids: bool = True,
                                     interpret: Optional[bool] = None):
    """Device-resident fused path: page indices -> target bitmap.

    Same bitmap contract as :func:`fused_decode_bitmap_batch`, but the
    packed column lives on device as its unpack plan
    (``PackedPages.device_plan``): the dispatch ships only ``staged``,
    one int32 vector packing ``idx`` (``p_pad`` clip-padded page
    indices), ``gidx`` (requested-row positions over the gathered row
    order, i.e. ``base_of_page[i] == i``), and the trailing range count
    -- one host->device put per dispatch.  There is no ``cached`` input
    -- with the column resident, re-decoding LRU-hit pages on device is
    cheaper than shipping their decoded rows across PCIe, and the
    IOMeter convention is untouched (misses charged host-side, hits
    free).  The bitmap tail walks the target words a tile at a time
    (:func:`_bitmap_tail_pallas`), so its cost follows the requested
    rows plus one step per word tile.  ``words_init`` (uint32[n_words])
    is accepted for signature parity with the dispatch layer's plane
    pool and not read: the words are a fresh output.

    With ``want_ids`` the decoded page matrix is emitted as a second
    output (rows follow ``idx`` order -- miss backfill indexes by
    position in the page list) and ``(words, ids)`` is returned.  The
    matrix is only ever needed to backfill the decoded-page LRU, so
    callers with no cache attached -- and warm steady-state ticks with
    zero misses -- pass ``want_ids=False`` and get ``words`` alone.
    """
    note_trace("fused_gather_decode_bitmap_batch")
    del words_init, page_size
    return fused_gather_bitmap(first, pos, mind, packed, staged, p_pad,
                               n_words, want_ids, interpret)


@functools.partial(jax.jit,
                   static_argnames=("page_size", "words_out", "interpret"))
def fused_decode_bitmap(first, min_deltas, bit_widths, word_offsets, packed,
                        counts, base, page_size: int, words_out: int,
                        interpret: Optional[bool] = None):
    """All pages' deltas -> one uint32[words_out] bitmap (base-relative)."""
    n, n_mini = min_deltas.shape
    max_words = packed.shape[1]
    kern = functools.partial(_fused_kernel, page_size=page_size,
                             words_out=words_out)
    return pl.pallas_call(
        kern,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, n_mini), lambda i: (i, 0)),
            pl.BlockSpec((1, n_mini), lambda i: (i, 0)),
            pl.BlockSpec((1, n_mini), lambda i: (i, 0)),
            pl.BlockSpec((1, max_words), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, words_out), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, words_out), jnp.uint32),
        interpret=refuse_off_cpu("fused_decode_bitmap", _BLOCK_REFUSAL,
                                 interpret),
    )(first, min_deltas, bit_widths, word_offsets, packed, counts,
      base.reshape(1, 1))[0]
