"""RLE interval list -> page-aligned bitmap Pallas kernel (paper §5.1).

Input: the interval position list ``P`` of a label column and the first
run's value.  Output: the label's boolean column as bitmap words, built a
word-tile at a time: each bit position finds its run via an in-VMEM binary
search (``searchsorted``) over ``P`` -- O(log |P|) per lane, lane-parallel
across the tile -- then bits are packed to words with a power-of-two dot.
This keeps the O(|P|) storage advantage while producing the bitmap form
that the selection-pushdown kernels consume.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._pad import refuse_off_cpu

WORD_TILE = 64  # words per grid step = 2048 bits

#: what the v5e compiler said of the (1, 64) output block; quoted by
#: :func:`refuse_off_cpu` off the CPU.
_BLOCK_REFUSAL = (
    "ValueError: The Pallas TPU lowering currently requires that the "
    "last two dimensions of your block shape are divisible by 8 and "
    "128 respectively, or be equal to the respective dimensions of "
    "the overall array.")


def _rle_kernel(pos_ref, meta_ref, out_ref, *, n_pos):
    wt = pl.program_id(0)
    positions = pos_ref[0]
    first_value = meta_ref[0, 0]      # 1 if first run is True
    want = meta_ref[0, 1]             # filter for label == want
    count = meta_ref[0, 2]            # number of rows
    bit_base = wt * WORD_TILE * 32
    lanes = bit_base + jnp.arange(WORD_TILE * 32, dtype=jnp.int32)
    run = jnp.searchsorted(positions, lanes, side="right").astype(jnp.int32) - 1
    value = (first_value ^ (run & 1)).astype(jnp.int32)
    bits = (value == want) & (lanes < count)
    # pack: [WORD_TILE, 32] x 2^b  (sum of distinct powers == OR)
    b = bits.reshape(WORD_TILE, 32).astype(jnp.uint32)
    pows = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    out_ref[0] = (b * pows[None, :]).sum(axis=1, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("n_words", "interpret"))
def rle_to_bitmap_pallas(positions, meta, n_words: int,
                         interpret: Optional[bool] = None):
    """positions int32[1, n_pos] (padded with ``count``), meta int32[1, 3] =
    (first_value, want, count). Returns uint32[n_words]."""
    assert n_words % WORD_TILE == 0
    n_pos = positions.shape[1]
    kern = functools.partial(_rle_kernel, n_pos=n_pos)
    return pl.pallas_call(
        kern,
        grid=(n_words // WORD_TILE,),
        in_specs=[
            pl.BlockSpec((1, n_pos), lambda wt: (0, 0)),
            pl.BlockSpec((1, 3), lambda wt: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, WORD_TILE), lambda wt: (0, wt)),
        out_shape=jax.ShapeDtypeStruct((1, n_words), jnp.uint32),
        interpret=refuse_off_cpu("rle_to_bitmap_pallas", _BLOCK_REFUSAL,
                                 interpret),
    )(positions, meta)[0]
