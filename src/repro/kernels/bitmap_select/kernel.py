"""Bitmap selection pushdown Pallas kernel (paper §4.3 / [45]).

Given one data page of property values and the page's PAC bitmap, emit the
selected values *compacted to the front* plus the match count -- the TPU
form of selection pushdown: the page is scanned once in VMEM, the bitmap is
expanded to a lane mask, and an in-VMEM prefix sum computes each selected
value's output slot (scatter within the tile).  HBM sees only the page read
and the compacted write.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._pad import refuse_off_cpu

#: what the v5e compiler said of the (1, page_size) blocks; quoted by
#: :func:`refuse_off_cpu` off the CPU.
_BLOCK_REFUSAL = (
    "ValueError: The Pallas TPU lowering currently requires that the "
    "last two dimensions of your block shape are divisible by 8 and "
    "128 respectively, or be equal to the respective dimensions of "
    "the overall array.")


def _select_kernel(vals_ref, words_ref, out_ref, cnt_ref, *, page_size):
    vals = vals_ref[0]
    words = words_ref[0]
    lanes = jnp.arange(page_size, dtype=jnp.int32)
    bit = (jnp.take(words, lanes >> 5) >> (lanes & 31).astype(jnp.uint32)) \
        & jnp.uint32(1)
    mask = bit.astype(jnp.int32)
    pos = jnp.cumsum(mask) - 1            # output slot per selected lane
    n = mask.sum()
    out = jnp.zeros_like(vals)
    out = out.at[jnp.where(mask == 1, pos, page_size)].set(
        vals, mode="drop")
    out_ref[0] = out
    cnt_ref[0, 0] = n


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def bitmap_select_pallas(vals, words, page_size: int,
                         interpret: Optional[bool] = None):
    """vals f32[n_pages, page_size]; words uint32[n_pages, page_size//32].

    Returns (compacted f32[n_pages, page_size], counts int32[n_pages, 1]).
    """
    n = vals.shape[0]
    wpp = page_size // 32
    kern = functools.partial(_select_kernel, page_size=page_size)
    return pl.pallas_call(
        kern,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, page_size), lambda i: (i, 0)),
            pl.BlockSpec((1, wpp), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, page_size), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, page_size), vals.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
        ],
        interpret=refuse_off_cpu("bitmap_select_pallas", _BLOCK_REFUSAL,
                                 interpret),
    )(vals, words)
