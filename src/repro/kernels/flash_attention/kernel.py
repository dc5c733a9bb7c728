"""Blockwise online-softmax (flash) attention Pallas kernel.

The LM-side compute hot spot of the framework's training/prefill path.
Grid = (batch*heads, q_blocks, k_blocks) with the running max / denominator
/ accumulator carried in VMEM scratch across the innermost (k) grid
dimension -- the canonical TPU flash-attention schedule: K/V blocks stream
through VMEM while the MXU consumes (block_q x d) @ (d x block_k) tiles.

Supports causal masking (blocks entirely above the diagonal are skipped
via ``pl.when``) and GQA is handled by the caller (K/V heads broadcast).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._pad import pallas_interpret

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale, causal, block_q, block_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    should_run = True
    if causal:
        # skip blocks strictly above the diagonal
        should_run = qi * block_q + block_q - 1 >= ki * block_k

    @pl.when(should_run)
    def _body():
        q = q_ref[0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0].astype(jnp.float32)          # [bk, d]
        v = v_ref[0].astype(jnp.float32)          # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_cur)
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, causal: bool = True,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: Optional[bool] = None):
    """q/k/v: [bh, seq, d] (same seq for q and kv). Returns [bh, seq, d]."""
    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    block_q = min(block_q, seq_q)
    block_k = min(block_k, seq_k)
    assert seq_q % block_q == 0 and seq_k % block_k == 0
    grid = (bh, seq_q // block_q, seq_k // block_k)
    scale = 1.0 / (d ** 0.5)
    kern = functools.partial(_flash_kernel, scale=scale, causal=causal,
                             block_q=block_q, block_k=block_k)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=pallas_interpret(interpret),
    )(q, k, v)
