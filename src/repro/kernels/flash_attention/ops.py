"""Jit'd wrapper exposing flash attention over [batch, heads, seq, d]."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from . import kernel as K
from . import ref as R


def mha(q, k, v, causal: bool = True, use_pallas: bool = True,
        block_q: int = K.DEFAULT_BLOCK_Q, block_k: int = K.DEFAULT_BLOCK_K,
        interpret: Optional[bool] = None):
    """q: [b, h, sq, d]; k/v: [b, h_kv, sk, d] (h_kv divides h: GQA)."""
    b, h, sq, d = q.shape
    h_kv = k.shape[1]
    if h_kv != h:
        rep = h // h_kv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, -1, d)
    vf = v.reshape(b * h, -1, d)
    if use_pallas:
        o = K.flash_attention(qf, kf, vf, causal=causal, block_q=block_q,
                              block_k=block_k, interpret=interpret)
    else:
        o = R.attention_ref(qf, kf, vf, causal=causal)
    return o.reshape(b, h, sq, d)
