"""Pallas traversal kernels (CPU interpreter only, for now).

Same contracts as :mod:`repro.kernels.traversal.ref` -- the hop body
(frontier gather through ``key_sorted`` -> bit-pack -> popcount-rank
expand -> predicate AND -> visited ANDNOT) runs inside a
``pallas_call``; the ``lax.scan`` over hops, seed-plane construction,
and word packing stay in the surrounding jitted entry, so k hops are
still one dispatch with no host round-trips between hops.

The hop bodies gather through ``key_sorted`` (and BI-2's counting hop
scatter-adds) inside the kernel, which Mosaic does not lower, so off
the CPU every entry raises :class:`repro.kernels._pad.KernelNotLowered`
and the jnp engine (``engine="jax"``) is the device path.  A TPU build
would gather in XLA and tile the rank expansion over the value id space
the way the pac_decode bitmap tail tiles target words.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._pad import note_trace, refuse_off_cpu

from .ref import _filter_bits, _pack_words, _seed_plane, expand_counts

#: what the v5e compiler said of the hop bodies (an in-kernel 1-D
#: gather, and BI-2's scatter-add); quoted by :func:`refuse_off_cpu`
#: off the CPU.
_GATHER_REFUSAL = "NotImplementedError: Only 2D gather is supported"
_SCATTER_REFUSAL = ("NotImplementedError: Unimplemented primitive in "
                    "Pallas TPU lowering for KernelType.TC: scatter-add")


def _hop_kernel(ks_ref, voff_ref, f_ref, vis_ref, fw_ref, nxt_ref, *,
                n_out):
    """One hop: rank-expand the frontier plane, AND the predicate bits,
    ANDNOT the visited plane.  All planes live in VMEM; only the
    newly-discovered plane is written out."""
    plane = (expand_counts(ks_ref[...], voff_ref[...], f_ref[...])
             > 0).astype(jnp.int32)
    bits = _filter_bits(fw_ref[...], n_out)
    nxt_ref[...] = plane * bits * (1 - vis_ref[...])


def _hop_pallas(key_sorted, voff, frontier, visited, fwords, *,
                n_out: int, interpret: Optional[bool] = None):
    kern = functools.partial(_hop_kernel, n_out=n_out)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((n_out,), jnp.int32),
        interpret=refuse_off_cpu("khop_scan_pallas", _GATHER_REFUSAL,
                                 interpret),
    )(key_sorted, voff, frontier, visited, fwords)


@functools.partial(jax.jit, static_argnames=("n_out", "interpret"))
def khop_scan_pallas(key_sorted, voff, seed_ids, filt_words, *,
                     n_out: int, interpret: Optional[bool] = None):
    """Fused k-hop (see :func:`...ref.khop_scan_ref`): one scan-stepped
    dispatch, the hop body a pallas kernel."""
    note_trace("khop_pallas")
    f0 = _seed_plane(seed_ids, n_out)

    def hop(carry, fw):
        frontier, visited = carry
        nxt = _hop_pallas(key_sorted, voff, frontier, visited, fw,
                          n_out=n_out, interpret=interpret)
        return (nxt, visited + nxt), nxt

    # unrolled like khop_scan_ref (compile time on the TPU)
    (_, visited), planes = jax.lax.scan(hop, (f0, f0), filt_words,
                                         unroll=True)
    return visited, planes, planes.sum(axis=1)


def _expand_kernel(ks_ref, voff_ref, f_ref, out_ref):
    out_ref[...] = (expand_counts(ks_ref[...], voff_ref[...], f_ref[...])
                    > 0).astype(jnp.int32)


def _expand_pallas(key_sorted, voff, frontier, *, n_out: int,
                   interpret: Optional[bool] = None):
    return pl.pallas_call(
        _expand_kernel,
        out_shape=jax.ShapeDtypeStruct((n_out,), jnp.int32),
        interpret=refuse_off_cpu("_expand_pallas", _GATHER_REFUSAL,
                                 interpret),
    )(key_sorted, voff, frontier)


@functools.partial(jax.jit, static_argnames=("n_key", "n_mid", "n_out",
                                             "n_words", "interpret"))
def two_hop_pallas(ks_a, voff_a, ks_b, voff_b, seed_ids, filt_words, *,
                   n_key: int, n_mid: int, n_out: int, n_words: int,
                   interpret: Optional[bool] = None):
    """Heterogeneous two-hop chain, both expansions pallas kernels in
    one dispatch (see :func:`...ref.two_hop_ref`)."""
    note_trace("twohop_pallas")
    f0 = _seed_plane(seed_ids, n_key)
    mid = _expand_pallas(ks_a, voff_a, f0, n_out=n_mid,
                         interpret=interpret)
    out = _expand_pallas(ks_b, voff_b, mid, n_out=n_out,
                         interpret=interpret)
    return mid, _pack_words(out, n_words) & filt_words


def _count_kernel(ks_ref, voff_ref, starts_ref, ends_ref, out_ref, *,
                  n_key):
    delta = jnp.zeros((n_key + 1,), jnp.int32) \
        .at[starts_ref[...]].add(1, mode="drop") \
        .at[ends_ref[...]].add(-1, mode="drop")
    plane = (jnp.cumsum(delta)[:n_key] > 0).astype(jnp.int32)
    out_ref[...] = expand_counts(ks_ref[...], voff_ref[...], plane)


@functools.partial(jax.jit, static_argnames=("n_key", "n_out", "interpret"))
def count_hop_pallas(key_sorted, voff, starts, ends, *, n_key: int,
                     n_out: int, interpret: Optional[bool] = None):
    """Counting expansion (see :func:`...ref.count_hop_ref`) as one
    pallas kernel: interval frontier -> per-target edge counts."""
    note_trace("counthop_pallas")
    kern = functools.partial(_count_kernel, n_key=n_key)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((n_out,), jnp.int32),
        interpret=refuse_off_cpu("count_hop_pallas", _SCATTER_REFUSAL,
                                 interpret),
    )(key_sorted, voff, starts, ends)
