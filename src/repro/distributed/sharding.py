"""Sharding rules: parameter/activation/cache PartitionSpecs.

Strategy (production mesh ``(data=16, model=16)``, multi-pod adds an outer
``pod`` axis folded into data parallelism):

* **FSDP** -- every large parameter's d_model-like dimension is sharded over
  the data axes, so per-chip parameter+optimizer memory scales 1/NxDP.
* **TP**   -- head/ffn/expert dimensions shard over ``model``.
* **EP**   -- MoE expert banks shard their expert dimension over ``model``
  (16 / 64 / 128 experts all divide the 16-way model axis).
* **SP**   -- long-context decode (batch=1) shards the KV-cache *sequence*
  dimension over the data axes (flash-decode style partial attention; GSPMD
  inserts the log-sum-exp-equivalent reductions).
* Vectors (norm scales, A_log, biases) are replicated -- negligible bytes.

Vocab dims are padded to multiples of 256 (``padded_vocab``) so embedding /
head shards divide evenly (Megatron-style vocab padding).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import AbstractMesh, Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig


def padded_vocab(cfg: ModelConfig, multiple: int = 256) -> int:
    return -(-cfg.vocab_size // multiple) * multiple


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """All data-parallel axes: ('pod', 'data') on the multi-pod mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _dp(mesh: Mesh):
    ax = data_axes(mesh)
    return ax if len(ax) > 1 else (ax[0] if ax else None)


def param_spec(path: Tuple[str, ...], leaf, mesh: Mesh) -> P:
    """PartitionSpec for one parameter, keyed on its tree path.

    Parameters under ``units``/``enc_units`` are stacked along a leading
    scan axis; rules apply to the trailing dims with a ``None`` prepended.
    """
    dp = _dp(mesh)
    name = "/".join(str(p) for p in path)
    shape = leaf.shape
    # optimizer states nest param paths under m/v/row/col; the scan axis is
    # present whenever 'units'/'enc_units' appears anywhere in the path
    lead = 1 if any(p in ("units", "enc_units") for p in path) else 0

    # int8-quantized moment leaves ({"q": [..., nblk, 128], "scale":
    # [..., nblk, 1]}) inherit the parent matrix's spec: the split last
    # dim (nblk) takes the parent's last-dim axis, the block dim is local.
    if path and str(path[-1]) in ("q", "scale") and len(shape) - lead >= 3:
        class _Dummy:
            pass
        parent = _Dummy()
        parent.shape = shape[:-2] + (shape[-2] * max(shape[-1], 1),)
        pspec = param_spec(path[:-1], parent, mesh)
        entries = list(pspec) + [None] * (len(parent.shape) - len(pspec))
        return P(*entries, None)
    core = len(shape) - lead
    pre = [None] * lead
    # vectors & scalars: replicate
    if core <= 1:
        return P()
    # embeddings: lookup table keeps vocab UNsharded (token gather stays
    # collective-free) with d_model over model; the decoupled head is
    # vocab-parallel so logits land vocab-sharded with no psum.
    if name.endswith("embed"):
        return P(None, "model")
    if name.endswith("lm_head"):
        return P(None, "model")
    # MoE expert banks [E, d_in, d_out]: EP over model + FSDP over data
    if "/moe/" in name and core == 3:
        return P(*pre, "model", dp, None)
    if name.endswith("/moe/router"):
        return P(*pre, dp, None)
    if name.endswith("conv_w"):          # [W, C]: channels over model
        return P(*pre, None, "model")
    # attention / mlp / ssm projections [d_in, d_out]
    if core == 2:
        # contract-side sharding heuristic: project *out of* d_model -> TP on
        # the output dim; project back *into* d_model -> TP on the input dim.
        if name.endswith(("/o", "/down", "/out_proj")):
            return P(*pre, "model", dp)
        return P(*pre, dp, "model")
    return P()


def _validate(spec: P, shape, mesh: Mesh) -> P:
    """Drop mesh axes whose size does not divide the dim (safety net)."""
    out = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            out.append(None if i >= len(shape) else entry)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = int(np.prod([mesh.shape[a] for a in axes]))
        out.append(entry if shape[i] % n == 0 else None)
    return P(*out)


def shard_params(params: Dict, mesh: Mesh) -> Dict:
    """Pytree of NamedShardings matching ``params``."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    def spec_for(kp, leaf):
        path = tuple(getattr(k, "key", getattr(k, "idx", str(k)))
                     for k in kp)
        spec = _validate(param_spec(path, leaf, mesh), leaf.shape, mesh)
        return NamedSharding(mesh, spec)

    specs = [spec_for(kp, leaf) for kp, leaf in flat]
    treedef = jax.tree_util.tree_structure(params)
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# activations / batches / caches
# ---------------------------------------------------------------------------

def batch_spec(mesh: Mesh, batch_size: int) -> P:
    """Tokens/labels [B, S]: shard batch over data axes when divisible."""
    dp = _dp(mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))
    if batch_size % n_dp == 0 and batch_size >= n_dp:
        return P(dp, None)
    return P(None, None)


def shard_batch(batch_tree: Dict, mesh: Mesh, batch_size: int) -> Dict:
    spec = batch_spec(mesh, batch_size)

    def one(leaf):
        nd = len(leaf.shape)
        return NamedSharding(mesh, P(*(list(spec) + [None] * (nd - 2))))

    return jax.tree.map(one, batch_tree)


def cache_spec(path: Tuple[str, ...], leaf, mesh: Mesh,
               batch_size: int) -> P:
    """KV/SSM cache sharding.

    batch > 1: shard batch over data, head_dim over model.
    batch == 1 (long-context): sequence parallelism -- shard the cache
    sequence dim over data instead.
    """
    dp = _dp(mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))
    name = "/".join(str(p) for p in path)
    shape = leaf.shape
    batch_ok = batch_size % n_dp == 0 and batch_size >= n_dp
    if name.endswith("index"):
        return P()
    nd = len(shape)
    # leading axis may be the scan (units) axis: detect via 'units' in path
    scan_off = 1 if "units" in name else 0
    core = nd - scan_off
    lead = [None] * scan_off
    if core == 4 and ("/kv/" in name or "/cross/" in name):
        # [B, L, KV, dh] -- KV-sequence parallelism: the cache length
        # shards over 'model' (flash-decode partial attention; kv-head
        # counts often do not divide the model axis); batch over data
        # when divisible, else (long-context batch=1) L takes every axis.
        if batch_ok:
            if shape[scan_off + 1] % mesh.shape["model"] == 0:
                return P(*lead, dp, "model", None, None)
            return P(*lead, dp, None, None, "model")
        all_ax = tuple(a for a in ("pod", "data", "model")
                       if a in mesh.axis_names)
        n_all = int(np.prod([mesh.shape[a] for a in all_ax]))
        if shape[scan_off + 1] % n_all == 0:
            return P(*lead, None, all_ax, None, None)
        return P(*lead, None, None, None, "model")
    if core == 4 and "/ssm/" in name and name.endswith("state"):
        # [B, H, P, N]
        if batch_ok:
            return P(*lead, dp, "model", None, None)
        return P(*lead, None, "model", None, None)
    if core == 3 and name.endswith("conv"):
        # [B, W-1, C]
        if batch_ok:
            return P(*lead, dp, None, "model")
        return P(*lead, None, None, "model")
    return P()


def shard_cache(cache: Dict, mesh: Mesh, batch_size: int) -> Dict:
    flat = jax.tree_util.tree_flatten_with_path(cache)[0]

    def spec_for(kp, leaf):
        path = tuple(getattr(k, "key", getattr(k, "idx", str(k)))
                     for k in kp)
        return NamedSharding(mesh, cache_spec(path, leaf, mesh, batch_size))

    specs = [spec_for(kp, leaf) for kp, leaf in flat]
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(cache), specs)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# in-model activation constraints (mesh-context-aware, no-op without a mesh)
# ---------------------------------------------------------------------------

def _context_mesh() -> Optional[AbstractMesh]:
    """The mesh ``jax.set_mesh`` installed around this trace, if any."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def constrain_like_params(tree):
    """Constrain a param-shaped pytree (e.g. gradients) to the parameter
    sharding rules against the ambient mesh.  Placing this right where
    gradients are produced makes GSPMD reduce-scatter each dW into its
    FSDP/TP shard instead of all-reducing the full matrix and re-slicing
    (measured ~2x collective bytes on the 123B dense config)."""
    mesh = _context_mesh()
    if mesh is None:
        return tree
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]

    def one(kp, leaf):
        path = tuple(getattr(k, "key", getattr(k, "idx", str(k)))
                     for k in kp)
        spec = _validate(param_spec(path, leaf, mesh), leaf.shape, mesh)
        return jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, spec))

    leaves = [one(kp, leaf) for kp, leaf in flat]
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(tree), leaves)


def constrain(x, *axes):
    """``with_sharding_constraint`` against the ambient mesh context.

    ``axes`` entries: "dp" -> all data axes, "model", or None.  Axes not
    present in the ambient mesh (or no mesh at all: smoke tests,
    single-device runs) degrade to a no-op, keeping model code
    mesh-agnostic.
    """
    mesh = _context_mesh()
    if mesh is None:
        return x
    resolved = []
    for a in axes:
        if a == "dp":
            ax = data_axes(mesh)
            resolved.append(ax if len(ax) > 1 else (ax[0] if ax else None))
        elif a is None or a in mesh.axis_names:
            resolved.append(a)
        else:
            resolved.append(None)
    spec = _validate(P(*resolved), x.shape, mesh)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec))
