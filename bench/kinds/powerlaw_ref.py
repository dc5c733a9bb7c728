"""Plain reference for store kind ``powerlaw``.

Straight numpy over the generated edge list: it imports nothing of the
program and reads nothing the program made.  A vertex's neighbors are
the ``dst`` of its edges; a retrieval's answer is the sorted union over
the batch (duplicate edges collapse, as in a set).

``fanout`` caps each vertex at its first ``fanout`` neighbors in id
order -- neighbor sampling, the approximation a GNN sampler would
tempt a store to make.  It breaks the configuration's guarantee of
exact answers and serves as the control.
"""
from __future__ import annotations

import types
from typing import Iterable, Optional

import numpy as np


def prepare(cfg: dict, data,
            requests: Optional[Iterable]) -> types.SimpleNamespace:
    """Edges of every source vertex the requests name (of every vertex
    for ``None``), grouped by source and sorted by (source, target)."""
    s, d = data.src, data.dst
    if requests is not None:
        need = np.zeros(data.n, bool)
        for _, args in requests:
            need[args["ids"]] = True
        sel = need[s]
        s, d = s[sel], d[sel]
    order = np.lexsort((d, s))
    return types.SimpleNamespace(src=s[order], dst=d[order])


def _ranges(lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    lengths = hi - lo
    starts = np.repeat(lo - np.concatenate([[0], np.cumsum(lengths)[:-1]]),
                       lengths)
    return starts + np.arange(int(lengths.sum()))


def retrieve(ref, ids: np.ndarray, fanout: int | None = None) -> np.ndarray:
    lo = np.searchsorted(ref.src, ids, "left")
    hi = np.searchsorted(ref.src, ids, "right")
    if fanout is not None:
        hi = np.minimum(hi, lo + fanout)
    return np.unique(ref.dst[_ranges(lo, hi)])


OPS = {"retrieve": retrieve}
