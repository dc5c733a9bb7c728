"""The benchmark's own data generators.

``clustered_labels`` is a verbatim copy of ``repro.data.synthetic``'s;
``powerlaw_graph`` replaces that module's generator, whose sources are
uniform whatever their Zipf rank (no hubs).  The benchmark keeps its
own generators so that a later change to the program's cannot move the
yardstick.  Everything here is host numpy and is seeded by the caller.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def degree_sequence(num_vertices: int, num_edges: int, alpha: float,
                    max_degree: int) -> np.ndarray:
    """Out-degrees by rank, highest first: a power law ``P(d) ~ d**-alpha``
    truncated at ``max_degree``, summing to ``num_edges`` exactly.

    Rank ``i`` takes the ``(i + 1/2) / num_vertices`` upper quantile of the
    continuous law, floored; the lower end ``d_min`` is found by bisection
    so the floors sum to at most ``num_edges``, and the few edges left over
    go one each to the lowest ranks.  No seed enters: every seed stores
    the same multiset of degrees, only on other vertices.
    """
    q = (np.arange(num_vertices) + 0.5) / num_vertices
    e = alpha - 1.0

    def degrees(d_min: float) -> np.ndarray:
        t = (d_min / max_degree) ** e
        return np.floor(d_min * (q + (1.0 - q) * t) ** (-1.0 / e)) \
            .astype(np.int64)

    lo, hi = 1.0, float(max_degree)
    if degrees(lo).sum() > num_edges or degrees(hi).sum() < num_edges:
        raise ValueError(f"{num_edges} edges do not fit {num_vertices} "
                         f"degrees in [1, {max_degree}] at alpha {alpha}")
    for _ in range(32):
        mid = (lo + hi) / 2
        lo, hi = (mid, hi) if degrees(mid).sum() <= num_edges else (lo, mid)
    deg = degrees(lo)
    deg[num_vertices - (num_edges - int(deg.sum())):] += 1
    return deg


def powerlaw_graph(num_vertices: int, num_edges: int, locality: float,
                   alpha: float, max_degree: int,
                   seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """Edge list (src, dst) with power-law out-degrees and ID locality.

    The out-degrees are :func:`degree_sequence`'s, dealt to the vertices
    by a permutation drawn from the seed, so hubs sit anywhere in the id
    space.  ``locality`` is the fraction of edges whose endpoint lies in
    a window around the source id (log-normal offsets, widened with the
    source's degree so a hub's local edges stay mostly distinct); the
    rest are uniform (long-range links).  Self-loops are dropped.
    """
    rng = np.random.default_rng(seed)
    deg = degree_sequence(num_vertices, num_edges, alpha, max_degree)
    owner = rng.permutation(num_vertices)
    src = np.repeat(owner, deg)
    spread = np.repeat(np.log(np.maximum(deg / (num_edges / num_vertices),
                                         1.0)), deg)
    local = rng.random(num_edges) < locality
    offs = np.maximum(rng.lognormal(3.0, 1.5, num_edges) * np.exp(spread),
                      1).astype(np.int64)
    sign = rng.choice(np.array([-1, 1]), num_edges)
    dst_local = (src + sign * offs) % num_vertices
    dst_rand = rng.integers(0, num_vertices, num_edges)
    dst = np.where(local, dst_local, dst_rand)
    keep = src != dst
    return src[keep].astype(np.int64), dst[keep].astype(np.int64)


def clustered_labels(num_vertices: int, names: List[str],
                     density: float = 0.3, run_scale: int = 4096,
                     seed: int = 0) -> Dict[str, np.ndarray]:
    """Boolean label columns arranged in runs (short RLE interval lists)."""
    rng = np.random.default_rng(seed)
    out: Dict[str, np.ndarray] = {}
    for k, name in enumerate(names):
        col = np.zeros(num_vertices, bool)
        pos = 0
        r = np.random.default_rng(seed * 1000003 + k)
        while pos < num_vertices:
            run = max(int(r.exponential(run_scale)), 32)
            val = r.random() < density
            col[pos:pos + run] = val
            pos += run
        out[name] = col
    return out
