"""Store kind ``powerlaw``: a SNAP-size social graph as a GraphAr store.

``make_data`` draws the graph from the seed with the benchmark's own
generator; ``build`` hands it to the program (``GraphArBuilder``,
``by_src`` adjacency, clustered RLE labels, one int64 property) and
uploads the engine's resident plan.  ``OPS`` are the requests a traffic
mix may name: each calls the program's entry and returns what a caller
gets, numpy ids.  ``shape`` says which requests the warm-up must run
so that every size the window meets is compiled.
"""
from __future__ import annotations

import types

import numpy as np
from jax.profiler import TraceAnnotation

from bench import gen


def _frozen(a: np.ndarray) -> np.ndarray:
    # the reference reads these arrays after the window: the program
    # must not have written to them
    a.flags.writeable = False
    return a


def make_data(cfg: dict, seed: int) -> types.SimpleNamespace:
    n = int(cfg["vertices"])
    src, dst = gen.powerlaw_graph(n, int(cfg["edges"]),
                                  locality=cfg["locality"],
                                  alpha=cfg["alpha"],
                                  max_degree=int(cfg["max_degree"]),
                                  seed=seed)
    labels = gen.clustered_labels(n, [f"l{i}" for i in range(cfg["labels"])],
                                  density=cfg["label_density"],
                                  run_scale=cfg["label_run_scale"],
                                  seed=seed + 1)
    # a creation-stamp-like property: rises with the id, with jitter
    rng = np.random.default_rng(seed + 2)
    ts = np.arange(n, dtype=np.int64) * 16 + rng.integers(0, 4096, n)
    offsets = np.concatenate([[0], np.cumsum(np.bincount(src, minlength=n))])
    for a in (src, dst, ts, offsets, *labels.values()):
        _frozen(a)
    return types.SimpleNamespace(n=n, src=src, dst=dst, labels=labels,
                                 ts=ts, offsets=offsets,
                                 page_size=int(cfg["page_size"]),
                                 domains={"vertices": n})


def _pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def shape(data, op: str, ids: np.ndarray) -> tuple:
    """Adjacency pages a retrieval touches and rows it reads, each rounded
    up to a power of two: the sizes a store pads its device programs to,
    so requests of one shape share a compiled program."""
    lo, hi = data.offsets[ids], data.offsets[ids + 1]
    some = hi > lo
    first = lo[some] // data.page_size
    last = (hi[some] - 1) // data.page_size
    order = np.argsort(first)
    first, reach = first[order], np.maximum.accumulate(last[order])
    # the union of the [first, last] page intervals
    new = np.flatnonzero(np.r_[True, first[1:] > reach[:-1]])
    ends = np.r_[new[1:], len(first)] - 1
    pages = int((reach[ends] - first[new] + 1).sum())
    return op, _pow2(pages), _pow2(int((hi - lo).sum()))


def build(cfg: dict, data) -> types.SimpleNamespace:
    import jax
    from repro.core import (BY_SRC, EdgeTypeSchema, GraphArBuilder,
                            PropertySchema, VertexTypeSchema, pack_column)

    ps = int(cfg["page_size"])
    b = GraphArBuilder(cfg["name"])
    b.add_vertices(VertexTypeSchema("person",
                                    [PropertySchema("ts", "int64")],
                                    labels=list(data.labels), page_size=ps),
                   {"ts": data.ts}, data.labels)
    b.add_edges(EdgeTypeSchema("person", "knows", "person",
                               adjacency=[BY_SRC], page_size=ps),
                data.src, data.dst)
    g = b.build()
    adj = g.adjacency("person-knows-person", BY_SRC)
    col = adj.table[adj.value_col].encoded
    jax.block_until_ready(pack_column(col).device_plan(cfg["engine"]))
    return types.SimpleNamespace(adj=adj, tps=g.vertex("person").page_size,
                                 engine=cfg["engine"],
                                 resident=bool(cfg["resident"]))


def retrieve(store, ids: np.ndarray) -> np.ndarray:
    from repro.core import retrieve_neighbors_batch
    with TraceAnnotation("retrieve_neighbors_batch"):
        pac = retrieve_neighbors_batch(store.adj, ids, store.tps,
                                       engine=store.engine,
                                       resident=store.resident)
    with TraceAnnotation("to_ids"):
        return pac.to_ids()


OPS = {"retrieve": retrieve}
#: host spans the ops open, for the trace's idle-gap attribution
SPANS = ("retrieve_neighbors_batch", "to_ids")
