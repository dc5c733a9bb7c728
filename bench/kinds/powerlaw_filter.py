"""Store kind ``powerlaw_filter``: the ``powerlaw`` store queried through
the label plane.

The graph and the shape of a request are ``powerlaw``'s, and so is the
store, so both kinds hold the same graph for a seed; the build here
also keeps the vertex table, which a ``LabelFilter`` reads.  The label
columns hold the same runs for every seed (:func:`placed_labels`), so
every seed's predicates evaluate run lists of one length over labels of
one density.  The configuration lists its label predicates as text
(``predicates``).  Op ``filter<i>`` is an ad-hoc caller with predicate
``i``: it builds a fresh ``LabelFilter`` over the vertex table,
retrieves the neighbors of its batch that satisfy the predicate through
the program's entry, and returns numpy ids.
"""
from __future__ import annotations

import types
from typing import Dict, List

import jax
import numpy as np

from bench import gen
from bench.kinds import powerlaw
from bench.kinds.powerlaw_filter_ref import N_PREDICATES, predicate

shape = powerlaw.shape

#: the one draw of ``gen.clustered_labels`` whose runs every seed places
RUNS_SEED = 0


def placed_labels(n: int, names: List[str], density: float,
                  run_scale: int, seed: int) -> Dict[str, np.ndarray]:
    """Label columns with the same runs for every seed, placed by it.

    The runs are those of one draw of ``gen.clustered_labels`` (seed
    ``RUNS_SEED``): a column alternates runs of set and clear bits.  The
    seed shuffles the set runs among the set slots and the clear runs
    among the clear ones, so each label keeps its run count, its run
    lengths and its density, and only where its runs lie changes, as
    the degree multiset of ``gen.degree_sequence`` is dealt to other
    vertices.
    """
    base = gen.clustered_labels(n, names, density=density,
                                run_scale=run_scale, seed=RUNS_SEED)
    out = {}
    for k, name in enumerate(names):
        col = base[name]
        starts = np.r_[0, np.flatnonzero(np.diff(col.astype(np.int8))) + 1]
        lens = np.diff(np.r_[starts, n])
        rng = np.random.default_rng([seed, k])
        lens[0::2] = rng.permutation(lens[0::2])
        lens[1::2] = rng.permutation(lens[1::2])
        values = np.zeros(len(lens), bool)
        values[0::2] = col[0]
        values[1::2] = not col[0]
        out[name] = np.repeat(values, lens)
        out[name].flags.writeable = False
    return out


def make_data(cfg: dict, seed: int) -> types.SimpleNamespace:
    """``powerlaw.make_data``, its labels replaced by
    :func:`placed_labels` drawn from the same seed."""
    data = powerlaw.make_data(cfg, seed)
    data.labels = placed_labels(data.n, list(data.labels),
                                cfg["label_density"],
                                cfg["label_run_scale"], seed + 1)
    return data


def build(cfg: dict, data) -> types.SimpleNamespace:
    """``powerlaw.build``, keeping the vertex table and the predicates."""
    from repro.core import (BY_SRC, EdgeTypeSchema, GraphArBuilder, L,
                            PropertySchema, VertexTypeSchema, pack_column)

    if len(cfg["predicates"]) != N_PREDICATES:
        raise ValueError(f"{len(cfg['predicates'])} predicates; this kind "
                         f"has an op for each of {N_PREDICATES}")
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
    vt = g.vertex("person")
    return types.SimpleNamespace(adj=adj, vt=vt, tps=vt.page_size,
                                 engine=cfg["engine"],
                                 resident=bool(cfg["resident"]),
                                 conds=[predicate(text, L)
                                        for text in cfg["predicates"]])


def _filtered(i: int):
    def op(store, ids):
        from repro.core import LabelFilter, retrieve_neighbors_batch
        pac = retrieve_neighbors_batch(
            store.adj, ids, store.tps, engine=store.engine,
            resident=store.resident,
            filter=LabelFilter(store.vt, store.conds[i]))
        return pac.to_ids()
    return op


OPS = {f"filter{i}": _filtered(i) for i in range(N_PREDICATES)}


def _program_spans() -> tuple:
    from repro import obs
    return getattr(obs, "ALL_SPANS", obs.SPANS)


#: host spans for the trace's idle-gap attribution: the program's own
#: (``graphar.*``), the label plane's among them where the program has
#: it
SPANS = _program_spans()
