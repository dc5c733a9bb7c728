"""Chip smoke test: drive the store's main path once on a TPU.

Builds a SNAP ``soc-LiveJournal1``-sized power-law graph from ``--seed``
through the normal ``GraphArBuilder`` (``by_src`` adjacency, 8 clustered
RLE labels, one int64 property), then runs, in one process and with an
explicit ``engine=`` on every call:

* neighbor retrieval on the Pallas engine (resident, fused where the
  batch is large enough) and on the jnp engine, against the numpy oracle;
* filtered retrieval with a label and a numeric predicate;
* a fused k-hop traversal at 2 and 3 hops, against the host loop;
* the LDBC-style queries IS-3, IC-8 and BI-2, against their acero twins;
* serving: ``ServeEngine`` + ``GraphRetriever`` with ``smollm-360m`` at
  its published widths (random weights from the seed), 8 requests.

Each phase prints one line with its sizes, times and counters, and
asserts that its device route was really taken (kernel traces, no host
fallbacks).  A phase whose Pallas entries do not lower for the TPU runs
the jnp engine (XLA on the chip) and says so.  ``--chips 4`` runs only
the partition plane across four chips: a 4-partition column, sharded
fused retrieval and sharded k-hop, against one device and numpy.

The last line printed is ``{"ok": true, "device": {...}}``.  Without a
TPU the script exits non-zero before running anything.

Run from the repository root:  python3 chip_smoke.py [--seed N] [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: SNAP soc-LiveJournal1 (https://snap.stanford.edu/data/soc-LiveJournal1.html)
LJ_VERTICES = 4_847_571
LJ_EDGES = 68_993_773
EDGES = LJ_EDGES
N_LABELS = 8
BATCHES = (1, 64, 1024)
FILTER_BATCH = 1024
KHOP_SEEDS = 64
LDBC_SCALE = 10          # ldbc_like: 100k persons, 800k messages
SERVE_ARCH = "smollm-360m"
SERVE_REQUESTS = 8
SERVE_DOCS = 50_000
#: the traversal Pallas entries raise KernelNotLowered on the TPU
#: (in-kernel gathers / scatter-add), so traversal -- and IC-8 / BI-2,
#: which expand through it -- run on the jnp engine, XLA on the chip
TRAVERSAL_ENGINE = "jax"


def report(name: str, **fields) -> None:
    parts = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{name}] {parts}", flush=True)


def _secs(t0: float) -> str:
    return f"{time.perf_counter() - t0:.3f}s"


def device_check(chips: int) -> dict:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX platform {dev.platform!r}); "
                 f"nothing was run")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: {chips} chips asked for, JAX sees "
                 f"{len(devices)}")
    report("device", platform=dev.platform, kind=repr(dev.device_kind),
           count=len(devices))
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def build_graph(n: int, m: int, seed: int):
    """The LiveJournal-sized graph; returns (graph, adjacency, persons)."""
    import jax
    from repro.core import (BY_SRC, EdgeTypeSchema, GraphArBuilder,
                            PropertySchema, VertexTypeSchema, pack_column)
    from repro.data.synthetic import clustered_labels, powerlaw_graph

    t0 = time.perf_counter()
    src, dst = powerlaw_graph(n, m / n, seed=seed)
    labels = clustered_labels(n, [f"l{i}" for i in range(N_LABELS)],
                              density=0.3, run_scale=4096, seed=seed + 1)
    # a creation-stamp-like int64 property: rises with the id, with
    # jitter, so numeric predicates prune pages and partitions
    rng = np.random.default_rng(seed + 2)
    ts = np.arange(n, dtype=np.int64) * 16 + rng.integers(0, 4096, n)
    b = GraphArBuilder("soc-livejournal1")
    b.add_vertices(VertexTypeSchema("person", [PropertySchema("ts", "int64")],
                                    labels=list(labels)), {"ts": ts}, labels)
    b.add_edges(EdgeTypeSchema("person", "knows", "person",
                               adjacency=[BY_SRC]), src, dst)
    g = b.build()
    adj = g.adjacency("person-knows-person", BY_SRC)
    host_s = time.perf_counter() - t0
    col = adj.table[adj.value_col].encoded
    t1 = time.perf_counter()
    packed = pack_column(col)
    plans = [packed.device_plan(e) for e in ("pallas", "jax")]
    jax.block_until_ready(plans)
    report("build", vertices=n, edges=len(src), pages=len(col.pages),
           page_size=col.page_size, labels=N_LABELS, host_build=f"{host_s:.3f}s",
           plan_upload=_secs(t1),
           plan_device_bytes_per_engine=sum(a.nbytes for a in plans[0]),
           resident_engines=len(plans))
    return g, adj, g.vertex("person")


def _traced(before: dict) -> list:
    from repro.kernels._pad import trace_counts
    return sorted(k for k, v in trace_counts().items()
                  if v > before.get(k, 0))


def _packed(adj):
    return adj.table[adj.value_col].encoded.packed_cache


def retrieval_phase(adj, vt, n: int, seed: int) -> None:
    from repro.core import retrieve_neighbors_batch
    from repro.kernels._pad import trace_counts

    tps = vt.page_size
    rng = np.random.default_rng(seed + 3)
    for batch in BATCHES:
        vs = rng.integers(0, n, batch)
        t0 = time.perf_counter()
        want = retrieve_neighbors_batch(adj, vs, tps,
                                        engine="numpy").to_ids()
        numpy_s = _secs(t0)
        before = trace_counts()
        t0 = time.perf_counter()
        cold = retrieve_neighbors_batch(adj, vs, tps, engine="pallas",
                                        resident=True)
        cold_s = _secs(t0)
        route = _traced(before)
        t0 = time.perf_counter()
        warm = retrieve_neighbors_batch(adj, vs, tps, engine="pallas",
                                        resident=True)
        warm_s = _secs(t0)
        t0 = time.perf_counter()
        jx = retrieve_neighbors_batch(adj, vs, tps, engine="jax",
                                      resident=True)
        jax_s = _secs(t0)
        for got in (cold, warm, jx):
            np.testing.assert_array_equal(got.to_ids(), want)
        assert route, f"batch {batch}: no Pallas entry traced"
        report("retrieve", batch=batch, neighbors=want.size,
               route="+".join(route), pallas_cold=cold_s, pallas_warm=warm_s,
               jax=jax_s, numpy=numpy_s)
    counts = trace_counts()
    assert counts.get("fused_gather_decode_bitmap_batch", 0) > 0
    assert counts.get("gather_decode", 0) > 0
    packed = _packed(adj)
    assert packed.fallbacks == 0, packed.device_stats()
    report("retrieve-counters", traces=counts.get(
        "fused_gather_decode_bitmap_batch", 0), fallbacks=packed.fallbacks,
        transfers=packed.device_transfers)


def filtered_phase(adj, vt, n: int, seed: int) -> None:
    from repro.core import (L, LabelFilter, NumericFilter, NumProp,
                            retrieve_neighbors_batch)
    from repro.kernels._pad import trace_counts

    tps = vt.page_size
    vs = np.random.default_rng(seed + 4).integers(0, n, FILTER_BATCH)
    # ``ts`` rises ~16 per id: about the lower half of the ids qualify
    conds = {"label": (LabelFilter, (L("l0") & ~L("l1")) | L("l2")),
             "numeric": (NumericFilter, NumProp("ts") < n * 8)}
    for kind, (cls, cond) in conds.items():
        want = retrieve_neighbors_batch(adj, vs, tps, engine="numpy",
                                        filter=cls(vt, cond)).to_ids()
        before = trace_counts()
        t0 = time.perf_counter()
        got = retrieve_neighbors_batch(adj, vs, tps, engine="pallas",
                                       filter=cls(vt, cond), resident=True)
        cold_s = _secs(t0)
        route = _traced(before)
        t0 = time.perf_counter()
        again = retrieve_neighbors_batch(adj, vs, tps, engine="pallas",
                                         filter=cls(vt, cond),
                                         resident=True)
        warm_s = _secs(t0)
        for out in (got, again):
            np.testing.assert_array_equal(out.to_ids(), want)
        report("filter", kind=kind, batch=FILTER_BATCH, kept=want.size,
               traced="+".join(route) or "none-new", pallas_cold=cold_s,
               pallas_warm=warm_s)
    counts = trace_counts()
    assert counts.get("fused_gather_decode_filter_bitmap_batch", 0) > 0
    assert counts.get("cond_bitmap_pallas", 0) > 0
    assert _packed(adj).fallbacks == 0


def traversal_phase(adj, n: int, seed: int) -> None:
    from repro.core import k_hop
    from repro.kernels.traversal.ops import traversal_stats

    seeds = np.random.default_rng(seed + 5).integers(0, n, KHOP_SEEDS)
    for hops in (2, 3):
        t0 = time.perf_counter()
        want = k_hop(adj, seeds, hops, engine="numpy", fused=False)
        host_s = _secs(t0)
        t0 = time.perf_counter()
        got = k_hop(adj, seeds, hops, engine=TRAVERSAL_ENGINE, fused=True)
        dev_s = _secs(t0)
        np.testing.assert_array_equal(got, want)
        report("k_hop", hops=hops, seeds=KHOP_SEEDS, reached=want.size,
               engine=TRAVERSAL_ENGINE, note="pallas-traversal-not-lowered",
               device=dev_s, host_loop=host_s)
    stats = traversal_stats(adj)
    assert stats["fallbacks"] == 0 and stats["hops_fused"] > 0, stats
    report("k_hop-counters", dispatches=stats["dispatches"],
           hops_fused=stats["hops_fused"], fallbacks=stats["fallbacks"],
           roundtrips=stats["traversal_device_roundtrips"])


def query_phase(seed: int, scale: int) -> None:
    from repro.core.query import (bi2_acero, bi2_graphar,
                                  build_snb_baseline, build_snb_graphar,
                                  ic8_acero, ic8_graphar, is3_acero,
                                  is3_graphar)
    from repro.data.synthetic import ldbc_like
    from repro.kernels._pad import trace_counts

    t0 = time.perf_counter()
    snb = ldbc_like(scale=scale, seed=seed)
    g = build_snb_graphar(snb)
    base = build_snb_baseline(snb)
    person = int(np.argmax(np.bincount(snb.knows_src,
                                       minlength=snb.num_persons)))
    report("ldbc-build", scale=scale, persons=snb.num_persons,
           knows=len(snb.knows_src), time=_secs(t0))
    before = trace_counts()
    t0 = time.perf_counter()
    friends, _ = is3_graphar(g, person, engine="pallas")
    is3_s = _secs(t0)
    assert set(friends) == set(is3_acero(base, person)[0])
    t0 = time.perf_counter()
    replies, _ = ic8_graphar(g, person, engine=TRAVERSAL_ENGINE)
    ic8_s = _secs(t0)
    np.testing.assert_array_equal(replies, ic8_acero(base, person)[0])
    t0 = time.perf_counter()
    counts = bi2_graphar(g, "TagClass1", engine=TRAVERSAL_ENGINE)
    bi2_s = _secs(t0)
    assert counts == bi2_acero(base, "TagClass1")
    # IS-3 decoded on the Pallas engine; IC-8 / BI-2 took their fused
    # device expansions (two-hop, counting hop), not the host loops
    traced = _traced(before)
    assert {"gather_decode", "twohop_ref", "counthop_ref"} <= set(traced), \
        traced
    report("queries", person=person, is3_friends=len(friends),
           is3_engine="pallas", is3=is3_s, ic8_replies=len(replies),
           ic8_engine=TRAVERSAL_ENGINE, ic8=ic8_s, bi2_tags=len(counts),
           bi2_engine=TRAVERSAL_ENGINE, bi2=bi2_s)


def serving_phase(seed: int, docs: int) -> None:
    from repro.configs import get_config
    from repro.core import (BY_SRC, EdgeTypeSchema, GraphArBuilder, L,
                            PropertySchema, VertexTypeSchema)
    from repro.data.synthetic import document_graph
    from repro.kernels._pad import trace_counts
    from repro.models import build_model, param_count
    from repro.serve.engine import Request, ServeEngine
    from repro.serve.retrieval import GraphRetriever

    cfg = get_config(SERVE_ARCH)
    t0 = time.perf_counter()
    lake = document_graph(num_docs=docs, vocab=cfg.vocab_size, mean_len=48,
                          seed=seed)
    b = GraphArBuilder("passages")
    b.add_vertices(VertexTypeSchema("doc", [PropertySchema("tokens",
                                                           "tokens")],
                                    labels=list(lake.labels)),
                   {"tokens": lake.tokens}, lake.labels)
    b.add_edges(EdgeTypeSchema("doc", "links", "doc"), lake.links_src,
                lake.links_dst)
    lake_g = b.build()
    adj = lake_g.adjacency("doc-links-doc", BY_SRC)
    tokens = lake_g.vertex("doc").table["tokens"]
    cond = ~L("Spam")

    def retriever(engine):
        return GraphRetriever(adj, tokens, max_neighbors=4,
                              tokens_per_neighbor=16, engine=engine,
                              filter_vt=lake_g.vertex("doc"),
                              filter_cond=cond)

    model = build_model(cfg)
    params = model.init(seed)
    setup_s = _secs(t0)
    rng = np.random.default_rng(seed + 6)
    seeds = rng.integers(0, docs, SERVE_REQUESTS)
    reqs = [Request(i, tokens.get(int(d))[:24].astype(np.int32),
                    max_new_tokens=8, context_vertex=int(d))
            for i, d in enumerate(seeds)]
    device_retriever = retriever("pallas")
    eng = ServeEngine(model, params, max_slots=SERVE_REQUESTS, max_len=256,
                      eos_id=-1, seed=seed, context_fn=device_retriever)
    before = trace_counts()
    t0 = time.perf_counter()
    for r in reqs:
        assert eng.submit(r).admitted
    finished = eng.run_until_drained(max_ticks=200)
    serve_s = _secs(t0)
    assert len(finished) == SERVE_REQUESTS
    assert sum(r.context_tokens for r in finished) > 0
    for r in finished:
        assert len(r.output) == r.max_new_tokens, r
        assert all(0 <= t < cfg.vocab_size for t in r.output), r
    # the contexts the device engine retrieved equal the host oracle's
    oracle = retriever("numpy")(seeds)
    for got, want in zip(device_retriever(seeds), oracle):
        np.testing.assert_array_equal(got, want)
    traced = _traced(before)
    assert "gather_decode" in traced and "cond_bitmap_pallas" in traced, \
        traced
    assert _packed(adj).fallbacks == 0
    report("serve", arch=cfg.name, params=param_count(params),
           docs=docs, requests=len(finished), ticks=eng.steps,
           context_tokens=sum(r.context_tokens for r in finished),
           retrieval_engine="pallas", retrieval_calls=device_retriever.calls,
           setup=setup_s, serve=serve_s)


def sharded_phase(adj, vt, n: int, seed: int, chips: int) -> None:
    """The partition plane across ``chips`` devices, against one device
    and numpy (routing forced past the adaptive SPMD threshold)."""
    from repro.core import k_hop, partition_column, retrieve_neighbors_batch
    from repro.kernels.pac_decode import ops as pac_ops
    from repro.kernels.traversal.ops import traversal_stats

    col = adj.table[adj.value_col].encoded
    parts = partition_column(col, chips)
    tps = vt.page_size
    rng = np.random.default_rng(seed + 7)
    vs = rng.integers(0, n, 1024)
    seeds = rng.integers(0, n, KHOP_SEEDS)
    want = retrieve_neighbors_batch(adj, vs, tps, engine="numpy").to_ids()
    want_k = k_hop(adj, seeds, 2, engine="numpy", fused=False)
    times = {}
    for mode, threshold in (("sharded", 0), ("one_device", 1 << 30)):
        pac_ops.SHARD_MIN_PAGES = threshold
        for _ in range(2):   # cold (compile), then warm
            t0 = time.perf_counter()
            got = retrieve_neighbors_batch(adj, vs, tps, engine="pallas",
                                           resident=True, fused=True)
            times[f"{mode}_retrieve"] = _secs(t0)
            np.testing.assert_array_equal(got.to_ids(), want)
            t0 = time.perf_counter()
            got_k = k_hop(adj, seeds, 2, engine=TRAVERSAL_ENGINE, fused=True)
            times[f"{mode}_k_hop"] = _secs(t0)
            np.testing.assert_array_equal(got_k, want_k)
        if mode == "sharded":
            devices = parts.stats()["devices"]
            assert len(devices) == chips, devices
    stats = traversal_stats(adj)
    assert stats["fallbacks"] == 0 and stats["hops_fused"] > 0, stats
    assert _packed(adj).fallbacks == 0
    report("sharded", partitions=parts.n_parts, devices=len(devices),
           batch=len(vs), neighbors=want.size, k_hop_reached=want_k.size,
           k_hop_engine=TRAVERSAL_ENGINE, dispatches=parts.stats()[
               "dispatches"], **times)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the partition plane across 4 chips")
    args = ap.parse_args(argv)
    device = device_check(args.chips)

    from repro.launch.compile_cache import use_compile_cache
    report("compile-cache", dir=use_compile_cache())
    if EDGES != LJ_EDGES:
        report("cut", edges=EDGES, of=LJ_EDGES, vertices=LJ_VERTICES,
               reason="host build time inside the run's limit")
    t0 = time.perf_counter()
    g, adj, vt = build_graph(LJ_VERTICES, EDGES, args.seed)
    if args.chips > 1:
        sharded_phase(adj, vt, LJ_VERTICES, args.seed, args.chips)
    else:
        retrieval_phase(adj, vt, LJ_VERTICES, args.seed)
        filtered_phase(adj, vt, LJ_VERTICES, args.seed)
        traversal_phase(adj, LJ_VERTICES, args.seed)
        query_phase(args.seed, LDBC_SCALE)
        serving_phase(args.seed, SERVE_DOCS)
    report("total", time=_secs(t0))
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
