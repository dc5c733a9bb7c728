"""Pipelined serving plane: overlap is free, semantics are identical.

The contract under test: with ``pipeline=True`` the engine issues tick
t+1's batched retrieval while tick t's decode is in flight, yet ids,
tokens, and IOMeter accounting stay **bit-identical** to the sequential
engine -- across engines, across partition counts, and across
mis-speculations (which restore the retrieval plane's snapshot and replay
the synchronous path).
"""
import numpy as np
import pytest

from _engines import engines
from repro.core import (BY_SRC, EdgeTypeSchema, GraphArBuilder, IOMeter,
                        PropertySchema, VertexTypeSchema)
from repro.data.synthetic import document_graph
from repro.serve.engine import Request, ServeEngine
from repro.serve.retrieval import GraphRetriever

MAX_LEN = 96


@pytest.fixture(scope="module")
def engine_parts():
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("smollm-360m").reduced().with_(n_units=2)
    model = build_model(cfg)
    return cfg, model, model.init(0)


def _fresh_lake(num_docs=200, seed=5):
    """A fresh graph per engine instance: the decoded-page LRU attaches
    to the adjacency column, so paired sequential/pipelined runs must not
    share one."""
    lake = document_graph(num_docs=num_docs, vocab=512, mean_len=32,
                          seed=seed)
    b = GraphArBuilder("docs")
    b.add_vertices(
        VertexTypeSchema("doc", [PropertySchema("tokens", "tokens")],
                         labels=list(lake.labels), page_size=128),
        {"tokens": lake.tokens}, lake.labels)
    b.add_edges(EdgeTypeSchema("doc", "links", "doc", page_size=128),
                lake.links_src, lake.links_dst)
    g = b.build()
    return g.adjacency("doc-links-doc", BY_SRC), \
        g.vertex("doc").table["tokens"]


def _retriever(engine, partitions, meter):
    adj, tok = _fresh_lake()
    return GraphRetriever(adj, tok, max_neighbors=2, tokens_per_neighbor=8,
                          meter=meter, engine=engine, page_cache_pages=64,
                          partitions=partitions)


def _requests(cfg, adj, n, mnt=3, seed=0):
    rng = np.random.default_rng(seed)
    seeds = np.flatnonzero(adj.degrees() > 0)
    vs = seeds[rng.integers(0, len(seeds), n)]
    return [Request(i, rng.integers(4, cfg.vocab_size, size=6)
                    .astype(np.int32), max_new_tokens=mnt,
                    context_vertex=int(v))
            for i, v in enumerate(vs)]


def _run(model, params, cfg, engine, partitions, pipeline, n=10):
    meter = IOMeter()
    retr = _retriever(engine, partitions, meter)
    eng = ServeEngine(model, params, max_slots=3, max_len=MAX_LEN,
                      eos_id=-1, context_fn=retr, pipeline=pipeline)
    for r in _requests(cfg, retr.adj, n):
        eng.submit(r)
    finished = eng.run_until_drained()
    return eng, retr, meter, finished


def _assert_identical(fin_a, fin_b, m_a, m_b, r_a, r_b):
    assert [r.request_id for r in fin_a] == [r.request_id for r in fin_b]
    for a, b in zip(fin_a, fin_b):
        np.testing.assert_array_equal(a.prompt, b.prompt)
        assert a.output == b.output
        assert a.context_tokens == b.context_tokens
    assert (m_a.nbytes, m_a.nrequests) == (m_b.nbytes, m_b.nrequests)
    assert r_a.calls == r_b.calls
    assert r_a.vertices_seen == r_b.vertices_seen
    ca, cb = r_a.page_cache, r_b.page_cache
    assert (ca.hits, ca.misses) == (cb.hits, cb.misses)


# --------------------- pipelined == sequential oracle ---------------------

@pytest.mark.parametrize("partitions", [1, 2, 8])
@pytest.mark.parametrize("engine", engines())
def test_pipelined_bit_identical_to_sequential(engine_parts, engine,
                                               partitions):
    cfg, model, params = engine_parts
    eng_s, retr_s, m_s, fin_s = _run(model, params, cfg, engine,
                                     partitions, pipeline=False)
    eng_p, retr_p, m_p, fin_p = _run(model, params, cfg, engine,
                                     partitions, pipeline=True)
    assert len(fin_s) == len(fin_p) == 10
    _assert_identical(fin_s, fin_p, m_s, m_p, retr_s, retr_p)
    # the pipeline actually pipelined: speculative retrievals were
    # consumed by the predicted admissions, not just rolled back
    pstats = eng_p.stats()["pipeline"]
    assert pstats["enabled"] and pstats["prefetch_hits"] > 0
    assert pstats["prefetch_issued"] == \
        pstats["prefetch_hits"] + pstats["mis_speculations"]
    sstats = eng_s.stats()["pipeline"]
    assert not sstats["enabled"] and sstats["prefetch_issued"] == 0


# ------------------------- mis-speculation paths --------------------------

def test_mis_speculation_on_graph_mutation(engine_parts):
    """An ingest between prefetch and consumption moves the mutation
    epoch: the engine must restore and fall back synchronously, landing
    bit-identical to a sequential run with the same interleaving."""
    cfg, model, params = engine_parts

    def run(pipeline):
        meter = IOMeter()
        retr = _retriever("numpy", None, meter)
        eng = ServeEngine(model, params, max_slots=1, max_len=MAX_LEN,
                          eos_id=-1, context_fn=retr, pipeline=pipeline)
        for r in _requests(cfg, retr.adj, 2, mnt=2):
            eng.submit(r)
        eng.step()                       # prefetch for req 1 issued here
        eng.ingest([0], [1])             # epoch moves under the prefetch
        eng.run_until_drained()
        return eng, retr, meter, eng.finished

    eng_s, retr_s, m_s, fin_s = run(False)
    eng_p, retr_p, m_p, fin_p = run(True)
    assert len(fin_p) == 2
    _assert_identical(fin_s, fin_p, m_s, m_p, retr_s, retr_p)
    p = eng_p.stats()["pipeline"]
    assert p["mis_speculations"] >= 1


def test_mis_speculation_on_queue_change(engine_parts):
    """A cancelled/replaced queue entry invalidates the predicted batch:
    the actual admission differs from the prefetched one, so the engine
    rolls back and retrieves synchronously for the real batch."""
    cfg, model, params = engine_parts

    def run(pipeline):
        meter = IOMeter()
        retr = _retriever("numpy", None, meter)
        eng = ServeEngine(model, params, max_slots=1, max_len=MAX_LEN,
                          eos_id=-1, context_fn=retr, pipeline=pipeline)
        reqs = _requests(cfg, retr.adj, 3, mnt=2)
        eng.submit(reqs[0])
        eng.submit(reqs[1])
        eng.step()                       # prefetch speculated for reqs[1]
        eng.queue.clear()                # reqs[1] cancelled...
        eng.submit(reqs[2])              # ...a different request replaces it
        eng.run_until_drained()
        return eng, retr, meter, eng.finished

    eng_s, retr_s, m_s, fin_s = run(False)
    eng_p, retr_p, m_p, fin_p = run(True)
    assert [r.request_id for r in fin_p] == [0, 2]
    _assert_identical(fin_s, fin_p, m_s, m_p, retr_s, retr_p)
    p = eng_p.stats()["pipeline"]
    assert p["mis_speculations"] >= 1


def test_prefetch_skipped_without_snapshot_support(engine_parts):
    """A context_fn without snapshot/restore cannot be rolled back, so
    the engine must never speculate against it."""
    cfg, model, params = engine_parts
    calls = []

    def ctx(vs):
        calls.append(np.asarray(vs).copy())
        return [np.zeros(0, np.int32)] * len(vs)

    eng = ServeEngine(model, params, max_slots=2, max_len=MAX_LEN,
                      eos_id=-1, context_fn=ctx, pipeline=True)
    for r in _requests(cfg, _fresh_lake()[0], 4, mnt=2):
        eng.submit(r)
    finished = eng.run_until_drained()
    assert len(finished) == 4
    p = eng.stats()["pipeline"]
    assert p["prefetch_issued"] == 0 and p["mis_speculations"] == 0
    assert len(calls) == 2               # one synchronous batch per admit


# ------------- double buffering + steady state without retraces -----------

def test_steady_state_double_buffered_no_retraces(engine_parts):
    """~100 warm ticks of pipelined serving: the dispatch plane must
    reuse exactly two staged output buffers per (engine, class) -- never
    a single aliased one -- and kernel trace counts must stay flat (no
    retrace per tick)."""
    from repro.kernels._pad import reset_trace_counts, trace_count
    from repro.kernels.pac_decode import ops as pac_ops
    cfg, model, params = engine_parts
    retr = _retriever("jax", None, IOMeter())
    eng = ServeEngine(model, params, max_slots=2, max_len=MAX_LEN,
                      eos_id=-1, context_fn=retr, pipeline=True)
    # one shared seed vertex -> constant prompt length; slots retire and
    # refill every other tick, so retrieval stays on the hot path
    v = int(np.flatnonzero(retr.adj.degrees() > 0)[0])
    rng = np.random.default_rng(1)
    reqs = [Request(i, rng.integers(4, cfg.vocab_size, size=6)
                    .astype(np.int32), max_new_tokens=3, context_vertex=v)
            for i in range(110)]
    for r in reqs[:10]:
        eng.submit(r)
    pac_ops.reset_dispatch_pools()
    reset_trace_counts()
    for _ in range(8):                   # warmup: traces + pool fill
        eng.step()
    warm = trace_count()
    ticks = 0
    for r in reqs[10:]:
        eng.submit(r)
    while (eng.queue or any(s is not None for s in eng.slots)) \
            and ticks < 200:
        eng.step()
        ticks += 1
    assert ticks >= 90
    assert trace_count() == warm         # zero retraces in steady state
    assert len(eng.finished) == len(reqs)
    assert eng.stats()["pipeline"]["prefetch_hits"] > 0


def test_words_pool_double_buffered_non_aliasing():
    """The fused dispatch's bitmap output ring must hold TWO distinct
    device buffers: donating the most recent output back into the next
    dispatch would alias a buffer the pipelined engine may still be
    consuming.  Steady state alternates between exactly two buffers per
    (engine, n_words) class, results staying bit-identical."""
    from repro.core import retrieve_neighbors_batch
    from repro.kernels.pac_decode import ops as pac_ops
    adj, _ = _fresh_lake()
    vs = np.flatnonzero(adj.degrees() > 0)[:16]
    want = retrieve_neighbors_batch(adj, vs, 128, engine="numpy").to_ids()
    pac_ops.reset_dispatch_pools()
    for _ in range(5):
        got = retrieve_neighbors_batch(adj, vs, 128, engine="jax")
        np.testing.assert_array_equal(got.to_ids(), want)
    rings = [r for r in pac_ops._WORDS_POOL.values() if len(r)]
    assert rings
    for ring in rings:
        assert len(ring) == 2            # steady state: exactly 2 planes
        a, b = ring
        assert a is not b
        assert a.unsafe_buffer_pointer() != b.unsafe_buffer_pointer()


# --------------------- admission clamping regression ----------------------

def test_admission_clamps_prompt_and_max_new_tokens(engine_parts):
    """A prompt at/over max_len used to overflow the slot's cache rows
    (silently dropped writes); admission now clamps the prompt to
    max_len - 2 and max_new_tokens to the remaining rows."""
    cfg, model, params = engine_parts
    max_len = 24
    eng = ServeEngine(model, params, max_slots=1, max_len=max_len,
                      eos_id=-1)
    rng = np.random.default_rng(3)
    req = Request(0, rng.integers(4, cfg.vocab_size, size=max_len + 5)
                  .astype(np.int32), max_new_tokens=10_000)
    eng.submit(req)
    finished = eng.run_until_drained()
    assert len(finished) == 1 and finished[0].done
    assert len(req.prompt) == max_len - 2
    assert req.max_new_tokens == max_len - 1 - len(req.prompt)
    assert len(req.output) <= req.max_new_tokens
    assert len(req.prompt) + len(req.output) <= max_len


def test_context_budget_respects_clamped_tokens(engine_parts):
    """Context attachment happens after clamping, so the context budget
    is computed from the clamped prompt/max_new_tokens pair and the slot
    still fits."""
    cfg, model, params = engine_parts
    max_len = 32
    retr = _retriever("numpy", None, None)
    eng = ServeEngine(model, params, max_slots=1, max_len=max_len,
                      eos_id=-1, context_fn=retr)
    v = int(np.flatnonzero(retr.adj.degrees() > 0)[0])
    rng = np.random.default_rng(4)
    req = Request(0, rng.integers(4, cfg.vocab_size, size=max_len * 2)
                  .astype(np.int32), max_new_tokens=99, context_vertex=v)
    eng.submit(req)
    finished = eng.run_until_drained()
    assert len(finished) == 1 and finished[0].done
    assert len(req.prompt) + len(req.output) <= max_len


# ------------------------------ env default -------------------------------

def test_pipeline_env_default(engine_parts, monkeypatch):
    cfg, model, params = engine_parts

    def mk(**kw):
        return ServeEngine(model, params, max_slots=1, max_len=16, **kw)

    monkeypatch.delenv("REPRO_PIPELINE", raising=False)
    assert mk().pipeline is True
    monkeypatch.setenv("REPRO_PIPELINE", "0")
    assert mk().pipeline is False
    assert mk(pipeline=True).pipeline is True      # explicit arg wins
    monkeypatch.setenv("REPRO_PIPELINE", "off")
    assert mk().pipeline is False
    monkeypatch.setenv("REPRO_PIPELINE", "1")
    assert mk().pipeline is True
    assert mk(pipeline=False).pipeline is False
    s = mk().stats()["pipeline"]
    for k in ("enabled", "prefetch_issued", "prefetch_hits",
              "mis_speculations", "pipeline_overlap_ms", "last_tick",
              "totals"):
        assert k in s


def test_serves_bfloat16_compute_config():
    """Published configs compute in bfloat16: the engine's KV cache must
    follow the compute dtype, or the attention output promotes the
    layer scan's carry to float32 and prefill fails to trace."""
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("smollm-360m").reduced().with_(
        n_units=2, param_dtype="bfloat16", compute_dtype="bfloat16")
    model = build_model(cfg)
    eng = ServeEngine(model, model.init(0), max_slots=2, max_len=32,
                      eos_id=-1)
    eng.submit(Request(0, np.arange(5, 15, dtype=np.int32),
                       max_new_tokens=4))
    (req,) = eng.run_until_drained(max_ticks=50)
    assert len(req.output) == 4
    assert all(0 <= t < cfg.vocab_size for t in req.output)
