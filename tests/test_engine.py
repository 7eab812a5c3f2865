"""Unified query engine tests: differential matrix (modes × backends ×
growth policies), incremental device-image refresh (immediate access on the
device path without collate()), planner routing, shard fan-out, serving."""

import numpy as np
import pytest

from repro.core import query as Q
from repro.core.sharded_index import ShardedEngine
from repro.engine import Engine, PlannerConfig, Query, UnsupportedQueryError
from repro.engine.types import TermStats
from repro.serve import QueryService


@pytest.fixture(scope="module")
def small_docs():
    rng = np.random.default_rng(42)
    vocab = [f"t{i}" for i in range(120)]
    probs = 1.0 / np.arange(1, 121) ** 1.05
    probs /= probs.sum()
    docs = [[vocab[i] for i in rng.choice(120, size=rng.integers(5, 45),
                                          p=probs)]
            for _ in range(260)]
    return vocab, docs


@pytest.fixture(scope="module")
def engine_const(small_docs):
    """Const-mode engine frozen mid-stream: 180 docs collated, 80 in the
    delta — every device query below must see both halves."""
    vocab, docs = small_docs
    eng = Engine(B=64, growth="const")
    for d in docs[:180]:
        eng.add_document(d)
    eng.collate_now()
    for d in docs[180:]:
        eng.add_document(d)
    return vocab, eng


def _host_expected(eng, query):
    if query.mode == "conjunctive":
        return Q.brute_conjunctive(eng.index, query.terms), None
    if query.mode == "ranked_tfidf":
        return Q.ranked_disjunctive_taat(eng.index, list(query.terms),
                                         k=query.k)
    return Q.ranked_bm25(eng.index, list(query.terms), eng.doclens_array(),
                         k=query.k)


# --------------------------------------------------------------------------
# differential matrix: every backend must agree with the host oracle
# --------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["host", "device", "pallas"])
@pytest.mark.parametrize("mode", ["conjunctive", "ranked_tfidf", "bm25"])
def test_backend_matrix_const(engine_const, backend, mode):
    vocab, eng = engine_const
    rng = np.random.default_rng(hash((backend, mode)) % 2**32)
    for _ in range(6):
        nt = int(rng.integers(1, 4))
        terms = tuple(vocab[i] for i in
                      rng.choice(60, size=nt, replace=False))
        res = eng.execute(Query(terms=terms, mode=mode, k=10,
                                backend=backend))
        exp_d, exp_s = _host_expected(eng, Query(terms=terms, mode=mode,
                                                 k=10))
        assert res.backend == backend
        if mode == "conjunctive":
            assert res.docids.tolist() == exp_d.tolist()
        else:
            assert len(res.scores) == len(exp_s)
            assert np.allclose(np.sort(res.scores), np.sort(exp_s),
                               rtol=1e-5)


@pytest.mark.parametrize("growth", ["triangle", "expon"])
def test_variable_growth_host_routing(small_docs, growth):
    """Non-Const layouts execute on the host backend (planner fallback) and
    still answer every mode correctly."""
    vocab, docs = small_docs
    eng = Engine(B=64, growth=growth)
    for d in docs[:120]:
        eng.add_document(d)
    res = eng.execute(Query(terms=(vocab[1], vocab[4]), mode="conjunctive"))
    assert res.backend == "host"
    exp = Q.brute_conjunctive(eng.index, [vocab[1], vocab[4]])
    assert res.docids.tolist() == exp.tolist()
    d, s = Q.ranked_disjunctive_taat(eng.index, [vocab[2]], k=5)
    r2 = eng.execute(Query(terms=(vocab[2],), mode="ranked_tfidf", k=5))
    assert np.allclose(np.sort(r2.scores), np.sort(s), rtol=1e-6)
    with pytest.raises(ValueError):
        eng.execute(Query(terms=(vocab[0],), backend="device"))
    # Pallas decodes postings host-side, so variable-block layouts work
    r3 = eng.execute(Query(terms=(vocab[1], vocab[4]), mode="conjunctive",
                           backend="pallas"))
    assert r3.docids.tolist() == exp.tolist()


# --------------------------------------------------------------------------
# incremental device-image refresh (the immediate-access TPU path)
# --------------------------------------------------------------------------


def test_device_answers_post_freeze_docs_without_collate(engine_const):
    vocab, eng = engine_const
    assert eng.stats().collations == 1  # the fixture's single freeze
    # docs 181..260 exist only in the delta; conjunctive must return them
    res = eng.execute(Query(terms=(vocab[0],), mode="conjunctive",
                            backend="device"))
    assert res.docids.max() > 180, "device path missed post-freeze documents"
    assert eng.stats().collations == 1, "device query triggered a collation"
    assert eng.stats().delta_refreshes >= 1


def test_k_below_one_rejected():
    """k=0 slices diverge across backends — Query must reject it."""
    with pytest.raises(ValueError):
        Query(terms=("a",), mode="ranked_tfidf", k=0)
    with pytest.raises(ValueError):
        Query(terms=("a",), mode="bm25", k=-3)


def test_device_large_k_clamped(engine_const):
    """k beyond the accumulator width must clamp, not crash top_k
    (both the dense ranked path and the sort-based bm25 path)."""
    vocab, eng = engine_const
    for mode in ("ranked_tfidf", "bm25"):
        r = eng.execute(Query(terms=(vocab[0], vocab[2]), mode=mode,
                              k=5000, backend="device"))
        exp_d, exp_s = _host_expected(eng, Query(terms=(vocab[0], vocab[2]),
                                                 mode=mode, k=5000))
        assert len(r.scores) == len(exp_s)
        # the full tail is compared here (not just top-10), so f32-vs-f64
        # accumulation differences on tiny scores need a looser tolerance
        assert np.allclose(np.sort(r.scores), np.sort(exp_s),
                           rtol=1e-3, atol=1e-6)


def test_device_works_before_any_collation(small_docs):
    """Empty frozen image + all-delta: the device path needs no collate at
    all (the delta covers the whole index)."""
    vocab, docs = small_docs
    eng = Engine(B=64, growth="const")
    for d in docs[:60]:
        eng.add_document(d)
    res = eng.execute(Query(terms=(vocab[1], vocab[3]), mode="conjunctive",
                            backend="device"))
    exp = Q.brute_conjunctive(eng.index, [vocab[1], vocab[3]])
    assert res.docids.tolist() == exp.tolist()
    assert eng.stats().collations == 0


def test_refresh_cycles_and_new_terms(small_docs):
    """Interleave ingest and device queries over several refresh cycles,
    including a term that did not exist at freeze time."""
    vocab, docs = small_docs
    eng = Engine(B=64, growth="const")
    for d in docs[:100]:
        eng.add_document(d)
    eng.collate_now()
    rng = np.random.default_rng(5)
    for cycle in range(3):
        for d in docs[100 + 40 * cycle:100 + 40 * (cycle + 1)]:
            eng.add_document(list(d) + ["postfreeze"])
        terms = ("postfreeze", vocab[int(rng.integers(0, 40))])
        got = eng.execute(Query(terms=terms, mode="conjunctive",
                                backend="device"))
        exp = Q.brute_conjunctive(eng.index, list(terms))
        assert got.docids.tolist() == exp.tolist()
        r = eng.execute(Query(terms=terms, mode="ranked_tfidf", k=8,
                              backend="device"))
        _, s = Q.ranked_disjunctive_taat(eng.index, list(terms), k=8)
        assert np.allclose(np.sort(r.scores), np.sort(s), rtol=1e-5)
    assert eng.stats().collations == 1
    assert eng.stats().delta_refreshes >= 3


def test_auto_collate_bounds_delta(small_docs):
    vocab, docs = small_docs
    eng = Engine(B=64, growth="const", auto_collate_delta_frac=0.25)
    for d in docs[:80]:
        eng.add_document(d)
    eng.collate_now()
    base = eng.stats().collations
    for i, d in enumerate(docs[80:170]):
        eng.add_document(d)
        if i % 40 == 39:
            eng.execute(Query(terms=(vocab[0],), mode="conjunctive",
                              backend="device"))
    assert eng.stats().collations > base, "delta grew unbounded"


# --------------------------------------------------------------------------
# planner
# --------------------------------------------------------------------------


def test_planner_batches_route_to_device(engine_const):
    vocab, eng = engine_const
    batch = [Query(terms=(vocab[i], vocab[i + 2]), mode="ranked_tfidf")
             for i in range(5)]
    res = eng.execute_many(batch)
    assert all(r.backend == "device" for r in res)
    single = eng.execute(Query(terms=(vocab[40],), mode="ranked_tfidf"))
    assert single.backend == "host"  # small batch never device


def test_planner_volume_threshold(engine_const):
    vocab, eng = engine_const
    cfg = PlannerConfig(pallas_min_postings=1, allow_pallas=True)
    from repro.engine import Planner
    eng2 = Engine(B=64, growth="const", planner=cfg)
    assert isinstance(eng2.planner, Planner)
    eng2.add_document([vocab[0], vocab[1]])
    r = eng2.execute(Query(terms=(vocab[0],), mode="ranked_tfidf"))
    assert r.backend == "pallas"


def _pallas_winning_crossover():
    from repro.engine.planner import CrossoverTable
    return CrossoverTable(min_batch={
        m: {"device": None, "pallas": 1}
        for m in ("conjunctive", "ranked_tfidf", "bm25")})


@pytest.mark.parametrize("mode", ["conjunctive", "ranked_tfidf", "bm25"])
@pytest.mark.parametrize("crossover", [False, True])
def test_default_planner_never_routes_to_pallas(mode, crossover):
    """The Pallas flavour does not compile for a TPU, so the default config
    must route no batch and no single query there: not on candidate
    volume, not on a crossover table in which pallas wins everything."""
    from repro.engine import Planner
    cfg = PlannerConfig(crossover=_pallas_winning_crossover()
                        if crossover else None)
    p = Planner(cfg)
    q = Query(terms=("a", "b"), mode=mode)
    for ft in (1, 2048, 10 ** 7):
        stats = [TermStats(ft=ft, nblocks=ft // 32 + 1)] * 2
        for batch in (1, 2, 4, 32, 256):
            for device_capable in (True, False):
                d = p.plan(q, batch, stats, device_capable=device_capable)
                assert d.backend != "pallas", (ft, batch, d)
    # opting in brings both pallas rules back
    p_in = Planner(PlannerConfig(crossover=cfg.crossover, allow_pallas=True,
                                 pallas_min_postings=1))
    stats = [TermStats(ft=4096, nblocks=129)] * 2
    assert p_in.plan(q, 1, stats, device_capable=True).backend == "pallas"


@pytest.mark.parametrize("how", ["query", "engine"])
def test_forced_pallas_still_routes_to_pallas(small_docs, how):
    vocab, docs = small_docs
    eng = Engine(B=64, growth="const",
                 force_backend="pallas" if how == "engine" else None)
    for d in docs[:40]:
        eng.add_document(d)
    q = Query(terms=(vocab[0], vocab[1]), mode="ranked_tfidf",
              backend="pallas" if how == "query" else None)
    assert eng.execute(q).backend == "pallas"
    assert all(r.backend == "pallas" for r in eng.execute_many([q] * 6))


def test_force_backend_knob(small_docs):
    vocab, docs = small_docs
    eng = Engine(B=64, growth="const", force_backend="host")
    for d in docs[:30]:
        eng.add_document(d)
    batch = [Query(terms=(vocab[0],), mode="ranked_tfidf")] * 6
    assert all(r.backend == "host" for r in eng.execute_many(batch))


def test_phrase_requires_word_level_host():
    eng = Engine(B=64, growth="const", word_level=True)
    eng.add_document(["to", "be", "or", "not", "to", "be"])
    eng.add_document(["be", "or", "to"])
    res = eng.execute(Query(terms=("to", "be"), mode="phrase"))
    assert res.backend == "host"
    assert res.docids.tolist() == [1]
    with pytest.raises(ValueError):
        eng.execute(Query(terms=("to", "be"), mode="phrase",
                          backend="pallas"))
    doc_eng = Engine(B=64, growth="const")
    doc_eng.add_document(["a", "b"])
    with pytest.raises(UnsupportedQueryError):
        doc_eng.execute(Query(terms=("a", "b"), mode="phrase"))


# --------------------------------------------------------------------------
# shard fan-out + serving
# --------------------------------------------------------------------------


def test_sharded_engine_conjunctive_exact(small_docs):
    vocab, docs = small_docs
    se = ShardedEngine(num_shards=3, B=64, growth="const")
    for d in docs[:90]:
        se.add_document(d)
    se.collate_now()
    for d in docs[90:130]:
        se.add_document(d)
    rng = np.random.default_rng(11)
    for _ in range(10):
        terms = [vocab[i] for i in rng.choice(40, size=2, replace=False)]
        got = se.execute(Query(terms=tuple(terms), mode="conjunctive"))
        exp = [g for g, d in enumerate(docs[:130], start=1)
               if all(t in d for t in terms)]
        assert got.docids.tolist() == exp
    ranked = se.execute(Query(terms=(vocab[0], vocab[2]),
                              mode="ranked_tfidf", k=7))
    assert len(ranked.docids) <= 7
    assert (np.diff(ranked.scores) <= 1e-9).all()  # descending


def test_query_service_immediate_access(small_docs):
    vocab, docs = small_docs
    eng = Engine(B=64, growth="const")
    svc = QueryService(eng, max_batch=4)
    for d in docs[:20]:
        svc.ingest(d)
    t1 = svc.submit(Query(terms=(vocab[0],), mode="conjunctive"))
    svc.ingest(docs[20])
    tickets = svc.flush()
    assert t1.done and t1 in tickets
    exp = Q.brute_conjunctive(eng.index, [vocab[0]])
    assert t1.result.docids.tolist() == exp.tolist()
    summary = svc.latency_summary()
    assert summary["query"]["n"] == 1 and summary["ingest"]["n"] == 21


def test_engine_adopts_existing_index(small_docs):
    vocab, docs = small_docs
    from repro.core.index import DynamicIndex
    idx = DynamicIndex(B=64, growth="const")
    for d in docs[:50]:
        idx.add_document(d)
    eng = Engine(index=idx)
    r = eng.execute(Query(terms=(vocab[1],), mode="bm25", k=5,
                          backend="host"))
    exp_d, exp_s = Q.ranked_bm25(idx, [vocab[1]], eng.doclens_array(), k=5)
    assert np.allclose(r.scores, exp_s)
