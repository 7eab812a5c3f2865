"""Tiered static-tier lifecycle: background freeze, atomic swap, exact
merge with the dynamic suffix, planner routing, and the serving-layer
query-result cache (epoch/version keyed)."""

import threading

import numpy as np
import pytest

from repro.core import query as Q
from repro.core.lifecycle import FreezeManager, FreezePolicy
from repro.engine import Engine, Query as EQuery, UnsupportedQueryError
from repro.serve import QueryService


@pytest.fixture(scope="module")
def stream_docs():
    rng = np.random.default_rng(77)
    vocab = [f"t{i}" for i in range(150)]
    probs = 1.0 / np.arange(1, 151) ** 1.05
    probs /= probs.sum()
    docs = [[vocab[i] for i in rng.choice(150, size=rng.integers(5, 40),
                                          p=probs)]
            for _ in range(300)]
    return vocab, docs


def _assert_identical(eng, terms, mode, k=10):
    rt = eng.execute(EQuery(terms=terms, mode=mode, k=k, backend="tiered"))
    rh = eng.execute(EQuery(terms=terms, mode=mode, k=k, backend="host"))
    assert rt.backend == "tiered" and rh.backend == "host"
    assert rt.docids.tolist() == rh.docids.tolist(), (mode, terms)
    if mode != "conjunctive":
        # byte-identical scores: same arithmetic over the same values
        assert np.array_equal(rt.scores, rh.scores), (mode, terms)


# --------------------------------------------------------------------------
# the acceptance differential: ingest + background freeze + queries, exact
# --------------------------------------------------------------------------


@pytest.mark.parametrize("growth", ["const", "triangle", "expon"])
@pytest.mark.parametrize("codec", ["bp128", "interp"])
def test_tiered_identical_to_host_during_background_freeze(
        stream_docs, growth, codec):
    """Every tiered result must be byte-identical to the host backend while
    documents keep arriving and a background freeze completes mid-stream."""
    vocab, docs = stream_docs
    eng = Engine(B=64, growth=growth,
                 tier_policy=FreezePolicy(codec=codec, background=True))
    for d in docs[:120]:
        eng.add_document(d)
    rng = np.random.default_rng(3)

    def check(n=4):
        for _ in range(n):
            nt = int(rng.integers(1, 4))
            terms = tuple(vocab[i] for i in
                          rng.choice(70, size=nt, replace=False))
            for mode in ("conjunctive", "ranked_tfidf", "bm25"):
                _assert_identical(eng, terms, mode)

    check()                                   # before any tier exists
    assert eng.lifecycle.freeze(blocking=False)
    # the freeze runs on its own thread; ingest + queries continue against
    # the previous (empty) tier with no availability gap
    saw_in_flight = eng.lifecycle.in_flight
    for d in docs[120:180]:
        eng.add_document(d)
        check(1)
    eng.lifecycle.wait()
    assert saw_in_flight or eng.lifecycle.epoch == 1
    assert eng.lifecycle.tier is not None
    assert eng.lifecycle.tier.epoch == 1
    assert eng.lifecycle.tier.num_docs == 120
    check()                                   # after the swap
    # a second freeze epoch over the grown index
    eng.lifecycle.freeze(blocking=True)
    assert eng.lifecycle.tier.num_docs == eng.index.num_docs
    for d in docs[180:220]:
        eng.add_document(d)
    check()
    assert eng.stats().freezes == 2 and eng.stats().tier_epoch == 2


def test_policy_triggers_freeze_automatically(stream_docs):
    vocab, docs = stream_docs
    eng = Engine(B=64, growth="const",
                 tier_policy=FreezePolicy(every_docs=50, background=False))
    for d in docs[:170]:
        eng.add_document(d)
    # 170 docs with a 50-doc trigger: epochs at 50, 100, 150
    assert eng.lifecycle.freezes == 3
    assert eng.lifecycle.tier.num_docs == 150
    _assert_identical(eng, (vocab[0], vocab[5]), "conjunctive")
    _assert_identical(eng, (vocab[2], vocab[9]), "bm25")


def test_background_policy_single_freeze_in_flight(stream_docs):
    """A freeze request while one is running is a no-op, not a pile-up."""
    vocab, docs = stream_docs
    eng = Engine(B=64, growth="const")
    mgr = eng.enable_tiering(FreezePolicy(every_docs=10, background=True))
    for d in docs[:150]:
        eng.add_document(d)
    mgr.wait()
    # at least one freeze happened; never more than one thread at a time
    assert 1 <= mgr.freezes <= 15
    assert threading.active_count() < 10
    _assert_identical(eng, (vocab[1], vocab[4]), "conjunctive")


def test_freeze_empty_engine():
    """Freezing before any document exists must publish an empty tier, not
    crash (the empty-list guard in StaticIndex.add_list)."""
    eng = Engine(B=64, growth="const", tier_policy=FreezePolicy())
    eng.lifecycle.freeze(blocking=True)
    tier = eng.static_tier()
    assert tier is not None and tier.num_docs == 0 and tier.epoch == 1
    eng.add_document(["a", "b"])
    r = eng.execute(EQuery(terms=("a",), mode="conjunctive",
                           backend="tiered"))
    assert r.docids.tolist() == [1]


# --------------------------------------------------------------------------
# word-level tiers: the ⟨d,w⟩ lifecycle, differential vs host (ISSUE 3)
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def word_stream_docs():
    rng = np.random.default_rng(55)
    vocab = [f"w{i}" for i in range(80)]
    probs = 1.0 / np.arange(1, 81) ** 1.05
    probs /= probs.sum()
    docs = [[vocab[i] for i in rng.choice(80, size=rng.integers(4, 30),
                                          p=probs)]
            for _ in range(260)]
    return vocab, docs


from conftest import naive_phrase as _phrase_oracle  # noqa: E402
from conftest import naive_proximity as _prox_oracle  # noqa: E402
from conftest import naive_ranked as _ranked_oracle  # noqa: E402


@pytest.mark.parametrize("growth", ["const", "triangle", "expon"])
@pytest.mark.parametrize("codec", ["bp128", "interp"])
def test_word_level_tiered_identical_to_host_during_freeze(
        word_stream_docs, growth, codec):
    """The acceptance differential at word level: every tiered result —
    conjunctive, ranked (tfidf/bm25/bm25_prox), phrase AND proximity —
    byte-identical to the host backend while ingest continues and a
    background freeze completes mid-stream; phrase/proximity results
    additionally pinned to a naive scan of the raw docs, ranked results to
    the brute-force doc-level oracle (the ISSUE-4 w-gaps-as-frequencies bug
    cannot regress silently)."""
    vocab, docs = word_stream_docs
    eng = Engine(B=64, growth=growth, word_level=True,
                 tier_policy=FreezePolicy(codec=codec, background=True))
    for d in docs[:120]:
        eng.add_document(d)
    rng = np.random.default_rng(9)

    def check(n=3, ingested=120):
        for _ in range(n):
            nt = int(rng.integers(1, 4))
            terms = tuple(vocab[i] for i in
                          rng.choice(40, size=nt, replace=False))
            for mode in ("conjunctive", "ranked_tfidf", "bm25",
                         "bm25_prox"):
                _assert_identical(eng, terms, mode)
            # ranked modes vs the brute-force doc-level oracle (exact)
            for mode, oracle in (("ranked_tfidf", "tfidf"),
                                 ("bm25", "bm25"),
                                 ("bm25_prox", "bm25_prox")):
                r = eng.execute(EQuery(terms=terms, mode=mode, k=10,
                                       backend="tiered"))
                ed, es = _ranked_oracle(docs[:ingested], list(terms), k=10,
                                        mode=oracle)
                assert r.docids.tolist() == ed.tolist(), (mode, terms)
                assert np.allclose(r.scores, es, rtol=1e-12), (mode, terms)
            pt = terms[:2]
            rt = eng.execute(EQuery(terms=pt, mode="phrase",
                                    backend="tiered"))
            rh = eng.execute(EQuery(terms=pt, mode="phrase", backend="host"))
            exp = _phrase_oracle(docs[:ingested], pt)
            assert rt.docids.tolist() == exp, (pt,)
            assert rh.docids.tolist() == exp, (pt,)
            w = int(rng.integers(1, 9))
            qt = eng.execute(EQuery(terms=pt, mode="proximity", window=w,
                                    backend="tiered"))
            qh = eng.execute(EQuery(terms=pt, mode="proximity", window=w,
                                    backend="host"))
            pexp = _prox_oracle(docs[:ingested], pt, w)
            assert qt.docids.tolist() == pexp, (pt, w)
            assert qh.docids.tolist() == pexp, (pt, w)

    check()                                   # before any tier exists
    assert eng.lifecycle.freeze(blocking=False)
    for i, d in enumerate(docs[120:180]):
        eng.add_document(d)
        check(1, ingested=121 + i)
    eng.lifecycle.wait()
    assert eng.lifecycle.tier is not None
    assert eng.lifecycle.tier.num_docs == 120
    assert eng.lifecycle.tier.index.word_level
    check(ingested=180)                       # after the swap
    eng.lifecycle.freeze(blocking=True)       # second epoch, grown index
    assert eng.lifecycle.tier.num_docs == eng.index.num_docs
    for d in docs[180:220]:
        eng.add_document(d)
    check(ingested=220)
    assert eng.stats().freezes == 2 and eng.stats().tier_epoch == 2
    # word-level accounting flows through the stats plumbing
    assert eng.stats().num_words == eng.index.num_words > 0
    assert eng.index.num_words == eng.index.num_postings  # §5.1: 1/occurrence


def test_word_level_policy_and_planner_routing(word_stream_docs):
    """Policy-triggered word-level freezes; once a tier is published the
    planner routes phrase queries to it by default."""
    vocab, docs = word_stream_docs
    eng = Engine(B=64, growth="const", word_level=True,
                 tier_policy=FreezePolicy(every_docs=60, background=False))
    before = eng.execute(EQuery(terms=(vocab[0], vocab[1]), mode="phrase"))
    assert before.backend == "host"           # no tier yet
    for d in docs[:130]:
        eng.add_document(d)
    assert eng.lifecycle.freezes == 2         # epochs at 60, 120
    assert eng.lifecycle.tier.num_docs == 120
    after = eng.execute(EQuery(terms=(vocab[0], vocab[1]), mode="phrase"))
    assert after.backend == "tiered"
    assert after.docids.tolist() == _phrase_oracle(
        docs[:130], (vocab[0], vocab[1]))
    # proximity and bm25_prox follow the same positional routing rule
    prox = eng.execute(EQuery(terms=(vocab[0], vocab[1]), mode="proximity",
                              window=4))
    assert prox.backend == "tiered"
    assert prox.docids.tolist() == _prox_oracle(
        docs[:130], (vocab[0], vocab[1]), 4)
    assert eng.execute(EQuery(terms=(vocab[0], vocab[1]),
                              mode="bm25_prox")).backend == "tiered"
    _assert_identical(eng, (vocab[1], vocab[3]), "conjunctive")
    _assert_identical(eng, (vocab[2], vocab[5]), "bm25")


def test_word_level_static_tier_compression(word_stream_docs):
    """The frozen ⟨d,w⟩ tier must beat the dynamic form on bytes/posting —
    the §5 'small amount more for word-level indexing' claim."""
    vocab, docs = word_stream_docs
    eng = Engine(B=64, growth="const", word_level=True,
                 tier_policy=FreezePolicy())
    for d in docs[:200]:
        eng.add_document(d)
    eng.lifecycle.freeze(blocking=True)
    tier = eng.lifecycle.tier
    assert tier.num_postings == eng.index.num_postings
    assert tier.index.bytes_per_posting() < eng.index.bytes_per_posting()
    assert eng.index.stats()["num_words"] == eng.index.num_postings


def test_forced_phrase_on_doc_level_tiered_raises():
    eng = Engine(B=64, growth="const")       # doc-level
    eng.add_document(["x", "y"])
    with pytest.raises((ValueError, UnsupportedQueryError)):
        eng.execute(EQuery(terms=("x", "y"), mode="phrase",
                           backend="tiered"))
    with pytest.raises((ValueError, UnsupportedQueryError)):
        eng.execute(EQuery(terms=("x", "y"), mode="proximity", window=3,
                           backend="tiered"))
    with pytest.raises((ValueError, UnsupportedQueryError)):
        eng.execute(EQuery(terms=("x", "y"), mode="bm25_prox",
                           backend="tiered"))


def test_forced_device_or_pallas_on_positional_modes_raises():
    """Positional modes never run on the device/Pallas backends — a forced
    override must raise, not silently fall back (same contract as phrase)."""
    eng = Engine(B=64, growth="const", word_level=True)
    eng.add_document(["x", "y", "x"])
    for mode, kw in (("proximity", {"window": 2}), ("bm25_prox", {})):
        for backend in ("device", "pallas"):
            with pytest.raises((ValueError, UnsupportedQueryError)):
                eng.execute(EQuery(terms=("x", "y"), mode=mode,
                                   backend=backend, **kw))


def test_query_window_validation():
    with pytest.raises(ValueError):
        EQuery(terms=("a", "b"), mode="proximity")            # no window
    with pytest.raises(ValueError):
        EQuery(terms=("a", "b"), mode="proximity", window=0)  # degenerate
    with pytest.raises(ValueError):
        EQuery(terms=("a",), mode="conjunctive", window=3)    # misplaced


def test_planner_prefers_tiered_once_published(stream_docs):
    vocab, docs = stream_docs
    eng = Engine(B=64, growth="const", tier_policy=FreezePolicy())
    for d in docs[:80]:
        eng.add_document(d)
    before = eng.execute(EQuery(terms=(vocab[120],), mode="conjunctive"))
    assert before.backend == "host"          # no tier yet
    eng.lifecycle.freeze(blocking=True)
    after = eng.execute(EQuery(terms=(vocab[120],), mode="conjunctive"))
    assert after.backend == "tiered"
    # batches still go to the device image
    batch = [EQuery(terms=(vocab[i], vocab[i + 1]), mode="ranked_tfidf")
             for i in range(6)]
    assert all(r.backend == "device" for r in eng.execute_many(batch))


def test_suffix_cursor_skips_frozen_prefix(stream_docs):
    """The tiered view reads the dynamic chains only past the horizon."""
    vocab, docs = stream_docs
    eng = Engine(B=64, growth="const", tier_policy=FreezePolicy())
    for d in docs[:100]:
        eng.add_document(d)
    eng.lifecycle.freeze(blocking=True)
    for d in docs[100:140]:
        eng.add_document(d)
    view = eng.backends["tiered"].view()
    assert view.horizon == 100
    for t in vocab[:30]:
        ds, fs = view.suffix_postings(t)
        full_d, full_f = eng.index.postings(t)
        cut = np.searchsorted(full_d, 101, side="left")
        assert ds.tolist() == full_d[cut:].tolist()
        assert fs.tolist() == full_f[cut:].tolist()


# --------------------------------------------------------------------------
# serving-layer query-result cache (epoch/version keyed)
# --------------------------------------------------------------------------


def test_query_cache_hits_and_invalidation(stream_docs):
    vocab, docs = stream_docs
    eng = Engine(B=64, growth="const", tier_policy=FreezePolicy())
    svc = QueryService(eng, max_batch=4, cache_size=32)
    for d in docs[:60]:
        svc.ingest(d)
    q = EQuery(terms=(vocab[0], vocab[3]), mode="conjunctive")
    r1 = svc.query(q)
    assert svc.cache_hits == 0 and svc.cache_misses == 1
    r2 = svc.query(q)
    assert svc.cache_hits == 1 and r2.docids.tolist() == r1.docids.tolist()
    # ingest bumps engine.version -> old entries unreachable
    svc.ingest(docs[60])
    r3 = svc.query(q)
    assert svc.cache_misses == 2
    assert r3.docids.tolist() == Q.brute_conjunctive(
        eng.index, list(q.terms)).tolist()
    # a tier swap bumps the epoch -> invalidates even with no ingest
    svc.query(q)
    assert svc.cache_hits == 2
    eng.lifecycle.freeze(blocking=True)
    svc.query(q)
    assert svc.cache_misses == 3
    summary = svc.latency_summary()
    assert summary["cache"]["hits"] == 2 and summary["cache"]["misses"] == 3


def test_query_cache_immune_to_caller_mutation(stream_docs):
    """A caller mutating its result in place must not corrupt later hits."""
    vocab, docs = stream_docs
    eng = Engine(B=64, growth="const")
    svc = QueryService(eng, cache_size=8)
    for d in docs[:40]:
        svc.ingest(d)
    q = EQuery(terms=(vocab[0],), mode="conjunctive")
    r1 = svc.query(q)
    expected = r1.docids.tolist()
    r1.docids[:] = -1          # hostile in-place edit
    r2 = svc.query(q)
    assert svc.cache_hits == 1
    assert r2.docids.tolist() == expected
    r2.docids[:] = -2          # mutating a hit copy is also harmless
    assert svc.query(q).docids.tolist() == expected


def test_query_cache_disabled_and_bounded(stream_docs):
    vocab, docs = stream_docs
    eng = Engine(B=64, growth="const")
    svc = QueryService(eng, cache_size=0)
    for d in docs[:20]:
        svc.ingest(d)
    q = EQuery(terms=(vocab[0],), mode="conjunctive")
    svc.query(q)
    svc.query(q)
    assert svc.cache_hits == 0 and svc.cache_misses == 0
    bounded = QueryService(eng, cache_size=2)
    for i in range(5):
        bounded.query(EQuery(terms=(vocab[i],), mode="conjunctive"))
    assert len(bounded._cache) <= 2


def test_query_cache_key_covers_window(word_stream_docs):
    """The same terms at different proximity windows are different cache
    entries — ``window`` is part of the Query value, hence of the key."""
    vocab, docs = word_stream_docs
    eng = Engine(B=64, growth="const", word_level=True)
    svc = QueryService(eng, cache_size=16)
    for d in docs[:40]:
        svc.ingest(d)
    r1 = svc.proximity((vocab[0], vocab[1]), window=1)
    r2 = svc.proximity((vocab[0], vocab[1]), window=20)
    assert svc.cache_misses == 2 and svc.cache_hits == 0
    assert set(r1.docids.tolist()) <= set(r2.docids.tolist())
    assert svc.proximity((vocab[0], vocab[1]),
                         window=1).docids.tolist() == r1.docids.tolist()
    assert svc.cache_hits == 1


def test_flush_cache_key_computed_once_per_ticket(stream_docs):
    """ISSUE-4 satellite: a background freeze bumping ``lifecycle.epoch``
    while ``execute_many`` runs must not file the result under the NEW
    epoch (it was computed against the old tier).  The fix computes the key
    once at lookup and reuses it at store time — so after the bump, the
    next query at the new epoch is a miss, never a stale hit."""
    vocab, docs = stream_docs
    eng = Engine(B=64, growth="const", tier_policy=FreezePolicy())
    svc = QueryService(eng, cache_size=16)
    for d in docs[:60]:
        svc.ingest(d)

    real_execute_many = eng.execute_many

    def racing_execute_many(queries):
        res = real_execute_many(queries)
        eng.lifecycle.freeze(blocking=True)   # epoch bumps mid-flush
        return res

    eng.execute_many = racing_execute_many
    q = EQuery(terms=(vocab[0], vocab[2]), mode="conjunctive")
    r1 = svc.query(q)                          # miss; epoch bumps during it
    eng.execute_many = real_execute_many
    assert svc.cache_misses == 1
    r2 = svc.query(q)                          # new epoch -> must MISS
    assert svc.cache_misses == 2, \
        "result was cached under an epoch it was not computed for"
    assert r2.docids.tolist() == r1.docids.tolist()
    # and the old-epoch entry is simply unreachable, not wrong
    assert svc.query(q).docids.tolist() == r1.docids.tolist()
    assert svc.cache_hits == 1


def test_freeze_manager_standalone(stream_docs):
    """FreezeManager works without the Engine constructor knob."""
    vocab, docs = stream_docs
    eng = Engine(B=64, growth="const")
    mgr = FreezeManager(eng, FreezePolicy(codec="interp"))
    eng.lifecycle = mgr
    for d in docs[:90]:
        eng.add_document(d)
    mgr.freeze(blocking=True)
    tier = mgr.tier
    assert tier.index.codec == "interp"
    assert tier.num_postings == eng.index.num_postings
    assert tier.index.bytes_per_posting() < eng.index.bytes_per_posting()
    _assert_identical(eng, (vocab[0], vocab[2]), "ranked_tfidf")


# --------------------------------------------------------------------------
# pinning tests for the repro.analysis first-run findings (PR 7): freeze
# metadata is published atomically, and suffix_size snapshots the tier once
# --------------------------------------------------------------------------


def test_freeze_metadata_published_atomically(stream_docs):
    """epoch/freezes/last_freeze_s are derived views of the ONE published
    ``tier`` reference.  Under the old three-field publication
    (tier, then epoch, then freezes), a concurrent reader could observe
    ``tier.epoch`` ahead of ``epoch`` ahead of ``freezes``; reading the
    derived views in (tier, epoch, freezes) order must now always satisfy
    freezes >= epoch >= tier.epoch (values only move forward in time)."""
    vocab, docs = stream_docs
    eng = Engine(B=64, growth="const",
                 tier_policy=FreezePolicy(every_docs=12, background=True))
    mgr = eng.lifecycle
    stop = threading.Event()
    bad = []

    def reader():
        while not stop.is_set():
            tier = mgr.tier                   # earliest snapshot...
            epoch = mgr.epoch
            freezes = mgr.freezes             # ...latest snapshot
            t_ep = tier.epoch if tier is not None else 0
            if not freezes >= epoch >= t_ep:
                bad.append((t_ep, epoch, freezes))
            if tier is not None and tier.encode_s is None:
                bad.append(("tier published without encode_s", tier.epoch))

    threads = [threading.Thread(target=reader) for _ in range(2)]
    for t in threads:
        t.start()
    try:
        for d in docs[:200]:
            eng.add_document(d)
    finally:
        stop.set()
        for t in threads:
            t.join()
    mgr.wait()
    assert not bad, f"inconsistent freeze metadata observed: {bad[:5]}"
    # the derived-view invariant, settled: one freeze == one epoch
    assert mgr.freezes == mgr.epoch == mgr.tier.epoch > 0
    assert mgr.last_freeze_s == mgr.tier.encode_s is not None


def test_suffix_size_snapshots_tier_once():
    """A background swap completing MID-read of suffix_size must not mix
    two horizons.  The fake index publishes a new tier from inside its
    ``num_postings`` property — exactly between the old code's second and
    third loads of ``self.tier`` — which used to yield (50 docs, 0
    postings): a torn read spanning both horizons."""
    from repro.core.lifecycle import StaticTier

    class SwappingIndex:
        mgr = None

        @property
        def num_docs(self):
            return 100

        @property
        def num_postings(self):
            # a freeze thread swaps the tier mid-read
            self.mgr.tier = StaticTier(index=None, num_docs=100,
                                       num_postings=1000, epoch=2)
            return 1000

    class FakeEngine:
        def __init__(self, idx):
            self.index = idx

    idx = SwappingIndex()
    mgr = FreezeManager(FakeEngine(idx), FreezePolicy())
    idx.mgr = mgr
    mgr.tier = StaticTier(index=None, num_docs=50, num_postings=500, epoch=1)
    assert mgr.suffix_size() == (50, 500)   # ONE horizon, the snapshot's
    assert mgr.suffix_size() == (0, 0)      # next call sees the new tier
