"""Differential parity matrix for the fused decode→score→top-k launch.

The fused path has two flavours over ONE resident image pair: the Pallas
kernel (``flavor="pallas"``) and the inline reference (``flavor="ref"``,
the ``device`` backend).  Both run the same ``fused_tile`` math, so the
kernel must be **byte-identical** to the reference — same docids, same f32
score bits, same tie order — while both must agree with the host oracle.
The matrix covers the three fused workloads, doc- and word-level layouts,
a mid-stream freeze swap, and a delta-only query after ingest; plus the
resident-image amortization counters, the delta-compaction policy, and the
measured planner crossover table the benchmark sweep feeds.

Everything here runs on CPU (Pallas interpret mode) — the CI smoke job
selects the file via the ``pallas`` marker.
"""

import json

import numpy as np
import pytest

from repro.core import query as Q
from repro.engine import Engine, PlannerConfig, Query
from repro.engine.device_backend import fused_execute
from repro.engine.planner import CrossoverTable, Planner, TermStats
from repro.serve import QueryService

pytestmark = pytest.mark.pallas

MODES = ("conjunctive", "ranked_tfidf", "bm25")


@pytest.fixture(scope="module")
def zdocs():
    rng = np.random.default_rng(71)
    vocab = [f"w{i}" for i in range(90)]
    probs = 1.0 / np.arange(1, 91) ** 1.1
    probs /= probs.sum()
    docs = [[vocab[i] for i in rng.choice(90, size=rng.integers(4, 30),
                                          p=probs)]
            for _ in range(220)]
    return vocab, docs


@pytest.fixture(scope="module")
def eng(zdocs):
    """150 docs collated into the resident frozen image, 70 in the delta:
    every fused launch below merges both images."""
    vocab, docs = zdocs
    e = Engine(B=64, growth="const")
    for d in docs[:150]:
        e.add_document(d)
    e.collate_now()
    for d in docs[150:]:
        e.add_document(d)
    return vocab, e


def _batch(vocab, mode, n=6, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        nt = int(rng.integers(1, 4))
        ts = tuple(vocab[i] for i in rng.choice(50, size=nt, replace=False))
        out.append(Query(terms=ts, mode=mode, k=10))
    return out


def _host_expected(e, query):
    if query.mode == "conjunctive":
        return Q.brute_conjunctive(e.index, query.terms), None
    if query.mode == "ranked_tfidf":
        return Q.ranked_disjunctive_taat(e.index, list(query.terms),
                                         k=query.k)
    return Q.ranked_bm25(e.index, list(query.terms), e.doclens_array(),
                         k=query.k)


# --------------------------------------------------------------------------
# pallas flavour ≡ ref flavour, byte for byte
# --------------------------------------------------------------------------


@pytest.mark.parametrize("mode", MODES)
def test_pallas_byte_identical_to_ref(eng, mode):
    """The kernel and the inline reference share ``fused_tile``; nothing in
    the pallas_call plumbing may perturb a single bit of the output."""
    vocab, e = eng
    batch = _batch(vocab, mode, seed=3)
    e.resident.refresh()
    ref = fused_execute(e, e.resident, batch, mode, 10,
                        flavor="ref", interpret=True, name="ref")
    pal = fused_execute(e, e.resident, batch, mode, 10,
                        flavor="pallas", interpret=True, name="pallas")
    for r, p in zip(ref, pal):
        assert r.docids.tolist() == p.docids.tolist()
        if mode != "conjunctive":
            assert r.scores.tobytes() == p.scores.tobytes()


# --------------------------------------------------------------------------
# fused backends vs the host oracle (frozen + delta merged in one launch)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["device", "pallas"])
@pytest.mark.parametrize("mode", MODES)
def test_fused_matches_host_matrix(eng, backend, mode):
    vocab, e = eng
    for q in _batch(vocab, mode, seed=11):
        res = e.execute(Query(terms=q.terms, mode=mode, k=10,
                              backend=backend))
        assert res.backend == backend
        exp_d, exp_s = _host_expected(e, q)
        if mode == "conjunctive":
            assert res.docids.tolist() == exp_d.tolist()
        else:
            assert len(res.scores) == len(exp_s)
            assert np.allclose(np.sort(res.scores), np.sort(exp_s),
                               rtol=1e-5)
            # canonical tie order: score desc, docid asc within equal scores
            s, d = res.scores, res.docids
            assert (np.diff(s) <= 1e-12).all()
            ties = np.isclose(s[1:], s[:-1], rtol=0, atol=0)
            assert (np.diff(d)[ties] > 0).all()


@pytest.mark.parametrize("mode", MODES)
def test_fused_batch_equals_singletons(eng, mode):
    """Batched execution (one launch, query-major grid) returns exactly the
    per-query results — padding rows never leak."""
    vocab, e = eng
    batch = _batch(vocab, mode, n=5, seed=23)
    forced = [Query(terms=q.terms, mode=mode, k=10, backend="pallas")
              for q in batch]
    got = e.execute_many(forced)
    for q, r in zip(forced, got):
        single = e.execute(Query(terms=q.terms, mode=mode, k=10,
                                 backend="pallas"))
        assert r.docids.tolist() == single.docids.tolist()
        if mode != "conjunctive":
            assert r.scores.tobytes() == single.scores.tobytes()


# --------------------------------------------------------------------------
# word-level layouts: fused path refuses, host ≡ tiered still holds
# --------------------------------------------------------------------------


def test_word_level_fused_refuses_and_host_tiered_agree(zdocs):
    from repro.core.lifecycle import FreezePolicy

    vocab, docs = zdocs
    e = Engine(B=64, growth="const", word_level=True,
               tier_policy=FreezePolicy())
    for d in docs[:120]:
        e.add_document(d)
    e.lifecycle.freeze(blocking=True)
    for d in docs[120:150]:
        e.add_document(d)
    q = Query(terms=(vocab[2], vocab[5]), mode="ranked_tfidf", k=10)
    for backend in ("device", "pallas"):
        with pytest.raises(ValueError):
            e.execute(Query(terms=q.terms, mode=q.mode, k=10,
                            backend=backend))
    host = e.execute(Query(terms=q.terms, mode=q.mode, k=10,
                           backend="host"))
    tiered = e.execute(Query(terms=q.terms, mode=q.mode, k=10,
                             backend="tiered"))
    assert host.docids.tolist() == tiered.docids.tolist()
    assert np.allclose(host.scores, tiered.scores, rtol=1e-6)


# --------------------------------------------------------------------------
# lifecycle: freeze swap mid-stream, delta-only suffix, amortization
# --------------------------------------------------------------------------


def test_mid_stream_freeze_swap_stays_correct(zdocs):
    """A second collation mid-stream swaps the resident frozen image; the
    very next fused batch must serve from the new epoch and still match
    the host."""
    vocab, docs = zdocs
    e = Engine(B=64, growth="const")
    for d in docs[:100]:
        e.add_document(d)
    e.collate_now()
    for d in docs[100:140]:
        e.add_document(d)
    batch = _batch(vocab, "bm25", n=4, seed=5)
    forced = [Query(terms=q.terms, mode=q.mode, k=10, backend="pallas")
              for q in batch]
    e.execute_many(forced)
    assert e.resident.frozen_uploads == 1
    e.collate_now()                      # freeze swap: epoch 1 -> 2
    for d in docs[140:160]:
        e.add_document(d)
    got = e.execute_many(forced)
    assert e.resident.frozen_uploads == 2
    assert e.resident.epoch == 2
    for q, r in zip(batch, got):
        exp_d, exp_s = _host_expected(e, q)
        assert len(r.scores) == len(exp_s)
        assert np.allclose(np.sort(r.scores), np.sort(exp_s), rtol=1e-5)


def test_delta_only_query_after_ingest(zdocs):
    """Terms that exist ONLY in the post-freeze suffix are answered from
    the delta image without triggering a collation (immediate access)."""
    vocab, docs = zdocs
    e = Engine(B=64, growth="const")
    for d in docs[:80]:
        e.add_document(d)
    e.collate_now()
    fresh = [e.add_document(["qx1", "qx2", vocab[0]]) for _ in range(3)]
    before = e.stats().collations
    res = e.execute(Query(terms=("qx1", "qx2"), mode="conjunctive",
                          backend="pallas"))
    assert res.docids.tolist() == fresh
    assert e.stats().collations == before, "delta query forced a collation"
    host = Q.brute_conjunctive(e.index, ("qx1", "qx2"))
    assert res.docids.tolist() == host.tolist()


def test_resident_upload_amortized_across_batches(zdocs):
    """One freeze = one upload; every later fused batch (both flavours)
    reuses the resident image and ships only the delta suffix."""
    vocab, docs = zdocs
    e = Engine(B=64, growth="const")
    for d in docs[:100]:
        e.add_document(d)
    e.collate_now()
    for d in docs[100:120]:
        e.add_document(d)
    batch = _batch(vocab, "ranked_tfidf", n=4, seed=9)
    for backend in ("device", "pallas", "device"):
        e.execute_many([Query(terms=q.terms, mode=q.mode, k=10,
                              backend=backend) for q in batch])
    assert e.resident.frozen_uploads == 1
    assert e.stats().resident_uploads == 1
    assert e.resident.batches_served >= 3
    # ingest between batches refreshes the delta, not the frozen upload
    e.add_document([vocab[0], vocab[1]])
    e.execute_many([Query(terms=q.terms, mode=q.mode, k=10,
                          backend="pallas") for q in batch])
    assert e.resident.frozen_uploads == 1
    assert e.resident.batches_served >= 4


# --------------------------------------------------------------------------
# delta-compaction policy (fragmentation threshold)
# --------------------------------------------------------------------------


def test_compaction_policy_triggers_on_fragmented_delta(zdocs):
    """Past the fragmentation threshold an incremental refresh falls back
    to a full collation — the delta path is never the slower option."""
    vocab, docs = zdocs
    e = Engine(B=64, growth="const", delta_compact_frac=0.05,
               delta_compact_min_blocks=4)
    for d in docs[:60]:
        e.add_document(d)
    e.collate_now()
    for d in docs[60:140]:
        e.add_document(d)
    before = e.stats().collations
    res = e.execute(Query(terms=(vocab[0],), mode="ranked_tfidf", k=10,
                          backend="device"))
    assert e.stats().delta_compactions >= 1
    assert e.stats().collations > before
    exp_d, exp_s = _host_expected(e, Query(terms=(vocab[0],),
                                           mode="ranked_tfidf", k=10))
    assert np.allclose(np.sort(res.scores), np.sort(exp_s), rtol=1e-5)


def test_compaction_policy_spares_small_deltas(eng):
    """The absolute block floor keeps small fixtures on the honest
    incremental path: the module fixture's 70-doc delta must NOT compact."""
    vocab, e = eng
    e.execute(Query(terms=(vocab[0],), mode="conjunctive",
                    backend="device"))
    assert e.stats().delta_compactions == 0
    assert e.stats().collations == 1


# --------------------------------------------------------------------------
# measured crossover table -> planner routing
# --------------------------------------------------------------------------


def _rows():
    rows = []
    for size in (300, 1200):
        for batch in (1, 8, 32):
            rows.append({"workload": "bm25", "backend": "host",
                         "size": size, "batch": batch, "us_per_query": 100.0})
            # device wins from batch 8 at EVERY size
            rows.append({"workload": "bm25", "backend": "device",
                         "size": size, "batch": batch,
                         "us_per_query": 150.0 if batch < 8 else 60.0})
            # pallas wins at 32 on ONE size only -> conservative None
            rows.append({"workload": "bm25", "backend": "pallas",
                         "size": size, "batch": batch,
                         "us_per_query": 80.0 if (batch == 32 and
                                                  size == 300) else 140.0})
    return rows


def test_crossover_table_derivation():
    t = CrossoverTable.from_rows(_rows())
    assert t.min_batch["bm25"]["device"] == 8
    assert t.min_batch["bm25"]["pallas"] is None   # must win at every size


def test_planner_routes_by_measured_crossover():
    t = CrossoverTable.from_rows(_rows())
    p = Planner(PlannerConfig(crossover=t, pallas_min_postings=10 ** 9,
                              allow_pallas=True))
    stats = [TermStats(ft=50, nblocks=2)]
    q = Query(terms=("a",), mode="bm25", k=10)
    assert p.plan(q, 8, stats, device_capable=True).backend == "device"
    assert p.plan(q, 1, stats, device_capable=True).backend == "host"
    # a mode the sweep never measured keeps the static default
    q2 = Query(terms=("a",), mode="ranked_tfidf", k=10)
    assert p.plan(q2, 8, stats, device_capable=True).backend == "device"


def test_crossover_from_bench_round_trip(tmp_path):
    path = tmp_path / "bench.json"
    path.write_text(json.dumps({"crossover": {"rows": _rows()}}))
    assert CrossoverTable.from_bench(str(path)).min_batch == \
        CrossoverTable.from_rows(_rows()).min_batch


# --------------------------------------------------------------------------
# serving: whole-batch hand-off with intra-flush dedupe
# --------------------------------------------------------------------------


def test_query_service_hands_whole_batch_deduped(eng):
    vocab, e = eng
    calls = []
    real = e.execute_many

    def counting(queries):
        calls.append(len(queries))
        return real(queries)

    e.execute_many = counting
    try:
        svc = QueryService(e, max_batch=64, cache_size=0)
        q1 = Query(terms=(vocab[0], vocab[1]), mode="bm25", k=10)
        q2 = Query(terms=(vocab[2],), mode="bm25", k=10)
        t = [svc.submit(q) for q in (q1, q2, q1, q1)]
        svc.flush()
        assert calls == [2], "duplicates must collapse into one engine batch"
        assert all(x.done for x in t)
        assert t[0].result.docids.tolist() == t[2].result.docids.tolist()
        assert t[2].result is not t[0].result  # private copies
    finally:
        e.execute_many = real
