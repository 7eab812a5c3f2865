"""Schedules are pure in their seed; flush groups close at size or deadline."""

import hashlib
import json
import os

import numpy as np
import pytest

from bench.lib import schedule

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")


def load(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as fh:
        return json.load(fh)


def test_same_seed_same_schedule_other_seed_other_order():
    ranked = np.arange(5000)
    for name in ("query", "bm25_k1000"):
        t = load(name)
        a = schedule.build(t, ranked, 2**31 + 17, 5.0)
        b = schedule.build(t, ranked, 2**31 + 17, 5.0)
        c = schedule.build(t, ranked, 4, 5.0)
        assert np.array_equal(a.at_s, b.at_s) and a.terms == b.terms
        assert a.groups == b.groups and a.modes == b.modes
        assert len(a.at_s) == len(c.at_s) == round(t["rate_qps"] * 5.0)
        assert a.terms != c.terms


def test_term_rules():
    ranked = np.arange(10000)[::-1]          # rank 0 is term 9999
    t = load("bm25_k1000")
    s = schedule.build(t, ranked, 9, 20.0)
    lens = [len(q) for q in s.terms]
    assert min(lens) >= 2 and max(lens) <= 12
    assert abs(np.mean(lens) - 5.9) < 0.3
    top = set(ranked[:33].tolist())
    assert not any(set(q) & top for q in s.terms)
    assert all(len(set(q)) == len(q) for q in s.terms)
    w = schedule.build(load("query"), ranked, 9, 20.0)
    band = set(ranked[10:1500].tolist())
    assert all(set(q) <= band and 1 <= len(q) <= 4 for q in w.terms)
    assert w.modes[:4] == ["conjunctive", "ranked_tfidf", "bm25",
                           "conjunctive"]


def test_groups_close_at_size_or_deadline():
    at = np.array([0.0, 0.01, 0.02, 0.5, 0.51, 0.52, 0.53])
    groups, close = schedule.flush_groups(at, 3, 0.1)
    assert groups == [[0, 1, 2], [3, 4, 5], [6]]
    assert np.allclose(close, [0.02, 0.52, 0.63])


def test_term_names_are_base36_of_rank_plus_31():
    from bench.lib import corpus
    names = corpus.term_names(50_000)
    assert names.dtype == object
    for i in (0, 4, 5, 1_265, 46_624, 49_999):
        assert names[i] == np.base_repr(i + 31, 36).lower()
    assert len(set(names.tolist())) == 50_000


def digest(*parts) -> str:
    h = hashlib.sha256()
    for p in parts:
        h.update(p.tobytes() if isinstance(p, np.ndarray) else
                 repr(p).encode())
    return h.hexdigest()[:16]


# Taken from the benchmark as it was before traffic could name a write
# stream: a traffic without ``ingest`` still yields these schedules, and
# every configuration still holds these documents before any stream.
SCHEDULES = {
    ("query", 0): "142fb6dca90a6e83",
    ("query", 7): "4d2f263760a605d4",
    ("query", 2**31 + 17): "12b09ccdbc1f663f",
    ("query", 2**40 + 3): "9c767ae25db13a16",
    ("bm25_k1000", 0): "5044961926c756a8",
    ("bm25_k1000", 7): "e9b631322c802143",
    ("bm25_k1000", 2**31 + 17): "a20cdcde74065fd1",
    ("bm25_k1000", 2**40 + 3): "e4cabd19b355c073",
}
COLLECTIONS = {
    ("wsj1", "query", True): "d813a64f5ee72139",
    ("wsj1", "query", False): "e8335887a734b471",
    ("msmarco", "bm25_k1000", True): "444dca330deb19c5",
}


@pytest.mark.parametrize("name,seed", sorted(SCHEDULES))
def test_schedule_without_ingest_is_unchanged(name, seed):
    s = schedule.build(load(name), np.arange(5000), seed, 5.0)
    got = digest(np.asarray(s.at_s, np.float64),
                 (s.terms, s.modes, s.k, s.groups),
                 np.asarray(s.close_s, np.float64))
    assert got == SCHEDULES[name, seed]
    assert s.ingest_at_s is None and s.batch_docs == 0


@pytest.mark.parametrize("config,traffic,rehearse", sorted(COLLECTIONS))
def test_collection_before_the_stream_is_unchanged(config, traffic,
                                                   rehearse):
    from bench.lib.cell import collection
    with open(os.path.join(os.path.dirname(TRAFFIC), "configs",
                           config + ".json")) as fh:
        c = json.load(fh)
    t = load(traffic)
    if rehearse:
        c.update(c["rehearse"])
        t.update(t.get("rehearse", {}))
    n = int(c["n_docs"]) + int(t.get("delta_docs", 0))
    coll = collection(c, n)
    assert coll.n == n
    assert digest(np.asarray(coll.ptr, np.int64),
                  np.asarray(coll.ids, np.int32)) == COLLECTIONS[
                      config, traffic, rehearse]
