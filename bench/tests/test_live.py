"""A window with a write stream: the driver runs ingests on schedule and
records each answer's horizons, a rehearsal of an ingest traffic reads
correct, and each planted fault of immediate access reads not correct."""

import json
from types import SimpleNamespace

import numpy as np
import pytest

from bench import run as bench_run
from bench.lib import corpus, driver, schedule
from bench.lib.cell import CHECKS, verdict
from bench.lib.reference import Readings


def live_traffic(monkeypatch, docs_per_s=10, batch_docs=5):
    """Every cell's traffic with a write stream overlaid in memory."""
    real = bench_run.load_cell

    def load_cell(name, rehearse):
        spec, cell, config, traffic = real(name, rehearse)
        return spec, cell, config, schedule.with_ingest(
            traffic, docs_per_s, batch_docs)
    monkeypatch.setattr(bench_run, "load_cell", load_cell)


def planted(monkeypatch, fault):
    """``fault(svc, monkeypatch)`` breaks the timed path just before the
    window is driven."""
    real = driver.drive

    def drive(svc, *a, **kw):
        fault(svc, monkeypatch)
        return real(svc, *a, **kw)
    monkeypatch.setattr(driver, "drive", drive)


def rehearse(capsys, seed=2**31 + 11, seconds=3):
    assert bench_run.main(["--workload", "wsj1.query", "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", "0",
                           "--rehearse"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_ingest_traffic_rehearses_correct(capsys, monkeypatch):
    live_traffic(monkeypatch)
    out = rehearse(capsys)
    assert out["correct"] is True, out["checks"]
    assert list(out["checks"]) == list(CHECKS)
    assert out["checks"]["ingest_unacked"] == {"value": 0, "limit": 0}
    assert list(out)[-2:] == ["ingest", "checks"]
    live = out["ingest"]
    assert live["docs"] == 30 and live["utf8_bytes"] > 30 * 100
    assert live["mb_per_min"] > 0
    assert 0 <= live["late_ms_p50"] <= live["late_ms_max"]
    assert live["late_ms_p50"] <= live["ack_ms_p50"] <= live["ack_ms_max"]


def visible_one_batch_late(svc, mp):
    """``ingest_batch`` acknowledges the next docids but ingests each batch
    only when the next one comes."""
    real = svc.ingest_batch
    held = []

    def ingest_batch(docs):
        if held:
            real(held.pop())
        n = svc.engine.index.num_docs
        held.append(docs)
        return list(range(n + 1, n + 1 + len(docs)))
    mp.setattr(svc, "ingest_batch", ingest_batch)


def image_not_refreshed(svc, mp):
    """The device image stays as it was when the window began."""
    mp.setattr(svc.engine.resident, "refresh", lambda: False)


def wrong_docids(svc, mp):
    """Every document is ingested but acknowledged with the wrong docid."""
    real = svc.ingest_batch
    mp.setattr(svc, "ingest_batch",
               lambda docs: [d + 1 for d in real(docs)])


@pytest.mark.parametrize("fault", [visible_one_batch_late,
                                   image_not_refreshed, wrong_docids])
def test_planted_live_fault_is_not_correct(capsys, monkeypatch, fault):
    live_traffic(monkeypatch)
    planted(monkeypatch, fault)
    out = rehearse(capsys)
    assert out["correct"] is False, out["checks"]
    if fault is wrong_docids:
        assert out["checks"]["ingest_unacked"]["value"] == 30


class Service:
    """A stand-in ``QueryService``: each answer is the number of
    documents it saw when its flush ran."""

    max_batch = 4

    def __init__(self, docs):
        self.docs, self.pending, self.seen = docs, [], []

    def ingest_batch(self, docs):
        self.docs += len(docs)
        return list(range(self.docs - len(docs) + 1, self.docs + 1))

    def submit(self, query):
        t = SimpleNamespace(result=None)
        self.pending.append(t)
        if len(self.pending) >= self.max_batch:
            self.flush()
        return t

    def flush(self):
        if not self.pending:
            return
        self.seen.append(self.docs)
        for t in self.pending:
            t.result = self.docs
        self.pending = []


def live_schedule(seed, seconds=1.0):
    t = {"modes": ["bm25"], "k": 10, "rate_qps": 60, "max_batch": 4,
         "deadline_ms": 50, "limits": {},
         "terms": {"min": 1, "max": 2, "skip": 0, "pick": "uniform"}}
    return schedule.build(schedule.with_ingest(t, 30, 3), np.arange(100),
                          seed, seconds)


def test_flush_horizons_are_the_documents_acknowledged_before_it():
    sched = live_schedule(2**31 + 3)
    assert len(sched.ingest_at_s) == 10 and sched.batch_docs == 3
    svc = Service(100)
    batches = [[["a"]] * 3 for _ in sched.ingest_at_s]
    w = driver.drive(svc, sched, [None] * len(sched.at_s), 1.0,
                     batches=batches, horizon=100)
    assert [f.horizon for f in w.flushes] == svc.seen
    assert [x.horizon for x in w.ingests] == list(range(103, 131, 3))
    assert [x.at for x in w.ingests] == sched.ingest_at_s.tolist()
    assert all(x.start >= x.at for x in w.ingests)
    assert w.results == [svc.seen[f] for f in w.flush_of]
    # a query is submitted no later than its flush and sees at least what
    # was acknowledged when it was submitted
    flush_h = np.array([f.horizon for f in w.flushes])[w.flush_of]
    assert (100 <= w.submit_horizon).all()
    assert (w.submit_horizon <= flush_h).all()
    assert (w.submit_horizon < flush_h).any()


def test_an_ingest_runs_before_a_query_due_at_the_same_time():
    sched = schedule.Schedule(
        at_s=np.array([0.05, 0.1]), terms=[(1,), (2,)], modes=["bm25"] * 2,
        k=10, groups=[[0, 1]], close_s=np.array([0.15]),
        ingest_at_s=np.array([0.05, 0.1]), batch_docs=2)
    w = driver.drive(Service(7), sched, [None, None], 0.2,
                     batches=[[["a"]] * 2] * 2, horizon=7)
    assert w.submit_horizon.tolist() == [9, 11]
    assert [f.horizon for f in w.flushes] == [11]


def test_an_ingest_after_the_last_flush_still_runs():
    sched = schedule.Schedule(
        at_s=np.array([0.01]), terms=[(1,)], modes=["bm25"], k=10,
        groups=[[0]], close_s=np.array([0.02]),
        ingest_at_s=np.array([0.1]), batch_docs=1)
    w = driver.drive(Service(0), sched, [None], 0.2, batches=[[["a"]]])
    assert [f.horizon for f in w.flushes] == [0]
    assert [x.horizon for x in w.ingests] == [1]


def test_the_stream_continues_the_collection():
    first = corpus.generate(300, 40.0, 1.07, 2000, 9)
    a = corpus.generate(300, 40.0, 1.07, 2000, 9)
    corpus.extend(a, 300, 2500, 40.0, 1.07, 2000, 9)
    b = corpus.generate(300, 40.0, 1.07, 2000, 9)
    corpus.extend(b, 300, 10, 40.0, 1.07, 2000, 9)
    corpus.extend(b, 300, 2500, 40.0, 1.07, 2000, 9)
    assert a.n == b.n == 300 + 3 * corpus.STREAM_BLOCK
    assert np.array_equal(a.ptr, b.ptr) and np.array_equal(a.ids, b.ids)
    assert np.array_equal(a.ptr[:301], first.ptr)
    assert np.array_equal(a.ids[:first.ptr[-1]], first.ids)
    # the stream is not the collection's own generator run on
    longer = corpus.generate(400, 40.0, 1.07, 2000, 9)
    assert not np.array_equal(a.ptr[301:401], longer.ptr[301:])


def test_the_schedule_of_an_overlay_keeps_the_queries():
    t = {"modes": ["conjunctive", "bm25"], "k": 10, "rate_qps": 50,
         "max_batch": 8, "deadline_ms": 40, "limits": {"unanswered": 0},
         "terms": {"min": 1, "max": 3, "skip": 2, "pick": "uniform"}}
    plain = schedule.build(t, np.arange(300), 12, 4.0)
    live = schedule.build(schedule.with_ingest(t, 25, 4), np.arange(300),
                          12, 4.0)
    assert plain.ingest_at_s is None and plain.batch_docs == 0
    assert np.array_equal(plain.at_s, live.at_s)
    assert plain.terms == live.terms and plain.groups == live.groups
    assert len(live.ingest_at_s) == 25
    assert (np.diff(live.ingest_at_s) >= 0).all()
    assert 0 <= live.ingest_at_s[0] and live.ingest_at_s[-1] <= 4.0


def test_ingest_unacked_is_compared_where_the_limits_name_it():
    limits = {"unanswered": 0, "conj_wrong": 0, "rank_gap": 1e-4,
              "score_gap": 1e-4}
    ok, checks = verdict(Readings(ingest_unacked=3), limits)
    assert ok and list(checks) == list(CHECKS[:4])
    ok, checks = verdict(Readings(ingest_unacked=3),
                         dict(limits, ingest_unacked=0))
    assert not ok and checks["ingest_unacked"] == {"value": 3, "limit": 0}
