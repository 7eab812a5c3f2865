"""The span facility (``repro.obs``) and the spans of the query path."""

import os
import sys
import threading

import jax
import numpy as np
import pytest

from repro import obs
from repro.core.sharded_index import ShardedEngine
from repro.engine import Engine, Query
from repro.serve import QueryService

PATH = ("repro.service.flush", "repro.engine.execute", "repro.engine.plan",
        "repro.device.refresh", "repro.device.prep", "repro.device.launch",
        "repro.device.wait", "repro.device.readback", "repro.device.unpack")


@pytest.fixture
def clock(monkeypatch):
    """A hand-moved clock for the span table."""
    now = [0.0]
    monkeypatch.setattr(obs, "_clock", lambda: now[0])
    return now


@pytest.fixture
def notes(monkeypatch):
    """Every annotation a span opens, as (name, ids)."""
    seen = []

    class Note:
        def __init__(self, name, **ids):
            seen.append((name, ids))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(obs, "TraceAnnotation", Note)
    return seen


@pytest.fixture(scope="module")
def docs():
    rng = np.random.default_rng(7)
    vocab = [f"t{i}" for i in range(60)]
    return vocab, [[vocab[i] for i in rng.integers(0, 60, rng.integers(5, 30))]
                   for _ in range(160)]


def device_engine(docs):
    vocab, ds = docs
    eng = Engine(B=64, growth="const", force_backend="device")
    eng.add_documents(ds[:120])
    eng.collate_now()
    eng.add_documents(ds[120:])
    return eng


def mixed_batch(vocab):
    return [Query(terms=(vocab[i], vocab[i + 1]), mode=m, k=5)
            for i in range(3) for m in ("conjunctive", "bm25")]


def test_nested_spans_give_self_time(clock):
    table = obs.SpanTable()
    with table.span("outer"):
        clock[0] = 1.0
        with table.span("inner"):
            clock[0] = 3.0
            with table.span("leaf"):
                clock[0] = 3.5
        clock[0] = 4.0
        with table.span("inner"):
            clock[0] = 6.0
        clock[0] = 10.0
    snap = table.snapshot()
    assert snap["outer"] == [1, 10.0, 10.0 - 2.5 - 2.0]
    assert snap["inner"] == [2, 4.5, 4.0]
    assert snap["leaf"] == [1, 0.5, 0.5]


def test_span_seconds_and_totals(clock):
    table = obs.SpanTable()
    with table.span("a") as sp:
        clock[0] = 2.5
    assert sp.seconds == 2.5
    assert table.total("a") == 2.5 and table.total("missing") == 0.0


def test_spans_from_threads_land_in_one_table():
    """More threads than cores, switching often: no count is lost and no
    thread's nesting leaks into another's self time."""
    table = obs.SpanTable()
    n, per = (os.cpu_count() or 4) + 2, 200

    def work():
        for _ in range(per):
            with table.span("outer"):
                with table.span("inner"):
                    pass

    threads = [threading.Thread(target=work) for _ in range(n)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    snap = table.snapshot()
    assert snap["outer"][0] == snap["inner"][0] == n * per
    # each thread keeps its own stack: outer's self time never goes negative
    assert 0.0 <= snap["outer"][2] <= snap["outer"][1]


def test_flush_id_is_carried_by_its_children(docs, notes):
    vocab, _ = docs
    eng = device_engine(docs)
    svc = QueryService(eng, max_batch=64, cache_size=0)
    del notes[:]                             # set-up: ingest and freeze
    for _ in range(2):
        for q in mixed_batch(vocab):
            svc.submit(q)
        svc.flush()
    flushes = [ids for name, ids in notes if name == "repro.service.flush"]
    assert [f["flush"] for f in flushes] == [1, 2]
    assert flushes[0]["tickets"] == 6 and flushes[0]["queries"] == 6
    children = [(name, ids) for name, ids in notes
                if name.startswith("repro.") and name != "repro.service.flush"]
    assert {name for name, _ in children} >= set(PATH[1:])
    assert all("flush" in ids for _, ids in children)
    # the first flush's children carry 1, the second's 2, in order
    seq = [ids["flush"] for _, ids in children]
    assert seq == sorted(seq) and set(seq) == {1, 2}
    launches = [ids for name, ids in notes if name == "repro.device.launch"]
    assert [ln["mode"] for ln in launches[:2]] == ["conjunctive", "bm25"]
    # each shape was warmed by the first flush: the second compiles nothing
    assert [ln["new_shape"] for ln in launches] == [1, 1, 0, 0]
    assert launches[0]["bucket"] == launches[2]["bucket"] == 0
    # no span outside a flush carries a flush id
    with eng.spans.span("repro.test.after"):
        pass
    assert notes[-1] == ("repro.test.after", {})


def test_sharded_fanout_carries_the_flush_id(docs, notes):
    vocab, ds = docs
    se = ShardedEngine(num_shards=2, B=64, growth="const")
    try:
        se.add_documents(ds)
        svc = QueryService(se, max_batch=64, cache_size=0)
        for q in mixed_batch(vocab):
            svc.submit(q)
        svc.flush()
        execs = [ids for name, ids in notes
                 if name == "repro.engine.execute"]
        assert len(execs) == 2 and all(e["flush"] == 1 for e in execs)
    finally:
        se.close()


def test_engine_stats_export_spans(docs):
    vocab, ds = docs
    eng = device_engine(docs)
    eng.execute_many(mixed_batch(vocab))
    s = eng.stats()
    assert s.spans["repro.engine.ingest"][0] == 2
    assert s.spans["repro.engine.collate"][0] == 1
    assert s.spans["repro.device.upload"][0] == 1
    assert s.spans["repro.device.launch"][0] == 2    # one per (mode, k)
    for n, total, own in s.spans.values():
        assert n >= 1 and 0.0 <= own <= total + 1e-12
    # the old timers are the span totals now: one clock, not two
    assert s.query_time_s == s.spans["repro.engine.execute"][1] > 0.0
    assert s.ingest_time_s == s.spans["repro.engine.ingest"][1] > 0.0

    se = ShardedEngine(num_shards=3, B=64, growth="const")
    try:
        se.add_documents(ds)
        svc = QueryService(se, max_batch=8, cache_size=0)
        svc.query(Query(terms=(vocab[0],), mode="bm25", k=3))
        per = [e.stats().spans for e in se.engines]
        agg = se.stats().spans
        assert agg["repro.engine.ingest"][0] == sum(
            p["repro.engine.ingest"][0] for p in per) == 3
        assert agg["repro.engine.execute"][0] == 3
        assert agg["repro.engine.execute"][1] == pytest.approx(
            sum(p["repro.engine.execute"][1] for p in per))
        assert agg["repro.service.flush"][0] == 1   # the fleet's own table
    finally:
        se.close()


def test_lifecycle_encode_is_a_span(docs):
    from repro.core.lifecycle import FreezePolicy
    _, ds = docs
    eng = Engine(B=64, growth="const",
                 tier_policy=FreezePolicy(background=False))
    eng.add_documents(ds)
    eng.lifecycle.freeze(blocking=True)
    n, total, _ = eng.stats().spans["repro.tier.encode"]
    assert n == 1 and total == eng.lifecycle.last_freeze_s > 0.0


def test_profiler_trace_holds_the_spans_nested(docs, tmp_path):
    from jax.profiler import ProfileData
    vocab, _ = docs
    eng = device_engine(docs)
    svc = QueryService(eng, max_batch=64, cache_size=0)
    batch = mixed_batch(vocab)[1:2]          # one bm25 launch
    for q in batch:
        svc.submit(q)
    svc.flush()                              # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for q in batch:
        svc.submit(q)
    svc.flush()
    jax.profiler.stop_trace()
    path, = tmp_path.glob("**/*.xplane.pb")
    data = ProfileData.from_file(str(path))
    evs = sorted(((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                   dict(ev.stats))
                  for plane in data.planes if plane.name.startswith("/host:")
                  for line in plane.lines for ev in line.events
                  if ev.name.startswith("repro.")), key=lambda e: e[0])
    names = [e[2] for e in evs]
    assert names == list(PATH)
    flush = evs[0]
    assert all(flush[0] <= s <= e <= flush[1] for s, e, _, _ in evs)
    execute = evs[1]
    assert all(execute[0] <= s <= e <= execute[1] for s, e, _, _ in evs[2:])
    # the device stages follow one another
    stages = evs[4:]
    assert all(a[1] <= b[0] for a, b in zip(stages, stages[1:]))
    assert {e[3]["flush"] for e in evs} == {2}


def test_lowered_fused_query_names_its_stages(docs):
    from repro.kernels.fused_query.ops import fused_query
    vocab, _ = docs
    eng = device_engine(docs)
    eng.resident.refresh()
    qt = jax.numpy.asarray(np.array([[1, 2, 0, 0]], np.int32))
    qm = jax.numpy.asarray(np.array([[True, True, False, False]]))
    for mode in ("bm25", "conjunctive"):
        text = fused_query.lower(
            eng.resident.images, qt, qm, mode=mode, k=5, max_blocks=(8, 8),
            doclens=eng.resident._doclens,
            n_stat=eng.resident._n_stat).as_text(debug_info=True)
        for scope in ("gather", "decode", "accumulate", "topk"):
            assert f"/{scope}/" in text, (mode, scope)
